"""The plain reference that decides ``correct``.

A Delaunay graph is planar, and its reference drawing, the points it was
triangulated from, has no crossing at all and nearly even edges. The
configuration states the quality a layout has to reach, each number at
most its limit (``limits`` in the configuration, by the names of
``NUMBERS``). This module measures them plainly, with nothing of the
program under test:

* ``crossings_per_edge`` (CRE, the paper's Table 1 metric): for a sample
  of edges drawn from the seed, each one's proper crossings with every
  edge of the graph; CRE is the mean of those counts (each crossing
  involves two edges, so the mean over all edges is 2 * crossings / m);
* ``neld`` (the paper's Table 1 metric too): the standard deviation of
  the edge lengths over their mean, on every edge.

A layout with a non-finite coordinate or the wrong number of rows reads
infinite on both.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

SAMPLE_EDGES = 4096
_BLOCK_S = 256
_BLOCK_M = 32768


def _pad_to(x: np.ndarray, size: int, fill) -> np.ndarray:
    out = np.full((size,) + x.shape[1:], fill, x.dtype)
    out[: len(x)] = x
    return out


def _bucket(k: int, floor: int) -> int:
    return max(floor, 1 << int(np.ceil(np.log2(max(k, 1)))))


@jax.jit
def _crossings(pos, edges, emask, sample):
    """Proper crossings of each ``sample`` edge with every valid edge."""
    a, b = pos[sample[:, 0]], pos[sample[:, 1]]                  # [S, 2]
    nb = edges.shape[0] // _BLOCK_M
    eb = edges.reshape(nb, _BLOCK_M, 2)
    mb = emask.reshape(nb, _BLOCK_M)

    def orient(p, q, r):
        return ((q[..., 0] - p[..., 0]) * (r[..., 1] - p[..., 1])
                - (q[..., 1] - p[..., 1]) * (r[..., 0] - p[..., 0]))

    def one_sample_block(sb):
        s_e, pa, pb = sb                                          # [Bs, ...]

        def body(acc, blk):
            e, m = blk
            c, d = pos[e[:, 0]][None], pos[e[:, 1]][None]         # [1, Bm, 2]
            p, q = pa[:, None], pb[:, None]                       # [Bs, 1, 2]
            d1, d2 = orient(p, q, c), orient(p, q, d)
            d3, d4 = orient(c, d, p), orient(c, d, q)
            share = ((s_e[:, 0, None] == e[None, :, 0])
                     | (s_e[:, 0, None] == e[None, :, 1])
                     | (s_e[:, 1, None] == e[None, :, 0])
                     | (s_e[:, 1, None] == e[None, :, 1]))
            hit = (d1 * d2 < 0) & (d3 * d4 < 0) & ~share & m[None]
            return acc + hit.sum(1, dtype=jnp.int32), None

        acc, _ = jax.lax.scan(body, jnp.zeros(s_e.shape[0], jnp.int32),
                              (eb, mb))
        return acc

    ns = sample.shape[0] // _BLOCK_S
    blocks = (sample.reshape(ns, _BLOCK_S, 2), a.reshape(ns, _BLOCK_S, 2),
              b.reshape(ns, _BLOCK_S, 2))
    return jax.lax.map(one_sample_block, blocks).reshape(-1)


def crossings_per_edge(pos, edges: np.ndarray, n: int,
                       rng: np.random.Generator,
                       sample: int = SAMPLE_EDGES) -> float:
    """CRE of the drawing ``pos`` of the graph ``edges`` on ``n`` vertices,
    estimated from ``sample`` edges drawn with ``rng`` (all edges when the
    graph has fewer)."""
    pos = np.asarray(pos, np.float64)
    edges = np.asarray(edges, np.int64).reshape(-1, 2)
    if pos.shape != (n, 2) or not np.isfinite(pos).all():
        return float("inf")
    m = len(edges)
    if m == 0:
        return 0.0
    # centre and scale by the median edge length: the orientation tests
    # then work on numbers of order one to a few hundred in float32
    lengths = np.linalg.norm(pos[edges[:, 0]] - pos[edges[:, 1]], axis=1)
    scale = float(np.median(lengths)) or 1.0
    p = ((pos - pos.mean(0)) / scale).astype(np.float32)
    pick = (np.arange(m) if m <= sample
            else np.sort(rng.choice(m, size=sample, replace=False)))
    s_pad = _bucket(len(pick), _BLOCK_S)
    m_pad = _bucket(m, _BLOCK_M)
    valid_s = np.arange(s_pad) < len(pick)
    smp = _pad_to(edges[pick].astype(np.int32), s_pad, 0)
    e32 = _pad_to(edges.astype(np.int32), m_pad, 0)
    emask = np.arange(m_pad) < m
    counts = np.asarray(_crossings(jnp.asarray(p), jnp.asarray(e32),
                                   jnp.asarray(emask), jnp.asarray(smp)))
    return float(counts[valid_s].mean())


def neld(pos, edges: np.ndarray, n: int,
         rng: np.random.Generator | None = None) -> float:
    """Edge lengths' standard deviation over their mean, on every edge
    (``rng`` unused: every edge is measured)."""
    pos = np.asarray(pos, np.float64)
    edges = np.asarray(edges, np.int64).reshape(-1, 2)
    if pos.shape != (n, 2) or not np.isfinite(pos).all():
        return float("inf")
    if len(edges) == 0:
        return 0.0
    lengths = np.linalg.norm(pos[edges[:, 0]] - pos[edges[:, 1]], axis=1)
    mean = float(lengths.mean())
    return float(lengths.std() / mean) if mean > 0 else float("inf")


#: every number a configuration's ``limits`` may name:
#: ``f(pos, edges, n, rng) -> float``, higher is worse
NUMBERS = {"crossings_per_edge": crossings_per_edge, "neld": neld}
