"""GiLA — the single-level distributed force-directed refinement (paper §3.4).

Fruchterman–Reingold forces where the repulsive term of vertex v is
restricted to its k-hop neighborhood N_v(k) (the paper's locality
principle). Two TPU-native realizations of the repulsion:

  * ``exact``    — tiled all-pairs N-body (used when n is small, i.e. the
                   coarse levels; dispatches to the Pallas kernel on TPU,
                   to the jnp reference elsewhere);
  * ``neighbor`` — padded k-hop neighbor lists built once per level by
                   controlled-flooding expansion (GiLA floods *positions*
                   every iteration because a Giraph vertex cannot store the
                   set; the set itself is topology-only, so we materialize
                   it once and gather positions per iteration — identical
                   forces, strictly less communication);
  * ``grid``     — grid-bucketed approximate repulsion (flat Barnes–Hut,
                   kernels/grid_force): exact forces within the 3×3 cell
                   neighborhood, per-cell aggregates beyond. Positions are
                   rebinned every iteration inside the layout loop, so the
                   spatial structure tracks the moving layout; used on fine
                   levels where even capped neighbor lists are too coarse
                   or too slow.

The per-level schedule of k follows the paper exactly:
k = 6 (m<1e3), 5 (m<5e3), 4 (m<1e4), 3 (m<1e5), 2 (m<1e6), 1 (m≥1e6).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.graphs.graph import PaddedGraph, edge_gather, to_csr, unique_edges
from repro.kernels import backend as kernel_backend


def paper_k_schedule(m: int) -> int:
    """k(m) exactly as tuned in paper §3.4."""
    if m < 1_000:
        return 6
    if m < 5_000:
        return 5
    if m < 10_000:
        return 4
    if m < 100_000:
        return 3
    if m < 1_000_000:
        return 2
    return 1


@dataclasses.dataclass(frozen=True)
class GilaParams:
    """Force-model parameters for one level."""
    ideal_len: float = 1.0       # base ideal edge length L
    rep_const: float = 1.0       # repulsion strength C (f_r = C·m_u·m_v·L²/d)
    iters: int = 100
    temp0: float = 1.0           # initial max displacement
    temp_decay: float = 0.97     # multiplicative cooling per iteration
    min_dist: float = 1e-3


# -- k-hop neighbor lists (controlled flooding, topology-only) ----------------

def khop_neighbors(edges: np.ndarray, n: int, k: int, cap: int,
                   seed: int = 0) -> tuple[np.ndarray, np.ndarray]:
    """Padded k-hop neighbor lists via iterated expansion with random
    subsampling above ``cap`` (GiLA's flooding with bounded message load).

    Fully vectorized over (vertex, neighbor) pair arrays — the previous
    per-vertex Python loop was O(n) host work per level and dominated
    mid-level setup time. Per round: the frontier pairs (v, u) expand to
    u's CSR neighborhood with ``np.repeat`` range arithmetic, candidates
    are deduplicated and checked against the accumulated sets via sorted
    ``v·(n+1)+u`` keys, and each vertex admits a uniform random sample of
    its remaining room (rank-by-random-priority within the vertex group —
    equivalent to the old per-vertex ``rng.choice`` without replacement).
    Deterministic in ``seed``; the random stream differs from the old
    loop's, and each vertex's list is returned in ascending id order.

    Returns (idx[n, cap] int32 with sentinel n, mask[n, cap] bool).
    """
    rng = np.random.default_rng(seed)
    row_ptr, col = to_csr(edges, n)
    deg = np.diff(row_ptr).astype(np.int64)
    col = col.astype(np.int64)
    base = n + 1                      # (v, u) pair → unique int64 key

    def per_vertex_sample(v, u, room_of):
        """Keep a uniform random sample of ≤ room_of[v] pairs per vertex
        (rank candidates by random priority within each vertex group)."""
        pri = rng.random(v.size)
        order = np.lexsort((pri, v))
        sv, su = v[order], u[order]
        rank = np.arange(sv.size) - np.searchsorted(sv, sv, side="left")
        keep = rank < room_of[sv]
        return sv[keep], su[keep]

    # hop 1: the CSR pairs themselves, subsampled to cap where deg > cap
    src_v = np.repeat(np.arange(n, dtype=np.int64), deg)
    cv, cu = per_vertex_sample(src_v, col, np.full(n, cap, np.int64))
    counts = np.bincount(cv, minlength=n)
    cur_keys = np.sort(cv * base + cu)
    fv, fu = cv, cu                   # frontier: last round's additions

    for _ in range(k - 1):
        room_of = cap - counts
        act = room_of[fv] > 0 if fv.size else np.zeros(0, bool)
        fv, fu = fv[act], fu[act]
        if fv.size == 0:
            break
        # expand each frontier pair (v, u) to u's whole neighborhood
        d_u = deg[fu]
        tot = int(d_u.sum())
        if tot == 0:
            break
        ev = np.repeat(fv, d_u)
        idx_ = (np.repeat(row_ptr[fu], d_u)
                + (np.arange(tot) - np.repeat(np.cumsum(d_u) - d_u, d_u)))
        ew = col[idx_]
        ok = ev != ew
        keys = np.unique(ev[ok] * base + ew[ok])          # dedup candidates
        # drop pairs already collected (cur_keys is sorted + unique)
        pos = np.searchsorted(cur_keys, keys)
        pos = np.minimum(pos, max(cur_keys.size - 1, 0))
        fresh = (keys != cur_keys[pos]) if cur_keys.size else \
            np.ones(keys.size, bool)
        keys = keys[fresh]
        if keys.size == 0:
            break
        av, au = per_vertex_sample(keys // base, keys % base, room_of)
        counts = counts + np.bincount(av, minlength=n)
        cur_keys = np.sort(np.concatenate([cur_keys, av * base + au]))
        fv, fu = av, au

    allv, allu = cur_keys // base, cur_keys % base        # sorted by (v, u)
    rank = np.arange(allv.size) - np.searchsorted(allv, allv, side="left")
    idx = np.full((n, cap), n, dtype=np.int32)
    mask = np.zeros((n, cap), dtype=bool)
    idx[allv, rank] = allu
    mask[allv, rank] = True
    return idx, mask


def pad_neighbors(idx: np.ndarray, mask: np.ndarray, n_pad: int
                  ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pad [n,cap] lists up to [n_pad,cap] with sentinel n_pad."""
    n, cap = idx.shape
    out = np.full((n_pad, cap), n_pad, dtype=np.int32)
    out[:n] = np.where(mask, idx, n_pad)
    om = np.zeros((n_pad, cap), dtype=bool)
    om[:n] = mask
    return jnp.asarray(out), jnp.asarray(om)


# -- forces -------------------------------------------------------------------

def _repulsion_exact(pos, mass, vmask, C, L, min_dist):
    """All-pairs FR repulsion (jnp reference; Pallas kernel in kernels/nbody)."""
    from repro.kernels.nbody import ops as nbody_ops
    return nbody_ops.nbody_repulsion(pos, mass, vmask, C, L, min_dist)


def _repulsion_neighbors(pos, mass, nbr_idx, nbr_mask, vmask, C, L, min_dist):
    from repro.kernels.neighbor_force import ops as nf_ops
    return nf_ops.neighbor_repulsion(pos, mass, nbr_idx, nbr_mask, vmask,
                                     C, L, min_dist)


def _repulsion_grid(pos, mass, vmask, C, L, min_dist, grid_dim, cell_cap):
    """Grid-bucketed approximation (kernels/grid_force); rebins per call."""
    from repro.kernels.grid_force import ops as grid_ops
    return grid_ops.grid_repulsion(pos, mass, vmask, C, L, min_dist,
                                   grid_dim=grid_dim, cell_cap=cell_cap)


def _attraction(g: PaddedGraph, pos, L, min_dist):
    """FR attraction along edges with per-edge desired length ℓ_e = w_e·L:
    f_a(d) = d² / ℓ_e, directed toward the neighbor."""
    n_pad = g.n_pad
    pos_src = edge_gather(g, pos)
    pos_dst = pos[jnp.clip(g.dst, 0, n_pad - 1)]
    delta = pos_src - pos_dst                       # pull dst toward src
    dist = jnp.sqrt(jnp.sum(delta * delta, axis=1) + min_dist ** 2)
    ell = jnp.maximum(g.ewt, 1e-6) * L
    f = (dist * dist) / ell                         # FR: d²/ℓ
    vec = delta / dist[:, None] * f[:, None]
    vec = jnp.where(g.emask[:, None], vec, 0.0)
    out = jax.ops.segment_sum(vec, g.dst, num_segments=n_pad + 1)
    return out[:n_pad]


def gila_forces(g: PaddedGraph, pos, nbr_idx, nbr_mask, params_arr,
                mode: str = "neighbor", grid_dim: int = 0, cell_cap: int = 0):
    """Total force per vertex; ``params_arr = [C, L, min_dist]`` (traced).

    ``grid_dim``/``cell_cap`` are the static grid parameters for
    ``mode="grid"`` (pick them with ``kernels.grid_force.choose_grid``).

    Not jitted on its own: it is traced inline by the step that calls it,
    so the kernel backend it reads belongs to that step's cache key.

    The named scopes (``gila.repulsion``, ``gila.attraction``) only label
    the compiled operations, so a device profile names each stage."""
    C, L, min_dist = params_arr[0], params_arr[1], params_arr[2]
    with jax.named_scope("gila.repulsion"):
        if mode == "exact":
            rep = _repulsion_exact(pos, g.mass, g.vmask, C, L, min_dist)
        elif mode == "grid":
            rep = _repulsion_grid(pos, g.mass, g.vmask, C, L, min_dist,
                                  grid_dim, cell_cap)
        else:
            rep = _repulsion_neighbors(pos, g.mass, nbr_idx, nbr_mask,
                                       g.vmask, C, L, min_dist)
    with jax.named_scope("gila.attraction"):
        att = _attraction(g, pos, L, min_dist)
    return rep + att


def layout_iteration(g: PaddedGraph, pos, nbr_idx, nbr_mask, params_arr,
                     temp, *, mode: str, grid_dim: int = 0, cell_cap: int = 0):
    """One GiLA iteration: forces + cooling displacement clamp (shared by
    ``gila_layout`` and the bucketed cached step in core/bucketing.py)."""
    f = gila_forces(g, pos, nbr_idx, nbr_mask, params_arr, mode=mode,
                    grid_dim=grid_dim, cell_cap=cell_cap)
    with jax.named_scope("gila.move"):
        norm = jnp.sqrt(jnp.sum(f * f, axis=1) + 1e-12)
        step = jnp.minimum(norm, temp)
        pos = pos + f / norm[:, None] * step[:, None]
        return jnp.where(g.vmask[:, None], pos, 0.0)


def check_backend(backend: str) -> None:
    """Trace-time guard of a jitted function keyed on a static ``backend``:
    the kernels about to be traced read ``kernel_backend()``, so the key
    must name that same backend."""
    if backend != kernel_backend():
        raise ValueError(f"traced with backend={backend!r} while the "
                         f"kernels would use {kernel_backend()!r}")


@partial(jax.jit, static_argnames=("mode", "iters", "grid_dim", "cell_cap",
                                   "backend"))
def gila_layout(g: PaddedGraph, pos0, nbr_idx, nbr_mask, *, mode: str,
                iters: int, temp0: float, temp_decay: float,
                ideal_len: float, rep_const: float, backend: str,
                min_dist: float = 1e-3, grid_dim: int = 0, cell_cap: int = 0):
    """Run ``iters`` force iterations with a cooling displacement clamp.

    In ``mode="grid"`` the spatial binning happens inside ``gila_forces``,
    i.e. vertices are rebinned on every iteration of the loop.

    ``backend`` is ``repro.kernels.backend()`` as the caller sees it: the
    trace bakes that kernel backend in, so it keys the trace cache.

    This is the exact-shape path: ``iters`` (and ``g.n``/``g.m``) are
    static, so every distinct level retraces. The multilevel driver uses
    the bucketed, compile-cached equivalent in core/bucketing.py unless
    ``LayoutConfig.bucketing=False``."""
    check_backend(backend)
    params_arr = jnp.asarray([rep_const, ideal_len, min_dist], jnp.float32)

    def body(i, carry):
        pos, temp = carry
        pos = layout_iteration(g, pos, nbr_idx, nbr_mask, params_arr, temp,
                               mode=mode, grid_dim=grid_dim, cell_cap=cell_cap)
        return pos, temp * temp_decay

    pos, _ = jax.lax.fori_loop(0, iters, body,
                               (pos0, jnp.asarray(temp0, jnp.float32)))
    return pos


def random_init(g: PaddedGraph, scale: float, seed: int = 0) -> jnp.ndarray:
    """Uniform initial positions, derived per-vertex (utils/prng.py) so the
    draw for a real vertex does not depend on the padding bucket."""
    from repro.utils.prng import uniform2_per_vertex
    from repro.utils.transfer import io_boundary
    with io_boundary():                 # staging: seed + id table → device
        key = jax.random.PRNGKey(seed)
        ids = jnp.arange(g.n_pad, dtype=jnp.int32)
        pos = uniform2_per_vertex(key, ids, minval=-scale, maxval=scale)
        return jnp.where(g.vmask[:, None], pos, 0.0)


def build_level_neighbors(g: PaddedGraph, k: int, cap: int, seed: int = 0
                          ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Host-side k-hop list construction for a padded graph."""
    edges = unique_edges(g)
    idx, mask = khop_neighbors(edges, g.n, k, cap, seed)
    return pad_neighbors(idx, mask, g.n_pad)
