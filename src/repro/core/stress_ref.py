"""Plain reference of the maxent-stress refinement (``core/stress.py``).

Written from the equations (``core/stress.py``'s docstring, DESIGN.md §14;
Meyerhenke, Nöllenburg, Schulz, *Drawing Large Graphs by Multilevel
Maxent-Stress Optimization*, IEEE TVCG 24(5), 2018), in plain
``jax.numpy`` and float32, with no bucketing, compile cache, batching or
buffer donation, and without ``stress_iteration``. One Jacobi iteration
moves every vertex i to

    x_i ← ( Σ_e w_e · t_e  +  α · r_i ) / ρ_i ,    ρ_i = Σ_e w_e ,

over the half-edges e = (j → i): ℓ_e = max(ewt_e, 1e-6) · L, w_e = 1/ℓ_e²,
t_e the point at distance ℓ_e from x_j on the ray from x_j through x_i,
and r_i the entropy term. Where the repo departs from the paper, this
reference follows the repo:

* stress terms are taken on edges only (the paper also samples distant
  pairs), and a coarse edge's ℓ_e is the hierarchy's compounded weight
  (the paper takes graph distances);
* r_i is GiLA's repulsion with the strength α·C in the kernels' constant
  slot: Σ_u C · L² · mass_u · (x_i − x_u) / (|x_i − x_u|² + min_dist²)
  over all vertices (exact), over the k-hop list (neighbor), or through
  the grid approximation (grid): exact over the 3×3 cell neighborhood's
  buckets, cell aggregates beyond, the bucket overflow as softened
  aggregates, written here from the approximation's definition
  (``kernels/grid_force/README.md``) with none of its code;
* a vertex with ρ_i = 0 keeps its position, and the move is clamped by
  the cooling temperature as GiLA's is (the paper takes the full step);
* α anneals geometrically from ``stress.ALPHA0`` by a total factor of
  ``stress.ALPHA_SHRINK`` over a level's iterations (the repo's constants,
  from a scan of a mesh suite; the paper's own schedule differs).

The exact and neighbor repulsion run through the kernels' plain
references (``kernels/nbody/ref.py``, ``kernels/neighbor_force/ref.py``),
never through the backend dispatch; the grid mode's binning, cell sums,
near field, far field and overflow terms are this module's own plain
``jnp``. So the same numbers come out on the chip, on the CPU and in
interpret mode, and a change to ``kernels/grid_force/ops.py`` moves the
program alone.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import stress
from repro.kernels.nbody.ref import (nbody_repulsion_ref,
                                     nbody_repulsion_ref_chunked)
from repro.kernels.neighbor_force.ref import neighbor_repulsion_ref

#: above this many rows the all-pairs reference is taken in row blocks
#: (its [n, n, 2] table would not fit)
_DENSE_MAX = 8192
#: vertices per block of the grid mode's near and far fields: bounds the
#: reference's memory at 131,072 vertices (11,025 cells, 9 x 48 partners)
#: to about a hundred MB a block
_GRID_ROWS = 1024

#: the 3x3 cell neighborhood, the cell itself included
_OFFSETS = tuple((ox, oy) for oy in (-1, 0, 1) for ox in (-1, 0, 1))


def _blocked(fn, xs: tuple, rows: int):
    """``fn`` over the arrays ``xs``' shared leading axis, in blocks of
    ``rows`` (the last block padded with zeros, its rows dropped)."""
    n = xs[0].shape[0]
    pad = -n % rows
    blocks = tuple(
        jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape(
            (-1, rows) + x.shape[1:]) for x in xs)
    out = jax.lax.map(lambda b: fn(*b), blocks)
    return out.reshape((-1,) + out.shape[2:])[:n]


def _field(x, src, w, C, L, min_dist, soft=None):
    """Σ_k C·L²·w_k·(x − s_k) / (|x − s_k|² + min_dist² [+ soft_k²]) for
    each row: x [B, 2] against its sources src [B, K, 2] of weight w [B, K]
    (0 drops a source) → [B, 2]."""
    d = x[:, None, :] - src
    d2 = jnp.sum(d * d, axis=2) + min_dist * min_dist
    if soft is not None:
        d2 = d2 + soft * soft
    return jnp.sum(d * ((C * L * L) * w / d2)[:, :, None], axis=1)


def _cell_sums(pos, w, cid, nc: int):
    """Mass, weighted position sum, centroid and RMS radius of each cell's
    members (weights ``w``, 0 for a non-member): [nc+1], [nc+1, 2],
    [nc+1, 2], [nc+1]; an empty cell reads mass 0 at the origin."""
    M = jnp.zeros(nc + 1).at[cid].add(w)
    some = jnp.where(M > 0, M, 1.0)
    S = jnp.zeros((nc + 1, 2)).at[cid].add(w[:, None] * pos)
    mu = S / some[:, None]
    dev = pos - mu[cid]
    var = jnp.zeros(nc + 1).at[cid].add(w * jnp.sum(dev * dev, axis=1))
    return M, S, mu, jnp.sqrt(var / some)


def grid_repulsion(pos, mass, vmask, C, L, min_dist, *, grid_dim: int,
                   cell_cap: int):
    """The grid approximation of the repulsion, written from its
    definition (``kernels/grid_force/README.md``), none of its code taken
    from ``kernels/grid_force``.

    The valid vertices' bounding box is cut into G x G cells, and in each
    cell the first ``cell_cap`` vertices in array order are *bucketed*, the
    rest *overflow*. A vertex i in cell c feels:

    * exactly, every bucketed vertex of c's 3x3 neighborhood (i itself
      adds nothing), if i is bucketed;
    * every cell beyond that neighborhood as one point of its whole mass
      at its centroid;
    * each cell k of the neighborhood's overflow vertices, i left out, as
      one point at their centroid softened by their RMS radius r:
      the denominator |x_i − µ|² + min_dist² + r²;
    * if i overflows, each cell k of the neighborhood's bucketed vertices,
      as one softened point in the same way.
    """
    G, cap = grid_dim, cell_cap
    nc = G * G
    n = pos.shape[0]
    idx = jnp.arange(n)
    w = jnp.where(vmask, mass, 0.0)

    # cells over the valid vertices' bounding box
    big = jnp.float32(3e38)
    lo = jnp.min(jnp.where(vmask[:, None], pos, big), axis=0)
    hi = jnp.max(jnp.where(vmask[:, None], pos, -big), axis=0)
    size = jnp.maximum(hi - lo, jnp.float32(1e-6)) / jnp.float32(G)
    ij = jnp.clip(jnp.floor((pos - lo) / size), 0, G - 1).astype(jnp.int32)
    cx, cy = ij[:, 0], ij[:, 1]
    cid = jnp.where(vmask, cy * G + cx, nc)                 # nc: no cell

    # a vertex's rank in its cell: how many of the cell's vertices come
    # before it in array order
    order = jnp.lexsort((idx, cid))
    count = jnp.zeros(nc + 1, jnp.int32).at[cid].add(1)
    first = jnp.cumsum(count) - count
    rank = jnp.zeros(n, jnp.int32).at[order].set(idx) - first[cid]
    inb = vmask & (rank < cap)
    # bucketed vertices by cell and rank; empty slots read vertex n
    bucket = jnp.full((nc + 1, cap), n, jnp.int32).at[
        jnp.where(inb, cid, nc + 1), rank].set(idx, mode="drop")

    # each vertex's 3x3 neighborhood; a cell off the grid is the empty nc
    near9 = jnp.stack([
        jnp.where((0 <= cx + ox) & (cx + ox < G) & (0 <= cy + oy)
                  & (cy + oy < G), (cy + oy) * G + cx + ox, nc)
        for ox, oy in _OFFSETS], axis=1)                    # [n, 9]
    near9 = jnp.where(vmask[:, None], near9, nc)

    # exact near field of the bucketed vertices
    pos_p = jnp.concatenate([pos, jnp.zeros((1, 2))], axis=0)
    w_p = jnp.concatenate([w, jnp.zeros((1,))], axis=0)

    def near(x, cells, own):
        src = bucket[cells].reshape(x.shape[0], 9 * cap)
        f = _field(x, pos_p[src], w_p[src], C, L, min_dist)
        return jnp.where(own[:, None], f, 0.0)

    f_near = _blocked(near, (pos, near9, inb), _GRID_ROWS)

    # whole cells beyond the neighborhood, as points
    M, _, mu, _ = _cell_sums(pos, w, cid, nc)
    kx, ky = jnp.arange(nc) % G, jnp.arange(nc) // G

    def far(x, ix, iy):
        beyond = ((jnp.abs(kx[None] - ix[:, None]) > 1)
                  | (jnp.abs(ky[None] - iy[:, None]) > 1))
        src = jnp.broadcast_to(mu[:nc], (x.shape[0], nc, 2))
        return _field(x, src, jnp.where(beyond, M[None, :nc], 0.0), C, L,
                      min_dist)

    f_far = _blocked(far, (pos, cx, cy), _GRID_ROWS)

    # the neighborhood's overflow, i left out of its own cell, softened
    w_out = jnp.where(inb, 0.0, w)
    M_o, S_o, _, r_o = _cell_sums(pos, w_out, cid, nc)
    own = jnp.arange(9) == 4                                # offset (0, 0)
    m9 = jnp.maximum(M_o[near9] - jnp.where(own, w_out[:, None], 0.0), 0.0)
    s9 = S_o[near9] - jnp.where(own[:, None], w_out[:, None, None]
                                * pos[:, None], 0.0)
    mu9 = s9 / jnp.where(m9 > 0, m9, 1.0)[..., None]
    f_out = _field(pos, mu9, m9, C, L, min_dist, soft=r_o[near9])

    # an overflow vertex meets the neighborhood's bucketed vertices as
    # softened points
    M_b, _, mu_b, r_b = _cell_sums(pos, jnp.where(inb, w, 0.0), cid, nc)
    f_bkt = _field(pos, mu_b[near9], M_b[near9], C, L, min_dist,
                   soft=r_b[near9])
    f_bkt = jnp.where((vmask & ~inb)[:, None], f_bkt, 0.0)

    return jnp.where(vmask[:, None], f_near + f_far + f_out + f_bkt, 0.0)


def repulsion(pos, mass, vmask, nbr_idx, nbr_mask, C, L, min_dist, *,
              mode: str, grid_dim: int = 0, cell_cap: int = 0):
    """The entropy term's repulsion r in ``mode``, at strength ``C``."""
    if mode == "exact":
        dense = (nbody_repulsion_ref if pos.shape[0] <= _DENSE_MAX
                 else nbody_repulsion_ref_chunked)
        return dense(pos, mass, vmask, C, L, min_dist)
    if mode == "neighbor":
        return neighbor_repulsion_ref(pos, mass, nbr_idx, nbr_mask, vmask,
                                      C, L, min_dist)
    if mode == "grid":
        return grid_repulsion(pos, mass, vmask, C, L, min_dist,
                              grid_dim=grid_dim, cell_cap=cell_cap)
    raise ValueError(f"unknown repulsion mode {mode!r}")


def terms(g, L):
    """ℓ_e, w_e (0 on padding) and ρ_i = Σ_{e → i} w_e of a padded graph."""
    ell = jnp.maximum(g.ewt, 1e-6) * L
    w = jnp.where(g.emask, 1.0 / (ell * ell), 0.0)
    rho = jnp.zeros(g.n_pad + 1).at[g.dst].add(w)[:g.n_pad]
    return ell, w, rho


@partial(jax.jit, static_argnames=("mode", "grid_dim", "cell_cap"))
def iteration(g, pos, nbr_idx, nbr_mask, params, temp, alpha, *,
              mode: str, grid_dim: int = 0, cell_cap: int = 0):
    """One maxent-stress Jacobi iteration; ``params`` = [C, L, min_dist]."""
    C, L, md = params[0], params[1], params[2]
    n_pad = g.n_pad
    ell, w, rho = terms(g, L)
    pos_p = jnp.concatenate([pos, jnp.zeros((1, 2))], axis=0)
    xj, xi = pos_p[g.src], pos_p[g.dst]          # padding edges read row n
    ray = xi - xj
    length = jnp.sqrt(jnp.sum(ray * ray, axis=1) + md * md)
    target = xj + ray * (ell / length)[:, None]
    pull = jnp.zeros((n_pad + 1, 2)).at[g.dst].add(w[:, None] * target)
    r = repulsion(pos, g.mass, g.vmask, nbr_idx, nbr_mask, alpha * C, L, md,
                  mode=mode, grid_dim=grid_dim, cell_cap=cell_cap)
    has = rho > 0
    bary = (pull[:n_pad] + r) / jnp.where(has, rho, 1.0)[:, None]
    move = jnp.where(has[:, None], bary, pos) - pos
    norm = jnp.sqrt(jnp.sum(move * move, axis=1) + 1e-12)
    out = pos + move * (jnp.minimum(norm, temp) / norm)[:, None]
    return jnp.where(g.vmask[:, None], out, 0.0)


def refine(g, pos0, sched, nbr_idx, nbr_mask, *, ideal_len: float = 1.0,
           rep_const: float = 1.0, min_dist: float = 1e-3):
    """One level's refinement under ``sched`` (a ``LevelSchedule``):
    ``sched.iters`` iterations, the temperature cooling by
    ``sched.temp_decay`` and α by ``ALPHA_SHRINK ** (1 / iters)`` after
    each, both as float32 products."""
    params = jnp.asarray([rep_const, ideal_len, min_dist], jnp.float32)
    decay = jnp.float32(sched.temp_decay)
    shrink = jnp.float32(stress.ALPHA_SHRINK ** (1.0 / max(sched.iters, 1)))
    temp, alpha = jnp.float32(sched.temp0), jnp.float32(stress.ALPHA0)
    pos = jnp.asarray(pos0, jnp.float32)
    with jax.default_matmul_precision("highest"):
        for _ in range(sched.iters):
            pos = iteration(g, pos, nbr_idx, nbr_mask, params, temp, alpha,
                            mode=sched.mode, grid_dim=sched.grid_dim,
                            cell_cap=sched.cell_cap)
            temp, alpha = temp * decay, alpha * shrink
    return pos
