"""Grid-bucketed approximate repulsion — binning, composition, dispatch.

``grid_repulsion`` is the op the layout engine calls (mode="grid" in
core/gila.py). Everything here is jit-compatible with static
``grid_dim``/``cell_cap``, so the whole op — including the per-iteration
rebinning — lives inside ``gila_layout``'s fori_loop.

Pipeline per call (positions move every iteration, so all of it reruns):

  1. *Bin*: bounding box of the valid vertices → uniform ``G×G`` grid;
     each vertex gets a cell id. A stable argsort + searchsorted assigns a
     within-cell rank; vertices with rank < ``cell_cap`` land in a dense
     bucket table [G²+1, cap] (sentinel row/slots = n). Overflow vertices
     keep repelling through the aggregate terms (see 3).
  2. *Near field* (exact): every bucketed vertex vs the buckets of its
     3×3 cell neighborhood — the grouped Pallas kernel of
     kernels/neighbor_force, one group per cell (jnp oracle in ref.py
     elsewhere).
  3. *Far field* (approximate): every vertex vs per-cell aggregates
     (total mass at centroid) of ALL cells, minus the same aggregate field
     of its 9 near cells (those were counted exactly), plus the
     aggregate field of near-cell *overflow* vertices (those were NOT in
     the buckets), Plummer-softened by the overflow set's RMS radius — a
     point stand-in for a spread-out set misbehaves at near range.
     Overflow vertices themselves additionally receive the softened
     in-bucket aggregates of their 9 near cells (they have no bucket row,
     so the exact kernel never sees them). With no overflow this is the
     textbook flat Barnes–Hut with opening radius one cell; with overflow
     it degrades gracefully instead of dropping mass. The all-cells term
     is the all-pairs Pallas kernel of kernels/nbody with the cells as
     sources.

Approximation error: far cells are ≥ 1 cell width away, so the opening
angle is ≤ 1 and the centroid approximation of the 1/d force field is
accurate to a few percent; tests/test_grid_force.py bounds it end-to-end
against the all-pairs oracle on random and clustered inputs.

``repro.kernels.backend`` picks the kernel backend (``REPRO_PALLAS``).

The helpers here are also the building blocks of the *sharded* grid path
(`core/distributed.py:sharded_grid_force`, DESIGN.md §4.3): binning and the
per-cell raw sums are local per shard and psum'd over the vertex axes;
``far_corrections`` then composes the far field from the replicated sums,
and ``near_field`` resolves the 3×3 near field per shard.
"""
from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels import backend as kernel_backend, round_up
from repro.kernels.grid_force.ref import grid_near_ref, grid_far_ref
from repro.kernels.nbody.kernel import nbody_pallas
from repro.kernels.neighbor_force.kernel import neighbor_pallas

_EPS = 1e-12


def choose_grid(n: int, *, avg_occupancy: int = 12,
                multiple_of: int = 1) -> tuple[int, int]:
    """Static (grid_dim, cell_cap) for an n-vertex level.

    grid_dim targets ``avg_occupancy`` vertices per cell; cell_cap covers
    the mean plus ~6σ of a Poisson cell load (overflow beyond the cap is
    handled by the aggregate terms, so the cap bounds *work*, not
    correctness). ``multiple_of`` rounds grid_dim to a multiple — the
    sharded halo variant bands the grid rows over the vertex shards and
    needs grid_dim % vsize == 0 (core/distributed.py).
    """
    n = max(int(n), 1)
    G = int(round(math.sqrt(n / avg_occupancy)))
    G = max(2, min(G, 128))
    if multiple_of > 1:
        G = max(multiple_of, G // multiple_of * multiple_of)
    avg = n / (G * G)
    cap = int(math.ceil(avg + 6.0 * math.sqrt(avg) + 8.0))
    cap = min(max(8, (cap + 7) // 8 * 8), n)
    return G, max(cap, 1)


def grid_cell_size(lo, hi, grid_dim: int, xp=jnp):
    """Canonical G×G cell size over box (lo, hi): ``max(hi-lo, 1e-6)/G``
    in f32. Every consumer that must agree bit-for-bit on which cell/tile
    a point lands in (``bin_vertices``, ``cell_centers_from_box``, the
    serving layer's tile binning and viewport cover — serve/tiles.py,
    serve/query.py) derives the cell size HERE, with ``xp`` numpy or
    jax.numpy, instead of re-implementing the formula."""
    return xp.maximum(hi - lo, xp.float32(1e-6)) / xp.float32(grid_dim)


def _neighbor_table(G: int) -> np.ndarray:
    """[G²+1, 9] cell ids of each cell's 3×3 neighborhood (incl. itself);
    out-of-range neighbors and the sentinel row point at cell G²."""
    nc = G * G
    cells = np.arange(nc)
    cx, cy = cells % G, cells // G
    cols = []
    for oy in (-1, 0, 1):
        for ox in (-1, 0, 1):
            nx, ny = cx + ox, cy + oy
            ok = (0 <= nx) & (nx < G) & (0 <= ny) & (ny < G)
            cols.append(np.where(ok, ny * G + nx, nc))
    table = np.stack(cols, axis=1).astype(np.int32)
    return np.concatenate([table, np.full((1, 9), nc, np.int32)], axis=0)


def bin_vertices(pos, vmask, grid_dim: int, cell_cap: int, *, box=None):
    """Bucket vertices into a G×G grid over their bounding box.

    Returns (cid[n] int32 with sentinel G², bucket[G²+1, cap] int32 with
    sentinel n, inb[n] bool — vertex made it into its cell's bucket).

    ``box`` optionally fixes the binning box to ``(lo[2], hi[2])`` instead of
    the vertices' own bounding box — the serving tile pyramid
    (serve/tiles.py) bins every zoom band against the same global box so
    tile keys align across bands. Bucket slot order is the vertices' array
    order (the argsort is stable), which is how the pyramid builder turns
    the slots into a top-k: it presents vertices sorted by descending mass.
    """
    n = pos.shape[0]
    G, cap = grid_dim, cell_cap
    nc = G * G
    if box is None:
        big = jnp.float32(3e38)
        lo = jnp.min(jnp.where(vmask[:, None], pos, big), axis=0)
        hi = jnp.max(jnp.where(vmask[:, None], pos, -big), axis=0)
    else:
        lo, hi = box
    cell = grid_cell_size(lo, hi, G)
    ij = jnp.clip(jnp.floor((pos - lo) / cell), 0, G - 1).astype(jnp.int32)
    cid = jnp.where(vmask, ij[:, 1] * G + ij[:, 0], nc).astype(jnp.int32)

    order = jnp.argsort(cid)                       # stable in JAX
    sc = cid[order]
    rank = jnp.arange(n) - jnp.searchsorted(sc, sc, side="left")
    ok = (rank < cap) & (sc < nc)
    bucket = jnp.full((nc + 1, cap), n, jnp.int32)
    bucket = bucket.at[jnp.where(ok, sc, nc),
                       jnp.where(ok, rank, 0)].set(
        jnp.where(ok, order.astype(jnp.int32), n))
    inb = jnp.zeros((n,), bool).at[order].set(ok)
    return cid, bucket, inb


def _cell_aggregates(pos, w, cid, nc: int):
    """(mass[nc+1], weighted-sum[nc+1, 2], centroid[nc+1, 2]) per cell
    (sentinel row is empty)."""
    M = jax.ops.segment_sum(w, cid, num_segments=nc + 1)
    S = jax.ops.segment_sum(w[:, None] * pos, cid, num_segments=nc + 1)
    return M, S, S / jnp.maximum(M, _EPS)[:, None]


def _agg_field_9(pos, mu9, m9, C, L, md, r9=None):
    """Aggregate force field of each vertex's 9 gathered cells, on
    lane-major planes with the vertices on lanes: pos [2, n], mu9
    [2, 9, n], m9 [9, n] → [2, n]. ``r9`` [9, n] optionally
    Plummer-softens each aggregate by its RMS radius (a point mass cannot
    faithfully stand in for a spread-out set at near range — softening by
    the set's extent bounds the spurious 1/d² spike)."""
    dx = pos[0][None, :] - mu9[0]
    dy = pos[1][None, :] - mu9[1]
    d2 = dx * dx + dy * dy + md * md
    if r9 is not None:
        d2 = d2 + r9 * r9
    inv = (C * L * L) * m9 / d2
    return jnp.stack([jnp.sum(dx * inv, axis=0),
                      jnp.sum(dy * inv, axis=0)])


def _far_all_cells(pos, cell_xyw, C, L, md, mode: str):
    """Aggregate field of ALL cells on every vertex (backend-dispatched):
    pos [n, 2] vs cell_xyw [nc, 3] (x, y, mass) → [n, 2]."""
    n = pos.shape[0]
    if mode == "ref":
        chunk = 512
        npad = round_up(n, chunk)
        pp = jnp.pad(pos, ((0, npad - n), (0, 0)))
        out = jax.lax.map(
            lambda blk: grid_far_ref(blk, cell_xyw, C, L, md),
            pp.reshape(npad // chunk, chunk, 2))
        return out.reshape(npad, 2)[:n]
    return nbody_pallas(pos.T, cell_xyw.T, C, L, md,
                        interpret=(mode == "interpret")).T


def near_field(rows, nbrs, C, L, min_dist, *, backend: str | None = None):
    """Backend-dispatched near-field evaluation over lane-major planes:
    rows [2, cap, R] (x, y) vs each row group's partners nbrs [3, K, R]
    (x, y, weight; 0 = masked) → forces [2, cap, R].

    The sharded path calls this per shard with cap = 1 (one group per
    local vertex); the single-device path with cap = cell_cap (one group
    per cell)."""
    backend = backend or kernel_backend()
    if backend == "ref":
        t = lambda a: jnp.transpose(a, (2, 1, 0))
        return t(grid_near_ref(t(rows), t(nbrs[:2]), nbrs[2].T,
                               C, L, min_dist))
    return neighbor_pallas(rows, nbrs, C, L, min_dist,
                           interpret=(backend == "interpret"))


def cell_centers_from_box(lo, hi, grid_dim: int):
    """Geometric centers of the G×G cells over bounding box (lo, hi):
    [G²+1, 2] (sentinel row = 0). Shared by the single-device op and the
    sharded SPMD body (which derives lo/hi by pmin/pmax) so the centered
    second moments stay bit-identical across the two paths."""
    G = grid_dim
    cell = grid_cell_size(lo, hi, G)
    ids = jnp.arange(G * G)
    xy = jnp.stack([ids % G, ids // G], axis=1).astype(jnp.float32)
    ctr = lo[None, :] + (xy + 0.5) * cell[None, :]
    return jnp.concatenate([ctr, jnp.zeros((1, 2), jnp.float32)], axis=0)


def cell_centers(pos, vmask, grid_dim: int):
    """Geometric centers of the G×G cells over the vertices' bounding box.
    Second moments are accumulated about these — |pos − center| is at most
    a cell diagonal, so the RMS-radius cancellation ``Q/M − |µ|²`` stays
    well-conditioned in f32 no matter where the box sits (a cluster far
    from the origin would otherwise lose the radius entirely)."""
    big = jnp.float32(3e38)
    lo = jnp.min(jnp.where(vmask[:, None], pos, big), axis=0)
    hi = jnp.max(jnp.where(vmask[:, None], pos, -big), axis=0)
    return cell_centers_from_box(lo, hi, grid_dim)


def _rms(Q, M, S, centers):
    """Per-cell RMS radius from mass M, weighted-position sum S and the
    second moment Q accumulated about ``centers``."""
    mu_rel = S / jnp.maximum(M, _EPS)[:, None] - centers
    return jnp.sqrt(jnp.maximum(
        Q / jnp.maximum(M, _EPS) - jnp.sum(mu_rel * mu_rel, axis=1), 0.0))


def far_corrections(pos, w_out, cid, inb,
                    M_full, S_full, Q_full, M_out, S_out, Q_out,
                    C, L, md, *, grid_dim: int, centers):
    """Near-9 / overflow correction terms of the far field, computed from
    *replicated* per-cell raw sums (mass M, weighted position sum S, second
    moment Q about the cell ``centers``; ``_full`` = every vertex,
    ``_out`` = bucket-overflow only).

    Returns the per-vertex force to ADD to the all-cells aggregate term
    (``_far_all_cells``): subtract the 9 near cells' full aggregates (those
    pairs were counted exactly by the near field), add back the softened
    overflow aggregates, and — for overflow vertices only, which the exact
    kernel never sees — the softened in-bucket aggregates of the 9 cells.
    Shared verbatim between ``grid_repulsion`` and the sharded SPMD body in
    ``core/distributed.py`` (there the raw sums arrive via psum).
    """
    mu_full = S_full / jnp.maximum(M_full, _EPS)[:, None]
    mu_out = S_out / jnp.maximum(M_out, _EPS)[:, None]
    r_out = _rms(Q_out, M_out, S_out, centers)
    M_in = M_full - M_out
    S_in = S_full - S_out
    mu_in = S_in / jnp.maximum(M_in, _EPS)[:, None]
    r_in = _rms(Q_full - Q_out, M_in, S_in, centers)

    # every per-cell quantity in one [13, nc+1] plane with the cells on
    # lanes; each cell's 3×3 neighborhood gathered once ([117, nc+1]), then
    # fetched for every vertex by one gather along the lane axis into
    # [13, 9, n] (vertices on lanes). Gathering each [nc+1] or [nc+1, 2]
    # array by ``table[cid]`` instead puts 9 or 2 on the lane axis, and the
    # TPU compiler fetches those element by element.
    cells = jnp.concatenate([
        mu_full.T, M_full[None],                            # 0:2, 2
        mu_out.T, M_out[None], r_out[None],                 # 3:5, 5, 6
        mu_in.T, M_in[None], r_in[None],                    # 7:9, 9, 10
        S_out.T], axis=0)                                   # 11:13
    F, nc1 = cells.shape
    table = jnp.asarray(_neighbor_table(grid_dim).T)         # [9, nc+1]
    cells9 = cells[:, table].reshape(F * 9, nc1)            # [117, nc+1]
    g = cells9[:, cid].reshape(F, 9, -1)                    # [13, 9, n]
    p = pos.T                                               # [2, n]
    f = -_agg_field_9(p, g[0:2], g[2], C, L, md)
    # overflow add-back: an overflowed vertex sits inside its own cell's
    # overflow aggregate, which would exert a spurious self-force — remove
    # its own (mass, position) from the center cell before evaluating.
    # Column 4 of the table is the cell itself (the sentinel's too), so
    # block 4 of the gather holds M_out[cid] and S_out[cid].
    m_self = w_out                                          # w if overflowed
    m_adj = jnp.maximum(g[5, 4] - m_self, 0.0)
    s_adj = g[11:13, 4] - m_self[None, :] * p
    m9 = g[5].at[4].set(m_adj)
    mu9 = g[3:5].at[:, 4].set(s_adj / jnp.maximum(m_adj, _EPS)[None, :])
    f += _agg_field_9(p, mu9, m9, C, L, md, r9=g[6])
    # an overflowed vertex also never met the *bucketed* vertices of its
    # 3×3 neighborhood (it has no bucket row of its own) — restore them as
    # softened in-bucket aggregates, gated to overflow vertices only
    f_bkt = _agg_field_9(p, g[7:9], g[9], C, L, md, r9=g[10])
    return (f + jnp.where(inb, 0.0, 1.0)[None, :] * f_bkt).T


def grid_repulsion(pos, mass, vmask, C, L, min_dist, *,
                   grid_dim: int, cell_cap: int):
    """Grid-approximated FR repulsion: pos f32[n, 2] → forces f32[n, 2].

    Static ``grid_dim``/``cell_cap`` (pick with ``choose_grid``); all array
    work is traced, so the op rebins on every call. Each stage runs under
    a named scope (``grid.bin``, ``grid.aggregates``, ``grid.near``,
    ``grid.far``, ``grid.corrections``) that labels its device operations
    in a profile and changes nothing else.
    """
    assert grid_dim >= 2 and cell_cap >= 1, (grid_dim, cell_cap)
    mode = kernel_backend()
    n = pos.shape[0]
    G, cap = grid_dim, cell_cap
    nc = G * G
    pos = pos.astype(jnp.float32)
    w = jnp.where(vmask, mass, 0.0).astype(jnp.float32)

    with jax.named_scope("grid.bin"):
        cid, bucket, inb = bin_vertices(pos, vmask, G, cap)
    with jax.named_scope("grid.aggregates"):
        M_full, S_full, mu_full = _cell_aggregates(pos, w, cid, nc)
        w_out = jnp.where(inb, 0.0, w)
        M_out, S_out, _ = _cell_aggregates(pos, w_out, cid, nc)
        # per-cell second moments → RMS radii (for near-range softening),
        # accumulated about the cell centers (see cell_centers)
        centers = cell_centers(pos, vmask, G)
        q = jnp.sum((pos - centers[cid]) ** 2, axis=1)
        Q_full = jax.ops.segment_sum(w * q, cid, num_segments=nc + 1)
        Q_out = jax.ops.segment_sum(w_out * q, cid, num_segments=nc + 1)

    # -- near field: exact within the 3×3 neighborhood ------------------------
    # gathered straight into the kernel's lane-major planes (cells on lanes).
    # Column 4 of the neighbor table is offset (0, 0), the cell itself, so
    # block 4 of the partner planes holds the cell's own bucket: the rows are
    # a static slice of the one gather. (A separate ``xyw_p[:2, rows.T]``
    # pairs the row slice with a column index; the TPU compiler lowers that
    # point gather to one loop trip per bucket slot.)
    with jax.named_scope("grid.near"):
        table = jnp.asarray(_neighbor_table(G))             # [nc+1, 9]
        xyw_p = jnp.pad(jnp.concatenate([pos.T, w[None]], axis=0),
                        ((0, 0), (0, 1)))                   # [3, n+1]
        rows_idx = bucket[:nc]                              # [nc, cap]
        nbr_bucket = bucket[table[:nc]].reshape(nc, 9 * cap)
        partners = xyw_p[:, nbr_bucket.T]                   # [3, 9cap, nc]
        near = near_field(partners[:2, 4 * cap:5 * cap], partners,
                          C, L, min_dist, backend=mode)     # [2, cap, nc]
        f_near = jnp.zeros((n + 1, 2), jnp.float32).at[
            rows_idx.reshape(-1)].set(
            jnp.transpose(near, (2, 1, 0)).reshape(-1, 2))[:n]

    # -- far field: all-cell aggregates, near cells swapped for overflow ------
    with jax.named_scope("grid.far"):
        cell_xyw = jnp.concatenate([mu_full[:nc], M_full[:nc, None]], axis=1)
        f_far = _far_all_cells(pos, cell_xyw, C, L, min_dist, mode)
    with jax.named_scope("grid.corrections"):
        f_far += far_corrections(pos, w_out, cid, inb,
                                 M_full, S_full, Q_full, M_out, S_out, Q_out,
                                 C, L, min_dist, grid_dim=G, centers=centers)

    return jnp.where(vmask[:, None], f_near + f_far, 0.0)


# public aliases for the sharded path (core/distributed.py) and tests
neighbor_table = _neighbor_table
cell_aggregates = _cell_aggregates
agg_field_9 = _agg_field_9
far_all_cells = _far_all_cells
