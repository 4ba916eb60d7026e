"""shard_map distribution of the layout supersteps over the production mesh.

Decomposition (DESIGN.md §4):
  * per-vertex state is sharded over the flattened vertex axes
    VTX = ("pod", "data") — or ("data",) on a single pod;
  * the all-pairs repulsion partner dimension is sharded over "model",
    giving a 2-D decomposition of the interaction matrix: device (v, m)
    computes rows of its vertex block against column chunk m, then psums
    partials over "model";
  * edge lists are pre-sorted by destination shard (Spinner order) so each
    device's segment-sum lands in its own vertex block; source positions
    come from an all_gather over VTX (8 bytes/vertex — the same per-round
    broadcast volume the paper's Giraph workers pay), or from a halo
    exchange of only the boundary vertices (optimized variant, §Perf);
  * the grid-bucketed repulsion (mode="grid", the fine levels of big
    hierarchies) bins each device's vertex block locally against the
    psum'd global bounding box, psums the per-cell mass/centroid/second-
    moment aggregates over the vertex axes (O(G²) floats — cheap),
    computes the far field from the replicated aggregates with the cell
    columns split over "model", and resolves the exact 3×3 near field
    either from an all_gather of the bucketed positions (baseline) or by
    exchanging only the boundary-cell buckets with the two neighboring
    shards (halo variant, DESIGN.md §4.3).

Every function here is pure SPMD and lowers on the 512-chip mesh; the
dry-run rows for the layout engine come from `layout_step_specs` below.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map


def vtx_axes(mesh: Mesh) -> tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _axis_size(mesh: Mesh, names) -> int:
    s = 1
    for n in (names if isinstance(names, tuple) else (names,)):
        s *= mesh.shape[n]
    return s


# -- exact N-body, 2-D decomposed ---------------------------------------------

def sharded_nbody(mesh: Mesh, n_pad: int):
    """Returns a jitted f(pos[n_pad,2], w[n_pad]) → forces, 2-D decomposed."""
    VTX = vtx_axes(mesh)
    msize = mesh.shape["model"]

    def local(pos_blk, w_blk, params):
        C, L, md = params[0], params[1], params[2]
        pos_all = jax.lax.all_gather(pos_blk, VTX, tiled=True)   # [n_pad, 2]
        w_all = jax.lax.all_gather(w_blk, VTX, tiled=True)       # [n_pad]
        chunk = n_pad // msize
        mi = jax.lax.axis_index("model")
        cpos = jax.lax.dynamic_slice_in_dim(pos_all, mi * chunk, chunk)
        cw = jax.lax.dynamic_slice_in_dim(w_all, mi * chunk, chunk)
        dx = pos_blk[:, 0][:, None] - cpos[:, 0][None, :]
        dy = pos_blk[:, 1][:, None] - cpos[:, 1][None, :]
        d2 = dx * dx + dy * dy + md * md
        inv = (C * L * L) * cw[None, :] / d2
        partial = jnp.stack([jnp.sum(dx * inv, 1), jnp.sum(dy * inv, 1)], 1)
        return jax.lax.psum(partial, "model")

    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(VTX, None), P(VTX), P()),
                   out_specs=P(VTX, None))
    return jax.jit(fn)


# -- message superstep (attraction / merger push) ------------------------------

def sharded_attraction(mesh: Mesh, n_pad: int, m_pad: int):
    """f(pos, src, dst_local, emask, ewt, params) → attraction forces.

    Edge arrays are sharded over VTX with ``dst_local`` already offset into
    the local vertex block (host-side pre-partitioning by destination).
    """
    VTX = vtx_axes(mesh)
    vsize = _axis_size(mesh, VTX)
    n_loc = n_pad // vsize

    def local(pos_blk, src, dst_local, emask, ewt, params):
        C, L, md = params[0], params[1], params[2]
        pos_all = jax.lax.all_gather(pos_blk, VTX, tiled=True)
        pos_all = jnp.concatenate([pos_all, jnp.zeros((1, 2), pos_all.dtype)], 0)
        ps = pos_all[src]                       # [m_loc, 2] remote reads
        pd = pos_blk[jnp.clip(dst_local, 0, n_loc - 1)]
        delta = ps - pd
        dist = jnp.sqrt(jnp.sum(delta * delta, 1) + md * md)
        ell = jnp.maximum(ewt, 1e-6) * L
        f = (dist * dist) / ell
        vec = jnp.where(emask[:, None], delta / dist[:, None] * f[:, None], 0.0)
        out = jax.ops.segment_sum(vec, jnp.clip(dst_local, 0, n_loc),
                                  num_segments=n_loc + 1)
        return out[:n_loc]

    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(VTX, None), P(VTX), P(VTX), P(VTX), P(VTX), P()),
                   out_specs=P(VTX, None))
    return jax.jit(fn)


def sharded_push_max(mesh: Mesh, n_pad: int):
    """Distributed merger superstep: broadcast int values, max-combine."""
    VTX = vtx_axes(mesh)
    vsize = _axis_size(mesh, VTX)
    n_loc = n_pad // vsize

    def local(vals_blk, src, dst_local, emask):
        vals_all = jax.lax.all_gather(vals_blk, VTX, tiled=True)
        vals_all = jnp.concatenate([vals_all, jnp.full((1,), -1, vals_all.dtype)], 0)
        msgs = jnp.where(emask, vals_all[src], -1)
        out = jax.ops.segment_max(msgs, jnp.clip(dst_local, 0, n_loc),
                                  num_segments=n_loc + 1)
        return jnp.maximum(out[:n_loc], -1)

    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(VTX), P(VTX), P(VTX), P(VTX)),
                   out_specs=P(VTX))
    return jax.jit(fn)


# -- neighbor-list repulsion (fine levels) -------------------------------------

def sharded_neighbor_force(mesh: Mesh, n_pad: int, cap: int):
    """f(pos, w, nbr_idx[n_pad,cap]) — k-hop repulsion with remote gathers."""
    VTX = vtx_axes(mesh)

    def local(pos_blk, w_blk, nbr_idx, params):
        C, L, md = params[0], params[1], params[2]
        pos_all = jax.lax.all_gather(pos_blk, VTX, tiled=True)
        w_all = jax.lax.all_gather(w_blk, VTX, tiled=True)
        pos_all = jnp.concatenate([pos_all, jnp.zeros((1, 2), pos_all.dtype)], 0)
        w_all = jnp.concatenate([w_all, jnp.zeros((1,), w_all.dtype)], 0)
        npos = pos_all[nbr_idx]                 # [n_loc, cap, 2]
        nw = w_all[nbr_idx]
        delta = pos_blk[:, None, :] - npos
        d2 = jnp.sum(delta * delta, -1) + md * md
        inv = (C * L * L) * nw / d2
        return jnp.sum(delta * inv[:, :, None], axis=1)

    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(VTX, None), P(VTX), P(VTX, None), P()),
                   out_specs=P(VTX, None))
    return jax.jit(fn)


# -- grid-bucketed repulsion, sharded (fine levels of big hierarchies) ---------

def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _chunk_for(n: int, target: int = 2048) -> int:
    """Largest divisor of ``n`` that is ≤ ``target`` (near-field row chunk)."""
    for c in range(min(n, target), 0, -1):
        if n % c == 0:
            return c
    return 1


def _grid_rep_spmd(pos_blk, w_blk, C, L, md, *, mesh: Mesh, n_pad: int,
                   grid_dim: int, cell_cap: int, variant: str, backend: str,
                   pos_all=None, w_all=None):
    """SPMD-local grid repulsion for one vertex block (call inside shard_map).

    ``w_blk`` is the vmask-zeroed vertex mass (w = 0 ⇔ padding). Matches the
    single-device ``grid_repulsion`` composition term for term:

      * global bounding box via pmin/pmax over the vertex axes (exact);
      * binning: the baseline all_gathers positions/weights (which the
        full superstep needs for attraction anyway) and reruns the
        single-device ``bin_vertices`` on the replicated arrays — cell
        ids, bucket table, and bucket membership are bit-identical to the
        single-device op at zero extra collectives; the halo variant bins
        its block locally and uses local stable ranks (the band contract
        guarantees a cell's vertices share a shard, so local = global);
      * per-cell raw sums (mass / weighted position / second moment, full
        and overflow-only) psum'd over the vertex axes: O(G²) floats;
      * far field = all-cells aggregate term with the cell columns split
        over "model" (psum), plus the replicated correction terms
        (`kernels.grid_force.ops.far_corrections`);
      * near field = exact 3×3-neighborhood pairs for bucketed vertices,
        evaluated per local vertex in row chunks with the 9·cap partner
        columns split over "model". Partner buckets come from the
        replicated bucket table (variant="allgather") or from the
        band-local bucket table extended by the two ppermute'd boundary
        rows (variant="halo").

    The halo variant assumes the band contract (DESIGN.md §4.3): device d's
    vertices lie in grid rows [d·G/vsize, (d+1)·G/vsize). A vertex that
    violates it is reclassified as bucket overflow: it keeps the exact far
    field, its neighbors keep a softened aggregate view of its mass, and
    only its own near field degrades to the softened in-bucket aggregates
    — graceful degradation, never a blow-up or dropped mass.
    """
    from repro.kernels.grid_force import ops as gops

    VTX = vtx_axes(mesh)
    vsize = _axis_size(mesh, VTX)
    msize = mesh.shape["model"]
    G, cap = grid_dim, cell_cap
    nc = G * G
    n_loc = pos_blk.shape[0]
    pos_blk = pos_blk.astype(jnp.float32)
    w_blk = w_blk.astype(jnp.float32)
    vmask_blk = w_blk > 0
    mi = jax.lax.axis_index("model")
    di = jnp.int32(0)                    # flattened device index over VTX
    for a in VTX:
        di = di * mesh.shape[a] + jax.lax.axis_index(a)

    # -- bin against the global bounding box ----------------------------------
    big = jnp.float32(3e38)
    lo = jax.lax.pmin(
        jnp.min(jnp.where(vmask_blk[:, None], pos_blk, big), axis=0), VTX)
    hi = jax.lax.pmax(
        jnp.max(jnp.where(vmask_blk[:, None], pos_blk, -big), axis=0), VTX)
    cell = jnp.maximum(hi - lo, 1e-6) / G
    bucket = None
    if variant == "halo":
        # local binning + local stable ranks (band contract: a cell's
        # vertices all share this shard, so local ranks are global ranks)
        ij = jnp.clip(jnp.floor((pos_blk - lo) / cell), 0,
                      G - 1).astype(jnp.int32)
        cid = jnp.where(vmask_blk, ij[:, 1] * G + ij[:, 0],
                        nc).astype(jnp.int32)
        order = jnp.argsort(cid)         # stable → ascending index in cell
        sc = cid[order]
        grank = jnp.zeros((n_loc,), jnp.int32).at[order].set(
            (jnp.arange(n_loc) - jnp.searchsorted(sc, sc, side="left"))
            .astype(jnp.int32))
        Gb = G // vsize
        nc_band = Gb * G
        lc = cid - di * nc_band          # band-local cell index
        band_ok = (lc >= 0) & (lc < nc_band) & (cid < nc)
        # a band-contract violator counts as bucket OVERFLOW, not in-bucket:
        # it enters the psum'd overflow aggregates, so its neighbors keep a
        # softened view of its mass and it keeps the exact far field — only
        # its own near field degrades (the documented contract)
        inb = (grank < cap) & band_ok
    else:
        # replicated global binning on the all_gathered arrays (the full
        # superstep gathers positions for attraction anyway): cell ids,
        # bucket table and bucket membership are bit-identical to the
        # single-device op, at zero extra collectives
        if pos_all is None:
            pos_all = jax.lax.all_gather(pos_blk, VTX, tiled=True)
            w_all = jax.lax.all_gather(w_blk, VTX, tiled=True)
        pos_all = pos_all.astype(jnp.float32)
        w_all = w_all.astype(jnp.float32)
        cid_all, bucket, inb_all = gops.bin_vertices(pos_all, w_all > 0,
                                                     G, cap)
        cid = jax.lax.dynamic_slice_in_dim(cid_all, di * n_loc, n_loc)
        inb = jax.lax.dynamic_slice_in_dim(inb_all, di * n_loc, n_loc)

    # -- per-cell raw sums, psum'd over the vertex axes (O(G²) floats) --------
    # second moments about the cell centers, matching cell_centers()'s
    # conditioning argument (kernels/grid_force/ops.py)
    centers = gops.cell_centers_from_box(lo, hi, G)
    q = jnp.sum((pos_blk - centers[cid]) ** 2, axis=1)
    w_out = jnp.where(inb, 0.0, w_blk)

    def sums(wv):
        M = jax.ops.segment_sum(wv, cid, num_segments=nc + 1)
        S = jax.ops.segment_sum(wv[:, None] * pos_blk, cid,
                                num_segments=nc + 1)
        Q = jax.ops.segment_sum(wv * q, cid, num_segments=nc + 1)
        return M, S, Q
    M_full, S_full, Q_full, M_out, S_out, Q_out = jax.lax.psum(
        sums(w_blk) + sums(w_out), VTX)

    # -- far field: all-cells term (cell columns split over "model") ----------
    mu_full = S_full / jnp.maximum(M_full, 1e-12)[:, None]
    cell_xyw = jnp.concatenate([mu_full[:nc], M_full[:nc, None]], axis=1)
    ncp = _round_up(nc, msize)
    cells_p = jnp.pad(cell_xyw, ((0, ncp - nc), (0, 0)))     # pad mass = 0
    cells_m = jax.lax.dynamic_slice_in_dim(cells_p, mi * (ncp // msize),
                                           ncp // msize)
    rep = jax.lax.psum(
        gops.far_all_cells(pos_blk, cells_m, C, L, md, backend), "model")
    rep += gops.far_corrections(pos_blk, w_out, cid, inb,
                                M_full, S_full, Q_full, M_out, S_out, Q_out,
                                C, L, md, grid_dim=G, centers=centers)

    # -- near field: exact 3×3 pairs, chunked rows × "model"-split columns ----
    K = 9 * cap
    Kp = _round_up(K, msize)
    Kc = Kp // msize
    ch = _chunk_for(n_loc)
    if variant == "halo":
        okb = inb                        # already implies band_ok
        xyw = jnp.concatenate([pos_blk, w_blk[:, None]], axis=1)
        tbl = jnp.zeros((nc_band + 1, cap, 3), jnp.float32).at[
            jnp.where(okb, lc, nc_band), jnp.where(okb, grank, 0)].set(
            jnp.where(okb[:, None], xyw, 0.0))
        band = tbl[:nc_band].reshape(Gb, G, cap, 3)
        # boundary-bucket exchange: first/last grid row to the two neighbors
        # (2·G·cap·3 floats vs the baseline's n_pad·3-float all_gather);
        # devices with no peer receive zeros = empty buckets, which is
        # exactly right for rows beyond the grid.
        fwd = [(d, d + 1) for d in range(vsize - 1)]
        bwd = [(d + 1, d) for d in range(vsize - 1)]
        halo_top = jax.lax.ppermute(band[-1], VTX, fwd)      # d-1's last row
        halo_bot = jax.lax.ppermute(band[0], VTX, bwd)       # d+1's first row
        ext = jnp.concatenate([halo_top[None], band, halo_bot[None]], axis=0)
        sent = (Gb + 2) * G                                  # empty sentinel
        ext = jnp.concatenate([ext.reshape(sent * cap, 3),
                               jnp.zeros((cap, 3), jnp.float32)], axis=0)
        ext = ext.reshape(sent + 1, cap, 3)
        cx, cy = cid % G, cid // G
        ey = cy - di * Gb + 1                                # extended row
        cols = []
        for oy in (-1, 0, 1):
            for ox in (-1, 0, 1):
                nx, ny = cx + ox, cy + oy
                valid = band_ok & (nx >= 0) & (nx < G) & (ny >= 0) & (ny < G)
                cols.append(jnp.where(valid, (ey + oy) * G + nx, sent))
        near9 = jnp.stack(cols, axis=1).astype(jnp.int32)    # [n_loc, 9]
        near_mask = inb

        def near_chunk(args):
            pos_c, n9_c = args
            nbr = ext[n9_c].reshape(-1, K, 3)
            nbr = jnp.pad(nbr, ((0, 0), (0, Kp - K), (0, 0)))
            nbr = jax.lax.dynamic_slice_in_dim(nbr, mi * Kc, Kc, axis=1)
            return gops.near_field(pos_c.T[:, None, :], nbr.T, C, L, md,
                                   backend=backend)[:, 0].T
    else:
        pos_p = jnp.concatenate(
            [pos_all, jnp.zeros((1, 2), jnp.float32)], 0)
        w_p = jnp.concatenate(
            [w_all, jnp.zeros((1,), jnp.float32)], 0)
        table = jnp.asarray(gops.neighbor_table(G))
        near9 = table[cid]                                   # [n_loc, 9]
        near_mask = inb

        def near_chunk(args):
            pos_c, n9_c = args
            idx = bucket[n9_c].reshape(-1, K)
            idx = jnp.pad(idx, ((0, 0), (0, Kp - K)), constant_values=n_pad)
            idx = jax.lax.dynamic_slice_in_dim(idx, mi * Kc, Kc, axis=1).T
            nbrs = jnp.concatenate([pos_p.T[:, idx], w_p[idx][None]], 0)
            return gops.near_field(pos_c.T[:, None, :], nbrs, C, L, md,
                                   backend=backend)[:, 0].T

    f_near = jax.lax.map(near_chunk,
                         (pos_blk.reshape(n_loc // ch, ch, 2),
                          near9.reshape(n_loc // ch, ch, 9)))
    f_near = jax.lax.psum(f_near.reshape(n_loc, 2), "model")
    rep += jnp.where(near_mask[:, None], f_near, 0.0)
    return jnp.where(vmask_blk[:, None], rep, 0.0)


def sharded_grid_force(mesh: Mesh, n_pad: int, grid_dim: int, cell_cap: int,
                       variant: str = "allgather",
                       backend: str | None = None):
    """Returns a jitted f(pos[n_pad, 2], w[n_pad], params[3]) → forces.

    ``params = [C, L, min_dist]``; ``w`` is the vmask-zeroed vertex mass.
    Matches the single-device ``grid_repulsion`` (same grid_dim/cell_cap)
    to float tolerance; see ``_grid_rep_spmd`` for the decomposition and
    kernels/grid_force/README.md for when variant="halo" beats the
    all_gather baseline.
    """
    assert variant in ("allgather", "halo"), variant
    assert grid_dim >= 2 and cell_cap >= 1, (grid_dim, cell_cap)
    VTX = vtx_axes(mesh)
    vsize = _axis_size(mesh, VTX)
    assert n_pad % vsize == 0, (n_pad, vsize)
    if variant == "halo":
        assert grid_dim % vsize == 0, (grid_dim, vsize)
    if backend is None:
        from repro.kernels import backend as kernel_backend
        backend = kernel_backend()

    def local(pos_blk, w_blk, params):
        C, L, md = params[0], params[1], params[2]
        return _grid_rep_spmd(pos_blk, w_blk, C, L, md, mesh=mesh,
                              n_pad=n_pad, grid_dim=grid_dim,
                              cell_cap=cell_cap, variant=variant,
                              backend=backend)

    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(VTX, None), P(VTX), P()),
                   out_specs=P(VTX, None))
    return jax.jit(fn)


# -- full distributed layout step (used by the dry-run) ------------------------

def layout_train_step(mesh: Mesh, n_pad: int, m_pad: int, cap: int,
                      mode: str = "neighbor", grid_dim: int = 0,
                      cell_cap: int = 0, engine: str = "gila"):
    """One full distributed refinement iteration for ``engine``.

    ``mode`` is "exact" | "neighbor" | "grid" (the same selection
    core/schedule.py makes by level size). Grid mode needs the static
    ``grid_dim``/``cell_cap`` from ``kernels.grid_force.choose_grid`` and
    ignores ``nbr_idx`` (pass cap = 1 dummies, see ``layout_step_specs``).

    ``engine="gila"`` is the FR superstep (repulsion + attraction +
    temp-clamped displacement). ``engine="stress"`` is the maxent-stress
    Jacobi superstep (core/stress.py): the per-vertex numerator/denominator
    segment-sums run over this shard's destination block (the same
    Spinner-order edge partition the attraction uses), the entropy repulsion
    reuses the mode branches with C scaled by the traced ``alpha``, and the
    step takes one extra replicated scalar ``alpha`` after ``temp``.

    Returns (step_fn, input_shardings) suitable for
    jax.jit(step_fn, in_shardings=...).lower(*specs).
    """
    VTX = vtx_axes(mesh)
    vsize = _axis_size(mesh, VTX)
    n_loc = n_pad // vsize
    msize = mesh.shape["model"]
    if mode == "grid":
        assert grid_dim >= 2 and cell_cap >= 1, (grid_dim, cell_cap)
        from repro.kernels import backend as kernel_backend
        grid_backend = kernel_backend()

    def repulsion(pos_blk, w_blk, nbr_idx, pos_all, w_all, pos_pad, w_pad,
                  C, L, md):
        if mode == "exact":
            chunk = n_pad // msize
            mi = jax.lax.axis_index("model")
            cpos = jax.lax.dynamic_slice_in_dim(pos_all, mi * chunk, chunk)
            cw = jax.lax.dynamic_slice_in_dim(w_all, mi * chunk, chunk)
            dx = pos_blk[:, 0][:, None] - cpos[:, 0][None, :]
            dy = pos_blk[:, 1][:, None] - cpos[:, 1][None, :]
            d2 = dx * dx + dy * dy + md * md
            inv = (C * L * L) * cw[None, :] / d2
            return jax.lax.psum(
                jnp.stack([jnp.sum(dx * inv, 1), jnp.sum(dy * inv, 1)], 1),
                "model")
        if mode == "grid":
            return _grid_rep_spmd(pos_blk, w_blk, C, L, md, mesh=mesh,
                                  n_pad=n_pad, grid_dim=grid_dim,
                                  cell_cap=cell_cap, variant="allgather",
                                  backend=grid_backend,
                                  pos_all=pos_all, w_all=w_all)
        # split the neighbor cap over the model axis → 2-D decomposition
        ccap = cap // msize
        mi = jax.lax.axis_index("model")
        nidx = jax.lax.dynamic_slice_in_dim(nbr_idx, mi * ccap, ccap, axis=1)
        npos = pos_pad[nidx]
        nw = w_pad[nidx]
        delta = pos_blk[:, None, :] - npos
        d2 = jnp.sum(delta * delta, -1) + md * md
        inv = (C * L * L) * nw / d2
        return jax.lax.psum(jnp.sum(delta * inv[:, :, None], axis=1), "model")

    def local(pos_blk, w_blk, nbr_idx, src, dst_local, emask, ewt, params, temp):
        C, L, md = params[0], params[1], params[2]
        pos_all = jax.lax.all_gather(pos_blk, VTX, tiled=True)
        w_all = jax.lax.all_gather(w_blk, VTX, tiled=True)
        pos_pad = jnp.concatenate([pos_all, jnp.zeros((1, 2), pos_all.dtype)], 0)
        w_pad = jnp.concatenate([w_all, jnp.zeros((1,), w_all.dtype)], 0)

        rep = repulsion(pos_blk, w_blk, nbr_idx, pos_all, w_all, pos_pad,
                        w_pad, C, L, md)

        ps = pos_pad[src]
        pd = pos_blk[jnp.clip(dst_local, 0, n_loc - 1)]
        delta = ps - pd
        dist = jnp.sqrt(jnp.sum(delta * delta, 1) + md * md)
        f = (dist * dist) / (jnp.maximum(ewt, 1e-6) * L)
        vec = jnp.where(emask[:, None], delta / dist[:, None] * f[:, None], 0.0)
        att = jax.ops.segment_sum(vec, jnp.clip(dst_local, 0, n_loc),
                                  num_segments=n_loc + 1)[:n_loc]

        force = rep + att
        norm = jnp.sqrt(jnp.sum(force * force, 1) + 1e-12)
        step = jnp.minimum(norm, temp)
        return pos_blk + force / norm[:, None] * step[:, None]

    def local_stress(pos_blk, w_blk, nbr_idx, src, dst_local, emask, ewt,
                     params, temp, alpha):
        C, L, md = params[0], params[1], params[2]
        pos_all = jax.lax.all_gather(pos_blk, VTX, tiled=True)
        w_all = jax.lax.all_gather(w_blk, VTX, tiled=True)
        pos_pad = jnp.concatenate([pos_all, jnp.zeros((1, 2), pos_all.dtype)], 0)
        w_pad = jnp.concatenate([w_all, jnp.zeros((1,), w_all.dtype)], 0)

        # entropy term: the FR repulsion field with C annealed by alpha
        rep = repulsion(pos_blk, w_blk, nbr_idx, pos_all, w_all, pos_pad,
                        w_pad, alpha * C, L, md)

        # weighted-Jacobi stress term over this shard's destination block
        ell = jnp.maximum(ewt, 1e-6) * L
        we = jnp.where(emask, 1.0 / (ell * ell), 0.0)
        ps = pos_pad[src]
        pd = pos_blk[jnp.clip(dst_local, 0, n_loc - 1)]
        delta = pd - ps
        dist = jnp.sqrt(jnp.sum(delta * delta, 1) + md * md)
        tgt = ps + delta / dist[:, None] * ell[:, None]
        vec = jnp.where(emask[:, None], we[:, None] * tgt, 0.0)
        seg = jnp.clip(dst_local, 0, n_loc)
        num = jax.ops.segment_sum(vec, seg, num_segments=n_loc + 1)[:n_loc]
        rho = jax.ops.segment_sum(we, seg, num_segments=n_loc + 1)[:n_loc]

        new = (num + rep) / jnp.maximum(rho, 1e-12)[:, None]
        new = jnp.where(rho[:, None] > 0, new, pos_blk)
        d = new - pos_blk
        norm = jnp.sqrt(jnp.sum(d * d, 1) + 1e-12)
        step = jnp.minimum(norm, temp)
        return pos_blk + d / norm[:, None] * step[:, None]

    if engine == "stress":
        step = shard_map(
            local_stress, mesh=mesh,
            in_specs=(P(VTX, None), P(VTX), P(VTX, None), P(VTX), P(VTX),
                      P(VTX), P(VTX), P(), P(), P()),
            out_specs=P(VTX, None))
    else:
        step = shard_map(
            local, mesh=mesh,
            in_specs=(P(VTX, None), P(VTX), P(VTX, None), P(VTX), P(VTX),
                      P(VTX), P(VTX), P(), P()),
            out_specs=P(VTX, None))
    shardings = dict(
        pos=NamedSharding(mesh, P(VTX, None)),
        w=NamedSharding(mesh, P(VTX)),
        nbr_idx=NamedSharding(mesh, P(VTX, None)),
        edge=NamedSharding(mesh, P(VTX)),
        scalar=NamedSharding(mesh, P()),
    )
    return step, shardings


def layout_train_step_halo(mesh: Mesh, n_pad: int, m_pad: int, cap: int,
                           halo: int, mode: str = "neighbor",
                           grid_dim: int = 0, cell_cap: int = 0):
    """GiLA iteration with HALO EXCHANGE instead of the position all-gather
    (§Perf hillclimb C — the paper's Spinner-locality insight made explicit).

    With a Spinner partition, almost all k-hop neighbors are shard-local;
    each device needs only the boundary ("halo") positions of its peers.
    Host-side preprocessing produces, per device, ``send_idx[P, halo]``
    (local vertices each peer needs; sentinel-padded) and neighbor lists
    remapped into [local | halo-slot | sentinel] coordinates. Communication
    per superstep drops from all-gather(n·12B) to all_to_all(P·halo·12B).

    ``mode="grid"`` replaces the neighbor-list repulsion with the sharded
    grid repulsion in its halo variant (boundary-cell bucket ppermute,
    ``nbr_local`` ignored — pass cap = 1 dummies). The attraction keeps
    this step's halo machinery, so no superstep all-gathers positions;
    requires the band contract of ``_grid_rep_spmd``.
    """
    VTX = vtx_axes(mesh)
    vsize = _axis_size(mesh, VTX)
    n_loc = n_pad // vsize
    if mode == "grid":
        assert grid_dim >= 2 and cell_cap >= 1, (grid_dim, cell_cap)
        assert grid_dim % vsize == 0, (grid_dim, vsize)
        from repro.kernels import backend as kernel_backend
        grid_backend = kernel_backend()

    def local(pos_blk, w_blk, nbr_local, send_idx, src_local, dst_local,
              emask, ewt, params, temp):
        C, L, md = params[0], params[1], params[2]
        P_ = send_idx.shape[0]
        table = jnp.concatenate(
            [pos_blk, jnp.zeros((1, 2), pos_blk.dtype)], 0)
        wtab = jnp.concatenate([w_blk, jnp.zeros((1,), w_blk.dtype)], 0)
        sidx = jnp.clip(send_idx, 0, n_loc)
        send = jnp.concatenate(
            [table[sidx], wtab[sidx][..., None]], axis=-1)     # [P, halo, 3]
        # hierarchical personalized all-to-all over the vertex axes:
        # peers laid out [pod, data]; exchange the data stage, then pod.
        shape = tuple(mesh.shape[a] for a in VTX)
        recv = send.reshape(shape + send.shape[1:])
        for d, ax in enumerate(VTX):
            recv = jax.lax.all_to_all(recv, ax, split_axis=d, concat_axis=d)
        recv = recv.reshape(P_, -1, 3)

        halo_pos = recv[..., :2].reshape(-1, 2)
        halo_w = recv[..., 2].reshape(-1)
        full_pos = jnp.concatenate(
            [pos_blk, halo_pos, jnp.zeros((1, 2), pos_blk.dtype)], 0)
        full_w = jnp.concatenate([w_blk, halo_w,
                                  jnp.zeros((1,), w_blk.dtype)], 0)

        if mode == "grid":
            rep = _grid_rep_spmd(pos_blk, w_blk, C, L, md, mesh=mesh,
                                 n_pad=n_pad, grid_dim=grid_dim,
                                 cell_cap=cell_cap, variant="halo",
                                 backend=grid_backend)
        else:
            npos = full_pos[nbr_local]              # [n_loc, cap, 2]
            nw = full_w[nbr_local]
            delta = pos_blk[:, None, :] - npos
            d2 = jnp.sum(delta * delta, -1) + md * md
            inv = (C * L * L) * nw / d2
            rep = jnp.sum(delta * inv[:, :, None], axis=1)

        ps = full_pos[src_local]
        pd = pos_blk[jnp.clip(dst_local, 0, n_loc - 1)]
        delta = ps - pd
        dist = jnp.sqrt(jnp.sum(delta * delta, 1) + md * md)
        f = (dist * dist) / (jnp.maximum(ewt, 1e-6) * L)
        vec = jnp.where(emask[:, None], delta / dist[:, None] * f[:, None], 0.0)
        att = jax.ops.segment_sum(vec, jnp.clip(dst_local, 0, n_loc),
                                  num_segments=n_loc + 1)[:n_loc]

        force = rep + att
        norm = jnp.sqrt(jnp.sum(force * force, 1) + 1e-12)
        step = jnp.minimum(norm, temp)
        return pos_blk + force / norm[:, None] * step[:, None]

    step = shard_map(
        local, mesh=mesh,
        in_specs=(P(VTX, None), P(VTX), P(VTX, None), P(VTX, None), P(VTX),
                  P(VTX), P(VTX), P(VTX), P(), P()),
        out_specs=P(VTX, None))
    shardings = dict(
        pos=NamedSharding(mesh, P(VTX, None)),
        w=NamedSharding(mesh, P(VTX)),
        nbr_idx=NamedSharding(mesh, P(VTX, None)),
        send=NamedSharding(mesh, P(VTX, None)),
        edge=NamedSharding(mesh, P(VTX)),
        scalar=NamedSharding(mesh, P()),
    )
    return step, shardings


def layout_halo_specs(mesh: Mesh, n_pad: int, m_pad: int, cap: int,
                      halo: int, mode: str = "neighbor"):
    VTX = vtx_axes(mesh)
    vsize = _axis_size(mesh, VTX)
    if mode == "grid":
        cap = 1                          # nbr_local unused in grid mode
    f32, i32 = jnp.float32, jnp.int32
    return dict(
        pos=jax.ShapeDtypeStruct((n_pad, 2), f32),
        w=jax.ShapeDtypeStruct((n_pad,), f32),
        nbr_local=jax.ShapeDtypeStruct((n_pad, cap), i32),
        send_idx=jax.ShapeDtypeStruct((vsize * vsize, halo), i32),
        src_local=jax.ShapeDtypeStruct((m_pad,), i32),
        dst_local=jax.ShapeDtypeStruct((m_pad,), i32),
        emask=jax.ShapeDtypeStruct((m_pad,), jnp.bool_),
        ewt=jax.ShapeDtypeStruct((m_pad,), f32),
        params=jax.ShapeDtypeStruct((3,), f32),
        temp=jax.ShapeDtypeStruct((), f32),
    )


def layout_step_specs(n_pad: int, m_pad: int, cap: int,
                      mode: str = "neighbor", engine: str = "gila"):
    """ShapeDtypeStructs for the dry-run (no allocation). In grid mode the
    neighbor lists are unused; cap collapses to a 1-wide dummy. The stress
    engine's step takes one extra replicated annealing scalar ``alpha``."""
    if mode == "grid":
        cap = 1
    f32, i32 = jnp.float32, jnp.int32
    specs = dict(
        pos=jax.ShapeDtypeStruct((n_pad, 2), f32),
        w=jax.ShapeDtypeStruct((n_pad,), f32),
        nbr_idx=jax.ShapeDtypeStruct((n_pad, cap), i32),
        src=jax.ShapeDtypeStruct((m_pad,), i32),
        dst_local=jax.ShapeDtypeStruct((m_pad,), i32),
        emask=jax.ShapeDtypeStruct((m_pad,), jnp.bool_),
        ewt=jax.ShapeDtypeStruct((m_pad,), f32),
        params=jax.ShapeDtypeStruct((3,), f32),
        temp=jax.ShapeDtypeStruct((), f32),
    )
    if engine == "stress":
        specs["alpha"] = jax.ShapeDtypeStruct((), f32)
    return specs


# -- host-side level driver (engine="multigila_dist" in core/multilevel.py) ----

def partition_edges(src, dst, emask, ewt, n_pad: int, vsize: int,
                    bucket: bool = False):
    """Host-side Spinner-order edge partition: group edges by the device
    block that owns their destination, pad every block to the max block
    length, and offset destinations into block-local coordinates.

    Returns (src[m_pad2], dst_local[m_pad2], emask[m_pad2], ewt[m_pad2],
    m_pad2) laid out so ``P(VTX)`` sharding puts each device exactly its
    own destination block (padding edges: src = n_pad sentinel, mask off).

    ``bucket=True`` rounds the per-device block length up to the next pow2
    bucket: the block length is otherwise data-dependent (max in-degree
    load), which would defeat the compiled-step cache keyed on m_pad
    (core/bucketing.py).
    """
    src = np.asarray(src)
    dst = np.asarray(dst)
    emask = np.asarray(emask)
    ewt = np.asarray(ewt)
    n_loc = n_pad // vsize
    src, dst, ewt = src[emask], dst[emask], ewt[emask]
    owner = dst // n_loc
    m_loc = max(int(np.bincount(owner, minlength=vsize).max()), 1)
    if bucket:
        from repro.graphs.graph import bucket_pad
        m_loc = bucket_pad(m_loc, minimum=64)
    S = np.full((vsize, m_loc), n_pad, np.int32)
    DL = np.zeros((vsize, m_loc), np.int32)
    EM = np.zeros((vsize, m_loc), bool)
    EW = np.ones((vsize, m_loc), np.float32)
    for d in range(vsize):
        sel = owner == d
        k = int(sel.sum())
        S[d, :k] = src[sel]
        DL[d, :k] = dst[sel] - d * n_loc
        EM[d, :k] = True
        EW[d, :k] = ewt[sel]
    return (S.reshape(-1), DL.reshape(-1), EM.reshape(-1), EW.reshape(-1),
            vsize * m_loc)


def _mesh_cache_key(mesh: Mesh) -> tuple:
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            tuple(d.id for d in mesh.devices.flat))


def cached_layout_step(mesh: Mesh, n_pad: int, m_pad: int, cap: int, *,
                       mode: str, grid_dim: int = 0, cell_cap: int = 0,
                       engine: str = "gila"):
    """Process-wide cached (jitted step, shardings) for one shape bucket.

    ``layout_train_step`` returns a FRESH shard_map + jit wrapper per call,
    so calling it per level recompiles even for identical shapes; keying on
    (mesh, bucket shapes, mode statics) makes the whole hierarchy — and
    every later same-bucket graph — reuse one compiled program. The
    position argument is donated (no per-iteration copy on accelerators).

    Returns (jitted_step, shardings, fresh).
    """
    from repro.core import bucketing

    key = ("dist_step", engine, _mesh_cache_key(mesh), n_pad, m_pad, cap,
           mode, grid_dim, cell_cap, bucketing.kernel_backend())

    def build():
        step, sh = layout_train_step(mesh, n_pad, m_pad, cap, mode=mode,
                                     grid_dim=grid_dim, cell_cap=cell_cap,
                                     engine=engine)
        jitted = jax.jit(
            step, donate_argnums=bucketing.donate_argnums_if_supported(0))
        return jitted, sh

    (jitted, sh), fresh = bucketing.STEP_CACHE.get(key, build)
    return jitted, sh, fresh


def run_layout_level(mesh: Mesh, g, pos0, sched, *, ideal_len: float,
                     rep_const: float, min_dist: float = 1e-3,
                     seed: int = 0, bucket: bool = True) -> np.ndarray:
    """Lay out ONE hierarchy level with the distributed superstep.

    Host-side wrapper around ``layout_train_step``: re-pads the level to
    mesh-divisible sizes, partitions edges by destination shard, builds
    k-hop lists for mode="neighbor" (global indices — the step gathers
    from the replicated position table), and runs ``sched.iters`` cooling
    iterations. Returns positions [g.n_pad, 2] (numpy, padding zeroed),
    so it is a drop-in for ``gila.gila_layout`` in the multilevel driver.

    With ``bucket=True`` (the driver default) the step function comes from
    the process-wide compile cache and the edge partition is padded to a
    pow2 block bucket, so same-bucket levels share one compiled program.
    """
    import time

    from repro.core import gila
    from repro.core.bucketing import PHASES
    from repro.graphs.graph import unique_edges

    VTX = vtx_axes(mesh)
    vsize = _axis_size(mesh, VTX)
    msize = mesh.shape["model"]
    n_pad = _round_up(g.n_pad, vsize * msize)

    pos = np.zeros((n_pad, 2), np.float32)
    pos[:g.n_pad] = np.asarray(pos0, np.float32)[:g.n_pad]
    w = np.zeros((n_pad,), np.float32)
    w[:g.n_pad] = np.where(np.asarray(g.vmask), np.asarray(g.mass),
                           0.0).astype(np.float32)
    pos[w == 0] = 0.0

    src_e, dst_local, emask, ewt, m_pad = partition_edges(
        np.asarray(g.src), np.asarray(g.dst), np.asarray(g.emask),
        np.asarray(g.ewt), n_pad, vsize, bucket=bucket)

    if sched.mode == "neighbor":
        cap = _round_up(sched.cap, msize)
        idx, mask = gila.khop_neighbors(unique_edges(g), g.n, sched.k, cap,
                                        seed)
        nbr = np.full((n_pad, cap), n_pad, np.int32)
        nbr[:g.n] = np.where(mask, idx, n_pad)
    else:
        cap = 1
        nbr = np.full((n_pad, 1), n_pad, np.int32)

    engine = getattr(sched, "engine", "gila")
    jitted, sh, fresh = cached_layout_step(mesh, n_pad, m_pad, cap,
                                           mode=sched.mode,
                                           grid_dim=sched.grid_dim,
                                           cell_cap=sched.cell_cap,
                                           engine=engine)
    from repro.utils.transfer import io_boundary

    if engine == "stress":
        from repro.core.stress import alpha_schedule
        alpha, alpha_decay = alpha_schedule(sched.iters)
    else:
        alpha, alpha_decay = None, 1.0

    dput = jax.device_put
    with io_boundary():                     # ingest: host partition → mesh
        pos_d = dput(jnp.asarray(pos), sh["pos"])
        w_d = dput(jnp.asarray(w), sh["w"])
        nbr_d = dput(jnp.asarray(nbr), sh["nbr_idx"])
        src_d = dput(jnp.asarray(src_e), sh["edge"])
        dst_d = dput(jnp.asarray(dst_local), sh["edge"])
        em_d = dput(jnp.asarray(emask), sh["edge"])
        ew_d = dput(jnp.asarray(ewt), sh["edge"])
        params = dput(
            jnp.asarray([rep_const, ideal_len, min_dist], jnp.float32),
            sh["scalar"])
    temp = sched.temp0
    t0 = time.perf_counter()
    for it in range(sched.iters):
        with io_boundary():                 # staging: annealing scalars
            temp_d = dput(jnp.asarray(temp, jnp.float32), sh["scalar"])
            if alpha is not None:
                al_d = dput(jnp.asarray(alpha, jnp.float32), sh["scalar"])
        if alpha is not None:
            pos_d = jitted(pos_d, w_d, nbr_d, src_d, dst_d, em_d, ew_d,
                           params, temp_d, al_d)
        else:
            pos_d = jitted(pos_d, w_d, nbr_d, src_d, dst_d, em_d, ew_d,
                           params, temp_d)
        if it == 0 and fresh:               # first call traces + compiles
            pos_d.block_until_ready()
            PHASES.add("compile", time.perf_counter() - t0)
            t0 = time.perf_counter()
        temp *= sched.temp_decay
        if alpha is not None:
            alpha *= alpha_decay
    pos_d.block_until_ready()
    PHASES.add("refine", time.perf_counter() - t0)
    with io_boundary():                     # egress: gather to host
        out = np.asarray(pos_d)[:g.n_pad]
    return np.where(w[:g.n_pad, None] > 0, out, 0.0).astype(np.float32)
