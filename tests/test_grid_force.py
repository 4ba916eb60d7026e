"""Grid-force kernel sweeps (Pallas interpret vs jnp oracle), end-to-end
approximation-error bounds vs the all-pairs oracle, and schedule wiring."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels.grid_force.ref import grid_near_ref, grid_far_ref
from repro.kernels.nbody.kernel import nbody_pallas
from repro.kernels.neighbor_force.kernel import neighbor_pallas
from repro.kernels.grid_force.ops import grid_repulsion, choose_grid
from repro.kernels.nbody.ref import nbody_repulsion_ref


@pytest.mark.parametrize("nc,cap,block", [(16, 8, 1), (64, 16, 4),
                                          (25, 24, 5)])
def test_grid_near_kernel_matches_ref(nc, cap, block):
    rng = np.random.default_rng(nc + cap)
    rows = rng.random((nc, cap, 2)).astype(np.float32) * 4
    npos = rng.random((nc, 9 * cap, 2)).astype(np.float32) * 4
    nw = np.where(rng.random((nc, 9 * cap)) > 0.3,
                  rng.random((nc, 9 * cap)) + 0.5, 0.0).astype(np.float32)
    # lane-major planes, one group per cell; ``block`` lanes of 128 cells
    t = lambda a: np.transpose(a, (2, 1, 0))
    planes = t(np.concatenate([npos, nw[..., None]], axis=2))
    out = t(np.asarray(neighbor_pallas(
        jnp.asarray(t(rows)), jnp.asarray(planes), 1.3, 0.8, 1e-2,
        block_cols=128 * block, interpret=True)))
    ref = grid_near_ref(jnp.asarray(rows), jnp.asarray(npos),
                        jnp.asarray(nw), 1.3, 0.8, 1e-2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n,nc,br,bc", [(256, 128, 128, 128),
                                        (384, 256, 128, 256)])
def test_grid_far_kernel_matches_ref(n, nc, br, bc):
    rng = np.random.default_rng(n)
    pos = rng.random((n, 2)).astype(np.float32) * 10
    cells = np.concatenate(
        [rng.random((nc, 2)).astype(np.float32) * 10,
         (rng.random((nc, 1)) * 20).astype(np.float32)], axis=1)
    out = nbody_pallas(jnp.asarray(pos.T), jnp.asarray(cells.T), 1.1, 0.9,
                       1e-2, block_rows=br, block_cols=bc,
                       interpret=True).T
    ref = grid_far_ref(jnp.asarray(pos), jnp.asarray(cells), 1.1, 0.9, 1e-2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def _rel_err(f_approx, f_exact):
    """Per-vertex error normalized by |f_exact| + mean|f_exact| (avoids
    division blow-up at force-balance points)."""
    dn = np.linalg.norm(np.asarray(f_approx) - np.asarray(f_exact), axis=1)
    en = np.linalg.norm(np.asarray(f_exact), axis=1)
    return dn / (en + en.mean())


def test_grid_repulsion_error_bound_random():
    """Uniform-random positions (the layout-realistic regime): total force
    within 10% of the all-pairs oracle everywhere."""
    rng = np.random.default_rng(3)
    n = 3000
    pos = jnp.asarray(rng.random((n, 2)) * 12, jnp.float32)
    mass = jnp.asarray(rng.random(n) + 0.5, jnp.float32)
    vmask = jnp.asarray(rng.random(n) > 0.1)
    G, cap = choose_grid(n)
    f_g = grid_repulsion(pos, mass, vmask, 1.2, 0.9, 1e-2,
                         grid_dim=G, cell_cap=cap)
    f_e = nbody_repulsion_ref(pos, mass, vmask, 1.2, 0.9, 1e-2)
    rel = _rel_err(f_g, f_e)
    assert rel.max() < 0.10, rel.max()


def test_grid_repulsion_error_bound_cluster():
    """Gaussian clusters overflow cell caps: in-bucket vertices still stay
    within 10% far-field error; overflowed vertices degrade to the softened
    aggregate but remain bounded (never the raw-point-mass blow-up)."""
    from repro.kernels.grid_force.ops import bin_vertices
    rng = np.random.default_rng(5)
    pos_np = np.concatenate([rng.normal(0, 0.8, (800, 2)),
                             rng.normal(7, 0.6, (800, 2)),
                             rng.normal((0, 8), 1.2, (448, 2))])
    n = len(pos_np)
    pos = jnp.asarray(pos_np, jnp.float32)
    mass = jnp.asarray(rng.random(n) + 0.5, jnp.float32)
    vmask = jnp.ones((n,), bool)
    G, cap = choose_grid(n)
    f_g = grid_repulsion(pos, mass, vmask, 1.2, 0.9, 1e-2,
                         grid_dim=G, cell_cap=cap)
    f_e = nbody_repulsion_ref(pos, mass, vmask, 1.2, 0.9, 1e-2)
    rel = _rel_err(f_g, f_e)
    _, _, inb = bin_vertices(pos, vmask, G, cap)
    inb = np.asarray(inb)
    # vertices that made it into their bucket: near field exact except for
    # overflowed neighbors, far field within the flat-BH bound (observed
    # ~0.35 worst-case next to a saturated cell, ~0.02 median)
    assert rel[inb].max() < 0.45, rel[inb].max()
    assert np.median(rel[inb]) < 0.10
    # overflowed vertices: approximate near field, but softening keeps the
    # error the same order as the force scale
    assert rel.max() < 1.0, rel.max()


def test_grid_far_field_component_within_10pct():
    """The acceptance bound proper: the far-field approximation (everything
    outside the 3×3 neighborhood) is within 10% of its exact counterpart,
    even on clustered inputs."""
    from repro.kernels.grid_force.ops import (bin_vertices, _cell_aggregates,
                                              _neighbor_table, _agg_field_9,
                                              _far_all_cells)
    rng = np.random.default_rng(7)
    pos_np = np.concatenate([rng.normal(0, 0.8, (900, 2)),
                             rng.normal(6, 0.5, (900, 2)),
                             rng.random((900, 2)) * 10 - 2])
    n = len(pos_np)
    pos = jnp.asarray(pos_np, jnp.float32)
    mass = jnp.asarray(rng.random(n) + 0.5, jnp.float32)
    vmask = jnp.asarray(rng.random(n) > 0.05)
    C, L, md = 1.2, 0.9, 1e-2
    G, cap = choose_grid(n)
    nc = G * G
    w = jnp.where(vmask, mass, 0.0).astype(jnp.float32)
    cid, _, _ = bin_vertices(pos, vmask, G, cap)
    M, _, mu = _cell_aggregates(pos, w, cid, nc)
    near9 = jnp.asarray(_neighbor_table(G).T)[:, cid]      # [9, n]
    cell_xyw = jnp.concatenate([mu[:nc], M[:nc, None]], axis=1)
    f_far = np.asarray(
        _far_all_cells(pos, cell_xyw, C, L, md, "ref")
        - _agg_field_9(pos.T, mu.T[:, near9], M[near9], C, L, md).T)

    # exact far field: all pairs minus pairs within the 3×3 neighborhood
    cid_np = np.asarray(cid)
    cxy = np.stack([cid_np % G, cid_np // G], axis=1)
    p = np.asarray(pos)
    w_np = np.asarray(w)
    dx = p[:, 0][:, None] - p[:, 0][None, :]
    dy = p[:, 1][:, None] - p[:, 1][None, :]
    d2 = dx * dx + dy * dy + md * md
    inv = C * L * L * w_np[None, :] / d2
    cheb = np.maximum(np.abs(cxy[:, 0][:, None] - cxy[:, 0][None, :]),
                      np.abs(cxy[:, 1][:, None] - cxy[:, 1][None, :]))
    far_pair = (cheb > 1) & (cid_np[:, None] < nc) & (cid_np[None, :] < nc)
    f_far_exact = np.stack([(dx * inv * far_pair).sum(1),
                            (dy * inv * far_pair).sum(1)], axis=1)
    vm = np.asarray(vmask)
    err = np.linalg.norm((f_far - f_far_exact) * vm[:, None], axis=1)
    scale = np.linalg.norm(f_far_exact * vm[:, None], axis=1).mean()
    assert err.max() < 0.10 * scale, (err.max(), scale)


def test_grid_mode_reduces_stress():
    """gila_layout in grid mode lays out a grid graph about as well as
    exact mode (end-to-end integration through core/gila.py)."""
    from repro.graphs import generators as GEN
    from repro.graphs.graph import build_graph
    from repro.graphs.metrics import sampled_stress
    from repro.core import gila
    from repro.kernels import backend
    e, n = GEN.grid(16, 16)
    g = build_graph(e, n)
    pos0 = gila.random_init(g, 6.0, 1)
    G, cap = choose_grid(g.n_pad)
    dummy_i = jnp.zeros((g.n_pad, 1), jnp.int32)
    dummy_m = jnp.zeros((g.n_pad, 1), bool)
    pos1 = gila.gila_layout(g, pos0, dummy_i, dummy_m, mode="grid",
                            iters=200, temp0=2.0, temp_decay=0.98,
                            ideal_len=1.0, rep_const=1.0, backend=backend(),
                            grid_dim=G, cell_cap=cap)
    s0 = sampled_stress(np.asarray(pos0)[:n], e, n)
    s1 = sampled_stress(np.asarray(pos1)[:n], e, n)
    assert np.isfinite(np.asarray(pos1)).all()
    assert s1 < s0 * 0.5, (s0, s1)


def test_make_schedule_selects_grid():
    from repro.core.schedule import make_schedule
    # small level → exact
    s = make_schedule(2, 3, 1000, 3000)
    assert s.mode == "exact" and s.grid_dim == 0
    # mid level → neighbor (the paper's regime)
    s = make_schedule(1, 3, 10_000, 30_000)
    assert s.mode == "neighbor" and s.grid_dim == 0
    # fine level of a big hierarchy → grid, with usable static params
    s = make_schedule(0, 3, 100_000, 400_000)
    assert s.mode == "grid"
    assert s.grid_dim >= 2 and s.cell_cap >= 8
    # thresholds are tunable (centralized engine forces exact everywhere)
    s = make_schedule(0, 3, 100_000, 400_000, exact_threshold=10 ** 9)
    assert s.mode == "exact"
    s = make_schedule(0, 3, 100_000, 400_000, grid_threshold=10 ** 9)
    assert s.mode == "neighbor"


def test_choose_grid_scaling():
    for n in (1, 100, 5_000, 50_000, 1_000_000):
        G, cap = choose_grid(n)
        assert 2 <= G <= 128
        assert 1 <= cap <= max(n, 8)
    G5, _ = choose_grid(50_000)
    G1m, _ = choose_grid(1_000_000)
    assert G1m > G5                   # finer grids for bigger levels


def _centre_block_input(kind):
    """A random input with 5% of vertices masked, or Gaussian clusters that
    overflow their cells' buckets."""
    rng = np.random.default_rng(11)
    if kind == "random":
        pos_np = rng.random((2500, 2)) * 12
    else:
        pos_np = np.concatenate([rng.normal(0, 0.5, (900, 2)),
                                 rng.normal(6, 0.4, (900, 2)),
                                 rng.random((500, 2)) * 10 - 2])
    n = len(pos_np)
    pos = jnp.asarray(pos_np, jnp.float32)
    mass = jnp.asarray(rng.random(n) + 0.5, jnp.float32)
    vmask = jnp.asarray(rng.random(n) > 0.05)
    return pos, mass, vmask


def _parent_grid_repulsion(pos, mass, vmask, C, L, md, *, grid_dim,
                           cell_cap, far_corrections=None):
    """``grid_repulsion`` as it was with two gathers: the rows fetched by
    their own ``xyw_p[:2, bucket[:nc].T]``, apart from the partner planes.
    ``far_corrections`` defaults to the module's."""
    from repro.kernels.grid_force.ops import (
        bin_vertices, cell_aggregates, cell_centers, neighbor_table,
        near_field, far_all_cells)
    if far_corrections is None:
        from repro.kernels.grid_force.ops import far_corrections
    from repro.kernels import backend
    mode = backend()
    n = pos.shape[0]
    G, cap = grid_dim, cell_cap
    nc = G * G
    w = jnp.where(vmask, mass, 0.0).astype(jnp.float32)
    cid, bucket, inb = bin_vertices(pos, vmask, G, cap)
    M_full, S_full, mu_full = cell_aggregates(pos, w, cid, nc)
    w_out = jnp.where(inb, 0.0, w)
    M_out, S_out, _ = cell_aggregates(pos, w_out, cid, nc)
    centers = cell_centers(pos, vmask, G)
    q = jnp.sum((pos - centers[cid]) ** 2, axis=1)
    Q_full = jax.ops.segment_sum(w * q, cid, num_segments=nc + 1)
    Q_out = jax.ops.segment_sum(w_out * q, cid, num_segments=nc + 1)
    table = jnp.asarray(neighbor_table(G))
    xyw_p = jnp.pad(jnp.concatenate([pos.T, w[None]], axis=0),
                    ((0, 0), (0, 1)))
    rows_idx = bucket[:nc]
    nbr_bucket = bucket[table[:nc]].reshape(nc, 9 * cap)
    near = near_field(xyw_p[:2, rows_idx.T], xyw_p[:, nbr_bucket.T],
                      C, L, md, backend=mode)
    f_near = jnp.zeros((n + 1, 2), jnp.float32).at[
        rows_idx.reshape(-1)].set(
        jnp.transpose(near, (2, 1, 0)).reshape(-1, 2))[:n]
    cell_xyw = jnp.concatenate([mu_full[:nc], M_full[:nc, None]], axis=1)
    f_far = far_all_cells(pos, cell_xyw, C, L, md, mode)
    f_far += far_corrections(pos, w_out, cid, inb,
                             M_full, S_full, Q_full, M_out, S_out, Q_out,
                             C, L, md, grid_dim=G, centers=centers)
    return jnp.where(vmask[:, None], f_near + f_far, 0.0)


@pytest.mark.parametrize("kind", ["random", "cluster"])
def test_neighbor_table_centre_is_the_cell(kind):
    from repro.kernels.grid_force.ops import neighbor_table
    pos, _, _ = _centre_block_input(kind)
    G, _ = choose_grid(pos.shape[0])
    np.testing.assert_array_equal(neighbor_table(G)[:G * G, 4],
                                  np.arange(G * G))


@pytest.mark.parametrize("kind", ["random", "cluster"])
def test_partner_centre_block_is_the_rows(kind):
    """Block 4 of the gathered partner planes is the cells' own buckets,
    sentinel slots and overflowing cells included."""
    from repro.kernels.grid_force.ops import bin_vertices, neighbor_table
    pos, mass, vmask = _centre_block_input(kind)
    G, cap = choose_grid(pos.shape[0])
    nc = G * G
    _, bucket, inb = bin_vertices(pos, vmask, G, cap)
    if kind == "cluster":
        assert not np.asarray(inb)[np.asarray(vmask)].all()  # overflow
    w = jnp.where(vmask, mass, 0.0)
    xyw_p = jnp.pad(jnp.concatenate([pos.T, w[None]], axis=0),
                    ((0, 0), (0, 1)))
    nbr_bucket = bucket[jnp.asarray(neighbor_table(G))[:nc]].reshape(
        nc, 9 * cap)
    partners = xyw_p[:, nbr_bucket.T]
    np.testing.assert_array_equal(np.asarray(partners[:2, 4 * cap:5 * cap]),
                                  np.asarray(xyw_p[:2, bucket[:nc].T]))


@pytest.mark.parametrize("backend", ["ref", "interpret"])
@pytest.mark.parametrize("kind", ["random", "cluster"])
def test_grid_repulsion_matches_two_gather_form(kind, backend, monkeypatch):
    """Taking the rows from the partner planes changes no bit of the
    forces, in either CPU backend."""
    monkeypatch.setenv("REPRO_PALLAS", backend)
    pos, mass, vmask = _centre_block_input(kind)
    G, cap = choose_grid(pos.shape[0])
    args = (pos, mass, vmask, 1.2, 0.9, 1e-2)
    got = grid_repulsion(*args, grid_dim=G, cell_cap=cap)
    want = _parent_grid_repulsion(*args, grid_dim=G, cell_cap=cap)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _parent_agg_field_9(pos, mu9, m9, C, L, md, r9=None):
    """``_agg_field_9`` as it was, vertex-major: pos [n, 2], mu9 [n, 9, 2],
    m9 [n, 9] → [n, 2]."""
    dx = pos[:, 0][:, None] - mu9[..., 0]
    dy = pos[:, 1][:, None] - mu9[..., 1]
    d2 = dx * dx + dy * dy + md * md
    if r9 is not None:
        d2 = d2 + r9 * r9
    inv = (C * L * L) * m9 / d2
    return jnp.stack([jnp.sum(dx * inv, axis=1),
                      jnp.sum(dy * inv, axis=1)], axis=1)


def _parent_far_corrections(pos, w_out, cid, inb,
                            M_full, S_full, Q_full, M_out, S_out, Q_out,
                            C, L, md, *, grid_dim, centers):
    """``far_corrections`` as it was: every per-cell quantity gathered on
    its own by ``table[cid]`` into ``[n, 9]`` and ``[n, 9, 2]`` arrays."""
    from repro.kernels.grid_force.ops import _EPS, _rms, neighbor_table
    mu_full = S_full / jnp.maximum(M_full, _EPS)[:, None]
    mu_out = S_out / jnp.maximum(M_out, _EPS)[:, None]
    r_out = _rms(Q_out, M_out, S_out, centers)
    M_in = M_full - M_out
    S_in = S_full - S_out
    mu_in = S_in / jnp.maximum(M_in, _EPS)[:, None]
    r_in = _rms(Q_full - Q_out, M_in, S_in, centers)
    near9 = jnp.asarray(neighbor_table(grid_dim))[cid]       # [n, 9]
    f = -_parent_agg_field_9(pos, mu_full[near9], M_full[near9], C, L, md)
    m9 = M_out[near9]
    mu9 = mu_out[near9]
    m_self = w_out
    m_adj = jnp.maximum(M_out[cid] - m_self, 0.0)
    s_adj = S_out[cid] - m_self[:, None] * pos
    m9 = m9.at[:, 4].set(m_adj)
    mu9 = mu9.at[:, 4].set(s_adj / jnp.maximum(m_adj, _EPS)[:, None])
    f += _parent_agg_field_9(pos, mu9, m9, C, L, md, r9=r_out[near9])
    f_bkt = _parent_agg_field_9(pos, mu_in[near9], M_in[near9], C, L, md,
                                r9=r_in[near9])
    return f + jnp.where(inb, 0.0, 1.0)[:, None] * f_bkt


def _corrections_args(pos, mass, vmask, G, cap):
    """``far_corrections``' arguments as ``grid_repulsion`` builds them."""
    from repro.kernels.grid_force.ops import (bin_vertices, cell_aggregates,
                                              cell_centers)
    nc = G * G
    w = jnp.where(vmask, mass, 0.0).astype(jnp.float32)
    cid, _, inb = bin_vertices(pos, vmask, G, cap)
    M_full, S_full, _ = cell_aggregates(pos, w, cid, nc)
    w_out = jnp.where(inb, 0.0, w)
    M_out, S_out, _ = cell_aggregates(pos, w_out, cid, nc)
    centers = cell_centers(pos, vmask, G)
    q = jnp.sum((pos - centers[cid]) ** 2, axis=1)
    Q_full = jax.ops.segment_sum(w * q, cid, num_segments=nc + 1)
    Q_out = jax.ops.segment_sum(w_out * q, cid, num_segments=nc + 1)
    return ((pos, w_out, cid, inb, M_full, S_full, Q_full, M_out, S_out,
             Q_out, 1.2, 0.9, 1e-2), centers, inb)


@pytest.mark.parametrize("backend", ["ref", "interpret"])
@pytest.mark.parametrize("kind", ["random", "cluster", "overflow"])
def test_far_corrections_match_the_vertex_major_form(kind, backend,
                                                     monkeypatch):
    """The lane-major corrections (every per-cell quantity gathered into
    ``[13, 9, n]`` at once) against the vertex-major ``[n, 9]`` gathers,
    alone and inside the whole op; ``overflow`` bins the clusters at cell
    cap 8, so most vertices overflow their buckets."""
    from repro.kernels.grid_force.ops import far_corrections
    monkeypatch.setenv("REPRO_PALLAS", backend)
    pos, mass, vmask = _centre_block_input(
        "random" if kind == "random" else "cluster")
    G, cap = choose_grid(pos.shape[0])
    if kind == "overflow":
        cap = 8
    args, centers, inb = _corrections_args(pos, mass, vmask, G, cap)
    if kind != "random":
        assert (~np.asarray(inb)).sum() > 300
    kw = dict(grid_dim=G, centers=centers)
    got = np.asarray(jax.jit(lambda *a: far_corrections(*a, **kw))(*args))
    want = np.asarray(
        jax.jit(lambda *a: _parent_far_corrections(*a, **kw))(*args))
    # the same gathered values in the same float32 operations; only the
    # order of each 9-term sum may differ, so the two agree to rounding
    # of the largest correction
    atol = 1e-6 * np.abs(want).max()
    np.testing.assert_allclose(got, want, rtol=0, atol=atol)
    f_args = (pos, mass, vmask, 1.2, 0.9, 1e-2)
    f_got = grid_repulsion(*f_args, grid_dim=G, cell_cap=cap)
    f_want = _parent_grid_repulsion(*f_args, grid_dim=G, cell_cap=cap,
                                    far_corrections=_parent_far_corrections)
    np.testing.assert_allclose(np.asarray(f_got), np.asarray(f_want),
                               rtol=0, atol=atol)
