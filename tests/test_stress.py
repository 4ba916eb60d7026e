"""The maxent-stress refinement engine (core/stress.py) through every layer.

Contracts (DESIGN.md §14):
  * PADDING INVARIANCE — a vertex's stress update does not depend on the
    padding bucket its level landed in;
  * DETERMINISM — same seed → bit-identical positions, across runs and
    across the sequential/batched drivers;
  * ENGINE SEAM — mixed-engine batches group by engine and stay
    bit-identical to dedicated runs; warm passes of either engine compile
    zero new programs (the engine id is a cache-key component, never a
    cache invalidator);
  * WEIGHTS — edge weights parsed by ``load_edgelist`` survive pruning and
    scale the stress target lengths ℓ_e = w_e·L.
"""
import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from repro.graphs import generators as G, build_graph
from repro.graphs.io import load_edgelist
from repro.core import (LayoutConfig, multigila_layout,
                        multigila_layout_many, bucketing, gila, stress)
from repro.core.engine import get_engine
from repro.kernels import backend as kernel_backend
from repro.core.pruning import prune_degree_one
from repro.utils.transfer import io_boundary, no_implicit_transfers


@pytest.fixture(autouse=True)
def _no_implicit_transfers():
    """Hot-path tests run under jax.transfer_guard("disallow"); see the
    twin fixture in tests/test_bucketing.py."""
    with no_implicit_transfers():
        yield


# -- the engine registry seam --------------------------------------------------

def test_engine_registry():
    assert get_engine("gila").name == "gila"
    assert get_engine("stress").name == "stress"   # lazily imported
    assert get_engine("stress").sched_k == 4
    with pytest.raises(ValueError, match="unknown refinement engine"):
        get_engine("nope")


def test_layoutconfig_driver_engine_shim():
    """Back-compat: the old ``engine=<driver>`` spelling selects the driver
    and leaves the refinement engine at gila."""
    cfg = LayoutConfig(engine="flat")
    assert (cfg.driver, cfg.engine) == ("flat", "gila")
    cfg = LayoutConfig(engine="stress")
    assert (cfg.driver, cfg.engine) == ("multigila", "stress")
    # dataclasses.replace re-runs the shim harmlessly
    cfg2 = dataclasses.replace(cfg, seed=9)
    assert (cfg2.driver, cfg2.engine) == ("multigila", "stress")


# -- padding invariance --------------------------------------------------------

def test_stress_layout_padding_invariant():
    """Vertex v's maxent-stress trajectory does not depend on the padding
    bucket (ρ = 0 keeps padding pinned; masked edges carry zero weight)."""
    e, n = G.delaunay(700, 3)
    g1 = build_graph(e, n, n_pad=1024, m_pad=8192)
    g2 = build_graph(e, n, n_pad=2048, m_pad=16384)
    kw = dict(mode="exact", iters=20, temp0=3.0, temp_decay=0.96,
              alpha0=0.05, alpha_decay=0.9, ideal_len=1.0, rep_const=1.0,
              backend=kernel_backend())
    with io_boundary():                 # test-side staging (dummies, scalars)
        p1 = stress.stress_layout(g1, gila.random_init(g1, 5.0, 3),
                                  jnp.zeros((g1.n_pad, 1), jnp.int32),
                                  jnp.zeros((g1.n_pad, 1), bool), **kw)
        p2 = stress.stress_layout(g2, gila.random_init(g2, 5.0, 3),
                                  jnp.zeros((g2.n_pad, 1), jnp.int32),
                                  jnp.zeros((g2.n_pad, 1), bool), **kw)
    np.testing.assert_allclose(np.asarray(p1)[:n], np.asarray(p2)[:n],
                               atol=1e-5)
    # padding rows stay pinned at the origin
    assert not np.asarray(p1)[n:].any()


# -- determinism + batched parity ----------------------------------------------

def test_stress_per_seed_determinism():
    e, n = G.tri_mesh(9, 9)
    cfg = LayoutConfig(seed=4, engine="stress")
    a, sa = multigila_layout(e, n, cfg)
    b, sb = multigila_layout(e, n, cfg)
    assert sa.levels == sb.levels
    assert np.array_equal(np.asarray(a), np.asarray(b))
    c, _ = multigila_layout(e, n, dataclasses.replace(cfg, seed=5))
    assert not np.array_equal(np.asarray(a), np.asarray(c))


def _assert_parity(graphs, cfg, seeds=None, engines=None):
    outs = multigila_layout_many(graphs, cfg, seeds=seeds, engines=engines)
    for i, (e, n) in enumerate(graphs):
        scfg = cfg
        if seeds is not None:
            scfg = dataclasses.replace(scfg, seed=int(seeds[i]))
        if engines is not None:
            scfg = dataclasses.replace(scfg, engine=engines[i])
        ps, ss = multigila_layout(e, n, scfg)
        pb, sb = outs[i]
        assert sb.levels == ss.levels
        assert np.array_equal(np.asarray(pb), np.asarray(ps)), f"graph {i}"
    return outs


def test_stress_batched_bit_identical_to_sequential():
    gs = [G.delaunay(150, 30 + i) for i in range(3)]
    _assert_parity(gs, LayoutConfig(seed=5, engine="stress"))


def test_stress_batched_mixed_buckets():
    gs = [G.delaunay(120, 3), G.delaunay(500, 4), G.grid(14, 14)]
    _assert_parity(gs, LayoutConfig(seed=2, engine="stress"),
                   seeds=[7, 8, 9])


@pytest.mark.parametrize("kw", [dict(exact_threshold=64),
                                dict(exact_threshold=64, grid_threshold=96)],
                         ids=["neighbor-mode", "grid-mode"])
def test_stress_batched_neighbor_and_grid_modes(kw):
    gs = [G.delaunay(150, 50 + i) for i in range(2)]
    _assert_parity(gs, LayoutConfig(seed=4, engine="stress", **kw))


def test_mixed_engine_wave_grouping():
    """One batch, both engines: lanes group by engine inside the wave loop
    (group_key leads with the engine id) and every lane stays bit-identical
    to its dedicated-engine sequential run."""
    gs = [G.delaunay(150, 60 + i) for i in range(4)]
    engines = ["gila", "stress", "gila", "stress"]
    _assert_parity(gs, LayoutConfig(seed=3), engines=engines)


def test_service_engine_override():
    """The continuous-batching service's per-request engine override:
    validated at the submit boundary (unknown ids bounce, they never reach
    the worker), and each request stays bit-identical to its dedicated
    sequential run even when the wave mixes engines."""
    from repro.serve.engine import ContinuousLayoutService
    e, n = G.delaunay(80, 2)
    ref_s, _ = multigila_layout(e, n, LayoutConfig(seed=0, engine="stress"))
    ref_g, _ = multigila_layout(e, n, LayoutConfig(seed=0))
    svc = ContinuousLayoutService(LayoutConfig(seed=0), max_lanes=4)
    try:
        with pytest.raises(ValueError, match="unknown refinement engine"):
            svc.submit(e, n, engine="nope")
        rs = svc.submit(e, n, engine="stress")
        rg = svc.submit(e, n)
        pos_s, _ = rs.result(300)
        pos_g, _ = rg.result(300)
    finally:
        svc.close()
    assert np.array_equal(np.asarray(pos_s), np.asarray(ref_s))
    assert np.array_equal(np.asarray(pos_g), np.asarray(ref_g))


# -- warm path: engine id widens the key, never invalidates it -----------------

def test_warm_cross_engine_zero_new_compiles():
    """After one pass of EACH engine over a bucket family, fresh same-bucket
    graphs under either engine trigger zero new compiles — the stress
    programs are cached beside the GiLA ones, not over them."""
    multigila_layout(*G.delaunay(3000, 5), LayoutConfig(seed=5))
    multigila_layout(*G.delaunay(3000, 6),
                     LayoutConfig(seed=5, engine="stress"))
    before = bucketing.cache_stats()
    assert before["jit_entries"] > 0, "jit cache probe broken"
    multigila_layout(*G.delaunay(3000, 7), LayoutConfig(seed=6))
    mid = bucketing.cache_stats()
    assert mid["misses"] == before["misses"], (before, mid)
    assert mid["jit_entries"] == before["jit_entries"], (before, mid)
    multigila_layout(*G.delaunay(3000, 8),
                     LayoutConfig(seed=6, engine="stress"))
    after = bucketing.cache_stats()
    assert after["misses"] == before["misses"], (before, after)
    assert after["jit_entries"] == before["jit_entries"], (before, after)
    assert after["hits"] > mid["hits"] > before["hits"]


# -- weighted graphs -----------------------------------------------------------

def test_load_edgelist_weights(tmp_path):
    p = tmp_path / "w.txt"
    p.write_text("# comment\n0 1 2.5\n1 2\n2 3 0.5\n")
    e, n = load_edgelist(str(p))                       # 2-tuple unchanged
    assert e.shape == (3, 2) and n == 4
    e, n, w = load_edgelist(str(p), weights=True)
    assert np.array_equal(e, [[0, 1], [1, 2], [2, 3]])
    np.testing.assert_allclose(w, [2.5, 1.0, 0.5])     # missing → 1.0
    assert w.dtype == np.float32

    m = tmp_path / "w.mtx"
    m.write_text("%%MatrixMarket matrix coordinate real general\n"
                 "3 3 2\n1 2 4.0\n2 3 0.25\n")
    e, n, w = load_edgelist(str(m), weights=True)
    assert np.array_equal(e, [[0, 1], [1, 2]]) and n == 3
    np.testing.assert_allclose(w, [4.0, 0.25])


def test_prune_preserves_weights():
    # triangle 0-1-2 with a leaf 3 on vertex 1; the leaf edge's weight is
    # dropped with the leaf, the surviving weights stay aligned
    edges = np.array([[0, 1], [1, 2], [2, 0], [1, 3]])
    w = np.array([2.0, 0.5, 1.5, 9.0], np.float32)
    pr = prune_degree_one(edges, 4, weights=w)
    assert pr.n == 3 and len(pr.edges) == 3
    np.testing.assert_allclose(pr.ewt, [2.0, 0.5, 1.5])
    assert prune_degree_one(edges, 4).ewt is None


def test_weighted_layout_scales_target_lengths():
    """ℓ_e = w_e·L: on a weighted path, the heavy edge draws ~w× longer
    than the unit edge under the stress engine."""
    edges, n = G.grid(10, 10)
    rng = np.random.default_rng(0)
    w = rng.uniform(0.5, 2.0, len(edges)).astype(np.float32)
    cfg = LayoutConfig(seed=1, engine="stress")
    pu, _ = multigila_layout(edges, n, cfg)
    pw, _ = multigila_layout(edges, n, cfg, weights=w)
    assert not np.array_equal(pu, pw), "weights must reach the layout"
    lens = np.linalg.norm(pw[edges[:, 0]] - pw[edges[:, 1]], axis=1)
    # weighted correlation: long-target edges draw longer
    r = np.corrcoef(w, lens)[0, 1]
    assert r > 0.5, f"edge lengths do not track weights (r={r:.2f})"


def test_weighted_layout_batched_parity():
    edges, n = G.grid(10, 10)
    rng = np.random.default_rng(1)
    w = rng.uniform(0.5, 2.0, len(edges)).astype(np.float32)
    cfg = LayoutConfig(seed=2, engine="stress")
    outs = multigila_layout_many([(edges, n)] * 2, cfg, seeds=[4, 5],
                                 weights=[w, None])
    pw, _ = multigila_layout(edges, n, dataclasses.replace(cfg, seed=4),
                             weights=w)
    pu, _ = multigila_layout(edges, n, dataclasses.replace(cfg, seed=5))
    assert np.array_equal(np.asarray(outs[0][0]), np.asarray(pw))
    assert np.array_equal(np.asarray(outs[1][0]), np.asarray(pu))
