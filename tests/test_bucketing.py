"""Shape-bucketed compilation of the multilevel driver (core/bucketing.py).

Three contracts:
  * PARITY — the bucketed driver (cached dynamic-iteration steps, donated
    buffers, normalized static fields, per-vertex RNG) is behavior-
    preserving vs. the exact-shape legacy path;
  * WARM PATH — a fresh graph whose levels land in already-compiled shape
    buckets triggers ZERO new compiles (via jit cache stats);
  * PADDING INVARIANCE — re-padding the same graph to a different bucket
    changes nothing for real vertices: same initial positions, same
    forces, same merger decisions.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.graphs import generators as G, build_graph
from repro.graphs.graph import bucket_pad
from repro.core import (multigila_layout, LayoutConfig, build_hierarchy,
                        run_merger, gila, bucketing)
from repro.utils.transfer import io_boundary, no_implicit_transfers


@pytest.fixture(autouse=True)
def _no_implicit_transfers():
    """The whole hot path runs under jax.transfer_guard("disallow"): every
    intentional host<->device hop in the drivers is wrapped in
    utils/transfer.io_boundary(); any bare transfer is a bug this guard
    (and gilalint R3) exists to catch."""
    with no_implicit_transfers():
        yield


PARITY_GRAPHS = [
    # n ≤ 512 keeps n_pad identical between round-256 and pow2 padding, so
    # parity is exact; the bucket-padding degree of freedom is covered
    # separately by the padding-invariance tests below (full-pipeline float
    # parity across DIFFERENT reduction shapes is not a meaningful contract
    # — ulp-level reduction-order differences amplify over hundreds of
    # chaotic force iterations).
    ("grid_20_20", *G.grid(20, 20)),
    ("delaunay_450", *G.delaunay(450, 3)),
    ("scale_free_480", *G.scale_free(480, 2, 4)),
]


@pytest.mark.parametrize("name,e,n", PARITY_GRAPHS,
                         ids=[g[0] for g in PARITY_GRAPHS])
def test_parity_bucketed_vs_exact_shape(name, e, n):
    """Golden parity: positions within 1e-5 (observed: bit-identical) and
    identical hierarchy level counts."""
    pb, sb = multigila_layout(e, n, LayoutConfig(seed=7, bucketing=True))
    pe, se = multigila_layout(e, n, LayoutConfig(seed=7, bucketing=False))
    assert sb.levels == se.levels
    np.testing.assert_allclose(pb, pe, atol=1e-5)


@pytest.mark.parametrize("kw", [dict(exact_threshold=128),
                                dict(grid_threshold=256)],
                         ids=["neighbor-mode", "grid-mode"])
def test_parity_covers_neighbor_and_grid_steps(kw):
    """The cached neighbor-mode and grid-mode refine steps are also
    behavior-preserving (thresholds forced down so a 400-vertex graph
    exercises them)."""
    e, n = G.grid(20, 20)
    pb, sb = multigila_layout(e, n, LayoutConfig(seed=7, bucketing=True, **kw))
    pe, se = multigila_layout(e, n, LayoutConfig(seed=7, bucketing=False, **kw))
    assert sb.levels == se.levels
    np.testing.assert_allclose(pb, pe, atol=1e-5)


def test_warm_path_zero_new_compiles():
    """Acceptance: a fresh same-bucket graph reuses every compiled program
    — no new step-cache misses AND no new jit trace entries anywhere in
    the driver (merger, placer, refine)."""
    e1, n1 = G.delaunay(3000, 5)
    multigila_layout(e1, n1, LayoutConfig(seed=5))
    before = bucketing.cache_stats()
    # guard against a vacuous pass: if the private jit cache-size probe
    # ever disappears from this JAX version, fail loudly instead of
    # comparing 0 == 0
    assert before["jit_entries"] > 0, "jit cache probe broken"
    # fresh graph, same generator sizes → same pow2 buckets at every level
    e2, n2 = G.delaunay(3000, 9)
    pos, st = multigila_layout(e2, n2, LayoutConfig(seed=6))
    after = bucketing.cache_stats()
    assert pos.shape == (n2, 2) and st.levels >= 2
    assert after["misses"] == before["misses"], (before, after)
    assert after["jit_entries"] == before["jit_entries"], (before, after)
    assert after["hits"] > before["hits"]


def test_padding_invariance_of_init_forces_and_merger():
    """Vertex v's random draws, forces, and merger fate do not depend on
    the padding bucket (the property that makes bucketing safe at all)."""
    e, n = G.delaunay(700, 3)
    g1 = build_graph(e, n, n_pad=1024, m_pad=8192)
    g2 = build_graph(e, n, n_pad=2048, m_pad=16384)

    pos1 = gila.random_init(g1, 5.0, 3)
    pos2 = gila.random_init(g2, 5.0, 3)
    np.testing.assert_allclose(np.asarray(pos1)[:n], np.asarray(pos2)[:n],
                               atol=1e-6)

    with io_boundary():                 # test-side staging
        params = jnp.asarray([1.0, 1.0, 1e-3], jnp.float32)
        dummy = (jnp.zeros((g1.n_pad, 1), jnp.int32),
                 jnp.zeros((g1.n_pad, 1), bool))
        dummy2 = (jnp.zeros((g2.n_pad, 1), jnp.int32),
                  jnp.zeros((g2.n_pad, 1), bool))
    forces = jax.jit(gila.gila_forces, static_argnames=("mode",))
    f1 = forces(g1, pos1, *dummy, params, mode="exact")
    f2 = forces(g2, pos2, *dummy2, params, mode="exact")
    np.testing.assert_allclose(np.asarray(f1)[:n], np.asarray(f2)[:n],
                               atol=1e-5)

    st1 = run_merger(g1, seed=1)
    st2 = run_merger(g2, seed=1)
    for field in ("state", "sun", "depth", "parent"):
        a = np.asarray(getattr(st1, field))[:n]
        b = np.asarray(getattr(st2, field))[:n]
        assert (a == b).all(), field


def test_export_reports_true_n_not_bucket_padding():
    """The serve export path must see true vertex counts: bucket padding is
    an implementation detail of the compiled steps, never of the data
    contract."""
    e, n = G.delaunay(700, 3)          # 700 → bucket 1024: n ≠ n_pad
    pos, st, exp = multigila_layout(e, n, LayoutConfig(seed=2), export=True)
    assert pos.shape == (n, 2)
    assert exp.levels[0].n == n
    assert exp.pos.shape == (n, 2)
    sizes = [lvl.n for lvl in exp.levels]
    for lvl in exp.levels:
        assert lvl.rep.shape == (lvl.n,)
        if lvl.parent is not None:
            assert lvl.parent.shape == (lvl.n,)
        if len(lvl.edges):
            assert lvl.edges.max() < lvl.n
    # level sizes strictly decrease (true sizes, not padded buckets)
    assert all(sizes[i + 1] < sizes[i] for i in range(len(sizes) - 1))


def test_bucket_pad():
    assert bucket_pad(1) == 256
    assert bucket_pad(256) == 256
    assert bucket_pad(257) == 512
    assert bucket_pad(600) == 1024
    assert bucket_pad(1024) == 1024
    assert bucket_pad(3, minimum=8) == 8
    assert bucket_pad(9, minimum=8) == 16


def test_build_hierarchy_invariant_no_shrink():
    """Degenerate case: a graph that cannot shrink (edgeless — every vertex
    becomes its own sun). The final merger's coarse graph AND info are
    discarded together; the graphs/infos length invariant holds."""
    g0 = build_graph(np.zeros((0, 2), np.int64), 100)
    graphs, infos = build_hierarchy(g0, LayoutConfig())
    assert len(graphs) == len(infos) + 1
    assert len(graphs) == 1 and graphs[0] is g0


def test_build_hierarchy_invariant_normal():
    e, n = G.grid(16, 16)
    graphs, infos = build_hierarchy(build_graph(e, n, bucket=True),
                                    LayoutConfig())
    assert len(graphs) == len(infos) + 1
    assert len(graphs) >= 2
    # bucketed levels carry pow2 padded shapes
    for g in graphs:
        assert g.n_pad == bucket_pad(g.n_pad)
