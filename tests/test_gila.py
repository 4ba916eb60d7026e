import numpy as np
import pytest
import jax.numpy as jnp

from repro.graphs import generators as G, build_graph
from repro.core import gila
from repro.core.schedule import make_schedule
from repro.kernels import backend as kernel_backend


def test_paper_k_schedule():
    # exactly the paper's §3.4 table
    assert gila.paper_k_schedule(999) == 6
    assert gila.paper_k_schedule(1_000) == 5
    assert gila.paper_k_schedule(4_999) == 5
    assert gila.paper_k_schedule(5_000) == 4
    assert gila.paper_k_schedule(9_999) == 4
    assert gila.paper_k_schedule(10_000) == 3
    assert gila.paper_k_schedule(99_999) == 3
    assert gila.paper_k_schedule(100_000) == 2
    assert gila.paper_k_schedule(999_999) == 2
    assert gila.paper_k_schedule(1_000_000) == 1


def test_khop_neighbors_match_bfs():
    import networkx as nx
    e, n = G.gnp(60, 3.0, 7)
    nxg = nx.Graph(e.tolist())
    idx, mask = gila.khop_neighbors(e, n, k=2, cap=n)
    for v in range(n):
        if v not in nxg:
            continue
        expect = {u for u, d in
                  nx.single_source_shortest_path_length(nxg, v, 2).items()
                  if 0 < d <= 2}
        got = set(idx[v][mask[v]].tolist())
        assert got == expect, (v, got, expect)


def test_khop_cap_respected():
    e, n = G.scale_free(300, 4, 0)
    idx, mask = gila.khop_neighbors(e, n, k=3, cap=16)
    assert mask.sum(axis=1).max() <= 16


def _khop_reference(edges, n, k):
    """Straightforward per-vertex BFS ball (no caps) — the content oracle
    for the vectorized builder."""
    adj = [set() for _ in range(n)]
    for a, b in edges:
        adj[a].add(int(b))
        adj[b].add(int(a))
    balls = []
    for v in range(n):
        seen, frontier = {v}, {v}
        for _ in range(k):
            frontier = set().union(*(adj[u] for u in frontier)) - seen \
                if frontier else set()
            seen |= frontier
        balls.append(seen - {v})
    return balls


@pytest.mark.parametrize("k", [1, 2, 3])
def test_khop_vectorized_matches_reference_contents(k):
    """Parity-shaped regression for the vectorized (CSR-sliced) builder:
    with cap ≥ the ball size, list CONTENTS equal the BFS k-hop ball
    exactly — the old per-vertex-Python-loop semantics."""
    e, n = G.gnp(70, 3.0, 9)
    idx, mask = gila.khop_neighbors(e, n, k=k, cap=n)
    balls = _khop_reference(e, n, k)
    for v in range(n):
        assert set(idx[v][mask[v]].tolist()) == balls[v], v


def test_khop_sampled_lists_are_valid_and_deterministic():
    """Under the cap, lists are a deterministic-in-seed subset of the true
    k-hop ball, and hop-1 neighbors fill before anything else when they
    fit (the expansion only tops up remaining room)."""
    e, n = G.scale_free(250, 3, 1)
    cap = 24
    i1, m1 = gila.khop_neighbors(e, n, k=3, cap=cap, seed=7)
    i2, m2 = gila.khop_neighbors(e, n, k=3, cap=cap, seed=7)
    assert np.array_equal(i1, i2) and np.array_equal(m1, m2)
    balls = _khop_reference(e, n, 3)
    hop1 = _khop_reference(e, n, 1)
    for v in range(n):
        got = set(i1[v][m1[v]].tolist())
        assert got <= balls[v]
        assert len(got) == min(cap, len(got))
        if len(hop1[v]) <= cap:
            assert hop1[v] <= got, v      # direct neighbors never sampled out
    assert m1.sum(axis=1).max() <= cap


def test_exact_vs_neighbor_forces_agree_on_full_lists():
    """With cap ≥ n and k ≥ diameter, neighbor mode equals exact mode
    (minus the self term, which is zero anyway)."""
    e, n = G.grid(6, 6)
    g = build_graph(e, n, n_pad=64)
    idx, mask = gila.khop_neighbors(e, n, k=12, cap=n)
    nbr_idx, nbr_mask = gila.pad_neighbors(idx, mask, g.n_pad)
    pos = gila.random_init(g, 3.0, 0)
    params = jnp.asarray([1.0, 1.0, 1e-3], jnp.float32)
    f_exact = gila.gila_forces(g, pos, nbr_idx, nbr_mask, params, mode="exact")
    f_nbr = gila.gila_forces(g, pos, nbr_idx, nbr_mask, params, mode="neighbor")
    np.testing.assert_allclose(np.asarray(f_exact), np.asarray(f_nbr),
                               rtol=1e-4, atol=1e-4)


def test_layout_reduces_stress():
    from repro.graphs.metrics import sampled_stress
    e, n = G.grid(10, 10)
    g = build_graph(e, n)
    pos0 = gila.random_init(g, 5.0, 3)
    sched = make_schedule(0, 1, g.n, g.m)
    pos1 = gila.gila_layout(g, pos0, jnp.zeros((g.n_pad, 1), jnp.int32),
                            jnp.zeros((g.n_pad, 1), bool), mode="exact",
                            iters=200, temp0=2.0, temp_decay=0.98,
                            ideal_len=1.0, rep_const=1.0,
                            backend=kernel_backend())
    s0 = sampled_stress(np.asarray(pos0)[:n], e, n)
    s1 = sampled_stress(np.asarray(pos1)[:n], e, n)
    assert s1 < s0 * 0.5, (s0, s1)


def test_gila_layout_traces_per_kernel_backend(monkeypatch):
    """Switching ``REPRO_PALLAS`` mid-process gives ``gila_layout`` a trace
    of its own: the interpret-mode trace holds the Pallas kernel, the
    ref-mode one does not, and a key naming another backend than the
    kernels would use is refused."""
    import jax
    e, n = G.grid(8, 8)
    g = build_graph(e, n)
    pos0 = gila.random_init(g, 4.0, 0)
    dummy = (jnp.zeros((g.n_pad, 1), jnp.int32),
             jnp.zeros((g.n_pad, 1), bool))
    kw = dict(mode="exact", iters=5, temp0=1.0, temp_decay=0.9,
              ideal_len=1.0, rep_const=1.0)
    texts, outs = {}, {}
    for b in ("ref", "interpret"):
        monkeypatch.setenv("REPRO_PALLAS", b)
        run = lambda p: gila.gila_layout(g, p, *dummy, backend=b, **kw)
        texts[b] = str(jax.make_jaxpr(run)(pos0))
        outs[b] = np.asarray(run(pos0))
    assert "pallas_call" in texts["interpret"]
    assert "pallas_call" not in texts["ref"]
    np.testing.assert_allclose(outs["interpret"], outs["ref"], atol=1e-4)
    with pytest.raises(ValueError, match="backend"):
        gila.gila_layout(g, pos0, *dummy, backend="pallas", **kw)
