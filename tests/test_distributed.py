"""Multi-device SPMD tests — run in a subprocess with 8 host devices so the
main pytest process keeps its single-device jax config."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, extra_env: dict | None = None) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.update(extra_env or {})
    # every snippet builds meshes through the version-portable constructor
    prelude = "from repro.launch.mesh import make_mesh\n"
    out = subprocess.run([sys.executable, "-c",
                          prelude + textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_sharded_nbody_matches_reference():
    out = run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import distributed as D
        from repro.graphs import generators as G
        from repro.graphs.graph import build_graph
        from repro.kernels.nbody.ref import nbody_repulsion_ref
        mesh = make_mesh((4,2), ("data","model"))
        n_pad = 256
        e, n = G.grid(12, 12)
        g = build_graph(e, n, n_pad=n_pad)
        pos = np.random.default_rng(0).random((n_pad,2)).astype(np.float32)
        w = np.where(np.asarray(g.vmask), np.asarray(g.mass), 0).astype(np.float32)
        fn = D.sharded_nbody(mesh, n_pad)
        out = fn(jnp.asarray(pos), jnp.asarray(w),
                 jnp.asarray([1.,1.,1e-3], jnp.float32))
        ref = nbody_repulsion_ref(jnp.asarray(pos), g.mass, g.vmask, 1., 1., 1e-3)
        err = float(jnp.abs(jnp.where(g.vmask[:,None], out - ref, 0)).max())
        assert err < 1e-3, err
        print("OK", err)
    """)
    assert "OK" in out


def test_sharded_train_step_matches_single_device():
    """Same params/batch → same loss with and without the mesh (GSPMD is
    numerically faithful for this model at f32)."""
    out = run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.models import init_params, loss_fn
        from repro.models.model import param_specs
        from repro.parallel.sharding import make_rules, use_shardings, param_shardings
        cfg = get_smoke_config("internlm2-1.8b")
        params = init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (4, 64)), jnp.int32),
                 "labels": jnp.asarray(rng.integers(0, cfg.vocab, (4, 64)), jnp.int32)}
        l0, _ = jax.jit(lambda p,b: loss_fn(p, cfg, b))(params, batch)
        mesh = make_mesh((4,2), ("data","model"))
        rules = make_rules(mesh, cfg)
        with use_shardings(mesh, rules):
            sh = param_shardings(mesh, rules, param_specs(cfg, rules))
            psh = jax.tree.map(lambda p, s: jax.device_put(p, s), params, sh)
            l1, _ = jax.jit(lambda p,b: loss_fn(p, cfg, b))(psh, batch)
        d = abs(float(l0) - float(l1))
        assert d < 2e-2, (float(l0), float(l1))
        print("OK", float(l0), float(l1))
    """)
    assert "OK" in out


def test_ring_collective_matmul_matches_allgather():
    out = run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.parallel.collectives import ring_collective_matmul
        mesh = make_mesh((1,8), ("data","model"))
        S, K, N = 64, 32, 48
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(S,K)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(K,N)), jnp.float32)
        fn = jax.jit(ring_collective_matmul(mesh, "model"))
        y = fn(x, w)
        np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                                   rtol=1e-4, atol=1e-4)
        print("OK")
    """)
    assert "OK" in out


def test_spinner_partition_improves_shuffled_cut():
    out = run_sub("""
        import numpy as np
        from repro.core.partition import spinner_partition, edge_cut
        from repro.graphs import generators as G
        from repro.graphs.graph import build_graph
        e, n = G.grid(24, 24)
        perm = np.random.default_rng(0).permutation(n)
        e2 = perm[e]
        g = build_graph(e2, n)
        blocked = np.minimum(np.arange(g.n_pad)*4//max(g.n,1), 3)
        labels = spinner_partition(g, 4, iters=48)
        c0, c1 = edge_cut(g, blocked), edge_cut(g, labels)
        assert c1 < c0 * 0.8, (c0, c1)
        print("OK", c0, c1)
    """)
    assert "OK" in out


def test_spinner_partition_respects_slack_capacity():
    """Regression for the unquotaed-flip overshoot: simultaneous label
    adoptions are now admitted against a per-label migration quota, so the
    max partition load stays ≤ floor(slack · n / P) at every slack tried
    (the docstring's 'balanced within slack' promise, previously false)."""
    import numpy as np
    from repro.core.partition import spinner_partition, edge_cut
    from repro.graphs import generators as G
    from repro.graphs.graph import build_graph

    e, n = G.grid(24, 24)
    perm = np.random.default_rng(0).permutation(n)
    g = build_graph(perm[e], n)
    vm = np.asarray(g.vmask)
    for P, slack, seed in [(4, 1.10, 0), (4, 1.03, 5), (8, 1.05, 2)]:
        labels = np.asarray(spinner_partition(g, P, iters=48, slack=slack,
                                              seed=seed))
        loads = np.bincount(labels[vm], minlength=P)
        cap = np.floor(slack * n / P)
        assert loads.max() <= cap, (P, slack, loads, cap)
    # and the quota must not cost the cut-quality contract
    blocked = np.minimum(np.arange(g.n_pad) * 4 // max(g.n, 1), 3)
    labels = spinner_partition(g, 4, iters=48)
    assert edge_cut(g, labels) < edge_cut(g, blocked) * 0.8


def test_shardmap_moe_matches_gspmd():
    """§Perf hillclimb B: the explicit shard_map MoE is numerically
    identical to the GSPMD-partitioned formulation."""
    out = run_sub("""
        import dataclasses
        import numpy as np, jax, jax.numpy as jnp
        from repro.models import moe as MOE
        from repro.configs.base import MoEConfig
        from repro.parallel.sharding import make_rules, use_shardings
        mesh = make_mesh((2,4), ("data","model"))
        m = MoEConfig(n_experts=8, top_k=2, d_expert=16, capacity_factor=2.0)
        p = MOE.init_moe(jax.random.PRNGKey(0), 32, m)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32), jnp.float32)
        rules = dataclasses.replace(make_rules(mesh, None), experts="model")
        with use_shardings(mesh, rules):
            y1, a1 = jax.jit(lambda p, x: MOE.apply_moe(p, x, m))(p, x)
            y2, a2 = jax.jit(lambda p, x: MOE.apply_moe_shardmap(p, x, m))(p, x)
        err = float(jnp.abs(y1 - y2).max())
        assert err < 1e-4, err
        print("OK", err)
    """)
    assert "OK" in out


def test_a2a_moe_matches_reference():
    """§Perf hillclimb B iteration 3: EP-via-all-to-all MoE is exactly the
    reference MoE (dropless capacity)."""
    out = run_sub("""
        import dataclasses
        import numpy as np, jax, jax.numpy as jnp
        from repro.models import moe as MOE
        from repro.configs.base import MoEConfig
        from repro.parallel.sharding import make_rules, use_shardings
        mesh = make_mesh((2, 4), ("data", "model"))
        m = MoEConfig(n_experts=8, top_k=2, d_expert=16, capacity_factor=4.0)
        p = MOE.init_moe(jax.random.PRNGKey(0), 32, m)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 32, 32), jnp.float32)
        y1, _ = jax.jit(lambda p, x: MOE.apply_moe(p, x, m))(p, x)
        rules = dataclasses.replace(make_rules(mesh, None), experts="model",
                                    batch=("data","model"),
                                    moe_impl="all_to_all")
        with use_shardings(mesh, rules):
            y2, _ = jax.jit(lambda p, x: MOE.apply_moe_a2a(p, x, m))(p, x)
        err = float(jnp.abs(y1 - y2).max())
        assert err < 1e-4, err
        print("OK", err)
    """)
    assert "OK" in out


def test_sharded_grid_force_matches_single_device():
    """Tentpole parity: the sharded grid repulsion (psum'd aggregates +
    all_gathered bucketed positions) matches single-device grid_repulsion
    within 1e-4 relative error — uniform AND cell-overflow inputs."""
    out = run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import distributed as D
        from repro.kernels.grid_force.ops import (grid_repulsion, choose_grid,
                                                  bin_vertices)
        mesh = make_mesh((4, 2), ("data", "model"))
        n_pad = 512
        rng = np.random.default_rng(0)
        params = jnp.asarray([1.2, 0.9, 1e-2], jnp.float32)
        # uniform case (with masked padding), then a tight cluster that
        # overflows its cell's bucket cap
        uni = (rng.random((n_pad, 2)) * 10).astype(np.float32)
        vmask = rng.random(n_pad) > 0.1
        uni = np.where(vmask[:, None], uni, 0.0).astype(np.float32)
        w_uni = np.where(vmask, rng.random(n_pad) + 0.5, 0.0)
        clu = np.concatenate([rng.normal(0, 0.05, (200, 2)),
                              rng.random((n_pad - 200, 2)) * 8])
        w_clu = rng.random(n_pad) + 0.5
        for name, pos, w in (("uniform", uni, w_uni),
                             ("overflow", clu, w_clu)):
            pos = jnp.asarray(pos, jnp.float32)
            w = jnp.asarray(w, jnp.float32)
            G, cap = choose_grid(n_pad)
            if name == "overflow":
                _, _, inb = bin_vertices(pos, w > 0, G, cap)
                assert int((~np.asarray(inb)).sum()) > 50   # caps overflowed
            fn = D.sharded_grid_force(mesh, n_pad, G, cap)
            got = np.asarray(fn(pos, w, params))
            ref = grid_repulsion(pos, w, w > 0, 1.2, 0.9, 1e-2,
                                 grid_dim=G, cell_cap=cap)
            ref = np.asarray(jnp.where((w > 0)[:, None], ref, 0.0))
            rel = np.abs(got - ref).max() / np.abs(ref).max()
            assert rel < 1e-4, (name, rel)
            print("OK", name, rel)
    """)
    assert out.count("OK") == 2


def test_sharded_grid_force_halo_matches_under_band_partition():
    """Halo variant: exchanging only the two boundary-cell bucket rows
    reproduces grid_repulsion when each shard's vertices sit in its grid
    row band — including a bucket-overflow cluster inside one band."""
    out = run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import distributed as D
        from repro.kernels.grid_force.ops import grid_repulsion, bin_vertices
        mesh = make_mesh((4, 2), ("data", "model"))
        n_pad, vsize = 512, 4
        n_loc = n_pad // vsize
        G, cap = 8, 16                       # G % vsize == 0 (band contract)
        rng = np.random.default_rng(1)
        # device d's block lies in grid rows [2d, 2d+2) of a [0,10)² box
        pos = np.zeros((n_pad, 2), np.float32)
        for d in range(vsize):
            ylo, yhi = d * 2.5 + 0.05, (d + 1) * 2.5 - 0.05
            blk = rng.random((n_loc, 2)).astype(np.float32)
            pos[d*n_loc:(d+1)*n_loc, 0] = blk[:, 0] * 10
            pos[d*n_loc:(d+1)*n_loc, 1] = ylo + blk[:, 1] * (yhi - ylo)
        pos[0] = (0.0, 0.0); pos[-1] = (10.0, 10.0)   # pin the bbox
        # overflow: cram 40 > cap vertices of block 1 into one cell
        pos[n_loc:n_loc + 40] = (5.2, 3.1) + \\
            rng.normal(0, 0.02, (40, 2)).astype(np.float32)
        w = (rng.random(n_pad) + 0.5).astype(np.float32)
        params = jnp.asarray([1.2, 0.9, 1e-2], jnp.float32)
        _, _, inb = bin_vertices(jnp.asarray(pos), jnp.ones(n_pad, bool),
                                 G, cap)
        assert int((~np.asarray(inb)).sum()) > 10
        fn = D.sharded_grid_force(mesh, n_pad, G, cap, variant="halo")
        got = np.asarray(fn(jnp.asarray(pos), jnp.asarray(w), params))
        ref = np.asarray(grid_repulsion(jnp.asarray(pos), jnp.asarray(w),
                                        jnp.ones(n_pad, bool), 1.2, 0.9,
                                        1e-2, grid_dim=G, cell_cap=cap))
        rel = np.abs(got - ref).max() / np.abs(ref).max()
        assert rel < 1e-4, rel
        print("OK", rel)
        # band-contract violation degrades gracefully: the violator is
        # reclassified as overflow (softened far-field forces, mass kept
        # for its neighbors), everyone else stays on the single-device op
        pos[5] = (5.0, 9.0)              # stored on shard 0, sits in band 3
        got = np.asarray(fn(jnp.asarray(pos), jnp.asarray(w), params))
        ref = np.asarray(grid_repulsion(jnp.asarray(pos), jnp.asarray(w),
                                        jnp.ones(n_pad, bool), 1.2, 0.9,
                                        1e-2, grid_dim=G, cell_cap=cap))
        assert np.isfinite(got).all()
        assert np.linalg.norm(got[5]) > 0.1 * np.linalg.norm(ref[5])
        others = np.delete(np.arange(n_pad), 5)
        rel = np.abs(got[others] - ref[others]).max() / np.abs(ref).max()
        assert rel < 0.05, rel
        print("OK violation", rel)
    """)
    assert out.count("OK") == 2


def test_layout_grid_step_lowers_and_matches():
    """Acceptance: layout_train_step(mode="grid") lowers under shard_map on
    a 4-vertex-shard mesh and one superstep equals the single-device update
    built from grid_repulsion, within 1e-4 relative error."""
    out = run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.distributed import layout_train_step, layout_step_specs
        from repro.kernels.grid_force.ops import grid_repulsion, choose_grid
        mesh = make_mesh((4, 2), ("data", "model"))
        n_pad, m_pad = 512, 64
        G, cap = choose_grid(n_pad)
        rng = np.random.default_rng(3)
        pos = (rng.random((n_pad, 2)) * 10).astype(np.float32)
        w = (rng.random(n_pad) + 0.5).astype(np.float32)
        nbr = np.full((n_pad, 1), n_pad, np.int32)
        # no edges → the superstep is repulsion + clamped update only
        src = np.full(m_pad, n_pad, np.int32)
        dst_l = np.zeros(m_pad, np.int32)
        emask = np.zeros(m_pad, bool)
        ewt = np.ones(m_pad, np.float32)
        params = jnp.asarray([1.2, 0.9, 1e-2], jnp.float32)
        temp = jnp.asarray(0.5, jnp.float32)
        step, sh = layout_train_step(mesh, n_pad, m_pad, 1, mode="grid",
                                     grid_dim=G, cell_cap=cap)
        specs = layout_step_specs(n_pad, m_pad, 1, mode="grid")
        lowered = jax.jit(step, in_shardings=(
            sh["pos"], sh["w"], sh["nbr_idx"], sh["edge"], sh["edge"],
            sh["edge"], sh["edge"], sh["scalar"], sh["scalar"])).lower(
            specs["pos"], specs["w"], specs["nbr_idx"], specs["src"],
            specs["dst_local"], specs["emask"], specs["ewt"],
            specs["params"], specs["temp"])
        lowered.compile()                    # sharding config is coherent
        got = np.asarray(jax.jit(step)(pos, w, nbr, src, dst_l, emask, ewt,
                                       params, temp))
        f = grid_repulsion(jnp.asarray(pos), jnp.asarray(w),
                           jnp.ones(n_pad, bool), 1.2, 0.9, 1e-2,
                           grid_dim=G, cell_cap=cap)
        norm = jnp.sqrt(jnp.sum(f * f, 1) + 1e-12)
        ref = np.asarray(pos + f / norm[:, None]
                         * jnp.minimum(norm, temp)[:, None])
        rel = np.abs(got - ref).max() / (np.abs(ref).max() + 1e-9)
        assert rel < 1e-4, rel
        print("OK", rel)
    """)
    assert "OK" in out


def test_multigila_dist_engine_end_to_end():
    """engine="multigila_dist": the full multilevel pipeline with every
    level refined by the sharded superstep (exact/neighbor/grid by size)
    produces a finite layout that untangles the graph."""
    out = run_sub("""
        import numpy as np
        from repro.graphs import generators as G
        from repro.graphs.graph import build_graph
        from repro.graphs.metrics import sampled_stress
        from repro.core import multigila_layout, LayoutConfig
        from repro.core.gila import random_init
        edges, n = G.grid(18, 18)
        pos, stats = multigila_layout(edges, n, LayoutConfig(
            seed=0, engine="multigila_dist", mesh_shape=(4, 2)))
        assert np.isfinite(pos).all()
        g = build_graph(edges, n)
        p0 = np.asarray(random_init(g, 6.0, 0))[:n]
        s0, s1 = sampled_stress(p0, edges, n), sampled_stress(pos, edges, n)
        assert s1 < s0 * 0.5, (s0, s1)
        print("OK", stats.levels, s0, s1)
    """, extra_env={"JAX_TRANSFER_GUARD": "disallow"})
    # the guard proves the sharded hot path does no implicit host<->device
    # hops: every intentional one sits in a utils/transfer.io_boundary()
    assert "OK" in out


def test_multigila_dist_stress_engine_end_to_end():
    """driver="multigila_dist" × engine="stress": every level refined by
    the sharded maxent-stress superstep (its extra annealing scalar staged
    per iteration) produces a finite layout that untangles the graph."""
    out = run_sub("""
        import numpy as np
        from repro.graphs import generators as G
        from repro.graphs.graph import build_graph
        from repro.graphs.metrics import sampled_stress
        from repro.core import multigila_layout, LayoutConfig
        from repro.core.gila import random_init
        edges, n = G.grid(18, 18)
        pos, stats = multigila_layout(edges, n, LayoutConfig(
            seed=0, driver="multigila_dist", engine="stress",
            mesh_shape=(4, 2)))
        assert np.isfinite(pos).all()
        g = build_graph(edges, n)
        p0 = np.asarray(random_init(g, 6.0, 0))[:n]
        s0, s1 = sampled_stress(p0, edges, n), sampled_stress(pos, edges, n)
        assert s1 < s0 * 0.5, (s0, s1)
        print("OK", stats.levels, s0, s1)
    """, extra_env={"JAX_TRANSFER_GUARD": "disallow"})
    assert "OK" in out


def test_layout_halo_step_runs():
    """§Perf hillclimb C: halo-exchange superstep compiles and matches the
    all-gather superstep when every neighbor is covered by the halo."""
    out = run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.distributed import (layout_train_step,
                                            layout_train_step_halo)
        mesh = make_mesh((4, 2), ("data", "model"))
        n_pad, cap = 64, 8
        vsize, n_loc = 4, 16
        halo = n_loc                     # full halo → exactly the AG step
        rng = np.random.default_rng(0)
        pos = rng.random((n_pad, 2)).astype(np.float32)
        w = np.ones(n_pad, np.float32)
        params = jnp.asarray([1., 1., 1e-2], jnp.float32)
        temp = jnp.asarray(0.5, jnp.float32)
        # global neighbor list: each vertex talks to 8 random others
        nbr = rng.integers(0, n_pad, (n_pad, cap)).astype(np.int32)
        # no edges (pure repulsion) keeps the remap simple
        m_pad = 8
        src = np.full(m_pad, n_pad, np.int32); dst_l = np.zeros(m_pad, np.int32)
        emask = np.zeros(m_pad, bool); ewt = np.ones(m_pad, np.float32)

        step, sh = layout_train_step(mesh, n_pad, m_pad, cap, mode="neighbor")
        out1 = jax.jit(step)(pos, w, nbr, src, dst_l, emask, ewt, params, temp)

        # halo version: send_idx[d][p] = all local indices (full halo);
        # remap neighbor ids: owner o, local l → if o == self: l
        # else n_loc + recv_slot(o, l) with recv layout [peer, halo]
        send_idx = np.tile(np.arange(n_loc, dtype=np.int32), (vsize*vsize, 1))
        nbr_local = np.zeros_like(nbr)
        for v in range(n_pad):
            me = v // n_loc
            for j in range(cap):
                u = nbr[v, j]; o, l = u // n_loc, u % n_loc
                nbr_local[v, j] = l if o == me else n_loc + o * n_loc + l
        step2, sh2 = layout_train_step_halo(mesh, n_pad, m_pad, cap, halo)
        out2 = jax.jit(step2)(pos, w, nbr_local, send_idx, src, dst_l,
                              emask, ewt, params, temp)
        err = float(jnp.abs(out1 - out2).max())
        assert err < 1e-4, err
        print("OK", err)
    """)
    assert "OK" in out


def test_pipeline_parallel_matches_reference():
    """GPipe over the pod axis equals the plain forward, and jax.grad
    differentiates through the pipeline (reverse schedule for free).
    f32 activations: XLA:CPU crashes on bf16 inside partial-manual regions
    (TPU-native bf16 is unaffected) — see parallel/pipeline.py."""
    out = run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.models import init_params, forward
        from repro.parallel.pipeline import pipeline_forward
        from repro.parallel.sharding import make_rules, use_shardings
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = get_smoke_config("internlm2-1.8b")
        params = init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 64)),
                                       jnp.int32)}
        ref, _ = forward(params, cfg, batch)
        rules = make_rules(mesh, cfg)
        with use_shardings(mesh, rules):
            pp = jax.jit(lambda p, b: pipeline_forward(p, cfg, b, mesh,
                                                       n_microbatches=4))
            got = pp(params, batch)
            err = float(jnp.abs(np.asarray(ref, np.float32)
                                - np.asarray(got, np.float32)).max())
            assert err < 0.05, err
            # grads flow through the pipeline (reverse schedule)
            def loss(p):
                lg = pipeline_forward(p, cfg, batch, mesh, n_microbatches=4)
                return jnp.sum(lg.astype(jnp.float32) ** 2) * 1e-6
            g = jax.jit(jax.grad(loss))(params)
            gn = sum(float(jnp.sum(jnp.abs(x.astype(jnp.float32))))
                     for x in jax.tree.leaves(g["groups"]))
            assert gn > 0
        print("OK", err, gn)
    """, extra_env={"REPRO_ACT_DTYPE": "float32"})
    assert "OK" in out


def test_ring_attention_matches_sdpa():
    """Context parallelism: ring attention (seq-sharded, ppermute KV ring,
    streaming softmax) equals the reference SDPA, causal and full, f32+bf16."""
    out = run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.parallel.ring_attention import ring_attention
        from repro.models.layers import _sdpa
        mesh = make_mesh((2, 4), ("data", "model"))
        rng = np.random.default_rng(0)
        B, S, H, KV, hd = 2, 256, 4, 2, 32
        for dtype, tol in ((jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)):
            q = jnp.asarray(rng.normal(size=(B, S, H, hd)), dtype)
            k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), dtype)
            v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), dtype)
            for causal in (True, False):
                fn = jax.jit(ring_attention(mesh, causal=causal))
                got = fn(q, k, v)
                ref = _sdpa(q, k, v, causal=causal)
                err = float(jnp.abs(got.astype(jnp.float32)
                                    - ref.astype(jnp.float32)).max())
                assert err < tol, (dtype, causal, err)
        print("OK")
    """)
    assert "OK" in out


def test_small_mesh_dryrun_decode():
    """decode_step lowers+compiles on an 8-device mesh with sharded caches —
    the fast version of the production dry-run."""
    out = run_sub("""
        import jax, jax.numpy as jnp
        from functools import partial
        from repro.configs import get_smoke_config, SHAPES
        from repro.models import model as M
        from repro.parallel.sharding import make_rules, use_shardings
        cfg = get_smoke_config("gemma-2b")
        mesh = make_mesh((4,2), ("data","model"))
        rules = make_rules(mesh, cfg)
        B, cache = 8, 256
        params_struct = jax.eval_shape(partial(M.init_params, cfg),
                                       jax.random.PRNGKey(0))
        state_struct = jax.eval_shape(partial(M.init_decode_state, cfg, B, cache))
        with use_shardings(mesh, rules):
            def step(params, tok, state, pos):
                return M.decode_step(params, cfg, tok, state, pos)
            lowered = jax.jit(step).lower(
                params_struct,
                jax.ShapeDtypeStruct((B,1), jnp.int32),
                state_struct, jax.ShapeDtypeStruct((), jnp.int32))
            compiled = lowered.compile()
            print("OK", compiled.memory_analysis().temp_size_in_bytes)
    """)
    assert "OK" in out
