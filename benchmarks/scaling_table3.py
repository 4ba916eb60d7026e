"""Paper Table 3 / Fig. 3: strong scalability of the layout engine.

Two views (this container has ONE physical core, so wall-clock cannot show
multi-worker speedup directly):

  1. *BSP cost model* — per-worker work/communication of one GiLA superstep
     for worker counts p ∈ {4, 8, 16, 32} from the SPMD-lowered program
     (the quantity the paper's Fig. 3 tracks: max per-worker load/superstep).
     Derived in a subprocess with p virtual devices via the roofline parser.
     Emitted for both repulsion regimes of a big hierarchy: mode="neighbor"
     (the paper's k-hop supersteps) and mode="grid" (the grid-bucketed
     approximation the schedule selects above 32768 vertices — the finest
     levels, where the mesh matters most).

  2. *Wall-clock vs graph size* — layout time on RealGraphs-class stand-ins
     of growing m on the single device (the paper's Table 3 row direction:
     time grows ~linearly in m thanks to the k(m) schedule). Sizes above
     the 32768-vertex grid threshold exercise mode="grid" on their finest
     levels.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np

from repro.graphs import generators as G
from repro.core import multigila_layout, LayoutConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def bsp_cost_model(ps=(4, 8, 16, 32), modes=("neighbor", "grid")):
    rows = []
    for p in ps:
        for mode in modes:
            code = f"""
            import os
            os.environ["XLA_FLAGS"] = \\
                "--xla_force_host_platform_device_count={p}"
            import json, jax
            from repro.core.distributed import (layout_train_step,
                                                layout_step_specs)
            from repro.kernels.grid_force.ops import choose_grid
            from repro.launch.roofline import analyze_text
            from repro.launch.mesh import make_mesh
            mesh = make_mesh(({p // 2}, 2), ("data", "model"))
            n_pad, m_pad, cap = 1 << 18, 1 << 20, 32
            G, cc = choose_grid(n_pad) if "{mode}" == "grid" else (0, 0)
            step, sh = layout_train_step(mesh, n_pad, m_pad, cap,
                                         mode="{mode}", grid_dim=G,
                                         cell_cap=cc)
            specs = layout_step_specs(n_pad, m_pad, cap, mode="{mode}")
            lowered = jax.jit(step, in_shardings=(
                sh["pos"], sh["w"], sh["nbr_idx"], sh["edge"], sh["edge"],
                sh["edge"], sh["edge"], sh["scalar"], sh["scalar"])).lower(
                specs["pos"], specs["w"], specs["nbr_idx"], specs["src"],
                specs["dst_local"], specs["emask"], specs["ewt"],
                specs["params"], specs["temp"])
            comp = lowered.compile()
            cost = analyze_text(comp.as_text(), world={p})
            print(json.dumps(dict(p={p}, mode="{mode}", flops=cost.flops,
                                  bytes=cost.bytes, coll=cost.coll_bytes)))
            """
            # the child compiles for virtual CPU devices only: keep it off
            # the chip, which this process may hold
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       PYTHONPATH=os.path.join(REPO, "src"))
            out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                                 capture_output=True, text=True, env=env,
                                 timeout=600)
            assert out.returncode == 0, out.stderr[-2000:]
            rows.append(json.loads(out.stdout.strip().splitlines()[-1]))
            r = rows[-1]
            print(f"  table3-model p={r['p']:3d} mode={r['mode']:9s} "
                  f"flops/worker={r['flops']:.3e} "
                  f"bytes/worker={r['bytes']:.3e} coll/worker={r['coll']:.3e}",
                  flush=True)
    return rows


def wallclock_scaling(small: bool = False):
    sizes = [(2_000, 3), (8_000, 3), (40_000, 3)] if small else \
            [(5_000, 3), (20_000, 3), (60_000, 3), (150_000, 3)]
    cfg = LayoutConfig(seed=1)
    rows = []
    for n, m_attach in sizes:
        edges, nn = G.scale_free(n, m_attach, seed=5)
        t0 = time.perf_counter()
        pos, stats = multigila_layout(edges, nn, cfg)
        dt = time.perf_counter() - t0
        # the finest level's repulsion mode, from the size actually laid
        # out (post-pruning), mirroring make_schedule's selection
        n0 = stats.level_sizes[0][0] if stats.level_sizes else nn
        finest = ("exact" if n0 <= cfg.exact_threshold else
                  "neighbor" if n0 <= cfg.grid_threshold else "grid")
        rows.append({"n": nn, "m": len(edges), "t": dt,
                     "levels": stats.levels, "finest_mode": finest})
        print(f"  table3-time n={nn:7d} m={len(edges):8d} "
              f"levels={stats.levels} finest={finest} t={dt:7.1f}s",
              flush=True)
    return rows


def run(small: bool = False):
    model = bsp_cost_model((4, 8, 16) if small else (4, 8, 16, 32))
    wall = wallclock_scaling(small)
    return {"model": model, "wall": wall}


def csv_rows(res):
    out = []
    for r in res["model"]:
        out.append((f"table3_bsp_{r['mode']}_p{r['p']}", 0.0,
                    f"flops={r['flops']:.3e};coll={r['coll']:.3e}"))
    for r in res["wall"]:
        out.append((f"table3_wall_m{r['m']}", r["t"] * 1e6,
                    f"levels={r['levels']};finest={r['finest_mode']}"))
    return out
