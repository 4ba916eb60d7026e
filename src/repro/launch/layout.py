"""End-to-end Multi-GiLA driver (the paper's pipeline).

    PYTHONPATH=src python -m repro.launch.layout --graph grid --args 40 40 \
        --engine multigila --svg /tmp/grid.svg

Runs pruning → coarsening → placement/refinement → reinsertion, reports the
paper's quality metrics (CRE, NELD) + timing, optionally writes an SVG.

``--many B`` instead lays out B seed-varied requests of the graph through
the batched multi-graph driver (``multigila_layout_many``) — one vmapped
device program per level wave — and reports graphs/sec;
``--many-compare`` additionally runs the sequential single-graph driver
over the same requests and checks per-graph bit-identity (DESIGN.md §9,
benchmarks/many_bench.py for the measured suite).

``--trace out.json`` records a Chrome/Perfetto span timeline of the run
(coarsen/place/refine per level — per lane under ``--many``; open in
https://ui.perfetto.dev, DESIGN.md §12).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np

from repro.graphs import generators
from repro.graphs.metrics import quality_report
from repro.graphs.graph import build_graph
from repro.graphs.io import save_svg
from repro.core import multigila_layout, multigila_layout_many, LayoutConfig
from repro.utils.compile_cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="grid",
                    help="generator name from repro.graphs.generators")
    ap.add_argument("--args", nargs="*", type=float, default=[20, 20])
    ap.add_argument("--engine", default="multigila",
                    choices=["multigila", "multigila_dist", "centralized",
                             "flat", "gila", "stress"],
                    help="refinement engine (gila | stress); the driver "
                         "names stay accepted for back-compat and select "
                         "--driver instead (LayoutConfig shim)")
    ap.add_argument("--driver", default=None,
                    choices=["multigila", "multigila_dist", "centralized",
                             "flat"],
                    help="hierarchy driver (default multigila)")
    ap.add_argument("--mesh", default="",
                    help="multigila_dist mesh as DATAxMODEL, e.g. 4x2 "
                         "(default: one mesh over all local devices)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--svg", default="")
    ap.add_argument("--no-cre", action="store_true")
    ap.add_argument("--many", type=int, default=0, metavar="B",
                    help="lay out B seed-varied requests through the "
                         "batched multi-graph driver")
    ap.add_argument("--many-compare", action="store_true",
                    help="with --many: also run the sequential driver and "
                         "check per-graph bit-identity")
    ap.add_argument("--trace", default="", metavar="OUT.json",
                    help="write a Chrome/Perfetto trace of the run")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.trace:
        from repro.obs import trace as obs_trace
        obs_trace.enable()

    edges, n, gargs = generators.from_cli(args.graph, args.args)
    print(f"graph {args.graph}{gargs}: n={n} m={len(edges)}")

    mesh_shape = (tuple(int(s) for s in args.mesh.split("x"))
                  if args.mesh else None)
    cfg = LayoutConfig(engine=args.engine, seed=args.seed,
                       mesh_shape=mesh_shape)
    if args.driver is not None:
        cfg = dataclasses.replace(cfg, driver=args.driver)

    if args.many > 0:
        B = args.many
        seeds = [args.seed + i for i in range(B)]
        reqs = [(edges, n)] * B
        t0 = time.perf_counter()
        outs = multigila_layout_many(reqs, cfg, seeds=seeds)
        dt = time.perf_counter() - t0
        print(f"batched: {B} layouts in {dt:.2f}s = {B / dt:.2f} graphs/s "
              f"(levels={outs[0][1].levels})")
        if args.many_compare:
            t0 = time.perf_counter()
            seq = [multigila_layout(e, nn,
                                    dataclasses.replace(cfg, seed=s))
                   for (e, nn), s in zip(reqs, seeds)]
            ds = time.perf_counter() - t0
            same = all(np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
                       for a, b in zip(seq, outs))
            print(f"sequential: {ds:.2f}s = {B / ds:.2f} graphs/s → "
                  f"batched speedup {ds / dt:.2f}x, bit-identical={same}")
        pos, stats = outs[0]
    else:
        t0 = time.perf_counter()
        pos, stats = multigila_layout(edges, n, cfg)
        dt = time.perf_counter() - t0
        print(f"levels={stats.levels} sizes={stats.level_sizes} time={dt:.2f}s")

    g = build_graph(edges, n)
    rep = quality_report(g, np.pad(pos, ((0, g.n_pad - n), (0, 0))),
                         max_cre_edges=0 if args.no_cre else 40000)
    print(f"CRE={rep['cre']:.3f} NELD={rep['neld']:.3f} "
          f"stress={rep['stress']:.4f}")
    if args.svg:
        save_svg(args.svg, pos, edges)
        print(f"wrote {args.svg}")
    if args.trace:
        from repro.obs import trace as obs_trace
        obs_trace.export(args.trace)
        print(f"wrote trace to {args.trace} "
              f"({len(obs_trace.get_tracer())} events)")
    return rep


if __name__ == "__main__":
    main()
