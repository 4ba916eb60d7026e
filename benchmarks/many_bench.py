"""Batched multi-graph layout benchmark: one device program lays out B graphs.

The multi-tenant serving scenario (DESIGN.md §9): B concurrent users each
submit a (small) graph and expect a finished drawing. This bench measures
the batched driver ``multigila_layout_many`` against the sequential
single-graph driver on a same-bucket B-graph suite, warm cache both ways:

  * ``sequential`` — one ``multigila_layout`` call per graph (the PR-4
    bucketed driver, warm compile cache);
  * ``batched``    — ONE ``multigila_layout_many`` call for the whole
    suite: per-level refinements grouped by shape bucket, one vmapped
    device program per level wave, lanes re-padded to the finer batch
    buckets (graphs/packing.py).

Both passes run on FRESH graphs (``seed_shift``) against caches warmed by
a preceding warm-up suite — the steady-state serving scenario. The two
DETERMINISTIC acceptance properties are asserted (CI fails on
regression): ``bit_identical`` per-graph results vs the sequential pass
and ``new_compiles == 0`` during the measured batched pass. ``speedup``
is recorded, not asserted — it depends on machine load (bar: ≥ 3× on the
16-graph suite; measured 5.3×, EXPERIMENTS.md §Many).

    PYTHONPATH=src python -m benchmarks.many_bench [--smoke] \
        [--out BENCH_many.json]

Writes the JSON trajectory file that CI uploads as an artifact;
EXPERIMENTS.md §Many records the measured numbers.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


def suite(kind: str, seed_shift: int = 0):
    """B same-bucket graphs: one generator family and one size, so every
    level of every hierarchy lands in a warm shape bucket (the per-seed
    wobble of coarse-level sizes stays inside one pow2 bucket)."""
    from repro.graphs import generators as G

    if kind == "smoke":
        count, nn = 6, 100
    else:
        count, nn = 16, 120
    return [(f"delaunay_{nn}_{i}", *G.delaunay(nn, seed_shift + 10 + i))
            for i in range(count)]


def run(kind: str = "full") -> dict:
    import jax

    from repro.core import (LayoutConfig, multigila_layout,
                            multigila_layout_many, bucketing)

    cfg = LayoutConfig(seed=3)
    warm = suite(kind)
    graphs = suite(kind, seed_shift=1000)
    B = len(graphs)
    res = dict(bench="many", suite=kind, backend=jax.default_backend(),
               n_graphs=B,
               total_vertices=int(sum(n for _, _, n in graphs)),
               total_edges=int(sum(len(e) for _, e, _ in graphs)))

    print(f"[many] warm-up pass ({B} graphs, batched + sequential)...",
          flush=True)
    t0 = time.perf_counter()
    multigila_layout_many([(e, n) for _, e, n in warm], cfg)
    for _, e, n in warm:
        multigila_layout(e, n, cfg)
    res["warmup_seconds"] = round(time.perf_counter() - t0, 3)

    print(f"[many] sequential pass ({B} fresh same-bucket graphs)...",
          flush=True)
    bucketing.PHASES.reset()
    t0 = time.perf_counter()
    seq = [multigila_layout(e, n, cfg) for _, e, n in graphs]
    t_seq = time.perf_counter() - t0
    res["sequential"] = dict(
        seconds=round(t_seq, 3), graphs_per_sec=round(B / t_seq, 3),
        phases={k: round(v, 4) for k, v in
                bucketing.PHASES.snapshot().items()})

    print("[many] batched pass (one multi-graph driver call)...", flush=True)
    bucketing.PHASES.reset()
    stats0 = bucketing.cache_stats()
    t0 = time.perf_counter()
    out = multigila_layout_many([(e, n) for _, e, n in graphs], cfg)
    t_bat = time.perf_counter() - t0
    stats1 = bucketing.cache_stats()
    res["batched"] = dict(
        seconds=round(t_bat, 3), graphs_per_sec=round(B / t_bat, 3),
        phases={k: round(v, 4) for k, v in
                bucketing.PHASES.snapshot().items()},
        new_compiles=stats1["misses"] - stats0["misses"],
        jit_entries_added=stats1["jit_entries"] - stats0["jit_entries"])

    res["bit_identical"] = bool(all(
        np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
        for a, b in zip(seq, out)))
    res["speedup"] = round(t_seq / t_bat, 2)
    # deterministic acceptance properties — fail loudly (CI runs --smoke)
    assert res["bit_identical"], \
        "batched results diverged from the sequential driver"
    assert res["batched"]["new_compiles"] == 0, \
        f"warm batched pass compiled {res['batched']['new_compiles']} steps"
    print(f"[many] sequential {res['sequential']['graphs_per_sec']} g/s, "
          f"batched {res['batched']['graphs_per_sec']} g/s → "
          f"{res['speedup']}x (bar ≥3x on the 16-graph suite), "
          f"bit_identical={res['bit_identical']}, "
          f"warm compiles={res['batched']['new_compiles']}", flush=True)
    from repro.obs import metrics as obs_metrics
    res["metrics"] = obs_metrics.REGISTRY.snapshot()
    return res


def csv_rows(res: dict):
    return [
        ("many_sequential_total", res["sequential"]["seconds"] * 1e6,
         f"{res['sequential']['graphs_per_sec']}_graphs_per_sec"),
        ("many_batched_total", res["batched"]["seconds"] * 1e6,
         f"{res['batched']['graphs_per_sec']}_graphs_per_sec"),
        ("many_speedup", 0.0,
         f"{res['speedup']}x_bit_identical={res['bit_identical']}"
         f"_compiles={res['batched']['new_compiles']}"),
    ]


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized: 6 graphs, still writes the JSON")
    ap.add_argument("--out", default="BENCH_many.json")
    args = ap.parse_args(argv)
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    res = run("smoke" if args.smoke else "full")
    res["date"] = time.strftime("%Y-%m-%d")
    with open(args.out, "w") as f:
        json.dump(res, f, indent=2)
    print(f"[many] wrote {args.out}")
    return res


if __name__ == "__main__":
    main()
