"""End-to-end large-graph run: the paper's headline scenario, measured.

The paper's Table 2 reports wall clock for complete multilevel layouts of
real-world graphs up to ~10M edges in about an hour on inexpensive cloud
hardware (Amazon EC2, Giraph). This bench reproduces the *shape* of that
experiment at whatever size the host can hold: generate the largest graph
the tier allows, round-trip it through the chunked edge-list loader
(``graphs/io.py`` — the ingest path a real dataset takes, exercising the
streaming parser), then run the full bucketed multilevel pipeline and
record per-phase wall clock (coarsen / place / refine / compile) from
``core.bucketing.PHASES`` plus the device-merger round counters.

    PYTHONPATH=src python -m benchmarks.bigrun_bench [--smoke|--small]
        [--out BENCH_bigrun.json]

``--smoke`` is the CI size (a few seconds); ``--small`` (grid_400x400,
~320k edges) is the tier recorded in EXPERIMENTS.md §Bigrun; the default
("full") is a ~2M-edge grid for hosts with a longer time budget.
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import numpy as np

# the paper's reference point for this scenario (Table 2, com-Youtube /
# soc-Pokec class runs): ~10M edges in ~60 minutes end-to-end on a small
# Giraph cluster of commodity cloud machines
PAPER_REFERENCE = {
    "source": "arXiv:1608.08522 Table 2",
    "edges": 10_000_000,
    "minutes_end_to_end": 60.0,
}


def make_graph(kind: str):
    """(name, edges, n): regular grids — deterministic, any size, and the
    worst case for coarsening depth (diameter O(sqrt n))."""
    from repro.graphs import generators as G
    side = {"smoke": 80, "small": 400}.get(kind, 1000)
    return f"grid_{side}x{side}", *G.grid(side, side)


def run(kind: str = "full") -> dict:
    import jax

    from repro.core import LayoutConfig, bucketing, multigila_layout
    from repro.graphs import io as gio
    from repro.obs import metrics as obs_metrics

    name, edges, n = make_graph(kind)
    res = dict(bench="bigrun", suite=kind, graph=name,
               backend=jax.default_backend(),
               n=int(n), m=int(len(edges)),
               paper_reference=PAPER_REFERENCE)

    # ingest through the chunked streaming loader, as a real dataset would
    fd, path = tempfile.mkstemp(suffix=".txt")
    os.close(fd)
    try:
        gio.save_edgelist(path, edges)
        t0 = time.perf_counter()
        edges, n_loaded = gio.load_edgelist(path)
        res["load_seconds"] = round(time.perf_counter() - t0, 4)
        res["load_bytes"] = os.path.getsize(path)
    finally:
        os.unlink(path)
    assert n_loaded == n, (n_loaded, n)
    print(f"[bigrun] {name}: n={n:,} m={len(edges):,} "
          f"(loaded {res['load_bytes'] / 1e6:.1f} MB in "
          f"{res['load_seconds']:.2f}s)", flush=True)

    bucketing.PHASES.reset()
    def _rounds():
        snap = obs_metrics.REGISTRY.snapshot()
        vals = snap.get("gila_merger_rounds_total", {}).get("values", {})
        return sum(vals.values())

    rounds0 = _rounds()
    t0 = time.perf_counter()
    pos, stats = multigila_layout(edges, n, LayoutConfig(seed=0,
                                                         bucketing=True))
    total = time.perf_counter() - t0
    assert pos.shape == (n, 2) and np.isfinite(pos).all()

    phases = {k: round(v, 4) for k, v in bucketing.PHASES.snapshot().items()}
    # one-time XLA compiles (cold cache) vs the repeatable compute; a warm
    # serving process — or any second run of the same shape buckets — pays
    # only the latter, so both rates are recorded
    compute = max(total - phases.get("compile", 0.0), 1e-9)
    res.update(
        seconds=round(total, 4),
        phases=phases,
        compute_seconds=round(compute, 4),
        levels=int(stats.levels),
        level_sizes=[[int(x) for x in s] if np.ndim(s) else int(s)
                     for s in stats.level_sizes],
        merger_rounds=int(_rounds() - rounds0),
        edges_per_second=round(len(edges) / total, 1),
        edges_per_second_warm=round(len(edges) / compute, 1),
        # scale ratio vs the paper's run: wall-clock per edge, ours / theirs
        paper_minutes_at_this_rate=round(
            PAPER_REFERENCE["edges"] / max(len(edges) / total, 1e-9) / 60, 1),
        paper_minutes_at_warm_rate=round(
            PAPER_REFERENCE["edges"] / (len(edges) / compute) / 60, 1),
    )
    print(f"[bigrun] layout {total:.1f}s over {stats.levels} levels "
          f"({res['merger_rounds']} merger rounds) — phases {res['phases']}",
          flush=True)
    print(f"[bigrun] {res['edges_per_second']:,.0f} edges/s cold "
          f"({res['edges_per_second_warm']:,.0f} warm, compiles excluded) → "
          f"a 10M-edge run ≈ {res['paper_minutes_at_this_rate']} min cold / "
          f"{res['paper_minutes_at_warm_rate']} min warm "
          f"(paper: ~60 min on a Giraph cluster)", flush=True)
    return res


def csv_rows(res: dict):
    return [(f"bigrun_{res['graph']}_total", res["seconds"] * 1e6,
             f"levels={res['levels']}")]


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized graph, still writes the JSON")
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--out", default="BENCH_bigrun.json")
    args = ap.parse_args(argv)
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    kind = "smoke" if args.smoke else ("small" if args.small else "full")
    res = run(kind)
    res["date"] = time.strftime("%Y-%m-%d")
    with open(args.out, "w") as f:
        json.dump(res, f, indent=2)
    print(f"[bigrun] wrote {args.out}")
    return res


if __name__ == "__main__":
    main()
