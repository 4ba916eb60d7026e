"""Continuous-batching layout service under Poisson load.

Compares the two layout front doors on one mixed-size multi-tenant
workload (small delaunay "minnows" with a periodic 420-vertex "whale" —
the shape mix that makes window batching hurt):

- fixed-window baseline: ``serve.layout_service.LayoutService`` — the
  deadline-window collector. Every request in a window resolves when the
  WHOLE batch finishes (convoy), and while a batch runs nothing else
  does, so a minnow stuck behind a whale inherits the whale's latency.
- continuous: ``serve.engine.ContinuousLayoutService`` — requests join
  the wave scheduler mid-flight between level waves and complete the
  moment their own lanes finish.

Headline metric — matched-p99 rate doubling. For rate pairs ``(r, 2r)``
the continuous engine is offered TWICE the arrival rate and must still
deliver a p99 latency no worse than the fixed window's at ``r``: that is
"≥2x the graphs/sec at equal p99 latency", checked per pair and recorded
in BENCH_service.json.

Two modes:

- ``--smoke`` (the CI gate): deterministic virtual-clock simulation.
  Both systems are replayed on the SAME scripted Poisson traces under
  the same per-group wave cost model (serve/engine.py:default_wave_cost)
  — the continuous engine through ``run_sim`` on an ``EngineCore`` with
  ``null_dispatch``, the baseline through ``simulate_fixed_window``
  below, which reproduces the ``_BatcherCore`` window semantics
  event-by-event. No wall clock anywhere: the run is bit-stable (the
  continuous engine's scheduling log is asserted identical across two
  replays) and the 2x property is checked on model time.
- full (default): real threaded measurement against the live services —
  warm-up covering every (shape, lane-bucket) the trace can reach, then
  open-loop Poisson load at each rate, p50/p99 stamped by Future
  callbacks, and a zero-warm-compile assertion over the whole measured
  region (core/bucketing.py:cache_stats).

    PYTHONPATH=src python benchmarks/service_bench.py [--smoke]
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core import LayoutConfig, bucketing, multigila_layout_many
from repro.core.multilevel import WaveScheduler
from repro.graphs import generators as G
from repro.serve.engine import (EngineCore, VirtualClock, default_wave_cost,
                                null_dispatch, poisson_trace, run_sim,
                                WAVE_COST_BASE_S, WAVE_COST_PER_LANE_S)

WHALE_EVERY = 6                     # every 6th request is a 420-vertex graph
MINNOW_SIZES = (90, 120)
WHALE_SIZE = 420
RATE_PAIRS = ((3, 6), (6, 12))      # (fixed rate, continuous rate) in graphs/s
CONT_MAX_LANES = 16                 # admission cap: bounds wave weight so
                                    # whales can't make every wave heavy


def make_workload(count: int, seed0: int = 2000) -> list:
    """The mixed-size request stream: graph i of every trace."""
    out = []
    for i in range(count):
        size = (WHALE_SIZE if i % WHALE_EVERY == 3
                else MINNOW_SIZES[i % len(MINNOW_SIZES)])
        out.append(G.delaunay(size, seed0 + i))
    return out


def warm(cfg: LayoutConfig, graphs: list) -> None:
    """Compile every (shape, lane-bucket) combination the services can
    reach: one pass over the full workload at each reachable lane bucket
    (pow2, floor 8 — graphs/packing.py:lane_bucket)."""
    for b in (8, 16, 32):
        for i in range(0, len(graphs), b):
            multigila_layout_many(graphs[i:i + b], cfg)


# -- deterministic simulation (smoke mode) --------------------------------------

def simulate_fixed_window(events: list, cfg: LayoutConfig, *,
                          max_batch: int = 16, window_s: float = 0.010,
                          wave_cost=None) -> tuple:
    """Replay the fixed-window ``LayoutService`` on virtual time.

    Mirrors ``serve.batcher._BatcherCore``: the serial worker picks up
    the oldest queued request, anchors a ``window_s`` collection window
    there, dispatches early when ``max_batch`` fills, then runs the batch
    TO COMPLETION — every member resolves when the last lane finishes,
    and requests arriving meanwhile wait for the next pickup. Batch
    durations come from draining a real ``WaveScheduler`` (real
    coarsening, real level waves, ``null_dispatch``) under ``wave_cost``,
    so both simulated systems are costed by the same model.

    Returns ``(latencies, schedule)`` with latencies in trace order.
    """
    cost = wave_cost or default_wave_cost
    subs = sorted((e for e in events if e.kind == "submit"),
                  key=lambda e: e.t)
    lats, t_free, i = [], 0.0, 0
    waves = groups = batches = 0
    while i < len(subs):
        t_pick = max(t_free, subs[i].t)
        t_close = t_pick + window_s
        j, t_start = i, t_close
        while (j < len(subs) and j - i < max_batch
               and subs[j].t <= t_close + 1e-12):
            j += 1
            if j - i == max_batch:     # early dispatch: window cut short
                t_start = max(t_pick, subs[j - 1].t)
        batch = subs[i:j]
        sched = WaveScheduler(cfg, dispatch=null_dispatch)
        for ev in batch:
            sched.admit(ev.edges, ev.n, seed=ev.seed)
        dur = 0.0
        while True:
            s = sched.step()
            if not s["lanes"]:
                break
            dur += cost(s)
            waves += 1
            groups += len(s["groups"])
        t_done = t_start + dur
        lats.extend(t_done - ev.t for ev in batch)
        t_free, i = t_done, j
        batches += 1
    return lats, dict(batches=batches, waves=waves, groups=groups)


def simulate_continuous(events: list, cfg: LayoutConfig, *,
                        max_lanes: int = CONT_MAX_LANES,
                        wave_cost=None) -> tuple:
    """Replay the continuous engine on virtual time; returns
    ``(latencies, core)`` — latencies for completed requests in trace
    order, the core for its log/counters."""
    core = EngineCore(cfg, clock=VirtualClock(), max_queue=4 * max_lanes,
                      max_lanes=max_lanes, dispatch=null_dispatch)
    handles = run_sim(core, events, wave_cost=wave_cost)
    lats = [h.latency for h in handles
            if h is not None and h.status == "done"]
    return lats, core


def _pcts(lats: list) -> dict:
    a = np.asarray(lats, dtype=float)
    return dict(count=int(a.size),
                p50_ms=round(float(np.percentile(a, 50)) * 1e3, 1),
                p99_ms=round(float(np.percentile(a, 99)) * 1e3, 1))


def run_sim_mode(count: int = 60) -> dict:
    """Virtual-clock comparison: deterministic, wall-clock-free."""
    cfg = LayoutConfig(seed=0)
    graphs = make_workload(count)
    mk = lambda i, rng: graphs[i % len(graphs)]
    pairs = []
    for r_fixed, r_cont in RATE_PAIRS:
        # same trace seed: the two traces are the same unit-exponential
        # draws scaled by 1/rate, so the comparison is paired, not noisy
        tr_f = poisson_trace(r_fixed, count, mk, seed=17)
        tr_c = poisson_trace(r_cont, count, mk, seed=17)
        lat_f, sched_f = simulate_fixed_window(tr_f, cfg)
        lat_c, core = simulate_continuous(tr_c, cfg)
        lat_c2, core2 = simulate_continuous(tr_c, cfg)
        assert core.log == core2.log, \
            "continuous sim replay produced a different scheduling log"
        assert len(lat_c) == count, \
            f"sim dropped requests: {len(lat_c)}/{count} completed"
        f, c = _pcts(lat_f), _pcts(lat_c)
        pairs.append(dict(
            rate_fixed=r_fixed, rate_cont=r_cont, fixed=f, cont=c,
            fixed_schedule=sched_f,
            cont_waves=core.counters["waves"],
            pass_2x=bool(c["p99_ms"] <= f["p99_ms"])))
        print(f"[service/sim] fixed@{r_fixed}: p99={f['p99_ms']}ms  "
              f"cont@{r_cont}: p99={c['p99_ms']}ms  "
              f"2x_at_equal_p99={'PASS' if pairs[-1]['pass_2x'] else 'FAIL'}",
              flush=True)
    assert all(p["pass_2x"] for p in pairs), \
        "continuous batching failed the matched-p99 rate doubling in sim"
    return dict(deterministic=True, pairs=pairs,
                model=dict(base_s=WAVE_COST_BASE_S,
                           per_lane_s=WAVE_COST_PER_LANE_S))


# -- real threaded measurement (full mode) --------------------------------------

def drive(submit, graphs: list, rate_hz: float, seed: int,
          timeout: float = 600.0) -> list:
    """Open-loop Poisson load against a live service: submit each graph at
    its scripted arrival time, stamp completion latency from a Future
    done-callback (NOT after-the-fact — early completions must be stamped
    when they happen), return per-request latencies."""
    rng = np.random.RandomState(seed)
    gaps = rng.exponential(1.0 / rate_hz, size=len(graphs))
    lats: list = [None] * len(graphs)
    futs = []
    t_next = time.perf_counter()
    for i, (e, n) in enumerate(graphs):
        t_next += gaps[i]
        dt = t_next - time.perf_counter()
        if dt > 0:
            time.sleep(dt)
        t0 = time.perf_counter()
        f = submit(e, n)
        f.add_done_callback(
            lambda _f, i=i, t0=t0:
                lats.__setitem__(i, time.perf_counter() - t0))
        futs.append(f)
    for f in futs:
        f.result(timeout)
    return lats


def run_real_mode(count: int = 120, seeds=(17, 41)) -> dict:
    """Measure the live services; asserts zero warm compiles and the
    matched-p99 doubling on at least one rate pair (wall-clock runs are
    noisy; the deterministic gate is the sim)."""
    from repro.serve import LayoutService
    from repro.serve.engine import ContinuousLayoutService

    cfg = LayoutConfig(seed=0)
    graphs = make_workload(count)
    print(f"[service] warming {len(graphs)} graphs x lane buckets 8/16/32 "
          "...", flush=True)
    warm(cfg, graphs)
    st0 = bucketing.cache_stats()

    pairs = []
    for r_fixed, r_cont in RATE_PAIRS:
        lat_f, lat_c = [], []
        for seed in seeds:
            svc = LayoutService(cfg)
            lat_f += drive(svc.submit, graphs, r_fixed, seed)
            svc.close()
            svc2 = ContinuousLayoutService(cfg, max_lanes=CONT_MAX_LANES)
            lat_c += drive(lambda e, n: svc2.submit(e, n).future,
                           graphs, r_cont, seed)
            svc2.close()
        f, c = _pcts(lat_f), _pcts(lat_c)
        pairs.append(dict(rate_fixed=r_fixed, rate_cont=r_cont,
                          fixed=f, cont=c,
                          pass_2x=bool(c["p99_ms"] <= f["p99_ms"])))
        print(f"[service] fixed@{r_fixed}: p50={f['p50_ms']}ms "
              f"p99={f['p99_ms']}ms   cont@{r_cont}: p50={c['p50_ms']}ms "
              f"p99={c['p99_ms']}ms   "
              f"2x_at_equal_p99={'PASS' if pairs[-1]['pass_2x'] else 'FAIL'}",
              flush=True)
    st1 = bucketing.cache_stats()
    compiles = st1["misses"] - st0["misses"]
    assert compiles == 0, f"measured region compiled {compiles} steps"
    assert any(p["pass_2x"] for p in pairs), \
        "no rate pair sustained 2x graphs/sec at equal p99"
    return dict(pairs=pairs, warm_compiles=compiles,
                seeds=list(seeds), cont_max_lanes=CONT_MAX_LANES)


def run(mode: str = "full") -> dict:
    res = dict(
        workload=dict(whale_every=WHALE_EVERY, whale_size=WHALE_SIZE,
                      minnow_sizes=list(MINNOW_SIZES)),
        rate_pairs=[list(p) for p in RATE_PAIRS],
        sim=run_sim_mode())
    if mode == "full":
        res["real"] = run_real_mode()
    from repro.obs import metrics as obs_metrics
    res["metrics"] = obs_metrics.REGISTRY.snapshot()
    return res


def csv_rows(res: dict):
    rows = []
    for scope in ("sim", "real"):
        for p in res.get(scope, {}).get("pairs", ()):
            rows.append((
                f"service_{scope}_fixed_r{p['rate_fixed']}",
                p["fixed"]["p99_ms"] * 1e3, "p99"))
            rows.append((
                f"service_{scope}_cont_r{p['rate_cont']}",
                p["cont"]["p99_ms"] * 1e3,
                f"p99_2x_pass={p['pass_2x']}"))
    return rows


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="deterministic virtual-clock simulation only "
                         "(wall-clock-stable; the CI gate)")
    ap.add_argument("--out", default="BENCH_service.json")
    args = ap.parse_args(argv)
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    res = run("smoke" if args.smoke else "full")
    res["date"] = time.strftime("%Y-%m-%d")
    res["mode"] = "smoke" if args.smoke else "full"
    with open(args.out, "w") as f:
        json.dump(res, f, indent=2)
    print(f"[service] wrote {args.out}")
    return res


if __name__ == "__main__":
    main()
