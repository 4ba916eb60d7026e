#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result line.

    python3 bench/run.py --workload delaunay_n17.offline --seed 7 \\
        --seconds 30 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``). In one process, which owns the chip,
the run loads the system, makes its graphs from ``--seed``, warms up every
shape the window reaches, then measures for ``--seconds``. With
``--trace 1`` the window is recorded by the profiler and the per-layer
metrics (``bench/metrics/<metric>.py``) are reported in place of the
end-to-end ones. After the window the plain reference
(``bench/lib/reference.py``) judges every answer, and the program's own
spans, recorded in every run, are checked against the stated schedule
(``bench/lib/schedule.py``). The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``, then
``check``, each compared number beside its limit.

Off a TPU, or with fewer chips than the cell asks for, the run exits 2
and prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

CACHE_DIR = ROOT / ".jax_cache"


def process_age() -> float:
    """Seconds since this process started, from the kernel's record of its
    start (clock ticks since boot)."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    return (time.clock_gettime(time.CLOCK_BOOTTIME)
            - ticks / os.sysconf("SC_CLK_TCK"))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout; the
    program's own ``enable_compile_cache`` follows the same variable."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: a size cap set in the environment (the chip machines set
    # JAX_COMPILATION_CACHE_MAX_SIZE) would evict the cell's programs and
    # make every run compile them again
    jax.config.update("jax_compilation_cache_max_size", -1)


@contextlib.contextmanager
def annotate(name: str):
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


def judge(system, plan, items, seed: int, limits: dict) -> tuple:
    """The reference's verdict on every answer: (per item, answered and
    within every limit; the worst reading of each number and graph size;
    answers judged; answers that never came or came as an error)."""
    from bench.lib import graphs, reference
    ok, worst, judged, missing = [], {}, 0, 0
    for it in items:
        ans = it.answer
        if ans is None or not ans.ok:
            ok.append(False)
            missing += 1
            continue
        edges, n = plan.pool[it.graph]
        pos = system.positions(ans)
        good = True
        for name, by_size in limits.items():
            value = reference.NUMBERS[name](
                pos, edges, n, graphs.rng_for(seed, 5, it.index))
            key = f"{name}.n{n}"
            worst[key] = (max(worst.get(key, (value,))[0], value),
                          float(by_size[str(n)]))
            good &= value <= float(by_size[str(n)])
        judged += 1
        ok.append(good)
    return ok, worst, judged, missing


def main(argv=None) -> int:
    args = parse(argv)
    from bench.lib import spec as S
    bench = S.load()
    cell = S.cell(bench, args.workload)
    conf = S.config(bench, cell["config"])
    mix = S.traffic(cell["traffic"])
    wanted = S.metrics_of(bench, cell["name"], bool(args.trace))

    use_compile_cache()
    from bench.lib import chip
    devs = chip.require(int(cell["chips"]))
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the system under test is missing: no "
              f"{ROOT / 'src' / 'repro'}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    from bench.lib import compiles, drive, profile, schedule
    from bench.lib.record import Run
    from repro.core.bucketing import PHASES
    from repro.obs import trace as obs_trace

    clog = compiles.CompileLog()
    plan = drive.plan(conf, mix, args.seed)
    System = S.entry(conf["entry"])
    system = System(conf)

    def reqs(it):
        edges, n = plan.pool[it.graph]
        return system.encode(edges, n, it.seed)

    quiet = lambda it: contextlib.nullcontext()          # noqa: E731
    warm = mix.get("warm", {})
    if plan.replay:     # every request the window sends, at fewer iterations
        warm_sys = System(dict(conf, layout={**conf["layout"],
                                             **warm.get("layout", {})}))
        drive.closed_loop(warm_sys, lambda it: warm_sys.encode(
            *plan.pool[it.graph], it.seed), plan.replay,
            time.perf_counter(), float("inf"), quiet)
        warm_sys.close()

    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    tracing = profile.capture(tmp) if args.trace else contextlib.nullcontext()
    # the program's own spans: the schedule check reads them in every run
    obs_trace.reset()
    obs_trace.enable()
    span = (lambda it: annotate("bench.request")) if args.trace else quiet
    with tracing:
        p0 = PHASES.snapshot()
        mono0 = time.monotonic_ns()
        with annotate(profile.WINDOW_SPAN):
            t0 = time.perf_counter()
            setup_s = process_age()
            items = drive.closed_loop(system, reqs, plan.items, t0,
                                      args.seconds, span)
            tw = time.perf_counter()
        phases = {k: v - p0.get(k, 0.0) for k, v in PHASES.snapshot().items()}
    t1 = max([it.done for it in items] + [t0])
    in_window = clog.between(t0, tw)
    peak = chip.memory_peak_bytes(devs)
    obs_events = obs_trace.get_tracer().to_dict()["traceEvents"]
    obs_trace.disable()
    system.close()

    ok, worst, judged, missing = judge(system, plan, items, args.seed,
                                       conf["limits"])
    answered = sum(it.answer is not None and it.answer.ok for it in items)
    unrefined = schedule.levels_unrefined(obs_events, answered,
                                          conf["layout"])
    run = Run(setup_s=setup_s, t0=t0, t1=t1, items=items, phases=phases,
              compiles=in_window)
    device = dict(chip.describe(devs), memory_peak_bytes=peak)
    breakdown = None
    if args.trace:
        t_read = time.perf_counter()
        prof = profile.read(profile.xplane_file(tmp))
        print(f"bench: trace of {prof['window_s']:.1f} s read in "
              f"{time.perf_counter() - t_read:.1f} s", file=sys.stderr)
        run.profile = prof
        device.update(busy_s=prof["busy_s"], window_s=prof["window_s"])
        offset = prof["window_ns"][0] - mono0
        spans = prof["host"] + [
            (int(e["ts"] * 1e3) + offset,
             int((e["ts"] + e["dur"]) * 1e3) + offset, e["name"])
            for e in obs_events if e.get("ph") == "X"]
        ops = dict(prof["ops"])
        if prof["dropped_s"] > 0:
            # no operation's time can be read there: the stretch stands in
            # the list under its own name
            ops[profile.DROPPED] = prof["dropped_s"]
            print(f"bench: the profiler dropped {prof['dropped_events']} "
                  f"device events over {prof['dropped_s']:.3f} s of the "
                  "window, counted busy", file=sys.stderr)
        breakdown = {
            "device_ops": [[k, v] for k, v in sorted(
                ops.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": profile.name_gaps(prof["idle"], spans),
        }
    shutil.rmtree(tmp, ignore_errors=True)

    metrics = {}
    for m in wanted:
        value = S.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    attempted = len(items)
    failed = sum(not x for x in ok)
    check = {k: {"value": v, "limit": lim}
             for k, (v, lim) in sorted(worst.items())}
    check["levels_unrefined"] = {"value": unrefined, "limit": 0}
    check["unanswered"] = {"value": missing, "limit": 0}
    correct = judged > 0 and all(v["value"] <= v["limit"]
                                 for v in check.values())
    notes = [f"bench: {cell['name']} seed={args.seed} requests={attempted} "
             f"answered_ok={judged} failed={failed} compiles_in_window="
             f"{len(in_window)} memory_peak_bytes={peak} window_s="
             f"{t1 - t0:.6f}"]
    notes += [f"bench: answer {it.index}: {it.answer.note}" for it in items
              if it.answer is not None and it.answer.note]
    for c in in_window:
        notes.append(f"bench: compiled in window: {c[2]} ({c[1]:.3f}s)")
    for line in notes:
        print(line, file=sys.stderr)
    for k, v in check.items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = check
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
