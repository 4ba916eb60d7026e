"""Observability layer tests (DESIGN.md §12): tracer fast path and export
determinism, metrics registry + Prometheus exposition, padding-occupancy
hand checks, the PHASES thread-safety fix, and the engine stats snapshot."""
import json
import threading
import urllib.request

import numpy as np
import pytest

from repro.core import LayoutConfig, bucketing
from repro.core.schedule import make_schedule
from repro.graphs import generators as G
from repro.graphs.graph import build_graph, bucket_pad
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.clock import SystemClock, VirtualClock
from repro.serve.engine import (EngineCore, SimEvent, null_dispatch, run_sim)


# -- tracer basics -------------------------------------------------------------

def test_disabled_tracer_emits_nothing_and_allocates_no_contexts():
    tr = obs_trace.Tracer()
    assert not tr.enabled
    # the fast path returns ONE shared nullcontext — identity, not just
    # equality — so a disabled span costs no allocation
    assert tr.span("a") is tr.span("b", x=1)
    with tr.span("a"):
        pass
    tr.instant("i", x=1)
    tr.counter("c", 3)
    tr.complete("r", 0.0, 1.0)
    assert len(tr) == 0
    # the module-level hooks share the same fast path object
    assert not obs_trace.TRACER.enabled
    assert obs_trace.span("a") is tr.span("b")
    assert tr.root("layout") is obs_trace._NULL
    assert obs_trace.root("layout", n=1) is obs_trace._NULL
    assert tr.complete("r", 0.0, 1.0) is None


def test_span_nesting_and_export_shape():
    vc = VirtualClock()
    tr = obs_trace.Tracer(clock=vc, enabled=True)
    with tr.span("outer", cat="host", level=1):
        vc.advance(1.0)
        with tr.span("inner", key=(64, 512)):
            vc.advance(0.5)
    tr.instant("mark", ts=0.25, rid=3)
    tr.counter("depth", 2, ts=0.25)
    d = tr.to_dict()
    evs = d["traceEvents"]
    assert all(e["pid"] == 1 for e in evs)
    meta = [e for e in evs if e["ph"] == "M"]
    assert {m["name"] for m in meta} == {"process_name", "thread_name"}
    by_name = {e["name"]: e for e in evs if e["ph"] != "M"}
    # inner closed first; both carry µs timestamps and durations
    assert by_name["inner"]["ts"] == 1.0e6
    assert by_name["inner"]["dur"] == 0.5e6
    assert by_name["outer"]["ts"] == 0.0
    assert by_name["outer"]["dur"] == 1.5e6
    # the span's own args, then its id and its parent's
    assert by_name["outer"]["args"] == {"level": 1, "span_id": 1}
    assert by_name["inner"]["args"] == {"key": [64, 512],  # json-safe tuples
                                        "span_id": 2, "parent_id": 1}
    assert by_name["mark"]["ph"] == "i" and by_name["mark"]["ts"] == 0.25e6
    assert by_name["depth"]["ph"] == "C"
    json.loads(tr.json_bytes())             # valid JSON document


def test_tracer_thread_tracks_use_names_not_os_ids():
    tr = obs_trace.Tracer(clock=VirtualClock(), enabled=True)

    def work():
        tr.instant("from-worker")

    t = threading.Thread(target=work, name="engine-worker")
    t.start()
    t.join()
    tr.instant("from-main")
    evs = tr.to_dict()["traceEvents"]
    names = {e["args"]["name"]: e["tid"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert set(names) == {"engine-worker", "MainThread"}
    by = {e["name"]: e for e in evs if e["ph"] == "i"}
    assert by["from-worker"]["tid"] == names["engine-worker"]
    assert by["from-main"]["tid"] == names["MainThread"]


def _spans(tr):
    return {e["name"]: e["args"] for e in tr.to_dict()["traceEvents"]
            if e["ph"] == "X"}


def test_span_ids_and_parents_follow_each_threads_nesting():
    vc = VirtualClock()
    tr = obs_trace.Tracer(clock=vc, enabled=True)
    tr.annotation = None
    seen = {}

    def work():
        # another thread's stack: its spans do not nest under main's
        with tr.span("worker.outer"):
            with tr.span("worker.inner"):
                pass

    with tr.span("main.outer"):
        t = threading.Thread(target=work, name="engine-worker")
        t.start()
        t.join()
        with tr.span("main.inner") as inner:
            seen["inner"] = inner.id
            sib = tr.complete("main.done", 0.0, 1.0)
        linked = tr.complete("linked", 0.0, 1.0, parent=seen["inner"])
    a = _spans(tr)
    ids = [v["span_id"] for v in a.values()]
    assert len(set(ids)) == len(ids) == 6
    assert "parent_id" not in a["main.outer"]
    assert "parent_id" not in a["worker.outer"]
    assert a["worker.inner"]["parent_id"] == a["worker.outer"]["span_id"]
    assert a["main.inner"]["parent_id"] == a["main.outer"]["span_id"]
    # complete(): the span open around the call, or the explicit parent
    assert a["main.done"]["parent_id"] == seen["inner"]
    assert a["main.done"]["span_id"] == sib
    assert a["linked"]["parent_id"] == seen["inner"]
    assert a["linked"]["span_id"] == linked


def test_layout_id_is_the_root_spans_id_and_carried_below_it():
    tr = obs_trace.Tracer(clock=VirtualClock(), enabled=True)
    tr.annotation = None
    with tr.span("before"):
        pass
    with tr.root("layout", n=4, m=3) as root:
        with tr.span("coarsen"):
            with tr.span("merger.dispatch"):
                pass
        group = tr.complete("refine.group", 0.0, 1.0)
        tr.complete("refine", 0.0, 1.0, parent=group)
    tr.complete("outside", 0.0, 1.0)
    a = _spans(tr)
    lid = a["layout"]["layout_id"]
    assert lid == a["layout"]["span_id"] == root.id
    assert a["layout"]["n"] == 4 and a["layout"]["m"] == 3
    for name in ("coarsen", "merger.dispatch", "refine.group", "refine"):
        assert a[name]["layout_id"] == lid, name
    assert a["refine"]["parent_id"] == group
    assert "layout_id" not in a["before"] and "layout_id" not in a["outside"]
    # a second root starts a second layout
    with tr.root("layout"):
        with tr.span("place"):
            pass
    assert _spans(tr)["place"]["layout_id"] not in (None, lid)


def test_reset_clears_the_id_counter():
    tr = obs_trace.Tracer(clock=VirtualClock(), enabled=True)
    tr.annotation = None

    def one():
        with tr.root("layout"):
            with tr.span("refine.level"):
                pass
        return tr.json_bytes()

    first = one()
    assert len(tr) == 2
    tr.reset()
    assert len(tr) == 0
    assert one() == first       # ids start again at 1
    assert _spans(tr)["layout"]["span_id"] == 1


def test_enabled_spans_mirror_into_the_profiler():
    """While enabled, each span also opens the annotation hook under the
    same name (jax.profiler.TraceAnnotation by default); complete(),
    whose bounds are past, does not."""
    tr = obs_trace.Tracer(clock=VirtualClock())
    assert tr.annotation is None              # nothing imported until enable
    tr.enable()
    import jax
    assert tr.annotation is jax.profiler.TraceAnnotation
    log = []

    class Hook:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("open", self.name))

        def __exit__(self, *exc):
            log.append(("close", self.name))

    tr.annotation = Hook
    with tr.root("layout"):
        with tr.span("refine.dispatch"):
            pass
    tr.complete("refine", 0.0, 1.0)
    assert log == [("open", "layout"), ("open", "refine.dispatch"),
                   ("close", "refine.dispatch"), ("close", "layout")]
    tr.disable()
    with tr.span("off"):
        pass
    assert len(log) == 4


# -- one layout's spans, on XLA:CPU ----------------------------------------------

def _trace_layout(engine: str) -> dict:
    """A 4,096-vertex delaunay graph laid out by ``engine`` with the process
    tracer on: three levels, the finest in neighbor mode (exact_threshold
    1,024); the spans, and the iterations counter's increase per mode."""
    from repro.core import multigila_layout
    e, n = G.delaunay(4096, 1)
    cfg = LayoutConfig(exact_threshold=1024, seed=3, engine=engine)
    modes = ("exact", "neighbor", "grid")
    it = bucketing.REFINE_ITERATIONS
    before = {m: it.value(engine=engine, mode=m) for m in modes}
    tr = obs_trace.get_tracer()
    tr.reset()
    tr.enable()
    try:
        _, stats = multigila_layout(e, n, cfg)
    finally:
        tr.disable()
    events = [x for x in tr.to_dict()["traceEvents"] if x["ph"] == "X"]
    tr.reset()
    counted = {m: it.value(engine=engine, mode=m) - before[m] for m in modes}
    return dict(cfg=cfg, n=n, m=len(e), stats=stats, events=events,
                counted=counted)


@pytest.fixture(scope="module")
def traced_layout():
    return _trace_layout("gila")


@pytest.fixture(scope="module")
def traced_stress_layout():
    return _trace_layout("stress")


def _named(events, name):
    return [x for x in events if x["name"] == name]


def test_each_level_has_one_dispatch_with_the_scheduled_iterations(
        traced_layout):
    t = traced_layout
    cfg, stats, events = t["cfg"], t["stats"], t["events"]
    levels = _named(events, "refine.level")
    assert len(levels) == stats.levels == 3
    modes = []
    for lv in levels:
        i = lv["args"]["level"]
        n_i, m_i = stats.level_sizes[i]
        want = make_schedule(i, stats.levels, n_i, m_i,
                             exact_threshold=cfg.exact_threshold,
                             grid_threshold=cfg.grid_threshold,
                             coarsest_iters=cfg.coarsest_iters,
                             finest_iters=cfg.finest_iters)
        steps = [x for x in _named(events, "refine.dispatch")
                 if x["args"]["parent_id"] == lv["args"]["span_id"]]
        assert len(steps) == 1, (i, steps)
        assert steps[0]["args"]["iters"] == want.iters, i
        assert steps[0]["args"]["mode"] == want.mode, i
        modes.append(want.mode)
    assert sorted(modes) == ["exact", "exact", "neighbor"]


def test_khop_build_nests_inside_the_neighbor_level(traced_layout):
    events = traced_layout["events"]
    (khop,) = _named(events, "refine.khop")
    (level,) = [x for x in _named(events, "refine.level")
                if x["args"]["span_id"] == khop["args"]["parent_id"]]
    (step,) = [x for x in _named(events, "refine.dispatch")
               if x["args"]["parent_id"] == level["args"]["span_id"]]
    assert step["args"]["mode"] == "neighbor"
    assert level["ts"] <= khop["ts"]
    assert khop["ts"] + khop["dur"] <= step["ts"]
    assert step["ts"] + step["dur"] <= level["ts"] + level["dur"]


def test_iteration_counter_equals_the_spans_iterations(traced_layout):
    events, counted = traced_layout["events"], traced_layout["counted"]
    by_mode = {}
    for x in _named(events, "refine.dispatch"):
        by_mode[x["args"]["mode"]] = (by_mode.get(x["args"]["mode"], 0)
                                      + x["args"]["iters"])
    assert by_mode == {m: v for m, v in counted.items() if v}


def test_stress_dispatches_record_the_schedule_and_the_annealing(
        traced_stress_layout):
    """Under the stress engine each level has one ``refine.dispatch`` with
    ``engine`` stress, the ``iters`` of ``make_schedule`` and the
    ``alpha0``/``alpha_decay`` of ``alpha_schedule(iters)``."""
    from repro.core.stress import alpha_schedule
    t = traced_stress_layout
    cfg, stats, events = t["cfg"], t["stats"], t["events"]
    levels = _named(events, "refine.level")
    assert len(levels) == stats.levels == 3
    for lv in levels:
        i = lv["args"]["level"]
        n_i, m_i = stats.level_sizes[i]
        want = make_schedule(i, stats.levels, n_i, m_i,
                             exact_threshold=cfg.exact_threshold,
                             grid_threshold=cfg.grid_threshold,
                             coarsest_iters=cfg.coarsest_iters,
                             finest_iters=cfg.finest_iters,
                             engine="stress")
        (step,) = [x for x in _named(events, "refine.dispatch")
                   if x["args"]["parent_id"] == lv["args"]["span_id"]]
        args = step["args"]
        assert (args["engine"], args["mode"], args["iters"]) == (
            "stress", want.mode, want.iters), i
        assert (args["alpha0"], args["alpha_decay"]) == alpha_schedule(
            want.iters), i


def test_stress_iteration_counter_equals_the_spans_iterations(
        traced_stress_layout):
    events, counted = (traced_stress_layout["events"],
                       traced_stress_layout["counted"])
    by_mode = {}
    for x in _named(events, "refine.dispatch"):
        assert x["args"]["engine"] == "stress"
        by_mode[x["args"]["mode"]] = (by_mode.get(x["args"]["mode"], 0)
                                      + x["args"]["iters"])
    assert by_mode == {m: v for m, v in counted.items() if v}
    assert set(by_mode) == {"exact", "neighbor"}


def test_a_gila_dispatch_records_no_annealing(traced_layout):
    for x in _named(traced_layout["events"], "refine.dispatch"):
        assert "alpha0" not in x["args"] and "alpha_decay" not in x["args"]


def test_every_span_of_the_layout_carries_its_layout_id(traced_layout):
    t = traced_layout
    events = t["events"]
    (root,) = _named(events, "layout")
    assert root["args"]["n"] == t["n"] and root["args"]["m"] == t["m"]
    lid = root["args"]["layout_id"]
    assert all(x["args"]["layout_id"] == lid for x in events)
    names = {x["name"] for x in events}
    for leaf in ("layout.components", "layout.prune", "layout.build",
                 "coarsen.sort", "refine.stage", "layout.finish"):
        assert leaf in names, leaf
    # each span lies inside its parent
    by_id = {x["args"]["span_id"]: x for x in events}
    for x in events:
        p = by_id.get(x["args"].get("parent_id"))
        if p is not None:
            assert p["ts"] <= x["ts"] and \
                x["ts"] + x["dur"] <= p["ts"] + p["dur"], (x, p)


# -- metrics registry ----------------------------------------------------------

def test_registry_families_and_prometheus_text():
    r = obs_metrics.Registry()
    c = r.counter("t_hits_total", "hits", "")
    c.inc(); c.inc(2, kind="warm")
    g = r.gauge("t_ratio", "a ratio", "ratio")
    g.set(0.5, bucket="n64")
    h = r.histogram("t_lat_seconds", "latency", "seconds", buckets=(0.1, 1.0))
    h.observe(0.05); h.observe(0.5); h.observe(2.0)
    cb = r.gauge("t_live", "callback", fn=lambda: 7)
    assert c.value() == 1.0 and c.value(kind="warm") == 2.0
    assert cb.value() == 7.0
    st = h.stats()
    assert st["count"] == 3 and st["sum"] == pytest.approx(2.55)
    assert st["buckets"] == {"0.1": 1, "1": 2}      # cumulative
    text = r.to_prometheus()
    assert "# TYPE t_hits_total counter" in text
    assert 't_hits_total{kind="warm"} 2' in text
    assert 't_ratio{bucket="n64"} 0.5' in text
    assert 't_lat_seconds_bucket{le="+Inf"} 3' in text
    assert "t_lat_seconds_count 3" in text
    assert "t_live 7" in text
    # registration is idempotent; re-registering returns the same family
    assert r.counter("t_hits_total") is c
    # snapshot is JSON-able and reset zeroes values but keeps families
    json.dumps(r.snapshot())
    r.reset()
    assert c.value(kind="warm") == 0.0 and r.get("t_lat_seconds") is h
    assert cb.value() == 7.0                        # callbacks survive reset


def test_phase_times_is_thread_safe():
    """The PR 7 race regression: concurrent PHASES.add from many threads
    must lose no update (the old dict read-modify-write could)."""
    before = bucketing.PHASES.snapshot().get("hammer", 0.0)
    N, K = 8, 2000

    def work():
        for _ in range(K):
            bucketing.PHASES.add("hammer", 1.0)

    ts = [threading.Thread(target=work) for _ in range(N)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    after = bucketing.PHASES.snapshot()["hammer"]
    assert after - before == N * K                  # 1.0 sums are exact


# -- padding occupancy ---------------------------------------------------------

def _path_request(n, seed=0):
    edges = np.stack([np.arange(n - 1), np.arange(1, n)], 1)
    g = build_graph(edges, n, bucket=True)
    sched = make_schedule(0, 1, g.n, g.m, exact_threshold=2048,
                          grid_threshold=32768, coarsest_iters=5,
                          ideal_len=1.0, n_pad=g.n_pad)
    pos0 = np.zeros((g.n_pad, 2), np.float32)
    return bucketing.make_request(g, pos0, sched, seed), edges


def _slots(name, bucket, axis):
    return obs_metrics.REGISTRY.get(name).value(bucket=bucket, axis=axis)


def test_padding_occupancy_gauges_match_hand_computed():
    """Mixed-bucket 3-graph wave, then the same pair again: two paths share
    the n64 lane bucket, the third lands in n128. The true and padded slot
    counters sum over dispatches, so the deltas between two readings give
    that window's occupancy exactly."""
    (r1, e1), (r2, e2), (r3, e3) = (_path_request(10), _path_request(20),
                                    _path_request(100))
    assert bucketing.group_key(r1) == bucketing.group_key(r2)
    assert bucketing.group_key(r3) != bucketing.group_key(r1)
    n_pad, m_pad = r1.g.n_pad, r1.g.m_pad
    assert (n_pad, m_pad) == (bucket_pad(10, 64), bucket_pad(2 * 9, 512))
    b = f"n{n_pad}_e{m_pad}"
    b3 = f"n{r3.g.n_pad}_e{r3.g.m_pad}"
    assert r3.g.n_pad == 128
    axes = ("vertices", "edges", "lanes")

    def read():
        return {(bk, ax, kind): _slots(f"gila_wave_{kind}_slots_total",
                                       bk, ax)
                for bk in (b, b3) for ax in axes
                for kind in ("true", "padded")}

    lanes = 8                                       # lane_bucket(2, 8)
    before = read()
    bucketing.refine_level_many([r1, r2], ideal_len=1.0, rep_const=1.0)
    bucketing.refine_level_many([r3], ideal_len=1.0, rep_const=1.0)
    mid = read()
    bucketing.refine_level_many([r1, r2], ideal_len=1.0, rep_const=1.0)
    after = read()

    def occ(lo, hi, bk, ax):
        return ((hi[bk, ax, "true"] - lo[bk, ax, "true"])
                / (hi[bk, ax, "padded"] - lo[bk, ax, "padded"]))

    for lo, hi in ((before, mid), (mid, after), (before, after)):
        assert occ(lo, hi, b, "vertices") == (10 + 20) / (lanes * n_pad)
        assert occ(lo, hi, b, "edges") == (2 * 9 + 2 * 19) / (lanes * m_pad)
        assert occ(lo, hi, b, "lanes") == 2 / lanes
    assert occ(before, mid, b3, "vertices") == 100 / (8 * r3.g.n_pad)
    assert occ(before, mid, b3, "edges") == 2 * 99 / (8 * r3.g.m_pad)
    assert occ(before, mid, b3, "lanes") == 1 / 8
    # the second wave of the pair left the n128 bucket's counters alone
    for ax in axes:
        for kind in ("true", "padded"):
            assert after[b3, ax, kind] == mid[b3, ax, kind]
    assert after[b, "vertices", "padded"] - before[b, "vertices", "padded"] \
        == 2 * lanes * n_pad


# -- sim trace replay determinism ----------------------------------------------

def _scripted_events():
    out = []
    for i in range(5):
        e, n = G.gnp(24 + 4 * i, 2.0, 50 + i)
        out.append(SimEvent(t=0.02 * i, edges=e, n=n, seed=i,
                            priority=i % 2))
    # one doomed request: deadline already passed at delivery
    e, n = G.gnp(30, 2.0, 99)
    out.append(SimEvent(t=0.01, edges=e, n=n, seed=9, deadline_s=0.0))
    return out


def _run_traced_sim():
    vc = VirtualClock()
    tr = obs_trace.Tracer(clock=vc, enabled=True)
    core = EngineCore(LayoutConfig(seed=0), clock=vc, max_lanes=4,
                      wave_lanes=2, dispatch=null_dispatch, tracer=tr)
    run_sim(core, _scripted_events())
    return core, tr


def test_sim_trace_replays_byte_identical():
    core1, tr1 = _run_traced_sim()
    core2, tr2 = _run_traced_sim()
    assert core1.log == core2.log
    b1, b2 = tr1.json_bytes(), tr2.json_bytes()
    assert len(tr1) > 10
    assert b1 == b2, "sim trace is not replay-deterministic"
    names = {e["name"] for e in json.loads(b1)["traceEvents"]}
    # the scheduling log, wave spans, per-lane refine spans, and request
    # lifetimes all ride one timeline
    for expected in ("engine.submit", "engine.admit", "engine.complete",
                     "engine.expire", "wave", "refine.group", "refine",
                     "request", "engine.queue_depth"):
        assert expected in names, (expected, names)


def test_sim_trace_links_lane_spans_to_their_group():
    _, tr = _run_traced_sim()
    evs = [e for e in tr.to_dict()["traceEvents"] if e["ph"] == "X"]
    groups = {e["args"]["span_id"] for e in evs if e["name"] == "refine.group"}
    lanes = [e for e in evs if e["name"] == "refine"]
    assert lanes and all(e["args"]["parent_id"] in groups for e in lanes)


def test_engine_stats_snapshot_against_scripted_trace():
    """EngineCore.stats(): counters, queue-depth high-water mark, and the
    atomically-taken metrics snapshot agree with the scripted run."""
    fam = obs_metrics.REGISTRY.get("gila_engine_requests_total")
    before = {k: v for k, v in fam.values().items()}
    core, _ = _run_traced_sim()
    s = core.stats()
    assert s["completed"] == 5 and s["expired"] == 1
    assert s["queued"] == 0 and s["running"] == 0
    assert s["queue_depth_hwm"] >= 1
    assert s["straggler_waves"] == 0        # VirtualClock waves take 0s
    snap = s["metrics"]["gila_engine_requests_total"]["values"]
    for event, want in (("submitted", 6), ("completed", 5), ("expired", 1)):
        key = (("event", event),)
        delta = snap[f'event="{event}"'] - before.get(key, 0.0)
        assert delta == want, (event, delta)
    # the snapshot is JSON-able end-to-end (it rides /stats and BENCH json)
    json.dumps(s["metrics"])


# -- HTTP: /metrics round trip -------------------------------------------------

def test_prometheus_endpoint_round_trip():
    from repro.launch.service import make_server
    from repro.serve.engine import ContinuousLayoutService

    svc = ContinuousLayoutService(LayoutConfig(seed=0), max_lanes=4)
    httpd = make_server(svc)
    host, port = httpd.server_address
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        e, n = G.delaunay(80, 3)
        pos, _ = svc.layout(e, n, timeout=600)
        assert pos.shape == (n, 2)
        with urllib.request.urlopen(f"http://{host}:{port}/metrics",
                                    timeout=60) as resp:
            assert resp.headers["Content-Type"].startswith("text/plain")
            text = resp.read().decode()
    finally:
        httpd.shutdown()
        svc.close()
    # every sample line parses as <name>[{labels}] <float>
    samples = {}
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        name_part, value = line.rsplit(" ", 1)
        float(value)
        samples[name_part] = float(value)
    prefixed = [k for k in samples if k.startswith("gila_")]
    assert prefixed, text[:400]
    # the acceptance series: cache hit/miss and padding occupancy
    assert samples["gila_compile_cache_misses_total"] >= 1
    assert "gila_compile_cache_hits_total" in samples
    padded = {k.split("{", 1)[1]: v for k, v in samples.items()
              if k.startswith("gila_wave_padded_slots_total{")}
    true = {k.split("{", 1)[1]: v for k, v in samples.items()
            if k.startswith("gila_wave_true_slots_total{")}
    assert padded and set(true) == set(padded), (true, padded)
    assert all(0.0 < true[k] <= padded[k] for k in padded), (true, padded)
    assert any(k.startswith("gila_engine_requests_total") for k in samples)
    assert any(k.startswith("gila_request_latency_seconds_bucket")
               for k in samples)
