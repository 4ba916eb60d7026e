"""Shape buckets + process-wide compile cache for the multilevel driver.

The paper's headline number is END-TO-END wall clock (10M edges in ~60
minutes on commodity cloud machines), and at that scale the coarsen →
place → refine *driver* — not the force kernel — dominates time-to-layout.
Before this module, every hierarchy level paid a fresh XLA compile: each
level has a distinct (n, m), ``PaddedGraph`` carries them as static pytree
fields, and ``gila_layout`` additionally bakes the iteration count into the
trace. A 10-level hierarchy compiled ten programs; the next graph compiled
ten more.

The fix has three parts (DESIGN.md §8):

  1. *Pow2 shape buckets* — every level's ``PaddedGraph`` is padded (vertex
     and edge axes independently) to the next power-of-two bucket
     (``graphs.graph.bucket_pad``), so all levels of all hierarchies share
     O(log n_max) distinct shapes. Randomness is per-vertex
     (``utils/prng.py``), so re-padding is behavior-preserving.
  2. *Process-wide compile cache* — the per-level refinement runs through
     one cached jitted step per key ``(engine, bucket_n, bucket_e, cap,
     mode, grid_dim, cell_cap)`` (plus the mesh for the dist driver). The
     engine id selects WHICH step program the builder constructs
     (core/engine.py — GiLA forces vs maxent-stress share the key space
     but never an entry), so a warm stress pass compiles zero new GiLA
     variants and vice versa. The static
     ``n``/``m`` fields are normalized away before tracing
     (``shape_normalized``), iteration count / temperature / cooling are
     traced scalars, and the schedule picks grid_dim/cell_cap from the
     bucket — so a fresh graph whose levels land in warm buckets triggers
     ZERO new compiles (asserted in tests/test_bucketing.py).
  3. *Buffer donation* — the position buffer is donated through the
     refinement loop (no copy per level / per distributed iteration on
     accelerators; donation is skipped on CPU where XLA does not implement
     it and only warns).

``PHASES`` collects the per-phase wall clock (coarsen / place / refine /
compile) that benchmarks/pipeline_bench.py reports.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from functools import partial

import jax
import jax.numpy as jnp

from repro.graphs.graph import PaddedGraph, bucket_pad
from repro.graphs import packing
from repro.core import engine as engines
from repro.core import gila
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.utils.transfer import io_boundary


def shape_normalized(g: PaddedGraph) -> PaddedGraph:
    """Zero the static n/m fields: jitted consumers that never read them
    then cache on the padded shapes alone (one trace per shape bucket)."""
    return dataclasses.replace(g, n=0, m=0)


def donate_argnums_if_supported(*argnums: int) -> tuple:
    """Buffer donation is a no-op (plus a warning per call) on CPU."""
    return argnums if jax.default_backend() != "cpu" else ()


def kernel_backend() -> str:
    """The kernel backend ('pallas' | 'interpret' | 'ref') the NEXT trace
    will bake in — the ``REPRO_PALLAS`` override or the platform default.

    The kernel dispatchers read this ambient state at trace time, so it is
    part of the compiled program and must be part of every compile-cache
    key: an entry cached under one backend must not be served after the env
    var changes mid-process (tools/gilalint rule R2 enforces this for any
    new cache site)."""
    from repro.kernels import backend
    return backend()


# -- per-phase wall-clock accounting ------------------------------------------

# storage for the phase accounting lives in the thread-safe metrics
# registry (obs/metrics.py), one labeled counter series per phase
PHASE_SECONDS = obs_metrics.REGISTRY.counter(
    "gila_phase_seconds_total",
    "Wall-clock seconds per pipeline phase (coarsen/place/refine/compile)",
    "seconds")


class PhaseTimes:
    """Per-phase wall-clock accounting (coarsen/place/refine/compile).
    ``compile`` is the first call into a cold cache entry — trace
    + XLA compile + the first execution (inseparable under jit dispatch);
    merger-superstep compiles land in ``coarsen`` the same way.

    DEPRECATED facade: the numbers now live in the metrics registry
    (``gila_phase_seconds_total{phase=...}``), which is lock-protected —
    the old dict-backed version was mutated from the engine worker thread
    (host coarsening inside ``EngineCore``) and the caller thread
    concurrently, a read-modify-write race. The ``PHASES`` alias and its
    ``add``/``phase``/``snapshot``/``reset`` API are kept so
    benchmarks/pipeline_bench.py output is unchanged; new code should use
    ``obs_metrics.REGISTRY`` / ``obs_trace`` directly.
    """

    def add(self, name: str, seconds: float) -> None:
        PHASE_SECONDS.inc(max(float(seconds), 0.0), phase=name)

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add(name, time.perf_counter() - t0)

    def snapshot(self) -> dict:
        return {dict(k)["phase"]: v
                for k, v in PHASE_SECONDS.values().items()}

    def reset(self) -> None:
        PHASE_SECONDS.clear()


PHASES = PhaseTimes()


# -- the compile cache ---------------------------------------------------------

CACHE_HITS = obs_metrics.REGISTRY.counter(
    "gila_compile_cache_hits_total",
    "Warm lookups of the process-wide compiled-step cache")
CACHE_MISSES = obs_metrics.REGISTRY.counter(
    "gila_compile_cache_misses_total",
    "Cold lookups (each one builds + compiles a new step program)")


class CompileCache:
    """Process-wide cache of jitted step functions keyed on shape buckets.

    ``get(key, builder)`` returns ``(fn, fresh)``; ``fresh=True`` means the
    builder ran (the next call of ``fn`` traces and XLA-compiles).
    Lock-protected: the engine worker thread and direct callers share one
    process-wide instance."""

    def __init__(self):
        self.entries: dict = {}
        self.hits = 0
        self.misses = 0
        self._lock = threading.RLock()

    def get(self, key, builder):
        with self._lock:
            fn = self.entries.get(key)
            if fn is not None:
                self.hits += 1
                CACHE_HITS.inc()
                return fn, False
            self.misses += 1
            CACHE_MISSES.inc()
            fn = builder()
            self.entries[key] = fn
            return fn, True

    def clear(self) -> None:
        with self._lock:
            self.entries.clear()
            self.hits = 0
            self.misses = 0


STEP_CACHE = CompileCache()


def cache_stats() -> dict:
    """Introspection for tests/benchmarks: entries/hits/misses of the step
    cache plus the total jit-trace entry count of every tracked function."""
    return dict(entries=len(STEP_CACHE.entries), hits=STEP_CACHE.hits,
                misses=STEP_CACHE.misses, jit_entries=jit_cache_entries())


def jit_cache_entries() -> int:
    """Total trace-cache entries across the driver's jitted functions —
    the cached refine steps plus the jitted supersteps the driver calls.
    If this number does not grow across a layout, that layout triggered
    zero new traces (and hence zero new XLA compiles)."""
    import importlib
    # the package __init__ rebinds these names to functions; go through
    # importlib to reach the modules themselves
    _merger = importlib.import_module("repro.core.solar_merger")
    _placer = importlib.import_module("repro.core.solar_placer")

    fns = []
    for entry in STEP_CACHE.entries.values():
        # dist-engine entries are (jitted_step, shardings) tuples
        fns.append(entry[0] if isinstance(entry, tuple) else entry)
    fns += [_merger.sun_election, _merger.system_growth,
            _placer._place, gila.gila_layout]
    total = 0
    for f in fns:
        size = getattr(f, "_cache_size", None)
        if callable(size):
            try:
                total += int(size())
            except Exception:
                pass
    return total


# callback gauges: sampled at scrape/snapshot time, so a long-running
# service's /metrics always reports the LIVE cache state
obs_metrics.REGISTRY.gauge(
    "gila_compile_cache_entries",
    "Live compiled-step entries in the process-wide cache",
    fn=lambda: len(STEP_CACHE.entries))
obs_metrics.REGISTRY.gauge(
    "gila_jit_trace_entries",
    "Total jit trace-cache entries across the driver's tracked functions",
    fn=jit_cache_entries)


# per-engine dispatch accounting: which refinement engine served how many
# cached-step dispatches, split by the single-graph vs batched path
REFINE_DISPATCHES = obs_metrics.REGISTRY.counter(
    "gila_refine_dispatches_total",
    "Cached refine-step dispatches, labeled by engine and dispatch path")
# the iteration count handed to each dispatched step, which its fori_loop
# runs exactly (the batched step: its lanes' largest budget); host ints,
# so counting reads nothing back from the device
REFINE_ITERATIONS = obs_metrics.REGISTRY.counter(
    "gila_refine_iterations_total",
    "Iterations run by cached refine steps, labeled by engine and mode")


# -- the bucketed refinement step ----------------------------------------------

def _build_refine(mode: str, grid_dim: int, cell_cap: int,
                  engine: str = "gila"):
    """Build the jitted per-level refinement step for ``engine`` — a thin
    dispatch into the engine registry (core/engine.py), kept here so the
    gilalint jaxpr audit and tests keep one stable entry point. The step
    has TRACED iteration count and annealing vector: one compile covers
    every level (and every graph) whose arrays land in the same shape
    bucket. The position buffer is donated."""
    return engines.get_engine(engine).build_refine(mode, grid_dim, cell_cap)


def cached_refine(g: PaddedGraph, pos0, sched, nbr_idx, nbr_mask, *,
                  ideal_len: float, rep_const: float, min_dist: float = 1e-3):
    """(cache_key, fn, fresh, args) for one level's bucketed refine step.

    The single place the single-graph refine key is derived and its
    arguments staged — shared by the driver (``refine_level``) and the
    jaxpr audit of tools/gilalint, so the audit traces exactly the program
    the driver would run (gilalint R2 statically checks this call site).
    ``sched.engine`` picks the step program AND is part of the key: GiLA
    and stress entries of the same shape bucket never collide.
    """
    eng = engines.get_engine(sched.engine)
    key = ("refine", sched.engine, g.n_pad, g.m_pad, int(nbr_idx.shape[1]),
           sched.mode, sched.grid_dim, sched.cell_cap, kernel_backend())
    fn, fresh = STEP_CACHE.get(
        key, lambda: eng.build_refine(sched.mode, sched.grid_dim,
                                      sched.cell_cap))
    with obs_trace.span("refine.stage", cat="host"), \
            io_boundary():                  # intentional host→device staging
        params = jnp.asarray([rep_const, ideal_len, min_dist], jnp.float32)
        args = (jnp.asarray(pos0), g.src, g.dst, g.vmask, g.emask, g.mass,
                g.ewt, nbr_idx, nbr_mask,
                jnp.asarray(sched.iters, jnp.int32),
                jnp.asarray(eng.lane_schedule(sched), jnp.float32), params)
    return key, fn, fresh, args


def refine_level(g: PaddedGraph, pos0, sched, *, ideal_len: float,
                 rep_const: float, min_dist: float = 1e-3, seed: int = 0):
    """Bucketed drop-in for ``gila.gila_layout`` in the multilevel driver.

    Looks up (or builds) the cached step for this level's shape bucket and
    runs it with iters/temp as traced scalars. The first call into a cold
    entry is accounted to the ``compile`` phase, warm calls to ``refine``.
    """
    eng = engines.get_engine(sched.engine)
    if sched.mode == "neighbor":
        with PHASES.phase("refine"), obs_trace.span(     # host k-hop lists
                "refine.khop", cat="host", n=g.n, k=sched.k, cap=sched.cap):
            nbr_idx, nbr_mask = eng.init_state(g, sched, seed)
    else:
        nbr_idx, nbr_mask = eng.init_state(g, sched, seed)

    key, fn, fresh, args = cached_refine(g, pos0, sched, nbr_idx, nbr_mask,
                                         ideal_len=ideal_len,
                                         rep_const=rep_const,
                                         min_dist=min_dist)

    # the span brackets the existing dispatch + block_until_ready pair —
    # NO new host↔device sync is introduced by tracing (gilalint-checked)
    t0 = time.perf_counter()
    with obs_trace.span("refine.dispatch", cat="device", key=key,
                        fresh=fresh, mode=sched.mode, engine=sched.engine,
                        iters=sched.iters, **eng.span_args(sched)):
        pos = fn(*args)
        pos.block_until_ready()
    PHASES.add("compile" if fresh else "refine", time.perf_counter() - t0)
    REFINE_DISPATCHES.inc(engine=sched.engine, path="single")
    REFINE_ITERATIONS.inc(sched.iters, engine=sched.engine, mode=sched.mode)
    return pos


# -- the batched (multi-graph) refinement step ---------------------------------
#
# The multi-graph driver (core/multilevel.py:multigila_layout_many) groups the
# pending per-level refinements of MANY graphs by shape bucket and runs each
# group as ONE vmapped cached step: a 16-graph request whose levels land in
# warm buckets compiles nothing and dispatches one device program per level
# wave. Iteration counts / temperatures stay per-lane traced arrays; lanes
# whose iteration budget is exhausted (and the dead padding lanes of a pow2
# batch bucket) carry their positions through the remaining loop trips
# unchanged, which keeps every lane bit-identical to the same refinement run
# alone (tests/test_many.py).

# Lane shape-bucket floors for the batched driver. The vertex floor sits
# BELOW the single-graph driver's 256: with B lanes amortizing the compile,
# finer buckets pay for themselves immediately — a 45-vertex coarse level
# costs 64² pair interactions per lane instead of 256² (padding invariance
# makes the finer re-pad behavior-preserving). The edge floor is coarser
# than pow2-of-2m so that small per-seed wobbles in coarse-level edge counts
# do not mint fresh cache keys (attraction work is linear in m_pad — cheap
# relative to the n_pad² repulsion).
BATCH_MIN_N = 64
BATCH_MIN_E = 512
# use the incidence-gather attraction (see _build_refine_many) up to this
# per-vertex degree bucket; beyond it (hub-heavy graphs) the [n_pad, K]
# gather table outgrows the edge list and the flat scatter wins back
INC_K_MAX = 32


@dataclasses.dataclass
class RefineRequest:
    """One graph-level refinement queued for a batched group dispatch.

    ``g``/``pos0`` are already re-padded to the LANE bucket
    (``lane_shape``); ``sched`` carries the level's iteration budget and
    (static) mode/grid parameters; ``seed`` feeds the neighbor-list build;
    ``inc``/``inc_k`` the incidence-gather table (inc_k = 0 → the program
    aggregates attraction with a flat scatter instead). Build with
    ``make_request``. ``level``/``lane`` are observability metadata only
    (span annotations) — they MUST stay out of ``group_key``, or equal
    shapes at different hierarchy levels would stop sharing compiles.
    """
    g: PaddedGraph
    pos0: jnp.ndarray
    sched: "object"          # core.schedule.LevelSchedule
    seed: int
    inc: jnp.ndarray
    inc_k: int
    level: int = 0
    lane: object = None


def lane_shape(n: int, m: int) -> tuple[int, int]:
    """(n_pad, m_pad) lane bucket for a graph with n vertices / m edges."""
    return (bucket_pad(n, BATCH_MIN_N), bucket_pad(2 * m, BATCH_MIN_E))


def make_request(g: PaddedGraph, pos0, sched, seed: int, *, level: int = 0,
                 lane: object = None) -> RefineRequest:
    """Re-pad one level to its lane bucket and attach the incidence table."""
    n_pad, m_pad = lane_shape(g.n, g.m)
    g2 = packing.repad_graph(g, n_pad, m_pad)
    inc, k = packing.incidence_table(g2, INC_K_MAX)
    if inc is None:               # hub-heavy lane: flat-scatter attraction
        with io_boundary():
            inc, k = jnp.zeros((n_pad, 0), jnp.int32), 0
    return RefineRequest(g=g2, pos0=packing.repad_rows(pos0, n_pad),
                         sched=sched, seed=seed, inc=inc, inc_k=k,
                         level=int(level), lane=lane)


def group_key(req: RefineRequest) -> tuple:
    """Shape-bucket grouping key: requests with equal keys share one
    compiled batched program (and one device dispatch per wave)."""
    s = req.sched
    cap = s.cap if s.mode == "neighbor" else 1
    return (s.engine, req.g.n_pad, req.g.m_pad, cap, req.inc_k, s.mode,
            s.grid_dim, s.cell_cap)


# padding occupancy — the direct measurement of fragmentation loss: per
# shape bucket and axis (vertices, edges, lanes), the slots each dispatched
# [lanes, n_pad] / [lanes, m_pad] batch holds TRUE vertices / edge slots /
# live lanes, and the slots it dispatched in all. Counters sum over any
# window: two scrapes give the occupancy between them as the ratio of the
# two deltas.
OCC_TRUE = obs_metrics.REGISTRY.counter(
    "gila_wave_true_slots_total",
    "True vertices, directed edge slots or live lanes of batched dispatches,"
    " labeled by shape bucket and axis")
OCC_PADDED = obs_metrics.REGISTRY.counter(
    "gila_wave_padded_slots_total",
    "Dispatched (padded) slots of batched dispatches, labeled by shape "
    "bucket and axis")


def _record_occupancy(reqs: list["RefineRequest"], lanes: int) -> None:
    n_pad, m_pad = reqs[0].g.n_pad, reqs[0].g.m_pad
    bucket = f"n{n_pad}_e{m_pad}"
    for axis, true, padded in (
            ("vertices", sum(r.g.n for r in reqs), lanes * n_pad),
            ("edges", sum(2 * r.g.m for r in reqs), lanes * m_pad),
            ("lanes", len(reqs), lanes)):
        OCC_TRUE.inc(true, bucket=bucket, axis=axis)
        OCC_PADDED.inc(padded, bucket=bucket, axis=axis)


def _build_refine_many(mode: str, grid_dim: int, cell_cap: int, inc_k: int,
                       engine: str = "gila"):
    """Build the jitted batched refinement over ``[B, n_pad]`` lanes for
    ``engine`` — a thin dispatch into the engine registry (core/engine.py;
    the flat-index lowering rationale is documented on
    ``GilaEngine.build_refine_many``), kept here so the gilalint jaxpr
    audit and tests keep one stable entry point."""
    return engines.get_engine(engine).build_refine_many(
        mode, grid_dim, cell_cap, inc_k)


def cached_refine_many(reqs: list[RefineRequest], nbrs: list[tuple], *,
                       ideal_len: float, rep_const: float,
                       min_dist: float = 1e-3, lanes_min: int = 8):
    """(cache_key, fn, fresh, args) for one batched shape-bucket group.

    ``nbrs`` is the per-request (nbr_idx, nbr_mask) list (dummies for
    non-neighbor modes). Shared by ``refine_level_many`` and the gilalint
    jaxpr audit — the audit traces the production staging path (and
    gilalint R2 statically checks this call site).
    """
    key0 = group_key(reqs[0])
    assert all(group_key(r) == key0 for r in reqs), "mixed group"
    sched0 = reqs[0].sched
    eng = engines.get_engine(sched0.engine)
    b = len(reqs)
    lanes = packing.lane_bucket(b, lanes_min)
    packed = packing.pack_graphs([r.g for r in reqs], lanes=lanes)
    _record_occupancy(reqs, lanes)
    with io_boundary():                     # intentional host→device staging
        pl = lambda a: packing.pad_lanes(a, b, lanes)
        pos0 = pl(jnp.stack([jnp.asarray(r.pos0) for r in reqs]))
        nbr_idx = pl(jnp.stack([ni for ni, _ in nbrs]))
        nbr_mask = pl(jnp.stack([nm for _, nm in nbrs]))
        inc = pl(jnp.stack([r.inc for r in reqs]))
        # dead lanes: iteration budget 0 — they ride through untouched
        iters = jnp.asarray([r.sched.iters for r in reqs] + [0] * (lanes - b),
                            jnp.int32)
        # the per-lane annealing vector [lanes, sched_k] (engine-specific:
        # gila (temp0, decay); stress adds (alpha0, alpha_decay))
        sparams = pl(jnp.asarray([eng.lane_schedule(r.sched) for r in reqs],
                                 jnp.float32))
        params = jnp.asarray([rep_const, ideal_len, min_dist], jnp.float32)
        max_iters = jnp.asarray(max(r.sched.iters for r in reqs), jnp.int32)

    cache_key = ("refine_many", lanes, kernel_backend()) + key0
    fn, fresh = STEP_CACHE.get(
        cache_key,
        lambda: eng.build_refine_many(sched0.mode, sched0.grid_dim,
                                      sched0.cell_cap, reqs[0].inc_k))
    args = (pos0, packed.g.src, packed.g.dst, packed.g.vmask, packed.g.emask,
            packed.g.mass, packed.g.ewt, nbr_idx, nbr_mask, inc, iters,
            sparams, params, max_iters)
    return cache_key, fn, fresh, args


def refine_level_many(reqs: list[RefineRequest], *, ideal_len: float,
                      rep_const: float, min_dist: float = 1e-3,
                      lanes_min: int = 8,
                      lanes_cap: int | None = None) -> list[jnp.ndarray]:
    """Run one shape-bucket group of refinements as a single device program.

    All requests must share ``group_key``. Returns the per-request refined
    positions (lane-padded shape [n_pad, 2]), in request order.

    ``lanes_cap`` bounds the lane bucket of a single dispatch: an oversized
    group is split into ≤ lanes_cap chunks (lanes are arithmetically
    independent, so chunking is bit-exact). A long-lived engine
    (serve/engine.py) sets this so its lane-bucket spectrum is CLOSED —
    pow2 buckets in [lanes_min, lanes_cap] — and a mid-flight join can
    never mint a fresh lane-bucket compile once those buckets are warm.
    """
    assert reqs
    if lanes_cap is not None and len(reqs) > lanes_cap:
        out = []
        for i in range(0, len(reqs), lanes_cap):
            out.extend(refine_level_many(
                reqs[i:i + lanes_cap], ideal_len=ideal_len,
                rep_const=rep_const, min_dist=min_dist,
                lanes_min=lanes_min, lanes_cap=lanes_cap))
        return out
    mode = reqs[0].sched.mode
    eng = engines.get_engine(reqs[0].sched.engine)

    # per-lane engine state (host neighbor-list build for neighbor mode,
    # same code path + seed as the single-graph driver so the lists — and
    # hence the forces — match)
    if mode == "neighbor":
        with PHASES.phase("refine"), obs_trace.span(
                "refine.khop", cat="host", lanes=len(reqs)):
            nbrs = [eng.init_state(r.g, r.sched, r.seed) for r in reqs]
    else:
        z = eng.init_state(reqs[0].g, reqs[0].sched, reqs[0].seed)
        nbrs = [z] * len(reqs)

    key, fn, fresh, args = cached_refine_many(
        reqs, nbrs, ideal_len=ideal_len, rep_const=rep_const,
        min_dist=min_dist, lanes_min=lanes_min)
    # span brackets the existing dispatch + sync only (no added syncs);
    # every lane rides the loop's max_iters trips (idle past its own budget);
    # the annealing recorded is that of the lane whose budget sets them
    lead = max(reqs, key=lambda r: r.sched.iters).sched
    iters = lead.iters
    engine = lead.engine
    t0 = time.perf_counter()
    with obs_trace.span("refine_many.dispatch", cat="device", key=key,
                        fresh=fresh, lanes=len(reqs), engine=engine,
                        mode=mode, iters=iters, **eng.span_args(lead)):
        out = fn(*args)
        out.block_until_ready()
    PHASES.add("compile" if fresh else "refine", time.perf_counter() - t0)
    REFINE_DISPATCHES.inc(engine=engine, path="many")
    REFINE_ITERATIONS.inc(iters, engine=engine, mode=mode)
    b = len(reqs)
    with io_boundary():                     # egress: unpack the live lanes
        return [out[i] for i in range(b)]
