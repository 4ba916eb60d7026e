#!/usr/bin/env python3
"""Prove that the layout system runs on the chip, end to end.

    python chip_smoke.py              # one TPU chip: phases k, a, b, c, s
    python chip_smoke.py --chips 4    # four chips: the sharded driver only

All phases run in this one process, which owns the chip:

  k  kernels  each compiled Pallas kernel (exact all-pairs, neighbor list,
              grid near and far field) against its jnp oracle (ref.py),
              on the chip, at an n that fits no block.
  a  layout   grid_400x400 (160,000 vertices, 319,200 edges) through
              ``multigila_layout`` with the default ``LayoutConfig`` and the
              GiLA engine: a hierarchy from the on-device merger (7 levels
              on a v5e chip) that spans the exact, neighbor and grid
              repulsion modes. The layout must be finite and its stress
              below half of a random placement's; every compiled refine
              step must hold a ``tpu_custom_call``.
  b  service  the continuous-batching HTTP service, in process: Delaunay
              graphs large enough to reach the neighbor mode are POSTed
              concurrently, and each answer must equal, bit for bit, the
              dedicated driver's layout of the same graph and seed.
  c  tiles    the tile pyramid of (a)'s export answers 16 batched viewport
              queries, each equal, bit for bit, to ``reference_resolve``.
  s  stress   a 131,072-vertex Delaunay graph through ``multigila_layout``
              with the maxent-stress engine; from the drawing each level's
              refine step produced, the program's cached stress step at
              its grid level and its largest exact level after 1 and 5
              iterations, and at its neighbor level after 1, against the
              plain reference (``core/stress_ref.py``) within
              ``STRESS_TOL``.

``--chips 4`` lays grid_120x120 out (see ``SHARDED_SIDE``) with
``driver="multigila_dist"`` on a (4, 1) (data, model) mesh and with
``driver="multigila"`` on the first chip. It checks each level's first
sharded superstep against the one-device iteration (``STEP_TOL``), that
every superstep's position and edge arrays are spread over the four
devices, and the two layouts' quality (``DIST_BOUND``).

The script refuses to start unless JAX's first device is a TPU and
``REPRO_PALLAS`` is unset or ``pallas``. Any failed check raises, so the
exit code is non-zero and no result line is printed. On success the last
line of standard output is the JSON object
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Graphs are generated from fixed seeds; nothing outside git is read.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import http.client
import json
import os
import sys
import threading
import time
from collections import Counter
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

#: the 4-chip check lays out grid_120x120 (14,400 vertices, 28,560 edges)
#: rather than phase (a)'s grid_400x400, so that it takes minutes: the
#: sharded driver dispatches every superstep from the host. Its grid
#: threshold is lowered from 32768 so that the finest level runs the grid
#: mode and every repulsion mode is sharded. The sharded steps at the
#: grid_400x400 shapes are compiled for a v5e:2x2 in tests/test_tpu_compile.py.
SHARDED_SIDE = 120
SHARDED_CFG = dict(grid_threshold=8192)

#: the first superstep of every level, sharded vs the one-device GiLA
#: iteration from the same positions: the largest displacement difference
#: of a vertex, as a fraction of the level's step clamp ``temp0``. The two
#: differ only in the order of their float sums: at most 7.4e-6 on
#: grid_120x120 (XLA:CPU, 4 virtual devices, seeds 0-3), while one shard's
#: gathered positions zeroed, or the grid's per-cell sums left un-psum'd,
#: gives 1.9 or more.
STEP_TOL = 1e-3

#: the whole layouts: the largest relative difference of NELD and of
#: sampled stress between the sharded and the one-chip layout. The drivers
#: share the hierarchy, the schedules and the seeds; the force iteration
#: amplifies the supersteps' rounding differences into a layout of nearly
#: the same quality. Three times the largest difference of those sound runs
#: (NELD 0.0117, stress 0.0178); the un-psum'd grid sums above give stress
#: 0.54.
DIST_BOUND = {"neld": 0.035, "stress": 0.055}


#: phase (s): the program's cached maxent-stress step against the plain
#: reference from the same positions, after each count of iterations in
#: ``STRESS_ITERS``: the largest displacement difference of a vertex, as a
#: fraction of the level's step clamp ``temp0``. The two differ in the order
#: of their float sums, in the kernels (compiled Pallas tiles against the
#: jnp oracles) and, at the grid level, in the approximation's arrangement
#: (the reference writes it out from its definition): at 131,072 vertices
#: on the chip, from settled drawings, one iteration read at most 6.5e-6 at
#: every level and five at most 1.2e-4 at the grid and exact levels (two
#: graphs), while positions cast to bfloat16 inside the step read 0.074 or
#: more. Five iterations at the neighbor level read up to 3.2e-3: its k-hop
#: lists keep near-coincident pairs, whose repulsion turns rounding into
#: motion (the program against itself from positions a few ulps apart reads
#: 0.08 there), so that level is compared after one. PERF.md gives every
#: reading.
STRESS_TOL = 1e-3
STRESS_ITERS = {"grid": (1, 5), "exact": (1, 5), "neighbor": (1,)}


def _refuse(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(2)


def preflight(chips: int):
    """The devices to use, or exit non-zero when this is not the chip."""
    pallas = os.environ.get("REPRO_PALLAS")
    if pallas not in (None, "pallas"):
        _refuse(f"REPRO_PALLAS={pallas!r}: the chip check runs the compiled "
                "Pallas kernels only (unset it or set it to 'pallas')")
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        _refuse(f"JAX found no devices: {e}")
    if devs[0].platform != "tpu":
        _refuse(f"no TPU: JAX's first device is {devs[0].platform} "
                f"({devs[0].device_kind}); this check runs on the chip only")
    if len(devs) < chips:
        _refuse(f"--chips {chips} needs {chips} TPU devices, found {len(devs)}")
    if not (SRC / "repro").is_dir():
        _refuse(f"the layout package is missing: no {SRC / 'repro'}")
    sys.path.insert(0, str(SRC))
    return devs[:chips]


def _line(phase: str, **kv) -> None:
    import jax
    d = jax.devices()[0]
    kv = {"device": f"{d.platform}:{d.device_kind}", **kv}
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def _phase_seconds() -> dict:
    """Cumulative wall seconds per pipeline phase (coarsen, place, refine,
    compile) of this process, from ``core.bucketing.PHASES``."""
    from repro.core.bucketing import PHASES
    return PHASES.snapshot()


def _compile_seconds() -> float:
    return _phase_seconds().get("compile", 0.0)


def _mode_of(n: int, cfg) -> str:
    """The repulsion mode ``core.schedule.make_schedule`` picks for n."""
    if n <= cfg.exact_threshold:
        return "exact"
    return "neighbor" if n <= cfg.grid_threshold else "grid"


# -- phase k: each kernel against its oracle ------------------------------------

def phase_kernels(expect_backend: str = "pallas", n: int = 3000,
                  K: int = 128, G: int = 32, cap: int = 48) -> dict:
    """Every kernel through its dispatching op vs the jnp oracle."""
    import jax.numpy as jnp
    from repro.kernels import backend
    from repro.kernels.grid_force import ops as gops
    from repro.kernels.nbody.ops import nbody_repulsion
    from repro.kernels.nbody.ref import nbody_repulsion_ref
    from repro.kernels.neighbor_force.ops import neighbor_repulsion
    from repro.kernels.neighbor_force.ref import neighbor_repulsion_ref

    kb = backend()
    assert kb == expect_backend, (kb, expect_backend)
    t0 = time.perf_counter()
    rng = np.random.default_rng(0)
    C, L, md = 1.2, 0.9, 1e-2
    pos = jnp.asarray(rng.random((n, 2)) * 20, jnp.float32)
    mass = jnp.asarray(rng.random(n) + 0.5, jnp.float32)
    vmask = jnp.asarray(rng.random(n) > 0.1)
    nbr = jnp.asarray(rng.integers(0, n + 1, (n, K)), jnp.int32)
    nmask = jnp.asarray(rng.random((n, K)) > 0.2)
    nc = G * G
    rows = jnp.asarray(rng.random((2, cap, nc)) * 20, jnp.float32)
    nbrs = jnp.asarray(np.concatenate(
        [rng.random((2, 9 * cap, nc)) * 20,
         np.where(rng.random((1, 9 * cap, nc)) > 0.3, 1.0, 0.0)]),
        jnp.float32)
    cells = jnp.asarray(np.concatenate(
        [rng.random((nc, 2)) * 20, rng.random((nc, 1)) * 30], 1), jnp.float32)
    pairs = {
        "exact": (nbody_repulsion(pos, mass, vmask, C, L, md),
                  nbody_repulsion_ref(pos, mass, vmask, C, L, md)),
        "neighbor": (
            neighbor_repulsion(pos, mass, nbr, nmask, vmask, C, L, md),
            neighbor_repulsion_ref(pos, mass, nbr, nmask, vmask, C, L, md)),
        "grid_near": (gops.near_field(rows, nbrs, C, L, md, backend=kb),
                      gops.near_field(rows, nbrs, C, L, md, backend="ref")),
        "grid_far": (gops.far_all_cells(pos, cells, C, L, md, kb),
                     gops.far_all_cells(pos, cells, C, L, md, "ref")),
    }
    errs = {}
    for name, (got, ref) in pairs.items():
        got, ref = np.asarray(got), np.asarray(ref)
        assert got.shape == ref.shape and np.isfinite(got).all(), name
        scale = float(np.abs(ref).max())
        errs[name] = float(np.abs(got - ref).max() / scale)
        assert errs[name] < 1e-4, (name, errs[name])
    _line("k kernels", backend=kb, n=n, K=K, cells=nc, cap=cap,
          max_rel_err=json.dumps(errs, separators=(",", ":")),
          wall_s=round(time.perf_counter() - t0, 3))
    return errs


# -- phase a: a paper-scale layout ----------------------------------------------

@contextlib.contextmanager
def watch_refine_steps():
    """Record the refine steps called inside the block: each single-graph
    (``refine``) and batched (``refine_many``) compile-cache key, with the
    shapes of its first call's arguments."""
    import jax
    from repro.core import bucketing

    seen = {}
    cache, get = bucketing.STEP_CACHE, bucketing.STEP_CACHE.get

    def watched(key, build):
        fn, fresh = get(key, build)
        if key[0] not in ("refine", "refine_many"):
            return fn, fresh

        def call(*args):
            seen.setdefault(key, [jax.ShapeDtypeStruct(a.shape, a.dtype)
                                  for a in args])
            return fn(*args)
        return call, fresh

    cache.get = watched
    try:
        yield seen
    finally:
        del cache.get


def refine_step_modes(seen, expect_backend: str = "pallas") -> dict:
    """``{family: {mode: steps}}`` of the refine steps ``seen`` by
    ``watch_refine_steps``. Each key must name ``expect_backend``; on the
    chip each step's lowering must hold the Pallas kernel
    (``tpu_custom_call``)."""
    from repro.core import bucketing

    modes = {}
    for key, specs in seen.items():
        # ("refine", engine, n_pad, m_pad, K, mode, G, cap, backend) or
        # ("refine_many", lanes, backend, engine, n_pad, m_pad, K, inc_k,
        #  mode, G, cap)
        mode, kb = (key[5], key[8]) if key[0] == "refine" else (key[8], key[2])
        assert kb == expect_backend, key
        if expect_backend == "pallas":
            fn = bucketing.STEP_CACHE.entries[key]
            assert "tpu_custom_call" in fn.lower(*specs).as_text(), key
        modes.setdefault(key[0], Counter())[mode] += 1
    return {fam: dict(c) for fam, c in sorted(modes.items())}


def phase_layout(side: int = 400, cfg=None, expect_backend: str = "pallas"):
    """(a): returns the layout's hierarchy export for phase (c)."""
    from repro.core import LayoutConfig, multigila_layout
    from repro.graphs import generators as G
    from repro.graphs.graph import build_graph
    from repro.graphs.metrics import quality_report, sampled_stress

    cfg = cfg or LayoutConfig()
    edges, n = G.grid(side, side)
    p0 = _phase_seconds()
    t0 = time.perf_counter()
    with watch_refine_steps() as seen:
        pos, stats, exp = multigila_layout(edges, n, cfg, export=True)
    wall = time.perf_counter() - t0
    phases = {k: round(v - p0.get(k, 0.0), 3)
              for k, v in sorted(_phase_seconds().items())}
    assert pos.shape == (n, 2) and np.isfinite(pos).all()
    levels = Counter(_mode_of(nv, cfg) for nv, _ in stats.level_sizes)
    steps = refine_step_modes(seen, expect_backend)["refine"]
    assert set(steps) >= {"exact", "neighbor", "grid"}, steps

    g = build_graph(edges, n)
    rep = quality_report(g, np.pad(pos, ((0, g.n_pad - n), (0, 0))))
    lo, hi = pos.min(0), pos.max(0)
    rnd = np.random.default_rng(1).uniform(lo, hi, (n, 2))
    s_rnd = sampled_stress(rnd.astype(np.float32), edges, n)
    assert np.isfinite(rep["neld"]) and np.isfinite(rep["stress"]), rep
    assert rep["stress"] < 0.5 * s_rnd, (rep["stress"], s_rnd)
    cre = rep["cre"] if np.isfinite(rep["cre"]) else "not_computed(m>40000)"
    _line("a layout", graph=f"grid_{side}x{side}", n=n, m=len(edges),
          levels=stats.levels,
          levels_per_mode=json.dumps(dict(levels), separators=(",", ":")),
          compiled_steps_per_mode=json.dumps(steps, separators=(",", ":")),
          compile_s=phases.get("compile", 0.0), wall_s=round(wall, 3),
          phase_s=json.dumps(phases, separators=(",", ":")),
          NELD=round(rep["neld"], 4), CRE=cre,
          stress=round(rep["stress"], 4), random_stress=round(s_rnd, 4))
    return exp


# -- phase b: the layout service ------------------------------------------------

def _post(host: str, port: int, edges: np.ndarray, n: int, seed: int) -> dict:
    body = json.dumps({"edges": edges.tolist(), "n": int(n),
                       "seed": int(seed)})
    conn = http.client.HTTPConnection(host, port, timeout=1200)
    try:
        conn.request("POST", "/layout", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        out = json.loads(resp.read())
    finally:
        conn.close()
    assert resp.status == 200, (resp.status, out)
    return out


def _serve(svc, make_server, graphs, seed0: int):
    """POST every graph at once to ``svc`` behind an in-process HTTP
    server; returns the answers and the wall seconds until the last."""
    httpd = make_server(svc)
    host, port = httpd.server_address
    th = threading.Thread(target=httpd.serve_forever, daemon=True)
    th.start()
    t0 = time.perf_counter()
    try:
        with ThreadPoolExecutor(len(graphs)) as ex:
            futs = [ex.submit(_post, host, port, e, n, seed0 + i)
                    for i, (e, n) in enumerate(graphs)]
            outs = [f.result() for f in futs]
        wall = time.perf_counter() - t0
        completed = svc.stats()["completed"]
    finally:
        httpd.shutdown()
        httpd.server_close()
        svc.close()
        th.join(timeout=60)
    assert completed == len(graphs), completed
    return outs, wall


def phase_service(sizes=(2600, 3000, 3400, 3800), cfg=None, seed0: int = 7,
                  expect_backend: str = "pallas"):
    """(b): concurrent POSTs to the in-process HTTP service, each answer
    compared with the dedicated driver's layout bit for bit. The service's
    batched steps and the driver's single-graph steps must both run the
    kernels (see ``refine_step_modes``)."""
    from repro.core import LayoutConfig, multigila_layout
    from repro.graphs import generators as G
    from repro.launch.service import make_server
    from repro.serve.engine import ContinuousLayoutService

    cfg = cfg or LayoutConfig(seed=0)
    graphs = [G.delaunay(nv, seed0 + i) for i, nv in enumerate(sizes)]
    c0 = _compile_seconds()
    with watch_refine_steps() as seen:
        outs, wall = _serve(ContinuousLayoutService(cfg, max_lanes=8),
                            make_server, graphs, seed0)
        c_svc = _compile_seconds() - c0
        levels, refs = Counter(), []
        for i, (e, n) in enumerate(graphs):
            ref, st = multigila_layout(e, n, dataclasses.replace(
                cfg, seed=seed0 + i))
            refs.append(ref)
            levels.update(_mode_of(nv, cfg) for nv, _ in st.level_sizes)
    steps = refine_step_modes(seen, expect_backend)
    assert {"exact", "neighbor"} <= set(steps["refine_many"]), steps
    for i, ((e, n), out, ref) in enumerate(zip(graphs, outs, refs)):
        got = np.asarray(out["pos"], np.float32)
        ref = np.asarray(ref, np.float32)
        assert got.shape == (n, 2) and np.isfinite(got).all()
        assert np.array_equal(got, ref), (
            f"graph {i}: HTTP result differs from the dedicated driver "
            f"(max |diff| {np.abs(got - ref).max():.3e}, "
            f"{np.mean(got != ref):.3%} of coordinates)")
    assert levels["neighbor"] > 0, levels
    _line("b service", graphs=len(graphs), sizes=list(sizes),
          levels_per_mode=json.dumps(dict(levels), separators=(",", ":")),
          compiled_steps_per_mode=json.dumps(steps, separators=(",", ":")),
          compile_s=round(c_svc, 3), wall_s=round(wall, 3),
          latency_s=[o["latency_s"] for o in outs], parity="bit-exact")


# -- phase c: tile serving ------------------------------------------------------

def phase_tiles(exp, queries: int = 16, seed: int = 0):
    """(c): 16 batched viewport queries vs the NumPy reference resolver."""
    from repro.serve import (QueryEngine, build_pyramid, reference_resolve,
                             trim_result)
    from repro.serve.query import random_viewports

    t0 = time.perf_counter()
    pyr = build_pyramid(exp)
    t_build = time.perf_counter() - t0
    eng = QueryEngine(pyr)
    boxes, zs = random_viewports(pyr.lo, pyr.hi, max(b.zoom for b in pyr.bands),
                                 queries, seed=seed)
    t1 = time.perf_counter()
    out = eng.query(boxes, zs)             # first call compiles
    t_cold = time.perf_counter() - t1
    t2 = time.perf_counter()
    eng.query(boxes, zs)
    t_warm = time.perf_counter() - t2
    nonempty = 0
    for i in range(queries):
        got = trim_result(out, i)
        ref = reference_resolve(pyr, boxes[i], int(zs[i]))
        assert got["band"] == ref["band"] and got["covered"] == ref["covered"]
        for k in ("vid", "rep", "inside", "eid", "tiles"):
            assert np.array_equal(got[k], ref[k]), (i, k)
        for k in ("vpos", "epos", "vmass"):
            assert np.array_equal(np.asarray(got[k]).view(np.int32),
                                  np.asarray(ref[k]).view(np.int32)), (i, k)
        nonempty += len(got["vid"]) > 0
    assert nonempty >= queries // 2, nonempty
    _line("c tiles", bands=len(pyr.bands), band_sizes=[b.n for b in pyr.bands],
          queries=queries, nonempty=nonempty, build_s=round(t_build, 3),
          compile_s=round(max(t_cold - t_warm, 0.0), 3),
          wall_s=round(t_cold + t_warm, 3), parity="bit-exact")


# -- phase s: the maxent-stress step against its plain reference ----------------

def capture_levels(edges: np.ndarray, n: int, cfg, modes) -> dict:
    """Lay the graph out with ``cfg`` and keep, for each mode in ``modes``,
    the largest level refined in it: ``{mode: (g, pos, sched, kw)}``, the
    level's padded graph, the drawing its refine step produced (on the
    host), its schedule and the step's keyword arguments.

    The comparison starts from that settled drawing, not from the level's
    start: there the placer leaves vertices on top of one another (10 to
    850 per level at 16,384 vertices), the direction of their mutual
    repulsion is set by rounding, and five iterations from there read up
    to 1.33 ``temp0`` apart on the chip between any two sums of another
    order (PERF.md)."""
    from repro.core import bucketing, multigila_layout

    kept, one = {}, bucketing.refine_level

    def keep(g, pos0, sched, **kw):
        out = one(g, pos0, sched, **kw)
        if sched.mode in modes and (sched.mode not in kept
                                    or g.n > kept[sched.mode][0].n):
            kept[sched.mode] = (g, np.asarray(out), sched, kw)
        return out

    bucketing.refine_level = keep
    try:
        multigila_layout(edges, n, cfg)
    finally:
        bucketing.refine_level = one
    return kept


def stress_step_errors(levels: dict, iters=STRESS_ITERS,
                       build=None) -> dict:
    """``{"<mode>.<iters>": error}``: each captured level's cached stress
    step against ``core/stress_ref.py`` from the same positions, under the
    level's schedule cut to each of ``iters[mode]`` iterations: the largest
    displacement difference over the level's ``temp0``. ``build`` (an
    engine's ``build_refine``) replaces the cached program."""
    import jax.numpy as jnp
    from repro.core import bucketing, stress_ref
    from repro.core.engine import get_engine

    eng = get_engine("stress")
    errs = {}
    for mode, (g, pos, sched, kw) in levels.items():
        valid = np.asarray(g.vmask)
        for it in iters[mode]:
            s = dataclasses.replace(sched, iters=it)
            nbrs = eng.init_state(g, s, kw["seed"])
            _, fn, _, args = bucketing.cached_refine(
                g, pos, s, *nbrs, ideal_len=kw["ideal_len"],
                rep_const=kw["rep_const"])
            if build is not None:
                fn = build(s.mode, s.grid_dim, s.cell_cap)
            got = np.asarray(fn(*args))
            want = np.asarray(stress_ref.refine(
                g, jnp.asarray(pos), s, *nbrs, ideal_len=kw["ideal_len"],
                rep_const=kw["rep_const"]))
            errs[f"{mode}.{it}"] = float(
                np.abs(got - want)[valid].max() / sched.temp0)
    return errs


def phase_stress(n: int = 1 << 17, cfg=None, modes=tuple(STRESS_ITERS)):
    """(s): the stress step of a Delaunay graph's levels vs the reference."""
    from repro.core import LayoutConfig
    from repro.graphs import generators as G

    cfg = cfg or LayoutConfig(engine="stress")
    edges, n = G.delaunay(n, 17)
    t0 = time.perf_counter()
    levels = capture_levels(edges, n, cfg, modes)
    assert set(levels) == set(modes), (sorted(levels), modes)
    errs = stress_step_errors(levels)
    assert all(e <= STRESS_TOL for e in errs.values()), errs
    _line("s stress", graph=f"delaunay_{n}", m=len(edges),
          levels=json.dumps({m: [lv[0].n, lv[2].grid_dim, lv[2].cell_cap]
                             for m, lv in levels.items()},
                            separators=(",", ":")),
          step_err=json.dumps(errs, separators=(",", ":")),
          stress_tol=STRESS_TOL, wall_s=round(time.perf_counter() - t0, 3))
    return errs


# -- --chips 4: the sharded driver ----------------------------------------------

def _one_device_superstep(g, pos0, sched, *, ideal_len: float,
                          rep_const: float, seed: int, min_dist: float = 1e-3,
                          **_):
    """One ``gila.layout_iteration`` of a level on the first device, with the
    neighbor lists ``distributed.run_layout_level`` builds."""
    import jax
    import jax.numpy as jnp
    from repro.core import gila
    from repro.graphs.graph import unique_edges

    if sched.mode == "neighbor":
        idx, mask = gila.khop_neighbors(unique_edges(g), g.n, sched.k,
                                        sched.cap, seed)
        nbr, nmask = gila.pad_neighbors(idx, mask, g.n_pad)
    else:
        nbr = jnp.full((g.n_pad, 1), g.n_pad, jnp.int32)
        nmask = jnp.zeros((g.n_pad, 1), bool)
    step = jax.jit(gila.layout_iteration,
                   static_argnames=("mode", "grid_dim", "cell_cap"))
    params = jnp.asarray([rep_const, ideal_len, min_dist], jnp.float32)
    out = step(g, jnp.asarray(pos0, jnp.float32), nbr, nmask, params,
               jnp.float32(sched.temp0), mode=sched.mode,
               grid_dim=sched.grid_dim, cell_cap=sched.cell_cap)
    return np.asarray(out)


def phase_sharded(side: int = SHARDED_SIDE, chips: int = 4, cfg=None) -> dict:
    """The sharded driver on a (chips, 1) mesh vs the one-chip driver: each
    level's first superstep against the one-device iteration
    (``STEP_TOL``), the arrays of every superstep spread over all chips,
    and the finished layouts' quality (``DIST_BOUND``)."""
    from repro.core import LayoutConfig, distributed, multigila_layout
    from repro.graphs import generators as G
    from repro.graphs.graph import build_graph
    from repro.graphs.metrics import quality_report

    cfg = cfg or LayoutConfig(**SHARDED_CFG)
    edges, n = G.grid(side, side)
    spread = []                        # device counts per superstep's arrays
    step_err = Counter()               # mode -> largest first-superstep error
    cached, run_level = (distributed.cached_layout_step,
                         distributed.run_layout_level)

    def watched(*a, **k):
        jitted, sh, fresh = cached(*a, **k)

        def step(pos, w, nbr, src, *rest):
            out = jitted(pos, w, nbr, src, *rest)
            spread.append((len(pos.sharding.device_set),
                           len(src.sharding.device_set),
                           len(out.sharding.device_set)))
            return out
        return step, sh, fresh

    def checked(mesh, g, pos0, sched, **kw):
        first = dataclasses.replace(sched, iters=1)
        got = run_level(mesh, g, pos0, first, **kw)
        ref = _one_device_superstep(g, pos0, first, **kw)
        valid = np.asarray(g.vmask)
        err = float(np.abs(got - ref)[valid].max() / sched.temp0)
        assert err <= STEP_TOL, (sched.mode, g.n, err)
        step_err[sched.mode] = max(step_err[sched.mode], err)
        return run_level(mesh, g, pos0, sched, **kw)

    distributed.cached_layout_step = watched
    distributed.run_layout_level = checked
    try:
        t0 = time.perf_counter()
        pos_d, st_d = multigila_layout(
            edges, n, dataclasses.replace(cfg, driver="multigila_dist",
                                          mesh_shape=(chips, 1)))
        t_dist = time.perf_counter() - t0
    finally:
        distributed.cached_layout_step = cached
        distributed.run_layout_level = run_level
    t0 = time.perf_counter()
    pos_1, st_1 = multigila_layout(edges, n, cfg)
    t_one = time.perf_counter() - t0

    assert spread and all(s == (chips,) * 3 for s in spread), set(spread)
    assert st_d.level_sizes == st_1.level_sizes
    modes = Counter(_mode_of(nv, cfg) for nv, _ in st_d.level_sizes)
    assert set(step_err) == set(modes) == {"exact", "neighbor", "grid"}, (
        step_err, modes)
    g = build_graph(edges, n)
    pad = ((0, g.n_pad - n), (0, 0))
    reps = {}
    for name, p in (("dist", pos_d), ("single", pos_1)):
        assert np.isfinite(p).all(), name
        reps[name] = quality_report(g, np.pad(p, pad), max_cre_edges=0)
    rel = {k: abs(reps["dist"][k] - reps["single"][k]) / reps["single"][k]
           for k in DIST_BOUND}
    assert all(rel[k] <= DIST_BOUND[k] for k in rel), (rel, reps)
    _line("dist", graph=f"grid_{side}x{side}", n=n, m=len(edges),
          chips=chips, levels=st_d.levels, supersteps_checked=len(spread),
          devices_per_array=chips,
          levels_per_mode=json.dumps(dict(modes), separators=(",", ":")),
          wall_dist_s=round(t_dist, 3), wall_single_s=round(t_one, 3),
          superstep_err=json.dumps(dict(step_err), separators=(",", ":")),
          step_tol=STEP_TOL,
          NELD=f"{reps['dist']['neld']:.4f}/{reps['single']['neld']:.4f}",
          stress=f"{reps['dist']['stress']:.4f}/{reps['single']['stress']:.4f}",
          rel_diff=json.dumps(rel, separators=(",", ":")),
          bound=json.dumps(DIST_BOUND, separators=(",", ":")))
    return rel


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded-driver check on four chips")
    args = ap.parse_args(argv)
    preflight(args.chips)

    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.chips == 4:
        phase_sharded(chips=4)
    else:
        phase_kernels()
        exp = phase_layout()
        phase_service()
        phase_tiles(exp)
        phase_stress()
    import jax
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {"platform": d.platform,
                                             "kind": d.device_kind,
                                             "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
