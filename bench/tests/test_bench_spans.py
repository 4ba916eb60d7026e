"""The readers of the program's spans (``bench/metrics/*``, through
``bench/lib/spans.py``) on hand-built tracers and runs, and what the
spans record under a planted schedule cut (``bench/lib/faults.py``)."""
from __future__ import annotations

import contextlib
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib import spec as S                 # noqa: E402
from bench.lib.drive import Answer, Item        # noqa: E402
from bench.lib.record import Run                # noqa: E402

READERS = ["refine_grid_s.offline", "grid_iter_ms.offline",
           "refine_neighbor_s.offline", "khop_s.offline"]


def make_run(layouts: int) -> Run:
    items = [Item(index=i, seed=i, graph=0, done=1.0,
                  answer=Answer(True, None)) for i in range(layouts)]
    return Run(setup_s=1.0, t0=0.0, t1=1.0, items=items, phases={},
               compiles=[])


@pytest.fixture
def tracer(monkeypatch):
    """The process tracer, replaced for the test by a fresh one on a
    virtual clock."""
    from repro.obs import trace
    from repro.obs.clock import VirtualClock
    clock = VirtualClock()
    tr = trace.Tracer(clock=clock, enabled=True)
    tr.annotation = None
    monkeypatch.setattr(trace, "TRACER", tr)
    return tr, clock


def lay_out(tracer, grid=(2.5, 50), neighbor=(0.125, 60), khop=0.25,
            iters=True):
    """One layout's refine spans as the program records them: a grid
    step of ``grid`` = (seconds, iterations), a k-hop build, a neighbor
    step and an exact step."""
    tr, clock = tracer

    def step(mode, secs, n_iters):
        args = dict(mode=mode, engine="gila", fresh=False)
        if iters:
            args["iters"] = n_iters
        with tr.span("refine.dispatch", cat="device", **args):
            clock.advance(secs)

    with tr.root("layout", n=131072, m=393000):
        with tr.span("refine.level", level=2, n=1600):
            step("exact", 0.5, 122)
        with tr.span("refine.level", level=1, n=13700):
            if khop is not None:
                with tr.span("refine.khop", cat="host"):
                    clock.advance(khop)
            step("neighbor", *neighbor)
        with tr.span("refine.level", level=0, n=131072):
            step("grid", *grid)


def read(name, run):
    return S.reader(name)(run)


def test_readers_on_one_layout(tracer):
    lay_out(tracer)
    run = make_run(1)
    assert read("refine_grid_s.offline", run) == pytest.approx(2.5)
    assert read("grid_iter_ms.offline", run) == pytest.approx(50.0)
    assert read("refine_neighbor_s.offline", run) == pytest.approx(0.125)
    assert read("khop_s.offline", run) == pytest.approx(0.25)


def test_readers_average_over_finished_layouts(tracer):
    lay_out(tracer, grid=(2.0, 50), khop=0.25)
    lay_out(tracer, grid=(1.5, 25), khop=0.5)
    run = make_run(2)
    assert read("refine_grid_s.offline", run) == pytest.approx(1.75)
    # iterations weigh the steps: 3.5 s over 75 iterations
    assert read("grid_iter_ms.offline", run) == pytest.approx(3500 / 75)
    assert read("khop_s.offline", run) == pytest.approx(0.375)


def test_grid_iteration_times_fifty_is_the_grid_step(tracer):
    lay_out(tracer, grid=(62.4, 50))
    run = make_run(1)
    assert read("grid_iter_ms.offline", run) * 50 / 1e3 == pytest.approx(
        read("refine_grid_s.offline", run))


@pytest.mark.parametrize("name", READERS)
def test_no_finished_layout_reads_nothing(tracer, name):
    lay_out(tracer)
    assert read(name, make_run(0)) is None


@pytest.mark.parametrize("name", READERS)
def test_an_empty_window_reads_nothing(tracer, name):
    assert read(name, make_run(1)) is None


def test_a_program_without_the_new_spans_reads_only_what_it_has(tracer):
    """A program whose steps carry no ``iters`` and which records no
    ``refine.khop`` (the program before these spans) gives no value for
    those two readers, and raises nothing."""
    lay_out(tracer, iters=False, khop=None)
    run = make_run(1)
    assert read("grid_iter_ms.offline", run) is None
    assert read("khop_s.offline", run) is None
    assert read("refine_grid_s.offline", run) == pytest.approx(2.5)


def test_half_finest_shows_in_the_finest_levels_span():
    """Under ``faults.half_finest`` the finest level's refine step records
    half the stated iterations; every coarser level runs as stated."""
    from bench.lib import faults
    from repro.core import LayoutConfig, multigila_layout
    from repro.graphs import generators as G
    from repro.obs import trace

    e, n = G.delaunay(2048, 5)
    cfg = LayoutConfig(exact_threshold=512, seed=4)

    def finest_and_coarser(planted):
        tr = trace.get_tracer()
        tr.reset()
        tr.enable()
        try:
            with planted():
                multigila_layout(e, n, cfg)
        finally:
            tr.disable()
        evs = [x for x in tr.to_dict()["traceEvents"] if x["ph"] == "X"]
        tr.reset()
        level_of = {x["args"]["span_id"]: x["args"]["level"] for x in evs
                    if x["name"] == "refine.level"}
        return {level_of[x["args"]["parent_id"]]: x["args"]["iters"]
                for x in evs if x["name"] == "refine.dispatch"}

    sound = finest_and_coarser(contextlib.nullcontext)
    cut = finest_and_coarser(faults.half_finest)
    assert sound[0] == cfg.finest_iters
    assert cut[0] == cfg.finest_iters // 2
    assert {k: v for k, v in cut.items() if k} == \
        {k: v for k, v in sound.items() if k}
