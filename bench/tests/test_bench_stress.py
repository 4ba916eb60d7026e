"""The maxent-stress cell (``delaunay_n17_stress.offline``) off the chip:
the span readers it shares with the GiLA cell on stress steps' spans, and
the verdict on a whole run, sound and under each planted fault.

The readers match a refine step by ``mode``, whichever engine ran it, and
need no ``alpha0``/``alpha_decay`` (the annealing a stress step records),
so a program whose stress spans lack them reads the same.

The limits of the whole-run tests are for this test size, set as the
cell's are: at 2,048 vertices on XLA:CPU the stress engine read at most
3.52 crossings per edge and a NELD of 0.540; the faults at least 22.9
crossings (no_refine, half_batch, altered) or, for unrefined_finest, 7.85
crossings and a NELD of 0.796."""
from __future__ import annotations

import contextlib
import copy
import io
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib import spec as S                 # noqa: E402
from bench.lib.drive import Answer, Item        # noqa: E402
from bench.lib.record import Run                # noqa: E402

CELL = "delaunay_n17_stress.offline"
LIMITS = {"crossings_per_edge": {"2048": 6.0}, "neld": {"2048": 0.65}}
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
            engine="stress", alpha=True):
    """One layout's refine spans as the program records them under
    ``engine``: a grid step of ``grid`` = (seconds, iterations), a k-hop
    build, a neighbor step and an exact step. A stress step also records
    its annealing unless ``alpha`` is false."""
    tr, clock = tracer

    def step(mode, secs, n_iters):
        args = dict(mode=mode, engine=engine, fresh=False, iters=n_iters)
        if engine == "stress" and alpha:
            args.update(alpha0=0.05, alpha_decay=0.008 ** (1 / n_iters))
        with tr.span("refine.dispatch", cat="device", **args):
            clock.advance(secs)

    with tr.root("layout", n=131072, m=393000):
        with tr.span("refine.level", level=2, n=1600):
            step("exact", 0.5, 122)
        with tr.span("refine.level", level=1, n=13700):
            with tr.span("refine.khop", cat="host"):
                clock.advance(khop)
            step("neighbor", *neighbor)
        with tr.span("refine.level", level=0, n=131072):
            step("grid", *grid)


def read(name, run):
    return S.reader(name)(run)


def test_the_cell_reports_the_shared_readers():
    bench = S.load()
    names = {m["name"] for m in S.metrics_of(bench, CELL, trace=True)}
    assert set(READERS) <= names


def test_readers_on_two_stress_layouts(tracer):
    lay_out(tracer, grid=(8.0, 50), neighbor=(3.0, 60), khop=0.5)
    lay_out(tracer, grid=(7.0, 50), neighbor=(4.0, 60), khop=0.25)
    run = make_run(2)
    assert read("refine_grid_s.offline", run) == pytest.approx(7.5)
    assert read("grid_iter_ms.offline", run) == pytest.approx(150.0)
    assert read("refine_neighbor_s.offline", run) == pytest.approx(3.5)
    assert read("khop_s.offline", run) == pytest.approx(0.375)


@pytest.mark.parametrize("name", READERS)
def test_a_stress_layout_reads_as_a_gila_layout_with_the_same_spans(
        tracer, name):
    lay_out(tracer, engine="gila")
    gila = read(name, make_run(1))
    lay_out(tracer)
    assert gila is not None
    assert read(name, make_run(2)) == pytest.approx(gila)


@pytest.mark.parametrize("name", READERS)
def test_readers_need_no_annealing_arguments(tracer, name):
    """A program whose stress spans carry no ``alpha0``/``alpha_decay``
    (the program before them) reads the same."""
    lay_out(tracer, alpha=False)
    bare = read(name, make_run(1))
    lay_out(tracer)
    assert bare is not None
    assert read(name, make_run(2)) == pytest.approx(bare)


def run_cell(monkeypatch, fault: str | None) -> dict:
    import jax
    from bench import run as R
    from bench.lib import chip, faults
    cfg0 = S.config

    def config(bench, name, root=S.ROOT):
        c = copy.deepcopy(cfg0(bench, name, root))
        c["sizes"] = [2048]
        c["limits"] = LIMITS
        return c

    monkeypatch.setattr(S, "config", config)
    monkeypatch.setattr(chip, "require", lambda chips: jax.devices()[:chips])
    # XLA:CPU entries of another machine must not be loaded here
    monkeypatch.setattr(R, "use_compile_cache", lambda: None)
    out = io.StringIO()
    planted = faults.FAULTS[fault] if fault else contextlib.nullcontext
    with planted(), contextlib.redirect_stdout(out):
        assert R.main(["--workload", CELL, "--seed", str(2 ** 31 + 11),
                       "--seconds", "2.0", "--trace", "0"]) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_sound_run_is_correct(monkeypatch):
    res = run_cell(monkeypatch, None)
    assert res["correct"] is True, res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    for v in res["check"].values():
        assert v["value"] <= v["limit"]


@pytest.mark.parametrize("fault", ["no_refine", "half_batch", "altered",
                                   "unrefined_finest"])
def test_planted_fault_is_not_correct(monkeypatch, fault):
    res = run_cell(monkeypatch, fault)
    assert res["correct"] is False, (fault, res["check"])
    assert any(v["value"] > v["limit"] for v in res["check"].values())
