"""The verdict on a whole run, off the chip: the harness's look for a chip
is skipped and the rest of a run is driven at a size a test can hold,
once as the program is and once for each fault planted under the timed
path (``bench/lib/faults.py``). ``no_refine`` is also the control: the
guarantee that every level is refined, broken.

``unrefined_finest`` (the finest level given no iterations) is caught by
the edge-length spread, which the crossing count alone would miss.

The limits here are for this test size, set as the cell's are: at 2,048
vertices on XLA:CPU (seeds 11-13) the program read at most 2.41
crossings per edge and a NELD of 0.425; the faults at least 38.7
crossings (no_refine, half_batch, altered) or a NELD of 0.70
(unrefined_finest)."""
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

LIMITS = {"crossings_per_edge": {"2048": 8.0}, "neld": {"2048": 0.55}}
CELLS = {"delaunay_n17.offline": dict(sizes=[2048], seconds=2.0)}


def run_cell(monkeypatch, workload: str, fault: str | None) -> dict:
    import jax
    from bench import run as R
    from bench.lib import chip, faults, spec as S
    shape = CELLS[workload]
    cfg0 = S.config

    def config(bench, name, root=S.ROOT):
        c = copy.deepcopy(cfg0(bench, name, root))
        c["sizes"] = shape["sizes"]
        c["limits"] = LIMITS
        return c

    monkeypatch.setattr(S, "config", config)
    monkeypatch.setattr(chip, "require", lambda chips: jax.devices()[:chips])
    # XLA:CPU entries of another machine must not be loaded here
    monkeypatch.setattr(R, "use_compile_cache", lambda: None)
    out = io.StringIO()
    planted = faults.FAULTS[fault] if fault else contextlib.nullcontext
    with planted(), contextlib.redirect_stdout(out):
        assert R.main(["--workload", workload, "--seed", str(2 ** 31 + 11),
                       "--seconds", str(shape["seconds"]),
                       "--trace", "0"]) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_sound_run_is_correct(monkeypatch, workload):
    res = run_cell(monkeypatch, workload, None)
    assert res["correct"] is True, res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "check"
    for v in res["check"].values():
        assert v["value"] <= v["limit"]


@pytest.mark.parametrize("fault", ["no_refine", "half_batch", "altered",
                                   "unrefined_finest"])
@pytest.mark.parametrize("workload", sorted(CELLS))
def test_planted_fault_is_not_correct(monkeypatch, workload, fault):
    res = run_cell(monkeypatch, workload, fault)
    assert res["correct"] is False, (fault, res["check"])
    assert any(v["value"] > v["limit"] for v in res["check"].values())
