"""The checks that the plain reference and the program's spans make of an
answer, on made-up inputs: the stated schedule (``bench/lib/schedule.py``)
and the edge-length spread (``bench/lib/reference.neld``)."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib import reference, schedule    # noqa: E402

LAYOUT = {"exact_threshold": 2048, "grid_threshold": 32768}
#: one layout's levels, finest first: (level, n, mode)
LEVELS = [(0, 131072, "grid"), (1, 13700, "neighbor"), (2, 1600, "exact"),
          (3, 190, "exact")]


def events(levels=LEVELS, drop=None, mode=None, extra=None, tid=1):
    """Chrome trace events as the program's tracer writes them: a
    ``refine.level`` span per level holding one ``refine.dispatch``."""
    out, t = [], 0.0
    for level, n, m in reversed(levels):
        out.append({"ph": "X", "name": "refine.level", "ts": t, "dur": 100.0,
                    "tid": tid, "args": {"level": level, "n": n}})
        if level != drop:
            out.append({"ph": "X", "name": "refine.dispatch", "ts": t + 10,
                        "dur": 80.0, "tid": tid,
                        "args": {"mode": mode if level == extra else m}})
        t += 200.0
    return out


def test_sound_schedule_has_no_breach():
    assert schedule.levels_unrefined(events(), 1, LAYOUT) == 0


def test_modes_follow_the_stated_thresholds():
    for _, n, m in LEVELS:
        assert schedule.mode_for(n, LAYOUT) == m
    assert schedule.mode_for(2048, LAYOUT) == "exact"
    assert schedule.mode_for(32768, LAYOUT) == "neighbor"
    assert schedule.mode_for(32769, LAYOUT) == "grid"


@pytest.mark.parametrize("level", [0, 1, 3])
def test_a_level_without_its_refine_step_is_a_breach(level):
    assert schedule.levels_unrefined(events(drop=level), 1, LAYOUT) == 1


def test_a_step_in_another_mode_is_a_breach():
    ev = events(mode="exact", extra=0)
    assert schedule.levels_unrefined(ev, 1, LAYOUT) == 1


def test_a_layout_without_its_finest_level_is_a_breach():
    ev = events(LEVELS[1:])
    assert schedule.levels_unrefined(ev, 1, LAYOUT) == 1
    assert schedule.levels_unrefined(events() + events(tid=2), 2,
                                     LAYOUT) == 0


def test_a_second_step_in_one_level_is_a_breach():
    ev = events()
    ev.append(dict(ev[1], ts=ev[1]["ts"] + 1.0, dur=2.0))
    assert schedule.levels_unrefined(ev, 1, LAYOUT) == 1


def test_neld_of_even_and_uneven_drawings():
    square = np.asarray([[0, 0], [1, 0], [1, 1], [0, 1]], np.float64)
    ring = np.asarray([[0, 1], [1, 2], [2, 3], [0, 3]])
    assert reference.neld(square, ring, 4) == 0.0
    stretched = square * [3.0, 1.0]
    lengths = np.asarray([3.0, 1.0, 3.0, 1.0])
    assert reference.neld(stretched, ring, 4) == pytest.approx(
        lengths.std() / lengths.mean())


@pytest.mark.parametrize("bad", ["nan", "rows"])
def test_neld_of_a_broken_answer_is_infinite(bad):
    pos = np.zeros((4, 2))
    if bad == "nan":
        pos[2, 0] = np.nan
    else:
        pos = pos[:3]
    assert reference.neld(pos, np.asarray([[0, 1]]), 4) == float("inf")
