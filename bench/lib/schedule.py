"""The stated schedule, checked against the program's own spans.

A configuration states that every level of a layout is refined, each in
the repulsion mode that its size gives: ``exact`` up to
``exact_threshold`` vertices, ``neighbor`` up to ``grid_threshold``,
``grid`` above. With its tracer on (``repro.obs.trace``) the program
records one ``refine.level`` span per level (arguments ``level`` and
``n``) and, inside it, one ``refine.dispatch`` span per refine step
(argument ``mode``). ``levels_unrefined`` counts the breaches in a
window: a level whose span holds no refine step, or more than one, or
one in another mode; and a finished layout without a finest level.
"""
from __future__ import annotations

#: spans nest to the microsecond; the tracer's times are floats
_SLACK_US = 1.0


def mode_for(n: int, layout: dict) -> str:
    if n <= int(layout["exact_threshold"]):
        return "exact"
    if n <= int(layout["grid_threshold"]):
        return "neighbor"
    return "grid"


def levels_unrefined(events: list[dict], layouts: int, layout: dict) -> int:
    """Breaches of the stated schedule among the tracer's ``events``
    (Chrome trace events) of ``layouts`` finished layouts."""
    done = [e for e in events if e.get("ph") == "X"]
    levels = [e for e in done if e["name"] == "refine.level"]
    steps = [e for e in done if e["name"] == "refine.dispatch"]
    bad = 0
    for lv in levels:
        lo, hi = lv["ts"] - _SLACK_US, lv["ts"] + lv["dur"] + _SLACK_US
        inside = [s for s in steps if s["tid"] == lv["tid"]
                  and lo <= s["ts"] and s["ts"] + s["dur"] <= hi]
        want = mode_for(int(lv["args"]["n"]), layout)
        if len(inside) != 1 or inside[0].get("args", {}).get("mode") != want:
            bad += 1
    finest = sum(int(lv["args"]["level"]) == 0 for lv in levels)
    return bad + abs(layouts - finest)
