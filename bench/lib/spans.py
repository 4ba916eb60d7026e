"""The program's own spans in the window, as the span readers see them.

``bench/run.py`` resets the program's tracer (``repro.obs.trace``) just
before the window opens, turns it on, and never clears it after: when the
readers run, ``get_tracer().to_dict()`` still holds every span the
window's layouts recorded, and only those. A span is a Chrome trace event
(``ph`` ``X``, ``dur`` in microseconds, the span's arguments in
``args``).

A program that records no such span (one older than the span, or one
whose layouts never reach it) gives ``None``, not zero: the metric is
then left out of the result line.
"""
from __future__ import annotations


def window(name: str, **match) -> list[dict]:
    """The window's finished spans called ``name`` whose arguments hold
    every ``match`` value."""
    from repro.obs import trace
    return [e for e in trace.get_tracer().to_dict()["traceEvents"]
            if e.get("ph") == "X" and e["name"] == name
            and all(e.get("args", {}).get(k) == v for k, v in match.items())]


def seconds_per_layout(run, name: str, **match) -> float | None:
    """Summed seconds of the matching spans over the finished layouts."""
    done = run.finished()
    found = window(name, **match)
    if not done or not found:
        return None
    return sum(e["dur"] for e in found) / 1e6 / done


def ms_per_iteration(run, name: str, **match) -> float | None:
    """Summed milliseconds of the matching spans over the summed
    iterations their ``iters`` argument records."""
    found = window(name, **match)
    if not run.finished() or not found or \
            any("iters" not in e.get("args", {}) for e in found):
        return None
    iters = sum(int(e["args"]["iters"]) for e in found)
    return sum(e["dur"] for e in found) / 1e3 / iters if iters else None
