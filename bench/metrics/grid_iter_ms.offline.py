"""grid_iter_ms.offline: milliseconds per iteration of the grid-mode
refine steps: the summed durations of the program's ``refine.dispatch``
spans with ``mode`` grid over the sum of their ``iters`` (the iteration
count each step's loop runs). Whatever the schedule, this is the number
a change to the grid step moves. Read from
``repro.obs.trace.get_tracer().to_dict()``, which ``bench/run.py`` resets
before the window and never clears after, so it holds the window's spans
when the readers run (``bench/lib/spans.py``); a program whose spans
carry no ``iters`` gives nothing."""
from bench.lib import spans


def read(run):
    return spans.ms_per_iteration(run, "refine.dispatch", mode="grid")
