"""refine_grid_s.offline: seconds per finished layout in the grid-mode
refine steps: the program's ``refine.dispatch`` spans with ``mode`` grid
(the dispatch and its existing ``block_until_ready``), summed. Read from
``repro.obs.trace.get_tracer().to_dict()``, which ``bench/run.py`` resets
before the window and never clears after, so it holds the window's spans
when the readers run (``bench/lib/spans.py``)."""
from bench.lib import spans


def read(run):
    return spans.seconds_per_layout(run, "refine.dispatch", mode="grid")
