"""khop_s.offline: seconds per finished layout in the neighbor mode's
host k-hop list build: the program's ``refine.khop`` spans, summed. The
chip idles through it. Read from
``repro.obs.trace.get_tracer().to_dict()``, which ``bench/run.py`` resets
before the window and never clears after, so it holds the window's spans
when the readers run (``bench/lib/spans.py``); a program without the
span gives nothing."""
from bench.lib import spans


def read(run):
    return spans.seconds_per_layout(run, "refine.khop")
