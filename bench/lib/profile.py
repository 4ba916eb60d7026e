"""The profiler trace of a ``--trace 1`` run, reduced to numbers.

The run records the measured window with ``jax.profiler`` (host tracer
at its lowest level, which keeps the benchmark's annotations, Python
tracer off, so the window's host cost stays near an untraced run's). The reduction reads the XSpace file with ``jax.profiler.
ProfileData``:

* device planes are those named ``/device:TPU:<i>``; their ``XLA Ops``
  line holds one event per operation executed on the chip, named by the
  operation's HLO text (``%nbody_pallas.1 = f32[...] custom-call(...)``),
  which ``op_name`` cuts to the instruction's name (``nbody_pallas.1``);
* when its trace buffers fill, the chip drops operation events: the
  plane's ``dropped_traces`` statistic counts them and its ``XLA
  TraceMe`` line holds a ``Trace Buffers Dropped`` event over the
  stretch in which they were lost. A single refine step at 131,072
  vertices makes some ten million events and fills the buffers within
  seconds (PERF.md). The chip made those events, so it was running
  operations there: busy time counts a dropped stretch as busy, and no
  operation's own time can be read inside it;
* busy time is the union of the operation intervals and the dropped
  stretches inside the window, averaged over the chips; the idle share
  is ``1 - busy / window``;
* an operation's device time is the summed duration of its events (a
  kernel's reader sums the operations that carry the kernel's name);
* the window is the benchmark's own ``bench.window`` annotation on a host
  plane, which also ties the host's monotonic clock to the trace's.
"""
from __future__ import annotations

import contextlib
import glob
import os
from collections import Counter

import numpy as np

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
TRACEME_LINE = "XLA TraceMe"
DROPPED = "Trace Buffers Dropped"
DROPPED_STAT = "dropped_traces"
WINDOW_SPAN = "bench.window"


@contextlib.contextmanager
def capture(log_dir: str):
    """Trace the block into ``log_dir`` (host tracer only)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def op_name(event_name: str) -> str:
    """The HLO instruction's name out of an operation event's name."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def xplane_file(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def union_seconds(intervals: np.ndarray) -> float:
    """Length of the union of ``[start, end)`` intervals (ns, shape
    ``[k, 2]``), in seconds."""
    if len(intervals) == 0:
        return 0.0
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    end = np.maximum.accumulate(iv[:, 1])
    # a new run of overlapping intervals starts where the next start lies
    # past every earlier end
    new = np.concatenate([[True], iv[1:, 0] > end[:-1]])
    starts = iv[new, 0]
    ends = np.concatenate([end[:-1][new[1:]], end[-1:]])
    return float((ends - starts).sum()) / 1e9


def gaps(intervals: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """The idle stretches ``[k, 2]`` of ``[lo, hi)`` left by ``intervals``."""
    if len(intervals) == 0:
        return np.asarray([[lo, hi]], np.int64)
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    end = np.maximum.accumulate(iv[:, 1])
    g0 = np.concatenate([[lo], end])
    g1 = np.concatenate([iv[:, 0], [hi]])
    keep = g1 > g0
    return np.stack([g0[keep], g1[keep]], 1).astype(np.int64)


def read(path: str) -> dict:
    """Reduce one trace file (see the module docstring)."""
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path))


def _clip(events, lo: int, hi: int):
    for ev in events:
        s, e = int(ev.start_ns), int(ev.end_ns)
        if e > lo and s < hi:
            yield max(s, lo), min(e, hi), ev


def reduce(pd) -> dict:
    """Reduce one ``jax.profiler.ProfileData``.

    Returns ``window_ns`` (trace-clock bounds of the
    ``bench.window`` annotation), ``window_s``, ``busy_s`` (mean over the
    chips), ``device_count``, ``ops`` (seconds per operation name, summed
    over chips), ``dropped_s`` (seconds of dropped stretches, summed over
    chips) and ``dropped_events`` (events the chips dropped), ``idle``
    (the idle stretches of the first chip, trace-clock ns) and ``host``
    (the benchmark's own annotations overlapping the window, as
    ``(start_ns, end_ns, name)``)."""
    window = None
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            devices.append(plane)
            continue
        for line in plane.lines:
            # only the benchmark's own annotations are kept: the host lines
            # also hold millions of runtime events, too many to name gaps by
            for ev in line.events:
                name = ev.name
                if not name.startswith("bench."):
                    continue
                if name == WINDOW_SPAN:
                    window = (int(ev.start_ns), int(ev.end_ns))
                else:
                    host.append((int(ev.start_ns), int(ev.end_ns), name))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    lo, hi = window
    ops, busy, idle = Counter(), [], None
    dropped_s, dropped_events = 0.0, 0
    for plane in sorted(devices, key=lambda p: p.name):
        rows = []
        dropped_events += sum(int(v) for k, v in plane.stats
                              if k == DROPPED_STAT)
        for line in plane.lines:
            if line.name == OPS_LINE:
                for s, e, ev in _clip(line.events, lo, hi):
                    rows.append((s, e))
                    ops[op_name(ev.name)] += (e - s) / 1e9
            elif line.name == TRACEME_LINE:
                for s, e, ev in _clip(line.events, lo, hi):
                    if ev.name == DROPPED:
                        rows.append((s, e))
                        dropped_s += (e - s) / 1e9
        iv = np.asarray(rows, np.int64).reshape(-1, 2)
        busy.append(union_seconds(iv))
        if idle is None:
            idle = gaps(iv, lo, hi)
    return {
        "window_ns": window,
        "window_s": (hi - lo) / 1e9,
        "busy_s": float(np.mean(busy)) if busy else 0.0,
        "device_count": len(devices),
        "ops": dict(ops),
        "dropped_s": dropped_s,
        "dropped_events": dropped_events,
        "idle": idle if idle is not None else np.zeros((0, 2), np.int64),
        "host": [h for h in host if h[1] > lo and h[0] < hi],
    }


def name_gaps(idle: np.ndarray, spans: list[tuple[int, int, str]],
              top: int = 10) -> list[list]:
    """Attribute each idle stretch to what the host was doing: the
    innermost (shortest) host span that covers the stretch's midpoint,
    else ``"no span"``. Returns the ``top`` names by idle seconds."""
    out = Counter()
    if len(spans):
        sp = np.asarray([(s, e) for s, e, _ in spans], np.int64)
        names = [n for _, _, n in spans]
        dur = sp[:, 1] - sp[:, 0]
    for g0, g1 in idle:
        mid = (int(g0) + int(g1)) // 2
        label = "no span"
        if len(spans):
            cover = np.nonzero((sp[:, 0] <= mid) & (sp[:, 1] >= mid))[0]
            if cover.size:
                label = names[int(cover[np.argmin(dur[cover])])]
        out[label] += (int(g1) - int(g0)) / 1e9
    return [[k, v] for k, v in out.most_common(top)]
