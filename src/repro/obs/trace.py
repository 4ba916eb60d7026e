"""Structured span tracer with Chrome/Perfetto trace-event export
(DESIGN.md §12).

One process-wide ``Tracer`` collects *spans* (named, nested intervals),
*instant events* (point markers — the engine's scheduling log rides the
same timeline as device dispatch spans), and *counter samples* (queue
depth over time). ``export`` writes the Chrome trace-event JSON that
``chrome://tracing`` and https://ui.perfetto.dev load directly, so a
``--trace out.json`` run of any driver becomes a zoomable timeline in
which host coarsening, placement, device refine dispatches, and engine
waves are visually overlaid — the measurement ROADMAP items 1 and 5
stall on.

Design constraints, in order:

  * **~zero cost when disabled.** Every hook checks one attribute and
    returns a single shared ``nullcontext`` — no allocation, no clock
    read, no lock. The pipeline benchmark asserts the enabled overhead
    too (< 2% warm wall clock, EXPERIMENTS.md §Observability).
  * **Time through the Clock seam only** (obs/clock.py). Under a
    ``VirtualClock`` the same scripted service run replays to a
    byte-identical trace file: timestamps are virtual, the pid is fixed,
    and tids are assigned from thread-NAME first-appearance order rather
    than OS thread ids (tests/test_obs.py).
  * **Thread-aware.** Events record the emitting thread's name, so the
    engine worker thread (named ``engine-worker``) and the caller thread
    render as separate tracks.

Spans must close on the thread that opened them (the usual
``with span(...)`` shape guarantees it); cross-thread intervals are
emitted with explicit times via ``complete``.

**Identity.** Every span carries ``span_id`` and, when it has one,
``parent_id`` (the span open around it on the same thread, or the
explicit ``parent=`` of ``complete``) among its args. A span opened with
``root`` starts a new ``layout_id`` — its own span id — which every span
recorded on that thread while it is open carries. Ids come from a
per-``Tracer`` counter that ``reset`` clears, so a replay under
``VirtualClock`` exports the same ids.

**The profiler mirror.** While the tracer is enabled, each ``span``
also opens a ``jax.profiler.TraceAnnotation`` of the same name (the
``annotation`` hook, imported lazily by ``enable`` so this package never
imports jax at module level). A profiler capture then holds the
program's spans on the profiler's own host clock, over the device
operations they launched.
"""
from __future__ import annotations

import contextlib
import json
import threading

from repro.obs.clock import Clock, SystemClock

# the shared do-nothing context manager: the disabled-tracer fast path
# returns THIS object every time (identity-asserted in tests/test_obs.py)
_NULL = contextlib.nullcontext()


def _json_safe(v):
    """Clamp span/instant args to JSON-able values (tuples → lists,
    anything exotic → ``str``) so export never throws mid-benchmark."""
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    if isinstance(v, (list, tuple)):
        return [_json_safe(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _json_safe(x) for k, x in v.items()}
    return str(v)


class _Span:
    """Context object for one open span; created only when tracing is ON."""

    __slots__ = ("_tr", "_name", "_cat", "_args", "_t0", "_ann", "_root",
                 "id", "layout_id")

    def __init__(self, tr: "Tracer", name: str, cat: str, args: dict,
                 root: bool = False):
        self._tr = tr
        self._name = name
        self._cat = cat
        self._args = args
        self._root = root

    def __enter__(self):
        tr = self._tr
        stack = tr._stack()
        parent = stack[-1] if stack else None
        ids = tr._ids(parent.id if parent is not None else None,
                      parent.layout_id if parent is not None else None,
                      root=self._root)
        self.id, self.layout_id = ids["span_id"], ids.get("layout_id")
        self._args = {**self._args, **ids}
        stack.append(self)
        ann = tr.annotation
        self._ann = ann(self._name) if ann is not None else None
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = tr.clock.now()
        return self

    def __exit__(self, *exc):
        tr = self._tr
        t1 = tr.clock.now()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        tr._stack().pop()
        tr._append("X", self._name, self._cat, self._t0, t1 - self._t0,
                   self._args)
        return False


class Tracer:
    """Span/instant/counter collector bound to one ``Clock``.

    The module-level ``TRACER`` is the process default (SystemClock,
    disabled); tests and the sim rig construct their own on a
    ``VirtualClock``. All mutation is lock-protected — hooks fire from
    the engine worker thread and the caller thread concurrently.
    """

    def __init__(self, clock: Clock | None = None, *, enabled: bool = False):
        self.clock = clock or SystemClock()
        self.enabled = False
        #: ``name -> context manager`` opened around every span while
        #: enabled (the profiler mirror); None mirrors nothing
        self.annotation = None
        self._lock = threading.Lock()
        self._local = threading.local()     # per-thread stack of open spans
        # (ph, name, cat, t_seconds, dur_seconds, thread_name, args)
        self._events: list[tuple] = []
        self._last_id = 0
        if enabled:
            self.enable()

    # -- control ---------------------------------------------------------------
    def enable(self, clock: Clock | None = None) -> None:
        if clock is not None:
            self.clock = clock
        if self.annotation is None:
            # imported here: obs/ imports no jax at module level
            from jax.profiler import TraceAnnotation
            self.annotation = TraceAnnotation
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def reset(self) -> None:
        with self._lock:
            self._events.clear()
            self._last_id = 0

    def _ids(self, parent: int | None, layout: int | None, *,
             root: bool = False) -> dict:
        """A new span's identity args: its ``span_id``, ``parent_id`` and
        ``layout_id`` (``root``: its own id), the last two where set."""
        with self._lock:
            self._last_id += 1
            sid = self._last_id
        ids = {"span_id": sid}
        if parent is not None:
            ids["parent_id"] = parent
        if root or layout is not None:
            ids["layout_id"] = sid if root else layout
        return ids

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    # -- hooks (each is a no-op returning shared state when disabled) ----------
    def span(self, name: str, cat: str = "", **args):
        """``with tracer.span("coarsen", level=3): ...`` — a nested
        interval on the calling thread's track."""
        if not self.enabled:
            return _NULL
        return _Span(self, name, cat, args)

    def root(self, name: str, cat: str = "", **args):
        """``with tracer.root("layout", n=n): ...`` — a span that starts a
        new ``layout_id`` (its own span id), carried by every span
        recorded on this thread while it is open."""
        if not self.enabled:
            return _NULL
        return _Span(self, name, cat, args, root=True)

    def complete(self, name: str, t0: float, t1: float, cat: str = "",
                 parent: int | None = None, **args) -> int | None:
        """A finished interval with explicit clock-frame times — for
        spans whose bounds were observed elsewhere (request lifetimes,
        per-lane shares of a fused group dispatch). Its parent is
        ``parent`` (a span id this tracer returned) or else the span open
        around the call on this thread, whose ``layout_id`` it carries.
        Returns the new span's id."""
        if not self.enabled:
            return None
        stack = self._stack()
        top = stack[-1] if stack else None
        if parent is None and top is not None:
            parent = top.id
        ids = self._ids(parent, top.layout_id if top is not None else None)
        self._append("X", name, cat, float(t0), float(t1) - float(t0),
                     {**args, **ids})
        return ids["span_id"]

    def instant(self, name: str, ts: float | None = None, cat: str = "",
                **args) -> None:
        if not self.enabled:
            return
        t = self.clock.now() if ts is None else float(ts)
        self._append("i", name, cat, t, None, args)

    def counter(self, name: str, value, ts: float | None = None) -> None:
        """One sample of a time-series counter track (e.g. queue depth)."""
        if not self.enabled:
            return
        t = self.clock.now() if ts is None else float(ts)
        self._append("C", name, "", t, None, {"value": value})

    def _append(self, ph: str, name: str, cat: str, t: float,
                dur: float | None, args: dict) -> None:
        ev = (ph, name, cat, t, dur, threading.current_thread().name,
              {k: _json_safe(v) for k, v in args.items()} if args else None)
        with self._lock:
            self._events.append(ev)

    # -- export ----------------------------------------------------------------
    def to_dict(self) -> dict:
        """Chrome trace-event JSON object. Deterministic by construction:
        ``pid`` is always 1 (never ``os.getpid()``), ``tid`` is the
        first-appearance rank of the thread NAME, timestamps are the
        recorded clock readings in µs rounded to ns."""
        with self._lock:
            events = list(self._events)
        tids: dict[str, int] = {}
        out = [{"ph": "M", "pid": 1, "tid": 0, "name": "process_name",
                "args": {"name": "repro"}}]
        body = []
        for ph, name, cat, t, dur, tname, args in events:
            tid = tids.get(tname)
            if tid is None:
                tid = tids[tname] = len(tids) + 1
                out.append({"ph": "M", "pid": 1, "tid": tid,
                            "name": "thread_name", "args": {"name": tname}})
            ev = {"ph": ph, "pid": 1, "tid": tid, "name": name,
                  "ts": round(t * 1e6, 3)}
            if cat:
                ev["cat"] = cat
            if ph == "X":
                ev["dur"] = round((dur or 0.0) * 1e6, 3)
            if ph == "i":
                ev["s"] = "t"                   # thread-scoped instant
            if args:
                ev["args"] = args
            body.append(ev)
        return {"traceEvents": out + body, "displayTimeUnit": "ms"}

    def json_bytes(self) -> bytes:
        return json.dumps(self.to_dict(), sort_keys=True,
                          separators=(",", ":")).encode("utf-8")

    def export(self, path: str) -> str:
        with open(path, "wb") as f:
            f.write(self.json_bytes())
        return path


# -- the process-default tracer and its module-level hook surface --------------

TRACER = Tracer()


def get_tracer() -> Tracer:
    return TRACER


def span(name: str, cat: str = "", **args):
    return _NULL if not TRACER.enabled else _Span(TRACER, name, cat, args)


def root(name: str, cat: str = "", **args):
    return TRACER.root(name, cat, **args)


def complete(name: str, t0: float, t1: float, cat: str = "",
             parent: int | None = None, **args) -> int | None:
    return TRACER.complete(name, t0, t1, cat, parent, **args)


def instant(name: str, ts: float | None = None, cat: str = "", **args) -> None:
    TRACER.instant(name, ts, cat, **args)


def counter(name: str, value, ts: float | None = None) -> None:
    TRACER.counter(name, value, ts)


def enable(clock: Clock | None = None) -> None:
    TRACER.enable(clock)


def disable() -> None:
    TRACER.disable()


def reset() -> None:
    TRACER.reset()


def export(path: str) -> str:
    return TRACER.export(path)
