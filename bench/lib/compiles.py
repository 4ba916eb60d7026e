"""XLA backend compiles, from JAX's own compile events.

JAX records ``/jax/core/compile/backend_compile_duration`` once for every
program it hands to the backend, whether the backend compiles it or the
persistent cache supplies it. Either inside the measured window means the
warm-up missed a program.
"""
from __future__ import annotations

import threading
import time

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    """Every backend compile of this process: (end time on
    ``time.perf_counter``, seconds, function name)."""

    def __init__(self):
        import jax
        self.events: list[tuple[float, float, str]] = []
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE:
            with self._lock:
                self.events.append((time.perf_counter(), float(duration),
                                    str(kw.get("fun_name", "?"))))

    def between(self, t0: float, t1: float) -> list[tuple[float, float, str]]:
        with self._lock:
            return [e for e in self.events if t0 <= e[0] <= t1]
