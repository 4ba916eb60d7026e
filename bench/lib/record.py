"""What one run recorded, as the metric readers see it."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Run:
    setup_s: float                # process start -> window open
    t0: float                     # window open (perf_counter)
    t1: float                     # window close: the last answer
    items: list                   # drive.Item, every request of the window
    phases: dict                  # gila_phase_seconds_total delta, window
    compiles: list                # backend compiles inside the window
    profile: dict | None = None   # profile.read() of a --trace 1 run

    def phase(self, name: str) -> float:
        return float(self.phases.get(name, 0.0))

    def finished(self) -> int:
        """The requests whose work the window's phase counters hold: every
        answered one (the counters are read once the last has answered)."""
        return sum(it.answer is not None and it.answer.ok
                   for it in self.items)
