"""place_s.offline: seconds in the place phase (Solar Placer) per layout,
from the program's gila_phase_seconds_total{phase=place} over the
window."""


def read(run):
    return run.phase("place") / run.finished() if run.items else None
