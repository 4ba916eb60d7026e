"""coarsen_s.offline: seconds in the coarsen phase (on-device Solar Merger
rounds and the per-level compaction) per layout, from
the program's gila_phase_seconds_total{phase=coarsen} over the window."""


def read(run):
    done = run.finished()
    return run.phase("coarsen") / done if done else None
