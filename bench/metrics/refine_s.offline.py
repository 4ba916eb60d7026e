"""refine_s.offline: seconds in the refine steps per layout, from
the program's gila_phase_seconds_total{phase=refine} plus {phase=compile}
over the window. The host k-hop list build of the neighbor mode is inside
the refine phase."""


def read(run):
    done = run.finished()
    if not done:
        return None
    return (run.phase("refine") + run.phase("compile")) / done
