"""layout_s: seconds per whole layout, warm. Layouts run back to back from
the window's open; the last one runs to its end. Total elapsed time over
the number of layouts (host clock)."""


def read(run):
    if not run.items:
        return None
    return (run.t1 - run.t0) / len(run.items)
