"""device_idle.offline: share of the traced window in which no operation
ran on the chip, 100 * (1 - busy / window), busy being the union of the
device's operation intervals (profiler trace)."""


def read(run):
    p = run.profile
    if not p or p["window_s"] <= 0 or p["device_count"] == 0:
        return None
    return 100.0 * (1.0 - p["busy_s"] / p["window_s"])
