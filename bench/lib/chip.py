"""The device the run measures, and the guard that keeps it on the chip."""
from __future__ import annotations

import sys


class NoChip(SystemExit):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require(chips: int):
    """The first ``chips`` TPU devices; exits non-zero, printing no
    result, when the first device is not a TPU or there are too few."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"bench: JAX found no devices: {e}", file=sys.stderr)
        raise NoChip(2)
    if devs[0].platform != "tpu":
        print(f"bench: no TPU: JAX's first device is {devs[0].platform} "
              f"({devs[0].device_kind}); the benchmark measures the chip "
              "only", file=sys.stderr)
        raise NoChip(2)
    if len(devs) < chips:
        print(f"bench: the cell needs {chips} TPU chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        raise NoChip(2)
    return devs[:chips]


def memory_peak_bytes(devs) -> int:
    """``peak_bytes_in_use`` of the fullest chip (0 where not reported)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks, default=0))


def describe(devs) -> dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
