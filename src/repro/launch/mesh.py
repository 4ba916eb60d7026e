"""Mesh construction and the per-chip peak rates.

Functions (not module-level constants) so importing never touches jax
device state; dryrun.py sets XLA_FLAGS for 512 placeholder devices BEFORE
importing jax and then calls these.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """A mesh over the first prod(shape) devices with every axis ``Auto``:
    the layout code shards through ``shard_map`` and ``NamedSharding``, not
    through sharding-in-types (``jax.make_mesh`` now defaults to
    ``Explicit``)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Mesh over whatever devices exist (CPU tests / local runs)."""
    n = jax.device_count()
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))


# Published per-chip peaks, keyed by ``jax.Device.device_kind``. Source:
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM
# at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect over four links
# (50 GB/s per link and direction).
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9,
                    "hbm_bytes": 16e9},
}

#: the chip the production mesh and the dry-run roofline are sized for
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str = TARGET_KIND) -> dict:
    """Peak rates of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
