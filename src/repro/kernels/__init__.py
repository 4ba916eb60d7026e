"""Pallas TPU kernels for the compute hot spots the paper optimizes:

  nbody/          — every row vertex against every source: the exact
                    all-pairs FR repulsion (the single-level hot spot,
                    paper §3.4) and the grid mode's far field
  neighbor_force/ — each group of rows against its own partner list: the
                    k-hop neighbor-list repulsion (GiLA locality) and the
                    grid mode's exact 3×3 near field
  grid_force/     — grid-bucketed approximate repulsion (flat Barnes–Hut):
                    binning and composition over the two kernels above
  flash_attention/— blocked causal attention for the LM architecture zoo

Each force subpackage has kernel.py (pl.pallas_call over lane-major
coordinate planes), ops.py (the op the layout engine calls, dispatching on
``backend()``) and ref.py (the pure-jnp oracle). Kernels are validated on
the CPU with interpret=True and compiled for the chip in
tests/test_tpu_compile.py.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

BACKENDS = ("pallas", "interpret", "ref")


def backend() -> str:
    """The kernel backend the NEXT trace bakes in.

    ``REPRO_PALLAS=pallas|interpret|ref`` selects it. Unset (or ``auto``),
    the chip runs the compiled Pallas kernels and every other platform the
    jnp oracles. Any other value is an error, never a silent default. The
    result is part of every compile-cache key (``bucketing.kernel_backend``).
    """
    env = os.environ.get("REPRO_PALLAS", "auto")
    if env in BACKENDS:
        return env
    if env != "auto":
        raise ValueError(f"REPRO_PALLAS={env!r}: expected one of "
                         f"{BACKENDS} or 'auto'")
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def varying_axes(*arrays) -> frozenset:
    """Mesh axes a kernel's output varies over inside ``shard_map`` (the
    union of its inputs'; empty outside). Declared on ``out_shape``."""
    return frozenset().union(*(jax.typeof(a).vma for a in arrays))


def batch_native(call):
    """Lift ``call`` — a kernel launch whose every argument carries a
    leading problem axis — to one problem, with ``jax.vmap`` feeding the
    batch straight into that axis.

    Pallas's own batching rule would prepend a squeezed block dimension,
    and the chip refuses such blocks in SMEM, where these kernels keep
    their scalars. Unbatched arguments are broadcast to the batch."""
    @jax.custom_batching.custom_vmap
    def one(*args):
        return call(*[a[None] for a in args])[0]

    @one.def_vmap
    def _batched(axis_size, in_batched, *args):
        args = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args, in_batched)]
        return call(*args), True

    return one
