"""Platform dispatch for the all-pairs N-body repulsion.

On the chip the Pallas kernel runs compiled; elsewhere the pure-jnp
reference executes (XLA fuses it well on CPU). ``REPRO_PALLAS=interpret``
forces the Pallas kernel through the interpreter (integration tests); see
``repro.kernels.backend``. Any n works: the kernel pads to its blocks.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import backend
from repro.kernels.nbody.kernel import nbody_pallas
from repro.kernels.nbody.ref import nbody_repulsion_ref


def nbody_repulsion(pos, mass, vmask, C, L, min_dist):
    mode = backend()
    if mode == "ref":
        return nbody_repulsion_ref(pos, mass, vmask, C, L, min_dist)
    w = jnp.where(vmask, mass, 0.0).astype(jnp.float32)
    rows = pos.astype(jnp.float32).T                     # [2, n]
    f = nbody_pallas(rows, jnp.concatenate([rows, w[None]], axis=0),
                     C, L, min_dist, interpret=(mode == "interpret"))
    return jnp.where(vmask[:, None], f.T, 0.0)
