"""Platform dispatch for the neighbor-list repulsion.

The irregular gather of neighbor positions happens in XLA, straight into
the kernel's lane-major partner planes ``[3, K, n]`` (x, y, weight); the
sentinel index n reads a zero row, so masked slots contribute nothing.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import backend
from repro.kernels.neighbor_force.kernel import neighbor_pallas
from repro.kernels.neighbor_force.ref import neighbor_repulsion_ref


def neighbor_repulsion(pos, mass, nbr_idx, nbr_mask, vmask, C, L, min_dist):
    mode = backend()
    if mode == "ref":
        return neighbor_repulsion_ref(pos, mass, nbr_idx, nbr_mask, vmask,
                                      C, L, min_dist)
    w = jnp.where(vmask, mass, 0.0).astype(jnp.float32)
    rows = pos.astype(jnp.float32).T                     # [2, n]
    xy_p = jnp.pad(rows, ((0, 0), (0, 1)))               # sentinel column n
    w_p = jnp.pad(w, (0, 1))
    idx = nbr_idx.T                                      # [K, n]
    nbrs = jnp.stack([xy_p[0][idx], xy_p[1][idx],
                      jnp.where(nbr_mask.T, w_p[idx], 0.0)])
    f = neighbor_pallas(rows[:, None, :], nbrs, C, L, min_dist,
                        interpret=(mode == "interpret"))[:, 0]
    return jnp.where(vmask[:, None], f.T, 0.0)
