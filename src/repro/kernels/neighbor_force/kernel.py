"""Grouped pair-force accumulation — Pallas TPU kernel.

Each group g owns ``cap`` row slots and its own list of ``K`` partners
(x, y, weight; weight 0 = masked). The kernel returns, per row slot, the
FR repulsion summed over the group's partners. Two callers:

  * the k-hop neighbor-list repulsion: one group per vertex, cap = 1;
  * the grid mode's exact near field (kernels/grid_force): one group per
    cell, cap = the cell's bucket capacity, K = its 3×3 neighborhood's
    9·cap bucket slots.

Layout. Groups lie on the 128-lane axis and the partner slots on the
sublane axis: partner planes are ``[3, K, N]`` and row planes
``[2, cap, N]``, all lane-dense (no trailing 2 or 3). The gather that
fills the partner planes happens in XLA. For each row slot the kernel
broadcasts the slot's lane vector over the ``[K, block]`` partner tile
and reduces over sublanes, so the reduction never crosses lanes. The
scalars (C, L, min_dist) sit in SMEM; a ``jax.vmap`` over the op becomes
the leading grid axis (``kernels.batch_native``).

VMEM per program (f32): 3·K·Bc partner planes (double-buffered) plus
about four K·Bc temporaries; K = 432, Bc = 256 → ~5 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import batch_native, round_up, varying_axes

LANES = 128


def _neighbor_kernel(params_ref, rows_ref, nbrs_ref, out_ref):
    b = pl.program_id(0)
    C, L, md = params_ref[3 * b], params_ref[3 * b + 1], params_ref[3 * b + 2]
    cll = C * L * L
    md2 = md * md

    def row(s, carry):
        rx = rows_ref[0, pl.ds(s, 1), :]     # [1, Bc]
        ry = rows_ref[1, pl.ds(s, 1), :]
        dx = rx - nbrs_ref[0]                # [K, Bc]
        dy = ry - nbrs_ref[1]
        d2 = dx * dx + dy * dy + md2
        inv = cll * nbrs_ref[2] / d2
        out_ref[0, pl.ds(s, 1), :] = jnp.sum(dx * inv, axis=0, keepdims=True)
        out_ref[1, pl.ds(s, 1), :] = jnp.sum(dy * inv, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, rows_ref.shape[1], row, 0)


def _neighbor_call(rows, nbrs, C, L, md, *, block_cols: int,
                   interpret: bool):
    """Batched launch: rows [B, 2, cap, N], nbrs [B, 3, K, N], C/L/md [B]."""
    B, _, cap, N = rows.shape
    K = nbrs.shape[2]
    bc = min(block_cols, round_up(N, LANES))
    npad = round_up(N, bc)
    pad = ((0, 0), (0, 0), (0, 0), (0, npad - N))
    rows = jnp.pad(rows.astype(jnp.float32), pad)
    nbrs = jnp.pad(nbrs.astype(jnp.float32), pad)
    vma = varying_axes(rows, nbrs, C, L, md)
    params = jnp.stack([C, L, md], axis=1).astype(jnp.float32).reshape(-1)
    out = pl.pallas_call(
        _neighbor_kernel,
        grid=(B, npad // bc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, 2, cap, bc), lambda b, i: (b, 0, 0, i)),
            pl.BlockSpec((None, 3, K, bc), lambda b, i: (b, 0, 0, i)),
        ],
        out_specs=pl.BlockSpec((None, 2, cap, bc), lambda b, i: (b, 0, 0, i)),
        out_shape=jax.ShapeDtypeStruct((B, 2, cap, npad), jnp.float32,
                                       vma=vma),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="neighbor_pallas",     # a stable kernel name for profiles
    )(params, rows, nbrs)
    return out[..., :N]


@functools.partial(jax.jit, static_argnames=("block_cols", "interpret"))
def neighbor_pallas(rows, nbrs, C, L, min_dist, *, block_cols: int = 256,
                    interpret: bool = False):
    """rows f32[2, cap, N] (x, y); nbrs f32[3, K, N] (x, y, weight)
    → forces f32[2, cap, N].

    Groups are padded to a multiple of ``block_cols`` (a multiple of 128)
    with weight-0 partners, so any N works; padded groups are dropped.
    """
    call = functools.partial(_neighbor_call, block_cols=block_cols,
                             interpret=interpret)
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    return batch_native(call)(f32(rows), f32(nbrs), f32(C), f32(L),
                              f32(min_dist))
