"""Tiled all-pairs FR repulsion — Pallas TPU kernel.

Every row vertex against every source (x, y, weight). The same kernel
serves the exact repulsion mode (sources = the rows themselves) and the
grid mode's far field (sources = per-cell aggregates, kernels/grid_force).

Layout. Coordinates travel as lane-major planes, never with a trailing
dimension of 2 or 3 (the TPU tiling would pad that dimension to 128
lanes). Row planes ``rows[2, n]`` are viewed as ``[2, n/128, 128]`` so a
row block is dense ``(8k, 128)`` vregs; the force planes come back in the
same layout. Sources stream through SMEM in blocks of ``block_cols``
scalars per coordinate, and the kernel loops over them: each step
broadcasts one source's (x, y, w) against the whole row block. No lane
reduction and no relayout is needed, and the VMEM footprint is the row
block plus its two accumulators. The scalars (C, L, min_dist) sit in SMEM.

Grid = (problems, row_blocks, col_blocks): a ``jax.vmap`` over the op
becomes the leading grid axis (``kernels.batch_native``); rows are
parallel, source blocks are the reduction (the output block depends only
on the row block and is zeroed at the first source block).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import batch_native, round_up, varying_axes

LANES = 128
SMEM_TILE = 1024     # XLA tiles a 1-D SMEM operand in 1024-word blocks
UNROLL = 8


def _nbody_kernel(params_ref, sx_ref, sy_ref, sw_ref, rows_ref, out_ref):
    b = pl.program_id(0)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    C, L, md = params_ref[3 * b], params_ref[3 * b + 1], params_ref[3 * b + 2]
    cll = C * L * L
    md2 = md * md
    rx = rows_ref[0]                         # [R, 128] row x
    ry = rows_ref[1]

    def body(t, carry):
        fx, fy = carry
        for u in range(UNROLL):
            j = t * UNROLL + u
            dx = rx - sx_ref[j]
            dy = ry - sy_ref[j]
            d2 = dx * dx + dy * dy + md2
            inv = cll * sw_ref[j] / d2
            fx, fy = fx + dx * inv, fy + dy * inv
        return fx, fy

    zero = jnp.zeros_like(rx)
    fx, fy = jax.lax.fori_loop(0, sx_ref.shape[0] // UNROLL, body,
                               (zero, zero))
    out_ref[0] += fx
    out_ref[1] += fy


def _nbody_call(rows, src, C, L, md, *, block_rows: int, block_cols: int,
                interpret: bool):
    """Batched launch: rows [B, 2, n], src [B, 3, ns], C/L/md [B]."""
    B, _, n = rows.shape
    ns = src.shape[2]
    br = min(block_rows, round_up(n, LANES))
    bc = min(block_cols, round_up(ns, SMEM_TILE))
    npad, nspad = round_up(n, br), round_up(ns, bc)
    rows = jnp.pad(rows.astype(jnp.float32), ((0, 0), (0, 0), (0, npad - n)))
    src = jnp.pad(src.astype(jnp.float32), ((0, 0), (0, 0), (0, nspad - ns)))
    vma = varying_axes(rows, src, C, L, md)
    params = jnp.stack([C, L, md], axis=1).astype(jnp.float32).reshape(-1)
    # sources flattened per coordinate: problem b's block j is b·nsb + j
    sx, sy, sw = (src[:, k].reshape(-1) for k in range(3))
    nsb = nspad // bc
    r = br // LANES
    src_spec = pl.BlockSpec((bc,), lambda b, i, j: (b * nsb + j,),
                            memory_space=pltpu.SMEM)
    row_spec = pl.BlockSpec((None, 2, r, LANES), lambda b, i, j: (b, 0, i, 0))
    out = pl.pallas_call(
        _nbody_kernel,
        grid=(B, npad // br, nsb),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  src_spec, src_spec, src_spec, row_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((B, 2, npad // LANES, LANES),
                                       jnp.float32, vma=vma),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="nbody_pallas",        # a stable kernel name for profiles
    )(params, sx, sy, sw, rows.reshape(B, 2, npad // LANES, LANES))
    return out.reshape(B, 2, npad)[:, :, :n]


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols",
                                             "interpret"))
def nbody_pallas(rows, src, C, L, min_dist, *, block_rows: int = 1024,
                 block_cols: int = SMEM_TILE, interpret: bool = False):
    """rows f32[2, n] (x, y); src f32[3, ns] (x, y, weight) → f32[2, n].

    Force on row i: Σ_j C·L²·w_j·(p_i − s_j) / (|p_i − s_j|² + min_dist²).
    Rows are padded to a multiple of ``block_rows`` (a multiple of 128;
    on the chip a multiple of 1024, or all rows in one block) and sources
    to a multiple of ``block_cols`` (on the chip a multiple of 1024) with
    weight 0, so any n and ns work.
    """
    call = functools.partial(_nbody_call, block_rows=block_rows,
                             block_cols=block_cols, interpret=interpret)
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    return batch_native(call)(f32(rows), f32(src), f32(C), f32(L),
                              f32(min_dist))
