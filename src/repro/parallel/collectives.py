"""Distributed-optimization tricks: gradient compression and overlapped
collective matmuls.

* ``compress_grads`` / ``decompress_grads`` — int8 quantization with error
  feedback (EF-SGD style): the quantization residual is carried in a state
  buffer and re-added next step, so compression error is O(1) accumulated
  rather than O(steps). Under GSPMD the all-reduce of the int8 payload moves
  4× fewer bytes across the DP axes (the collective term of the roofline).

* ``ring_collective_matmul`` — all-gather-matmul overlap: instead of
  all-gather(x) → x @ W, the x shards rotate around the TP ring with
  ``ppermute`` while each device multiplies the shard it currently holds —
  compute hides the communication (the classic collective-matmul schedule).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map


def quantize_int8(x: jnp.ndarray):
    """Per-tensor symmetric int8 quantization → (q, scale)."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q, scale):
    return q.astype(jnp.float32) * scale


def compress_grads(grads, error_state):
    """Quantize each grad leaf with error feedback.

    Returns (quantized pytree of (q, scale), new_error_state). The caller
    all-reduces/averages the dequantized values (GSPMD already reduced the
    true grads across DP; in a hand-rolled DP loop you would psum ``q``)."""
    def one(g, e):
        corrected = g.astype(jnp.float32) + e
        q, s = quantize_int8(corrected)
        deq = dequantize_int8(q, s)
        return (q, s), corrected - deq
    flat_g, tdef = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(error_state)
    qs, errs = [], []
    for g, e in zip(flat_g, flat_e):
        (q, s), err = one(g, e)
        qs.append((q, s))
        errs.append(err)
    return jax.tree.unflatten(tdef, qs), jax.tree.unflatten(tdef, errs)


def decompress_grads(qgrads):
    return jax.tree.map(lambda qs: dequantize_int8(*qs), qgrads,
                        is_leaf=lambda x: isinstance(x, tuple))


def init_error_state(grads_like):
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads_like)


def ring_collective_matmul(mesh: Mesh, axis: str = "model"):
    """All-gather→matmul with compute/comm overlap (collective matmul).

    Computes ``all_gather(x, axis) @ w`` where x [S, K] is ROW-sharded over
    ``axis`` (sequence-parallel residual) and w [K, N] is COLUMN-sharded
    (Megatron column-parallel weight). Instead of materializing the gather,
    the x shards rotate around a ppermute ring; at step s, device d holds
    shard j = (d − s) mod size and fills output row-block j — the transfer
    of the next shard overlaps the current matmul on TPU (async collective
    permute). Output is [S, N/size] (row-complete, column-sharded).
    """
    size = mesh.shape[axis]
    perm = [(i, (i + 1) % size) for i in range(size)]

    def local(x_blk, w_blk):
        # x_blk [S/size, K], w_blk [K, N/size]
        idx = jax.lax.axis_index(axis)
        S_loc = x_blk.shape[0]

        def body(s, carry):
            acc, xs = carry
            j = jax.lax.rem(idx - s + size, size)        # shard id in hand
            part = (xs @ w_blk)[None]                    # [1, S/size, N/size]
            acc = jax.lax.dynamic_update_slice(acc, part, (j, 0, 0))
            xs = jax.lax.ppermute(xs, axis, perm)        # prefetch next shard
            return acc, xs

        acc0 = jnp.zeros((size, S_loc, w_blk.shape[1]), x_blk.dtype)
        # the carry becomes device-varying inside the loop (ppermute);
        # mark the initial zeros accordingly (shard_map vma rules)
        acc0 = jax.lax.pcast(acc0, (axis,), to="varying")
        acc, _ = jax.lax.fori_loop(0, size, body, (acc0, x_blk))
        return acc.reshape(size * S_loc, w_blk.shape[1])

    return shard_map(local, mesh=mesh,
                     in_specs=(P(axis, None), P(None, axis)),
                     out_specs=P(None, axis))
