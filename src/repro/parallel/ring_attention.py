"""Ring attention (context parallelism) — sequence-sharded exact attention.

For prefill/训练 at 500k-token contexts even flash attention needs the whole
KV on-device; ring attention shards the SEQUENCE over the model axis and
rotates KV blocks around the ring with `ppermute`, folding each arriving
block into a streaming softmax (the same running max/denominator as
kernels/flash_attention). Per device: Sq_loc × Sk_loc work per step, size
steps; communication (Sk_loc·KV·hd·2·2B per step) overlaps the block matmul
on TPU. Causality is enforced with GLOBAL positions, so whole future blocks
contribute nothing (their masked exp underflows to zero numerically — the
schedule stays shape-static).

This is the primitive that would lift the long_500k skip for full-attention
archs at prefill/train time; it is validated against the reference SDPA in
tests/test_distributed.py and exposed for integration.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map


def ring_attention(mesh: Mesh, *, axis: str = "model", causal: bool = True,
                   batch_axes=("data",)):
    """Returns f(q, k, v) with q/k/v [B, S, H|KV, hd], S sharded over
    ``axis`` (B over ``batch_axes``); computes exact (GQA) attention."""
    size = mesh.shape[axis]
    perm = [(i, (i + 1) % size) for i in range(size)]

    def local(q, k, v):
        # q [B, Sq_loc, H, hd]; k/v [B, Sk_loc, KV, hd]
        B, Sq, H, hd = q.shape
        Sk, KV = k.shape[1], k.shape[2]
        G = H // KV
        idx = jax.lax.axis_index(axis)
        qg = q.reshape(B, Sq, KV, G, hd)
        scale = hd ** -0.5
        qpos = idx * Sq + jnp.arange(Sq)

        def step(carry, s):
            m, l, acc, kb, vb = carry
            src = jax.lax.rem(idx - s + size, size)   # whose block we hold
            kpos = src * Sk + jnp.arange(Sk)
            sc = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kb) * scale
            sc = sc.astype(jnp.float32)
            if causal:
                sc = jnp.where(qpos[:, None] >= kpos[None, :], sc, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
            shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(sc - shift[..., None])
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - shift), 0.0)
            l = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(vb.dtype), vb)
            acc = acc * corr[..., None].astype(acc.dtype) + pv
            # rotate the KV block to the next rank (overlaps compute on TPU)
            kb = jax.lax.ppermute(kb, axis, perm)
            vb = jax.lax.ppermute(vb, axis, perm)
            return (m_new, l, acc, kb, vb), None

        m0 = jnp.full((B, KV, G, Sq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
        a0 = jnp.zeros((B, KV, G, Sq, hd), v.dtype)
        m0, l0, a0 = (jax.lax.pcast(x, (axis,), to="varying")
                      for x in (m0, l0, a0))
        (m, l, acc, _, _), _ = jax.lax.scan(
            step, (m0, l0, a0, k, v), jnp.arange(size))
        out = acc / jnp.maximum(l, 1e-30)[..., None].astype(acc.dtype)
        return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd)

    ba = tuple(a for a in batch_axes if a in mesh.axis_names)
    spec = P(ba if ba else None, axis, None, None)
    return shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)
