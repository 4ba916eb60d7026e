"""GPipe-style pipeline parallelism over the "pod" axis.

The layer-group stack (already scanned, params stacked [G, ...]) is split
into `pod`-many stages by sharding the G axis; microbatches stream through
the stages with `ppermute` handoffs. shard_map runs with
``axis_names={"pod"}`` (partial-manual), so TP/DP sharding over
data/model inside each stage is still handled by GSPMD — PP composes with
the rest of the mesh.

Schedule: plain GPipe fill-drain — T = M + S − 1 ticks; at tick t, stage s
computes microbatch (t − s) (bubbles compute garbage whose outputs are
masked out, so their gradient contribution is exactly zero). The whole
loop is a `lax.scan`, hence differentiable: `jax.grad` through it yields
the reverse pipeline automatically.

Cross-pod traffic per step: 2·M·(mb·S·D) activations (fwd + bwd) — versus
pod-DP's full gradient all-reduce; PP also divides the per-pod parameter
residency by the stage count, which is what makes >HBM models fit.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map


def pipeline_scan(mesh: Mesh, stage_fn, n_microbatches: int):
    """Build pp(x_mb, stage_params) → y_mb.

    stage_fn(params_local, x) applies THIS stage's layer groups (params
    already sliced to the local stage; inner dims may be TP/DP sharded by
    GSPMD). x_mb: [M, ...] microbatched activations (leading batch dim of
    each microbatch sharded over "data" as usual).
    """
    S_stages = mesh.shape["pod"]
    M = n_microbatches
    fwd_perm = [(s, s + 1) for s in range(S_stages - 1)]

    def pp(x_mb, params_local, stage_arr):
        # stage id arrives as a P("pod")-sharded iota instead of
        # lax.axis_index: inside a partial-manual region the latter lowers
        # to a partition-id HLO that 0.4.x GSPMD refuses to partition.
        stage = stage_arr[0]
        mb_shape = x_mb.shape[1:]

        def tick(prev_out, t):
            # hand the previous tick's output to the next stage
            recv = jax.lax.ppermute(prev_out, "pod", fwd_perm)
            mb_idx = t - stage
            x0 = jax.lax.dynamic_index_in_dim(
                x_mb, jnp.clip(mb_idx, 0, M - 1), axis=0, keepdims=False)
            x_in = jnp.where(stage == 0, x0, recv)
            y = stage_fn(params_local, x_in)
            return y, y                         # stack every tick's output

        y0 = jax.lax.pcast(jnp.zeros(mb_shape, x_mb.dtype), ("pod",),
                           to="varying")
        _, ys_all = jax.lax.scan(tick, y0, jnp.arange(M + S_stages - 1))
        # microbatch m finishes on the LAST stage at tick m + S − 1:
        # a STATIC slice of the stacked outputs (bubble ticks fall outside)
        out = ys_all[S_stages - 1: S_stages - 1 + M]
        mask = (stage == S_stages - 1).astype(x_mb.dtype)
        return jax.lax.psum(out * mask, "pod")

    sm = shard_map(pp, mesh=mesh,
                   in_specs=(P(), P("pod"), P("pod")),
                   out_specs=P(),
                   axis_names={"pod"}, check_vma=False)
    return lambda x_mb, params_local: sm(
        x_mb, params_local, jnp.arange(S_stages, dtype=jnp.int32))


def pipeline_forward(params, cfg, batch, mesh: Mesh, *,
                     n_microbatches: int = 4, remat: str = "none"):
    """Pipeline-parallel forward → logits (dense homogeneous stacks).

    Embedding/LM-head run replicated across pods (outside the pipeline);
    the scanned layer-group stack is stage-sharded over "pod" on its G axis.
    """
    import dataclasses as _dc
    from repro.models import layers as L
    from repro.models.model import _apply_sublayer, shard_batch
    from repro.parallel.sharding import current_rules, use_shardings
    pat = cfg.layer_pattern()
    assert cfg.moe is None and not cfg.enc_layers, \
        "pipeline_forward targets homogeneous dense stacks"
    tokens = batch["tokens"]
    B, S = tokens.shape
    M = n_microbatches
    assert B % M == 0

    # the pod axis carries STAGES here; inside the partial-manual region we
    # drop explicit sharding constraints entirely (mesh=None rules) — mixing
    # with_sharding_constraint with Manual axes trips an XLA:CPU SPMD bug
    # ("invalid binary instruction opcode copy"); GSPMD still infers the
    # data/model sharding inside from the operand shardings.
    outer = current_rules()
    inner_rules = _dc.replace(outer, mesh=None) if outer else None

    with use_shardings(mesh, inner_rules):
        x = L.apply_embedding(params["embed"], tokens)
        x = shard_batch(x)
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B // M, S))
        chunk = 2048 if S > 4096 else 0

        def stage_fn(gp_local, x):
            def body(x, gp):
                for i, kind in enumerate(pat):
                    x, _, _ = _apply_sublayer(gp[i], x, cfg, kind, positions,
                                              chunk=chunk)
                return x, None
            fn = body
            if remat != "none":
                fn = jax.checkpoint(lambda c, g: body(c, g),
                                    prevent_cse=False)
            y, _ = jax.lax.scan(fn, x, gp_local)
            return y

        x_mb = x.reshape((M, B // M) + x.shape[1:])
        pp = pipeline_scan(mesh, stage_fn, M)
        y_mb = pp(x_mb, params["groups"])
        y = y_mb.reshape((B,) + y_mb.shape[2:])
        y = L.apply_norm(params["final_norm"], y, cfg.norm)
        return L.apply_lm_head(params["embed"], params.get("lm_head"), y,
                               cfg.tie_embeddings)
