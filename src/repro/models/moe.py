"""Mixture-of-Experts layer with capacity-based dispatch (Switch-style) and
optional shared experts (DeepSeekMoE fine-grained recipe).

Dispatch is *per-sequence* (capacity C = ceil(cf · S · k / E)), which keeps
the expert buffers batch-sharded over DP and expert-sharded over EP without
any host-side regrouping: GSPMD turns the scatter/gather across the EP axis
into the dispatch all-to-all pattern. Overflow tokens are dropped (their
residual passes through), and a Switch load-balancing aux loss is returned.

Sharding:
  EP (experts % model == 0):   expert weights P("model", None, None)
  TP fallback (granite, 40e):  expert weights P(None, None, "model")
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.parallel.sharding import shard, current_rules
from jax import shard_map
from repro.models.layers import _normal


def capacity(S: int, cfg_moe) -> int:
    import math
    c = math.ceil(cfg_moe.capacity_factor * S * cfg_moe.top_k
                  / cfg_moe.n_experts)
    return max(1, c)


def init_moe(key, d_model: int, m):
    E, F = m.n_experts, m.d_expert
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    s_in, s_out = d_model ** -0.5, F ** -0.5
    p = {
        "router": _normal(k1, (d_model, E), s_in),
        "wup": _normal(k2, (E, d_model, F), s_in),
        "wgate": _normal(k3, (E, d_model, F), s_in),
        "wdown": _normal(k4, (E, F, d_model), s_out),
    }
    if m.n_shared:
        from repro.models.layers import init_mlp
        p["shared"] = init_mlp(k5, d_model, m.n_shared * F, "swiglu")
    return p


def moe_param_specs(m, rules):
    from jax.sharding import PartitionSpec as P
    if rules.experts:                    # EP
        w = P(rules.experts, None, None)
    else:                                # TP inside experts
        w = P(None, None, rules.expert_tp)
        wd = P(None, rules.expert_tp, None)
    specs = {
        "router": P(None, None),
        "wup": w, "wgate": w,
        "wdown": P(rules.experts, None, None) if rules.experts else
                 P(None, rules.expert_tp, None),
    }
    if m.n_shared:
        from repro.models.layers import mlp_param_specs
        specs["shared"] = mlp_param_specs("swiglu", rules)
    return specs


def apply_moe_shardmap(p, x, m, activation: str = "swiglu"):
    """EP MoE with an explicit shard_map over the model axis (§Perf B).

    Observation: activations are replicated across the EP (model) axis —
    only the batch axes shard them. Each EP rank can therefore build the
    dispatch buffer for ITS OWN expert shard entirely locally; the only
    cross-EP communication needed is the combine-reduction (psum of the
    per-rank partial outputs), the same volume as one dense TP layer.
    GSPMD's scatter/gather partitioning of the jnp formulation instead
    produces full-buffer all-reduces (~6× the collective bytes on the
    8-device smoke config, growing with E·capacity — measured in
    EXPERIMENTS.md §Perf).
    """
    from repro.parallel.sharding import current_rules
    r = current_rules()
    mesh = r.mesh
    E = m.n_experts
    msize = mesh.shape["model"]
    E_loc = E // msize
    B, S, D = x.shape
    C = capacity(S, m)
    dt = x.dtype
    from jax.sharding import PartitionSpec as P

    def local(x_blk, router, wup, wgate, wdown):
        # x_blk [B_loc, S, D] — replicated over "model"; w* [E_loc, ...]
        Bl = x_blk.shape[0]
        logits = jnp.einsum("bsd,de->bse", x_blk.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, m.top_k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
        onehot_k = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)
        f = onehot_k.sum(axis=2).mean(axis=1)
        aux = E * jnp.mean(jnp.sum(f * probs.mean(axis=1), axis=-1))
        aux = jax.lax.pmean(aux, "model")

        flat_choice = onehot_k.reshape(Bl, S * m.top_k, E)
        pos = jnp.cumsum(flat_choice, axis=1) - flat_choice
        pos = jnp.sum(pos * flat_choice, axis=-1).reshape(Bl, S, m.top_k)
        keep = pos < C
        # LOCAL expert shard only: experts [e0, e0+E_loc)
        e0 = jax.lax.axis_index("model") * E_loc
        local_e = expert_idx - e0
        in_shard = (local_e >= 0) & (local_e < E_loc) & keep
        slot = jnp.where(in_shard, local_e * C + pos.astype(jnp.int32),
                         E_loc * C)
        xk = jnp.broadcast_to(x_blk[:, :, None, :],
                              (Bl, S, m.top_k, D)).reshape(Bl, S * m.top_k, D)
        buf = jax.vmap(lambda s_ids, vals: jax.ops.segment_sum(
            vals, s_ids, num_segments=E_loc * C + 1))(
            slot.reshape(Bl, S * m.top_k), xk)
        buf = buf[:, : E_loc * C].reshape(Bl, E_loc, C, D)

        up = jnp.einsum("becd,edf->becf", buf, wup.astype(dt))
        gatep = jnp.einsum("becd,edf->becf", buf, wgate.astype(dt))
        h = (jax.nn.silu(gatep) if activation == "swiglu"
             else jax.nn.gelu(gatep)) * up
        out_buf = jnp.einsum("becf,efd->becd", h, wdown.astype(dt))

        flat = out_buf.reshape(Bl, E_loc * C, D)
        flat = jnp.concatenate([flat, jnp.zeros((Bl, 1, D), dt)], axis=1)
        gathered = jax.vmap(lambda fb, s_ids: fb[s_ids])(
            flat, slot.reshape(Bl, S * m.top_k)).reshape(Bl, S, m.top_k, D)
        w = jnp.where(in_shard, gate_vals, 0.0).astype(dt)
        y_part = jnp.einsum("bskd,bsk->bsd", gathered, w)
        # combine: sum partial outputs across EP ranks (tokens whose expert
        # lives elsewhere contributed zero here)
        return jax.lax.psum(y_part, "model"), aux

    batch_axes = r.batch
    y, aux = shard_map(
        local, mesh=mesh,
        in_specs=(P(batch_axes, None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(batch_axes, None, None), P()),
        check_vma=False,
    )(x, p["router"], p["wup"], p["wgate"], p["wdown"])

    if "shared" in p:
        from repro.models.layers import apply_mlp
        y = y + apply_mlp(p["shared"], x, "swiglu")
    return y, aux


def apply_moe_a2a(p, x, m, activation: str = "swiglu"):
    """EP MoE via true all-to-all (§Perf B iteration 3, DeepSpeed-MoE
    layout). Requires tokens sharded over the model axis too (strategy
    ``fsdp_dp``): each rank routes its own tokens, sends them to the rank
    owning their expert (one a2a), runs its expert shard, and a reverse a2a
    returns the results — per-device communication is tokens·k·D both ways,
    independent of expert count, vs FSDP's per-layer expert-weight gathers
    or GSPMD's full-buffer all-reduces.
    """
    import math
    from repro.parallel.sharding import current_rules
    r = current_rules()
    mesh = r.mesh
    E, k = m.n_experts, m.top_k
    msize = mesh.shape["model"]
    E_loc = E // msize
    B, S, D = x.shape
    dt = x.dtype
    from jax.sharding import PartitionSpec as P
    # per-destination-rank capacity (each source sends ≤ C_pair rows/peer)
    C_pair = max(1, math.ceil(m.capacity_factor * S * k / msize))
    # per-expert capacity after the exchange (rows from msize peers)
    C_big = max(1, math.ceil(m.capacity_factor * msize * C_pair / E_loc))

    def local(x_blk, router, wup, wgate, wdown):
        Bl = x_blk.shape[0]                     # B/(data·model) sequences
        logits = jnp.einsum("bsd,de->bse", x_blk.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)
        gate_vals = (gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)).astype(jnp.float32)
        onehot_k = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)
        f = onehot_k.sum(axis=2).mean(axis=1)
        aux = E * jnp.mean(jnp.sum(f * probs.mean(axis=1), axis=-1))
        aux = jax.lax.pmean(aux, "model")

        # destination rank + slot within the [dest, C_pair] send buffer
        flat_e = expert_idx.reshape(Bl, S * k)
        dest = flat_e // E_loc                                  # [Bl, S·k]
        dhot = jax.nn.one_hot(dest, msize, dtype=jnp.float32)
        pos = (jnp.cumsum(dhot, axis=1) - dhot)
        pos = jnp.sum(pos * dhot, axis=-1).astype(jnp.int32)    # [Bl, S·k]
        keep = pos < C_pair
        slot = jnp.where(keep, dest * C_pair + pos, msize * C_pair)

        xk = jnp.broadcast_to(x_blk[:, :, None, :], (Bl, S, k, D)) \
            .reshape(Bl, S * k, D)
        send = jax.vmap(lambda s_ids, vals: jax.ops.segment_sum(
            vals, s_ids, num_segments=msize * C_pair + 1))(slot, xk)
        send = send[:, : msize * C_pair]
        # metadata: local expert id at the destination (+1, 0 = invalid)
        meta = jax.vmap(lambda s_ids, vals: jax.ops.segment_sum(
            vals, s_ids, num_segments=msize * C_pair + 1))(
            slot, jnp.where(keep, (flat_e % E_loc) + 1, 0
                            ).astype(jnp.float32)[..., None])
        meta = meta[:, : msize * C_pair, 0]

        payload = jnp.concatenate(
            [send.astype(dt), meta.astype(dt)[..., None]], axis=-1) \
            .reshape(Bl, msize, C_pair, D + 1)
        recv = jax.lax.all_to_all(payload, "model", split_axis=1,
                                  concat_axis=1)
        recv = recv.reshape(Bl, msize, C_pair, D + 1)
        rx = recv[..., :D].reshape(Bl, msize * C_pair, D)
        rmeta = recv[..., D].reshape(Bl, msize * C_pair)
        e_loc = jnp.round(rmeta.astype(jnp.float32)).astype(jnp.int32) - 1
        valid = e_loc >= 0

        # pack into the local expert buffer [E_loc, C_big, D]
        ehot = jax.nn.one_hot(jnp.where(valid, e_loc, E_loc), E_loc + 1,
                              dtype=jnp.float32)[..., :E_loc]
        epos = (jnp.cumsum(ehot, axis=1) - ehot)
        epos = jnp.sum(epos * ehot, axis=-1).astype(jnp.int32)
        ekeep = valid & (epos < C_big)
        eslot = jnp.where(ekeep, e_loc * C_big + epos, E_loc * C_big)
        buf = jax.vmap(lambda s_ids, vals: jax.ops.segment_sum(
            vals, s_ids, num_segments=E_loc * C_big + 1))(eslot, rx)
        buf = buf[:, : E_loc * C_big].reshape(Bl, E_loc, C_big, D)

        up = jnp.einsum("becd,edf->becf", buf, wup.astype(dt))
        gatep = jnp.einsum("becd,edf->becf", buf, wgate.astype(dt))
        h = (jax.nn.silu(gatep) if activation == "swiglu"
             else jax.nn.gelu(gatep)) * up
        out_buf = jnp.einsum("becf,efd->becd", h, wdown.astype(dt))

        # unpack to recv layout, reverse a2a, combine at the source
        flat_out = out_buf.reshape(Bl, E_loc * C_big, D)
        flat_out = jnp.concatenate(
            [flat_out, jnp.zeros((Bl, 1, D), dt)], axis=1)
        back = jax.vmap(lambda fb, s: fb[s])(flat_out, eslot)   # recv order
        back = back.reshape(Bl, msize, C_pair, D)
        ret = jax.lax.all_to_all(back, "model", split_axis=1, concat_axis=1)
        ret = ret.reshape(Bl, msize * C_pair, D)
        ret = jnp.concatenate([ret, jnp.zeros((Bl, 1, D), dt)], axis=1)
        got = jax.vmap(lambda fb, s: fb[s])(ret, slot)          # [Bl,S·k,D]
        got = got.reshape(Bl, S, k, D)
        w = jnp.where(keep.reshape(Bl, S, k), gate_vals, 0.0).astype(dt)
        return jnp.einsum("bskd,bsk->bsd", got, w), aux

    batch_axes = r.batch        # includes "model" under fsdp_dp
    y, aux = shard_map(
        local, mesh=mesh,
        in_specs=(P(batch_axes, None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(batch_axes, None, None), P()),
        check_vma=False,
    )(x, p["router"], p["wup"], p["wgate"], p["wdown"])
    if "shared" in p:
        from repro.models.layers import apply_mlp
        y = y + apply_mlp(p["shared"], x, "swiglu")
    return y, aux


def apply_moe(p, x, m, activation: str = "swiglu"):
    """x [B,S,D] → ([B,S,D], aux_loss scalar)."""
    B, S, D = x.shape
    E, k = m.n_experts, m.top_k
    C = capacity(S, m)
    dt = x.dtype
    r = current_rules()

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)                  # [B,S,E]
    gate_vals, expert_idx = jax.lax.top_k(probs, k)          # [B,S,k]
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    # Switch LB loss: E · Σ_e f_e · P_e
    onehot_k = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # [B,S,k,E]
    f = onehot_k.sum(axis=2).mean(axis=1)                    # [B,E] token frac
    aux = E * jnp.mean(jnp.sum(f * probs.mean(axis=1), axis=-1))

    # position within expert (per sequence): running count over (S, k)
    flat_choice = onehot_k.reshape(B, S * k, E)
    pos = jnp.cumsum(flat_choice, axis=1) - flat_choice      # [B,S*k,E]
    pos = jnp.sum(pos * flat_choice, axis=-1).reshape(B, S, k)
    keep = pos < C
    slot = expert_idx * C + pos.astype(jnp.int32)            # [B,S,k]
    slot = jnp.where(keep, slot, E * C)                      # overflow bin

    # dispatch: scatter tokens into [B, E·C+1, D]
    xk = jnp.broadcast_to(x[:, :, None, :], (B, S, k, D)).reshape(B, S * k, D)
    buf = jax.vmap(
        lambda s_ids, vals: jax.ops.segment_sum(vals, s_ids,
                                                num_segments=E * C + 1)
    )(slot.reshape(B, S * k), xk)
    buf = buf[:, : E * C].reshape(B, E, C, D)
    if r is not None and r.mesh is not None:
        buf = shard(buf, r.batch, r.experts, None, None)

    # expert FFN (grouped einsum — MXU batched over E)
    up = jnp.einsum("becd,edf->becf", buf, p["wup"].astype(dt))
    gatep = jnp.einsum("becd,edf->becf", buf, p["wgate"].astype(dt))
    h = (jax.nn.silu(gatep) if activation == "swiglu"
         else jax.nn.gelu(gatep)) * up
    out_buf = jnp.einsum("becf,efd->becd", h, p["wdown"].astype(dt))
    if r is not None and r.mesh is not None:
        out_buf = shard(out_buf, r.batch, r.experts, None, None)

    # combine: gather each token's k slots back, weighted by gates
    flat = out_buf.reshape(B, E * C, D)
    flat = jnp.concatenate([flat, jnp.zeros((B, 1, D), dt)], axis=1)
    gathered = jax.vmap(lambda fb, s_ids: fb[s_ids])(flat,
                                                     slot.reshape(B, S * k))
    gathered = gathered.reshape(B, S, k, D)
    w = jnp.where(keep, gate_vals, 0.0).astype(dt)
    y = jnp.einsum("bskd,bsk->bsd", gathered, w)

    if "shared" in p:
        from repro.models.layers import apply_mlp
        y = y + apply_mlp(p["shared"], x, "swiglu")
    if r is not None and r.mesh is not None:
        y = shard(y, r.batch, None, None)
    return y, aux
