import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST run before any jax import: they give this process
512 placeholder CPU devices so `make_production_mesh` can build the real
16×16 (single-pod) and 2×16×16 (two-pod) meshes; `.lower().compile()` then
proves the sharding config is coherent (no sharding mismatch, no OOM at
compile, all collectives supported) without touching real hardware.

    PYTHONPATH=src python -m repro.launch.dryrun --suite lm --mesh both
    PYTHONPATH=src python -m repro.launch.dryrun --suite layout
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b --cell train_4k

Per cell it records memory_analysis (bytes/device — proves it fits),
cost_analysis, and the parsed roofline terms (launch/roofline.py) into
results/dryrun/<mesh>/<arch>__<cell>.json.
"""
import argparse
import dataclasses
import json
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, list_archs, cells_for, SHAPES
from repro.configs.base import ArchConfig, ShapeCell
from repro.launch.mesh import make_production_mesh, peaks
from repro.launch import roofline as RL
from repro.models import model as M
from repro.models.model import param_specs, input_specs
from repro.parallel.sharding import make_rules, use_shardings, param_shardings
from repro.train.optim import AdamWConfig, init_opt_state, apply_updates
from repro.utils.tree import tree_bytes, tree_cast

HBM_PER_CHIP = 16 * 1024 ** 3       # v5e: 16 GiB


# -- sharding helpers ---------------------------------------------------------

def _batch_spec(rules, B: int):
    dp = 1
    for a in rules.batch:
        dp *= rules.mesh.shape[a]
    return rules.batch if B % dp == 0 else None


def decode_state_specs(cfg: ArchConfig, rules, B: int):
    """PartitionSpec tree matching init_decode_state's structure."""
    bs = _batch_spec(rules, B)
    pat = cfg.layer_pattern()

    def kv_spec():
        if rules.kv_heads is not None:
            s = P(None, bs, None, rules.kv_heads, None)
        else:  # flash-decoding: shard the cache sequence
            s = P(None, bs, rules.kv_seq, None, None)
        return {"kv": {"k": s, "v": s}}

    def ssm_spec():
        d_inner = cfg.ssm.expand * cfg.d_model
        H = d_inner // cfg.ssm.head_dim
        ch = d_inner + 2 * cfg.ssm.n_groups * cfg.ssm.d_state
        msize = rules.mesh.shape["model"]
        conv = P(None, bs, None, "model" if ch % msize == 0 else None)
        h = P(None, bs, "model" if H % msize == 0 else None, None, None)
        return {"ssm": {"conv": conv, "h": h}}

    group = [kv_spec() if k == "attn" else ssm_spec() for k in pat]
    specs = {"groups": group}
    if cfg.moe is not None and cfg.moe.first_dense_ff:
        # prefix states lack the leading group axis
        def drop_lead(s):
            return P(*s[1:])
        specs["prefix"] = [jax.tree.map(
            drop_lead, group[0], is_leaf=lambda x: isinstance(x, P))]
    return specs


def _shardings_for(mesh, tree_specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))


# -- cell lowering -------------------------------------------------------------

@dataclasses.dataclass
class CellOpts:
    remat: str = "dots"
    seq_shard: bool = False
    params_dtype: str = "bfloat16"
    zero_opt: bool = True      # ZeRO-1: optimizer states sharded over DP
    fsdp: bool = False         # ZeRO-3: params themselves sharded over DP
    accum: int = 1             # gradient-accumulation microbatches
    strategy: str = "tp"       # tp | fsdp_dp (hillclimb A)
    moe_impl: str = "gspmd"    # gspmd | shard_map (hillclimb B)


def lower_cell(cfg: ArchConfig, cell: ShapeCell, mesh, opts: CellOpts):
    from repro.parallel.sharding import zero_shardings
    rules = make_rules(mesh, cfg, seq_shard=opts.seq_shard,
                       strategy=opts.strategy, moe_impl=opts.moe_impl)
    n_dev = mesh.devices.size
    pdtype = jnp.bfloat16 if opts.params_dtype == "bfloat16" else jnp.float32
    pspec_tree = param_specs(cfg, rules)
    params_struct = jax.eval_shape(
        lambda: tree_cast(M.init_params(cfg, jax.random.PRNGKey(0)), pdtype))
    if opts.strategy == "fsdp_dp":
        # ZeRO-3 over every axis not already used by the leaf's base spec
        # (a2a-MoE expert weights stay EP-sharded on "model")
        import repro.parallel.sharding as SH

        def one(spec, ref):
            used = set()
            for e in spec:
                if e is None:
                    continue
                used.update(e if isinstance(e, tuple) else (e,))
            free = tuple(a for a in mesh.axis_names if a not in used)
            return NamedSharding(
                mesh, SH.zero_spec(spec, ref.shape, mesh, axes=free))
        pshard = jax.tree.map(one, pspec_tree, params_struct,
                              is_leaf=lambda x: isinstance(x, P))
    elif opts.fsdp:
        pshard = zero_shardings(mesh, pspec_tree, params_struct)
    else:
        pshard = _shardings_for(mesh, pspec_tree)
    B = cell.global_batch
    bs = _batch_spec(rules, B)

    with use_shardings(mesh, rules):
        if cell.kind == "train":
            ocfg = AdamWConfig()
            opt_struct = jax.eval_shape(partial(init_opt_state, ocfg),
                                        params_struct)
            zshard = (zero_shardings(mesh, pspec_tree, params_struct)
                      if opts.zero_opt else pshard)
            oshard = type(opt_struct)(
                step=NamedSharding(mesh, P()),
                mu=zshard, nu=zshard, master=zshard)

            def step(params, opt, batch):
                from repro.models import loss_fn
                if opts.accum > 1:
                    # gradient accumulation: scan over microbatches
                    micro = jax.tree.map(
                        lambda x: x.reshape((opts.accum,
                                             x.shape[0] // opts.accum)
                                            + x.shape[1:]), batch)

                    def acc_body(carry, mb):
                        g_acc, l_acc = carry
                        (l, _), g = jax.value_and_grad(
                            lambda p: loss_fn(p, cfg, mb, remat=opts.remat),
                            has_aux=True)(params)
                        return (jax.tree.map(jnp.add, g_acc, g),
                                l_acc + l), None

                    g0 = jax.tree.map(jnp.zeros_like, params)
                    (grads, loss), _ = jax.lax.scan(
                        acc_body, (g0, jnp.zeros((), jnp.float32)), micro)
                    grads = jax.tree.map(lambda g: g / opts.accum, grads)
                    loss = loss / opts.accum
                else:
                    (loss, parts), grads = jax.value_and_grad(
                        lambda p: loss_fn(p, cfg, batch, remat=opts.remat),
                        has_aux=True)(params)
                params, opt, om = apply_updates(ocfg, params, grads, opt)
                return params, opt, loss

            batch = input_specs(cfg, cell)
            bshard = {k: NamedSharding(mesh, P(bs, *([None] * (len(v.shape) - 1))))
                      for k, v in batch.items()}
            jitted = jax.jit(step, in_shardings=(pshard, oshard, bshard),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params_struct, opt_struct, batch)

        elif cell.kind == "prefill":
            def step(params, batch):
                logits, state, _ = M.prefill(params, cfg, batch,
                                             cache_len=_dec_len(cfg, cell),
                                             chunks=opts.accum)
                return logits, state
            batch = input_specs(cfg, cell)
            bshard = {k: NamedSharding(mesh, P(bs, *([None] * (len(v.shape) - 1))))
                      for k, v in batch.items()}
            jitted = jax.jit(step, in_shardings=(pshard, bshard))
            lowered = jitted.lower(params_struct, batch)

        else:  # decode
            cache_len = _dec_len(cfg, cell)
            state_struct = jax.eval_shape(
                partial(M.init_decode_state, cfg, B, cache_len))
            sshard = _shardings_for(mesh, decode_state_specs(cfg, rules, B))
            spec = input_specs(cfg, cell)
            tok_shard = NamedSharding(mesh, P(bs, None))
            enc = None
            if cfg.enc_layers:
                enc = spec["enc_out"]

            def step(params, token, state, pos, enc_out=None):
                return M.decode_step(params, cfg, token, state, pos,
                                     enc_out=enc_out)
            in_sh = [pshard, tok_shard, sshard, NamedSharding(mesh, P())]
            args = [params_struct, spec["token"], state_struct, spec["pos"]]
            if enc is not None:
                in_sh.append(NamedSharding(mesh, P(bs, None, None)))
                args.append(enc)
            jitted = jax.jit(step, in_shardings=tuple(in_sh),
                             donate_argnums=(2,))
            lowered = jitted.lower(*args)

    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    return compiled, compile_s, rules


def _dec_len(cfg: ArchConfig, cell: ShapeCell) -> int:
    return cell.seq_len // 2 if cfg.enc_layers else cell.seq_len


def model_flops_per_device(cfg: ArchConfig, cell: ShapeCell, n_dev: int):
    N = cfg.active_param_count()
    if cell.kind == "train":
        tokens = cell.global_batch * _dec_len(cfg, cell)
        total = 6.0 * N * tokens
    elif cell.kind == "prefill":
        total = 2.0 * N * cell.global_batch * _dec_len(cfg, cell)
    else:
        total = 2.0 * N * cell.global_batch
    return total / n_dev


def analyze(compiled, cfg, cell, mesh, compile_s, opts):
    """Merge parsed-HLO costs with the analytic TPU model (launch/analytic).

    FLOPs + collective bytes: parsed from the SPMD HLO (dtype-exact).
    HBM bytes + resident memory: analytic model — XLA:CPU emulates bf16 in
    f32 (hoisting whole-stack converts), inflating the parsed values; those
    are kept as the `cpu_upper_bound` cross-check.
    """
    from repro.launch.analytic import analytic_cell
    n_dev = int(mesh.devices.size)
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    ma = compiled.memory_analysis()
    ca = RL.normalize_cost_analysis(compiled.cost_analysis())
    text = compiled.as_text()
    cost = RL.analyze_text(text, world=n_dev)
    mf = model_flops_per_device(cfg, cell, n_dev)
    an = analytic_cell(cfg, cell, mesh_shape,
                       remat=(opts.remat != "none"),
                       zero_opt=opts.zero_opt, fsdp=opts.fsdp,
                       seq_shard=opts.seq_shard, accum=opts.accum,
                       strategy=opts.strategy)
    peak_bytes = int(ma.argument_size_in_bytes + ma.temp_size_in_bytes
                     + ma.output_size_in_bytes - ma.alias_size_in_bytes)

    pk = peaks()
    compute_s = cost.flops / pk["flops_bf16"]
    memory_s = an["bytes"] / pk["hbm_bw"]
    coll_s = cost.coll_bytes / pk["ici_bw"]
    total = max(compute_s, memory_s, coll_s)
    bottleneck = {compute_s: "compute", memory_s: "memory",
                  coll_s: "collective"}[total]
    # roofline fraction: useful work over achievable peak. Train/prefill are
    # FLOP-normalized (MFU-like: 6·N·D / peak / step-time); decode is
    # bandwidth-normalized (its analytic bytes = params+state read once,
    # the information-theoretic floor for one token).
    if cell.kind == "decode":
        frac = memory_s / total if total > 0 else 0.0
    else:
        frac = (mf / pk["flops_bf16"] / total) if total > 0 else 0.0
    terms = {
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": coll_s, "bottleneck": bottleneck,
        "flops": cost.flops, "bytes_analytic": an["bytes"],
        "bytes_cpu_hlo": cost.bytes, "coll_bytes": cost.coll_bytes,
        "model_flops": mf,
        "useful_ratio": mf / cost.flops if cost.flops else 0.0,
        "roofline_frac": frac,
    }
    rec = {
        "arch": cfg.name, "cell": cell.name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "opts": dataclasses.asdict(opts),
        "compile_s": round(compile_s, 2),
        "memory": {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "peak_bytes_cpu_hlo": peak_bytes,
            "peak_bytes_analytic": int(an["peak"]),
            "fits_hbm": bool(an["peak"] < HBM_PER_CHIP),
        },
        "cost_analysis": {"flops_scan_once": float(ca.get("flops", 0.0)),
                          "bytes_scan_once": float(ca.get("bytes accessed", 0.0))},
        "roofline": terms,
        "collectives": RL.summarize_collectives(cost),
        "params_total": cfg.param_count(),
        "params_active": cfg.active_param_count(),
    }
    return rec


# -- layout-engine dry-run rows ---------------------------------------------------

def lower_layout(mesh, n_pad: int, m_pad: int, cap: int, mode: str,
                 grid_dim: int = 0, cell_cap: int = 0):
    from repro.core.distributed import layout_train_step, layout_step_specs
    step, shardings = layout_train_step(mesh, n_pad, m_pad, cap, mode=mode,
                                        grid_dim=grid_dim, cell_cap=cell_cap)
    specs = layout_step_specs(n_pad, m_pad, cap, mode=mode)
    in_sh = (shardings["pos"], shardings["w"], shardings["nbr_idx"],
             shardings["edge"], shardings["edge"], shardings["edge"],
             shardings["edge"], shardings["scalar"], shardings["scalar"])
    jitted = jax.jit(step, in_shardings=in_sh)
    lowered = jitted.lower(specs["pos"], specs["w"], specs["nbr_idx"],
                           specs["src"], specs["dst_local"], specs["emask"],
                           specs["ewt"], specs["params"], specs["temp"])
    t0 = time.time()
    compiled = lowered.compile()
    return compiled, time.time() - t0


def lower_layout_halo(mesh, n_pad: int, m_pad: int, cap: int, halo: int,
                      mode: str = "neighbor", grid_dim: int = 0,
                      cell_cap: int = 0):
    from repro.core.distributed import (layout_train_step_halo,
                                        layout_halo_specs)
    step, sh = layout_train_step_halo(mesh, n_pad, m_pad, cap, halo,
                                      mode=mode, grid_dim=grid_dim,
                                      cell_cap=cell_cap)
    specs = layout_halo_specs(mesh, n_pad, m_pad, cap, halo, mode=mode)
    in_sh = (sh["pos"], sh["w"], sh["nbr_idx"], sh["send"], sh["edge"],
             sh["edge"], sh["edge"], sh["edge"], sh["scalar"], sh["scalar"])
    jitted = jax.jit(step, in_shardings=in_sh)
    lowered = jitted.lower(specs["pos"], specs["w"], specs["nbr_local"],
                           specs["send_idx"], specs["src_local"],
                           specs["dst_local"], specs["emask"], specs["ewt"],
                           specs["params"], specs["temp"])
    t0 = time.time()
    compiled = lowered.compile()
    return compiled, time.time() - t0


def run_layout_suite(meshes, outdir):
    from repro.configs.multigila import BIG_GRAPH_DRYRUN
    from repro.kernels.grid_force.ops import choose_grid
    results = []
    for mesh_name, mesh in meshes:
        for gname, spec in BIG_GRAPH_DRYRUN.items():
            for mode in ("neighbor", "exact", "halo", "grid", "grid_halo"):
                if mode == "exact" and spec["n_pad"] > (1 << 16):
                    continue  # exact N-body only on coarse levels
                if mode in ("halo", "grid", "grid_halo") \
                        and spec["n_pad"] <= (1 << 16):
                    continue  # halo/grid target the fine levels
                tag = f"layout_{gname}_{mode}"
                try:
                    vsize = int(np.prod(
                        [mesh.shape[a] for a in mesh.axis_names
                         if a != "model"]))
                    G, cc = choose_grid(
                        spec["n_pad"],
                        multiple_of=vsize if mode == "grid_halo" else 1)
                    if mode in ("halo", "grid_halo"):
                        halo = max(spec["n_pad"] // vsize // 8, 128)
                        compiled, cs = lower_layout_halo(
                            mesh, spec["n_pad"], spec["m_pad"], spec["cap"],
                            halo,
                            mode="grid" if mode == "grid_halo" else "neighbor",
                            grid_dim=G, cell_cap=cc)
                    else:
                        compiled, cs = lower_layout(
                            mesh, spec["n_pad"], spec["m_pad"], spec["cap"],
                            mode, grid_dim=G, cell_cap=cc)
                    ma = compiled.memory_analysis()
                    cost = RL.analyze_text(compiled.as_text(),
                                           world=int(mesh.devices.size))
                    terms = RL.roofline_terms(cost)
                    rec = {"arch": tag, "cell": "layout_step",
                           "mesh": "x".join(str(s) for s in mesh.devices.shape),
                           "compile_s": round(cs, 2),
                           "memory": {"argument_bytes": int(ma.argument_size_in_bytes),
                                      "temp_bytes": int(ma.temp_size_in_bytes),
                                      "peak_bytes": int(ma.argument_size_in_bytes
                                                        + ma.temp_size_in_bytes),
                                      "fits_hbm": bool(
                                          ma.argument_size_in_bytes
                                          + ma.temp_size_in_bytes < HBM_PER_CHIP)},
                           "roofline": terms,
                           "collectives": RL.summarize_collectives(cost)}
                    _save(outdir, mesh_name, tag, "layout_step", rec)
                    results.append((f"{tag} × {mesh_name}", "OK",
                                    terms["bottleneck"], True))
                    print(f"[layout] {tag} {mesh_name}: OK "
                          f"({terms['bottleneck']}-bound, {cs:.1f}s)")
                except Exception as e:
                    results.append((f"{tag} × {mesh_name}", "FAIL",
                                    str(e)[:100], False))
                    print(f"[layout] {tag} {mesh_name}: FAIL {e}")
                    traceback.print_exc()
    return results


def run_pp_suite(outdir):
    """Pipeline-parallel proof on the 2-pod mesh: gemma-2b forward+grad with
    2 stages over the pod axis × TP16 × DP16 inside each stage.

    f32 activations (REPRO_ACT_DTYPE): XLA:CPU crashes on bf16 inside
    partial-manual shard_map regions; TPU-native bf16 is unaffected.
    """
    os.environ["REPRO_ACT_DTYPE"] = "float32"
    import importlib
    import repro.models.layers as RL_layers
    importlib.reload(RL_layers)
    from repro.parallel.pipeline import pipeline_forward
    mesh = make_production_mesh(multi_pod=True)
    cfg = get_config("gemma-2b")
    rules = make_rules(mesh, cfg)
    params_struct = jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    batch = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32),
             "labels": jax.ShapeDtypeStruct((256, 4096), jnp.int32)}
    pspec = param_specs(cfg, rules)
    # stage-shard the scanned group axis over "pod"
    pspec["groups"] = jax.tree.map(
        lambda s: P("pod", *s[1:]), pspec["groups"],
        is_leaf=lambda x: isinstance(x, P))
    pshard = _shardings_for(mesh, pspec)

    def step(params, batch):
        def loss(p):
            lg = pipeline_forward(p, cfg, batch, mesh, n_microbatches=8)
            return jnp.mean(lg.astype(jnp.float32) ** 2)
        return jax.grad(loss)(params)

    with use_shardings(mesh, rules):
        t0 = time.time()
        compiled = jax.jit(step, in_shardings=(pshard, None)).lower(
            params_struct, batch).compile()
        cs = time.time() - t0
    cost = RL.analyze_text(compiled.as_text(), world=512)
    ma = compiled.memory_analysis()
    rec = {"arch": "gemma-2b-pp2", "cell": "train_fwd_bwd",
           "mesh": "2x16x16", "compile_s": round(cs, 2),
           "roofline": RL.roofline_terms(cost),
           "memory": {"temp_bytes": int(ma.temp_size_in_bytes)},
           "collectives": RL.summarize_collectives(cost)}
    _save(outdir, "pods2x16x16", "gemma-2b-pp2", "train_fwd_bwd", rec)
    print(f"[pp] gemma-2b 2-stage pipeline × TP16 × DP16: OK "
          f"(compile {cs:.0f}s, coll {cost.coll_bytes/1e9:.1f} GB/dev)")
    out = [("gemma-2b-pp2 × 2x16x16", "OK", "pipeline", True)]

    # ring attention (context parallelism) at 32k context on the pod mesh
    from repro.parallel.ring_attention import ring_attention
    mesh1 = make_production_mesh(multi_pod=False)
    B, S, H, KV, hd = 32, 32768, 16, 8, 128
    fn = ring_attention(mesh1, causal=True)
    spec_q = jax.ShapeDtypeStruct((B, S, H, hd), jnp.float32)
    spec_kv = jax.ShapeDtypeStruct((B, S, KV, hd), jnp.float32)
    t0 = time.time()
    comp = jax.jit(fn).lower(spec_q, spec_kv, spec_kv).compile()
    cs = time.time() - t0
    cost = RL.analyze_text(comp.as_text(), world=256)
    rec = {"arch": "ring-attention-32k", "cell": "prefill_attn_layer",
           "mesh": "16x16", "compile_s": round(cs, 2),
           "roofline": RL.roofline_terms(cost),
           "collectives": RL.summarize_collectives(cost)}
    _save(outdir, "pod16x16", "ring-attention-32k", "prefill_attn_layer", rec)
    print(f"[ring] 32k-context ring attention layer: OK (compile {cs:.0f}s, "
          f"coll {cost.coll_bytes/1e9:.1f} GB/dev)")
    out.append(("ring-attention-32k × 16x16", "OK", "context-parallel", True))
    return out


def _save(outdir, mesh_name, arch, cell, rec):
    d = os.path.join(outdir, mesh_name)
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, f"{arch}__{cell}.json"), "w") as f:
        json.dump(rec, f, indent=1)


# -- main -------------------------------------------------------------------------

def cell_opts_for(cfg: ArchConfig, cell: ShapeCell,
                  mesh_shape: dict | None = None) -> CellOpts:
    """Baseline options with memory-driven escalation: if the analytic
    resident set exceeds HBM, enable (in order) sequence-parallel residuals,
    FSDP, then gradient accumulation — the same search a production config
    pass would run. Every escalation is recorded in the cell JSON."""
    from repro.launch.analytic import analytic_cell
    mesh_shape = mesh_shape or {"data": 16, "model": 16}
    opts = CellOpts(remat="full" if cell.kind == "train" else "none",
                    seq_shard=False,
                    fsdp=(cell.kind == "train"
                          and cfg.param_count() * 2 / 16 > 4 * 2 ** 30))

    def peak(o):
        return analytic_cell(cfg, cell, mesh_shape,
                             remat=(o.remat != "none"), zero_opt=o.zero_opt,
                             fsdp=o.fsdp, seq_shard=o.seq_shard,
                             accum=o.accum)["peak"]

    if cell.kind == "decode":
        return opts
    if cell.kind == "prefill":   # escalate via chunked prefill
        for escalation in (dict(accum=2), dict(accum=4)):
            if peak(opts) < HBM_PER_CHIP * 0.95:
                break
            opts = dataclasses.replace(opts, **escalation)
        return opts
    for escalation in (dict(seq_shard=True), dict(fsdp=True),
                       dict(accum=2), dict(accum=4), dict(accum=8)):
        if peak(opts) < HBM_PER_CHIP * 0.95:
            break
        opts = dataclasses.replace(opts, **escalation)
    return opts


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--suite", default="lm",
                    choices=["lm", "layout", "pp", "all"])
    ap.add_argument("--arch", default="")
    ap.add_argument("--cell", default="")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--seq-shard", default="auto",
                    choices=["auto", "on", "off"])
    ap.add_argument("--remat", default="")
    ap.add_argument("--strategy", default="", choices=["", "tp", "fsdp_dp"])
    ap.add_argument("--moe-impl", default="",
                    choices=["", "gspmd", "shard_map", "all_to_all"])
    args = ap.parse_args(argv)

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("pod16x16", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("pods2x16x16", make_production_mesh(multi_pod=True)))

    summary = []
    if args.suite in ("layout", "all"):
        summary += run_layout_suite(meshes, args.out)
    if args.suite == "pp":
        summary += run_pp_suite(args.out)

    if args.suite in ("lm", "all"):
        archs = [args.arch] if args.arch else list_archs()
        for name in archs:
            cfg = get_config(name)
            cells = ([SHAPES[args.cell]] if args.cell else cells_for(cfg))
            for cell in cells:
                opts = cell_opts_for(cfg, cell)  # escalation vs 16 GiB HBM
                if args.seq_shard != "auto":
                    opts = dataclasses.replace(
                        opts, seq_shard=args.seq_shard == "on")
                if args.remat:
                    opts = dataclasses.replace(opts, remat=args.remat)
                if args.strategy:
                    opts = dataclasses.replace(opts, strategy=args.strategy)
                if args.moe_impl:
                    opts = dataclasses.replace(opts, moe_impl=args.moe_impl)
                for mesh_name, mesh in meshes:
                    tag = f"{name} × {cell.name} × {mesh_name}"
                    try:
                        t0 = time.time()
                        compiled, cs, rules = lower_cell(cfg, cell, mesh, opts)
                        rec = analyze(compiled, cfg, cell, mesh, cs, opts)
                        _save(args.out, mesh_name, name, cell.name, rec)
                        r = rec["roofline"]
                        fits = rec["memory"]["fits_hbm"]
                        print(f"[OK]   {tag}: {r['bottleneck']}-bound "
                              f"frac={r['roofline_frac']:.2f} "
                              f"peak={rec['memory']['peak_bytes_analytic']/2**30:.1f}GiB "
                              f"fits={fits} compile={cs:.0f}s "
                              f"total={time.time()-t0:.0f}s", flush=True)
                        summary.append((tag, "OK", r["bottleneck"], fits))
                        del compiled
                    except Exception as e:
                        print(f"[FAIL] {tag}: {e}", flush=True)
                        traceback.print_exc()
                        summary.append((tag, "FAIL", str(e)[:100], False))

    n_ok = sum(1 for s in summary if s[1] == "OK")
    print(f"\n=== dry-run summary: {n_ok}/{len(summary)} OK ===")
    for s in summary:
        if s[1] != "OK":
            print("  FAILED:", s[0], s[2])
    return 0 if n_ok == len(summary) else 1


if __name__ == "__main__":
    raise SystemExit(main())
