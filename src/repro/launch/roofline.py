"""Roofline-term extraction from compiled SPMD HLO (DESIGN.md §5).

`compiled.cost_analysis()` on XLA:CPU is per-device and counts while-loop
bodies ONCE. This module re-derives per-device FLOPs / HBM bytes /
collective bytes by walking the optimized HLO call graph and multiplying
while bodies by their trip counts (taken from the `known_trip_count`
backend config XLA attaches to every counted loop — scans over layers,
attention KV chunks, SSD chunk scans are all covered, nested included).

Accounting rules (mirrors what cost_analysis fuses):
  * FLOPs: dots = 2·|out|·K (K from contracting dims); elementwise math =
    |out|; reduces = |operand|. Fusion bodies contribute FLOPs once per
    call; fusion-internal traffic contributes no bytes.
  * bytes: operands+result of every top-level instruction (fusion calls
    count at the call boundary) — an HBM-traffic proxy at fusion
    granularity.
  * collectives: ring-model bytes/device — all-gather/reduce-scatter
    (g−1)/g·size, all-reduce 2(g−1)/g·size, all-to-all (g−1)/g·size,
    collective-permute size — with g parsed from replica_groups.

Self-check: with trip counts forced to 1 the FLOPs agree with
cost_analysis() (validated in tests/test_roofline.py).
"""
from __future__ import annotations

import dataclasses
import json
import re
from collections import defaultdict

import numpy as np

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "abs",
    "negate", "exponential", "log", "tanh", "rsqrt", "sqrt", "power",
    "logistic", "cosine", "sine", "floor", "ceil", "round-nearest-afz",
    "remainder", "atan2", "expm1", "log-plus-one", "cbrt", "erf",
}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"^(\w+)\[([\d,]*)\]")
_INSTR_RE = re.compile(r"^\s+(ROOT\s+)?%([\w.\-]+)\s+=\s+(.*)$")
_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s+->")


def normalize_cost_analysis(ca) -> dict:
    """`Compiled.cost_analysis()` → flat dict, across JAX versions.

    JAX 0.4.x returns a one-element list of per-device dicts; newer JAX
    returns the dict directly; some backends return None.
    """
    if ca is None:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca)


def _parse_shape(type_str):
    """'f32[64,128]{1,0}' → (dtype, shape) | None for tuples/tokens."""
    m = _SHAPE_RE.match(type_str)
    if not m:
        return None
    dt, dims = m.group(1), m.group(2)
    if dt not in DTYPE_BYTES:
        return None
    shape = tuple(int(d) for d in dims.split(",")) if dims else ()
    return dt, shape


def _nbytes(sh):
    if sh is None:
        return 0
    dt, shape = sh
    return DTYPE_BYTES[dt] * int(np.prod(shape)) if shape else DTYPE_BYTES[dt]


@dataclasses.dataclass
class Instr:
    name: str
    opcode: str
    shape: tuple | None
    operands: list
    rest: str


def parse_module(text: str):
    """→ (computations: name → [Instr], entry_name, shapes: name → shape)."""
    computations: dict[str, list[Instr]] = {}
    shapes: dict[str, tuple | None] = {}
    entry = None
    cur = None
    for line in text.splitlines():
        mc = _COMP_RE.match(line)
        if mc and line.rstrip().endswith("{"):
            cur = mc.group(2)
            computations[cur] = []
            if mc.group(1):
                entry = cur
            continue
        if line.startswith("}"):
            cur = None
            continue
        mi = _INSTR_RE.match(line)
        if not (cur and mi):
            continue
        name, body = mi.group(2), mi.group(3)
        sh = _parse_shape(body)
        # tuple results: leave shape None (elements resolved via gte)
        # opcode = first word after the type
        rest = body
        # strip the result type
        depth = 0
        i = 0
        if body.startswith("("):
            for i, ch in enumerate(body):
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        break
            rest = body[i + 1:].strip()
        else:
            sp = body.find(" ")
            rest = body[sp + 1:].strip() if sp > 0 else ""
        mop = re.match(r"([\w\-]+)\(", rest)
        opcode = mop.group(1) if mop else rest.split("(")[0].strip()
        operands = re.findall(r"%([\w.\-]+)", rest.split("),", 1)[0]
        ) if "(" in rest else []
        shapes[name] = sh
        computations[cur].append(Instr(name, opcode, sh, operands, rest))
    return computations, entry, shapes


def _group_size(rest: str, world: int) -> int:
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", rest)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", rest)
    if m:
        return len(m.group(1).split(","))
    return world


def _dot_flops(instr: Instr, shapes) -> float:
    out = instr.shape
    if out is None:
        return 0.0
    lhs_sh = shapes.get(instr.operands[0]) if instr.operands else None
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", instr.rest)
    k = 1
    if lhs_sh and m and m.group(1):
        for d in m.group(1).split(","):
            k *= lhs_sh[1][int(d)]
    return 2.0 * float(np.prod(out[1]) if out[1] else 1) * k


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    coll_bytes: float = 0.0
    coll_detail: list = dataclasses.field(default_factory=list)

    def __iadd__(self, o):
        self.flops += o.flops
        self.bytes += o.bytes
        self.coll_bytes += o.coll_bytes
        self.coll_detail += o.coll_detail
        return self

    def scaled(self, k: float):
        return Cost(self.flops * k, self.bytes * k, self.coll_bytes * k,
                    [(n, b * k, g, int(mult * k)) for (n, b, g, mult)
                     in self.coll_detail])


def analyze_text(text: str, world: int = 1, *, force_trip_one: bool = False):
    """Parse optimized HLO → per-device Cost with loop multipliers applied."""
    comps, entry, shapes = parse_module(text)
    memo: dict[tuple, Cost] = {}

    def comp_cost(cname: str, in_fusion: bool) -> Cost:
        key = (cname, in_fusion)
        if key in memo:
            return memo[key]
        total = Cost()
        for ins in comps.get(cname, []):
            op = ins.opcode
            if op in ("parameter", "constant", "tuple", "get-tuple-element",
                      "bitcast", "after-all", "partition-id", "replica-id"):
                continue
            rb = _nbytes(ins.shape)
            ob = sum(_nbytes(shapes.get(o)) for o in ins.operands)
            if op == "fusion":
                m = re.search(r"calls=%?([\w.\-]+)", ins.rest)
                if m:
                    sub = comp_cost(m.group(1), True)
                    total += Cost(flops=sub.flops,
                                  coll_bytes=sub.coll_bytes,
                                  coll_detail=sub.coll_detail)
                if not in_fusion:
                    total += Cost(bytes=rb + ob)
                continue
            if op == "while":
                mb = re.search(r"body=%?([\w.\-]+)", ins.rest)
                mc = re.search(r"condition=%?([\w.\-]+)", ins.rest)
                mt = re.search(r'known_trip_count\D*(\d+)', ins.rest)
                trip = 1 if force_trip_one else (
                    int(mt.group(1)) if mt else 1)
                if mb:
                    total += comp_cost(mb.group(1), in_fusion).scaled(trip)
                if mc:
                    total += comp_cost(mc.group(1), in_fusion).scaled(trip)
                continue
            if op in ("call", "conditional"):
                for m in re.finditer(r"(?:to_apply|calls)=%?([\w.\-]+)",
                                     ins.rest):
                    total += comp_cost(m.group(1), in_fusion)
                if not in_fusion:
                    total += Cost(bytes=rb + ob)
                continue
            base = op.replace("-start", "")
            if base in COLLECTIVES:
                g = _group_size(ins.rest, world)
                size = max(rb, ob)
                if base == "all-reduce":
                    moved = 2.0 * (g - 1) / g * size
                elif base == "collective-permute":
                    moved = float(rb)
                else:
                    moved = (g - 1) / g * size
                # XLA:CPU emulates bf16 in f32, so activation/grad
                # collectives appear at 2× their TPU width; on TPU they
                # stay bf16. Halve f32 collective payloads (the only
                # intended f32 collectives are tiny loss-psum scalars).
                if ins.shape is not None and ins.shape[0] == "f32":
                    moved *= 0.5
                total += Cost(coll_bytes=moved,
                              coll_detail=[(base, moved, g, 1)])
                if not in_fusion:
                    total += Cost(bytes=rb + ob)
                continue
            if op.endswith("-done"):
                continue
            if op in ("dynamic-slice", "gather"):
                # reads only the sliced window, not the full operand
                rb_eff = 2 * rb
                if not in_fusion:
                    total += Cost(bytes=rb_eff)
                continue
            if op in ("dynamic-update-slice", "scatter"):
                upd = (_nbytes(shapes.get(ins.operands[1]))
                       if len(ins.operands) > 1 else rb)
                if not in_fusion:
                    total += Cost(bytes=2 * upd)
                continue
            fl = 0.0
            if op == "dot":
                fl = _dot_flops(ins, shapes)
            elif op in ELEMENTWISE:
                fl = float(np.prod(ins.shape[1])) if ins.shape and ins.shape[1] else 1.0
            elif op in ("reduce", "reduce-window"):
                fl = sum(float(np.prod(shapes[o][1]))
                         for o in ins.operands[:1]
                         if shapes.get(o) and shapes[o][1])
            elif op == "convolution":
                fl = 2.0 * _nbytes(ins.shape) / DTYPE_BYTES[ins.shape[0]]
            if in_fusion:
                total += Cost(flops=fl)
            else:
                total += Cost(flops=fl, bytes=rb + ob)
        memo[key] = total
        return total

    return comp_cost(entry, False)


# -- roofline terms ----------------------------------------------------------------

from repro.launch.mesh import peaks


def roofline_terms(cost: Cost, *, model_flops_per_device: float = 0.0):
    pk = peaks()
    compute_s = cost.flops / pk["flops_bf16"]
    memory_s = cost.bytes / pk["hbm_bw"]
    coll_s = cost.coll_bytes / pk["ici_bw"]
    dom = max((compute_s, "compute"), (memory_s, "memory"),
              (coll_s, "collective"))
    total = max(compute_s, memory_s, coll_s)
    return {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": coll_s,
        "bottleneck": dom[1],
        "flops": cost.flops,
        "bytes": cost.bytes,
        "coll_bytes": cost.coll_bytes,
        "model_flops": model_flops_per_device,
        "useful_ratio": (model_flops_per_device / cost.flops
                         if cost.flops else 0.0),
        "roofline_frac": (model_flops_per_device / pk["flops_bf16"] / total
                          if total > 0 else 0.0),
    }


def summarize_collectives(cost: Cost, top: int = 6):
    agg = defaultdict(float)
    for (name, b, g, mult) in cost.coll_detail:
        agg[(name, g)] += b
    rows = sorted(agg.items(), key=lambda kv: -kv[1])[:top]
    return [{"op": k[0], "group": k[1], "bytes": v} for k, v in rows]
