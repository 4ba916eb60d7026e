"""Layer 2 of gilalint: trace every registered cached-step family and audit
the jaxprs the production code would actually run.

The AST layer (rules.py) reasons about source; this layer reasons about the
traced program. For each family it calls the PRODUCTION staging entry point
(``bucketing.cached_refine``, ``bucketing.cached_refine_many``,
``distributed.cached_layout_step``) on small representative graphs, then
checks:

  A1  no host round-trips: the jaxpr contains no callback / infeed /
      outfeed / device_put primitives (anywhere, including sub-jaxprs of
      while/scan/jit/shard_map) — a hot step must stay on device.
  A2  dtype discipline: no float64/complex128 avals anywhere in the traced
      program (CPU silently eats f64; accelerators pay 2x for it).
  A3  donation: with ``donate_argnums_if_supported`` forced on (it is a
      no-op on CPU), the builder's jit donates argument 0 — the position
      buffer — so accelerators update positions in place.
  A4  padding invariance, structurally: two graphs with DIFFERENT true
      sizes in the SAME shape bucket must produce the identical cache key
      and a textually identical jaxpr — the compiled program may depend on
      the bucket only, never on the payload.

``run_audit()`` returns a JSON-ready report; any entry in a family's
``failures`` list fails the CLI (tools/gilalint/__main__.py) and CI.
Keep graphs here tiny: the audit only traces (and lowers, for A3); it
never executes a step.
"""
from __future__ import annotations

import contextlib

import numpy as np

# primitive names that imply a host round-trip or transfer inside the step
_BANNED_SUBSTRINGS = ("callback",)
_BANNED_PRIMS = {
    "infeed", "outfeed", "device_put", "copy_to_host_async",
    "host_local_array_to_global_array", "global_array_to_host_local_array",
}
_BANNED_DTYPES = {"float64", "complex128"}


# -- jaxpr walking -------------------------------------------------------------

def _sub_jaxprs(value):
    """Jaxprs hiding inside an eqn param (ClosedJaxpr, Jaxpr, or lists of
    either — e.g. cond branches)."""
    vals = value if isinstance(value, (list, tuple)) else (value,)
    for v in vals:
        inner = getattr(v, "jaxpr", v)       # ClosedJaxpr -> Jaxpr
        if hasattr(inner, "eqns"):
            yield inner


def iter_eqns(jaxpr):
    """Every eqn in ``jaxpr`` and, recursively, in its sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from iter_eqns(sub)


def primitive_names(closed) -> set:
    return {e.primitive.name for e in iter_eqns(closed.jaxpr)}


def aval_dtypes(closed) -> set:
    """Dtype names of every var flowing through the program."""
    out = set()

    def scoop(jaxpr):
        for v in list(jaxpr.invars) + list(jaxpr.constvars):
            dt = getattr(getattr(v, "aval", None), "dtype", None)
            if dt is not None:
                out.add(str(dt))
        for eqn in jaxpr.eqns:
            for v in list(eqn.invars) + list(eqn.outvars):
                dt = getattr(getattr(v, "aval", None), "dtype", None)
                if dt is not None:
                    out.add(str(dt))
            for p in eqn.params.values():
                for sub in _sub_jaxprs(p):
                    scoop(sub)

    scoop(closed.jaxpr)
    return out


def _check_program(family: str, closed, failures: list) -> dict:
    """A1 + A2 on one traced program; returns summary facts."""
    prims = primitive_names(closed)
    bad = sorted(
        p for p in prims
        if p in _BANNED_PRIMS or any(s in p for s in _BANNED_SUBSTRINGS))
    for p in bad:
        failures.append({
            "rule": "A1",
            "message": f"{family}: host-transfer/callback primitive "
                       f"'{p}' inside the cached step — hot steps must "
                       f"stay on device (stage inputs before the call)"})
    dts = aval_dtypes(closed)
    for dt in sorted(dts & _BANNED_DTYPES):
        failures.append({
            "rule": "A2",
            "message": f"{family}: {dt} aval in the cached step — keep "
                       f"kernels in f32 (gilalint R6 flags the source "
                       f"site)"})
    return {"n_primitives": len(prims), "dtypes": sorted(dts)}


def _donates_arg0(jitted, *args) -> bool:
    """True if tracing ``jitted`` yields a top-level ``jit`` equation that
    donates its first argument (the position buffer)."""
    import jax
    closed = jax.make_jaxpr(jitted)(*args)
    for eqn in closed.jaxpr.eqns:
        if eqn.primitive.name == "jit":
            donated = eqn.params.get("donated_invars")
            return bool(donated) and bool(donated[0])
    return False


@contextlib.contextmanager
def _donation_forced():
    """Force ``donate_argnums_if_supported`` on: on CPU it returns () (XLA
    ignores donation there), which would make A3 vacuous."""
    from repro.core import bucketing
    orig = bucketing.donate_argnums_if_supported
    bucketing.donate_argnums_if_supported = lambda *argnums: tuple(argnums)
    try:
        yield
    finally:
        bucketing.donate_argnums_if_supported = orig


# -- shared fixtures -----------------------------------------------------------

def _path_graph(n: int):
    from repro.graphs.graph import build_graph
    edges = np.stack([np.arange(n - 1), np.arange(1, n)], 1)
    return build_graph(edges, n, bucket=True)


def _sched(n: int, n_pad: int, engine: str = "gila"):
    from repro.core.schedule import make_schedule
    return make_schedule(0, 1, n, n - 1, n_pad=n_pad, engine=engine)


# -- the registered families ---------------------------------------------------

def _audit_single(engine: str = "gila") -> dict:
    """bucketing.cached_refine — the single-graph bucketed level step."""
    import jax
    import jax.numpy as jnp

    from repro.core import bucketing
    from repro.core.gila import random_init
    from repro.utils.transfer import io_boundary

    failures: list = []
    traced = []
    # two true sizes, one 256-vertex bucket — the A4 pair
    for n in (70, 90):
        g = _path_graph(n)
        sched = _sched(n, g.n_pad, engine)
        pos0 = random_init(g, 1.0, seed=0)
        with io_boundary():
            nbr_idx = jnp.zeros((g.n_pad, 1), jnp.int32)
            nbr_mask = jnp.zeros((g.n_pad, 1), bool)
        key, fn, _, args = bucketing.cached_refine(
            g, pos0, sched, nbr_idx, nbr_mask, ideal_len=1.0, rep_const=1.0)
        traced.append((n, key, jax.make_jaxpr(fn)(*args), args, sched))

    (_, key_a, jx_a, args, sched), (_, key_b, jx_b, _, _) = traced
    facts = _check_program(f"refine_single[{engine}]", jx_a, failures)
    if key_a != key_b:
        failures.append({
            "rule": "A4",
            "message": f"refine_single: same-bucket graphs produced "
                       f"different cache keys {key_a} vs {key_b}"})
    if str(jx_a) != str(jx_b):
        failures.append({
            "rule": "A4",
            "message": "refine_single: same-bucket graphs traced to "
                       "structurally different jaxprs — the step depends "
                       "on payload, not just the shape bucket"})
    with _donation_forced():
        fn2 = bucketing._build_refine(sched.mode, sched.grid_dim,
                                      sched.cell_cap, engine=engine)
        if not _donates_arg0(fn2, *args):
            failures.append({
                "rule": "A3",
                "message": "refine_single: position buffer (arg 0) is "
                           "not donated by _build_refine's jit"})
    return {"entry": "core.bucketing.cached_refine", "cache_key": repr(key_a),
            "failures": failures, **facts}


def _audit_many(engine: str = "gila") -> dict:
    """bucketing.cached_refine_many — the batched multi-graph lane step."""
    import jax
    import jax.numpy as jnp

    from repro.core import bucketing
    from repro.core.gila import random_init
    from repro.utils.transfer import io_boundary

    failures: list = []
    traced = []
    # two true sizes, one 64-vertex/512-edge lane bucket
    for n in (40, 55):
        g = _path_graph(n)
        sched = _sched(n, g.n_pad, engine)
        pos0 = random_init(g, 1.0, seed=0)
        req = bucketing.make_request(g, pos0, sched, seed=0)
        with io_boundary():
            dummy = (jnp.zeros((req.g.n_pad, 1), jnp.int32),
                     jnp.zeros((req.g.n_pad, 1), bool))
        key, fn, _, args = bucketing.cached_refine_many(
            [req], [dummy], ideal_len=1.0, rep_const=1.0)
        traced.append((key, jax.make_jaxpr(fn)(*args), args, req))

    (key_a, jx_a, args, req), (key_b, jx_b, _, _) = traced
    facts = _check_program(f"refine_many[{engine}]", jx_a, failures)
    if key_a != key_b:
        failures.append({
            "rule": "A4",
            "message": f"refine_many: same-lane-bucket graphs produced "
                       f"different cache keys {key_a} vs {key_b}"})
    if str(jx_a) != str(jx_b):
        failures.append({
            "rule": "A4",
            "message": "refine_many: same-lane-bucket graphs traced to "
                       "structurally different jaxprs"})
    with _donation_forced():
        fn2 = bucketing._build_refine_many(
            req.sched.mode, req.sched.grid_dim, req.sched.cell_cap,
            req.inc_k, engine=engine)
        if not _donates_arg0(fn2, *args):
            failures.append({
                "rule": "A3",
                "message": "refine_many: position batch (arg 0) is not "
                           "donated by _build_refine_many's jit"})
    return {"entry": "core.bucketing.cached_refine_many",
            "cache_key": repr(key_a), "failures": failures, **facts}


def _audit_dist(engine: str = "gila") -> dict:
    """distributed.cached_layout_step — the sharded level superstep.

    Traced through ShapeDtypeStructs (no allocation) on a host mesh over
    whatever devices exist — 8 forced CPU devices from the CLI, 1 in a
    bare pytest process; both shard the same program structure.
    """
    import jax

    from repro.core import bucketing, distributed
    from repro.launch.mesh import make_host_mesh

    failures: list = []
    mesh = make_host_mesh()
    vtx = distributed.vtx_axes(mesh)
    vsize = distributed._axis_size(mesh, vtx)
    msize = mesh.shape["model"]

    traced = []
    for n in (70, 90):
        g = _path_graph(n)
        n_pad = distributed._round_up(g.n_pad, vsize * msize)
        _, _, _, _, m_pad = distributed.partition_edges(
            np.asarray(g.src), np.asarray(g.dst), np.asarray(g.emask),
            np.asarray(g.ewt), n_pad, vsize, bucket=True)
        jitted, _, _ = distributed.cached_layout_step(
            mesh, n_pad, m_pad, 1, mode="exact", engine=engine)
        specs = distributed.layout_step_specs(n_pad, m_pad, 1, mode="exact",
                                              engine=engine)
        args = tuple(specs.values())
        traced.append(((n_pad, m_pad), jax.make_jaxpr(jitted)(*args), args))

    (shape_a, jx_a, args), (shape_b, jx_b, _) = traced
    facts = _check_program(f"dist_step[{engine}]", jx_a, failures)
    if shape_a != shape_b:
        failures.append({
            "rule": "A4",
            "message": f"dist_step: same-bucket graphs landed in "
                       f"different (n_pad, m_pad) {shape_a} vs {shape_b} "
                       f"— partition_edges bucketing regressed"})
    if str(jx_a) != str(jx_b):
        failures.append({
            "rule": "A4",
            "message": "dist_step: same-bucket graphs traced to "
                       "structurally different jaxprs"})
    with _donation_forced():
        step, _ = distributed.layout_train_step(
            mesh, shape_a[0], shape_a[1], 1, mode="exact", engine=engine)
        jd = jax.jit(
            step,
            donate_argnums=bucketing.donate_argnums_if_supported(0))
        if not _donates_arg0(jd, *args):
            failures.append({
                "rule": "A3",
                "message": "dist_step: position buffer (arg 0) is not "
                           "donated by cached_layout_step's jit"})
    return {"entry": "core.distributed.cached_layout_step",
            "cache_key": repr(("dist_step",) + shape_a),
            "mesh": dict(mesh.shape), "failures": failures, **facts}


def _audit_merger() -> dict:
    """solar_merger.cached_merger — the device-resident coarsening loop
    (election → growth → halting vote as one ``lax.while_loop``)."""
    import jax

    from repro.core import solar_merger
    from repro.utils.transfer import io_boundary

    failures: list = []
    traced = []
    # two true sizes, one 256-vertex bucket — the A4 pair
    for n in (70, 90):
        g = _path_graph(n)
        st = solar_merger.init_state(g)
        with io_boundary():
            rng = jax.random.PRNGKey(0)
        key, fn, _, args = solar_merger.cached_merger(
            g, st, rng, p_sun=0.35, max_rounds=96, force_every=4)
        traced.append((key, jax.make_jaxpr(fn)(*args), args))

    (key_a, jx_a, args), (key_b, jx_b, _) = traced
    facts = _check_program("merger", jx_a, failures)
    if key_a != key_b:
        failures.append({
            "rule": "A4",
            "message": f"merger: same-bucket graphs produced different "
                       f"cache keys {key_a} vs {key_b}"})
    if str(jx_a) != str(jx_b):
        failures.append({
            "rule": "A4",
            "message": "merger: same-bucket graphs traced to structurally "
                       "different jaxprs — the loop depends on payload, "
                       "not just the shape bucket"})
    with _donation_forced():
        fn2 = solar_merger._build_merger()
        if not _donates_arg0(fn2, *args):
            failures.append({
                "rule": "A3",
                "message": "merger: MergerState (arg 0) is not donated by "
                           "_build_merger's jit — the loop must update the "
                           "assignment buffers in place"})
    return {"entry": "core.solar_merger.cached_merger",
            "cache_key": repr(key_a), "failures": failures, **facts}


def _audit_coarsen() -> dict:
    """solar_merger.cached_compact + cached_assemble — the two halves of
    the on-device ``next_level`` compaction (input-bucket compaction, then
    coarse-bucket assembly around the host's true-size read)."""
    import jax

    from repro.core import solar_merger

    failures: list = []
    traced = []
    for n in (70, 90):
        g = _path_graph(n)
        st = solar_merger.init_state(g)
        key, fn, _, args = solar_merger.cached_compact(g, st)
        traced.append((key, jax.make_jaxpr(fn)(*args), args))

    (key_a, jx_a, cargs), (key_b, jx_b, _) = traced
    facts = _check_program("coarsen.compact", jx_a, failures)
    if key_a != key_b:
        failures.append({
            "rule": "A4",
            "message": f"coarsen: same-bucket graphs produced different "
                       f"compact cache keys {key_a} vs {key_b}"})
    if str(jx_a) != str(jx_b):
        failures.append({
            "rule": "A4",
            "message": "coarsen: same-bucket graphs traced to structurally "
                       "different compact jaxprs"})

    # assemble: trace at one coarse bucket decision; its key is pure shape
    # statics, so the A4 pair shares it by construction — audit A1/A2/A3
    import jax.numpy as jnp
    from repro.utils.transfer import io_boundary
    (parent_coarse, sun_of, depth, state, spi, n_coarse, cmass,
     ce_lo, ce_hi, ce_w, n_edges) = jax.eval_shape(
        lambda *a: solar_merger._build_compact()(*a), *cargs)
    with io_boundary():
        a_args = (jnp.zeros(ce_lo.shape, jnp.int32),
                  jnp.zeros(ce_hi.shape, jnp.int32),
                  jnp.zeros(ce_w.shape, jnp.float32),
                  jnp.asarray(0, jnp.int32),
                  jnp.zeros(cmass.shape, jnp.float32),
                  jnp.asarray(0, jnp.int32))
    akey, afn, _, aargs = solar_merger.cached_assemble(
        *a_args, n_pad_c=256, m_pad_c=256)
    ajx = jax.make_jaxpr(afn)(*aargs)
    _check_program("coarsen.assemble", ajx, failures)

    with _donation_forced():
        if not _donates_arg0(solar_merger._build_compact(), *cargs):
            failures.append({
                "rule": "A3",
                "message": "coarsen: MergerState (arg 0) is not donated by "
                           "_build_compact's jit"})
        if not _donates_arg0(solar_merger._build_assemble(256, 256), *aargs):
            failures.append({
                "rule": "A3",
                "message": "coarsen: edge buffer (arg 0) is not donated by "
                           "_build_assemble's jit"})
    return {"entry": "core.solar_merger.cached_compact + cached_assemble",
            "cache_key": repr((key_a, akey)), "failures": failures, **facts}


# every cached-step family in the repo; adding a CompileCache user without
# registering it here is itself a finding (A0) raised by tests/test_gilalint
FAMILIES = (
    ("refine_single", _audit_single),
    ("refine_many", _audit_many),
    ("dist_step", _audit_dist),
    ("merger", _audit_merger),
    ("coarsen", _audit_coarsen),
    # the stress engine's step family: same staging entry points, engine id
    # widened into the cache key (see core/engine.py)
    ("refine_single_stress", lambda: _audit_single("stress")),
    ("refine_many_stress", lambda: _audit_many("stress")),
    ("dist_step_stress", lambda: _audit_dist("stress")),
)


def run_audit() -> dict:
    """Trace + audit every family. Harness errors become A0 failures so a
    broken audit fails CI loudly instead of passing vacuously."""
    families = {}
    for name, fn in FAMILIES:
        try:
            families[name] = fn()
        except Exception as exc:          # noqa: BLE001 - report, don't mask
            families[name] = {
                "entry": None,
                "failures": [{"rule": "A0",
                              "message": f"{name}: audit harness error: "
                                         f"{exc!r}"}],
            }
    return {"families": families}
