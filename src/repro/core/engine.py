"""The refinement-engine seam: per-level force models as pluggable steps.

The multilevel driver (coarsen → place → refine, core/multilevel.py) fixes
the hierarchy but treats the per-level refinement as a black box — ROADMAP
item 4's claim is that a new force model is "a new step function, not a new
driver". This module is that seam. A ``RefinementEngine`` supplies:

  * ``init_state``   — per-level setup (the k-hop neighbor lists for
                       ``mode="neighbor"``, zero dummies otherwise);
  * ``build_refine`` / ``build_refine_many`` — the builders for the
    compile-cached single-graph and batched step programs that
    core/bucketing.py keys by shape bucket AND engine id;
  * ``lane_schedule`` — the per-lane traced schedule vector (length
    ``sched_k``): the scalars the step anneals each iteration. GiLA needs
    (temp0, temp_decay); maxent-stress adds (alpha0, alpha_decay). Keeping
    the vector per-engine (instead of a union of every engine's scalars)
    keeps dead lanes/args out of the traced programs;
  * ``tune``         — an engine hook over the freshly built per-level
    ``LevelSchedule`` (iteration budgets, mode thresholds).

Engines register themselves in ``ENGINES`` by name; ``get_engine`` lazily
imports ``core/stress.py`` so the GiLA-only path never pays for it.

The cached step signature every engine's builders must honor (staged by
``bucketing.cached_refine`` / ``cached_refine_many``):

    refine(pos0, src, dst, vmask, emask, mass, ewt, nbr_idx, nbr_mask,
           iters, sparams, params)                       # single graph
    refine_many(..., inc, iters, sparams, params, max_iters)   # batched

with ``sparams`` the ``lane_schedule`` vector — shape ``[sched_k]``
(single) or ``[lanes, sched_k]`` (batched, per-lane) — and
``params = [rep_const, ideal_len, min_dist]`` shared by all engines.

NOTE builders must resolve ``bucketing.donate_argnums_if_supported`` at
build time through the module object (not import it at module top): the
gilalint jaxpr audit monkeypatches it to force donation on CPU, and
``bucketing`` imports this module — a top-level back-import would cycle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.graphs.graph import PaddedGraph
from repro.core import gila
from repro.utils.transfer import io_boundary


class RefinementEngine:
    """One per-level refinement force model (see module docstring)."""

    #: registry id; also the cache-key / ``LevelSchedule.engine`` value
    name: str = "?"
    #: length of the ``lane_schedule`` vector
    sched_k: int = 2

    def lane_schedule(self, sched) -> tuple:
        """The per-lane annealing scalars for one level, length ``sched_k``."""
        raise NotImplementedError

    def span_args(self, sched) -> dict:
        """Arguments the refine dispatch spans record beside ``mode`` and
        ``iters``: the annealing a step of ``sched`` runs beyond the
        cooling (none for GiLA)."""
        return {}

    def tune(self, sched):
        """Hook over a freshly built ``LevelSchedule``; default: unchanged."""
        return sched

    def init_state(self, g: PaddedGraph, sched, seed: int):
        """Per-level (nbr_idx, nbr_mask): the k-hop lists for neighbor mode
        (host build, shared sampling across engines so forces are comparable
        on identical lists), zero dummies for the dense modes."""
        if sched.mode == "neighbor":
            return gila.build_level_neighbors(g, sched.k, sched.cap,
                                              seed=seed)
        with io_boundary():
            return (jnp.zeros((g.n_pad, 1), jnp.int32),
                    jnp.zeros((g.n_pad, 1), bool))

    def build_refine(self, mode: str, grid_dim: int, cell_cap: int):
        raise NotImplementedError

    def build_refine_many(self, mode: str, grid_dim: int, cell_cap: int,
                          inc_k: int):
        raise NotImplementedError


class GilaEngine(RefinementEngine):
    """Fruchterman–Reingold with k-hop-restricted repulsion (paper §3.4) —
    the per-iteration math lives in ``gila.layout_iteration``; the builders
    here are the compile-cached loop wrappers around it."""

    name = "gila"
    sched_k = 2                     # (temp0, temp_decay)

    def lane_schedule(self, sched) -> tuple:
        return (sched.temp0, sched.temp_decay)

    def build_refine(self, mode: str, grid_dim: int, cell_cap: int):
        """Jitted per-level refinement with TRACED iteration count and
        cooling schedule: one compile covers every level (and every graph)
        whose arrays land in the same shape bucket. pos0 is donated."""
        from repro.core import bucketing

        def refine(pos0, src, dst, vmask, emask, mass, ewt, nbr_idx,
                   nbr_mask, iters, sparams, params):
            g = PaddedGraph(src=src, dst=dst, vmask=vmask, emask=emask,
                            mass=mass, ewt=ewt, n=0, m=0)

            def body(i, carry):
                pos, temp = carry
                pos = gila.layout_iteration(g, pos, nbr_idx, nbr_mask,
                                            params, temp, mode=mode,
                                            grid_dim=grid_dim,
                                            cell_cap=cell_cap)
                return pos, temp * sparams[1]

            pos, _ = jax.lax.fori_loop(0, iters, body, (pos0, sparams[0]))
            return pos

        return jax.jit(
            refine,
            donate_argnums=bucketing.donate_argnums_if_supported(0))

    def build_refine_many(self, mode: str, grid_dim: int, cell_cap: int,
                          inc_k: int):
        """Jitted batched refinement over ``[B, n_pad]`` lanes.

        Per-lane arithmetic is element-for-element the computation of
        ``build_refine`` (gila.layout_iteration), so every lane is
        bit-identical to the same level refined alone; the per-lane traced
        iteration budget is masked against the group's shared trip count.

        The *lowering* differs from a naive ``vmap`` in one deliberate way:
        aggregation/gather with per-lane indices lowers to batched
        scatter/gather HLO that XLA CPU executes an order of magnitude
        slower than the flat single-graph form. So the lanes are flattened
        into ONE index space — lane b's slot v lives at
        ``b * (n_pad + 1) + v``, a per-lane zero sentinel row coming along
        at slot n_pad — and the attraction aggregation runs, for
        ``inc_k > 0``, as ``inc_k`` unrolled gathered adds over the
        incidence table (``packing.incidence_table``): each vertex
        accumulates its incoming edge vectors in ascending slot order,
        which is byte-for-byte the accumulation order of the sequential
        step's ``segment_sum`` scatter — so the float sums stay
        bit-identical while costing ~15× less than a batched scatter.
        Hub-heavy lanes (``inc_k == 0``) fall back to one flat
        ``segment_sum`` over the fused index space. The repulsion of every
        mode (and the cooling clamp) is vmapped over the same ops the
        single-graph step calls, so on the chip both run the same Pallas
        kernels — in grid mode that includes ``bin_vertices``, so spatial
        binning stays per-graph.
        """
        from repro.core import bucketing
        from repro.kernels.nbody import ops as nbody_ops
        from repro.kernels.neighbor_force import ops as nf_ops

        def refine_many(pos0, src, dst, vmask, emask, mass, ewt, nbr_idx,
                        nbr_mask, inc, iters, sparams, params, max_iters):
            B, n_pad = pos0.shape[0], pos0.shape[1]
            m_pad = src.shape[1]
            C, L, md = params[0], params[1], params[2]
            temp_decay = sparams[:, 1]
            offs = (jnp.arange(B, dtype=jnp.int32) * (n_pad + 1))[:, None]
            flat_dst = (dst + offs).reshape(-1)
            flat_src = src + offs
            flat_dst_clip = jnp.clip(dst, 0, n_pad - 1) + offs
            ell = jnp.maximum(ewt, 1e-6) * L                     # [B, m_pad]
            # incidence slots in the fused per-lane edge index space
            flat_inc = inc + (jnp.arange(B, dtype=jnp.int32)
                              * (m_pad + 1))[:, None, None]

            def flat_pos(pos):
                """[B, n_pad, 2] → [B*(n_pad+1), 2] with a zero sentinel
                row per lane (the dense-array 'empty inbox')."""
                posp = jnp.concatenate(
                    [pos, jnp.zeros((B, 1, 2), pos.dtype)], axis=1)
                return posp.reshape(B * (n_pad + 1), 2)

            def attraction(pos):
                flat = flat_pos(pos)
                pos_src = flat[flat_src]                         # [B, m_pad, 2]
                pos_dst = flat[flat_dst_clip]
                delta = pos_src - pos_dst
                dist = jnp.sqrt(jnp.sum(delta * delta, axis=2) + md ** 2)
                f = (dist * dist) / ell
                vec = delta / dist[..., None] * f[..., None]
                vec = jnp.where(emask[..., None], vec, 0.0)
                if inc_k > 0:
                    vflat = jnp.concatenate(
                        [vec, jnp.zeros((B, 1, 2), vec.dtype)],
                        axis=1).reshape(B * (m_pad + 1), 2)
                    acc = jnp.zeros((B, n_pad, 2), vec.dtype)
                    for k in range(inc_k):    # left-assoc: scatter order
                        acc = acc + vflat[flat_inc[:, :, k]]
                    return acc
                out = jax.ops.segment_sum(vec.reshape(-1, 2), flat_dst,
                                          num_segments=B * (n_pad + 1))
                return out.reshape(B, n_pad + 1, 2)[:, :n_pad]

            if mode == "exact":
                def repulsion(pos):
                    return jax.vmap(nbody_ops.nbody_repulsion,
                                    in_axes=(0, 0, 0, None, None, None))(
                        pos, mass, vmask, C, L, md)
            elif mode == "neighbor":
                def repulsion(pos):
                    return jax.vmap(nf_ops.neighbor_repulsion,
                                    in_axes=(0, 0, 0, 0, 0, None, None, None))(
                        pos, mass, nbr_idx, nbr_mask, vmask, C, L, md)
            else:
                from repro.kernels.grid_force import ops as grid_ops

                def repulsion(pos):
                    return jax.vmap(lambda p, m_, v_: grid_ops.grid_repulsion(
                        p, m_, v_, C, L, md,
                        grid_dim=grid_dim, cell_cap=cell_cap))(
                        pos, mass, vmask)

            def body(i, carry):
                pos, temp = carry
                # the single-graph step's scopes (gila.layout_iteration)
                with jax.named_scope("gila.repulsion"):
                    rep = repulsion(pos)
                with jax.named_scope("gila.attraction"):
                    att = attraction(pos)
                f = rep + att
                with jax.named_scope("gila.move"):
                    norm = jnp.sqrt(jnp.sum(f * f, axis=2) + 1e-12)
                    step = jnp.minimum(norm, temp[:, None])
                    new = pos + f / norm[..., None] * step[..., None]
                    new = jnp.where(vmask[..., None], new, 0.0)
                live = i < iters
                return (jnp.where(live[:, None, None], new, pos),
                        jnp.where(live, temp * temp_decay, temp))

            pos, _ = jax.lax.fori_loop(0, max_iters, body,
                                       (pos0, sparams[:, 0]))
            return pos

        return jax.jit(
            refine_many,
            donate_argnums=bucketing.donate_argnums_if_supported(0))


# -- registry -----------------------------------------------------------------

ENGINES: dict[str, RefinementEngine] = {}


def register(eng: RefinementEngine) -> RefinementEngine:
    ENGINES[eng.name] = eng
    return eng


def get_engine(name: str) -> RefinementEngine:
    """Engine by registry id; 'stress' loads core/stress.py on first use."""
    if name not in ENGINES and name == "stress":
        import repro.core.stress  # noqa: F401  — registers itself on import
    try:
        return ENGINES[name]
    except KeyError:
        raise ValueError(f"unknown refinement engine {name!r}; "
                         f"known: {sorted(ENGINES)}") from None


register(GilaEngine())
