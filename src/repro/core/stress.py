"""Maxent-stress refinement engine (PAPERS.md: Meyerhenke/Nöllenburg/Schulz,
*Drawing Large Graphs by Multilevel Maxent-Stress Optimization*).

The stress model places every vertex at the weighted barycenter of the
*targets* its edges prescribe: edge e = (j → i) wants i at distance
ℓ_e = max(ewt_e, 1e-6)·L from j, so it votes for the point on the j→i ray
at that distance, with weight w_e = 1/ℓ_e². Minimizing pure stress over
only the known (edge) distances collapses non-neighbors; the maxent
regularizer counters with a repulsive entropy term whose strength α anneals
from ``ALPHA0`` by a total factor ``ALPHA_SHRINK`` over the level's
iterations. The local
(Jacobi) iteration per vertex i:

    x_i ← ( Σ_e w_e · tgt_e  +  α · r_i ) / ρ_i ,    ρ_i = Σ_e w_e

with r_i the repulsion evaluated through the SAME exact / neighbor / grid
kernels GiLA uses (``gila._repulsion_*``), passing α·C in the kernels'
repulsion-constant slot — the entropy term reuses the k-hop sampling and
the grid/neighbor kernels rather than growing kernels of its own. Vertices
with ρ_i = 0 (padding, isolated) keep their position; the displacement is
clamped by the cooling temperature exactly like GiLA's update, which keeps
the update padding-invariant and bit-stable across shape buckets.

Because the hierarchy compounds edge weights level-to-level
(``solar_merger.next_level`` sums path weights into the coarse ``ewt``),
the weighted target distances come from the hierarchy for free: a coarse
edge's ℓ_e is the accumulated fine-path length, which is exactly the
distance estimate the multilevel maxent-stress paper computes.

``StressEngine`` plugs this into the engine seam (core/engine.py): the
compile-cached builders mirror ``GilaEngine``'s flat-index batched
lowering, and the per-lane schedule vector is
(temp0, temp_decay, alpha0, alpha_decay) — ``sched_k = 4``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.graphs.graph import PaddedGraph, edge_gather
from repro.core import gila
from repro.core import engine as engine_mod

#: entropy-term annealing: α starts at ALPHA0 and decays geometrically by a
#: TOTAL factor of ALPHA_SHRINK over the level's iteration budget. The pair
#: was picked by a mesh-suite scan (grid/tri_mesh/delaunay/torus, see
#: EXPERIMENTS.md §Stress): 0.05 keeps enough repulsion to untangle the
#: placement init without drowning the stress term; larger α₀ degrades NELD
#: toward plain FR, smaller collapses non-neighbor separation (CRE blowup).
ALPHA0 = 0.05
ALPHA_SHRINK = 0.008


def alpha_schedule(iters: int) -> tuple[float, float]:
    """(α₀, per-iteration multiplicative decay) reaching α₀·ALPHA_SHRINK at
    the level's last iteration — host-computed so the sequential and batched
    steps anneal with the identical f32 factor."""
    return ALPHA0, float(ALPHA_SHRINK ** (1.0 / max(int(iters), 1)))


def stress_terms(g: PaddedGraph, L):
    """Position-independent per-edge terms, hoisted out of the iteration
    loop: target lengths ℓ_e, weights w_e = 1/ℓ_e² (0 on padding), and the
    per-vertex weight sum ρ."""
    with jax.named_scope("stress.terms"):
        ell = jnp.maximum(g.ewt, 1e-6) * L
        we = jnp.where(g.emask, 1.0 / (ell * ell), 0.0)
        rho = jax.ops.segment_sum(we, g.dst,
                                  num_segments=g.n_pad + 1)[:g.n_pad]
        return ell, we, rho


def stress_iteration(g: PaddedGraph, pos, nbr_idx, nbr_mask, ell, we, rho,
                     params_arr, temp, alpha, *, mode: str, grid_dim: int = 0,
                     cell_cap: int = 0):
    """One maxent-stress Jacobi iteration (shared by ``stress_layout`` and
    the cached builders' per-lane arithmetic contract).

    The named scopes (``stress.targets``, ``stress.entropy``,
    ``stress.move``; ``stress.terms`` in ``stress_terms``) only label the
    compiled operations, so a device profile names each stage; the
    kernels' own ``grid.*`` scopes nest inside ``stress.entropy``."""
    C, L, md = params_arr[0], params_arr[1], params_arr[2]
    n_pad = g.n_pad
    with jax.named_scope("stress.targets"):
        ps = edge_gather(g, pos)                    # source endpoint per edge
        pd = pos[jnp.clip(g.dst, 0, n_pad - 1)]
        delta = pd - ps
        dist = jnp.sqrt(jnp.sum(delta * delta, axis=1) + md ** 2)
        tgt = ps + delta / dist[:, None] * ell[:, None]
        vec = jnp.where(g.emask[:, None], we[:, None] * tgt, 0.0)
        num = jax.ops.segment_sum(vec, g.dst, num_segments=n_pad + 1)[:n_pad]
    with jax.named_scope("stress.entropy"):
        ca = alpha * C                              # entropy strength α·C
        if mode == "exact":
            rep = gila._repulsion_exact(pos, g.mass, g.vmask, ca, L, md)
        elif mode == "grid":
            rep = gila._repulsion_grid(pos, g.mass, g.vmask, ca, L, md,
                                       grid_dim, cell_cap)
        else:
            rep = gila._repulsion_neighbors(pos, g.mass, nbr_idx, nbr_mask,
                                            g.vmask, ca, L, md)
    with jax.named_scope("stress.move"):
        new = (num + rep) / jnp.maximum(rho, 1e-12)[:, None]
        new = jnp.where(rho[:, None] > 0, new, pos)  # no edges → stay put
        d = new - pos
        norm = jnp.sqrt(jnp.sum(d * d, axis=1) + 1e-12)
        step = jnp.minimum(norm, temp)              # GiLA's cooling clamp
        pos = pos + d / norm[:, None] * step[:, None]
        return jnp.where(g.vmask[:, None], pos, 0.0)


@partial(jax.jit, static_argnames=("mode", "iters", "grid_dim", "cell_cap",
                                   "backend"))
def stress_layout(g: PaddedGraph, pos0, nbr_idx, nbr_mask, *, mode: str,
                  iters: int, temp0: float, temp_decay: float,
                  alpha0: float, alpha_decay: float, ideal_len: float,
                  rep_const: float, backend: str, min_dist: float = 1e-3,
                  grid_dim: int = 0, cell_cap: int = 0):
    """Exact-shape maxent-stress loop — the ``gila.gila_layout`` analogue
    used when ``LayoutConfig.bucketing=False`` (every level retraces); the
    multilevel driver uses the compile-cached builders below otherwise.
    ``backend`` keys the trace on the kernel backend, as there."""
    gila.check_backend(backend)
    params_arr = jnp.asarray([rep_const, ideal_len, min_dist], jnp.float32)
    ell, we, rho = stress_terms(g, params_arr[1])

    def body(i, carry):
        pos, temp, al = carry
        pos = stress_iteration(g, pos, nbr_idx, nbr_mask, ell, we, rho,
                               params_arr, temp, al, mode=mode,
                               grid_dim=grid_dim, cell_cap=cell_cap)
        return pos, temp * temp_decay, al * alpha_decay

    pos, _, _ = jax.lax.fori_loop(
        0, iters, body, (pos0, jnp.asarray(temp0, jnp.float32),
                         jnp.asarray(alpha0, jnp.float32)))
    return pos


class StressEngine(engine_mod.RefinementEngine):
    """Multilevel maxent-stress as a drop-in refinement engine."""

    name = "stress"
    sched_k = 4                 # (temp0, temp_decay, alpha0, alpha_decay)

    def lane_schedule(self, sched) -> tuple:
        a0, ad = alpha_schedule(sched.iters)
        return (sched.temp0, sched.temp_decay, a0, ad)

    def span_args(self, sched) -> dict:
        a0, ad = alpha_schedule(sched.iters)
        return dict(alpha0=a0, alpha_decay=ad)

    def build_refine(self, mode: str, grid_dim: int, cell_cap: int):
        """Compile-cached per-level stress loop: iteration count and the
        4-scalar annealing vector are traced, ℓ/w/ρ are hoisted once per
        level, pos0 is donated."""
        from repro.core import bucketing

        def refine(pos0, src, dst, vmask, emask, mass, ewt, nbr_idx,
                   nbr_mask, iters, sparams, params):
            g = PaddedGraph(src=src, dst=dst, vmask=vmask, emask=emask,
                            mass=mass, ewt=ewt, n=0, m=0)
            ell, we, rho = stress_terms(g, params[1])

            def body(i, carry):
                pos, temp, al = carry
                pos = stress_iteration(g, pos, nbr_idx, nbr_mask, ell, we,
                                       rho, params, temp, al, mode=mode,
                                       grid_dim=grid_dim, cell_cap=cell_cap)
                return pos, temp * sparams[1], al * sparams[3]

            pos, _, _ = jax.lax.fori_loop(
                0, iters, body, (pos0, sparams[0], sparams[2]))
            return pos

        return jax.jit(
            refine,
            donate_argnums=bucketing.donate_argnums_if_supported(0))

    def build_refine_many(self, mode: str, grid_dim: int, cell_cap: int,
                          inc_k: int):
        """Batched stress over ``[B, n_pad]`` lanes, mirroring
        ``GilaEngine.build_refine_many``'s flat-index lowering: per-lane
        arithmetic is element-for-element ``stress_iteration`` (same op
        order, same accumulation order for the edge aggregations — the
        incidence-gather adds reproduce ``segment_sum``'s ascending-slot
        scatter order), so each lane is bit-identical to the same level
        refined alone. Dead/finished lanes carry (pos, temp, α) through
        the remaining trips unchanged.
        """
        from repro.core import bucketing
        from repro.kernels.nbody import ops as nbody_ops
        from repro.kernels.neighbor_force import ops as nf_ops

        def refine_many(pos0, src, dst, vmask, emask, mass, ewt, nbr_idx,
                        nbr_mask, inc, iters, sparams, params, max_iters):
            B, n_pad = pos0.shape[0], pos0.shape[1]
            m_pad = src.shape[1]
            C, L, md = params[0], params[1], params[2]
            temp_decay, alpha_dec = sparams[:, 1], sparams[:, 3]
            offs = (jnp.arange(B, dtype=jnp.int32) * (n_pad + 1))[:, None]
            flat_dst = (dst + offs).reshape(-1)
            flat_src = src + offs
            flat_dst_clip = jnp.clip(dst, 0, n_pad - 1) + offs
            with jax.named_scope("stress.terms"):
                ell = jnp.maximum(ewt, 1e-6) * L                 # [B, m_pad]
                we = jnp.where(emask, 1.0 / (ell * ell), 0.0)
            flat_inc = inc + (jnp.arange(B, dtype=jnp.int32)
                              * (m_pad + 1))[:, None, None]

            def flat_pos(pos):
                posp = jnp.concatenate(
                    [pos, jnp.zeros((B, 1, 2), pos.dtype)], axis=1)
                return posp.reshape(B * (n_pad + 1), 2)

            def agg_edges(x):
                """Per-vertex sum of a per-edge quantity ([B, m_pad, ...]),
                in the sequential step's segment_sum accumulation order."""
                if inc_k > 0:
                    xf = jnp.concatenate(
                        [x, jnp.zeros((B, 1) + x.shape[2:], x.dtype)],
                        axis=1).reshape((B * (m_pad + 1),) + x.shape[2:])
                    acc = jnp.zeros((B, n_pad) + x.shape[2:], x.dtype)
                    for k in range(inc_k):    # left-assoc: scatter order
                        acc = acc + xf[flat_inc[:, :, k]]
                    return acc
                out = jax.ops.segment_sum(
                    x.reshape((B * m_pad,) + x.shape[2:]), flat_dst,
                    num_segments=B * (n_pad + 1))
                return out.reshape((B, n_pad + 1) + x.shape[2:])[:, :n_pad]

            with jax.named_scope("stress.terms"):
                rho = agg_edges(we)                              # [B, n_pad]

            def stress_num(pos):
                flat = flat_pos(pos)
                ps = flat[flat_src]                              # [B, m_pad, 2]
                pd = flat[flat_dst_clip]
                delta = pd - ps
                dist = jnp.sqrt(jnp.sum(delta * delta, axis=2) + md ** 2)
                tgt = ps + delta / dist[..., None] * ell[..., None]
                vec = jnp.where(emask[..., None], we[..., None] * tgt, 0.0)
                return agg_edges(vec)

            if mode == "exact":
                def repulsion(pos, ca):
                    return jax.vmap(nbody_ops.nbody_repulsion,
                                    in_axes=(0, 0, 0, 0, None, None))(
                        pos, mass, vmask, ca, L, md)
            elif mode == "neighbor":
                def repulsion(pos, ca):
                    return jax.vmap(nf_ops.neighbor_repulsion,
                                    in_axes=(0, 0, 0, 0, 0, 0, None, None))(
                        pos, mass, nbr_idx, nbr_mask, vmask, ca, L, md)
            else:
                from repro.kernels.grid_force import ops as grid_ops

                def repulsion(pos, ca):
                    return jax.vmap(
                        lambda p, m_, v_, c_: grid_ops.grid_repulsion(
                            p, m_, v_, c_, L, md,
                            grid_dim=grid_dim, cell_cap=cell_cap))(
                        pos, mass, vmask, ca)

            def body(i, carry):
                pos, temp, al = carry
                # the single-graph step's scopes (stress_iteration)
                with jax.named_scope("stress.targets"):
                    num = stress_num(pos)
                with jax.named_scope("stress.entropy"):
                    rep = repulsion(pos, al * C)
                with jax.named_scope("stress.move"):
                    new = (num + rep) / jnp.maximum(rho, 1e-12)[..., None]
                    new = jnp.where(rho[..., None] > 0, new, pos)
                    d = new - pos
                    norm = jnp.sqrt(jnp.sum(d * d, axis=2) + 1e-12)
                    step = jnp.minimum(norm, temp[:, None])
                    new = pos + d / norm[..., None] * step[..., None]
                    new = jnp.where(vmask[..., None], new, 0.0)
                live = i < iters
                return (jnp.where(live[:, None, None], new, pos),
                        jnp.where(live, temp * temp_decay, temp),
                        jnp.where(live, al * alpha_dec, al))

            pos, _, _ = jax.lax.fori_loop(
                0, max_iters, body, (pos0, sparams[:, 0], sparams[:, 2]))
            return pos

        return jax.jit(
            refine_many,
            donate_argnums=bucketing.donate_argnums_if_supported(0))


engine_mod.register(StressEngine())
