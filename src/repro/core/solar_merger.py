"""Distributed Solar Merger — the coarsening phase of Multi-GiLA (paper §3.2).

Vertex-centric BSP protocol mapped to dense JAX array supersteps:

  1. *Sun generation*: unassigned vertices self-elect with probability p;
     conflicts within graph distance < 3 are resolved by ID (two max-
     propagation supersteps — a sun survives iff it is the strict 2-hop
     maximum among candidates, which guarantees pairwise sun distance ≥ 3).
  2. *Solar-system generation*: suns broadcast offers; unassigned neighbors
     become planets of the max-ID offering sun; planets forward offers;
     unassigned 2-hop vertices become moons (recording the forwarding
     planet for two-hop routing).
  3. Steps 1–2 repeat until no vertex is unassigned (every 4th round is a
     *forced* round where all unassigned vertices self-elect; if even that
     stalls, desperation mode kicks in — see ``sun_election``).
  4. *Inter-system links*: edges whose endpoints lie in different systems
     are discovered; each contributes a path of length depth(u)+1+depth(v).
  5. *Next-level generation*: systems collapse into their suns; coarse-edge
     weight = max path length over the parallel links.

The whole election→growth→halting-vote loop is DEVICE-RESIDENT
(``run_merger``): one cached jitted program per shape bucket carries the
round counter, the stall/desperation state machine, and the BSP halting
vote ("any unassigned left?") as ``lax.while_loop`` loop-carried scalars,
so the host never syncs mid-coarsening — it reads two scalars (rounds
used, leftover count) once per merger call, where the per-round Python
driver (kept as ``run_merger_host``, the bit-parity reference) paid one
blocking device→host sync every round. ``next_level`` compaction is
likewise on-device for the bucketed driver (DESIGN.md §13): segment-summed
coarse masses, masked prefix-sum sun renumbering, and sort-based
parallel-link dedup run as fixed-shape cached programs; the host reads
only the two true sizes (n_coarse, n_edges) to pick the coarse shape
bucket.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.graphs.graph import (PaddedGraph, build_graph, bucket_pad,
                                edge_gather)
from repro.core import bucketing
from repro.core.bucketing import STEP_CACHE
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.utils.prng import uniform_per_vertex
from repro.utils.transfer import io_boundary

UNASSIGNED, SUN, PLANET, MOON = 0, 1, 2, 3

MERGER_ROUNDS = obs_metrics.REGISTRY.counter(
    "gila_merger_rounds_total",
    "BSP election+growth rounds executed inside the device merger loop")
MERGER_FORCED_SUNS = obs_metrics.REGISTRY.counter(
    "gila_merger_forced_suns_total",
    "Vertices self-elected by the terminal forced round (round-budget "
    "exhaustion — the documented graceful-degradation deviation)")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class MergerState:
    """Per-vertex solar-system assignment (padding rows are UNASSIGNED but
    masked out by g.vmask everywhere)."""
    state: jnp.ndarray   # int32[n_pad] — UNASSIGNED/SUN/PLANET/MOON
    sun: jnp.ndarray     # int32[n_pad] — index of the system's sun (n_pad = none)
    depth: jnp.ndarray   # int32[n_pad] — hops to the sun (0/1/2)
    parent: jnp.ndarray  # int32[n_pad] — next hop toward the sun (for 2-hop msgs)


# device-resident template per bucket: the init state is a pure function
# of n_pad and the merger program never mutates its inputs, so the same
# buffers can serve every dispatch — EXCEPT on backends where jit donation
# is active (donate_argnums_if_supported != ()), which would consume the
# cached buffers on first use; there we stage fresh ones per call.
_INIT_TEMPLATES: dict[int, MergerState] = {}


def init_state(g: PaddedGraph) -> MergerState:
    n_pad = g.n_pad
    reusable = not bucketing.donate_argnums_if_supported(0)
    if reusable:
        st = _INIT_TEMPLATES.get(n_pad)
        if st is not None:
            return st
    with io_boundary():                 # intentional host→device staging
        packed = jnp.asarray(
            np.stack([np.zeros(n_pad, np.int32),          # state
                      np.full(n_pad, n_pad, np.int32),    # sun
                      np.full(n_pad, -1, np.int32),       # depth
                      np.full(n_pad, n_pad, np.int32)]))  # parent
        st = MergerState(state=packed[0], sun=packed[1],
                         depth=packed[2], parent=packed[3])
    if reusable:
        _INIT_TEMPLATES[n_pad] = st
    return st


def _push_max(g: PaddedGraph, values: jnp.ndarray) -> jnp.ndarray:
    """Superstep: broadcast int values, combine with max (-1 = no message)."""
    msgs = edge_gather(g, values)
    msgs = jnp.where(g.emask, msgs, -1)
    out = jax.ops.segment_max(msgs, g.dst, num_segments=g.n_pad + 1,
                              indices_are_sorted=False)
    return jnp.maximum(out[: g.n_pad], -1)


@jax.jit
def sun_election(g: PaddedGraph, st: MergerState, key: jnp.ndarray,
                 p: jnp.ndarray, forced: jnp.ndarray,
                 respect_existing: jnp.ndarray) -> MergerState:
    """One sun-generation round (supersteps 1–3 of paper §3.2 step 1).

    Existing suns participate in the conflict broadcast with dominating
    priority (ID + n_pad) so fresh candidates never elect within 2 hops of
    an established system. ``respect_existing=False`` is the *desperation*
    mode used only when the BSP vote stalls: a vertex can be ≤2 hops from a
    sun yet unreachable by offers (all intermediaries owned by other
    systems), and must then be allowed to self-elect — a documented
    deviation required for guaranteed termination.
    """
    n_pad = g.n_pad
    ids = jnp.arange(n_pad, dtype=jnp.int32)
    unassigned = (st.state == UNASSIGNED) & g.vmask
    # per-vertex coin streams (utils/prng.py): vertex v's draw depends only
    # on (key, v), not on the padding bucket — re-padding the same graph
    # elects the same suns (the bucketing parity contract)
    coin = uniform_per_vertex(key, ids) < p
    cand = unassigned & (coin | forced)

    # candidates announce their ID; two forwarding supersteps compute, per
    # vertex, the maximum candidate ID within graph distance ≤ 2.
    sun_prio = jnp.where((st.state == SUN) & respect_existing, ids + n_pad, -1)
    h0 = jnp.maximum(jnp.where(cand, ids, -1), sun_prio)
    h1 = jnp.maximum(h0, _push_max(g, h0))
    h2 = jnp.maximum(h1, _push_max(g, h1))
    # a candidate survives iff no strictly greater candidate (or established
    # sun, which always dominates) is within 2 hops. Desperation mode relaxes
    # the radius to 1 hop: stuck vertices cluster behind moons (which never
    # forward offers), and pairwise non-adjacent ones must elect in parallel
    # for O(log n) convergence (Luby-MIS on the stuck set).
    h_conflict = jnp.where(respect_existing, h2, h1)
    new_sun = cand & (h_conflict <= ids)

    state = jnp.where(new_sun, SUN, st.state)
    sun = jnp.where(new_sun, ids, st.sun)
    depth = jnp.where(new_sun, 0, st.depth)
    parent = jnp.where(new_sun, ids, st.parent)
    return MergerState(state, sun, depth, parent)


@jax.jit
def system_growth(g: PaddedGraph, st: MergerState) -> MergerState:
    """One solar-system-generation round (offers → planets → moons)."""
    n_pad = g.n_pad
    ids = jnp.arange(n_pad, dtype=jnp.int32)
    unassigned = (st.state == UNASSIGNED) & g.vmask

    # Superstep A: suns broadcast offers; unassigned neighbors accept the
    # max-ID adjacent sun and become planets.
    offer1 = _push_max(g, jnp.where(st.state == SUN, ids, -1))
    becomes_planet = unassigned & (offer1 >= 0)
    state = jnp.where(becomes_planet, PLANET, st.state)
    sun = jnp.where(becomes_planet, offer1, st.sun)
    depth = jnp.where(becomes_planet, 1, st.depth)
    parent = jnp.where(becomes_planet, offer1, st.parent)  # next hop = the sun

    # Superstep B: new planets forward their sun's offer; remaining
    # unassigned vertices accept the max forwarded sun and become moons.
    planet_fwd = jnp.where(state == PLANET, sun, -1)
    offer2 = _push_max(g, planet_fwd)
    still_un = unassigned & ~becomes_planet
    becomes_moon = still_un & (offer2 >= 0)
    # pick the forwarding planet: max planet ID among in-neighbors whose sun
    # matches the accepted offer (two-hop confirmation route, paper §3.2).
    match_val = jnp.where(state == PLANET, ids, -1)
    msgs = edge_gather(g, jnp.stack([planet_fwd, match_val], axis=1))
    key_match = jnp.where(
        g.emask & (msgs[:, 0] >= 0) & (msgs[:, 0] == offer2[jnp.clip(g.dst, 0, n_pad - 1)])
        & (g.dst < n_pad),
        msgs[:, 1], -1)
    via = jax.ops.segment_max(key_match, g.dst, num_segments=n_pad + 1)[:n_pad]
    via = jnp.maximum(via, -1)

    state = jnp.where(becomes_moon, MOON, state)
    sun = jnp.where(becomes_moon, offer2, sun)
    depth = jnp.where(becomes_moon, 2, depth)
    parent = jnp.where(becomes_moon, via, parent)
    return MergerState(state, sun, depth, parent)


def round_budget(n: int, base: int = 96) -> int:
    """Merger round budget scaled with graph size.

    Election conflicts resolve in O(log n) rounds w.h.p. (Luby-MIS
    argument), so the budget grows logarithmically past the base that
    historically covered every CI-sized graph. Exhausting it no longer
    raises — the terminal forced round self-elects every leftover vertex
    (see ``run_merger``) — so the budget only bounds worst-case work.
    """
    n = max(int(n), 2)
    extra = max(0, int(np.ceil(np.log2(n / 4096))) * 8) if n > 4096 else 0
    return base + extra


def _terminal_forced(st: MergerState, vmask: jnp.ndarray,
                     ids: jnp.ndarray) -> MergerState:
    """Graceful degradation: any vertex still unassigned after the round
    budget becomes its own sun (a documented deviation, like desperation
    mode). Identity when the merger converged."""
    left = (st.state == UNASSIGNED) & vmask
    return MergerState(
        state=jnp.where(left, SUN, st.state),
        sun=jnp.where(left, ids, st.sun),
        depth=jnp.where(left, 0, st.depth),
        parent=jnp.where(left, ids, st.parent))


# Largest bucket where the single-primitive cummax lowering of the
# segmented max stays int32-exact: values sit in [-1, 2*n_pad], the
# per-segment offset is seg_id * (2*n_pad + 2), and the top segment must
# stay below 2^31 — ~2*n_pad^2, safe through n_pad = 2^14.
_CUMMAX_NPAD_MAX = 1 << 14


def _seg_max_scan(seg_start, seg_id, vals, n_pad: int):
    """Max within runs of a dst-sorted half-edge stream (−1 = neutral).

    Exact replacement for ``segment_max`` on XLA CPU, where scatter lowers
    to a sequential per-element loop (~45 ns/element) and dominates the
    merger round. Two lowerings, chosen at trace time by the static bucket:
    small buckets bias each value by ``seg_id * span`` so one ``cummax``
    does the segmentation (values ≥ −1 and span > max−min keep earlier
    segments strictly below later ones); big buckets run the classic
    segmented-scan operator on (flag, value) pairs, which has no overflow
    bound. Both are bit-exact vs the scatter (integers, max — no rounding).
    """
    if n_pad <= _CUMMAX_NPAD_MAX:
        span = jnp.asarray(2 * n_pad + 2, jnp.int32)
        adj = (vals + 1) + seg_id * span
        return jax.lax.cummax(adj) - seg_id * span - 1

    def op(a, b):
        af, av = a
        bf, bv = b
        return af | bf, jnp.where(bf, bv, jnp.maximum(av, bv))

    return jax.lax.associative_scan(op, (seg_start, vals))[1]


def _build_merger():
    """The device-resident merger loop: election → growth → on-device
    halting vote as one ``lax.while_loop``, the stall → desperation state
    machine carried as loop scalars. Replicates ``run_merger_host``'s
    control flow (and key stream: one ``jax.random.split`` per round)
    bit-for-bit — tests/test_merger_device.py holds that line.

    The supersteps here are the scan formulation of ``sun_election`` /
    ``system_growth``: messages ride the loop-invariant dst-sorted layout
    (``_merger_sort_args``) and each per-vertex max is a segmented scan +
    gather instead of a scatter ``segment_max`` — identical outputs (max
    over the same message multiset), several times faster per round on the
    CPU backend. The host-driver jits keep the scatter path, so the parity
    suite cross-checks the two formulations every run.
    """

    def merger(st, key, src, dst, emask, order, vmask, p, max_rounds,
               force_every):
        n_pad = vmask.shape[0]
        ids = jnp.arange(n_pad, dtype=jnp.int32)
        # loop-invariant dst-sorted layout, derived in-trace from the
        # host-computed permutation (XLA hoists it out of the while body):
        # O(m) gathers + one cumsum + a binary-search bound per vertex —
        # everything except the argsort itself, which stays on the host
        # where it is ~10x cheaper than an XLA CPU sort
        dst_s = dst[order]
        src_s = src[order]
        emask_s = emask[order]
        seg_start = jnp.concatenate(
            [jnp.ones((1,), bool), dst_s[1:] != dst_s[:-1]])
        seg_id = jnp.cumsum(seg_start.astype(jnp.int32)) - 1
        left = jnp.searchsorted(dst_s, ids, side="left")
        right = jnp.searchsorted(dst_s, ids, side="right")
        seg_has = right > left
        seg_last = jnp.maximum(right - 1, 0).astype(jnp.int32)
        src_c = jnp.clip(src_s, 0, n_pad - 1)   # padding slots masked below
        dst_c = jnp.clip(dst_s, 0, n_pad - 1)

        def push(values, msg_mask=None):
            mask = emask_s if msg_mask is None else (emask_s & msg_mask)
            msgs = jnp.where(mask, values[src_c], -1)
            run = _seg_max_scan(seg_start, seg_id, msgs, n_pad)
            return jnp.where(seg_has, run[seg_last], -1)

        def election(s, sub, forced, respect):
            unassigned = (s.state == UNASSIGNED) & vmask
            coin = uniform_per_vertex(sub, ids) < p
            cand = unassigned & (coin | forced)
            sun_prio = jnp.where((s.state == SUN) & respect, ids + n_pad, -1)
            h0 = jnp.maximum(jnp.where(cand, ids, -1), sun_prio)
            h1 = jnp.maximum(h0, push(h0))
            h2 = jnp.maximum(h1, push(h1))
            h_conflict = jnp.where(respect, h2, h1)
            new_sun = cand & (h_conflict <= ids)
            return MergerState(
                state=jnp.where(new_sun, SUN, s.state),
                sun=jnp.where(new_sun, ids, s.sun),
                depth=jnp.where(new_sun, 0, s.depth),
                parent=jnp.where(new_sun, ids, s.parent))

        def growth(s):
            unassigned = (s.state == UNASSIGNED) & vmask
            offer1 = push(jnp.where(s.state == SUN, ids, -1))
            becomes_planet = unassigned & (offer1 >= 0)
            state = jnp.where(becomes_planet, PLANET, s.state)
            sun = jnp.where(becomes_planet, offer1, s.sun)
            depth = jnp.where(becomes_planet, 1, s.depth)
            parent = jnp.where(becomes_planet, offer1, s.parent)

            planet_fwd = jnp.where(state == PLANET, sun, -1)
            offer2 = push(planet_fwd)
            still_un = unassigned & ~becomes_planet
            becomes_moon = still_un & (offer2 >= 0)
            fwd_msg = planet_fwd[src_c]
            via = push(jnp.where(state == PLANET, ids, -1),
                       msg_mask=(fwd_msg >= 0) & (fwd_msg == offer2[dst_c])
                       & (dst_s < n_pad))

            return MergerState(
                state=jnp.where(becomes_moon, MOON, state),
                sun=jnp.where(becomes_moon, offer2, sun),
                depth=jnp.where(becomes_moon, 2, depth),
                parent=jnp.where(becomes_moon, via, parent))

        def remaining_of(s):
            return jnp.sum(((s.state == UNASSIGNED) & vmask)
                           .astype(jnp.int32))

        n0 = remaining_of(st)

        def cond(carry):
            _, _, r, _, _, _, remaining = carry
            return (remaining > 0) & (r < max_rounds)

        def body(carry):
            s, k, r, prev, stalls, desperate, _ = carry
            # sticky desperation: once the vote stalls twice, run
            # Luby-MIS-style rounds until convergence
            desperate = desperate | (stalls >= 2)
            k, sub = jax.random.split(k)
            forced = desperate | (r % force_every == force_every - 1)
            s = election(s, sub, forced, ~desperate)
            s = growth(s)
            rem = remaining_of(s)
            stalls = jnp.where(rem < prev, 0, stalls + 1)
            return (s, k, r + 1, rem, stalls, desperate, rem)

        init = (st, key, jnp.asarray(0, jnp.int32), n0 + 1,
                jnp.asarray(0, jnp.int32), jnp.asarray(False), n0)
        st, _, rounds, _, _, _, remaining = jax.lax.while_loop(
            cond, body, init)
        # applied unconditionally (identity when converged): no extra
        # sync, no retrace, and the round-budget path can never raise
        st = _terminal_forced(st, vmask, ids)
        return st, rounds, remaining

    return jax.jit(merger, donate_argnums=bucketing.donate_argnums_if_supported(0))


def _merger_sort_args(g: PaddedGraph):
    """The dst-sort permutation for the scan supersteps, computed on the
    host once per merger dispatch (one ``np.argsort``, ~1 ms at the 32k
    bucket vs the ~8 ms/round the scan formulation saves on device; an XLA
    CPU sort would cost ~10x more). Everything derived from it — run
    boundaries, last-slot indices — is rebuilt in-trace inside the merger
    program, loop-invariant. Sort order within a destination is irrelevant
    (every consumer is a max), so stable-vs-quicksort changes can't
    perturb results.
    """
    with obs_trace.span("coarsen.sort", cat="host", m_pad=g.m_pad):
        with io_boundary():             # egress: graph topology (host sort)
            dst = np.asarray(g.dst)
        order = np.argsort(dst).astype(np.int32)   # unstable is fine
        with io_boundary():             # staging: permutation → device
            return jnp.asarray(order)


def cached_merger(g: PaddedGraph, st: MergerState, key: jnp.ndarray, *,
                  p_sun: float, max_rounds: int, force_every: int):
    """(cache_key, fn, fresh, args) for the device merger loop of one shape
    bucket — the single staging point, shared by ``run_merger`` and the
    gilalint jaxpr audit (A1–A4) so the audit traces exactly the program
    the driver runs."""
    cache_key = ("merger", g.n_pad, g.m_pad)
    fn, fresh = STEP_CACHE.get(cache_key, _build_merger)
    order = _merger_sort_args(g)
    with io_boundary():                 # staging: scalar knobs → device
        args = (st, key, g.src, g.dst, g.emask, order, g.vmask,
                jnp.asarray(p_sun, jnp.float32),
                jnp.asarray(max_rounds, jnp.int32),
                jnp.asarray(force_every, jnp.int32))
    return cache_key, fn, fresh, args


def run_merger(g: PaddedGraph, *, p_sun: float = 0.35, seed: int = 0,
               max_rounds: int | None = None,
               force_every: int = 4) -> MergerState:
    """Run election+growth rounds until every valid vertex is assigned.

    Device-resident: the whole round loop (including the BSP halting vote
    and the stall/desperation state machine) runs as one cached jitted
    ``lax.while_loop`` program per shape bucket; the host reads two
    scalars after the loop (rounds used, leftover count) instead of
    syncing every round. ``max_rounds=None`` scales the budget with graph
    size (``round_budget``); exhausting it degrades gracefully — the
    terminal forced round assigns every remaining vertex as its own sun —
    and never raises mid-pipeline.
    """
    if max_rounds is None:
        max_rounds = round_budget(g.n)
    st = init_state(g)
    with io_boundary():                 # staging: RNG seed → device key
        key = jax.random.PRNGKey(seed)
    cache_key, fn, fresh, args = cached_merger(
        g, st, key, p_sun=p_sun, max_rounds=max_rounds,
        force_every=force_every)
    # the span brackets the dispatch + the scalar reads that were already
    # the driver's only host syncs — no new transfer is introduced
    with obs_trace.span("merger.dispatch", cat="device", key=cache_key,
                        fresh=fresh):
        st, rounds, left = fn(*args)
        with io_boundary():             # egress: the two halting scalars
            rounds_i, left_i = int(rounds), int(left)
    MERGER_ROUNDS.inc(rounds_i)
    if left_i:
        MERGER_FORCED_SUNS.inc(left_i)
    return st


def run_merger_host(g: PaddedGraph, *, p_sun: float = 0.35, seed: int = 0,
                    max_rounds: int | None = None,
                    force_every: int = 4) -> MergerState:
    """Per-round host driver of the same protocol — one blocking
    device→host halting vote per round, as a Giraph aggregator would.

    Kept as the bit-parity reference for the device loop (identical key
    stream, identical stall → desperation transitions, identical terminal
    forced round — tests/test_merger_device.py) and as the measurable
    "host-bound path" baseline. Same graceful round-budget semantics as
    ``run_merger``: never raises.
    """
    if max_rounds is None:
        max_rounds = round_budget(g.n)
    st = init_state(g)
    # the jitted supersteps never read the static n/m fields, so normalize
    # them away: the jit caches key on padded shapes only, and every graph
    # in the same shape bucket reuses one compiled program (bucketing.py)
    gn = dataclasses.replace(g, n=0, m=0)
    with io_boundary():                 # staging: RNG seed → device key
        key = jax.random.PRNGKey(seed)
    prev_remaining = g.n + 1
    stalls = 0
    desperate = False
    for r in range(max_rounds):
        # sticky desperation: once the vote stalls twice, run Luby-MIS-style
        # rounds (all unassigned candidates, existing suns not respected)
        # until convergence — O(log n) rounds with strict progress.
        desperate = desperate or stalls >= 2
        with io_boundary():             # staging: per-round scalar knobs
            key, sub = jax.random.split(key)
            forced = jnp.asarray(desperate
                                 or r % force_every == force_every - 1)
            p = jnp.asarray(p_sun, jnp.float32)
            respect = jnp.asarray(not desperate)
        st = sun_election(gn, st, sub, p, forced, respect)
        st = system_growth(gn, st)
        # BSP halting vote (host sync, as a Giraph aggregator would)
        with io_boundary():
            remaining = int(jnp.sum((st.state == UNASSIGNED) & g.vmask))
        if remaining == 0:
            return st
        stalls = 0 if remaining < prev_remaining else stalls + 1
        prev_remaining = remaining
    # round budget exhausted: terminal forced round (same as the device
    # loop's — every leftover vertex becomes its own sun), never raise
    ids = jnp.arange(g.n_pad, dtype=jnp.int32)
    return _terminal_forced(st, g.vmask, ids)


def centralized_solar_merger(edges: np.ndarray, n: int, seed: int = 0
                             ) -> tuple[np.ndarray, int]:
    """Sequential Solar Merger reference (FM³'s greedy, Hachul 2005):
    visit vertices in random order; an unassigned vertex becomes a sun and
    absorbs its unassigned ≤2-hop neighborhood (planets then moons).
    Returns (sun_of[n], n_suns) — used for the Fig.5 level-count baseline.
    """
    from repro.graphs.graph import to_csr
    rng = np.random.default_rng(seed)
    row_ptr, col = to_csr(edges, n)
    sun_of = np.full(n, -1, dtype=np.int64)
    n_suns = 0
    for v in rng.permutation(n):
        if sun_of[v] >= 0:
            continue
        sun_of[v] = v
        n_suns += 1
        planets = [u for u in col[row_ptr[v]:row_ptr[v + 1]]
                   if sun_of[u] < 0]
        for u in planets:
            sun_of[u] = v
        for u in planets:
            for w in col[row_ptr[u]:row_ptr[u + 1]]:
                if sun_of[w] < 0:
                    sun_of[w] = v
    return sun_of, n_suns


def centralized_levels(edges: np.ndarray, n: int, *, threshold: int = 50,
                       max_levels: int = 24, seed: int = 0) -> list[int]:
    """Level sizes produced by iterating the centralized Solar Merger.

    Each level derives its own seed (``seed + 101 * lvl``, mirroring
    ``build_hierarchy``): reusing one seed across levels correlated the
    coarsening decisions of the Fig.5 baseline — a vertex surviving as a
    sun tended to stay early in every level's visiting permutation.
    """
    sizes = [n]
    cur_edges, cur_n = edges, n
    for lvl in range(max_levels):
        if cur_n <= threshold or len(cur_edges) == 0:
            break
        sun_of, n_suns = centralized_solar_merger(cur_edges, cur_n,
                                                  seed + 101 * lvl)
        if n_suns >= cur_n:
            break
        new_idx = np.full(cur_n, -1, dtype=np.int64)
        suns = np.unique(sun_of)
        new_idx[suns] = np.arange(len(suns))
        ce = new_idx[sun_of[cur_edges]]
        ce = ce[ce[:, 0] != ce[:, 1]]
        ce = np.unique(np.sort(ce, axis=1), axis=0) if len(ce) else ce
        cur_edges, cur_n = ce, len(suns)
        sizes.append(cur_n)
    return sizes


@dataclasses.dataclass
class LevelInfo:
    """Record connecting level i to level i+1 (for the placer).

    Arrays are numpy on the host compaction path (``bucket=False``) and
    device-resident on the bucketed path — consumers stage with
    ``jnp.asarray`` (solar_placer) or egress with ``np.asarray``
    (multilevel._build_export) and work with either.
    """
    parent_coarse: np.ndarray  # int32[n_pad_i] — coarse index of v's sun
    sun_of: np.ndarray         # int32[n_pad_i] — sun vertex of v (level-i idx)
    depth: np.ndarray          # int32[n_pad_i]
    state: np.ndarray          # int32[n_pad_i]
    sun_pos_index: np.ndarray  # int32[n_coarse] — level-i vertex of each coarse vertex


def next_level(g: PaddedGraph, st: MergerState, *, pad_mult: int = 256,
               bucket: bool = False) -> tuple[PaddedGraph, LevelInfo]:
    """Collapse solar systems into suns → coarse graph.

    Coarse vertices = suns (mass = Σ member masses); coarse edges = unique
    inter-system links, weighted by the longest member path
    (depth_u + 1 + depth_v) over all parallel links, times the max endpoint
    edge weight (so weights compound across levels as in FM³).

    ``bucket=True`` (the production multilevel driver) compacts ON DEVICE
    through two cached fixed-shape programs and pads the coarse graph to
    pow2 shape buckets; the host reads only the true sizes. ``bucket=False``
    keeps the original host-numpy compaction — the parity reference
    (tests/test_merger_device.py) and the exact-shape legacy path.
    """
    if bucket:
        return _next_level_device(g, st, pad_mult)
    return next_level_host(g, st, pad_mult=pad_mult, bucket=False)


def next_level_host(g: PaddedGraph, st: MergerState, *, pad_mult: int = 256,
                    bucket: bool = False) -> tuple[PaddedGraph, LevelInfo]:
    """Host-numpy compaction (the pre-device reference implementation)."""
    n_pad = g.n_pad
    state = np.asarray(st.state)
    sun = np.asarray(st.sun)
    depth = np.asarray(st.depth)
    vmask = np.asarray(g.vmask)
    mass = np.asarray(g.mass)
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    emask = np.asarray(g.emask)
    ewt = np.asarray(g.ewt)

    is_sun = (state == SUN) & vmask
    n_coarse = int(is_sun.sum())
    new_idx = np.full((n_pad + 1,), -1, dtype=np.int64)
    new_idx[:n_pad][is_sun] = np.arange(n_coarse)
    sun_safe = np.where(vmask, sun, n_pad)
    parent_coarse = new_idx[sun_safe]  # -1 for padding rows

    # coarse masses
    cmass = np.zeros((n_coarse,), dtype=np.float32)
    member = vmask & (parent_coarse >= 0)
    np.add.at(cmass, parent_coarse[member], mass[member])

    # inter-system links → coarse edges
    e_ok = emask & (src < n_pad) & (dst < n_pad)
    su, sv = sun_safe[src[e_ok]], sun_safe[dst[e_ok]]
    cross = su != sv
    cu = new_idx[su[cross]]
    cv = new_idx[sv[cross]]
    plen = (depth[src[e_ok]][cross] + 1 + depth[dst[e_ok]][cross]).astype(np.float32)
    plen = plen * ewt[e_ok][cross]  # compound desired lengths across levels
    lo = np.minimum(cu, cv)
    hi = np.maximum(cu, cv)
    key = lo * (n_coarse + 1) + hi
    order = np.argsort(key)
    key_s, lo_s, hi_s, w_s = key[order], lo[order], hi[order], plen[order]
    if key_s.size:
        uniq_mask = np.concatenate([[True], key_s[1:] != key_s[:-1]])
        seg_id = np.cumsum(uniq_mask) - 1
        n_edges = int(seg_id[-1]) + 1
        w_max = np.zeros((n_edges,), np.float32)
        np.maximum.at(w_max, seg_id, w_s)
        ce = np.stack([lo_s[uniq_mask], hi_s[uniq_mask]], axis=1)
    else:
        ce = np.zeros((0, 2), np.int64)
        w_max = np.zeros((0,), np.float32)

    sun_pos_index = np.nonzero(is_sun)[0].astype(np.int32)
    cg = build_graph(ce, n_coarse, mass=cmass, ewt=w_max, pad_mult=pad_mult,
                     bucket=bucket)
    info = LevelInfo(
        parent_coarse=parent_coarse[:n_pad].astype(np.int32),
        sun_of=sun_safe[:n_pad].astype(np.int32),
        depth=depth.astype(np.int32), state=state.astype(np.int32),
        sun_pos_index=sun_pos_index)
    return cg, info


def _build_compact():
    """The on-device half of ``next_level`` that depends only on the INPUT
    bucket: sun renumbering (masked prefix sum), segment-summed coarse
    masses, and sort-based parallel-link dedup, all at fixed [n_pad]/[m_pad]
    shapes with the true sizes returned as device scalars.

    Bit-parity notes vs ``next_level_host`` (verified by
    tests/test_merger_device.py): the scatter-add of member masses applies
    updates in ascending vertex order, matching ``np.add.at``; the dedup
    sorts lexicographically by (lo, hi) via a stable ``lexsort`` — the
    host's composite-key quicksort is unstable, but ties are exact
    duplicates and the per-group weight reduce is an order-independent max,
    so the compacted edge list and weights agree element-for-element. A
    composite ``lo * (n + 1) + hi`` key would overflow int32 at large
    buckets (f64 is banned — gilalint A2), hence the two-column sort.
    """

    def compact(st, src, dst, vmask, emask, mass, ewt):
        n_pad = vmask.shape[0]
        m_pad = src.shape[0]
        ids = jnp.arange(n_pad, dtype=jnp.int32)
        eids = jnp.arange(m_pad, dtype=jnp.int32)

        is_sun = (st.state == SUN) & vmask
        n_coarse = jnp.sum(is_sun.astype(jnp.int32))
        new_idx = jnp.where(is_sun,
                            jnp.cumsum(is_sun.astype(jnp.int32)) - 1, -1)
        new_ext = jnp.concatenate(
            [new_idx, jnp.full((1,), -1, jnp.int32)])
        sun_safe = jnp.where(vmask, st.sun, n_pad)
        parent_coarse = new_ext[sun_safe]          # -1 for padding rows
        # level-i vertex of each coarse vertex (ascending sun order)
        sun_pos_index = jnp.zeros((n_pad,), jnp.int32).at[
            jnp.where(is_sun, new_idx, n_pad)].set(ids, mode="drop")

        # coarse masses: ascending-order scatter-add (== np.add.at)
        member = vmask & (parent_coarse >= 0)
        cmass = jax.ops.segment_sum(
            jnp.where(member, mass, 0.0),
            jnp.where(member, parent_coarse, n_pad),
            num_segments=n_pad + 1)[:n_pad]

        # inter-system links over every half-edge slot
        sun_ext = jnp.concatenate(
            [sun_safe, jnp.full((1,), n_pad, jnp.int32)])
        depth_ext = jnp.concatenate(
            [st.depth, jnp.zeros((1,), jnp.int32)])
        e_ok = emask & (src < n_pad) & (dst < n_pad)
        su, sv = sun_ext[src], sun_ext[dst]
        cross = e_ok & (su != sv)
        cu, cv = new_ext[jnp.clip(su, 0, n_pad)], new_ext[jnp.clip(sv, 0, n_pad)]
        plen = (depth_ext[src] + 1 + depth_ext[dst]).astype(jnp.float32) * ewt
        lo = jnp.where(cross, jnp.minimum(cu, cv), n_pad)
        hi = jnp.where(cross, jnp.maximum(cu, cv), n_pad)
        w = jnp.where(cross, plen, 0.0)

        # parallel-link dedup: sort by (lo, hi) — invalid slots
        # (n_pad, n_pad) sink to the tail — then run-boundary compaction.
        # The weight payload rides the sort; its order within a (lo, hi)
        # tie is unspecified, which is fine: ties are exact duplicates and
        # the per-run weight reduce below is an order-independent max.
        # Small buckets pack both columns into one int32 key (~20% faster
        # XLA CPU sort); (n_pad + 1)^2 must stay below 2^31 (f64 packing is
        # banned — gilalint A2), so big buckets keep the two-key sort.
        if (n_pad + 1) ** 2 < 2 ** 31:
            key_s, w_s = jax.lax.sort(
                (lo * (n_pad + 1) + hi, w), num_keys=1)
            lo_s = key_s // (n_pad + 1)
            hi_s = key_s % (n_pad + 1)
        else:
            lo_s, hi_s, w_s = jax.lax.sort((lo, hi, w), num_keys=2)
        valid_s = lo_s < n_pad
        prev_same = jnp.concatenate(
            [jnp.zeros((1,), bool),
             (lo_s[1:] == lo_s[:-1]) & (hi_s[1:] == hi_s[:-1])])
        uniq = valid_s & ~prev_same
        seg_id = jnp.cumsum(uniq.astype(jnp.int32)) - 1
        n_edges = jnp.sum(uniq.astype(jnp.int32))
        # gather-only compaction (XLA CPU scatter is a sequential loop —
        # DESIGN.md §13): coarse edge j starts at the first slot of run j
        # (binary search over the nondecreasing run ids) and its weight is
        # the segmented running max read at the run's last slot. Invalid
        # tail slots continue the last run with weight 0 ≤ any real path
        # length, so they never perturb that run's max.
        first = jnp.searchsorted(seg_id, eids, side="left")
        last = jnp.searchsorted(seg_id, eids, side="right") - 1
        first_c = jnp.clip(first, 0, m_pad - 1)
        last_c = jnp.clip(last, 0, m_pad - 1)
        in_range = eids < n_edges
        ce_lo = jnp.where(in_range, lo_s[first_c], 0)
        ce_hi = jnp.where(in_range, hi_s[first_c], 0)

        def op(a, b):
            af, av = a
            bf, bv = b
            return af | bf, jnp.where(bf, bv, jnp.maximum(av, bv))

        w_run = jax.lax.associative_scan(
            op, (uniq, jnp.where(valid_s, w_s, 0.0)))[1]
        ce_w = jnp.where(in_range, w_run[last_c], 0.0)

        return (parent_coarse, sun_safe, st.depth, st.state, sun_pos_index,
                n_coarse, cmass, ce_lo, ce_hi, ce_w, n_edges)

    return jax.jit(compact, donate_argnums=bucketing.donate_argnums_if_supported(0))


def _build_assemble(n_pad_c: int, m_pad_c: int):
    """The on-device other half: lay the compacted coarse edges out in
    ``build_graph``'s exact buffer layout (forward half-edges first, then
    reversed; padding rows (n_pad, n_pad) with weight 1.0) at the coarse
    bucket shapes the host picked from the two true sizes. The coarse
    graph's arrays never exist on the host."""

    def assemble(ce_lo, ce_hi, ce_w, n_edges, cmass, n_coarse):
        m_pad_in = ce_lo.shape[0]
        # gather-only layout (XLA CPU scatter is a sequential loop): slot k
        # holds forward half-edge k while k < n_edges, reversed half-edge
        # k - n_edges while k < 2*n_edges, padding (n_pad_c, n_pad_c, w=1)
        # past that — exactly build_graph's buffer layout.
        idx = jnp.arange(m_pad_c, dtype=jnp.int32)
        in_fwd = idx < n_edges
        in_rev = ~in_fwd & (idx < 2 * n_edges)
        k_fwd = jnp.clip(idx, 0, m_pad_in - 1)
        k_rev = jnp.clip(idx - n_edges, 0, m_pad_in - 1)
        lo_f, hi_f = ce_lo[k_fwd], ce_hi[k_fwd]
        lo_r, hi_r = ce_lo[k_rev], ce_hi[k_rev]
        src = jnp.where(in_fwd, lo_f, jnp.where(in_rev, hi_r, n_pad_c))
        dst = jnp.where(in_fwd, hi_f, jnp.where(in_rev, lo_r, n_pad_c))
        emask = in_fwd | in_rev
        ewt = jnp.where(in_fwd, ce_w[k_fwd],
                        jnp.where(in_rev, ce_w[k_rev], 1.0))
        vmask = jnp.arange(n_pad_c, dtype=jnp.int32) < n_coarse
        # compact's cmass is already zero past n_coarse; the where keeps
        # the padding contract explicit (and exact under donation reuse)
        mass = jnp.where(vmask, cmass[:n_pad_c], 0.0)
        return src, dst, vmask, emask, mass, ewt

    return jax.jit(assemble, donate_argnums=bucketing.donate_argnums_if_supported(0))


def cached_compact(g: PaddedGraph, st: MergerState):
    """(cache_key, fn, fresh, args) for the input-bucket compaction program
    — shared by ``next_level`` and the gilalint jaxpr audit."""
    cache_key = ("compact", g.n_pad, g.m_pad)
    fn, fresh = STEP_CACHE.get(cache_key, _build_compact)
    args = (st, g.src, g.dst, g.vmask, g.emask, g.mass, g.ewt)
    return cache_key, fn, fresh, args


def cached_assemble(ce_lo, ce_hi, ce_w, n_edges, cmass, n_coarse, *,
                    n_pad_c: int, m_pad_c: int):
    """(cache_key, fn, fresh, args) for the coarse-bucket assembly program
    (``n_pad_c``/``m_pad_c`` are the host's bucket decision — the only
    payload-derived statics, and both appear in the key)."""
    cache_key = ("next_level", int(ce_lo.shape[0]), n_pad_c, m_pad_c)
    fn, fresh = STEP_CACHE.get(
        cache_key, lambda: _build_assemble(n_pad_c, m_pad_c))
    args = (ce_lo, ce_hi, ce_w, n_edges, cmass, n_coarse)
    return cache_key, fn, fresh, args


def _next_level_device(g: PaddedGraph, st: MergerState, pad_mult: int
                       ) -> tuple[PaddedGraph, LevelInfo]:
    """Device-resident ``next_level``: compact at the input bucket, read
    the two true sizes (the only host sync), assemble at the coarse
    bucket. The LevelInfo arrays stay on device."""
    ck, fn, fresh, args = cached_compact(g, st)
    with obs_trace.span("coarsen.compact", cat="device", key=ck,
                        fresh=fresh):
        (parent_coarse, sun_of, depth, state, sun_pos_index, n_coarse,
         cmass, ce_lo, ce_hi, ce_w, n_edges) = fn(*args)
        with io_boundary():             # egress: the two true sizes
            n_coarse_i, n_edges_i = int(n_coarse), int(n_edges)

    # the host's whole remaining job: the coarse shape-bucket decision
    # (must match build_graph(bucket=True) so both compaction paths land
    # levels in identical buckets)
    n_pad_c = bucket_pad(n_coarse_i, pad_mult)
    m_pad_c = bucket_pad(2 * n_edges_i, pad_mult)
    ak, afn, afresh, aargs = cached_assemble(
        ce_lo, ce_hi, ce_w, n_edges, cmass, n_coarse,
        n_pad_c=n_pad_c, m_pad_c=m_pad_c)
    with obs_trace.span("coarsen.assemble", cat="device", key=ak,
                        fresh=afresh):
        src, dst, vmask, emask, mass, ewt = afn(*aargs)
    cg = PaddedGraph(src=src, dst=dst, vmask=vmask, emask=emask, mass=mass,
                     ewt=ewt, n=n_coarse_i, m=n_edges_i)
    with io_boundary():    # staging: the slice start index is a host scalar
        spi = sun_pos_index[:n_coarse_i]
    info = LevelInfo(parent_coarse=parent_coarse, sun_of=sun_of,
                     depth=depth, state=state, sun_pos_index=spi)
    return cg, info
