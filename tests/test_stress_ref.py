"""The maxent-stress engine against its plain reference (core/stress_ref.py).

Seeded random graphs with compounded edge weights and vertex masses (as a
coarse level has them) and seeded positions, on XLA:CPU: the program's
cached single-graph step (``bucketing.cached_refine``) and its batched
lanes (``bucketing.refine_level_many``) in every repulsion mode, for one
and several iterations with the annealing, under the ``ref`` and
``interpret`` kernel backends.

``TOL``: the largest displacement difference of a vertex, as a fraction of
the level's step clamp ``temp0`` (``chip_smoke.py``'s ``STEP_TOL`` reads
the same way). The program and the reference compute the same equations
in another order of float32 sums (a scatter against ``segment_sum``, the
Pallas kernels' tiles against the oracles' whole rows) and, in grid mode,
in another arrangement: the reference sums the cells beyond a vertex's
3x3 neighborhood where the program subtracts the neighborhood from all
cells, and takes RMS radii about the centroid where the program takes
second moments about the cell centers. From a settled drawing such
differences stay under 2.2e-5 of temp0 after five iterations here (ref
and interpret, three seeds per level, the overflowing grid level the
largest), so 1e-4 leaves over four times that. From a random start the
iteration amplifies them (up to 8e-4 after five iterations, 0.36 after
twenty in grid mode, where a vertex that lands in another cell meets
other neighbors), which is why the levels are settled first. A change of
the equations moves the drawing by a visible part of a step: w_e taken as
1, α left out, or positions cast to bfloat16 give 0.09 or more, and a
change to the grid op's corrections 0.66 or more.
"""
import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import bucketing, stress, stress_ref
from repro.kernels.grid_force import ops as grid_ops
from repro.core.engine import get_engine
from repro.core.schedule import make_schedule
from repro.graphs import generators as G, build_graph
from repro.utils.transfer import io_boundary

TOL = 1e-4
SETTLE = 40

#: level -> (vertices, thresholds, cell cap): the grid level bins into 8x8
#: cells (``choose_grid`` of its padded rows), so the near field and the
#: far field both contribute. No bucket overflows at its cap of 48; at a
#: cap of 8 about 270 of its 600 vertices overflow, so the softened
#: overflow terms contribute too.
LEVELS = {
    "exact": (300, dict(exact_threshold=512, grid_threshold=1024), None),
    "neighbor": (700, dict(exact_threshold=256, grid_threshold=1024), None),
    "grid": (600, dict(exact_threshold=64, grid_threshold=256), None),
    "grid_overflow": (600, dict(exact_threshold=64, grid_threshold=256), 8),
}


def coarse_level(level: str, seed: int):
    """A Delaunay graph with edge weights in [1, 3] and masses in [1, 4],
    the schedule of the second-finest of three levels in ``level``'s mode,
    and a settled drawing: seeded normal positions after ``SETTLE``
    reference iterations of that schedule."""
    n, thr, cell_cap = LEVELS[level]
    mode = level.split("_")[0]
    e, _ = G.delaunay(n, seed)
    rng = np.random.default_rng(seed)
    g0 = build_graph(e, n)
    ewt = np.asarray(g0.ewt).copy()
    half = rng.uniform(1.0, 3.0, len(e)).astype(np.float32)
    src, dst = np.asarray(g0.src), np.asarray(g0.dst)
    und = {tuple(sorted(p)): w for p, w in zip(map(tuple, e), half)}
    valid = np.asarray(g0.emask)
    ewt[valid] = [und[tuple(sorted((s, d)))]
                  for s, d in zip(src[valid], dst[valid])]
    mass = np.asarray(g0.mass).copy()
    mass[:n] = rng.uniform(1.0, 4.0, n)
    with io_boundary():
        g = dataclasses.replace(g0, ewt=jnp.asarray(ewt),
                                mass=jnp.asarray(mass, jnp.float32))
    pos = np.zeros((g.n_pad, 2), np.float32)
    pos[:n] = rng.normal(size=(n, 2)) * np.sqrt(n) / 4
    sched = make_schedule(1, 3, n, len(e), n_pad=g.n_pad, engine="stress",
                          **thr)
    assert sched.mode == mode
    if cell_cap is not None:
        sched = dataclasses.replace(sched, cell_cap=cell_cap)
    nbrs = get_engine("stress").init_state(g, sched, 0)
    settle = dataclasses.replace(sched, iters=SETTLE)
    return g, reference(g, pos, settle, nbrs), sched


def program_step(g, pos, sched, iters, build=None):
    """The program's cached single-graph step at ``iters``, and the k-hop
    lists it ran with; ``build`` replaces the cached program."""
    sched = dataclasses.replace(sched, iters=iters)
    eng = get_engine("stress")
    nbr_idx, nbr_mask = eng.init_state(g, sched, 0)
    _, fn, _, args = bucketing.cached_refine(
        g, pos, sched, nbr_idx, nbr_mask, ideal_len=1.0, rep_const=1.0)
    if build is not None:
        fn = build(sched.mode, sched.grid_dim, sched.cell_cap)
    return np.asarray(fn(*args)), sched, (nbr_idx, nbr_mask)


def reference(g, pos, sched, nbrs):
    with io_boundary():
        return np.asarray(stress_ref.refine(g, jnp.asarray(pos), sched,
                                            *nbrs))


def err(got, want, g, sched) -> float:
    valid = np.asarray(g.vmask)
    return float(np.abs(got - want)[valid].max() / sched.temp0)


@pytest.fixture(params=["ref", "interpret"])
def backend(request, monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", request.param)
    return request.param


@pytest.mark.parametrize("iters", [1, 5])
@pytest.mark.parametrize("level", list(LEVELS))
def test_single_graph_step_matches_the_reference(backend, level, iters):
    g, pos, sched = coarse_level(level, seed=3)
    got, sched, nbrs = program_step(g, pos, sched, iters)
    want = reference(g, pos, sched, nbrs)
    assert np.abs(got - pos).max() > 1e-2 * sched.temp0   # it moved
    assert err(got, want, g, sched) <= TOL


@pytest.mark.parametrize("level", list(LEVELS))
def test_batched_lanes_match_the_reference(backend, level):
    """Two lanes of one batched dispatch, with budgets of 5 and 2
    iterations: each lane, re-padded to its lane bucket, against the
    reference of that lane alone."""
    g, pos, sched = coarse_level(level, seed=4)
    reqs = [bucketing.make_request(g, pos, dataclasses.replace(
        sched, iters=it), seed=11) for it in (5, 2)]
    out = bucketing.refine_level_many(reqs, ideal_len=1.0, rep_const=1.0)
    eng = get_engine("stress")
    for req, got in zip(reqs, out):
        nbrs = eng.init_state(req.g, req.sched, req.seed)
        want = reference(req.g, req.pos0, req.sched, nbrs)
        assert err(np.asarray(got), want, req.g, req.sched) <= TOL


def test_alpha_schedule_reaches_the_total_shrink_after_the_last_iteration():
    """The annealing both sides run: α₀ at a level's first iteration,
    α₀·ALPHA_SHRINK after its last."""
    iters = 7
    a0, decay = stress.alpha_schedule(iters)
    assert a0 == stress.ALPHA0
    assert decay ** iters == pytest.approx(stress.ALPHA_SHRINK, rel=1e-6)


# the program's functions as they are, for the planted changes below
_TERMS = stress.stress_terms
_ITERATION = stress.stress_iteration


def _stress_terms_unit_weight(g, L):
    ell, _, _ = _TERMS(g, L)
    we = jnp.where(g.emask, 1.0, 0.0)
    rho = jnp.zeros(g.n_pad + 1).at[g.dst].add(we)[:g.n_pad]
    return ell, we, rho


def _alpha_left_out(g, pos, nbr_idx, nbr_mask, ell, we, rho, params, temp,
                    alpha, **kw):
    return _ITERATION(g, pos, nbr_idx, nbr_mask, ell, we, rho, params, temp,
                      jnp.ones_like(alpha), **kw)


def _bf16_positions(g, pos, *a, **kw):
    return _ITERATION(g, pos.astype(jnp.bfloat16).astype(jnp.float32), *a,
                      **kw)


@pytest.mark.parametrize("fault", ["unit_weight", "alpha_left_out",
                                   "bf16_positions"])
@pytest.mark.parametrize("mode", ["exact", "grid"])
def test_a_planted_change_fails_the_tolerance(monkeypatch, mode, fault):
    """The same comparison with the program's equations changed: w_e taken
    as 1 (the targets' weights ignore the compounded lengths), α left out
    of the entropy term, or the positions cast to bfloat16 in the step."""
    monkeypatch.setenv("REPRO_PALLAS", "ref")
    name, value = {
        "unit_weight": ("stress_terms", _stress_terms_unit_weight),
        "alpha_left_out": ("stress_iteration", _alpha_left_out),
        "bf16_positions": ("stress_iteration", _bf16_positions),
    }[fault]
    monkeypatch.setattr(stress, name, value)
    g, pos, sched = coarse_level(mode, seed=3)
    got, sched, nbrs = program_step(g, pos, sched, 1,
                                    build=get_engine("stress").build_refine)
    assert err(got, reference(g, pos, sched, nbrs), g, sched) > 100 * TOL


# the grid op's aggregate field as it is: ``far_corrections`` takes it
# unsoftened for the near cells it subtracts, softened for the overflow it
# adds back and for an overflowed vertex's bucketed neighbors
_AGG_FIELD_9 = grid_ops._agg_field_9


def _overflow_dropped(pos, mu9, m9, C, L, md, r9=None):
    if r9 is None:
        return _AGG_FIELD_9(pos, mu9, m9, C, L, md)
    return jnp.zeros_like(pos)


def _near_cells_kept(pos, mu9, m9, C, L, md, r9=None):
    if r9 is None:
        return jnp.zeros_like(pos)
    return _AGG_FIELD_9(pos, mu9, m9, C, L, md, r9)


@pytest.mark.parametrize("fault", ["overflow_dropped", "near_cells_kept"])
def test_a_planted_grid_correction_fails_the_tolerance(monkeypatch, fault):
    """The grid op's far-field corrections changed in the program alone
    (the reference shares none of its code): the bucket overflow left out
    of the far field, or the 3x3 neighborhood's cells kept in it although
    the near field counted them exactly. Taken at the overflowing level,
    one iteration: each moves the drawing by over 100 times ``TOL``
    (about 6,600 and 19,000 times on XLA:CPU)."""
    monkeypatch.setenv("REPRO_PALLAS", "ref")
    monkeypatch.setattr(grid_ops, "_agg_field_9", {
        "overflow_dropped": _overflow_dropped,
        "near_cells_kept": _near_cells_kept}[fault])
    g, pos, sched = coarse_level("grid_overflow", seed=3)
    got, sched, nbrs = program_step(g, pos, sched, 1,
                                    build=get_engine("stress").build_refine)
    assert err(got, reference(g, pos, sched, nbrs), g, sched) > 100 * TOL
