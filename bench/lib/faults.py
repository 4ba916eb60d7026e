"""Faults planted under the timed path, and the control.

Each is a context manager that patches the program for the block, so a
run inside it drives the window, the reference and the verdict as usual
and should come out not correct:

* ``no_refine`` (the control, and the fault "a step that returns its
  state unchanged"): every refine step, single-graph and batched, returns
  the positions it was given. It breaks the configurations' guarantee
  that every level is refined;
* ``half_batch``: each refine step refines only the first half of its
  batch (the batched step's lanes; the single-graph step's vertex rows)
  and hands the rest back unchanged;
* ``altered``: each finished layout is altered where it is produced: the
  pruned-leaf reinsertion returns every vertex's position shifted to the
  next vertex;
* ``half_finest`` and ``unrefined_finest``: the finest level (the input
  graph) is refined for half its stated iterations, or for none; every
  coarser level runs as stated. They break the stated schedule in the
  level that takes most of a large layout's time.

The exchange between chips has no fault here: every cell runs on one
chip.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(module, name: str, value):
    old = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, old)


@contextlib.contextmanager
def no_refine():
    from repro.core import bucketing
    with _patched(bucketing, "refine_level",
                  lambda g, pos0, sched, **kw: pos0), \
            _patched(bucketing, "refine_level_many",
                     lambda reqs, **kw: [r.pos0 for r in reqs]):
        yield


@contextlib.contextmanager
def half_batch():
    import jax.numpy as jnp
    from repro.core import bucketing
    one, many = bucketing.refine_level, bucketing.refine_level_many

    def refine_level(g, pos0, sched, **kw):
        keep = jnp.array(pos0, copy=True)
        out = one(g, pos0, sched, **kw)
        rows = jnp.arange(out.shape[0])[:, None] < out.shape[0] // 2
        return jnp.where(rows, out, keep)

    def refine_level_many(reqs, **kw):
        keep = [jnp.array(r.pos0, copy=True) for r in reqs]
        out = many(reqs, **kw)
        half = (len(reqs) + 1) // 2
        return list(out[:half]) + keep[half:]

    with _patched(bucketing, "refine_level", refine_level), \
            _patched(bucketing, "refine_level_many", refine_level_many):
        yield


@contextlib.contextmanager
def altered():
    import numpy as np
    from repro.core import multilevel
    reinsert = multilevel.reinsert

    def shifted(*a, **kw):
        return np.roll(np.asarray(reinsert(*a, **kw)), 1, axis=0)

    with _patched(multilevel, "reinsert", shifted):
        yield


def _finest_iters(scale: float):
    import dataclasses
    from repro.core import multilevel
    make = multilevel.make_schedule

    def make_schedule(level, *a, **kw):
        sched = make(level, *a, **kw)
        if level != 0:
            return sched
        return dataclasses.replace(sched, iters=int(sched.iters * scale))

    return _patched(multilevel, "make_schedule", make_schedule)


@contextlib.contextmanager
def half_finest():
    with _finest_iters(0.5):
        yield


@contextlib.contextmanager
def unrefined_finest():
    with _finest_iters(0.0):
        yield


FAULTS = {"no_refine": no_refine, "half_batch": half_batch,
          "altered": altered, "half_finest": half_finest,
          "unrefined_finest": unrefined_finest}
