"""The general traffic generator: one mix file of parameters.

A mix (``bench/traffic/<name>.json``) says

* ``loop``: ``closed``: one client sends its next request when the last
  one has answered; requests start while the window is open, and the
  last runs to its end;
* ``pool_per_size``: distinct graphs made per size;
* ``requests``: distinct requests (a graph of the pool and a layout
  seed), equal shares of every size; the window cycles through them;
* ``warm``: set-up sends every distinct request once before the window
  (the same graph and layout seed, so the same hierarchy and shape
  buckets), with the configuration's ``layout`` keys overridden by
  ``layout`` (fewer iterations: the iteration count is a traced argument
  of the compiled steps, so the same programs load).

The configuration gives the graph sizes; every seed gets the same
multiset of sizes, in another order.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench.lib.graphs import SEED_SPAN, graph_pool, rng_for


@dataclasses.dataclass
class Answer:
    ok: bool
    body: object                  # what the entry hands back
    note: str = ""                # one line about the answer, for stderr


@dataclasses.dataclass
class Item:
    index: int
    seed: int
    graph: int                    # index into the plan's pool
    done: float | None = None     # perf_counter
    answer: Answer | None = None


@dataclasses.dataclass
class Plan:
    pool: list                    # [(edges, n)]
    items: list                   # the window's requests, in order
    replay: list                  # the warm-up's requests


def _requests(pool: list, nsizes: int, per: int, rng: np.random.Generator,
              count: int) -> list:
    """``count`` requests over ``pool``: equal shares of every size in an
    order drawn from ``rng``, each with its own layout seed."""
    cls = rng.permutation(np.arange(count) % nsizes)
    seeds = rng.integers(0, SEED_SPAN, count)
    used = np.zeros(nsizes, np.int64)
    items = []
    for i in range(count):
        c = int(cls[i])
        g = c + nsizes * int(used[c] % per)
        used[c] += 1
        items.append(Item(index=i, seed=int(seeds[i]), graph=g))
    return items


def plan(conf: dict, mix: dict, seed: int) -> Plan:
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    sizes = [int(s) for s in conf["sizes"]]
    per = int(mix.get("pool_per_size", 1))
    pool = graph_pool(seed, sizes, per * len(sizes), stream=1)
    distinct = _requests(pool, len(sizes), per, rng_for(seed, 2),
                         int(mix["requests"]))
    # enough for any window; the loop takes them in order
    items = [dataclasses.replace(distinct[i % len(distinct)], index=i)
             for i in range(10_000)]
    replay = ([dataclasses.replace(it) for it in distinct]
              if "warm" in mix else [])
    return Plan(pool=pool, items=items, replay=replay)


def closed_loop(system, reqs, items: list, t0: float, seconds: float,
                span) -> list:
    """One client sends the next request when the last has answered, while
    the window is open; the last one runs to its end."""
    taken = []
    for it in items:
        if time.perf_counter() - t0 >= seconds:
            break
        with span(it):
            it.answer = system.call(reqs(it))
        it.done = time.perf_counter()
        taken.append(it)
    return taken
