"""Delaunay graphs of the DIMACS10 ``delaunay_nX`` family, made from a seed.

``delaunay_nX`` is the Delaunay triangulation of 2^X points drawn
uniformly from the unit square. The points are drawn anew from the seed,
so the same seed gives the same graph. (A copy of the generator in
``repro.graphs.generators.delaunay``, kept with the yardstick.)
"""
from __future__ import annotations

import numpy as np

#: program seeds lie in [0, SEED_SPAN): the layout seeds feed 32-bit PRNG
#: keys inside the program, while the benchmark's --seed may exceed 2^31
SEED_SPAN = 1 << 24


def delaunay(n: int, rng: np.random.Generator) -> np.ndarray:
    """Unique undirected edges ``int64[m, 2]`` (u < v) of the Delaunay
    triangulation of ``n`` uniform points."""
    from scipy.spatial import Delaunay
    pts = rng.random((n, 2))
    s = Delaunay(pts).simplices
    e = np.concatenate([s[:, [0, 1]], s[:, [1, 2]], s[:, [0, 2]]])
    return np.unique(np.sort(e, axis=1), axis=0).astype(np.int64)


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator for one use of the run's seed."""
    return np.random.default_rng([int(seed), *map(int, stream)])


def graph_pool(seed: int, sizes: list[int], count: int,
               stream: int = 0) -> list[tuple[np.ndarray, int]]:
    """``count`` graphs cycling through ``sizes``, each with its own points."""
    out = []
    for i in range(count):
        n = int(sizes[i % len(sizes)])
        out.append((delaunay(n, rng_for(seed, stream, i)), n))
    return out
