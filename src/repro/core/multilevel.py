"""Multi-GiLA — the full multilevel pipeline (paper §3.1).

pruning → (partitioning) → coarsening* → coarsest layout → [placement →
single-level refinement]* → reinsertion, applied per connected component,
components packed on a shelf grid at the end.

The same pipeline powers four DRIVERS (``LayoutConfig.driver``):
  * ``multigila``   — the paper's algorithm (distributed-semantics supersteps);
  * ``multigila_dist`` — identical algorithm, but every level's refinement
                      runs through the *actually sharded* superstep
                      (core/distributed.py:run_layout_level) on a device
                      mesh: exact / neighbor / grid repulsion per the same
                      schedule, SPMD over (data, model);
  * ``centralized`` — FM³ stand-in baseline: identical hierarchy, exact
                      all-pairs forces and full iteration budget everywhere;
  * ``flat``        — single-level GiLA baseline (the paper's predecessor [5]).

Orthogonally, ``LayoutConfig.engine`` selects the per-level refinement
ENGINE (core/engine.py): ``"gila"`` — Fruchterman–Reingold forces — or
``"stress"`` — multilevel maxent-stress local iterations (core/stress.py).
Every driver threads the engine id through its schedules, so hierarchy,
placement, bucketing and wave grouping are engine-agnostic.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

from repro.graphs.graph import PaddedGraph, build_graph, unique_edges
from repro.core.solar_merger import run_merger, next_level, LevelInfo
from repro.core.solar_placer import solar_placer
from repro.core import gila, bucketing
from repro.core.bucketing import PHASES
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.clock import Clock, SystemClock
from repro.utils.timing import StepTimer
from repro.utils.transfer import io_boundary
from repro.core.schedule import make_schedule, LevelSchedule
from repro.core.pruning import prune_degree_one, reinsert


@dataclasses.dataclass(frozen=True)
class LayoutConfig:
    coarsest_threshold: int = 50     # halt coarsening below this many vertices
    max_levels: int = 24
    min_shrink: float = 0.96         # stop if a level shrinks less than this
    p_sun: float = 0.35
    exact_threshold: int = 2048      # exact N-body below this size
    grid_threshold: int = 32768      # grid-approx repulsion above this size
    coarsest_iters: int = 300
    finest_iters: int = 50
    ideal_len: float = 1.0
    rep_const: float = 1.0
    seed: int = 0
    driver: str = "multigila"   # multigila | multigila_dist | centralized | flat
    engine: str = "gila"        # per-level refinement engine: gila | stress
    # multigila_dist (data, model) mesh; None → one mesh over all local devices
    mesh_shape: tuple | None = None
    prune: bool = True
    # pow2 shape buckets + process-wide compile cache (core/bucketing.py);
    # False = the exact-shape legacy path (retraces per level), kept for
    # the parity test and as the pre-refactor benchmark baseline
    bucketing: bool = True

    def __post_init__(self):
        # back-compat shim: ``engine=`` used to name the DRIVER. Constructor
        # calls passing a driver name there keep working; the per-level
        # force model then stays the default. (frozen dataclass — rebind
        # via object.__setattr__; dataclasses.replace re-runs this no-op.)
        if self.engine in ("multigila", "multigila_dist", "centralized",
                           "flat"):
            object.__setattr__(self, "driver", self.engine)
            object.__setattr__(self, "engine", "gila")


@dataclasses.dataclass
class LayoutStats:
    levels: int = 0
    level_sizes: tuple = ()
    merger_rounds_total: int = 0
    supersteps: int = 0


@dataclasses.dataclass
class LevelExport:
    """One level of the hierarchy, as the serving layer consumes it.

    Level 0 is the FULL input graph (pruned leaves reinserted); levels
    1..L-1 are the solar-merger coarse graphs. ``parent[v]`` is v's vertex
    in the next coarser level (None at the coarsest); ``rep[v]`` is the
    level-0 vertex id of the system sun v collapses to, chained down the
    hierarchy — coarse vertices stay addressable in input-graph terms.
    """
    n: int
    edges: np.ndarray            # int64[m, 2] — unique undirected, level-local
    parent: np.ndarray | None    # int32[n] — index into the next coarser level
    rep: np.ndarray              # int64[n] — representative level-0 vertex id


@dataclasses.dataclass
class HierarchyExport:
    """Per-level structure of a finished layout (serve/tiles.py input).

    ``pos`` holds final positions for level 0 only; coarse-level positions
    are *derived* (mass-weighted member centroids) so every zoom band of the
    tile pyramid agrees with the drawing the user actually gets — the
    interior-level positions computed mid-refinement do not (fine refinement
    moves vertices after the coarse level is abandoned).
    """
    levels: list            # list[LevelExport], levels[0] = finest
    pos: np.ndarray         # float32[levels[0].n, 2]


def connected_components(edges: np.ndarray, n: int) -> np.ndarray:
    """Component labels, label = minimum vertex id in the component.

    Vectorized: ``scipy.sparse.csgraph`` when available (one C-level BFS
    sweep), else numpy pointer-jumping (hook each vertex to its minimum
    neighbor label, then ``label[label]`` doubling — O(m log n) array ops).
    Either path replaces the per-edge Python union-find loop whose
    interpreter time alone dominated ingest on million-edge graphs.
    """
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    if n <= 0:
        return np.zeros((0,), dtype=np.int64)
    if len(edges) == 0:
        return np.arange(n, dtype=np.int64)
    try:
        from scipy.sparse import coo_matrix
        from scipy.sparse.csgraph import connected_components as _cc
    except ImportError:                  # pragma: no cover - scipy is baked in
        return _components_pointer_jumping(edges, n)
    a = coo_matrix((np.ones(len(edges), np.int8),
                    (edges[:, 0], edges[:, 1])), shape=(n, n))
    _, comp = _cc(a, directed=False)
    # csgraph labels are arbitrary ints — remap to the contract (min vertex
    # id per component) so callers can rely on stable, seed-free labels
    first = np.full(int(comp.max()) + 1, n, dtype=np.int64)
    np.minimum.at(first, comp, np.arange(n, dtype=np.int64))
    return first[comp]


def _components_pointer_jumping(edges: np.ndarray, n: int) -> np.ndarray:
    """Scipy-free fallback: min-neighbor hooking + pointer doubling."""
    label = np.arange(n, dtype=np.int64)
    u, v = edges[:, 0], edges[:, 1]
    while True:
        lu, lv = label[u], label[v]
        # hook: every endpoint's label drops to the min over its edges
        np.minimum.at(label, u, lv)
        np.minimum.at(label, v, lu)
        # shortcut: pointer doubling until labels are roots
        while True:
            nxt = label[label]
            if np.array_equal(nxt, label):
                break
            label = nxt
        if np.array_equal(label[u], label[v]):
            return label


def build_hierarchy(g0: PaddedGraph, cfg: LayoutConfig
                    ) -> tuple[list[PaddedGraph], list[LevelInfo]]:
    """Coarsening loop: repeated Distributed Solar Merger applications.

    When the shrink-ratio break fires, the final merger's coarse graph AND
    its ``LevelInfo`` are both discarded together (the placer consumes
    ``infos[i]`` to go from ``graphs[i+1]`` back to ``graphs[i]``, so a
    dangling info with no coarse graph would desynchronize the walk-down).
    The returned lists always satisfy ``len(graphs) == len(infos) + 1``.
    """
    graphs, infos = [g0], []
    g = g0
    for lvl in range(cfg.max_levels):
        if g.n <= cfg.coarsest_threshold:
            break
        st = run_merger(g, p_sun=cfg.p_sun, seed=cfg.seed + 101 * lvl)
        cg, info = next_level(g, st, bucket=cfg.bucketing)
        if cg.n >= g.n * cfg.min_shrink or cg.n < 1:
            break
        graphs.append(cg)
        infos.append(info)
        g = cg
    assert len(graphs) == len(infos) + 1, (len(graphs), len(infos))
    return graphs, infos


def _layout_one_level(g: PaddedGraph, pos0, sched: LevelSchedule,
                      cfg: LayoutConfig, seed: int):
    if cfg.driver == "multigila_dist":
        from repro.core.distributed import run_layout_level
        from repro.launch.mesh import make_mesh, make_host_mesh
        mesh = (make_mesh(tuple(cfg.mesh_shape), ("data", "model"))
                if cfg.mesh_shape else make_host_mesh())
        return run_layout_level(mesh, g, pos0, sched,
                                ideal_len=cfg.ideal_len,
                                rep_const=cfg.rep_const, seed=seed,
                                bucket=cfg.bucketing)
    if cfg.bucketing:
        # bucketed path: cached compiled step per shape bucket, iteration
        # count and cooling schedule traced (core/bucketing.py)
        return bucketing.refine_level(g, pos0, sched,
                                      ideal_len=cfg.ideal_len,
                                      rep_const=cfg.rep_const, seed=seed)
    # exact/grid modes need no neighbor lists (grid rebins inside the
    # iteration loop); the engine's init_state builds k-hop lists otherwise
    from repro.core.engine import get_engine
    nbr_idx, nbr_mask = get_engine(sched.engine).init_state(g, sched, seed)
    # exact-shape path: compile time is inseparable here, and the jit call
    # stages its python-scalar schedule knobs h2d at dispatch (the bucketed
    # path stages them explicitly in cached_refine instead)
    with PHASES.phase("refine"), io_boundary():
        if sched.engine == "stress":
            from repro.core import stress
            a0, ad = stress.alpha_schedule(sched.iters)
            pos = stress.stress_layout(
                g, pos0, nbr_idx, nbr_mask, mode=sched.mode,
                iters=sched.iters, temp0=sched.temp0,
                temp_decay=sched.temp_decay, alpha0=a0, alpha_decay=ad,
                ideal_len=cfg.ideal_len, rep_const=cfg.rep_const,
                backend=bucketing.kernel_backend(),
                grid_dim=sched.grid_dim, cell_cap=sched.cell_cap)
        else:
            pos = gila.gila_layout(
                g, pos0, nbr_idx, nbr_mask, mode=sched.mode,
                iters=sched.iters, temp0=sched.temp0,
                temp_decay=sched.temp_decay, ideal_len=cfg.ideal_len,
                rep_const=cfg.rep_const, backend=bucketing.kernel_backend(),
                grid_dim=sched.grid_dim, cell_cap=sched.cell_cap)
        pos.block_until_ready()             # keep device time in-phase
    return pos


def _single_level_export(edges: np.ndarray, n: int, pos: np.ndarray
                         ) -> HierarchyExport:
    lvl = LevelExport(n=n, edges=np.asarray(edges, np.int64).reshape(-1, 2),
                      parent=None, rep=np.arange(n, dtype=np.int64))
    return HierarchyExport(levels=[lvl], pos=np.asarray(pos, np.float32))


def _input_to_work(pr, n: int) -> np.ndarray:
    """int64[n]: input vertex → work-graph (pruned) vertex. Leaf hosts are
    always kept (a host had degree ≥ 2, or is the kept end of a K2), so one
    indirection suffices."""
    if pr is None:
        return np.arange(n, dtype=np.int64)
    m = np.full(n, -1, np.int64)
    m[pr.old_of_new] = np.arange(pr.n)
    m[pr.leaves] = m[pr.leaf_host]
    return m


def _build_export(edges, n, pr, graphs, infos, pos_full) -> HierarchyExport:
    """Assemble the per-level export of one component (see HierarchyExport)."""
    L = len(graphs)
    if L <= 1:
        return _single_level_export(edges, n, pos_full)
    w_of_in = _input_to_work(pr, n)
    work_parent = np.asarray(infos[0].parent_coarse)[: graphs[0].n]
    rep_work = (pr.old_of_new if pr is not None
                else np.arange(n, dtype=np.int64))
    levels = [LevelExport(n=n, edges=np.asarray(edges, np.int64).reshape(-1, 2),
                          parent=work_parent[w_of_in].astype(np.int32),
                          rep=np.arange(n, dtype=np.int64))]
    rep = rep_work
    for i in range(1, L):
        gi = graphs[i]
        rep = rep[np.asarray(infos[i - 1].sun_pos_index)]
        parent = (np.asarray(infos[i].parent_coarse)[: gi.n].astype(np.int32)
                  if i < L - 1 else None)
        levels.append(LevelExport(n=gi.n, edges=unique_edges(gi),
                                  parent=parent, rep=rep.astype(np.int64)))
    return HierarchyExport(levels=levels, pos=np.asarray(pos_full, np.float32))


def layout_component(edges: np.ndarray, n: int, cfg: LayoutConfig,
                     *, export: bool = False, weights=None):
    """Multi-GiLA on one connected component; returns positions [n,2] (and,
    with ``export=True``, the HierarchyExport the serving layer consumes).

    ``weights`` (float[m], optional) are per-edge weights: the attraction
    term's ideal length ℓ_e = w_e·L, and the stress engine's target
    distances. They thread prune → build_graph → hierarchy (the solar
    merger compounds them into coarse ``ewt``)."""
    stats = LayoutStats()

    def ret(pos, stats, graphs=None, infos=None, pr=None):
        if not export:
            return pos, stats
        exp = (_build_export(edges, n, pr, graphs, infos, pos)
               if graphs is not None else _single_level_export(edges, n, pos))
        return pos, stats, exp

    if n == 1:
        return ret(np.zeros((1, 2), np.float32), stats)
    if cfg.prune and cfg.driver != "flat":
        with obs_trace.span("layout.prune", cat="host", n=n):
            pr = prune_degree_one(edges, n, weights=weights)
    else:
        pr = None

    work_edges = pr.edges if pr is not None else edges
    work_n = pr.n if pr is not None else n
    mass = pr.mass if pr is not None else None
    work_ewt = pr.ewt if pr is not None else weights
    if work_n == 0 or len(work_edges) == 0:
        # star graphs collapse entirely under pruning: lay out leaves only
        pos = reinsert(pr, np.zeros((max(work_n, 1), 2), np.float32), work_edges) \
            if pr is not None else np.zeros((n, 2), np.float32)
        return ret(pos, stats)
    with obs_trace.span("layout.build", cat="host", n=work_n):
        g0 = build_graph(work_edges, work_n, mass=mass, ewt=work_ewt,
                         bucket=cfg.bucketing)

    if cfg.driver == "flat":
        sched = make_schedule(0, 1, g0.n, g0.m,
                              exact_threshold=cfg.exact_threshold,
                              grid_threshold=cfg.grid_threshold,
                              coarsest_iters=cfg.coarsest_iters,
                              ideal_len=cfg.ideal_len, n_pad=g0.n_pad,
                              engine=cfg.engine)
        pos = gila.random_init(g0, cfg.ideal_len * max(g0.n, 4) ** 0.5,
                               cfg.seed)
        pos = _layout_one_level(g0, pos, sched, cfg, cfg.seed)
        stats.levels = 1
        stats.level_sizes = ((g0.n, g0.m),)
        return ret(np.asarray(pos)[:n], stats)

    with PHASES.phase("coarsen"), obs_trace.span("coarsen", cat="host",
                                                 n=g0.n, m=g0.m):
        graphs, infos = build_hierarchy(g0, cfg)
    L = len(graphs)
    stats.levels = L
    stats.level_sizes = tuple((g.n, g.m) for g in graphs)

    exact_thr = (10 ** 9) if cfg.driver == "centralized" else cfg.exact_threshold

    # coarsest level: random init + layout
    gk = graphs[-1]
    sched = make_schedule(L - 1, L, gk.n, gk.m, exact_threshold=exact_thr,
                          grid_threshold=cfg.grid_threshold,
                          coarsest_iters=cfg.coarsest_iters,
                          finest_iters=cfg.finest_iters,
                          ideal_len=cfg.ideal_len, n_pad=gk.n_pad,
                          engine=cfg.engine)
    pos = gila.random_init(gk, cfg.ideal_len * max(gk.n, 4) ** 0.5, cfg.seed)
    with obs_trace.span("refine.level", level=L - 1, n=gk.n):
        pos = _layout_one_level(gk, pos, sched, cfg, cfg.seed + L)

    # walk the hierarchy back down: place, then refine
    for i in range(L - 2, -1, -1):
        gi = graphs[i]
        with PHASES.phase("place"), obs_trace.span("place", cat="host",
                                                   level=i):
            pos = solar_placer(gi, infos[i], pos, seed=cfg.seed + i,
                               scatter_scale=0.5 * cfg.ideal_len)
            pos.block_until_ready()         # keep device time in-phase
        sched = make_schedule(i, L, gi.n, gi.m, exact_threshold=exact_thr,
                              grid_threshold=cfg.grid_threshold,
                              coarsest_iters=cfg.coarsest_iters,
                              finest_iters=cfg.finest_iters,
                              ideal_len=cfg.ideal_len, n_pad=gi.n_pad,
                              engine=cfg.engine)
        with obs_trace.span("refine.level", level=i, n=gi.n):
            pos = _layout_one_level(gi, pos, sched, cfg, cfg.seed + i)

    with obs_trace.span("layout.finish", cat="host", n=n):
        # read-back of the finest level, then the pruned leaves
        pos = np.asarray(pos, np.float32)[: g0.n]
        if pr is not None:
            pos = reinsert(pr, pos, work_edges)
        pos = pos[:n] if pr is None else pos
    return ret(pos, stats, graphs=graphs, infos=infos, pr=pr)


def _pack_components(layouts: list[np.ndarray], pad: float = 2.0) -> np.ndarray:
    """Shelf-pack component bounding boxes into a near-square arrangement."""
    boxes = []
    for P in layouts:
        lo = P.min(axis=0) if len(P) else np.zeros(2)
        hi = P.max(axis=0) if len(P) else np.zeros(2)
        boxes.append((P - lo, hi - lo + pad))
    order = np.argsort([-(b[1][0] * b[1][1]) for b in boxes])
    total_area = sum(float(b[1][0] * b[1][1]) for b in boxes)
    shelf_w = max(total_area ** 0.5, max(float(b[1][0]) for b in boxes))
    out = [None] * len(boxes)
    x = y = shelf_h = 0.0
    for oi in order:
        P, wh = boxes[oi]
        if x + wh[0] > shelf_w and x > 0:
            y += shelf_h
            x = shelf_h = 0.0
        out[oi] = P + np.array([x, y], np.float32)
        x += float(wh[0])
        shelf_h = max(shelf_h, float(wh[1]))
    return out


def _merge_exports(exports: list, index_maps: list, edges: np.ndarray,
                   n: int, pos: np.ndarray) -> HierarchyExport:
    """Merge per-component hierarchies into global zoom bands.

    Band 0 keeps the ORIGINAL global vertex ids (level-0 positions are the
    final packed drawing). Band b unions, from every component, its level
    ``min(b, L_c-1)`` — a component whose hierarchy is shallower than b
    keeps contributing its coarsest level with an identity parent map, so
    every band is a complete drawing of the whole graph.
    """
    n_bands = max(len(e.levels) for e in exports)
    if n_bands == 1:
        return _single_level_export(edges, n, pos)

    # per (band, component) offsets of the merged index space (band 0 is the
    # identity on global ids, so offsets start at band 1)
    offs = []
    for b in range(1, n_bands):
        sizes = [e.levels[min(b, len(e.levels) - 1)].n for e in exports]
        offs.append(np.concatenate([[0], np.cumsum(sizes[:-1])]).astype(np.int64))

    def off(b, ci):  # band-b merged index offset of component ci
        return int(offs[b - 1][ci])

    levels = []
    # band 0: global ids, parent composed per component
    parent0 = np.zeros(n, np.int32)
    for ci, (e, vs) in enumerate(zip(exports, index_maps)):
        l0 = e.levels[0]
        # a single-level component repeats identically in band 1 → identity
        p = (l0.parent if l0.parent is not None
             else np.arange(l0.n, dtype=np.int32))
        parent0[vs] = p + off(1, ci)
    levels.append(LevelExport(n=n, edges=np.asarray(edges, np.int64),
                              parent=parent0,
                              rep=np.arange(n, dtype=np.int64)))
    for b in range(1, n_bands):
        es, reps, parents = [], [], []
        nb = 0
        for ci, (e, vs) in enumerate(zip(exports, index_maps)):
            lvl = e.levels[min(b, len(e.levels) - 1)]
            es.append(lvl.edges + off(b, ci))
            reps.append(vs[lvl.rep])             # component-local → global id
            if b < n_bands - 1:
                if b + 1 < len(e.levels):
                    parents.append(lvl.parent + off(b + 1, ci))
                else:  # saturated: same level repeats in the next band
                    parents.append(np.arange(lvl.n, dtype=np.int32)
                                   + off(b + 1, ci))
            nb += lvl.n
        levels.append(LevelExport(
            n=nb,
            edges=(np.concatenate(es) if es else np.zeros((0, 2), np.int64)),
            parent=(np.concatenate(parents).astype(np.int32)
                    if b < n_bands - 1 else None),
            rep=np.concatenate(reps).astype(np.int64)))
    return HierarchyExport(levels=levels, pos=np.asarray(pos, np.float32))


class _ComponentTask:
    """Refinement state machine of one connected component, for the batched
    multi-graph driver (``multigila_layout_many``).

    Construction runs everything UP TO refinement exactly as
    ``layout_component`` does (pruning → hierarchy → schedules); the driver
    then pulls one ``RefineRequest`` per wave (coarsest level first, the
    placer invoked in between) and feeds the refined positions back.
    Per-level randomness, seeds and schedules match ``layout_component``
    line for line — with padding invariance (graphs/packing.py) that makes
    every fed-back position bit-identical to the sequential driver's.
    """

    def __init__(self, edges: np.ndarray, n: int, cfg: LayoutConfig,
                 lane: object = None, weights=None):
        self.cfg = cfg
        self.stats = LayoutStats()
        self.n = n
        self.lane = lane             # observability label: "<job_uid>.<comp>"
        self.final: np.ndarray | None = None
        self.pr = None
        if n == 1:
            self.final = np.zeros((1, 2), np.float32)
            return
        if cfg.prune:
            self.pr = prune_degree_one(edges, n, weights=weights)
        self.work_edges = self.pr.edges if self.pr is not None else edges
        work_n = self.pr.n if self.pr is not None else n
        mass = self.pr.mass if self.pr is not None else None
        work_ewt = self.pr.ewt if self.pr is not None else weights
        if work_n == 0 or len(self.work_edges) == 0:
            # star graphs collapse entirely under pruning (layout_component)
            self.final = (reinsert(self.pr,
                                   np.zeros((max(work_n, 1), 2), np.float32),
                                   self.work_edges)
                          if self.pr is not None
                          else np.zeros((n, 2), np.float32))
            return
        self.g0 = build_graph(self.work_edges, work_n, mass=mass,
                              ewt=work_ewt, bucket=True)
        with PHASES.phase("coarsen"), obs_trace.span(
                "coarsen", cat="host", lane=lane, n=self.g0.n, m=self.g0.m):
            self.graphs, self.infos = build_hierarchy(self.g0, cfg)
        L = len(self.graphs)
        self.stats.levels = L
        self.stats.level_sizes = tuple((g.n, g.m) for g in self.graphs)
        self._level = L - 1          # next level to refine (coarsest first)
        self._pos = None             # refined positions of the level above

    @property
    def done(self) -> bool:
        return self.final is not None

    def _sched(self, i: int) -> LevelSchedule:
        cfg, gi, L = self.cfg, self.graphs[i], len(self.graphs)
        return make_schedule(i, L, gi.n, gi.m,
                             exact_threshold=cfg.exact_threshold,
                             grid_threshold=cfg.grid_threshold,
                             coarsest_iters=cfg.coarsest_iters,
                             finest_iters=cfg.finest_iters,
                             ideal_len=cfg.ideal_len, n_pad=gi.n_pad,
                             engine=cfg.engine)

    def next_request(self) -> bucketing.RefineRequest:
        """Placement (when walking down) + the level's refine request,
        re-padded to its lane bucket."""
        assert not self.done
        cfg, i, L = self.cfg, self._level, len(self.graphs)
        gi = self.graphs[i]
        if i == L - 1:
            pos0 = gila.random_init(gi, cfg.ideal_len * max(gi.n, 4) ** 0.5,
                                    cfg.seed)
            seed = cfg.seed + L
        else:
            with PHASES.phase("place"), obs_trace.span(
                    "place", cat="host", level=i, lane=self.lane):
                pos0 = solar_placer(gi, self.infos[i], self._pos,
                                    seed=cfg.seed + i,
                                    scatter_scale=0.5 * cfg.ideal_len)
                pos0.block_until_ready()
            seed = cfg.seed + i
        return bucketing.make_request(gi, pos0, self._sched(i), seed,
                                      level=i, lane=self.lane)

    def feed(self, pos) -> None:
        """Accept the refined positions of the current level; finalize
        (reinsert pruned leaves) after the finest level."""
        self._pos = pos
        self._level -= 1
        if self._level >= 0:
            return
        p = np.asarray(pos, np.float32)[: self.g0.n]
        if self.pr is not None:
            self.final = reinsert(self.pr, p, self.work_edges)
        else:
            self.final = p[: self.n]


class GraphJob:
    """One submitted graph in a ``WaveScheduler``'s mutable lane set.

    Admission splits the (possibly disconnected) graph into per-component
    ``_ComponentTask`` lanes — each one the same pruning → hierarchy →
    placement state machine the sequential driver walks — and ``result()``
    reassembles them (component shelf-packing as in ``multigila_layout``)
    once every lane has finished its finest level. ``cancelled`` jobs keep
    their tasks but are skipped by the scheduler; their lanes are freed
    without touching any sibling lane's floats.
    """

    def __init__(self, edges: np.ndarray, n: int, cfg: LayoutConfig, *,
                 uid: int = -1, weights=None):
        self.cfg = cfg
        self.n = int(n)
        self.uid = int(uid)          # scheduler-local admission rank: lane
        self.cancelled = False       # labels stay deterministic across runs
        edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
        if weights is not None:
            weights = np.asarray(weights, np.float32).reshape(-1)
        labels = connected_components(edges, self.n)
        self.tasks, self.index_maps = [], []
        for k, c in enumerate(np.unique(labels)):
            vs = np.nonzero(labels == c)[0]
            remap = np.full(self.n, -1, np.int64)
            remap[vs] = np.arange(vs.size)
            emask = labels[edges[:, 0]] == c
            ce = np.stack([remap[edges[emask, 0]], remap[edges[emask, 1]]], 1)
            cw = weights[emask] if weights is not None else None
            self.tasks.append(_ComponentTask(ce, vs.size, cfg,
                                             lane=f"{self.uid}.{k}",
                                             weights=cw))
            self.index_maps.append(vs)

    @property
    def lanes(self) -> int:
        """Live (unfinished) lanes this job still occupies."""
        return 0 if self.cancelled else sum(not t.done for t in self.tasks)

    @property
    def done(self) -> bool:
        return self.cancelled or all(t.done for t in self.tasks)

    def result(self):
        """(pos[n, 2], LayoutStats) — identical to ``multigila_layout``."""
        assert self.done and not self.cancelled
        if len(self.tasks) == 1:
            return self.tasks[0].final, self.tasks[0].stats
        stats = LayoutStats()
        layouts = []
        for t in self.tasks:
            stats.levels = max(stats.levels, t.stats.levels)
            layouts.append(np.asarray(t.final))
        packed = _pack_components(layouts)
        pos = np.zeros((self.n, 2), np.float32)
        for vs, P in zip(self.index_maps, packed):
            pos[vs] = P
        return pos, stats


# wave-composition metrics (DESIGN.md §12): counted at dispatch so both
# the one-shot batched driver and the continuous engine feed them
WAVES_TOTAL = obs_metrics.REGISTRY.counter(
    "gila_waves_total", "Dispatched waves (>= 1 lane)")
LANE_DISPATCHES_TOTAL = obs_metrics.REGISTRY.counter(
    "gila_lane_dispatches_total", "Per-level lane refinements dispatched")
PREEMPTED_LANES_TOTAL = obs_metrics.REGISTRY.counter(
    "gila_preempted_lanes_total",
    "Lanes held past a wave because the wave cap was full")
STRAGGLER_WAVES_TOTAL = obs_metrics.REGISTRY.counter(
    "gila_straggler_waves_total",
    "Waves slower than the StepTimer EWMA threshold")
WAVE_GROUPS_HIST = obs_metrics.REGISTRY.histogram(
    "gila_wave_groups", "Shape-bucket groups per dispatched wave",
    buckets=(1, 2, 3, 4, 6, 8, 12, 16, 24, 32))
GROUP_LANES_HIST = obs_metrics.REGISTRY.histogram(
    "gila_group_lanes", "Member lanes per dispatched shape-bucket group",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128))


class WaveScheduler:
    """Long-lived wave scheduler with a mutable lane set (DESIGN.md §11).

    The inversion that makes continuous batching possible: instead of a
    closed-over batch driven to completion (``multigila_layout_many``'s old
    wave loop), the scheduler exposes ``admit`` / ``step`` / ``drain``.
    Jobs join (and leave, via ``remove``) at any wave boundary; each
    ``step()`` dispatches ONE wave — every selected lane's next per-level
    refinement, grouped by shape bucket and run as single cached batched
    device programs (``bucketing.refine_level_many``). A mid-flight join
    simply appears in the next wave's grouping: lane counts re-bucket to
    pow2 (floor 8, capped by ``lanes_cap``), so a warm engine compiles
    nothing for it. Lanes are arithmetically independent — wave membership
    never changes any lane's floats — so every job's result is
    bit-identical to a dedicated ``multigila_layout`` call with the same
    seed regardless of when it joined or which siblings rode along
    (tests/test_service.py).

    ``step(order=...)`` sorts jobs by the given key before picking lanes
    and ``max_lanes`` truncates the wave to the most urgent ones — the
    hook serve/engine.py uses to honor per-request priorities and
    deadlines (lanes past the cap are *preempted*: they simply do not ride
    until capacity frees). Pending ``RefineRequest``s are staged once per
    level and cached across preempted waves, so placement never reruns.
    """

    def __init__(self, cfg: LayoutConfig | None = None, *,
                 lanes_cap: int | None = None, dispatch=None,
                 tracer: "obs_trace.Tracer | None" = None,
                 clock: Clock | None = None):
        cfg = cfg or LayoutConfig()
        if cfg.driver != "multigila":
            raise ValueError("WaveScheduler supports driver='multigila' "
                             f"only, got {cfg.driver!r}")
        if not cfg.bucketing:
            raise ValueError("WaveScheduler requires cfg.bucketing=True")
        self.cfg = cfg
        self.lanes_cap = lanes_cap
        # tracer/clock seam: the engine passes ITS clock so wave spans and
        # straggler timing share the sim's virtual frame (a VirtualClock
        # never advances inside step(), so sim wave dt is exactly 0 and
        # straggler detection can never fire nondeterministically)
        self.tracer = tracer if tracer is not None else obs_trace.get_tracer()
        self.clock = clock or SystemClock()
        self._wave_timer = StepTimer()
        self._dispatch = dispatch or (lambda reqs: bucketing.refine_level_many(
            reqs, ideal_len=cfg.ideal_len, rep_const=cfg.rep_const,
            lanes_cap=lanes_cap))
        self._jobs: list[GraphJob] = []
        self._staged: dict = {}       # _ComponentTask -> RefineRequest
        self._next_uid = 0
        self.waves = 0
        self.lane_dispatches = 0
        self.straggler_waves = 0

    def admit(self, edges, n: int, *, seed: int | None = None,
              engine: str | None = None, weights=None) -> GraphJob:
        """Add one graph to the lane set (legal at any wave boundary).

        ``engine`` overrides the scheduler config's refinement engine for
        THIS job only: a wave may mix engines — grouping is by
        ``bucketing.group_key``, which leads with the engine id, so mixed
        waves dispatch one batched program per (engine, shape bucket) and
        lanes stay bit-identical to dedicated runs. ``weights`` are the
        job's per-edge weights."""
        cfg = self.cfg
        if seed is not None:
            cfg = dataclasses.replace(cfg, seed=int(seed))
        if engine is not None:
            cfg = dataclasses.replace(cfg, engine=engine)
        # lane labels derive from the scheduler-local admission rank, not
        # any global counter — two fresh runs of the same script produce
        # identical labels (trace replay determinism, tests/test_obs.py)
        job = GraphJob(edges, n, cfg, uid=self._next_uid, weights=weights)
        self._next_uid += 1
        self._jobs.append(job)
        return job

    def remove(self, job: GraphJob) -> None:
        """Cancel a job: free its lanes and drop its staged requests.
        Sibling lanes are untouched (their results stay bit-identical)."""
        job.cancelled = True
        for t in job.tasks:
            self._staged.pop(t, None)
        if job in self._jobs:
            self._jobs.remove(job)

    @property
    def active(self) -> bool:
        return any(not j.done for j in self._jobs)

    def lanes_live(self) -> int:
        return sum(j.lanes for j in self._jobs)

    def step(self, *, order=None, max_lanes: int | None = None) -> dict:
        """Dispatch one wave; returns ``{"lanes", "groups", "preempted"}``
        where ``groups`` lists ``(group_key, member_count)`` in dispatch
        order and ``preempted`` counts lanes held past this wave by the cap.

        ``order``: job sort key (ascending; stable, so admit order breaks
        ties). ``max_lanes``: only the first that-many lanes ride."""
        self._jobs = [j for j in self._jobs if not j.done]
        jobs = (sorted(self._jobs, key=order) if order is not None
                else list(self._jobs))
        pend = []
        for j in jobs:
            for t in j.tasks:
                if t.done:
                    continue
                r = self._staged.get(t)
                if r is None:
                    r = self._staged[t] = t.next_request()
                pend.append((t, r))
        preempted = 0
        if max_lanes is not None:
            preempted = max(0, len(pend) - max_lanes)
            pend = pend[:max_lanes]
        groups: dict = {}
        for t, r in pend:
            groups.setdefault(bucketing.group_key(r), []).append((t, r))
        tw0 = self.clock.now()
        ginfo = []
        for key, members in groups.items():
            tg0 = self.clock.now()
            outs = self._dispatch([r for _, r in members])
            tg1 = self.clock.now()
            gid = self.tracer.complete("refine.group", tg0, tg1, cat="wave",
                                       bucket=key, lanes=len(members))
            for (t, r), pos in zip(members, outs):
                del self._staged[t]
                t.feed(pos)
                # per-lane share of the fused group dispatch: same bounds
                # as the group span, annotated with level/lane so phase
                # sums and host/device overlap are computable per lane
                self.tracer.complete("refine", tg0, tg1, cat="wave",
                                     parent=gid, level=r.level, lane=r.lane)
            GROUP_LANES_HIST.observe(len(members))
            ginfo.append((key, len(members)))
        if pend:
            tw1 = self.clock.now()
            self.waves += 1
            self.lane_dispatches += len(pend)
            WAVES_TOTAL.inc()
            LANE_DISPATCHES_TOTAL.inc(len(pend))
            WAVE_GROUPS_HIST.observe(len(ginfo))
            if preempted:
                PREEMPTED_LANES_TOTAL.inc(preempted)
            self.tracer.complete("wave", tw0, tw1, cat="wave",
                                 lanes=len(pend), groups=ginfo,
                                 preempted=preempted)
            if self._wave_timer.record(tw1 - tw0):
                self.straggler_waves += 1
                STRAGGLER_WAVES_TOTAL.inc()
                self.tracer.instant("wave.straggler", ts=tw1, cat="wave",
                                    dur=tw1 - tw0, ewma=self._wave_timer.ewma)
        return {"lanes": len(pend), "groups": ginfo, "preempted": preempted}

    def drain(self) -> None:
        """Step until every admitted job has finished."""
        while self.step()["lanes"]:
            pass


def multigila_layout_many(graphs: list, cfg: LayoutConfig | None = None,
                          *, seeds: list | None = None,
                          engines: list | None = None,
                          weights: list | None = None) -> list:
    """Batched multi-graph Multi-GiLA: lay out B graphs through grouped,
    vmapped per-level refinement steps (one device program per level wave).

    ``graphs`` is a list of ``(edges, n)`` pairs; ``seeds`` / ``engines`` /
    ``weights`` optionally override ``cfg.seed`` / ``cfg.engine`` / the
    per-edge weights per graph (mixed-engine batches group by engine in
    the bucket key). Returns ``[(pos[n, 2], LayoutStats)]``
    in input order. Coarsening and placement run per component (they are
    host-synchronized and cheap); every wave of per-level refinements is
    grouped by shape bucket (core/bucketing.py:group_key) and dispatched as
    ONE vmapped cached step, so a warm-bucket request compiles nothing and
    each per-graph result is bit-identical to ``multigila_layout`` run one
    graph at a time (tests/test_many.py, benchmarks/many_bench.py).

    This is the one-shot convenience wrapper over ``WaveScheduler``: admit
    everything, drain, collect. The continuous-batching layout service
    (serve/engine.py) drives the same scheduler with mid-flight admission.
    """
    cfg = cfg or LayoutConfig()
    for name, lst in (("seeds", seeds), ("engines", engines),
                      ("weights", weights)):
        if lst is not None and len(lst) != len(graphs):
            raise ValueError(f"{name} must match graphs in length")
    sched = WaveScheduler(cfg)     # validates driver/bucketing
    jobs = [sched.admit(edges, n,
                        seed=None if seeds is None else int(seeds[k]),
                        engine=None if engines is None else engines[k],
                        weights=None if weights is None else weights[k])
            for k, (edges, n) in enumerate(graphs)]
    sched.drain()
    return [job.result() for job in jobs]


def multigila_layout(edges: np.ndarray, n: int,
                     cfg: LayoutConfig | None = None, *,
                     export: bool = False, weights=None):
    """Full pipeline on a possibly-disconnected graph. Returns pos[n,2] (and
    the merged HierarchyExport when ``export=True`` — the serving layer's
    input, see serve/tiles.py). ``weights`` (float[m], optional) are the
    per-edge weights (see ``layout_component``)."""
    cfg = cfg or LayoutConfig()
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    if weights is not None:
        weights = np.asarray(weights, np.float32).reshape(-1)
    # the root span: every span of this layout carries its layout_id
    with obs_trace.root("layout", cat="host", n=n, m=len(edges)):
        with obs_trace.span("layout.components", cat="host", n=n):
            labels = connected_components(edges, n)
            comps = np.unique(labels)
        stats = LayoutStats()
        if len(comps) == 1:
            return layout_component(edges, n, cfg, export=export,
                                    weights=weights)

        layouts, index_maps, exports = [], [], []
        for c in comps:
            vs = np.nonzero(labels == c)[0]
            remap = np.full(n, -1, np.int64)
            remap[vs] = np.arange(vs.size)
            emask = labels[edges[:, 0]] == c
            ce = np.stack([remap[edges[emask, 0]], remap[edges[emask, 1]]], 1)
            cw = weights[emask] if weights is not None else None
            out = layout_component(ce, vs.size, cfg, export=export, weights=cw)
            p, s = out[0], out[1]
            if export:
                exports.append(out[2])
            stats.levels = max(stats.levels, s.levels)
            layouts.append(np.asarray(p))
            index_maps.append(vs)
        packed = _pack_components(layouts)
        pos = np.zeros((n, 2), np.float32)
        for vs, P in zip(index_maps, packed):
            pos[vs] = P
        if not export:
            return pos, stats
        return pos, stats, _merge_exports(exports, index_maps, edges, n, pos)
