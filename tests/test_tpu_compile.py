"""Compile the Pallas kernels for a described TPU v5e chip, at the shapes a
160,000-vertex grid hierarchy produces (bigrun ``small``: exact levels up to
2048 vertices, neighbor levels up to 32768 with K from 32 to 256, a grid
level of 262144 padded vertices with G = 128, cap = 48).

Nothing runs: the chip's compiler accepts or refuses each program here, on
the CPU. The topology is described inside a fixture, never at import, so
every pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU library.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.nbody.kernel import nbody_pallas
from repro.kernels.neighbor_force.kernel import neighbor_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:        # no TPU compiler can be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def S(topo):
    """ShapeDtypeStruct factory placed on one described chip; the
    persistent compile cache stays off (its entries cannot be read back
    without a chip)."""
    one = SingleDeviceSharding(topo.devices[0])
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield lambda *shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one)
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile_text(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


@pytest.mark.parametrize("n", [256, 2048])
def test_exact_kernel_compiles(S, n):
    text = _compile_text(lambda r, s, c: nbody_pallas(r, s, c, 1.0, 1e-3),
                         S(2, n), S(3, n), S())
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("K", [32, 64, 128, 256])
def test_neighbor_kernel_compiles(S, K):
    text = _compile_text(
        lambda r, p, c: neighbor_pallas(r, p, c, 1.0, 1e-3),
        S(2, 1, 32768), S(3, K, 32768), S())
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("cap,groups", [(48, 16384), (1, 4096)],
                         ids=["cells", "sharded_rows"])
def test_grid_near_kernel_compiles(S, cap, groups):
    """One group per cell (single device), or per local vertex with cap = 1
    (the sharded near field, core/distributed.py)."""
    text = _compile_text(
        lambda r, p, c: neighbor_pallas(r, p, c, 1.0, 1e-3),
        S(2, cap, groups), S(3, 9 * 48, groups), S())
    assert "tpu_custom_call" in text


def test_grid_far_kernel_compiles(S):
    text = _compile_text(lambda r, s, c: nbody_pallas(r, s, c, 1.0, 1e-3),
                         S(2, 262144), S(3, 16384), S())
    assert "tpu_custom_call" in text


def test_batched_wave_kernels_compile(S):
    """The wave scheduler vmaps the kernels with a per-lane repulsion
    constant (the stress engine's annealed α·C); the kernels take the lanes
    as their leading grid axis."""
    far = jax.vmap(lambda r, s, c: nbody_pallas(r, s, c, 1.0, 1e-3))
    near = jax.vmap(lambda r, p, c: neighbor_pallas(r, p, c, 1.0, 1e-3))
    assert "tpu_custom_call" in _compile_text(far, S(8, 2, 256),
                                              S(8, 3, 256), S(8))
    assert "tpu_custom_call" in _compile_text(near, S(4, 2, 48, 4096),
                                              S(4, 3, 432, 4096), S(4))


def test_exact_refine_step_compiles(S, monkeypatch):
    """A whole cached refine step (core/engine.py) of an exact-mode level,
    traced with the compiled-kernel backend."""
    from repro.core import engine
    monkeypatch.setenv("REPRO_PALLAS", "pallas")
    n, m, K = 2048, 8192, 1
    step = engine.get_engine("gila").build_refine("exact", 0, 0)
    i32 = jnp.int32
    text = step.lower(
        S(n, 2), S(m, dt=i32), S(m, dt=i32), S(n, dt=bool), S(m, dt=bool),
        S(n), S(m), S(n, K, dt=i32), S(n, K, dt=bool), S(dt=i32), S(2),
        S(3)).compile().as_text()
    assert "tpu_custom_call" in text


def _compile_sharded_step(topo, mode, n_pad, m_pad, cap):
    """The superstep of ``driver="multigila_dist"`` for one level, compiled
    on a (4, 1) mesh of described chips."""
    import numpy as np
    from jax.sharding import Mesh
    from repro.core.distributed import layout_train_step, layout_step_specs
    from repro.kernels.grid_force.ops import choose_grid
    mesh = Mesh(np.array(topo.devices[:4]).reshape(4, 1), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    G, cc = choose_grid(n_pad) if mode == "grid" else (0, 0)
    step, sh = layout_train_step(mesh, n_pad, m_pad, cap, mode=mode,
                                 grid_dim=G, cell_cap=cc)
    specs = layout_step_specs(n_pad, m_pad, cap, mode=mode)
    kind = dict(pos="pos", w="w", nbr_idx="nbr_idx", src="edge",
                dst_local="edge", emask="edge", ewt="edge", params="scalar",
                temp="scalar")
    args = [jax.ShapeDtypeStruct(specs[k].shape, specs[k].dtype,
                                 sharding=sh[v]) for k, v in kind.items()]
    return jax.jit(step).lower(*args).compile()


def test_sharded_grid_step_compiles_on_four_chips(topo, S, monkeypatch):
    """The grid-mode superstep of ``driver="multigila_dist"`` on a (4, 1)
    mesh of described chips: the Pallas near and far kernels inside the
    vma-checked ``shard_map``."""
    monkeypatch.setenv("REPRO_PALLAS", "pallas")
    text = _compile_sharded_step(topo, "grid", 8192, 32768, 1).as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("mode,n_pad,m_pad,cap", [
    ("grid", 262144, 1 << 20, 1), ("neighbor", 32768, 1 << 18, 256)],
    ids=["grid", "neighbor"])
def test_sharded_steps_compile_at_paper_scale(topo, S, monkeypatch, mode,
                                              n_pad, m_pad, cap):
    """The sharded supersteps of grid_400x400 on four chips: its finest
    level (160,000 vertices padded to 2^18, 638,400 half-edges partitioned
    into four pow2 blocks of 2^18, G = 128, cap = 48) and its largest
    neighbor level (up to 2^15 vertices, K up to 256). Each must fit one
    chip's 16 GiB; the grid step runs the Pallas kernels."""
    monkeypatch.setenv("REPRO_PALLAS", "pallas")
    compiled = _compile_sharded_step(topo, mode, n_pad, m_pad, cap)
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16 << 30, used
    if mode == "grid":
        assert "tpu_custom_call" in compiled.as_text()


def test_grid_repulsion_has_no_row_gather_loop(S, monkeypatch):
    """The grid op at n = 16,384 (G 37, cap 48): the near field's rows come
    from the partner planes, so no gather of the bucket slots' x, y is
    lowered to a loop of one trip per slot (a ``while`` carrying a
    ``s32[nc*cap,2]`` point index, under ``grid.near``)."""
    from repro.kernels.grid_force.ops import choose_grid, grid_repulsion
    monkeypatch.setenv("REPRO_PALLAS", "pallas")
    n = 16384
    G, cap = choose_grid(n)
    assert (G, cap) == (37, 48)
    text = _compile_text(
        lambda p, m, v: grid_repulsion(p, m, v, 1.0, 1.0, 1e-3,
                                       grid_dim=G, cell_cap=cap),
        S(n, 2), S(n), S(n, dt=bool))
    assert "tpu_custom_call" in text
    loops = [ln for ln in text.splitlines() if " while(" in ln]
    assert loops
    near = [ln for ln in loops if "grid.near/gather" in ln]
    assert not near, near
    point_index = f"s32[{G * G * cap},2]"
    assert not [ln for ln in loops if point_index in ln], point_index


def test_stress_grid_step_at_the_cells_finest_level(S, monkeypatch):
    """The maxent-stress engine's cached grid step at the finest level of
    ``delaunay_n17_stress.offline`` (131,072 vertices, 1,048,576 half-edge
    slots, G 105, cap 48): both Pallas kernels, and no gather of the bucket
    slots' x, y lowered to a loop of one trip per slot (a ``while`` with a
    ``s32[nc*cap,2]`` point index, or one under ``grid.near``)."""
    from repro.core import engine
    from repro.kernels.grid_force.ops import choose_grid
    monkeypatch.setenv("REPRO_PALLAS", "pallas")
    n, m, K = 131072, 1 << 20, 1
    G, cap = choose_grid(n)
    assert (G, cap) == (105, 48)
    step = engine.get_engine("stress").build_refine("grid", G, cap)
    i32 = jnp.int32
    text = step.lower(
        S(n, 2), S(m, dt=i32), S(m, dt=i32), S(n, dt=bool), S(m, dt=bool),
        S(n), S(m), S(n, K, dt=i32), S(n, K, dt=bool), S(dt=i32), S(4),
        S(3)).compile().as_text()
    assert "%nbody_pallas" in text and "%neighbor_pallas" in text
    loops = [ln for ln in text.splitlines() if " while(" in ln]
    assert loops
    assert not [ln for ln in loops if "grid.near/gather" in ln]
    point_index = f"s32[{G * G * cap},2]"
    assert not [ln for ln in loops if point_index in ln], point_index


_HLO_RESULT = re.compile(r"= \w+\[([\d,]*)\]\{([\d,]*)")


def _corrections_ops(text):
    """The ``gather`` and ``while`` instructions under ``grid.corrections``,
    and the minor dimension of each gather's result (its layout's first
    entry)."""
    gathers, loops = [], []
    for ln in text.splitlines():
        if "grid.corrections" not in ln:
            continue
        if " while(" in ln:
            loops.append(ln)
        elif " gather(" in ln:
            dims, layout = _HLO_RESULT.search(ln).groups()
            minor = int(dims.split(",")[int(layout.split(",")[0])])
            gathers.append((minor, ln.strip()[:160]))
    return gathers, loops


@pytest.mark.parametrize("n,lanes", [(131072, 0), (16384, 8)],
                         ids=["n131072", "batched8_n16384"])
def test_grid_corrections_gather_on_lane_major_planes(S, monkeypatch, n,
                                                      lanes):
    """``far_corrections`` at the finest level of the ``offline`` cells
    (131,072 vertices, G 105, cap 48) and in the 8-lane batched step at
    16,384 (G 37): the per-cell quantities reach the vertices through at
    most two gathers, each cell's 3x3 neighborhood and then one per
    vertex, each with the cells or the vertices on the lane axis, never 2
    or 9 there (a result with 2 or 9 minor is fetched element by
    element), and no loop."""
    from repro.kernels.grid_force.ops import choose_grid, grid_repulsion
    monkeypatch.setenv("REPRO_PALLAS", "pallas")
    G, cap = choose_grid(n)
    assert (G, cap) == {131072: (105, 48), 16384: (37, 48)}[n]
    f = lambda p, m, v: grid_repulsion(p, m, v, 1.0, 1.0, 1e-3,
                                       grid_dim=G, cell_cap=cap)
    specs = (S(n, 2), S(n), S(n, dt=bool))
    if lanes:
        f = jax.vmap(f)
        specs = (S(lanes, n, 2), S(lanes, n), S(lanes, n, dt=bool))
    gathers, loops = _corrections_ops(_compile_text(f, *specs))
    assert 1 <= len(gathers) <= 2, gathers
    assert not [g for g in gathers if g[0] in (2, 9)], gathers
    assert not loops, loops
