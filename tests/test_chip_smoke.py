"""chip_smoke.py off the chip: its phases at a tiny size on the CPU with the
Pallas kernels in interpret mode, its refusal to run without a TPU, and the
compile-cache helper every entry point calls."""
import importlib.util
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.core import LayoutConfig

ROOT = pathlib.Path(__file__).resolve().parents[1]
TINY = dict(exact_threshold=64, grid_threshold=256, coarsest_iters=20,
            finest_iters=10)


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def cs(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    return _load_chip_smoke()


def test_kernel_phase_tiny(cs):
    errs = cs.phase_kernels("interpret", n=300, K=16, G=4, cap=8)
    assert set(errs) == {"exact", "neighbor", "grid_near", "grid_far"}


def test_layout_service_and_tile_phases_tiny(cs, capsys):
    """(a) drives all three repulsion modes through interpret-mode kernels,
    (b) keeps bit-parity with the dedicated driver, (c) with the NumPy
    reference resolver."""
    exp = cs.phase_layout(24, LayoutConfig(**TINY), "interpret")
    cs.phase_service((150, 180, 210), LayoutConfig(seed=0, **TINY),
                     expect_backend="interpret")
    cs.phase_tiles(exp)
    out = capsys.readouterr().out
    for tag in ("[a layout]", "[b service]", "[c tiles]"):
        assert tag in out
    assert '"grid":1' in out and "parity=bit-exact" in out


def test_stress_phase_tiny(cs, capsys):
    """(s) at a size whose finest level runs the grid mode: the cached
    stress step at the grid, neighbor and exact levels against the
    reference, through interpret-mode kernels. Its levels hold 600, 63
    and 7 vertices, so an exact threshold of 32 puts the middle one in the
    neighbor mode."""
    cfg = LayoutConfig(engine="stress", **dict(TINY, exact_threshold=32))
    errs = cs.phase_stress(600, cfg)
    assert set(errs) == {f"{m}.{i}" for m, its in cs.STRESS_ITERS.items()
                         for i in its}
    assert "[s stress]" in capsys.readouterr().out


def test_sharded_phase_tiny_on_four_virtual_devices():
    """--chips 4 path on four virtual CPU devices: arrays spread over all
    four, quality within the stated bound of the one-device driver. The
    kernels take their jnp oracles here: the Pallas interpreter cannot run
    inside a vma-checked ``shard_map`` (the compiled kernels can, see
    tests/test_tpu_compile.py)."""
    code = textwrap.dedent(f"""
        import importlib.util
        from repro.core import LayoutConfig
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        rel = cs.phase_sharded(24, 4, LayoutConfig(**{TINY!r}))
        print("REL", rel)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_PALLAS="ref",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "[dist]" in out.stdout and "devices_per_array=4" in out.stdout


@pytest.mark.parametrize("pallas,msg", [(None, "no TPU"),
                                        ("interpret", "REPRO_PALLAS")])
def test_chip_smoke_refuses_off_the_chip(pallas, msg):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("REPRO_PALLAS", None)
    if pallas is not None:
        env["REPRO_PALLAS"] = pallas
    out = subprocess.run([sys.executable, "chip_smoke.py"], env=env,
                         cwd=ROOT, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert msg in out.stderr, out.stderr[-2000:]
    assert '"ok"' not in out.stdout


def test_compile_cache_follows_env_or_fixed_checkout_path(monkeypatch):
    from repro.utils import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir is None   # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path   # fixed path
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
