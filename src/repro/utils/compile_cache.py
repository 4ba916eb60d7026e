"""JAX's persistent compilation cache for this repository's entry points.

A fresh process otherwise recompiles every refine step, merger superstep
and Pallas kernel it runs. Entry points (``chip_smoke.py``, the launchers
under ``repro.launch``, the benchmarks) call ``enable_compile_cache()``
first thing, before anything compiles.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the fixed cache directory inside the checkout (git-ignored). A fixed path
#: matters: the path is part of what JAX's cache looks up, so a directory
#: built from a temp name, a PID or the time would never hit.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed here. Otherwise the cache lives in ``DEFAULT_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
