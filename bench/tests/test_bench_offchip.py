"""``bench/run.py`` measures nothing off the chip: with JAX held to the CPU
it exits non-zero and prints no result line, from the repository and from
a directory that holds only ``BENCHMARK.json`` and the benchmark."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


def _run(cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "delaunay_n17.offline",
         "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("where", ["repo", "benchmark_only"])
def test_refuses_to_measure_off_the_chip(tmp_path, where):
    cwd = ROOT
    if where == "benchmark_only":
        cwd = tmp_path / "checkout"
        shutil.copytree(ROOT / "bench", cwd / "bench",
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(ROOT / "BENCHMARK.json", cwd)
    res = _run(cwd)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "no TPU" in res.stderr
