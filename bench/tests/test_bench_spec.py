"""BENCHMARK.json against the benchmark's contract, and the harness's
lookup of configurations, traffic mixes and metric readers by name."""
from __future__ import annotations

import copy
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib import spec as S          # noqa: E402


@pytest.fixture(scope="module")
def bench():
    return S.load()


def test_benchmark_json_is_sound(bench):
    assert S.validate(bench) == []


def test_names_and_units_use_allowed_characters(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    for e in bench["configs"] + bench["workloads"] + metrics:
        assert S.NAME.match(e["name"]), e["name"]
    for m in metrics:
        assert S.UNIT.match(m["unit"]), m["unit"]


def test_every_layer_metric_moves_an_end_to_end_metric_of_its_cells(bench):
    for m in bench["per_layer"]:
        for c in m["workloads"]:
            reported = {e["name"] for e in S.metrics_of(bench, c, False)}
            assert m["moves"] in reported, (m["name"], c)


@pytest.mark.parametrize("breach", [
    ("unit", "tokens per second"),
    ("name", "bad name"),
    ("moves", "coarsen_s.offline"),
    ("workloads", ["no.such_cell"]),
    ("source", "guess"),
])
def test_validator_refuses_a_breach(bench, breach):
    key, value = breach
    bad = copy.deepcopy(bench)
    bad["per_layer"][0][key] = value
    assert S.validate(bad) != []


def test_validator_refuses_a_loose_bound_and_a_missing_setup(bench):
    bad = copy.deepcopy(bench)
    bad["end_to_end"][0]["bound"] = 0.3
    assert any("bound" in e for e in S.validate(bad))
    bad = copy.deepcopy(bench)
    bad["end_to_end"] = [m for m in bad["end_to_end"]
                         if m["name"] != "setup_s"]
    assert any("setup_s" in e for e in S.validate(bad))


def test_each_cell_finds_its_config_traffic_and_readers(bench):
    for c in bench["workloads"]:
        conf = S.config(bench, c["config"])
        assert conf["sizes"] and isinstance(S.entry(conf["entry"]), type)
        assert S.traffic(c["traffic"])["loop"] == "closed"
        for trace in (False, True):
            for m in S.metrics_of(bench, c["name"], trace):
                assert callable(S.reader(m["name"]))


def test_new_files_are_found_without_editing_any(tmp_path, bench):
    """A later cell adds a configuration, a mix, an entry and a metric as
    files and entries; the harness finds them by name."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    new = copy.deepcopy(bench)
    conf = json.loads((ROOT / "bench/configs/delaunay_n17.json").read_text())
    conf["sizes"] = [32768]
    conf["entry"] = "echo"
    (tmp_path / "bench/configs/delaunay_n15.json").write_text(
        json.dumps(conf))
    (tmp_path / "bench/traffic/paired.json").write_text(json.dumps(
        {"loop": "closed", "pool_per_size": 9}))
    (tmp_path / "bench/entries/echo.py").write_text(
        "class System:\n    def __init__(self, conf):\n"
        "        self.conf = conf\n")
    (tmp_path / "bench/metrics/queue_s.paired.py").write_text(
        "def read(run):\n    return 4.5\n")
    new["configs"].append({"name": "delaunay_n15", "source": "x",
                           "file": "bench/configs/delaunay_n15.json",
                           "reduced": [], "why": "y"})
    new["workloads"].append({"name": "delaunay_n15.paired",
                             "config": "delaunay_n15", "traffic": "paired",
                             "chips": 1, "why": "z"})
    layout_s = next(m for m in new["end_to_end"] if m["name"] == "layout_s")
    layout_s["workloads"].append("delaunay_n15.paired")
    new["per_layer"].append({"name": "queue_s.paired", "unit": "s",
                             "better": "lower", "source": "program_span",
                             "layer": "scheduler", "moves": "layout_s",
                             "workloads": ["delaunay_n15.paired"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    loaded = S.load(tmp_path)
    assert S.validate(loaded, tmp_path) == []
    assert S.config(loaded, "delaunay_n15", tmp_path)["sizes"] == [32768]
    assert S.traffic("paired", tmp_path)["pool_per_size"] == 9
    echo = S.entry(S.config(loaded, "delaunay_n15", tmp_path)["entry"],
                   tmp_path)
    assert echo({"sizes": [1]}).conf == {"sizes": [1]}
    names = [m["name"] for m in S.metrics_of(loaded, "delaunay_n15.paired",
                                             True)]
    assert names == ["queue_s.paired"]
    assert S.reader("queue_s.paired", tmp_path)(None) == 4.5
