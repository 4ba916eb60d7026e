"""``BENCHMARK.json`` and the files it names, found by name.

A cell (``workloads`` entry) names a configuration and a traffic mix; each
lives in a file of its own (``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``), each metric is a reader of its own
(``bench/metrics/<metric>.py``), and each entry into the system under
test that a configuration names is a module of its own
(``bench/entries/<entry>.py``). Adding a cell or a metric adds files and
entries; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
E2E_SOURCES = {"host_clock", "device_trace"}
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def load(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _line(s, what: str) -> list[str]:
    ok = isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s
    return [] if ok else [f"{what}: 1-200 characters on one line, no tab"]


def validate(spec: dict, root: Path = ROOT) -> list[str]:
    """Every breach of the benchmark's contract that can be seen from the
    file alone (names, units, keys, references); empty when it is sound."""
    err = []
    if set(spec) != KEYS["top"]:
        err.append(f"top-level keys {sorted(spec)}")
        return err
    cmd, paths = spec["command"], spec["paths"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32):
        err.append("command: a list of 1-32 strings")
    for w in cmd:
        err += _line(w, f"command word {w!r}")
        if w.startswith("/") or ".." in w.split("/"):
            err.append(f"command word {w!r} leaves the checkout")
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16):
        err.append("paths: 1-16 directories")
    for p in paths:
        if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            err.append(f"path {p!r}")
    for w in cmd:
        if w.endswith(".py") and not any(w.startswith(p + "/") for p in paths):
            err.append(f"command names {w!r} outside paths")
    rs = spec["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        err.append(f"run_seconds {rs!r}")

    def names(entries, kind):
        seen = set()
        for e in entries:
            if set(e) - KEYS[kind] - ({"workloads"} if kind in (
                    "end_to_end", "per_layer") else set()) or \
                    not KEYS[kind] <= set(e):
                err.append(f"{kind} {e.get('name')!r}: keys {sorted(e)}")
            n = e.get("name", "")
            if not NAME.match(str(n)):
                err.append(f"{kind} name {n!r}")
            if n in seen:
                err.append(f"{kind} name {n!r} twice")
            seen.add(n)
        return seen

    cfgs = names(spec["configs"], "config")
    cells = names(spec["workloads"], "workload")
    e2e = names(spec["end_to_end"], "end_to_end")
    layer = names(spec["per_layer"], "per_layer")
    if not 1 <= len(cfgs) <= 24:
        err.append("configs: 1-24")
    if not 1 <= len(cells) <= 24:
        err.append("workloads: 1-24")
    if not 1 <= len(e2e) <= 16 or "setup_s" not in e2e:
        err.append("end_to_end: 1-16 metrics, setup_s among them")
    if not 1 <= len(layer) <= 128:
        err.append("per_layer: 1-128 metrics")
    if e2e & layer:
        err.append(f"metric names in both lists: {sorted(e2e & layer)}")

    files = set()
    for c in spec["configs"]:
        err += _line(c["source"], f"config {c['name']} source")
        err += _line(c["why"], f"config {c['name']} why")
        f = c["file"]
        if not any(f.startswith(p + "/") for p in paths) or f in files:
            err.append(f"config {c['name']} file {f!r}")
        files.add(f)
        if not (root / f).is_file():
            err.append(f"config {c['name']}: no file {f}")
        if not isinstance(c["reduced"], list) or len(c["reduced"]) > 16 or \
                not all(NAME.match(k) for k in c["reduced"]):
            err.append(f"config {c['name']} reduced {c['reduced']!r}")
    pairs, four = set(), 0
    used = set()
    for w in spec["workloads"]:
        err += _line(w["why"], f"workload {w['name']} why")
        if w["config"] not in cfgs:
            err.append(f"workload {w['name']}: no config {w['config']!r}")
        used.add(w["config"])
        if not NAME.match(str(w["traffic"])):
            err.append(f"workload {w['name']} traffic {w['traffic']!r}")
        if (w["config"], w["traffic"]) in pairs:
            err.append(f"workload {w['name']}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            err.append(f"workload {w['name']} chips {w['chips']!r}")
        four += w["chips"] == 4
    if cfgs - used:
        err.append(f"configs used by no cell: {sorted(cfgs - used)}")
    if four > max(1, len(cells) // 2):
        err.append(f"{four} cells on 4 chips")

    reported = {c: set() for c in cells}
    for m in spec["end_to_end"] + spec["per_layer"]:
        kind = "end_to_end" if m["name"] in e2e else "per_layer"
        if not UNIT.match(str(m["unit"])):
            err.append(f"{m['name']} unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            err.append(f"{m['name']} better {m['better']!r}")
        allowed = E2E_SOURCES if kind == "end_to_end" else SOURCES
        if m["source"] not in allowed:
            err.append(f"{m['name']} source {m['source']!r}")
        for c in m.get("workloads", []):
            if c not in cells:
                err.append(f"{m['name']}: no cell {c!r}")
        if kind == "end_to_end":
            b = m["bound"]
            if not (isinstance(b, (int, float)) and 0.01 <= b <= 0.25):
                err.append(f"{m['name']} bound {b!r}")
            for c in m.get("workloads", sorted(cells)):
                reported.setdefault(c, set()).add(m["name"])
        if not (root / "bench" / "metrics" / f"{m['name']}.py").is_file():
            err.append(f"{m['name']}: no reader bench/metrics/{m['name']}.py")
    for m in spec["per_layer"]:
        err += _line(m["layer"], f"{m['name']} layer")
        if m["moves"] not in e2e:
            err.append(f"{m['name']} moves {m['moves']!r}: not end-to-end")
        for c in m.get("workloads", []):
            if m["moves"] not in reported.get(c, ()):
                err.append(f"{m['name']}: cell {c} does not report "
                           f"{m['moves']}")
    for c in cells:
        if "setup_s" not in reported[c] or len(reported[c]) < 2:
            err.append(f"cell {c}: needs setup_s and one more end-to-end")
        if not metrics_of(spec, c, trace=True):
            err.append(f"cell {c}: no per-layer metric")
    return err


def cell(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(spec: dict, name: str, root: Path = ROOT) -> dict:
    for c in spec["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str, root: Path = ROOT) -> dict:
    return json.loads((root / "bench" / "traffic" / f"{name}.json")
                      .read_text())


def metrics_of(spec: dict, cell_name: str, trace: bool) -> list[dict]:
    """The cell's metrics: its end-to-end ones, or with ``trace`` its
    per-layer ones. An end-to-end metric without ``workloads`` is in every
    cell; a per-layer one without it is in every cell that reports the
    end-to-end metric it moves."""
    e2e = [m for m in spec["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    if not trace:
        return e2e
    moves = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in moves)]


def _module(kind: str, name: str, root: Path):
    path = root / "bench" / kind / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def reader(name: str, root: Path = ROOT):
    """The ``read(run) -> float | None`` function of metric ``name``."""
    return _module("metrics", name, root).read


def entry(name: str, root: Path = ROOT):
    """The ``System`` class of the entry ``name``
    (``bench/entries/<name>.py``), which a configuration names."""
    return _module("entries", name, root).System
