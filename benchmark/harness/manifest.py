"""BENCHMARK.json and the data files it names.

A cell is found by its name and resolved to files by the names inside it —
``configs/<config>.json``, ``traffic/<traffic>.json``, and for each metric
``end_to_end/<name>.json`` or ``layer_metrics/<name>.json`` — never by an
``if`` on a name.  ``problems()`` holds the whole to the character rules of
the driver's contract, so a bad name fails a test here and not a check there.
"""

from __future__ import annotations

import copy
import json
import os
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TRAFFIC_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


def _read(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load(root: str = ROOT) -> Dict[str, Any]:
    return _read(os.path.join(root, "BENCHMARK.json"))


def merged(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    """``over`` laid on ``base``, dict by dict (the ``rehearse`` overlays)."""
    out = copy.deepcopy(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = merged(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


@dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    reader: str
    args: Dict[str, Any] = field(default_factory=dict)
    layer: str = ""
    moves: str = ""


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _applies(entry: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def _metric(entry: Dict[str, Any], directory: str, root: str) -> Metric:
    spec = _read(os.path.join(root, "benchmark", directory,
                              entry["name"] + ".json"))
    return Metric(name=entry["name"], unit=entry["unit"],
                  better=entry["better"], source=entry["source"],
                  reader=spec["reader"], args=spec.get("args", {}),
                  layer=entry.get("layer", ""), moves=entry.get("moves", ""))


def _every_metric(entries: List[Dict[str, Any]], directory: str,
                  root: str) -> List[Dict[str, Any]]:
    """BENCHMARK.json's entries and, for each metric file of the directory
    that has no entry yet, the entry it proposes for itself (``proposed``: a
    metric waiting for a benchmark PR to admit it)."""
    out = list(entries)
    have = {e["name"] for e in entries}
    folder = os.path.join(root, "benchmark", directory)
    for f in sorted(os.listdir(folder)):
        name, ext = os.path.splitext(f)
        if ext == ".json" and name not in have:
            proposed = _read(os.path.join(folder, f)).get("proposed")
            if proposed:
                out.append(dict(proposed, name=name))
    return out


def cell(manifest: Dict[str, Any], name: str, rehearse: bool = False,
         root: str = ROOT) -> Cell:
    """The cell ``name`` of BENCHMARK.json; or, for trying a cell before it
    has an entry, ``<config>+<traffic>[+<chips>]`` straight from the data
    files, with every metric whose reader finds something to read."""
    entry = next((w for w in manifest["workloads"] if w["name"] == name),
                 None)
    listed = entry is not None
    if not listed and "+" in name:
        config_name, traffic_name, *chips = name.split("+")
        entry = {"name": name, "config": config_name,
                 "traffic": traffic_name,
                 "chips": int(chips[0]) if chips else 1}
    if entry is None:
        known = ", ".join(w["name"] for w in manifest["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")
    config = _read(os.path.join(root, "benchmark", "configs",
                                entry["config"] + ".json"))
    traffic = _read(os.path.join(root, "benchmark", "traffic",
                                 entry["traffic"] + ".json"))
    if rehearse:
        config = merged(config, config.get("rehearse", {}))
        traffic = merged(traffic, traffic.get("rehearse", {}))
    e2e_entries, layer_entries = manifest["end_to_end"], manifest["per_layer"]
    if not listed:
        e2e_entries = _every_metric(e2e_entries, "end_to_end", root)
        layer_entries = _every_metric(layer_entries, "layer_metrics", root)
    e2e = [_metric(m, "end_to_end", root) for m in e2e_entries
           if _applies(m, name) or not listed]
    moved = {m.name for m in e2e}
    # a per-layer metric is reported only where the metric it moves is
    layer = [_metric(m, "layer_metrics", root) for m in layer_entries
             if (_applies(m, name) or not listed) and m["moves"] in moved]
    return Cell(name=name, chips=entry["chips"], config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer)


def problems(manifest: Dict[str, Any], root: str = ROOT) -> List[str]:
    """Every breach of the contract's static rules that can be seen without
    a run; empty when the manifest is sound."""
    bad: List[str] = []

    def name_ok(what: str, value: Any) -> None:
        if not isinstance(value, str) or not NAME_RE.match(value):
            bad.append(f"{what}: {value!r} is not a name")

    def line_ok(what: str, value: Any) -> None:
        if not isinstance(value, str) or not 1 <= len(value) <= 200 \
                or "\n" in value or "\t" in value:
            bad.append(f"{what}: not one line of 1..200 characters")

    def exists(what: str, rel: str) -> None:
        if not PATH_RE.match(rel) or rel.startswith("/") \
                or ".." in rel.split("/"):
            bad.append(f"{what}: {rel!r} is not a path inside the repo")
        elif not os.path.isfile(os.path.join(root, rel)):
            bad.append(f"{what}: {rel} does not exist")
        elif not any(rel == p or rel.startswith(p.rstrip("/") + "/")
                     for p in manifest["paths"]):
            bad.append(f"{what}: {rel} is outside paths")

    if set(manifest) != TOP_KEYS:
        bad.append(f"top-level keys {sorted(manifest)} != {sorted(TOP_KEYS)}")
        return bad
    if not 1 <= len(manifest["paths"]) <= 16:
        bad.append("paths: 1 to 16 directories")
    for p in manifest["paths"]:
        if not PATH_RE.match(p) or p.startswith("/") or ".." in p.split("/"):
            bad.append(f"paths: {p!r}")
        elif not os.path.isdir(os.path.join(root, p)):
            bad.append(f"paths: {p} is not a directory")
    if not 1 <= len(manifest["command"]) <= 32:
        bad.append("command: 1 to 32 words")
    for word in manifest["command"]:
        line_ok("command", word)
    if not (isinstance(manifest["run_seconds"], int)
            and 1 <= manifest["run_seconds"] <= 51):
        bad.append("run_seconds: a whole number from 1 to 51")

    configs = {c.get("name"): c for c in manifest["configs"]}
    if len(configs) != len(manifest["configs"]):
        bad.append("configs: a name appears twice")
    files = [c.get("file") for c in manifest["configs"]]
    if len(set(files)) != len(files):
        bad.append("configs: a file appears twice")
    for c in manifest["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config {c.get('name')}: keys {sorted(c)}")
            continue
        name_ok("config name", c["name"])
        line_ok(f"config {c['name']} source", c["source"])
        line_ok(f"config {c['name']} why", c["why"])
        exists(f"config {c['name']} file", c["file"])
        if c["file"] != f"benchmark/configs/{c['name']}.json":
            bad.append(f"config {c['name']}: its file is found by its name, "
                       f"benchmark/configs/{c['name']}.json")
        if len(c["reduced"]) > 16:
            bad.append(f"config {c['name']}: more than 16 reduced keys")
        for key in c["reduced"]:
            name_ok(f"config {c['name']} reduced", key)

    cells = manifest["workloads"]
    if not 2 <= len(cells) <= 24:
        bad.append("workloads: 2 to 24 cells")
    if len({w.get("name") for w in cells}) != len(cells):
        bad.append("workloads: a name appears twice")
    if len({(w.get("config"), w.get("traffic")) for w in cells}) \
            != len(cells):
        bad.append("workloads: a pair of config and traffic appears twice")
    four = sum(1 for w in cells if w.get("chips") == 4)
    if four > max(1, len(cells) // 4):
        bad.append(f"workloads: {four} cells ask for 4 chips")
    for w in cells:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload {w.get('name')}: keys {sorted(w)}")
            continue
        name_ok("workload name", w["name"])
        name_ok(f"workload {w['name']} traffic", w["traffic"])
        line_ok(f"workload {w['name']} why", w["why"])
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']}: chips {w['chips']}")
        if w["config"] not in configs:
            bad.append(f"workload {w['name']}: no config {w['config']}")
        exists(f"workload {w['name']} traffic",
               f"benchmark/traffic/{w['traffic']}.json")
    for c in configs:
        if not any(w.get("config") == c for w in cells):
            bad.append(f"config {c}: used by no cell")

    cell_names = {w.get("name") for w in cells}
    e2e = manifest["end_to_end"]
    layer = manifest["per_layer"]
    if not 1 <= len(e2e) <= 16 or not 1 <= len(layer) <= 128:
        bad.append("end_to_end: 1 to 16 metrics; per_layer: 1 to 128")
    names = [m.get("name") for m in e2e + layer]
    if len(set(names)) != len(names):
        bad.append("metrics: a name appears twice")
    e2e_names = {m.get("name") for m in e2e}
    if "setup_s" not in e2e_names:
        bad.append("end_to_end: no setup_s")
    for m in e2e + layer:
        is_e2e = m in e2e
        want = ({"name", "unit", "better", "bound", "source"} if is_e2e
                else {"name", "unit", "better", "source", "layer", "moves"})
        if set(m) - {"workloads"} != want:
            bad.append(f"metric {m.get('name')}: keys {sorted(m)}")
            continue
        name_ok("metric name", m["name"])
        if not UNIT_RE.match(m["unit"]):
            bad.append(f"metric {m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"metric {m['name']}: better {m['better']!r}")
        if m["source"] not in SOURCES or (
                is_e2e and m["source"] not in ("host_clock", "device_trace")):
            bad.append(f"metric {m['name']}: source {m['source']!r}")
        if is_e2e and not 0.01 <= m["bound"] <= 0.1:
            bad.append(f"metric {m['name']}: bound {m['bound']}")
        if not is_e2e:
            line_ok(f"metric {m['name']} layer", m["layer"])
            if m["moves"] not in e2e_names:
                bad.append(f"metric {m['name']}: moves {m['moves']!r}")
        for w in m.get("workloads", ()):
            if w not in cell_names:
                bad.append(f"metric {m['name']}: no workload {w}")
        exists(f"metric {m['name']}",
               "benchmark/%s/%s.json" % (
                   "end_to_end" if is_e2e else "layer_metrics", m["name"]))
    for w in cell_names:
        have = [m["name"] for m in e2e if _applies(m, w)]
        if "setup_s" not in have or len(have) < 2:
            bad.append(f"workload {w}: needs setup_s and one more "
                       "end-to-end metric")
        if not any(_applies(m, w) and m.get("moves") in have for m in layer):
            bad.append(f"workload {w}: no per-layer metric")
    if len(json.dumps(manifest)) > 64 * 1024:
        bad.append("BENCHMARK.json is over 64 KiB")
    return bad
