"""Load ``BENCHMARK.json`` and resolve a cell's files by name.

A cell ``<config>.<traffic>`` is made of:

* its entry in the manifest's ``workloads`` (config, traffic, chips, why);
* the configuration's file (``configs[].file``): sizes, parameters, source;
* the traffic mix ``benchmark/traffic/<traffic>.json``: which runner drives
  it and with what (warm-up chunks, the traced window, trees the reference follows);
* ``benchmark/workloads/<cell>.json``: the limits that decide ``correct`` in
  this cell, set from chip readings (PERF.md section 2);
* the runner ``benchmark/runners/<runner>.py`` and one reader
  ``benchmark/layer_metrics/<metric>.py`` per per-layer metric.

Nothing here names a cell, a configuration or a metric: a later PR adds
files and manifest entries only.
"""

from __future__ import annotations

import importlib
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


def _read_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def problems(manifest: dict, root: str = ROOT) -> list[str]:
    """Every breach of the manifest's own rules that can be seen without a
    run: names, units, sources, files that resolve.  Empty when sound."""
    bad: list[str] = []
    if set(manifest) != TOP_KEYS:
        bad.append(f"top-level keys {sorted(manifest)} != {sorted(TOP_KEYS)}")
        return bad
    configs = {c["name"]: c for c in manifest["configs"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for kind, names in (("config", configs), ("cell", cells), ("metric", e2e)):
        for n in names:
            if not NAME.match(n):
                bad.append(f"{kind} name {n!r} has characters outside the allowed set")
    if "setup_s" not in e2e:
        bad.append("no end-to-end metric setup_s")
    for c in manifest["configs"]:
        if not os.path.isfile(os.path.join(root, c["file"])):
            bad.append(f"config {c['name']}: file {c['file']} is missing")
        if not any(c["file"].startswith(p + "/") for p in manifest["paths"]):
            bad.append(f"config {c['name']}: file {c['file']} lies outside paths")
    seen_pairs = set()
    for w in manifest["workloads"]:
        if w["config"] not in configs:
            bad.append(f"cell {w['name']}: unknown config {w['config']!r}")
        if not NAME.match(w["traffic"]):
            bad.append(f"cell {w['name']}: traffic {w['traffic']!r} is no legal name")
        if not os.path.isfile(traffic_path(w["traffic"], root)):
            bad.append(f"cell {w['name']}: no traffic file {traffic_path(w['traffic'], root)}")
        if w["chips"] not in (1, 4):
            bad.append(f"cell {w['name']}: chips {w['chips']}")
        if (w["config"], w["traffic"]) in seen_pairs:
            bad.append(f"cell {w['name']}: pair of config and traffic appears twice")
        seen_pairs.add((w["config"], w["traffic"]))
        if not 1 <= len(w["why"]) <= 200:
            bad.append(f"cell {w['name']}: why has {len(w['why'])} characters")
    used = {w["config"] for w in manifest["workloads"]}
    for c in configs:
        if c not in used:
            bad.append(f"config {c} is used by no cell")
    names = list(e2e)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if not UNIT.match(m["unit"]):
            bad.append(f"metric {m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"metric {m['name']}: better {m['better']!r}")
        if m["source"] not in SOURCES:
            bad.append(f"metric {m['name']}: source {m['source']!r}")
        for w in m.get("workloads", ()):
            if w not in cells:
                bad.append(f"metric {m['name']}: unknown cell {w!r}")
    for m in manifest["end_to_end"]:
        if not 0 < m["bound"] <= 0.1:
            bad.append(f"metric {m['name']}: bound {m['bound']}")
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end-to-end metric {m['name']}: source {m['source']!r}")
    for m in manifest["per_layer"]:
        if not NAME.match(m["name"]):
            bad.append(f"metric name {m['name']!r}")
        if m["name"] in names:
            bad.append(f"metric name {m['name']!r} appears twice")
        names.append(m["name"])
        if m["moves"] not in e2e:
            bad.append(f"metric {m['name']}: moves unknown metric {m['moves']!r}")
        if not os.path.isfile(metric_path(m["name"], root)):
            bad.append(f"metric {m['name']}: no reader {metric_path(m['name'], root)}")
    return bad


def traffic_path(traffic: str, root: str = ROOT) -> str:
    return os.path.join(root, "benchmark", "traffic", traffic + ".json")


def _module_of(metric: str) -> str:
    return metric.replace(".", "_").replace("-", "_")


def metric_path(metric: str, root: str = ROOT) -> str:
    return os.path.join(root, "benchmark", "layer_metrics", _module_of(metric) + ".py")


class Cell:
    """One entry of ``workloads`` with every file it names, loaded."""

    def __init__(self, manifest: dict, name: str, root: str = ROOT):
        entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
        if entry is None:
            raise KeyError(f"no cell {name!r} in BENCHMARK.json; cells: "
                           f"{[w['name'] for w in manifest['workloads']]}")
        self.name = name
        self.chips = int(entry["chips"])
        self.config_name = entry["config"]
        self.traffic_name = entry["traffic"]
        cfg_entry = next(c for c in manifest["configs"] if c["name"] == entry["config"])
        self.config = _read_json(os.path.join(root, cfg_entry["file"]))
        self.traffic = _read_json(traffic_path(entry["traffic"], root))
        cell_file = os.path.join(root, "benchmark", "workloads", name + ".json")
        self.cell = _read_json(cell_file) if os.path.isfile(cell_file) else {}
        self.limits = dict(self.cell.get("limits", {}))
        self.end_to_end = [m for m in manifest["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in manifest["per_layer"]
                          if name in m.get("workloads", [name])
                          and m["moves"] in {e["name"] for e in self.end_to_end}]

    def runner(self):
        return importlib.import_module("benchmark.runners." + self.traffic["runner"])


def metric_reader(metric: str):
    """The reader module of one per-layer metric: ``read(facts) -> number
    or None`` (None: nothing to read in this run, the metric is left out)."""
    return importlib.import_module("benchmark.layer_metrics." + _module_of(metric))
