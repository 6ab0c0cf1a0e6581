"""``BENCHMARK.json`` and the files it names: a configuration, a traffic
mix, a cell's limits and a per-layer metric's reader are each one file,
found by name. Adding a cell, a configuration or a metric is adding files
and entries; no file that is there needs an edit."""
from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Callable, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load_manifest(root: str = ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with the files it names, loaded."""

    def __init__(self, manifest: Dict[str, Any], name: str,
                 root: str = ROOT, bench_dir: str = HERE):
        cells = {w["name"]: w for w in manifest["workloads"]}
        if name not in cells:
            raise KeyError(
                f"no workload {name!r} in BENCHMARK.json; it has "
                f"{sorted(cells)}")
        w = cells[name]
        self.name, self.chips = name, int(w["chips"])
        self.config_name, self.traffic_name = w["config"], w["traffic"]
        cfg = {c["name"]: c for c in manifest["configs"]}[w["config"]]
        self.config = _json(os.path.join(root, cfg["file"]))
        self.mix = _json(os.path.join(
            bench_dir, "traffic", w["traffic"] + ".json"))
        limits = os.path.join(bench_dir, "limits", name + ".json")
        self.limits = _json(limits) if os.path.exists(limits) else {}
        self.kind = self.mix["kind"]
        self.end_to_end = [m for m in manifest["end_to_end"]
                           if name in m.get("workloads", [name])]
        e2e = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in manifest["per_layer"]
                          if name in m.get("workloads", [name])
                          and m["moves"] in e2e]
        self.bench_dir = bench_dir


def reader(metric: str, bench_dir: str = HERE) -> Callable:
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = os.path.join(bench_dir, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + re.sub(r"\W", "_", metric), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def check(manifest: Dict[str, Any], root: str = ROOT,
          bench_dir: str = HERE) -> List[str]:
    """What is wrong with a manifest, as the contract states it; empty
    when nothing is."""
    bad: List[str] = []

    def name_ok(n, what):
        if not isinstance(n, str) or not NAME.match(n):
            bad.append(f"{what}: name {n!r} outside the allowed characters")

    def unique(names, what):
        if len(set(names)) != len(names):
            bad.append(f"{what}: names repeat")

    configs = manifest.get("configs", [])
    cells = manifest.get("workloads", [])
    e2e = manifest.get("end_to_end", [])
    layer = manifest.get("per_layer", [])
    unique([c["name"] for c in configs], "configs")
    unique([w["name"] for w in cells], "workloads")
    unique([m["name"] for m in e2e + layer], "metrics")
    unique([(w["config"], w["traffic"]) for w in cells], "config x traffic")
    for c in configs:
        name_ok(c["name"], "config")
        if not os.path.exists(os.path.join(root, c["file"])):
            bad.append(f"config {c['name']}: no file {c['file']}")
        if not any(w["config"] == c["name"] for w in cells):
            bad.append(f"config {c['name']}: used by no cell")
    for w in cells:
        name_ok(w["name"], "workload")
        name_ok(w["traffic"], "traffic")
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']}: chips {w['chips']}")
        if not 1 <= len(w["why"]) <= 200:
            bad.append(f"workload {w['name']}: why of {len(w['why'])} chars")
        if w["config"] not in {c["name"] for c in configs}:
            bad.append(f"workload {w['name']}: unknown config")
        if not os.path.exists(os.path.join(
                bench_dir, "traffic", w["traffic"] + ".json")):
            bad.append(f"workload {w['name']}: no traffic file")
    four = sum(1 for w in cells if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        bad.append(f"{four} cells ask for four chips")
    names = [w["name"] for w in cells]
    if not any(m["name"] == "setup_s" for m in e2e):
        bad.append("no setup_s among the end-to-end metrics")
    for m in e2e + layer:
        name_ok(m["name"], "metric")
        if not UNIT.match(m.get("unit", "")):
            bad.append(f"metric {m['name']}: unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            bad.append(f"metric {m['name']}: better {m.get('better')!r}")
        if m.get("source") not in SOURCES:
            bad.append(f"metric {m['name']}: source {m.get('source')!r}")
        for w in m.get("workloads", []):
            if w not in names:
                bad.append(f"metric {m['name']}: unknown workload {w}")
    for m in e2e:
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end-to-end {m['name']}: source {m['source']}")
        if not 0 < m.get("bound", 0) <= 0.1:
            bad.append(f"end-to-end {m['name']}: bound {m.get('bound')}")
    reports = {m["name"]: set(m.get("workloads", names)) for m in e2e}
    for m in layer:
        if m.get("moves") not in reports:
            bad.append(f"per-layer {m['name']}: moves {m.get('moves')!r}")
            continue
        cells_of = set(m.get("workloads", reports[m["moves"]]))
        if not cells_of <= reports[m["moves"]]:
            bad.append(f"per-layer {m['name']}: a cell of it does not "
                       f"report {m['moves']}")
        if not os.path.exists(os.path.join(
                bench_dir, "metrics", m["name"] + ".py")):
            bad.append(f"per-layer {m['name']}: no reader file")
        if not 1 <= len(m.get("layer", "")) <= 200:
            bad.append(f"per-layer {m['name']}: layer missing")
    for w in names:
        mine = [m for m in e2e if w in m.get("workloads", names)]
        if len(mine) < 2:
            bad.append(f"workload {w}: fewer than two end-to-end metrics")
        if not any(w in m.get("workloads", reports.get(m.get("moves"), ()))
                   for m in layer):
            bad.append(f"workload {w}: no per-layer metric")
    return bad
