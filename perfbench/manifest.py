"""``BENCHMARK.json`` and the data files it names, found by name.

A cell names a configuration and a traffic mix; the harness finds
``perfbench/configs/<config>.json``, ``perfbench/traffic/<traffic>.json`` and,
for each per-layer metric, ``perfbench/layer_metrics/<name>.json``. Nothing in
the harness lists cells, mixes or metrics: a later PR adds files and entries.
"""
from __future__ import annotations

import importlib
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def _load(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


class Manifest:
    def __init__(self, root: str = ROOT, bench_dir: str = HERE):
        self.root = root
        self.dir = bench_dir
        self.data = _load(os.path.join(root, "BENCHMARK.json"))
        self.cells = {w["name"]: w for w in self.data["workloads"]}
        self.configs = {c["name"]: c for c in self.data["configs"]}
        self.end_to_end = {m["name"]: m for m in self.data["end_to_end"]}
        self.per_layer = {m["name"]: m for m in self.data["per_layer"]}

    # -- lookups by name -------------------------------------------------------
    def cell(self, name: str) -> dict:
        if name not in self.cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json (has: {sorted(self.cells)})")
        return self.cells[name]

    def config(self, name: str) -> dict:
        return _load(os.path.join(self.root, self.configs[name]["file"]))

    def traffic(self, name: str) -> dict:
        return _load(os.path.join(self.dir, "traffic", f"{name}.json"))

    def layer_metric(self, name: str) -> dict:
        return _load(os.path.join(self.dir, "layer_metrics", f"{name}.json"))

    def cell_metrics(self, group: str, cell: str) -> list:
        """Names of the ``end_to_end`` or ``per_layer`` metrics this cell reports."""
        out = []
        for m in self.data[group]:
            cells = m.get("workloads")
            if cells is None and group == "per_layer":
                cells = [
                    c for c in self.cells
                    if m["moves"] in self.cell_metrics("end_to_end", c)
                ]
            if cells is None or cell in cells:
                out.append(m["name"])
        return out

    # -- validation (perfbench/tests runs it; the harness runs it on every start) --
    def problems(self) -> list:
        bad = []
        d = self.data
        want = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
        if set(d) != want:
            bad.append(f"keys {sorted(d)} != {sorted(want)}")
            return bad
        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            names = [e["name"] for e in d[group]]
            if len(set(names)) != len(names):
                bad.append(f"{group}: a name appears twice")
            bad += [f"{group}: bad name {n!r}" for n in names if not NAME.match(n)]
        if "setup_s" not in self.end_to_end:
            bad.append("end_to_end lacks setup_s")
        for m in d["end_to_end"] + d["per_layer"]:
            if not UNIT.match(m["unit"]):
                bad.append(f"{m['name']}: bad unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                bad.append(f"{m['name']}: better is {m['better']!r}")
            if m["source"] not in SOURCES:
                bad.append(f"{m['name']}: source {m['source']!r}")
            for c in m.get("workloads", []):
                if c not in self.cells:
                    bad.append(f"{m['name']}: unknown workload {c!r}")
        for m in d["end_to_end"]:
            if m["source"] not in ("host_clock", "device_trace"):
                bad.append(f"{m['name']}: an end-to-end metric is the benchmark's own reading")
            if not 0 < m["bound"] <= 0.1:
                bad.append(f"{m['name']}: bound {m['bound']}")
        pairs = set()
        for w in d["workloads"]:
            if w["config"] not in self.configs:
                bad.append(f"{w['name']}: unknown config {w['config']!r}")
            if w["chips"] not in (1, 4):
                bad.append(f"{w['name']}: chips {w['chips']}")
            if (w["config"], w["traffic"]) in pairs:
                bad.append(f"{w['name']}: config and traffic appear twice")
            pairs.add((w["config"], w["traffic"]))
            if not 1 <= len(w["why"]) <= 200:
                bad.append(f"{w['name']}: why has {len(w['why'])} characters")
            if not os.path.exists(os.path.join(self.dir, "traffic", f"{w['traffic']}.json")):
                bad.append(f"{w['name']}: no traffic file {w['traffic']}.json")
            e2e = self.cell_metrics("end_to_end", w["name"])
            if "setup_s" not in e2e or len(e2e) < 2:
                bad.append(f"{w['name']}: reports {e2e}")
            if not self.cell_metrics("per_layer", w["name"]):
                bad.append(f"{w['name']}: no per-layer metric")
        four = sum(1 for w in d["workloads"] if w["chips"] == 4)
        if four > max(1, len(d["workloads"]) // 4):
            bad.append(f"{four} of {len(d['workloads'])} cells ask for four chips")
        for c in d["configs"]:
            if not any(w["config"] == c["name"] for w in d["workloads"]):
                bad.append(f"config {c['name']}: no cell uses it")
            if not os.path.exists(os.path.join(self.root, c["file"])):
                bad.append(f"config {c['name']}: no file {c['file']}")
        for m in d["per_layer"]:
            if m["moves"] not in self.end_to_end:
                bad.append(f"{m['name']}: moves unknown metric {m['moves']!r}")
                continue
            for c in m.get("workloads", []):
                if c in self.cells and m["moves"] not in self.cell_metrics("end_to_end", c):
                    bad.append(f"{m['name']}: cell {c} does not report {m['moves']}")
            path = os.path.join(self.dir, "layer_metrics", f"{m['name']}.json")
            if not os.path.exists(path):
                bad.append(f"{m['name']}: no layer_metrics/{m['name']}.json")
                continue
            own = _load(path)
            for key in ("layer", "unit", "moves"):
                if own.get(key) != m[key]:
                    bad.append(f"{m['name']}: {key} differs between its file and BENCHMARK.json")
            if not os.path.exists(os.path.join(self.dir, "reducers", f"{own['reducer']}.py")):
                bad.append(f"{m['name']}: no reducer {own['reducer']}")
        return bad


def load_module(package: str, name: str):
    """``perfbench/<package>/<name>.py``, found by the name a data file gives;
    ``perfbench/<name>.py`` (a configuration's cost module) where ``package``
    is empty."""
    if not NAME.match(name):
        raise ValueError(f"bad module name {name!r}")
    return importlib.import_module(".".join(filter(None, ("perfbench", package, name))))
