"""The manifest validates; every per-layer metric is one quantity with its
file, its reducer and, in every cell that lists it, the configuration keys it
reads; and a configuration, a mix, a per-layer metric and a reducer can each be
added as new files and new entries only."""
import inspect
import json
import os
import shutil
import subprocess
import sys

import pytest

from perfbench.manifest import HERE, ROOT, Manifest, load_module
from perfbench.reduce import resolved

#: The models the benchmark runs: none names a reducer or a per-layer metric.
MODELS = ("zaya", "ouro", "laguna", "nemotron", "joyai", "sdar", "solar", "olmoe")
#: The reducers that count a part's work by the configuration's cost module.
COSTED = ("lm_mfu_pct", "lm_roofline_pct")


def _renamed() -> dict:
    with open(os.path.join(HERE, "tests", "data", "renamed_metrics.json"), encoding="utf-8") as f:
        return json.load(f)


def _listed():
    """``(metric, cell, name it had before PR 53 or None)``: every reading the
    manifest lists, a folded one under each name it was listed by."""
    m = Manifest()
    before = {}
    for old, to in _renamed()["renamed"].items():
        for cell in to["cells"]:
            before[to["kept"], cell] = old
    return [(e["name"], cell, before.get((e["name"], cell)))
            for e in m.data["per_layer"] for cell in m.cells if e["name"] in m.cell_metrics("per_layer", cell)]


def check_listed(m: Manifest, metric: str, cell: str) -> None:
    """What one listed reading needs: the metric's file agrees with its entry,
    its reducer is there and takes the file's parameters, and this cell's
    configuration gives every key the file or the reducer leaves to it."""
    entry, spec = m.per_layer[metric], m.layer_metric(metric)
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], (metric, key)
    assert not any(model in metric or model in spec["reducer"] for model in MODELS)
    assert metric.endswith("_roofline") == (spec["unit"] == "%" and "cost" in spec["params"])
    reducer = load_module("reducers", spec["reducer"]).reduce
    config = m.config(m.cells[cell]["config"])
    params = resolved(spec.get("params", {}), config)
    assert params is not None, f"{cell}'s configuration lacks a perf key that {metric} reads"
    inspect.signature(reducer).bind(None, **params)
    if spec["reducer"] in COSTED:
        assert callable(getattr(load_module("", config["perf"]["costs"]), params.get("cost", "model")))


@pytest.mark.parametrize("metric,cell,before", _listed(), ids=lambda v: str(v))
def test_a_listed_metric_has_its_file_its_reducer_and_its_cells_configuration_keys(metric, cell, before):
    m = Manifest()
    check_listed(m, metric, cell)
    if before is not None:  # the quantity's one entry took this cell's reading over
        assert before not in m.per_layer
        assert not os.path.exists(os.path.join(HERE, "layer_metrics", f"{before}.json"))


def test_per_layer_has_room_and_names_quantities_not_models():
    m = Manifest()
    assert len(m.data["per_layer"]) <= 75
    table = _renamed()
    assert len(table["renamed"]) == 58
    for old, to in table["renamed"].items():
        assert old not in m.per_layer and set(to["cells"]) <= set(m.per_layer[to["kept"]]["workloads"])
    files = {f[:-5] for f in os.listdir(os.path.join(HERE, "layer_metrics"))}
    assert files == set(m.per_layer)  # no file without its entry
    for name in [f[:-3] for f in os.listdir(os.path.join(HERE, "reducers")) if f.endswith(".py")] + list(m.per_layer):
        assert not any(model in name for model in MODELS), name
    for name, entry in m.per_layer.items():
        if name not in ("fit_idle_pct", "fit_peak_hbm_gb"):  # those two read in every cell
            assert entry.get("workloads") and len(set(entry["workloads"])) == len(entry["workloads"]), name
    assert m.per_layer["layout_fill_parallel_x"]["workloads"] == ["criteo_lr.fit_resident", "criteo_lr_x4.fit_dp4"]


def test_manifest_validates():
    m = Manifest()
    assert m.problems() == []
    assert m.data["command"][0] == "python3" and m.data["paths"] == ["perfbench"]
    for name in m.cells:
        assert "setup_s" in m.cell_metrics("end_to_end", name)
        for metric in m.cell_metrics("per_layer", name):
            assert m.per_layer[metric]["moves"] in m.cell_metrics("end_to_end", name)
    for metric in m.data["end_to_end"] + m.data["per_layer"]:
        assert len(metric["unit"]) <= 16 and " " not in metric["unit"]


def test_every_data_file_is_found_by_name():
    m = Manifest()
    for cell in m.cells.values():
        assert m.config(cell["config"])["name"] == cell["config"]
        assert os.path.exists(os.path.join(HERE, "kinds", m.traffic(cell["traffic"])["kind"] + ".py"))
    for name in m.per_layer:
        spec = m.layer_metric(name)
        assert os.path.exists(os.path.join(HERE, "reducers", spec["reducer"] + ".py"))


def test_a_bad_manifest_is_named(tmp_path):
    root = _copy(tmp_path)
    path = os.path.join(root, "BENCHMARK.json")
    data = json.load(open(path))
    data["per_layer"][0]["unit"] = "rows per second"
    data["per_layer"][1]["moves"] = "no_such_metric"
    json.dump(data, open(path, "w"))
    bad = Manifest(root, os.path.join(root, "perfbench")).problems()
    assert any("bad unit" in b for b in bad) and any("no_such_metric" in b for b in bad)


def _copy(tmp_path) -> str:
    return copy_checkout(str(tmp_path / "checkout"))


def copy_checkout(root: str) -> str:
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(HERE, os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace", ".checkout", "__pycache__"))
    return root


def add_entries(root: str, entries: dict) -> None:
    """What a later PR does to ``BENCHMARK.json``: new entries, the new cells'
    names in the lists of the end-to-end metrics they report, and in the lists
    of the per-layer quantities they share with the cells that are there."""
    path = os.path.join(root, "BENCHMARK.json")
    data = json.load(open(path))
    for group, added in entries["add"].items():
        data[group] += added
    for metric in data["end_to_end"]:
        extra = entries["list_in"].get(metric["name"], [])
        if extra:
            metric.setdefault("workloads", []).extend(extra)
    for metric in data["per_layer"]:
        metric.get("workloads", []).extend(entries.get("join", {}).get(metric["name"], []))
    json.dump(data, open(path, "w"))


def test_additions_are_files_and_entries_only(tmp_path):
    """A later PR's cell: a new configuration, mix, per-layer metric and reducer,
    added beside what is there; no file that was there changes."""
    root = _copy(tmp_path)
    bench = os.path.join(root, "perfbench")
    before = {p: open(os.path.join(dp, p), "rb").read()
              for dp, _, fs in os.walk(bench) for p in fs}
    fixture = os.path.join(HERE, "tests", "data", "added")
    for sub in ("configs", "traffic", "layer_metrics", "reducers", "costs"):
        for name in os.listdir(os.path.join(fixture, sub)):
            target = os.path.join(bench, "" if sub == "costs" else sub, name)  # a cost module lies beside the others
            assert not os.path.exists(target)
            shutil.copy(os.path.join(fixture, sub, name), target)
    add_entries(root, json.load(open(os.path.join(fixture, "entries.json"))))
    for dp, _, fs in os.walk(bench):
        for p in fs:
            if p in before and "added" not in dp:
                assert open(os.path.join(dp, p), "rb").read() == before[p]

    m = Manifest(root, bench)
    assert m.problems() == []
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}
    proc = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", "criteo_lr_narrow.fit_short",
         "--seed", "7", "--seconds", "0.5", "--trace", "1", "--rehearse-on-cpu"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("rehearsal ")][-1]
    result = json.loads(line.split(" ", 1)[1])
    assert result["correct"] is True
    assert result["metrics"]["fits_in_window"]["value"] >= 1

    # the added LM configuration joins the shared quantities by its perf block and its cost module alone
    joined = json.load(open(os.path.join(fixture, "entries.json")))["join"]
    assert os.listdir(os.path.join(fixture, "reducers")) == ["fits_counted.py"]
    for metric in joined:
        assert metric in m.cell_metrics("per_layer", "narrow_lm.fit_short")
    proc = subprocess.run([sys.executable, "-c", JOINED, *joined], cwd=root, env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    got = json.loads(proc.stdout.splitlines()[-1])
    assert set(got) == set(joined) and got["lm_step_ms"] == 1000.0 and got["attn_ms"] == 200.0
    # narrow_lm_costs at the recorded shapes: 956,301,312 flop a step, 201,326,592 of them the fold's
    assert got["lm_mfu_pct"] == pytest.approx(100 * 956301312 / 2e9 / 1.0)
    assert got["attn_roofline"] == pytest.approx(100 * (201326592 / 2e9) / 0.2)


#: In the copy: the named metrics of the added cell as ``reduce.per_layer`` reads
#: each, over a recorded step (4 steps: a 4 s step program, 0.8 s of its marker's kernels).
JOINED = """
import json, sys, types
from perfbench.manifest import Manifest
from perfbench.reduce import metric_value
from perfbench.tests.test_manifest import check_listed
m = Manifest()
config = m.config("narrow_lm")
layout = {"tokens": 512, "batch": 2, "seq": 256, "layers": 2, "hidden": 128, "heads": 4, "width": 64, "vocab": 512}
ctx = types.SimpleNamespace(
    config=config, facts={"layout": layout, "steps": 4}, w0=0.0, w1=1e12, dev=0, per=lambda unit: 4,
    peaks={"bf16_flops": 2e9, "hbm_bytes_per_s": 1e12},
    ops=lambda: [("narrow_fold_fwd.1", 20.0, 800e6), ("flash_fold_fwd.2", 30.0, 800e6)],
    trace=types.SimpleNamespace(modules={0: [("jit_step", 19.0, 4000e6), ("jit_other", 5e9, 1e9)]}))
out = {}
for name in sys.argv[1:]:
    check_listed(m, name, "narrow_lm.fit_short")
    out[name] = metric_value(ctx, m.layer_metric(name))
print(json.dumps(out))
"""
