"""The manifest validates, and a configuration, a mix, a per-layer metric and
a reducer can each be added as new files and new entries only."""
import json
import os
import shutil
import subprocess
import sys

from perfbench.manifest import HERE, ROOT, Manifest


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
    """What a later PR does to ``BENCHMARK.json``: new entries, and the new
    cells' names in the lists of the end-to-end metrics they report."""
    path = os.path.join(root, "BENCHMARK.json")
    data = json.load(open(path))
    for group, added in entries["add"].items():
        data[group] += added
    for metric in data["end_to_end"]:
        extra = entries["list_in"].get(metric["name"], [])
        if extra:
            metric.setdefault("workloads", []).extend(extra)
    json.dump(data, open(path, "w"))


def test_additions_are_files_and_entries_only(tmp_path):
    """A later PR's cell: a new configuration, mix, per-layer metric and reducer,
    added beside what is there; no file that was there changes."""
    root = _copy(tmp_path)
    bench = os.path.join(root, "perfbench")
    before = {p: open(os.path.join(dp, p), "rb").read()
              for dp, _, fs in os.walk(bench) for p in fs}
    fixture = os.path.join(HERE, "tests", "data", "added")
    for sub in ("configs", "traffic", "layer_metrics", "reducers"):
        for name in os.listdir(os.path.join(fixture, sub)):
            target = os.path.join(bench, sub, name)
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
