"""``--rehearse-on-cpu`` drives each traffic kind end to end at toy size and
never prints a result line; a run with the timed path broken underneath comes
out not correct; a run without a TPU exits non-zero and prints no result."""
import json
import os
import subprocess
import sys

import pytest

from perfbench.manifest import ROOT, Manifest
from perfbench.tests.test_manifest import copy_checkout


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return copy_checkout(str(tmp_path_factory.mktemp("rehearse") / "checkout"))


CELLS = sorted(Manifest().cells.items())

#: Drives ``perfbench.run`` with the timed path broken underneath: the warm-up
#: fit is sound, every fit of the window is not.
BREAK = """
import sys
import numpy as np
from perfbench.systems import sparse_lr_fit
mode, sound, calls = sys.argv[1], sparse_lr_fit.SparseLrFit.fit, []
def fit(self):
    calls.append(1)
    if len(calls) == 1:
        return sound(self)
    if mode == "unchanged":  # a step that returns its state unchanged
        coef, losses = sound(self)
        return np.zeros_like(coef), losses
    self.batch //= 2  # drop_rows: a part of every batch left out
    try:
        return sound(self)
    finally:
        self.batch *= 2
sparse_lr_fit.SparseLrFit.fit = fit
from perfbench import run
sys.exit(run.main(sys.argv[2:]))
"""


def run_cell(root, *args, devices=1, broken=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    head = ["-c", BREAK, broken] if broken else ["-m", "perfbench.run"]
    return subprocess.run([sys.executable, *head, *args],
                          cwd=root, env=env, capture_output=True, text=True, timeout=900)
def rehearsal_of(proc):
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert not lines[-1].startswith("{"), "a rehearsal printed a result line"
    assert any(ln.startswith("setup_parts ") for ln in lines)
    assert any(ln.startswith("compiles_in_window ") for ln in lines)
    return json.loads([ln for ln in lines if ln.startswith("rehearsal ")][-1].split(" ", 1)[1])


@pytest.mark.parametrize("name,cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_every_cell(checkout, name, cell, trace):
    m = Manifest(checkout, os.path.join(checkout, "perfbench"))
    proc = run_cell(checkout, "--workload", name, "--seed", str(2**31 + 5), "--seconds", "1",
                    "--trace", str(trace), "--rehearse-on-cpu", devices=cell["chips"])
    out = rehearsal_of(proc)
    assert out["correct"] is True and out["attempted"] >= 1 and out["failed"] == 0
    group = "per_layer" if trace else "end_to_end"
    assert set(out["metrics"]) <= set(m.cell_metrics(group, name))
    if not trace:
        assert set(out["metrics"]) == set(m.cell_metrics(group, name))
    assert out["device"]["count"] == cell["chips"]


@pytest.mark.parametrize("name,cell", CELLS)
@pytest.mark.parametrize("mode", ["unchanged", "drop_rows"])
def test_a_broken_timed_path_is_not_correct(checkout, name, cell, mode):
    proc = run_cell(checkout, "--workload", name, "--seed", "9", "--seconds", "1", "--trace", "0",
                    "--rehearse-on-cpu", devices=cell["chips"], broken=mode)
    assert rehearsal_of(proc)["correct"] is False


def test_an_allocator_setting_that_cannot_be_applied_is_no_result(monkeypatch, capsys):
    """The configuration states the deployment's malloc setting; where it cannot
    be applied the run is refused before anything is measured."""
    import ctypes

    from perfbench import allocator, run

    assert allocator.apply(None) == "default"

    def no_libc(name):
        raise OSError(f"{name}: cannot open shared object file")

    monkeypatch.setattr(ctypes, "CDLL", no_libc)
    with pytest.raises(RuntimeError, match="mallopt"):
        allocator.apply({"mmap_threshold": 1 << 30, "trim_threshold": 1 << 30})
    rc = run.main(["--workload", CELLS[0][0], "--seed", "1", "--seconds", "1", "--trace", "0",
                   "--rehearse-on-cpu"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "mallopt" in out.err


def test_without_a_tpu_there_is_no_result():
    name = CELLS[0][0]
    proc = run_cell(ROOT, "--workload", name, "--seed", "1", "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())
    assert "no TPU" in proc.stderr


def test_an_unknown_workload_is_an_error():
    proc = run_cell(ROOT, "--workload", "no.such_cell", "--seed", "1", "--trace", "0")
    assert proc.returncode != 0 and not proc.stdout.strip()
