"""Tier-1 gate over the serving slice of graftcheck's ``layer-deps`` rule.

The L1 guarantee from the reference (SURVEY.md §2.6): the servable/serving
tier is deployable without the training runtime. This test makes tier-1
enforce it — any import (even lazy, function-local) of ``iteration/``,
``execution/``, ``builder/`` or ``models/`` from ``flink_ml_tpu/servable/``
or ``flink_ml_tpu/serving/`` fails the suite.
"""
import os

from tools.graftcheck.rules import layer_deps

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_serving_tier_is_runtime_free():
    problems, checked = layer_deps.servable_check(REPO_ROOT)
    assert not problems, "\n".join(problems)
    # Both packages must actually be present in the sweep — an empty check
    # passing would be the guard silently rotting.
    assert any("servable" in f for f in checked)
    assert any(os.path.join("flink_ml_tpu", "serving") in f for f in checked)


def test_checker_catches_lazy_imports(tmp_path):
    """The guard must see function-local imports, not just module top-level."""
    bad = tmp_path / "bad.py"
    bad.write_text(
        "def transform(df):\n"
        "    from flink_ml_tpu.models.linear import compute_dots\n"
        "    import flink_ml_tpu.iteration.datacache as dc\n"
        "    from flink_ml_tpu import builder\n"
        "    return compute_dots\n"
    )
    found = sorted(m for _, m in layer_deps.servable_violations_in_file(str(bad)))
    assert found == [
        "flink_ml_tpu.builder",
        "flink_ml_tpu.iteration.datacache",
        "flink_ml_tpu.models.linear",
    ]


def test_checker_allows_runtime_free_imports(tmp_path):
    good = tmp_path / "good.py"
    good.write_text(
        "import numpy as np\n"
        "from flink_ml_tpu.api.dataframe import DataFrame\n"
        "from flink_ml_tpu.ops.kernels import compute_dots\n"
        "from flink_ml_tpu.checkpoint import scan_numbered_dirs\n"
    )
    assert list(layer_deps.servable_violations_in_file(str(good))) == []
