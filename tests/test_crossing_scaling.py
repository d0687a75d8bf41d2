"""The sparse roofline's multi-chip scaling claim, measured (tools/crossing_scaling.py).

docs/benchmarks.md argues the one-hot program's crossing term falls ~1/p²
per chip under p-way data parallelism (p divides both the per-shard entry
count and — once under the 16384 cap — the sub-batch row space). This pins
the claim to XLA's compiled per-chip cost analysis on the virtual mesh: the
SPMD executable's FLOP count must fall SUPERLINEARLY in p.
"""
import numpy as np
import pytest

from tools.crossing_scaling import markdown_table, measure_scaling


@pytest.fixture(scope="module")
def rows():
    # B=8192 keeps every local batch under the 16384 sub cap, so the whole
    # sweep sits in the quadratic regime (sub_batch == local_batch).
    return measure_scaling([1, 2, 4, 8], global_batch=8192, dim=1 << 16, nnz=8, K=8)


def test_cost_analysis_reports_flops(rows):
    for r in rows:
        assert np.isfinite(r["flops_per_chip"]) and r["flops_per_chip"] > 0, r


def test_per_chip_flops_fall_superlinearly(rows):
    # Superlinear: p * flops(p) strictly decreasing — each doubling of the
    # mesh cuts per-chip work by MORE than half.
    by_p = {r["p"]: r["flops_per_chip"] for r in rows}
    for p_small, p_big in [(1, 2), (2, 4), (4, 8)]:
        assert by_p[p_big] * p_big < by_p[p_small] * p_small * 0.95, (
            f"p={p_small}->{p_big}: per-chip flops fell sublinearly: {by_p}"
        )
    # End to end the fall approaches quadratic: 8 chips, > 8x1.5 less work each
    assert by_p[1] / by_p[8] > 12.0, by_p


def test_sub_batch_tracks_local_batch_in_quadratic_regime(rows):
    for r in rows:
        assert r["sub_batch"] == r["local_batch"], r


def test_markdown_table_renders(rows):
    table = markdown_table(rows)
    assert "per-chip GFLOP/step" in table and table.count("|") > 20

