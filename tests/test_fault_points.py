"""Tier-1 gate over graftcheck's ``fault-points`` rule.

Every fault point registered in ``flink_ml_tpu.faults.FAULT_POINTS`` must
have a runtime ``faults.trip`` call site AND a test exercising it — this test
makes the tier-1 suite enforce that, so injection seams can't silently rot.
"""
import os

from tools.graftcheck.rules import fault_points

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_every_fault_point_is_tripped_and_tested():
    problems, trip_sites = fault_points.check(REPO_ROOT)
    assert not problems, "\n".join(problems)
    assert trip_sites, "no fault points found at all — the registry is empty?"
