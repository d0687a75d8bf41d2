"""The trace reduction gives known busy, idle and kernel times: on a table
small enough to work out by hand, and on a recorded one (the first fit of a
``criteo_lr.fit_resident`` run on a TPU v5 lite, cut after its 8th step)."""
import json
import os

import pytest

from perfbench import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def hand_made():
    ops = {0: [
        ["while.1", 100, 800],            # holds the next three
        ["kernel_a.1", 100, 200],
        ["all-reduce.1", 300, 300],       # 300..600
        ["fusion.2", 400, 100],           # hides 100 of the collective
        ["kernel_a.1", 1200, 100],
    ]}
    modules = {0: [["jit_step", 100, 800], ["jit_other", 1200, 100]]}
    host = [["window", 0, 2000], ["outer", 10, 1990], ["inner", 900, 250]]
    return xplane.Trace(ops, modules, host)


def test_union_and_gaps():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert xplane.gaps([(0, 3), (5, 8)], 1, 10) == [(3, 5), (8, 10)]
    assert xplane.total(xplane.clip([(0, 3), (5, 8)], 2, 6)) == 2


def test_hand_made_table():
    t = hand_made()
    assert xplane.busy_seconds(t, 0, 2000)[0] == pytest.approx(900e-9)
    by = xplane.seconds_by_name(t.ops[0], 0, 2000)
    assert "while.1" not in by and by["kernel_a.1"] == pytest.approx(300e-9)
    assert xplane.matching_seconds(t.ops[0], "kernel_a", 0, 2000) == pytest.approx(300e-9)
    # the collective runs 300 ns, 100 of them beside fusion.2
    assert xplane.exposed_seconds(t.ops[0], 0, 2000) == pytest.approx(200e-9)
    idle = xplane.idle_by_host_span(t, 0, 0, 2000)
    # idle: 0..10 (window), 10..100 (outer), 900..1150 (inner), 1150..1200 and 1300..2000 (outer)
    assert idle["window"] == pytest.approx(10e-9)
    assert idle["inner"] == pytest.approx(250e-9)
    assert idle["outer"] == pytest.approx((90 + 50 + 700) * 1e-9)
    assert sum(idle.values()) + 900e-9 == pytest.approx(2000e-9)


def test_op_names_are_the_instructions_own():
    text = "%onehot_mult_crossing_premat.6 = f32[4423680]{0:T(1024)S(1)} custom-call(s32[1]{0} %x)"
    assert xplane.OP_NAME.match(text).group(1) == "onehot_mult_crossing_premat.6"
    assert xplane.CONTAINER.match("while.5") and not xplane.CONTAINER.match("while_fusion")


def test_recorded_trace():
    fx = json.load(open(os.path.join(DATA, "fit_resident_8steps.json")))
    t = xplane.Trace(fx["ops"], fx["modules"], fx["host"])
    w0, w1 = fx["window"]
    # pinned from the recording; busy agrees with a 1-microsecond raster of the rows
    assert xplane.busy_seconds(t, w0, w1)[0] == pytest.approx(0.165018252, rel=1e-9)
    crossing = xplane.matching_seconds(t.ops[0], "onehot_(dot|mult)_crossing", w0, w1)
    assert crossing == pytest.approx(0.085598306, rel=1e-9)
    assert crossing / 8 * 1e3 == pytest.approx(10.7, abs=0.05)  # ms per step
    idle = xplane.idle_by_host_span(t, 0, w0, w1)
    assert idle["fit.layout_build"] == pytest.approx(1.980694224, rel=1e-9)
    assert idle["fit.pack"] == pytest.approx(0.411100425, rel=1e-9)
    busy = xplane.busy_seconds(t, w0, w1)[0]
    assert sum(idle.values()) + busy == pytest.approx((w1 - w0) / 1e9, rel=1e-9)
    assert [r[0] for r in t.modules[0]] == ["jit_premat_row_onehots", "jit_per_shard"]
