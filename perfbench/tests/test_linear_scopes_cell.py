"""The linear step's scopes as the two Criteo cells read them: eleven metric
files beside their ``BENCHMARK.json`` entries, every one a set of parameters
for a reducer that was there (``scope_ms_per_unit``, ``scope_coverage_pct``,
``program_span_pct``, ``program_span_stat``), and the scope reducers' sums on
a table of ``lin.``-rooted names small enough to work out by hand: one fused
program of two steps (the scan's ``while`` is a container and is not summed)
and the premat program beside it, which holds no crossing and is no step."""
import os

import pytest

from perfbench import op_scopes
from perfbench.manifest import HERE, Manifest
from perfbench.reducers import scope_coverage_pct, scope_ms_per_unit
from perfbench.tests.test_op_scopes import Ctx
from perfbench.tools import scopes as tool

CELLS = ["criteo_lr.fit_resident", "criteo_lr_x4.fit_dp4"]
HOLDS = "onehot_(dot|mult)_crossing"
STEP, LAYOUT, PACK = "fused step program", "sparse layout and device cache", "host featurize and pack"
#: name -> (reducer, layer, source, unit, better, the scopes or the span it reads)
NEW = {
    "lin_scope_coverage_pct": ("scope_coverage_pct", STEP, "device_trace", "%", "higher", None),
    "lin_gather_ms": ("scope_ms_per_unit", STEP, "device_trace", "ms", "lower", ["lin.gather"]),
    "lin_scatter_ms": ("scope_ms_per_unit", STEP, "device_trace", "ms", "lower", ["lin.scatter"]),
    "lin_light_rounds_ms": ("scope_ms_per_unit", STEP, "device_trace", "ms", "lower", ["light"]),
    "lin_chunk_rounds_ms": ("scope_ms_per_unit", STEP, "device_trace", "ms", "lower", ["chunks"]),
    "lin_unpack_ms": ("scope_ms_per_unit", STEP, "device_trace", "ms", "lower", ["lin.unpack"]),
    "lin_cross_ms": ("scope_ms_per_unit", STEP, "device_trace", "ms", "lower", ["lin.cross_dot", "lin.cross_mult"]),
    "lin_update_ms": ("scope_ms_per_unit", STEP, "device_trace", "ms", "lower",
                      ["lin.loss", "lin.reduce", "lin.update"]),
    "lin_slots_used_pct": ("program_span_pct", LAYOUT, "program_span", "%", "higher", "train.layout.plan"),
    "pack_span_s": ("program_span_stat", PACK, "program_span", "s", "lower", "train.pack"),
    "layout_span_s": ("program_span_stat", LAYOUT, "program_span", "s", "lower", "train.layout"),
}

BODY = "jit(per_shard)/shard_map/while/body/closed_call/"
#: one step's operations, ``(instruction, ns, op_name)``: 10,000 ns, of which 9,400 under a scope
ONE_STEP = [
    ("dynamic_slice.67", 200, BODY + "lin.unpack/dynamic_slice"),
    ("convert_bitcast_fusion.2", 800, BODY + "lin.unpack/convert_element_type"),
    ("select_reduce_fusion.34", 700, BODY + "lin.gather/light/w4/reduce_sum"),
    ("select_reduce_fusion.35", 900, BODY + "lin.gather/light/w8/reduce_sum"),
    ("fusion.15", 300, BODY + "lin.gather/chunks/jit(_take)/gather"),
    ("select_reduce_fusion.39", 600, BODY + "lin.gather/chunks/reduce_sum"),
    ("bitcast_multiply_fusion.2", 100, BODY + "lin.gather/mul"),
    ("copy.59", 150, BODY + "lin.cross_dot/reshape"),
    ("onehot_dot_crossing_premat.6", 1800, BODY + "lin.cross_dot/onehot_dot_crossing_premat/pallas_call"),
    ("multiply_multiply_fusion.2", 50, BODY + "lin.loss/mul"),
    ("onehot_mult_crossing_premat.6", 1750, BODY + "lin.cross_mult/onehot_mult_crossing_premat/pallas_call"),
    ("slice_multiply_fusion.2", 250, BODY + "lin.scatter/mul"),
    ("select_reduce_fusion.43", 1000, BODY + "lin.scatter/light/w8/reduce_sum"),
    ("select_reduce_fusion.47", 500, BODY + "lin.scatter/chunks/reduce_sum"),
    ("psum_invariant.1", 200, BODY + "lin.reduce/psum_invariant"),
    ("select_select_fusion.2", 100, BODY + "lin.update/jit(_where)/select_n"),
    ("copy_bitcast_fusion.2", 400, None),  # the compiler's own, nameless
    ("dynamic_update_slice.61", 200, "jit(per_shard)/shard_map/while/body/dynamic_update_slice"),  # the scan's stacking
]


def _table():
    """``rows``, ``modules``, ``window``: the premat program at 1,000 ns, the fused
    program of two steps at 10,000 (its ``while`` spans both), a program of
    the warm-up fit before the window."""
    rows = [["fusion.1", 1000, 500, "jit(premat_row_onehots)/eq"]]
    for program_start in (2000, 40000):  # the warm-up's chunk, then the window's
        rows.append(["while.2", program_start, 20000, "jit(per_shard)/shard_map/while"])
        at = program_start
        for _ in range(2):
            for name, dur, op_name in ONE_STEP:
                rows.append([name, at, dur, op_name])
                at += dur
    modules = [["jit_premat_row_onehots", 1000, 500], ["jit_per_shard", 2000, 20000], ["jit_per_shard", 40000, 20000]]
    return {"rows": rows, "modules": modules, "window": (30000, 100000)}


def test_the_manifest_holds_with_the_new_entries_behind_the_accepted_ones():
    m = Manifest()
    assert m.problems() == []
    names = [e["name"] for e in m.data["per_layer"]]
    first = names.index("lin_scope_coverage_pct")
    # appended in one block behind the entries a3c6254 had (103 then; PR 53 folded those before it)
    assert names[first:first + len(NEW)] == list(NEW)


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_metric_file_matches_its_entry_and_names_a_reducer_that_was_there(name):
    reducer, layer, source, unit, better, reads = NEW[name]
    m = Manifest()
    spec, entry = m.layer_metric(name), m.per_layer[name]
    assert entry == {"name": name, "unit": unit, "better": better, "source": source, "layer": layer,
                     "moves": "fit_rows_per_s", "workloads": CELLS}
    assert {k: spec[k] for k in entry if k != "workloads"} == {k: v for k, v in entry.items() if k != "workloads"}
    assert spec["reducer"] == reducer and os.path.exists(os.path.join(HERE, "reducers", reducer + ".py"))
    if source == "device_trace":  # one marker for a step program, the accepted step_ms's, and the root
        assert spec["params"]["holds"] == m.layer_metric("step_ms")["params"]["holds"] == HOLDS
        assert spec["params"]["root"] == "lin." and "renamed" not in spec["params"]
        assert spec["params"].get("scopes") == reads and spec["params"].get("per", "steps") == "steps"
    else:
        assert spec["params"]["span"] == reads
    for cell in CELLS:
        assert name in m.cell_metrics("per_layer", cell)
    assert name not in m.cell_metrics("per_layer", "olmoe_1b_7b.fit_packed4k")


@pytest.mark.parametrize("op_name,scope", [
    (BODY + "lin.gather/light/w4/reduce_sum", ("lin.gather", "light", "w4")),
    (BODY + "lin.gather/chunks/jit(_take)/gather", ("lin.gather", "chunks")),  # a nested jit ends the scope
    (BODY + "lin.cross_dot/onehot_dot_crossing_premat/pallas_call", ("lin.cross_dot", "onehot_dot_crossing_premat")),
    (BODY + "lin.update/jit(_where)/select_n", ("lin.update",)),
    ("jit(per_shard)/shard_map/while/body/dynamic_update_slice", None),
    ("jit(per_shard)/shard_map/lin.unpack/min", ("lin.unpack",)),  # no scan around it: the root counts wherever it stands
])
def test_a_linear_op_name_classifies_by_its_segments(op_name, scope):
    assert op_scopes.classify(op_name, "lin.")[0] == scope


def test_the_sums_by_hand():
    ctx, m = Ctx(_table()), Manifest()
    ms = lambda name: scope_ms_per_unit.reduce(ctx, **m.layer_metric(name)["params"])  # noqa: E731
    assert ms("lin_gather_ms") == pytest.approx((700 + 900 + 300 + 600 + 100) / 1e6)
    assert ms("lin_scatter_ms") == pytest.approx((250 + 1000 + 500) / 1e6)
    assert ms("lin_light_rounds_ms") == pytest.approx((700 + 900 + 1000) / 1e6)
    assert ms("lin_chunk_rounds_ms") == pytest.approx((300 + 600 + 500) / 1e6)
    assert ms("lin_unpack_ms") == pytest.approx((200 + 800) / 1e6)
    assert ms("lin_cross_ms") == pytest.approx((150 + 1800 + 1750) / 1e6)
    assert ms("lin_update_ms") == pytest.approx((50 + 200 + 100) / 1e6)
    # the rounds' classes are the rounds less what a round does outside a class: the two products
    assert ms("lin_light_rounds_ms") + ms("lin_chunk_rounds_ms") == pytest.approx(
        ms("lin_gather_ms") + ms("lin_scatter_ms") - (100 + 250) / 1e6)
    coverage = scope_coverage_pct.reduce(ctx, **m.layer_metric("lin_scope_coverage_pct")["params"])
    assert coverage == pytest.approx(100 * 9400 / 10000)
    # the five sums and the unscoped rest are the step
    parts = sum(ms(n) for n in ("lin_gather_ms", "lin_scatter_ms", "lin_unpack_ms", "lin_cross_ms", "lin_update_ms"))
    assert parts + 600 / 1e6 == pytest.approx(10000 / 1e6)


def test_only_the_windows_fused_program_counts_and_its_while_does_not():
    t = _table()
    rows = t["rows"]
    programs = op_scopes.step_programs(rows, t["modules"], HOLDS, *t["window"])
    assert programs == [(40000, 60000)]  # not the warm-up's, not the premat program
    ops = op_scopes.step_ops(rows, programs, "lin.")
    assert len(ops) == 2 * len(ONE_STEP) and "while.2" not in {op.name for op in ops}
    assert {op.direction for op in ops if op.scope} == {op_scopes.FWD}  # a linear step has no AD
    found = tool.table(ops, 2)
    assert sum(r["ms"] for r in found) == pytest.approx(10000 / 1e6)
    by = {r["scope"]: r for r in found}
    assert by[("lin.gather", "light", "w8")]["kinds"] == pytest.approx({"select_reduce_fusion": 900 / 1e6})
    assert by[("lin.cross_dot", "onehot_dot_crossing_premat")]["ms"] == pytest.approx(1800 / 1e6)
    assert by[None]["kinds"] == pytest.approx({"copy_bitcast_fusion.2": 400 / 1e6,
                                               "dynamic_update_slice.61": 200 / 1e6})


def test_the_parents_program_reads_none():
    """The parent of the PR that opened the scopes writes JAX's own segments
    and no ``lin.``: every scope metric is left out of its line."""
    t = _table()
    bare = [[n, s, d, None if name is None else "/".join(p for p in name.split("/") if not p.startswith("lin."))]
            for n, s, d, name in t["rows"]]
    ctx = Ctx(t, rows=bare)
    m = Manifest()
    for name, (reducer, *_rest) in NEW.items():
        if reducer == "scope_ms_per_unit":
            assert scope_ms_per_unit.reduce(ctx, **m.layer_metric(name)["params"]) is None, name
    assert scope_coverage_pct.reduce(ctx, **m.layer_metric("lin_scope_coverage_pct")["params"]) is None
