"""The step's scopes as the reducers read them (``perfbench/op_scopes.py``):
the classification of an ``op_name`` on the forms JAX writes, then the
arithmetic on a table small enough to work out by hand
(``data/op_scopes_table.json``): containers are not summed, operations outside
a step program are left out, a kernel the compiler renamed takes the scope its
metric names and its neighbour's direction, coverage, and a program without
scopes (the parent of the PR that brought them), which reads None."""
import json
import os

import pytest

from perfbench import op_scopes, xplane
from perfbench.manifest import Manifest
from perfbench.op_scopes import BWD, FWD, REMAT, classify, matches
from perfbench.reducers import scope_coverage_pct, scope_ms_per_unit
from perfbench.tools import scopes as tool

HERE = os.path.dirname(os.path.abspath(__file__))
RENAMED = {"^ragged-dot": "lm.block/experts"}
NEW = ("lm_scope_coverage_pct", "lm_head_ms", "lm_stream_ms", "lm_block_remat_ms", "lm_permute_ms", "lm_opt_ms")


def _table():
    with open(os.path.join(HERE, "data", "op_scopes_table.json"), encoding="utf-8") as f:
        return json.load(f)


class Ctx:
    """What a reducer reads of ``reduce.Context``."""

    def __init__(self, table, steps=2, rows=None):
        class Run:
            op_scopes = table["rows"] if rows is None else rows
        self.run = Run()
        self.w0, self.w1 = table["window"]
        self.trace = xplane.Trace({}, {0: table["modules"]}, [])
        self.dev = 0
        self.facts = {"steps": steps}

    def per(self, unit):
        return self.facts.get(unit) or None


#: The six forms a toy step with ``jax.checkpoint``, ``lax.scan``, a
#: ``custom_vjp`` and ``value_and_grad`` showed on the installed jax (ISSUE 35).
PROBED = [
    ("jit(step)/jvp(lm.head)/dot_general", ("lm.head",), FWD),
    ("jit(step)/transpose(jvp(lm.head))/dot_general", ("lm.head",), BWD),
    ("jit(step)/jvp()/while/body/closed_call/lm.block/attn/dot_general", ("lm.block", "attn"), FWD),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/lm.block/norm/mul",
     ("lm.block", "norm"), REMAT),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/lm.block/attn/cos", ("lm.block", "attn"), BWD),
    ("jit(step)/lm.opt/mul", ("lm.opt",), FWD),
]
#: And what the chip's three step programs add to them.
ON_THE_CHIP = [
    # a Pallas kernel's own name is a segment: the fold's kernels are rows of their own
    ("jit(step)/transpose(jvp(lm.block))/fold/flash_fold_bwd_dq/pallas_call",
     ("lm.block", "fold", "flash_fold_bwd_dq"), BWD),
    # the primitive is called transpose; the direction is not read from it
    ("jit(run)/lm.block/fold/transpose", ("lm.block", "fold"), FWD),
    # a nested jit is a library function's inside
    ("jit(step)/jvp(lm.head)/while/body/closed_call/jit(take_along_axis)/gather", ("lm.head",), FWD),
    ("jit(step)/jvp(lm.exit)/jit(cumsum)/_exit_distribution/reduce_window_sum", ("lm.exit",), FWD),
    # two instructions merged by a pass: the first name is the root's
    ("jit(step)/transpose(jvp(lm.exit))/reshape;jit(step)/transpose(jvp(lm.head))/reshape", ("lm.exit",), BWD),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/lm.block/route/norm/rsqrt",
     ("lm.block", "route", "norm"), REMAT),
    ("jit(step)/jvp(lm.final_norm)/norm/mul", ("lm.final_norm", "norm"), FWD),
]


@pytest.mark.parametrize("op_name,scope,direction", PROBED + ON_THE_CHIP)
def test_an_op_name_classifies_by_its_segments(op_name, scope, direction):
    assert classify(op_name, "lm.") == (scope, direction)


@pytest.mark.parametrize("op_name", [None, "", "ragged-dot-none", "jit(step)/add", "params['lm_head']",
                                     "jit(step)/transpose(jvp())/while/body/closed_call/add_any"])
def test_a_name_without_the_root_is_unscoped(op_name):
    assert classify(op_name, "lm.") == (None, None)
    assert classify("jit(step)/lm.opt/mul", "train.") == (None, None)  # the root is a parameter


def test_an_entry_is_a_path_prefix_or_a_segments_name():
    norm = ("lm.block", "route", "norm")
    assert matches(norm, ["lm.block"]) and matches(norm, ["lm.block/route"]) and matches(norm, ["norm"])
    assert not matches(norm, ["lm.head"]) and not matches(norm, ["lm.block/norm"]) and not matches(norm, ["fold"])
    assert not matches(None, ["norm"]) and not matches(norm, [])
    assert not matches(("lm.final_norm", "norm"), ["lm.final"])  # whole segments, not characters


def test_only_operations_of_the_windows_step_programs_count_and_containers_do_not():
    t = _table()
    programs = op_scopes.step_programs(t["rows"], t["modules"], "flash_fold_fwd", *t["window"])
    assert programs == [(40000, 50000), (60000, 70000)]  # not the warm-up's, the init's or the reference's
    ops = op_scopes.step_ops(t["rows"], programs, "lm.", RENAMED)
    assert len(ops) == 2 * 12 and "while.1" not in {op.name for op in ops}
    assert sum(op.dur for op in ops) == 2 * 9900
    assert "fusion.9" not in {op.name for op in ops}  # lm.head, but the reference program's


def test_a_renamed_kernel_takes_its_metrics_scope_and_its_neighbours_direction():
    t = _table()
    ops = op_scopes.step_ops(t["rows"], [(40000, 50000)], "lm.", RENAMED)
    by = {op.name: op for op in ops}
    assert by["ragged-dot-none.1"].scope == ("lm.block", "experts") and by["ragged-dot-none.1"].direction == FWD
    # lm.head's recomputation ran between: another outermost scope does not lend its direction
    assert by["ragged-dot-none.2"].direction == BWD
    assert by["copy.1"].scope is None and by["fusion.8"].scope is None
    # without the metric's word the kernels stay unscoped
    bare = {op.name: op for op in op_scopes.step_ops(t["rows"], [(40000, 50000)], "lm.")}
    assert bare["ragged-dot-none.1"].scope is None and bare["ragged-dot-none.1"].direction is None


def test_milliseconds_per_step_by_scope_and_direction():
    ctx = Ctx(_table())
    ms = lambda **kw: scope_ms_per_unit.reduce(ctx, "flash_fold_fwd", "steps", renamed=RENAMED, **kw)  # noqa: E731
    assert ms(scopes=["lm.head"]) == pytest.approx((1500 + 1500 + 500) / 1e6)
    assert ms(scopes=["lm.head"], direction="remat") == pytest.approx(500 / 1e6)
    assert ms(scopes=["permute"]) == pytest.approx(1000 / 1e6)
    assert ms(scopes=["lm.block"]) == pytest.approx((1000 + 500 + 1000 + 500 + 500) / 1e6)
    assert ms(scopes=["lm.block"], direction="bwd") == pytest.approx(1000 / 1e6)
    assert ms(scopes=["lm.opt", "lm.embed"]) == pytest.approx(2000 / 1e6)
    assert ms(scopes=["lm.block"], direction="remat") is None  # a lone block is not checkpointed
    assert ms(scopes=["conv"]) is None
    # a step count the run did not report
    assert scope_ms_per_unit.reduce(Ctx(_table(), steps=0), "flash_fold_fwd", "steps", ["lm.head"]) is None


def test_coverage_is_scoped_time_over_all_of_the_step_programs():
    ctx = Ctx(_table())
    assert scope_coverage_pct.reduce(ctx, "flash_fold_fwd", "lm.", RENAMED) == pytest.approx(100 * 9000 / 9900)
    # the renamed kernels unnamed: 1,500 ns a step less
    assert scope_coverage_pct.reduce(ctx, "flash_fold_fwd") == pytest.approx(100 * 7500 / 9900)


@pytest.mark.parametrize("strip", ["every op_name", "the scopes"])
def test_a_program_without_scopes_reads_none(strip):
    """The CPU rehearsal's events carry no ``op_name``; the parent's carry
    JAX's own segments and no scope."""
    t = _table()
    rows = [[n, s, d, None if strip == "every op_name" or name is None else
             name.replace("lm.", "").replace("//", "/")] for n, s, d, name in t["rows"]]
    ctx = Ctx(t, rows=rows)
    assert scope_coverage_pct.reduce(ctx, "flash_fold_fwd", "lm.", RENAMED) is None
    assert scope_ms_per_unit.reduce(ctx, "flash_fold_fwd", "steps", ["lm.head"], renamed=RENAMED) is None
    assert scope_ms_per_unit.reduce(ctx, "flash_fold_fwd", "steps", ["permute"]) is None
    # no device, no modules: the rehearsal
    assert scope_coverage_pct.reduce(Ctx({**t, "modules": []}), "flash_fold_fwd") is None


def test_the_tools_rows_add_up_to_the_step():
    t = _table()
    ops = op_scopes.step_ops(t["rows"], [(40000, 50000), (60000, 70000)], "lm.", RENAMED)
    rows = tool.table(ops, 2)
    assert sum(r["ms"] for r in rows) == pytest.approx(9900 / 1e6)
    by = {r["scope"]: r for r in rows}
    head = by[("lm.head",)]
    assert (head["fwd"], head["remat"], head["bwd"]) == pytest.approx((3000 / 1e6, 500 / 1e6, 0.0))
    assert head["ops"] == pytest.approx(3) and head["kinds"] == pytest.approx({"fusion": 3500 / 1e6})
    assert by[("lm.block", "experts")]["kinds"] == pytest.approx({"ragged-dot-none": 1500 / 1e6})
    assert by[("lm.block", "fold", "flash_fold_fwd")]["ms"] == pytest.approx(1000 / 1e6)
    # the unscoped operations keep their numbers: they are looked up by them
    assert by[None]["kinds"] == pytest.approx({"copy.1": 500 / 1e6, "fusion.8": 400 / 1e6})


def test_a_step_the_chip_ran_adds_up():
    """One step program of ``olmoe_1b_7b.fit_packed4k`` as recorded on the chip
    (``data/op_scopes_olmoe_step.json``): the rows add up to the step program's
    own time, the fold's kernels and the grouped expert matmuls are the numbers
    ``attn_ms`` and ``moe_expert_ms`` read by name, and the largest operations
    of the ledger's ``device_ops`` have a scope."""
    with open(os.path.join(HERE, "data", "op_scopes_olmoe_step.json"), encoding="utf-8") as f:
        t = json.load(f)
    ops = op_scopes.step_ops(t["rows"], [tuple(t["module"])], "lm.", RENAMED)
    rows = tool.table(ops, 1)
    by = {r["scope"]: r for r in rows}
    total = sum(r["ms"] for r in rows)
    assert total == pytest.approx(t["module"][1] / 1e6, rel=1e-3)  # 248.49 of 248.51 ms
    assert 100 * (total - by[None]["ms"]) / total == pytest.approx(97.71, abs=0.01)
    head = by[("lm.head",)]
    assert (head["fwd"], head["remat"], head["bwd"]) == pytest.approx((23.304, 22.133, 43.342), abs=1e-3)
    assert head["kinds"]["bitcast_dynamic-update-slice_fusion"] == pytest.approx(22.55, abs=0.01)  # dh
    kernels = sum(by[("lm.block", "fold", k)]["kinds"][k] for k in
                  ("flash_fold_fwd", "flash_fold_bwd_dq", "flash_fold_bwd_dkv"))
    assert kernels == pytest.approx(11.943, abs=1e-3)
    experts = by[("lm.block", "experts")]
    assert experts["kinds"]["ragged-dot-none"] == pytest.approx(47.349, abs=1e-3)
    # three grouped matmuls forward, six backward; a lone block is not recomputed
    grouped = [op.direction for op in ops if op.name.startswith("ragged-dot-none")]
    assert grouped.count(FWD) == 3 and grouped.count(BWD) == 6 and experts["remat"] == 0.0
    named = {op.name: op for op in ops}
    assert named["fusion.238"].scope == named["fusion.236"].scope == named["fusion.228"].scope == ("lm.head",)
    assert (named["fusion.238"].direction, named["fusion.236"].direction, named["fusion.228"].direction) == \
        (BWD, REMAT, FWD)
    assert named["fusion.58"].scope == ("lm.opt",)


# -- the file itself: a trace written field by field, as the chip's profiler lays it out --


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _plane(name, lines=(), event_metadata=(), stat_metadata=()):
    """An ``XPlane``: ``event_metadata`` rows ``(id, name, [stat, ...])``, ``lines`` rows
    ``(name, timestamp_ns, [(metadata_id, offset_ps, duration_ps), ...])``."""
    out = _field(2, name)
    for line_name, stamp, events in lines:
        body = _field(2, line_name) + _field(3, stamp)
        for metadata_id, offset, dur in events:
            body += _field(4, _field(1, metadata_id) + _field(2, offset) + _field(3, dur))
        out += _field(3, body)
    for i, text, stats in event_metadata:
        meta = _field(1, i) + _field(2, text) + b"".join(_field(5, st) for st in stats)
        out += _field(4, _field(1, i) + _field(2, meta))
    for i, text in stat_metadata:
        out += _field(5, _field(1, i) + _field(2, _field(1, i) + _field(2, text)))
    return _field(1, out)


TF_OP, FLOPS, A_NAME = 7, 8, 9
FUSED = "%fusion.2 = bf16[2048,64]{1,0:T(8,128)(2,1)} fusion(f32[2048,64]{1,0} %p), kind=kLoop, calls=%fc.2"
KERNEL = "%flash_fold_fwd.1 = (f32[64,4096,1]{2,1,0}) custom-call(s32[3]{0} %b), custom_call_target=\"tpu_custom_call\""
SHARED = "%copy.3 = f32[8]{0} copy(f32[8]{0} %p)"


def _written_trace(tmp_path):
    device = _plane(
        "/device:TPU:0",
        lines=[("XLA Modules", 1000, [(1, 0, 9_000_000)]),
               ("XLA Ops", 1000, [(2, 0, 1_000_000), (3, 1_000_000, 2_000_000), (4, 3_000_000, 500_000),
                                  (5, 4_000_000, 500_000), (6, 5_000_000, 250_000)])],
        event_metadata=[
            (1, "jit_step(123)", []),
            (2, FUSED, [_field(1, FLOPS) + _field(3, 42), _field(1, TF_OP) + _field(5, "jit(step)/lm.opt/mul:")]),
            # the name as a reference into stat_metadata, the form a profiler uses for a repeated string
            (3, KERNEL, [_field(1, TF_OP) + _field(7, A_NAME)]),
            (4, "%copy-start.1 = (f32[8]{0}) copy-start(f32[8]{0} %p)", [_field(1, FLOPS) + _field(3, 0)]),
            # one text in two programs under two names: it says nothing
            (5, SHARED, [_field(1, TF_OP) + _field(5, "jit(step)/jvp(lm.head)/copy:")]),
            (6, SHARED, [_field(1, TF_OP) + _field(5, "jit(reference)/copy:")]),
        ],
        stat_metadata=[(TF_OP, "tf_op"), (FLOPS, "flops"),
                       (A_NAME, "jit(step)/jvp(lm.block)/fold/flash_fold_fwd/pallas_call:")])
    host = _plane("/host:CPU", event_metadata=[(1, FUSED, [_field(1, TF_OP) + _field(5, "not/the/device:")])],
                  stat_metadata=[(TF_OP, "tf_op")])
    where = tmp_path / "plugins" / "profile" / "2026_01_01"
    where.mkdir(parents=True)
    (where / "vm.xplane.pb").write_bytes(host + device)
    return str(tmp_path)


def test_the_name_is_read_from_the_operations_event_metadata(tmp_path):
    trace_dir = _written_trace(tmp_path)
    names = op_scopes.op_names(xplane.find_xplane(trace_dir))
    assert names[FUSED] == "jit(step)/lm.opt/mul"  # "<op_name>:<op_type>", the type empty
    assert names[KERNEL] == "jit(step)/jvp(lm.block)/fold/flash_fold_fwd/pallas_call"
    assert names["%copy-start.1 = (f32[8]{0}) copy-start(f32[8]{0} %p)"] is None
    assert names[SHARED] is None and names["jit_step(123)"] is None
    rows = op_scopes.read(trace_dir)
    assert [r[0] for r in rows] == ["fusion.2", "flash_fold_fwd.1", "copy-start.1", "copy.3", "copy.3"]
    assert rows[0][1:] == [1000.0, 1000.0, "jit(step)/lm.opt/mul"] and rows[1][1:3] == [2000.0, 2000.0]
    assert classify(rows[1][3], "lm.") == (("lm.block", "fold", "flash_fold_fwd"), FWD)
    assert [r[3] for r in rows[2:]] == [None, None, None]


def test_a_trace_without_a_device_plane_has_no_names_and_no_rows(tmp_path):
    where = tmp_path / "plugins" / "profile" / "x"
    where.mkdir(parents=True)
    (where / "vm.xplane.pb").write_bytes(_plane("/host:CPU", lines=[("python", 5, [(1, 0, 10)])],
                                                event_metadata=[(1, "main", [])]))
    assert op_scopes.op_names(xplane.find_xplane(str(tmp_path))) == {}
    assert op_scopes.read(str(tmp_path)) == []


def test_the_tool_prints_a_written_traces_table(tmp_path, capsys):
    device = _plane(
        "/device:TPU:0",
        lines=[("XLA Modules", 1000, [(1, 0, 9_000_000)]),
               ("XLA Ops", 1000, [(2, 0, 1_000_000), (3, 1_000_000, 2_000_000), (4, 3_000_000, 500_000),
                                  (5, 4_000_000, 500_000)])],
        event_metadata=[
            (1, "jit_step(123)", []),
            (2, FUSED, [_field(1, TF_OP) + _field(5, "jit(step)/lm.opt/mul:")]),
            (3, KERNEL, [_field(1, TF_OP) + _field(5, "jit(step)/transpose(jvp(lm.block))/fold/flash_fold_fwd/pallas_call:")]),
            (4, "%ragged-dot-none.1 = bf16[8]{0} custom-call()", [_field(1, TF_OP) + _field(5, "ragged-dot-none:")]),
            (5, SHARED, []),
        ],
        stat_metadata=[(TF_OP, "tf_op")])
    host = _plane("/host:CPU", lines=[("main", 0, [(1, 0, 50_000_000)])], event_metadata=[(1, "window", [])])
    where = tmp_path / "plugins" / "profile" / "x"
    where.mkdir(parents=True)
    (where / "vm.xplane.pb").write_bytes(host + device)
    assert tool.main(["--workload", "olmoe_1b_7b.fit_packed4k", "--trace-dir", str(tmp_path)]) == 0
    lines = [ln.split(" ", 1) for ln in capsys.readouterr().out.splitlines()]
    rows = [json.loads(body) for kind, body in lines if kind == "scope"]
    assert [r["scope"] for r in rows] == ["lm.block/fold/flash_fold_fwd", "lm.opt", "lm.block/experts"]
    assert rows[2]["bwd"] == rows[2]["ms"] == 0.001 and rows[2]["kinds"] == {"ragged-dot-none": 0.001}
    assert [json.loads(body) for kind, body in lines if kind == "unscoped"] == [{"op": "copy.3", "ms": 0.001}]
    assert [json.loads(body) for kind, body in lines if kind == "unscoped_kinds"] == [{"copy": 0.001}]
    (step,) = [json.loads(body) for kind, body in lines if kind == "step"]
    assert step["steps"] == 1 and step["ops_ms"] == 0.004 and step["program_ms"] == 0.009
    assert step["coverage_pct"] == 87.5


def test_the_six_metrics_are_listed_for_the_lm_cells_and_read_the_configurations_step_marker():
    """(Seven until PR 53: nothing under ``lm.head`` is recomputed since PR 45, so its remat reading went.)"""
    m = Manifest()
    assert m.problems() == []
    lm = ["olmoe_1b_7b.fit_packed4k", "zaya1_8b.fit_packed8k", "ouro_2_6b.fit_looped4k"]
    for name in NEW:
        spec, entry = m.layer_metric(name), m.per_layer[name]
        assert set(entry["workloads"]) & set(lm) and entry["source"] == "device_trace"
        assert spec["params"]["holds"] == m.layer_metric("lm_step_ms")["params"]["holds"] == {"perf": "step_holds"}
        assert spec["params"]["renamed"] == {"perf": "renamed"}
    for cell in lm:
        perf = m.config(m.cells[cell]["config"])["perf"]
        assert perf["step_holds"] == "flash_fold_fwd" and perf["renamed"] == RENAMED
    assert m.per_layer["lm_block_remat_ms"]["workloads"] == lm[1:]  # OLMoE's lone block is not checkpointed
    assert m.per_layer["lm_permute_ms"]["workloads"][:2] == lm[:2]  # the ouro block has no experts
    assert lm[2] not in m.per_layer["lm_permute_ms"]["workloads"]
