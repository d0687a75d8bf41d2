"""The span tree of one ``Estimator.fit`` (scope ``ml.train``), at toy size on
the CPU: which phases a fit opens, under which parent and category, what they
count, and that ``tracer.phase`` reaches a profiler session nobody switched
the tracer on for. The contract is docs/observability.md, "The fit span tree".
"""
import ast
import glob
import json
import os

import numpy as np
import pytest

from flink_ml_tpu import trace
from flink_ml_tpu.api.dataframe import DataFrame
from flink_ml_tpu.iteration import DeviceDataCache
from flink_ml_tpu.linalg.vectors import SparseVector
from flink_ml_tpu.metrics import MLMetrics, metrics
from flink_ml_tpu.models.classification.logistic_regression import LogisticRegression
from flink_ml_tpu.ops.lossfunc import BinaryLogisticLoss
from flink_ml_tpu.ops.optimizer import SGD
from flink_ml_tpu.trace import (
    CAT_COMPILE,
    CAT_INGEST,
    CAT_PRODUCTIVE,
    CAT_READBACK,
    CATEGORIES,
    Span,
    tracer,
)
from tools.traceview import main as traceview_main

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPE = "ml.train"
N, DIM, K, BATCH, STEPS = 16384, 1 << 15, 8, 4096, 4

LAYOUT_CHILDREN = [
    "train.layout.prepare", "train.layout.count", "train.layout.plan",
    "train.layout.alloc", "train.layout.fill",
]
#: A sparse one-hot fit, in the order its phases open: (name, parent, category).
SPARSE_TREE = [
    ("train.fit", None, CAT_PRODUCTIVE),
    ("train.pack", "train.fit", CAT_INGEST),
    ("train.cache_put", "train.fit", CAT_INGEST),
    ("train.layout", "train.fit", CAT_INGEST),
    *[(name, "train.layout", CAT_INGEST) for name in LAYOUT_CHILDREN],
    ("train.layout_put", "train.fit", CAT_INGEST),
    ("train.premat", "train.fit", CAT_INGEST),
    ("train.program", "train.fit", CAT_COMPILE),
    ("train.dispatch", "train.fit", CAT_PRODUCTIVE),
    ("train.drain", "train.fit", CAT_PRODUCTIVE),
    ("train.readback", "train.fit", CAT_READBACK),
]
#: The phases every fused fit has, dense or sparse.
OUTER = ["train.fit", "train.pack", "train.cache_put", "train.program",
         "train.dispatch", "train.drain", "train.readback"]


@pytest.fixture(autouse=True)
def _tracer_off():
    tracer.disable()
    yield
    tracer.disable()


@pytest.fixture(scope="module")
def sparse_rows():
    rng = np.random.default_rng(7)
    idx = np.sort(rng.integers(0, DIM // K, (N, K)) * K + np.arange(K), axis=1)
    y = (rng.random(N) > 0.5).astype(np.float64)
    return idx, y


@pytest.fixture(scope="module")
def sparse_df(sparse_rows):
    idx, y = sparse_rows
    ones = np.ones(K)
    return DataFrame.from_dict(
        {"features": [SparseVector(DIM, row, ones) for row in idx], "label": y}
    )


def _estimator():
    return (
        LogisticRegression().set_max_iter(STEPS).set_global_batch_size(BATCH).set_tol(0.0)
    )


@pytest.fixture(scope="module")
def sparse_fit(sparse_df):
    """One traced sparse fit (after a first one, so that nothing compiles in
    it): the estimator and the spans it recorded, in the order they opened."""
    _estimator().fit(sparse_df)
    est = _estimator()
    with trace.capture() as recorder:
        est.fit(sparse_df)
    assert est.optimizer.onehot_premat_active
    return est, sorted(recorder.snapshot(), key=lambda s: s.span_id)


def _by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s.name, []).append(s)
    return out


def _fill_choices(attrs):
    """``train.layout.fill``'s counts less the three its loop times itself
    (``workers``, ``unit_us``, ``wall_us``: tests/test_onehot_sparse.py)."""
    attrs = dict(attrs)
    assert 1 <= attrs.pop("workers") <= attrs["units"]
    assert attrs.pop("unit_us") > 0 and attrs.pop("wall_us") > 0
    return attrs


def _children_s(spans, parent):
    return sum(s.duration for s in spans if s.parent_id == parent.span_id)


class TestSparseFitTree:
    def test_names_parents_and_categories(self, sparse_fit):
        _, spans = sparse_fit
        names = {s.span_id: s.name for s in spans}
        got = [(s.name, names.get(s.parent_id), s.category) for s in spans]
        assert got == SPARSE_TREE
        assert {s.scope for s in spans} == {SCOPE}
        assert len({s.thread_id for s in spans}) == 1

    def test_counts_are_what_the_arrays_say(self, sparse_fit):
        _, spans = sparse_fit
        one = {name: group[0].attrs for name, group in _by_name(spans).items()}
        assert one["train.fit"] == {"rows": N, "dim": DIM}
        assert one["train.pack"] == {
            "rows": N, "sparse": 1, "nnz": N * K, "width": K, "ragged_rows": 0,
        }
        # indices int32 + values f32 [N, K], labels + weights + mask f32 [N]
        assert one["train.cache_put"] == {"columns": 4, "bytes": N * K * 8 + 3 * N * 4}
        layout = one["train.layout"]
        assert layout["reused"] == 0 and layout["rows"] == N and layout["units"] >= 1
        assert one["train.layout.count"] == {"units": layout["units"]}
        # the fill's two choices: 16-bit sort keys (DIM / 128 blocks), no unit masked
        assert _fill_choices(one["train.layout.fill"]) == {
            "units": layout["units"], "key_bits": 16, "masked": 0,
        }
        stack_bytes = one["train.layout.alloc"]["bytes"]
        assert stack_bytes % (7 * layout["units"]) == 0  # 7 B a slot: int8 + int16 + f32
        # the plan of uniform ids: power-of-two classes alone, slots over the floor
        plan = one["train.layout.plan"]
        assert plan["classes"] >= 1 and plan["chunked_blocks"] == plan["chunks"] == 0
        assert plan["n_flat"] == stack_bytes // (7 * layout["units"])
        assert N * K // layout["units"] <= plan["max_sum"] <= plan["n_flat"]
        assert one["train.layout_put"] == {"bytes": stack_bytes}
        assert one["train.premat"]["reused"] == 0 and one["train.premat"]["active"] == 1
        assert one["train.premat"]["bytes"] > stack_bytes
        # 4 steps over a shard's 4 windows: none visited twice, so the ids are unpacked in the body
        assert one["train.program"] == {"built": 0, "steps": STEPS, "lane_unpacks": STEPS}
        assert one["train.dispatch"] == {"steps": STEPS} == one["train.drain"]
        assert one["train.readback"]["bytes"] >= DIM * 4

    def test_layout_children_cover_it_and_the_fit_has_little_self_time(self, sparse_fit, sparse_df):
        _, spans = sparse_fit
        by = _by_name(spans)
        (fit,), (layout,) = by["train.fit"], by["train.layout"]
        # the build lasts a few milliseconds here, so one descheduling between
        # two children is a large share of it: the least gap of a few fits
        gaps = [1 - _children_s(spans, layout) / layout.duration]
        while gaps[-1] > 0.10 and len(gaps) < 5:
            with trace.capture() as recorder:
                _estimator().fit(sparse_df)
            again = recorder.snapshot()
            (lay,) = _by_name(again)["train.layout"]
            gaps.append(1 - _children_s(again, lay) / lay.duration)
        assert 0 <= min(gaps) <= 0.10
        assert fit.duration - _children_s(spans, fit) < 0.10 * fit.duration
        for s in spans:
            if s is not fit:
                assert fit.start <= s.start and s.end <= fit.end

    def test_a_fit_of_padded_rows_masks_every_unit_and_counts_one_build(self):
        """Rows of unequal length reach the layout as padded CSR (value 0):
        the fill then takes its mask, says so per unit, and the build is
        still one, under the same five children."""
        rng = np.random.default_rng(11)
        n = 8192  # x K entries: the least the auto gate sends down the one-hot route
        rows = [
            np.sort(rng.choice(DIM, size=rng.integers(2, K + 1), replace=False))
            for _ in range(n)
        ]
        df = DataFrame.from_dict({
            "features": [SparseVector(DIM, r, np.ones(len(r))) for r in rows],
            "label": (rng.random(n) > 0.5).astype(np.float64),
        })
        est = LogisticRegression().set_max_iter(2).set_global_batch_size(2048).set_tol(0.0)
        builds = metrics.get(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LAYOUT_BUILDS) or 0
        with trace.capture() as recorder:
            est.fit(df)
        spans = recorder.snapshot()
        by = _by_name(spans)
        (layout,), (fill,) = by["train.layout"], by["train.layout.fill"]
        assert _fill_choices(fill.attrs) == {
            "units": layout.attrs["units"], "key_bits": 16, "masked": layout.attrs["units"],
        }
        children = [s for s in spans if s.parent_id == layout.span_id]
        assert sorted(s.name for s in children) == sorted(LAYOUT_CHILDREN)
        assert metrics.get(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LAYOUT_BUILDS) == builds + 1

    def test_a_fit_with_a_crowded_block_counts_its_chunks(self, sparse_rows):
        """A third of the entries in one block of 128 ids: the plan chunks it,
        says so on ``train.layout.plan``, and the registry counts the chunks."""
        from flink_ml_tpu.linalg.onehot_sparse import CHUNK

        idx, y = sparse_rows
        idx = idx.copy()
        idx[::3, 0] = np.arange(len(idx[::3])) % 128  # ids 0..127, ahead of the row's others
        idx[::3, 1:] = np.maximum(idx[::3, 1:], 128)
        idx.sort(axis=1)
        df = DataFrame.from_dict({
            "features": [SparseVector(DIM, row, np.ones(K)) for row in idx], "label": y,
        })
        chunks = metrics.get(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LAYOUT_CHUNKS) or 0
        with trace.capture() as recorder:
            _estimator().fit(df)
        by = _by_name(recorder.snapshot())
        (plan,), (layout,) = by["train.layout.plan"], by["train.layout"]
        per_unit = N // layout.attrs["units"] // 3  # a unit's rows that hold an id of the block
        assert plan.attrs["chunked_blocks"] >= 1
        assert plan.attrs["chunks"] >= -(-per_unit // CHUNK)
        assert plan.attrs["max_sum"] <= plan.attrs["n_flat"] < plan.attrs["max_sum"] * 2
        assert (
            metrics.get(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LAYOUT_CHUNKS)
            == chunks + plan.attrs["chunks"]
        )

    def test_goodput_report_sums_to_the_fits_wall(self, sparse_fit):
        _, spans = sparse_fit
        report = trace.GoodputReport.from_spans(spans)
        (fit,) = _by_name(spans)["train.fit"]
        assert report.wall_s(SCOPE) == pytest.approx(fit.duration, rel=1e-9)
        assert report.category_s(SCOPE, CAT_INGEST) > 0
        assert 0 < report.fraction(SCOPE) < 1
        assert set(report.totals[SCOPE]) <= set(CATEGORIES)


class TestReuse:
    def test_a_second_optimize_over_one_cache_reuses_layout_and_premat(self, sparse_rows):
        idx, y = sparse_rows
        cache = DeviceDataCache({
            "indices": idx.astype(np.int32), "values": np.ones((N, K), np.float32),
            "labels": y.astype(np.float32), "weights": np.ones(N, np.float32),
        })
        sgd = SGD(max_iter=STEPS, global_batch_size=BATCH, tol=0.0)
        builds = metrics.get(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LAYOUT_BUILDS) or 0
        reuses = metrics.get(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LAYOUT_REUSES) or 0
        premats = metrics.get(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_PREMAT_BUILDS) or 0
        first = sgd.optimize(np.zeros(DIM, np.float32), cache, BinaryLogisticLoss.INSTANCE)
        with trace.capture() as recorder:
            second = sgd.optimize(np.zeros(DIM, np.float32), cache, BinaryLogisticLoss.INSTANCE)
        by = _by_name(recorder.snapshot())
        assert by["train.layout"][0].attrs["reused"] == 1
        assert by["train.layout"][0].attrs["units"] >= 1
        assert by["train.premat"][0].attrs["reused"] == 1
        assert not any(name.startswith("train.layout.") for name in by)
        assert "train.layout_put" not in by and "train.cache_put" not in by
        np.testing.assert_array_equal(first, second)
        assert metrics.get(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LAYOUT_BUILDS) == builds + 1
        assert metrics.get(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LAYOUT_REUSES) == reuses + 1
        assert metrics.get(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_PREMAT_BUILDS) == premats + 1

    def test_a_second_estimator_fit_builds_again(self, sparse_df, sparse_fit):
        """``LinearEstimatorBase.fit`` packs and places the data anew, so the
        memo on the cache never answers: what ``layout_reuse_pct`` reads as 0."""
        put = metrics.get(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_H2D_BYTES)
        with trace.capture() as recorder:
            _estimator().fit(sparse_df)
        by = _by_name(recorder.snapshot())
        assert by["train.layout"][0].attrs["reused"] == 0
        assert by["train.premat"][0].attrs["reused"] == 0
        assert [s.name for s in recorder.snapshot() if s.name.startswith("train.layout.")]
        handed = by["train.cache_put"][0].attrs["bytes"] + by["train.layout_put"][0].attrs["bytes"]
        assert metrics.get(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_H2D_BYTES) == put + handed


def test_the_dense_fused_fit_has_the_outer_phases():
    rng = np.random.default_rng(3)
    X = rng.standard_normal((512, 16)).astype(np.float32)
    df = DataFrame.from_dict({"features": X, "label": (X[:, 0] > 0).astype(np.float64)})
    est = LogisticRegression().set_max_iter(3).set_global_batch_size(128).set_tol(0.0)
    with trace.capture() as recorder:
        est.fit(df)
    spans = sorted(recorder.snapshot(), key=lambda s: s.span_id)
    assert [s.name for s in spans] == OUTER
    by = _by_name(spans)
    assert by["train.pack"][0].attrs == {"rows": 512, "sparse": 0, "nnz": 512 * 16}
    assert by["train.fit"][0].attrs == {"rows": 512, "dim": 16}
    assert by["train.program"][0].attrs["built"] in (0, 1)
    assert all(s.parent_id == by["train.fit"][0].span_id for s in spans[1:])


class TestPhaseContract:
    def test_tracer_off_a_fit_records_nothing_and_phase_hands_back_no_span(self, sparse_df):
        recorder = trace.SpanRecorder(64)
        tracer.recorder = recorder
        _estimator().fit(sparse_df)
        assert len(recorder) == 0 and recorder.recorded == 0
        phase = tracer.phase("train.layout", CAT_INGEST, reused=0)
        assert not isinstance(phase, Span)
        with phase as opened:  # the bare annotation: a no-op outside a profiler session
            opened.set_metadata(units=3)
        assert tracer.current() is None

    def test_tracer_on_phase_is_a_recorded_span_with_counts_and_late_counts(self):
        with trace.capture(xprof=False) as recorder:
            with tracer.phase("outer", CAT_PRODUCTIVE, scope="s") as outer:
                with tracer.phase("inner", CAT_INGEST, scope="s", rows=5) as inner:
                    assert inner._annotation is not None  # entered whatever xprof says
                    inner.set_metadata(nnz=9)
                with tracer.span("plain", scope="s") as plain:
                    assert plain._annotation is None
        spans = {s.name: s for s in recorder.snapshot()}
        assert isinstance(outer, Span) and spans["inner"].parent_id == outer.span_id
        assert spans["inner"].attrs == {"rows": 5, "nnz": 9}
        assert spans["inner"].category == CAT_INGEST and spans["outer"].attrs is None

    def test_phases_reach_a_profiler_session_nobody_switched_the_tracer_on_for(
        self, sparse_df, tmp_path
    ):
        import jax
        from jax.profiler import ProfileData

        assert not tracer.enabled
        jax.profiler.start_trace(str(tmp_path))
        try:
            _estimator().fit(sparse_df)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
        events = {}
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith("train."):
                            events.setdefault(ev.name, []).append(
                                (ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
                            )
        assert set(events) == {name for name, _, _ in SPARSE_TREE}
        (f0, f1, fit_stats), = events["train.fit"]
        assert fit_stats == {"rows": N, "dim": DIM}
        assert events["train.pack"][0][2] == {
            "rows": N, "sparse": 1, "nnz": N * K, "width": K, "ragged_rows": 0,
        }
        assert events["train.layout"][0][2]["reused"] == 0
        units = events["train.layout"][0][2]["units"]
        assert _fill_choices(events["train.layout.fill"][0][2]) == {
            "units": units, "key_bits": 16, "masked": 0,
        }
        for name, group in events.items():
            for a, b, _ in group:
                assert f0 <= a and b <= f1, name

    def test_ingest_is_a_category_the_exporters_take(self, sparse_fit, tmp_path, capsys):
        _, spans = sparse_fit
        assert CAT_INGEST in CATEGORIES
        recorder = trace.SpanRecorder(256)
        for s in spans:
            recorder.record(s)
        path = str(tmp_path / "fit.json")
        assert recorder.export_chrome_trace(path) == len(spans)
        cats = {e["cat"] for e in json.load(open(path))["traceEvents"] if e.get("ph") == "X"}
        assert CAT_INGEST in cats
        assert traceview_main([path, "--scope", SCOPE]) == 0
        out = capsys.readouterr().out
        assert "ingest" in out and "train.layout.fill" in out


# -- where tracer.phase may be called -----------------------------------------

#: file -> the functions of docs/observability.md's table. A phase costs an
#: allocation when the tracer is off, so it belongs to sites that run a
#: bounded number of times per job; anything hotter uses ``tracer.span``.
PHASE_SITES = {
    "flink_ml_tpu/models/linear.py": {"fit"},
    "flink_ml_tpu/models/common.py": {"extract_labeled_data"},
    "flink_ml_tpu/iteration/datacache.py": {"__init__"},
    "flink_ml_tpu/linalg/onehot_sparse.py": {"build"},
    "flink_ml_tpu/ops/optimizer.py": {
        "optimize", "_optimize_onehot", "_onehot_layout", "_premat_onehots",
        "_optimize_streaming_onehot",  # ``train.program`` alone: its counts say where the lane ids are unpacked
    },
    # the LM fit's tree (docs/observability.md "The LM fit"; tests/test_lm_fit_trace.py)
    "flink_ml_tpu/models/lm/decoder_lm.py": {"fit", "_fit"},
}
#: The two phases only the LM fit opens.
LM_ONLY_PHASES = {"train.tokens_put", "train.init"}
#: The only loop a phase may sit in: one turn per dispatched chunk of steps.
CHUNK_LOOP_PHASES = {"train.dispatch", "train.drain"}


def _phase_calls():
    """(file, enclosing function, phase name, inside a loop) of every
    ``tracer.phase(...)`` call in the package."""
    found = []
    for path in glob.glob(os.path.join(ROOT, "flink_ml_tpu", "**", "*.py"), recursive=True):
        rel = os.path.relpath(path, ROOT).replace(os.sep, "/")
        tree = ast.parse(open(path, encoding="utf-8").read())

        def walk(node, func, in_loop):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    walk(child, child.name, False)
                    continue
                if (
                    isinstance(child, ast.Call)
                    and isinstance(child.func, ast.Attribute)
                    and child.func.attr == "phase"
                    and isinstance(child.func.value, ast.Name)
                    and child.func.value.id == "tracer"
                ):
                    found.append((rel, func, child.args[0].value, in_loop))
                walk(child, func, in_loop or isinstance(child, (ast.For, ast.While)))

        walk(tree, None, False)
    return found


def test_phase_is_called_only_at_the_sites_of_the_table():
    calls = _phase_calls()
    assert {name for _, _, name, _ in calls} == {name for name, _, _ in SPARSE_TREE} | LM_ONLY_PHASES
    for rel, func, name, _ in calls:
        assert func in PHASE_SITES.get(rel, ()), f"{name} opened in {rel}::{func}"
    assert {rel for rel, _, _, _ in calls} == set(PHASE_SITES)


def test_phase_sits_in_no_loop_but_the_chunk_loop():
    in_loops = {(rel, func, name) for rel, func, name, in_loop in _phase_calls() if in_loop}
    assert {name for _, _, name in in_loops} == CHUNK_LOOP_PHASES
    assert {(rel, func) for rel, func, _ in in_loops} == {
        ("flink_ml_tpu/ops/optimizer.py", "optimize"),
        ("flink_ml_tpu/ops/optimizer.py", "_optimize_onehot"),
    }
