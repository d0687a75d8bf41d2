"""graftscope (flink_ml_tpu/trace.py) — the tracing + goodput contract:

- **disabled is free**: zero spans recorded, the shared no-op span, no
  per-request span allocation on the serving path (structure and counts;
  what tracing costs when it is on is a chip reading, ``PERF.md`` §6, PR 24);
- **span model**: thread-local nesting, manual begin/end, retro recording,
  parent-ID integrity across the MicroBatcher thread handoff, ring-buffer
  wraparound under a multi-threaded soak;
- **serving tree**: one request → queue → batch → pad/dispatch/readback/
  respond, children nested inside their parents;
- **goodput**: per-scope category totals sum to root-span wall time,
  padding split from rows vs bucket, ``ml.goodput.*`` gauges;
- **exporters**: Chrome trace-event JSON schema, Prometheus text exposition
  (golden), ``Histogram.quantiles`` single-sort batch, and the
  ``tools/traceview.py`` CLI (exit codes + summary) on a seeded trace.
"""
import json
import threading

import numpy as np
import pytest

from flink_ml_tpu import trace
from flink_ml_tpu.api.dataframe import DataFrame
from flink_ml_tpu.config import Options, config
from flink_ml_tpu.metrics import Histogram, MetricsRegistry, MLMetrics, metrics
from flink_ml_tpu.trace import (
    CAT_COMPILE,
    CAT_PADDING,
    CAT_PRODUCTIVE,
    CAT_QUEUE,
    CAT_READBACK,
    CATEGORIES,
    GoodputReport,
    Span,
    SpanRecorder,
    Tracer,
    tracer,
)

from tools.traceview import main as traceview_main


@pytest.fixture(autouse=True)
def _tracer_off():
    """Every test starts and ends with the tracer in its default state."""
    tracer.disable()
    yield
    tracer.disable()


def _span(name, category, scope, start, end, span_id, parent_id=None, attrs=None):
    s = Span(name, category, scope, start, span_id, parent_id, 1, "t")
    s.end = end
    if attrs:
        s.attrs = dict(attrs)
    return s


def _serve(n_requests=6, rows=3, name="t-trace", threads=1, max_batch=8):
    """Drive a tiny logistic servable through the real serving path."""
    from flink_ml_tpu.servable.lib import LogisticRegressionModelServable
    from flink_ml_tpu.serving import InferenceServer, ServingConfig

    rng = np.random.default_rng(3)
    dim = 8
    servable = LogisticRegressionModelServable().set_features_col("features")
    servable.coefficient = rng.standard_normal(dim).astype(np.float32)
    X = rng.standard_normal((64, dim)).astype(np.float32)
    server = InferenceServer(
        servable,
        name=name,
        serving_config=ServingConfig(
            max_batch_size=max_batch, max_delay_ms=0.5, default_timeout_ms=60_000
        ),
        warmup_template=DataFrame.from_dict({"features": X[:1]}),
    )
    try:
        if threads == 1:
            for i in range(n_requests):
                server.predict(
                    DataFrame.from_dict({"features": X[i * rows : (i + 1) * rows]})
                )
        else:
            def client(tid):
                for i in range(n_requests):
                    j = (tid * 17 + i * rows) % (X.shape[0] - rows)
                    server.predict(DataFrame.from_dict({"features": X[j : j + rows]}))

            ts = [threading.Thread(target=client, args=(t,)) for t in range(threads)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
    finally:
        server.close()
    return server


# ---------------------------------------------------------------------------
# disabled path: zero spans, zero allocation
# ---------------------------------------------------------------------------


class TestDisabledPath:
    def test_span_returns_the_shared_noop(self):
        assert not tracer.enabled
        a = tracer.span("x", CAT_QUEUE, scope="s")
        b = tracer.span("y")
        assert a is b is trace._NOOP_SPAN  # same object — no allocation
        with a as sp:
            assert sp.set_attr("k", 1) is sp

    def test_begin_returns_none_and_end_is_none_safe(self):
        assert tracer.begin("x") is None
        tracer.end(None)  # no-op
        tracer.record("x", CAT_QUEUE, "s", 0.0, 1.0)  # dropped
        assert len(tracer.recorder) == 0

    def test_serving_path_records_nothing_and_allocates_no_request_span(self):
        before = tracer.recorder.recorded
        from flink_ml_tpu.servable.lib import LogisticRegressionModelServable
        from flink_ml_tpu.serving import InferenceServer, ServingConfig

        rng = np.random.default_rng(0)
        servable = LogisticRegressionModelServable().set_features_col("features")
        servable.coefficient = rng.standard_normal(4).astype(np.float32)
        X = rng.standard_normal((8, 4)).astype(np.float32)
        server = InferenceServer(
            servable,
            name="t-trace-off",
            serving_config=ServingConfig(max_batch_size=4, max_delay_ms=0.2),
            warmup_template=DataFrame.from_dict({"features": X[:1]}),
        )
        try:
            handle = server.submit(DataFrame.from_dict({"features": X[:2]}))
            assert handle.trace is None  # no per-request span allocation
            handle.result()
        finally:
            server.close()
        assert tracer.recorder.recorded == before  # zero spans recorded

    def test_config_option_defaults_off(self):
        assert config.get(Options.OBSERVABILITY_TRACE) is False
        assert config.get(Options.OBSERVABILITY_TRACE_CAPACITY) == 65_536


# ---------------------------------------------------------------------------
# span model
# ---------------------------------------------------------------------------


class TestSpanModel:
    def test_context_manager_nesting_sets_parent_ids(self):
        with trace.capture() as rec:
            with tracer.span("outer", CAT_PRODUCTIVE, scope="s") as outer:
                with tracer.span("inner", CAT_COMPILE, scope="s") as inner:
                    assert tracer.current() is inner
                assert tracer.current() is outer
        spans = {s.name: s for s in rec.snapshot()}
        assert spans["inner"].parent_id == spans["outer"].span_id
        assert spans["outer"].parent_id is None
        assert spans["inner"].end >= spans["inner"].start
        assert spans["inner"].category == CAT_COMPILE

    def test_manual_begin_end_and_explicit_parent(self):
        with trace.capture() as rec:
            root = tracer.begin("root", CAT_PRODUCTIVE, scope="s")
            with tracer.span("child", CAT_QUEUE, scope="s", parent=root):
                pass
            tracer.end(root)
            tracer.end(root)  # idempotent: second end does not re-record
        spans = rec.snapshot()
        assert [s.name for s in spans] == ["child", "root"]
        assert spans[0].parent_id == spans[1].span_id

    def test_record_retro_inherits_parent_thread_identity(self):
        with trace.capture() as rec:
            root = tracer.begin("root", CAT_PRODUCTIVE, scope="s")
            captured = {}

            def other_thread():
                tracer.record("q", CAT_QUEUE, "s", 1.0, 2.0, parent=root)
                captured["tid"] = threading.get_ident()

            t = threading.Thread(target=other_thread)
            t.start()
            t.join()
            tracer.end(root)
        q = [s for s in rec.snapshot() if s.name == "q"][0]
        assert q.thread_id == root.thread_id != captured["tid"]
        assert q.parent_id == root.span_id
        assert (q.start, q.end) == (1.0, 2.0)

    def test_exception_exit_records_error_attr(self):
        with trace.capture() as rec:
            with pytest.raises(ValueError):
                with tracer.span("boom", scope="s"):
                    raise ValueError("x")
        (s,) = rec.snapshot()
        assert s.attrs["error"] == "ValueError"

    def test_ring_wraparound_keeps_newest(self):
        with trace.capture(capacity=8) as rec:
            for i in range(20):
                with tracer.span(f"s{i}", scope="s"):
                    pass
        assert len(rec) == 8
        assert rec.recorded == 20
        assert rec.dropped == 12
        assert [s.name for s in rec.snapshot()] == [f"s{i}" for i in range(12, 20)]

    def test_recorder_rejects_degenerate_capacity(self):
        with pytest.raises(ValueError):
            SpanRecorder(0)

    def test_multithreaded_soak_ring_and_parent_integrity(self):
        n_threads, per_thread = 8, 120
        with trace.capture(capacity=n_threads * per_thread * 2) as rec:
            barrier = threading.Barrier(n_threads)

            def worker(tid):
                barrier.wait()
                for i in range(per_thread):
                    with tracer.span(f"outer-{tid}", scope=f"s{tid}") as outer:
                        with tracer.span(f"inner-{tid}", scope=f"s{tid}") as inner:
                            assert inner.parent_id == outer.span_id

            ts = [threading.Thread(target=worker, args=(t,)) for t in range(n_threads)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
        spans = rec.snapshot()
        assert len(spans) == n_threads * per_thread * 2
        by_id = {s.span_id: s for s in spans}
        ids = set(by_id)
        assert len(ids) == len(spans)  # unique ids across threads
        for s in spans:
            if s.name.startswith("inner"):
                parent = by_id[s.parent_id]
                # the parent is the same thread's outer span, same scope
                assert parent.name == f"outer-{s.name.split('-')[1]}"
                assert parent.thread_id == s.thread_id
            assert s.end is not None and s.end >= s.start


# ---------------------------------------------------------------------------
# the serving span tree (acceptance: queue → pad → dispatch → readback)
# ---------------------------------------------------------------------------


class TestServingSpanTree:
    def test_request_tree_and_thread_handoff(self):
        with trace.capture() as rec:
            _serve(n_requests=5, rows=3, name="t-trace-tree")
        spans = rec.snapshot()
        by_id = {s.span_id: s for s in spans}
        children = {}
        for s in spans:
            if s.parent_id is not None:
                children.setdefault(s.parent_id, []).append(s)
        requests = [s for s in spans if s.name == "serving.request"]
        assert len(requests) == 5
        main_tid = threading.get_ident()
        batch_names_seen = set()
        for req in requests:
            kid_names = {c.name for c in children.get(req.span_id, [])}
            assert "serving.queue" in kid_names
            # the request root carries the CLIENT thread identity; its queue
            # child (recorded by the batcher thread) inherits it — the
            # parent-ID handoff across the MicroBatcher boundary
            assert req.thread_id == main_tid
            for c in children.get(req.span_id, []):
                if c.name == "serving.queue":
                    assert c.thread_id == main_tid
                    assert c.category == CAT_QUEUE
        batches = [s for s in spans if s.name == "serving.batch"]
        assert batches
        for b in batches:
            assert by_id[b.parent_id].name == "serving.request"
            assert b.thread_id != main_tid  # executed on the batcher thread
            kid_names = {c.name for c in children.get(b.span_id, [])}
            batch_names_seen |= kid_names
            assert "serving.pad" in kid_names
        # across the run the full phase vocabulary appears (fastpath on:
        # dispatch + deferred readback; respond always)
        assert {"serving.pad", "serving.dispatch", "serving.readback",
                "serving.respond"} <= batch_names_seen

    def test_children_nest_inside_parents(self):
        with trace.capture() as rec:
            _serve(n_requests=8, rows=2, name="t-trace-nest", threads=2)
        spans = rec.snapshot()
        children = {}
        for s in spans:
            if s.parent_id is not None:
                children.setdefault(s.parent_id, []).append(s)
        checked = 0
        for s in spans:
            kids = [c for c in children.get(s.span_id, []) if c.scope == s.scope]
            if not kids:
                continue
            checked += 1
            for c in kids:
                assert c.start >= s.start - 1e-6
                assert c.end <= s.end + 1e-6
            # summed child time fits within the parent span
            assert sum(c.duration for c in kids) <= s.duration + 1e-6
        assert checked > 0

    def test_warmup_and_swap_spans_are_compile_and_swap(self):
        with trace.capture() as rec:
            _serve(n_requests=1, rows=1, name="t-trace-warm")
        names = {s.name: s for s in rec.snapshot()}
        assert names["serving.warmup"].category == CAT_COMPILE
        assert names["serving.swap"].category == "swap"
        assert names["serving.plan.warmup"].category == CAT_COMPILE
        # warmup nests under the swap that triggered it
        assert names["serving.warmup"].parent_id == names["serving.swap"].span_id


# ---------------------------------------------------------------------------
# goodput attribution
# ---------------------------------------------------------------------------


class TestGoodputReport:
    def test_self_time_attribution_sums_to_root_wall(self):
        spans = [
            _span("root", CAT_PRODUCTIVE, "s", 0.0, 10.0, 1),
            _span("queue", CAT_QUEUE, "s", 0.0, 2.0, 2, parent_id=1),
            _span("exec", CAT_PRODUCTIVE, "s", 2.0, 9.0, 3, parent_id=1),
            _span("readback", CAT_READBACK, "s", 6.0, 9.0, 4, parent_id=3),
        ]
        report = GoodputReport.from_spans(spans)
        totals = report.totals["s"]
        # root self 1.0 + exec self 4.0 productive; queue 2.0; readback 3.0
        assert totals[CAT_PRODUCTIVE] == pytest.approx(5.0)
        assert totals[CAT_QUEUE] == pytest.approx(2.0)
        assert totals[CAT_READBACK] == pytest.approx(3.0)
        assert report.wall_s("s") == pytest.approx(10.0)  # == root duration
        assert report.fraction("s") == pytest.approx(0.5)

    def test_padding_split_from_rows_vs_bucket(self):
        spans = [
            _span("exec", CAT_PRODUCTIVE, "s", 0.0, 4.0, 1, attrs={"rows": 3, "bucket": 4}),
        ]
        totals = GoodputReport.from_spans(spans).totals["s"]
        assert totals[CAT_PRODUCTIVE] == pytest.approx(3.0)
        assert totals[CAT_PADDING] == pytest.approx(1.0)

    def test_full_bucket_has_no_padding(self):
        spans = [
            _span("exec", CAT_PRODUCTIVE, "s", 0.0, 4.0, 1, attrs={"rows": 4, "bucket": 4}),
        ]
        totals = GoodputReport.from_spans(spans).totals["s"]
        assert CAT_PADDING not in totals

    def test_cross_scope_children_do_not_subtract(self):
        spans = [
            _span("loop.swap", "swap", "loop", 0.0, 5.0, 1),
            _span("serving.warmup", CAT_COMPILE, "serving", 1.0, 4.0, 2, parent_id=1),
        ]
        report = GoodputReport.from_spans(spans)
        assert report.totals["loop"]["swap"] == pytest.approx(5.0)
        assert report.totals["serving"][CAT_COMPILE] == pytest.approx(3.0)

    def test_publish_writes_goodput_gauges(self):
        registry = MetricsRegistry()
        GoodputReport({"sc": {CAT_PRODUCTIVE: 0.3, CAT_QUEUE: 0.1}}).publish(registry)
        assert registry.get("sc", MLMetrics.goodput_ms(CAT_PRODUCTIVE)) == pytest.approx(300.0)
        assert registry.get("sc", MLMetrics.goodput_ms(CAT_QUEUE)) == pytest.approx(100.0)
        assert registry.get("sc", MLMetrics.GOODPUT_FRACTION) == pytest.approx(0.75)

    def test_serving_categories_sum_to_traced_wall(self):
        with trace.capture() as rec:
            _serve(n_requests=6, rows=3, name="t-trace-goodput")
        spans = rec.snapshot()
        scope = "ml.serving[t-trace-goodput]"
        report = GoodputReport.from_spans(spans)
        # roots of the scope = spans without an in-scope parent
        ids = {s.span_id for s in spans if s.scope == scope}
        roots = [
            s for s in spans
            if s.scope == scope and (s.parent_id is None or s.parent_id not in ids)
        ]
        assert report.wall_s(scope) == pytest.approx(
            sum(r.duration for r in roots), rel=1e-9
        )
        assert 0.0 < report.fraction(scope) < 1.0
        # the padded remainder of partially-filled buckets was attributed
        assert report.category_s(scope, CAT_PADDING) > 0.0


# ---------------------------------------------------------------------------
# exporters: chrome trace + prometheus + quantiles
# ---------------------------------------------------------------------------


class TestChromeTraceExport:
    def test_schema_and_metadata(self, tmp_path):
        path = str(tmp_path / "trace.json")
        with trace.capture() as rec:
            _serve(n_requests=3, rows=2, name="t-trace-export")
            n = rec.export_chrome_trace(path)
        assert n == rec.recorded == len(rec.snapshot())
        payload = json.loads(open(path).read())
        events = payload["traceEvents"]
        xs = [e for e in events if e.get("ph") == "X"]
        assert len(xs) == n
        for e in xs:
            assert set(e) >= {"ph", "pid", "tid", "name", "cat", "ts", "dur", "args"}
            assert e["cat"] in CATEGORIES
            assert e["dur"] >= 0.0
            assert "span_id" in e["args"]
        procs = [
            e for e in events
            if e.get("ph") == "M" and e.get("name") == "process_name"
        ]
        proc_names = {e["args"]["name"] for e in procs}
        assert "ml.serving[t-trace-export]" in proc_names
        # one pid per scope
        assert len({e["pid"] for e in procs}) == len(procs)
        threads = [
            e for e in events
            if e.get("ph") == "M" and e.get("name") == "thread_name"
        ]
        assert threads  # tid metadata present

    def test_empty_recorder_exports_valid_file(self, tmp_path):
        path = str(tmp_path / "empty.json")
        rec = SpanRecorder(16)
        assert rec.export_chrome_trace(path) == 0
        assert json.loads(open(path).read())["traceEvents"] == []


class TestPrometheusExposition:
    def test_golden_rendering(self):
        registry = MetricsRegistry()
        registry.gauge("ml.serving[a]", "ml.serving.queue.depth", 3)
        registry.counter("ml.serving[a]", "ml.serving.requests", 7)
        registry.gauge("ml.loop[l]", "ml.loop.goodput.fraction", 0.75)
        hist = registry.histogram("ml.serving[a]", "ml.serving.latency.ms")
        for v in [1.0, 2.0, 3.0, 4.0]:
            hist.observe(v)
        golden = (
            '# TYPE ml_loop_goodput_fraction gauge\n'
            'ml_loop_goodput_fraction{scope="ml.loop[l]"} 0.75\n'
            '# TYPE ml_serving_latency_ms summary\n'
            'ml_serving_latency_ms{scope="ml.serving[a]",quantile="0.5"} 3\n'
            'ml_serving_latency_ms{scope="ml.serving[a]",quantile="0.9"} 4\n'
            'ml_serving_latency_ms{scope="ml.serving[a]",quantile="0.99"} 4\n'
            'ml_serving_latency_ms_count{scope="ml.serving[a]"} 4\n'
            'ml_serving_latency_ms_sum{scope="ml.serving[a]"} 10\n'
            '# TYPE ml_serving_queue_depth gauge\n'
            'ml_serving_queue_depth{scope="ml.serving[a]"} 3\n'
            '# TYPE ml_serving_requests_total counter\n'
            'ml_serving_requests_total{scope="ml.serving[a]"} 7\n'
        )
        assert registry.render_prometheus() == golden

    def test_skips_non_numeric_and_escapes_labels(self):
        registry = MetricsRegistry()
        registry.gauge('scope"with\\quotes', "m.x", 1)
        registry.gauge("s", "m.y", "not-a-number")
        out = registry.render_prometheus()
        assert 'scope="scope\\"with\\\\quotes"' in out
        assert "m_y" not in out

    def test_global_registry_renders_after_serving(self):
        _serve(n_requests=2, rows=2, name="t-trace-prom")
        out = metrics.render_prometheus()
        assert '# TYPE ml_serving_requests_total counter' in out
        assert 'ml_serving_requests_total{scope="ml.serving[t-trace-prom]"}' in out
        assert 'ml_serving_latency_ms{scope="ml.serving[t-trace-prom]",quantile="0.5"}' in out


class TestHistogramQuantiles:
    def test_batch_matches_single_quantiles(self):
        hist = Histogram(window=64)
        rng = np.random.default_rng(5)
        for v in rng.normal(size=50):
            hist.observe(float(v))
        qs = (0.0, 0.25, 0.5, 0.99, 1.0)
        assert hist.quantiles(qs) == [hist.quantile(q) for q in qs]

    def test_empty_and_validation(self):
        hist = Histogram(window=4)
        assert hist.quantiles((0.5, 0.99)) == [None, None]
        with pytest.raises(ValueError):
            hist.quantiles((0.5, 1.5))


# ---------------------------------------------------------------------------
# the other instrumented tiers
# ---------------------------------------------------------------------------


class TestOtherTiers:
    def test_batch_plan_chunk_spans(self):
        from flink_ml_tpu.builder import PipelineModel
        from flink_ml_tpu.models.feature.standard_scaler import StandardScalerModel

        rng = np.random.default_rng(2)
        d = 8
        m = StandardScalerModel().set_input_col("input").set_output_col("output")
        m.set_with_mean(True)
        m.mean = rng.normal(size=d)
        m.std = np.abs(rng.normal(size=d)) + 0.5
        model = PipelineModel([m])
        df = DataFrame.from_dict({"input": rng.normal(size=(64, d))})
        config.set(Options.BATCH_CHUNK_ROWS, 16)
        try:
            with trace.capture() as rec:
                model.transform(df)
        finally:
            config.unset(Options.BATCH_CHUNK_ROWS)
        spans = rec.snapshot()
        names = [s.name for s in spans if s.scope == "ml.batch[plan]"]
        assert names.count("batch.ingest") == 4  # 64 rows / 16-row chunks
        assert names.count("batch.chunk") == 4
        assert "batch.readback" in names
        assert "batch.transform" in names
        readbacks = [s for s in spans if s.name == "batch.readback"]
        assert all(s.category == CAT_READBACK for s in readbacks)
        umbrella = [s for s in spans if s.name == "batch.transform"][0]
        chunks = [s for s in spans if s.name == "batch.chunk"]
        assert all(c.parent_id == umbrella.span_id for c in chunks)

    def test_iteration_epoch_spans(self):
        from flink_ml_tpu.iteration import (
            IterationBodyResult,
            IterationConfig,
            iterate_bounded_until_termination,
        )

        def body(variables, epoch):
            return IterationBodyResult(
                feedback_variables=[variables[0] + 1], outputs=[variables[0]]
            )

        with trace.capture() as rec:
            iterate_bounded_until_termination(
                [0], body, IterationConfig(max_epochs=3)
            )
        epochs = [s for s in rec.snapshot() if s.name == "iteration.epoch"]
        assert [s.attrs["epoch"] for s in epochs] == [0, 1, 2]
        assert all(s.scope == "ml.iteration[bounded]" for s in epochs)

    def test_supervisor_attempt_and_recovery_spans(self):
        from flink_ml_tpu.execution import Supervisor

        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise OSError("transient")  # retryable per DEFAULT_CLASSIFIER
            return "ok"

        with trace.capture() as rec:
            assert Supervisor(name="t-trace-sup").run(flaky) == "ok"
        spans = rec.snapshot()
        scope = "ml.execution[t-trace-sup]"
        attempts = [s for s in spans if s.name == "execution.attempt"]
        recoveries = [s for s in spans if s.name == "execution.recovery"]
        assert len(attempts) == 3 and len(recoveries) == 2
        assert all(s.scope == scope for s in attempts + recoveries)
        assert all(s.category == "recovery" for s in recoveries)
        assert attempts[0].attrs["error"] == "OSError"
        assert "error" not in (attempts[-1].attrs or {})


# ---------------------------------------------------------------------------
# tools/traceview.py
# ---------------------------------------------------------------------------


class TestTraceviewCLI:
    def _export(self, tmp_path):
        path = str(tmp_path / "trace.json")
        with trace.capture() as rec:
            _serve(n_requests=4, rows=2, name="t-trace-cli")
            rec.export_chrome_trace(path)
        return path

    def test_summary_on_seeded_trace(self, tmp_path, capsys):
        path = self._export(tmp_path)
        assert traceview_main([path]) == 0
        out = capsys.readouterr().out
        assert "ml.serving[t-trace-cli]" in out
        assert "goodput fraction" in out
        assert "serving.request" in out
        assert "compile" in out  # the warmup slice shows up per category

    def test_scope_filter_and_top(self, tmp_path, capsys):
        path = self._export(tmp_path)
        assert traceview_main([path, "--scope", "ml.serving", "--top", "3"]) == 0
        out = capsys.readouterr().out
        assert out.count("scope ml.serving[t-trace-cli]") == 1

    def test_missing_file_exits_2(self, tmp_path, capsys):
        assert traceview_main([str(tmp_path / "nope.json")]) == 2
        assert "cannot load" in capsys.readouterr().err

    def test_invalid_json_exits_2(self, tmp_path, capsys):
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        assert traceview_main([str(bad)]) == 2

    def test_empty_trace_exits_2(self, tmp_path, capsys):
        empty = tmp_path / "empty.json"
        empty.write_text('{"traceEvents": []}')
        assert traceview_main([str(empty)]) == 2
        assert "no spans" in capsys.readouterr().err

    def test_roundtrip_matches_live_goodput(self, tmp_path):
        """The offline analyzer reproduces the live report's attribution."""
        from tools.traceview import load_spans

        path = str(tmp_path / "trace.json")
        with trace.capture() as rec:
            _serve(n_requests=4, rows=3, name="t-trace-rt")
            rec.export_chrome_trace(path)
            live = rec.goodput_report()
        offline = GoodputReport.from_spans(load_spans(path))
        scope = "ml.serving[t-trace-rt]"
        assert offline.fraction(scope) == pytest.approx(live.fraction(scope), rel=1e-6)
        for cat in CATEGORIES:
            assert offline.category_s(scope, cat) == pytest.approx(
                live.category_s(scope, cat), rel=1e-6, abs=1e-9
            )


# ---------------------------------------------------------------------------
# tracer lifecycle
# ---------------------------------------------------------------------------


class TestLifecycle:
    def test_capture_restores_previous_state(self):
        assert not tracer.enabled
        outer_recorder = tracer.recorder
        with trace.capture(capacity=4) as rec:
            assert tracer.enabled and tracer.recorder is rec
        assert not tracer.enabled
        assert tracer.recorder is outer_recorder

    def test_enable_disable(self):
        trace.enable(capacity=16)
        try:
            assert tracer.enabled and tracer.recorder.capacity == 16
        finally:
            trace.disable()
        assert not tracer.enabled

    def test_independent_tracer_instances(self):
        t = Tracer(SpanRecorder(8), enabled=True)
        with t.span("x", scope="s"):
            pass
        assert len(t.recorder) == 1
        assert len(tracer.recorder) == 0 or tracer.recorder is not t.recorder
