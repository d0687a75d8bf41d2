"""Flight recorder, incident bundles, live endpoint (flink_ml_tpu.telemetry).

The contract under test (docs/observability.md):

- the journal is append-only JSONL with monotone sequence numbers, written
  ONLY by the dedicated writer thread — the hot path pays one enqueue;
- a hard kill mid-write (the ``telemetry.journal`` fault point) leaves a
  torn tail the reader tolerates, and a new incarnation resumes the
  sequence without reuse and emits a crash-resume incident bundle;
- incident bundles are self-contained (journal window + metrics + config +
  lineage), rate-limited per kind, bounded-retention, and renderable by
  ``tools/traceview.py incident`` with exit 0;
- /metrics, /healthz and /events answer during live traffic, with 503 on
  drain/closed;
- runtime decisions (swap, rollback, controller action, fault trip,
  supervisor restart, plan choice) each land in the journal exactly once.
"""
from __future__ import annotations

import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import flink_ml_tpu.telemetry as telemetry
from flink_ml_tpu.api.dataframe import DataFrame
from flink_ml_tpu.faults import InjectedFault, faults
from flink_ml_tpu.metrics import MLMetrics, metrics
from flink_ml_tpu.servable.api import TransformerServable
from flink_ml_tpu.serving import InferenceServer, ServingConfig
from flink_ml_tpu.telemetry import FlightRecorder


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _wait_writer_dead(rec: FlightRecorder, timeout_s: float = 5.0) -> None:
    deadline = time.monotonic() + timeout_s
    while rec._alive() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not rec._alive(), "writer thread should have died on the injected fault"


class Echo(TransformerServable):
    def transform(self, df):
        return df.clone()


def _df(rows: int = 2, width: int = 4) -> DataFrame:
    return DataFrame.from_dict({"x": np.ones((rows, width), np.float32)})


# ---------------------------------------------------------------------------
# journal basics
# ---------------------------------------------------------------------------


class TestJournal:
    def test_emit_flush_read_roundtrip(self, tmp_path):
        rec = FlightRecorder(str(tmp_path))
        try:
            assert rec.emit("serving.swap", "ml.serving[t]", {"version": 3})
            assert rec.emit("controller.action", "ml.serving[t]", {"action": "shed"})
            assert rec.flush(10.0)
            records = telemetry.read_journal(str(tmp_path))
            kinds = [r["kind"] for r in records]
            assert kinds == ["recorder.start", "serving.swap", "controller.action"]
            seqs = [r["seq"] for r in records]
            assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
            swap = records[1]
            assert swap["data"] == {"version": 3}
            assert swap["scope"] == "ml.serving[t]"
            assert swap["inc"] == 1
            # monotonic + wall timestamps and the emitting thread ride along
            assert isinstance(swap["t"], float) and isinstance(swap["wall"], float)
            assert swap["thread"]
        finally:
            rec.close()

    def test_clean_close_writes_stop_marker(self, tmp_path):
        rec = FlightRecorder(str(tmp_path))
        rec.emit("a")
        rec.close()
        records = telemetry.read_journal(str(tmp_path))
        assert records[-1]["kind"] == "recorder.stop"

    def test_disabled_recorder_is_inert(self, tmp_path):
        rec = FlightRecorder(str(tmp_path / "j"), enabled=False)
        assert not rec.emit("a")
        assert not rec.incident("b")
        assert rec._thread is None
        assert not (tmp_path / "j").exists()

    def test_queue_overflow_drops_and_counts(self, tmp_path):
        rec = FlightRecorder(str(tmp_path), queue_capacity=4)
        try:
            assert rec.flush(10.0)  # writer started; now stall it artificially
            with rec._cond:  # hold the queue lock so nothing drains
                for i in range(10):
                    if len(rec._queue) >= rec.queue_capacity:
                        rec._dropped += 1
                    else:
                        rec._queue.append({"kind": f"e{i}", "t": 0.0, "wall": 0.0, "thread": "t"})
                        rec._enqueued += 1
            assert rec.dropped == 6
        finally:
            rec.close()

    def test_overflow_through_emit(self, tmp_path):
        # Arm the fault so the writer dies, then overfill through emit():
        # drop-and-count with zero blocking is the hot-path contract.
        rec = FlightRecorder(str(tmp_path), queue_capacity=8)
        try:
            faults.arm("telemetry.journal", at=1)
            rec.emit("killer")
            _wait_writer_dead(rec)
            for i in range(20):
                rec.emit(f"e{i}")
            assert rec.dropped >= 12
            assert not rec.flush(0.2)  # dead writer: flush reports failure
        finally:
            rec.close(timeout_s=0.5)

    def test_rotation_keeps_bounded_files_and_monotone_seq(self, tmp_path):
        rec = FlightRecorder(str(tmp_path), max_bytes=400, keep_files=3)
        try:
            for i in range(50):
                rec.emit("event", "ml.t", {"i": i, "pad": "x" * 40})
            assert rec.flush(10.0)
            files = telemetry.journal_files(str(tmp_path))
            assert 1 < len(files) <= 3
            records = telemetry.read_journal(str(tmp_path))
            seqs = [r["seq"] for r in records]
            assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
            assert records[-1]["data"]["i"] == 49  # the newest records survive
        finally:
            rec.close()

    def test_span_causal_id_links_to_graftscope(self, tmp_path):
        from flink_ml_tpu import trace

        rec = FlightRecorder(str(tmp_path))
        try:
            with trace.capture():
                with trace.tracer.span("loop.step", "productive", scope="ml.loop[t]") as sp:
                    rec.emit("loop.swap", "ml.loop[t]", {"version": 2})
                    span_id = sp.span_id
            assert rec.flush(10.0)
            swap = [r for r in telemetry.read_journal(str(tmp_path)) if r["kind"] == "loop.swap"][0]
            assert swap["span"] == span_id
        finally:
            rec.close()


# ---------------------------------------------------------------------------
# crash recovery: kill mid-write, torn tail, sequence resume, incident
# ---------------------------------------------------------------------------


class TestCrashRecovery:
    def test_kill_mid_write_leaves_torn_tail_reader_tolerates(self, tmp_path):
        rec = FlightRecorder(str(tmp_path))
        rec.emit("a", "ml.t", {"n": 1})
        assert rec.flush(10.0)
        faults.arm("telemetry.journal", at=1)
        rec.emit("b", "ml.t", {"n": 2})
        _wait_writer_dead(rec)
        faults.reset()
        # The file ends in a torn (half-written) line...
        path = telemetry.journal_files(str(tmp_path))[-1][2]
        with open(path, encoding="utf-8") as f:
            raw = f.read()
        assert not raw.endswith("\n")
        torn = raw.rsplit("\n", 1)[-1]
        with pytest.raises(ValueError):
            json.loads(torn)
        # ...and the reader returns every intact record, skipping the tail.
        records = telemetry.read_journal(str(tmp_path))
        assert [r["kind"] for r in records] == ["recorder.start", "a"]

    def test_new_incarnation_resumes_sequence_and_emits_incident(self, tmp_path):
        rec = FlightRecorder(str(tmp_path))
        rec.emit("a")
        assert rec.flush(10.0)
        faults.arm("telemetry.journal", at=1)
        rec.emit("b")
        _wait_writer_dead(rec)
        faults.reset()
        pre = telemetry.read_journal(str(tmp_path))
        max_seq = max(r["seq"] for r in pre)

        rec2 = FlightRecorder(str(tmp_path))
        try:
            rec2.emit("after-resume")
            assert rec2.flush(10.0)
            assert rec2.crash_resumed
            records = telemetry.read_journal(str(tmp_path))
            seqs = [r["seq"] for r in records]
            # monotone across incarnations, no reuse of a durable seq
            assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
            assert min(s for s in seqs if s > max_seq) == max_seq + 1
            assert rec2.incarnation == 2
            resume = [r for r in records if r["kind"] == "recorder.resume"][0]
            assert resume["data"]["prior_incarnation"] == 1
            assert resume["data"]["clean_shutdown"] is False
            assert resume["data"]["torn_tail"] is True
            # crash-resume itself produced an incident bundle...
            bundles = [
                b for b in telemetry.list_bundles(rec2.incident_dir)
                if b.endswith("crash-resume")
            ]
            assert len(bundles) == 1
            manifest = telemetry.load_bundle(bundles[0])["manifest"]
            assert manifest["kind"] == "crash-resume"
            assert manifest["config"]  # resolved runtime config snapshotted
            # ...that traceview renders as a postmortem with exit 0.
            import tools.traceview as traceview

            assert traceview.main(["incident", bundles[0], "--top", "5"]) == 0
        finally:
            rec2.close()

    def test_clean_restart_is_not_a_crash(self, tmp_path):
        rec = FlightRecorder(str(tmp_path))
        rec.emit("a")
        rec.close()
        rec2 = FlightRecorder(str(tmp_path))
        try:
            assert rec2.flush(10.0)
            assert not rec2.crash_resumed
            assert rec2.incarnation == 2
            assert not telemetry.list_bundles(rec2.incident_dir)
            resume = [
                r for r in telemetry.read_journal(str(tmp_path))
                if r["kind"] == "recorder.resume"
            ][0]
            assert resume["data"]["clean_shutdown"] is True
        finally:
            rec2.close()


# ---------------------------------------------------------------------------
# incidents: bundle contents, rate limit, retention
# ---------------------------------------------------------------------------


class TestIncidents:
    def test_bundle_contents_and_lineage(self, tmp_path):
        rec = FlightRecorder(str(tmp_path))
        try:
            rec.emit("loop.publish", "ml.loop[t]", {"version": 1})
            rec.emit("serving.swap", "ml.serving[t]", {"version": 1})
            rec.emit("serving.rollback", "ml.serving[t]", {"version": 1, "from": 2})
            rec.incident("rollback", "ml.loop[t]", {"from_version": 2, "restored": 1})
            assert rec.flush(10.0)
            bundle = telemetry.list_bundles(rec.incident_dir)[0]
            names = sorted(os.listdir(bundle))
            assert "incident.json" in names and "journal.jsonl" in names
            assert "metrics.prom" in names
            loaded = telemetry.load_bundle(bundle)
            assert loaded["manifest"]["kind"] == "rollback"
            assert loaded["manifest"]["context"]["restored"] == 1
            lineage = loaded["manifest"]["lineage"]
            assert [e["kind"] for e in lineage] == [
                "loop.publish", "serving.swap", "serving.rollback",
            ]
            # the bundle's journal window includes the incident's own record
            assert loaded["records"][-1]["kind"] == "incident"
        finally:
            rec.close()

    def test_rate_limit_is_per_kind(self, tmp_path):
        rec = FlightRecorder(str(tmp_path), incident_min_interval_s=3600.0)
        try:
            assert rec.incident("shed-episode", context={"n": 1})
            assert not rec.incident("shed-episode", context={"n": 2})  # suppressed
            assert rec.incident("swap-failure", context={"n": 3})  # different kind
            assert rec.flush(10.0)
            kinds = [os.path.basename(b) for b in telemetry.list_bundles(rec.incident_dir)]
            assert len(kinds) == 2
            assert any(k.endswith("shed-episode") for k in kinds)
            assert any(k.endswith("swap-failure") for k in kinds)
            assert rec.incidents_suppressed == 1
        finally:
            rec.close()

    def test_retention_bound(self, tmp_path):
        rec = FlightRecorder(
            str(tmp_path), incident_min_interval_s=0.0, incident_keep=2
        )
        try:
            for i in range(5):
                rec.incident(f"kind-{i}", context={"i": i})
                assert rec.flush(10.0)
            bundles = telemetry.list_bundles(rec.incident_dir)
            assert len(bundles) == 2
            assert bundles[-1].endswith("kind-4")  # newest retained
        finally:
            rec.close()


# ---------------------------------------------------------------------------
# the hot path: enqueue only — zero journal writes on the dispatch path
# ---------------------------------------------------------------------------


class TestHotPathIsEnqueueOnly:
    def test_all_file_writes_happen_on_the_writer_thread(self, tmp_path):
        rec = telemetry.configure(str(tmp_path))
        try:
            write_threads = []
            original = FlightRecorder._write_record

            def tracking(self, record):
                write_threads.append(threading.current_thread().name)
                return original(self, record)

            FlightRecorder._write_record = tracking
            try:
                server = InferenceServer(
                    Echo(),
                    name="telemetry-hot",
                    serving_config=ServingConfig(max_batch_size=8, max_delay_ms=0.0),
                    warmup_template=_df(1),
                )
                try:
                    for _ in range(10):
                        server.predict(_df(2))
                    server.swap(2, Echo())
                finally:
                    server.close()
                assert rec.flush(10.0)
            finally:
                FlightRecorder._write_record = original
            assert write_threads, "serving decisions should have been journaled"
            assert all(t.startswith("flight-recorder") for t in set(write_threads)), (
                f"journal writes leaked off the writer thread: {set(write_threads)}"
            )
            # and the decisions themselves landed exactly once each
            records = telemetry.read_journal(str(tmp_path))
            swaps = [r for r in records if r["kind"] == "serving.swap"]
            assert [s["data"]["version"] for s in swaps] == [1, 2]
        finally:
            telemetry.configure(None)

    def test_emit_does_not_touch_the_filesystem_on_the_caller_thread(self, tmp_path):
        rec = FlightRecorder(str(tmp_path))
        gate = threading.Event()
        try:
            assert rec.flush(10.0)
            before = os.stat(telemetry.journal_files(str(tmp_path))[-1][2]).st_size

            def gated(record):  # freeze the writer (outside every lock)
                gate.wait(timeout=10.0)

            rec._write_record = gated
            t0 = time.perf_counter()
            for i in range(100):
                assert rec.emit("e", "ml.t", {"i": i})
            emit_s = time.perf_counter() - t0
            after = os.stat(telemetry.journal_files(str(tmp_path))[-1][2]).st_size
            assert after == before  # nothing hit disk: emits only enqueued
            assert emit_s < 1.0  # and none of them blocked on the writer
        finally:
            gate.set()
            rec.close()


# ---------------------------------------------------------------------------
# the live endpoint
# ---------------------------------------------------------------------------


def _get(url: str):
    with urllib.request.urlopen(url, timeout=5.0) as resp:
        return resp.status, resp.read().decode("utf-8")


class TestHttpEndpoint:
    def test_metrics_healthz_events_during_live_traffic(self, tmp_path):
        rec = telemetry.configure(str(tmp_path))
        server = InferenceServer(
            Echo(),
            name="telemetry-http",
            serving_config=ServingConfig(
                max_batch_size=8, max_delay_ms=0.0, http_port=0
            ),
            warmup_template=_df(1),
        )
        try:
            url = server.telemetry.url
            for _ in range(5):
                server.predict(_df(2))
            status, body = _get(url + "/metrics")
            assert status == 200
            assert "# TYPE ml_serving_requests_total counter" in body
            assert 'ml_serving_requests_total{scope="ml.serving[telemetry-http]"}' in body
            status, body = _get(url + "/healthz")
            payload = json.loads(body)
            assert status == 200
            assert payload["status"] == "serving"
            assert payload["version"] == 1
            assert payload["queue_capacity_rows"] == server.config.queue_capacity_rows
            assert "controller" in payload
            rec.flush(10.0)
            status, body = _get(url + "/events?n=3")
            events = json.loads(body)
            assert status == 200 and 1 <= len(events) <= 3
            assert all("kind" in e and "seq" in e for e in events)
        finally:
            server.close()
            telemetry.configure(None)

    def test_healthz_503_on_drain_and_closed(self, tmp_path):
        release = threading.Event()

        class Gated(TransformerServable):
            def transform(self, df):
                release.wait(timeout=10.0)
                return df.clone()

        rec = telemetry.configure(str(tmp_path))
        server = InferenceServer(
            Gated(),
            name="telemetry-drain",
            serving_config=ServingConfig(
                max_batch_size=4, max_delay_ms=0.0, http_port=0,
                default_timeout_ms=30_000,
            ),
        )
        url = server.telemetry.url
        saw_503 = False
        try:
            handle = server.submit(_df(1))  # in-flight work to drain
            closer = threading.Thread(target=server.close, daemon=True)
            closer.start()
            # While draining (the batch is gated on `release`), /healthz
            # must answer 503 with the draining status in the payload.
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline and not saw_503:
                try:
                    _get(url + "/healthz")
                except urllib.error.HTTPError as e:
                    assert e.code == 503
                    payload = json.loads(e.read().decode("utf-8"))
                    assert payload["status"] in ("draining", "closed")
                    saw_503 = True
                except (urllib.error.URLError, OSError):
                    break  # endpoint stopped — close() already completed
                else:
                    time.sleep(0.01)
            release.set()
            closer.join(timeout=10.0)
            handle.result()  # the drained request still completed exactly once
        finally:
            release.set()
            server.close()
            telemetry.configure(None)
        assert saw_503, "draining server should have answered /healthz with 503"

    def test_404_on_unknown_path(self, tmp_path):
        rec = FlightRecorder(str(tmp_path))
        try:
            with telemetry.TelemetryServer(0, recorder=rec) as ts:
                with pytest.raises(urllib.error.HTTPError) as e:
                    _get(ts.url + "/nope")
                assert e.value.code == 404
                status, _ = _get(ts.url + "/healthz")  # bare server: 200 up
                assert status == 200
        finally:
            rec.close()


# ---------------------------------------------------------------------------
# hook integration: runtime decisions land in the journal
# ---------------------------------------------------------------------------


class TestDecisionHooks:
    def test_fault_trip_observer_journals_fires(self, tmp_path):
        rec = telemetry.configure(str(tmp_path))
        try:
            faults.arm("serving.admit", at=1)
            server = InferenceServer(
                Echo(),
                name="telemetry-trip",
                serving_config=ServingConfig(max_batch_size=4, max_delay_ms=0.0),
                warmup_template=_df(1),
            )
            try:
                with pytest.raises(InjectedFault):
                    server.predict(_df(1))
            finally:
                server.close()
            assert rec.flush(10.0)
            trips = [
                r for r in telemetry.read_journal(str(tmp_path))
                if r["kind"] == "fault.trip"
            ]
            assert len(trips) == 1
            assert trips[0]["data"]["point"] == "serving.admit"
        finally:
            telemetry.configure(None)

    def test_supervisor_restart_journals_and_bundles(self, tmp_path):
        from flink_ml_tpu.execution import Supervisor

        rec = telemetry.configure(str(tmp_path))
        try:
            calls = {"n": 0}

            def flaky():
                calls["n"] += 1
                if calls["n"] < 3:
                    raise OSError("spill file lost")  # retryable by contract
                return "done"

            assert Supervisor(name="telemetry-sup").run(flaky) == "done"
            assert rec.flush(10.0)
            records = telemetry.read_journal(str(tmp_path))
            restarts = [r for r in records if r["kind"] == "execution.restart"]
            assert len(restarts) == 2
            assert restarts[0]["data"]["error"] == "OSError"
            assert restarts[0]["scope"] == "ml.execution[telemetry-sup]"
            bundles = [
                b for b in telemetry.list_bundles(rec.incident_dir)
                if b.endswith("supervisor-restart")
            ]
            assert len(bundles) == 1  # rate-limited: one bundle per episode kind
        finally:
            telemetry.configure(None)

    def test_controller_action_carries_ledger_evidence(self, tmp_path):
        from flink_ml_tpu.serving.controller import AdaptiveController

        rec = telemetry.configure(str(tmp_path))
        try:
            clock = {"t": 0.0}
            ctrl = AdaptiveController(
                "ml.serving[t-ledger]", 64, 8,
                shed_sustain_ms=0.0, clock=lambda: clock["t"],
            )
            ctrl.observe_batch(8, 8, 0.5)
            ctrl.note_queue(60)
            clock["t"] += 1.0
            assert ctrl.should_shed(1, 60)
            ctrl.record_shed(1, 60)
            assert rec.flush(10.0)
            actions = [
                r for r in telemetry.read_journal(str(tmp_path))
                if r["kind"] == "controller.action"
            ]
            assert len(actions) == 1
            assert actions[0]["data"]["action"] == "shed"
            assert actions[0]["data"]["ledger_ms"].get("productive") == 500.0
            # the shed episode also requested an incident bundle
            bundles = [
                b for b in telemetry.list_bundles(rec.incident_dir)
                if b.endswith("shed-episode")
            ]
            assert len(bundles) == 1
        finally:
            telemetry.configure(None)

    def test_fusion_plan_choice_is_journaled(self, tmp_path):
        from flink_ml_tpu.servable.fusion import plan_recorder

        rec = telemetry.configure(str(tmp_path))
        try:
            on_plan = plan_recorder("ml.serving[t-plan]")
            on_plan("fused", 1234.5)
            assert rec.flush(10.0)
            plans = [
                r for r in telemetry.read_journal(str(tmp_path))
                if r["kind"] == "fusion.plan"
            ]
            assert len(plans) == 1
            assert plans[0]["data"] == {"choice": "fused", "score": 1234.5}
        finally:
            telemetry.configure(None)


# ---------------------------------------------------------------------------
# traceview --json (machine-readable attribution for CI)
# ---------------------------------------------------------------------------


class TestTraceviewJson:
    def _trace_file(self, tmp_path) -> str:
        from flink_ml_tpu import trace

        with trace.capture() as recorder:
            server = InferenceServer(
                Echo(),
                name="t-tvjson",
                serving_config=ServingConfig(max_batch_size=8, max_delay_ms=0.0),
                warmup_template=_df(1),
            )
            try:
                for _ in range(3):
                    server.predict(_df(2))
            finally:
                server.close()
            path = str(tmp_path / "trace.json")
            recorder.export_chrome_trace(path)
        return path

    def test_summarize_data_matches_live_attribution(self, tmp_path):
        import tools.traceview as traceview

        path = self._trace_file(tmp_path)
        spans = traceview.load_spans(path)
        data = traceview.summarize_data(spans)
        scope = "ml.serving[t-tvjson]"
        assert scope in data["scopes"]
        entry = data["scopes"][scope]
        assert entry["wall_ms"] > 0.0
        assert 0.0 <= entry["goodput_fraction"] <= 1.0
        # categories sum to the wall (the exact-attribution invariant)
        total = sum(c["ms"] for c in entry["categories"].values())
        assert total == pytest.approx(entry["wall_ms"], rel=1e-6)
        names = {s["name"] for s in entry["spans"]}
        assert "serving.request" in names and "serving.batch" in names
        for stat in entry["spans"]:
            assert set(stat) == {"name", "count", "p50_ms", "p99_ms", "total_ms", "share"}

    def test_cli_json_flag(self, tmp_path, capsys):
        import tools.traceview as traceview

        path = self._trace_file(tmp_path)
        assert traceview.main([path, "--json"]) == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["spans"] > 0
        assert "overall_goodput_fraction" in payload

