"""graftscope — end-to-end structured tracing with goodput attribution.

The runtime's four execution tiers (serving fast path, batch plans,
iteration, continuous loop) are instrumented with nested **spans**: where a
request's milliseconds go, phase by phase, and what fraction of traced wall
time is *productive* in the sense of the ML Productivity Goodput accounting
(PAPERS.md) — user rows moving through compiled programs — versus padding,
compiles, swaps, queueing, recovery and readback stalls.

Span model (docs/observability.md):

- ``tracer.span(name, category, scope=...)`` is a context manager; spans nest
  via a per-thread stack, so a warmup span opened inside a swap turn becomes
  its child with no plumbing.
- ``tracer.begin``/``tracer.end`` are the manual form for spans whose start
  and finish live on different code paths (a micro-batch dispatched on one
  loop turn and finalized on a later one). Parent IDs cross thread
  boundaries by carrying the parent span on a request object — the
  ``MicroBatcher`` handoff stores the request's root span on the
  ``PendingRequest`` and the batcher thread parents its queue/batch spans to
  it.
- ``tracer.record`` retro-records a completed span from already-measured
  monotonic timestamps (the queue-wait span is known only at claim time).
- ``tracer.phase(name, category, scope, **counts)`` is the form for sites that
  run a bounded number of times per job (the phases of one ``Estimator.fit``):
  it always enters a ``jax.profiler.TraceAnnotation`` carrying ``counts``, so
  any profiler session sees the phase on the device trace's clock whether or
  not the tracer records. Never on a hot site: that is ``tracer.span``'s.

**Disabled is free**: ``tracer.enabled`` is a plain attribute, and every
instrumented site either checks it or calls ``tracer.span(...)``, whose
disabled path is that single attribute check followed by returning one shared
no-op span — no allocation, no lock, no clock read. Tier-1 asserts this
structurally (tests/test_trace.py).

Goodput categories partition each scope's traced wall time by **self time**
(a span's duration minus its same-scope children), so per-scope category
totals sum exactly to the scope's root-span wall time. A span carrying
``rows``/``bucket`` attrs additionally splits its self time between its own
category and ``padding`` in the pad-rows proportion — the bucket-padding
waste the serving tier's power-of-two shapes trade for compile stability.

Exporters: :meth:`SpanRecorder.export_chrome_trace` writes Chrome
trace-event JSON (load in Perfetto / chrome://tracing; one pid per scope,
one tid per thread), ``metrics.render_prometheus()`` exposes the whole
metrics registry, and with ``observability.trace.xprof`` enabled spans
mirror into ``jax.profiler.TraceAnnotation`` so they nest inside XLA
profiler dumps captured around the region (the ``benchmark --profile``
wiring). ``tools/traceview.py`` is the offline half: per-category and
per-span latency breakdowns plus the goodput fraction from an exported
trace.
"""
from __future__ import annotations

import contextlib
import itertools
import json
import threading
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, Iterable, List, Optional

from flink_ml_tpu.config import Options, config
from flink_ml_tpu.metrics import MLMetrics, metrics

__all__ = [
    "CAT_PRODUCTIVE",
    "CAT_QUEUE",
    "CAT_PADDING",
    "CAT_COMPILE",
    "CAT_SWAP",
    "CAT_RECOVERY",
    "CAT_READBACK",
    "CAT_INGEST",
    "CATEGORIES",
    "Span",
    "SpanRecorder",
    "GoodputReport",
    "Tracer",
    "tracer",
    "enable",
    "disable",
    "capture",
]

#: The goodput categories — a fixed vocabulary so reports aggregate across
#: tiers (the ML Productivity Goodput buckets, docs/observability.md).
CAT_PRODUCTIVE = "productive"  # user rows moving through compiled programs
CAT_QUEUE = "queue"  # admitted but waiting (batcher queue, backpressure)
CAT_PADDING = "padding"  # bucket pad rows + host-side pad work
CAT_COMPILE = "compile"  # trace/lower/compile + AOT warmup
CAT_SWAP = "swap"  # version publish / flip / checkpoint persistence
CAT_RECOVERY = "recovery"  # restart backoff, rollback, restore
CAT_READBACK = "readback"  # blocking device->host readback
CAT_INGEST = "ingest"  # host featurize, pack, layout, upload: data on its way to the device
CATEGORIES = (
    CAT_PRODUCTIVE,
    CAT_QUEUE,
    CAT_PADDING,
    CAT_COMPILE,
    CAT_SWAP,
    CAT_RECOVERY,
    CAT_READBACK,
    CAT_INGEST,
)

#: Process-wide monotonically increasing span ids (itertools.count.__next__
#: is a single C call — atomic under the GIL, no lock needed).
_next_id = itertools.count(1).__next__


class Span:
    """One timed region. Created by the tracer; finished either by the
    ``with`` protocol (stack-managed) or by ``tracer.end`` (manual)."""

    __slots__ = (
        "name",
        "category",
        "scope",
        "start",
        "end",
        "span_id",
        "parent_id",
        "thread_id",
        "thread_name",
        "attrs",
        "_tracer",
        "_annotation",
    )

    def __init__(
        self,
        name: str,
        category: str,
        scope: str,
        start: float,
        span_id: int,
        parent_id: Optional[int],
        thread_id: int,
        thread_name: str,
        tracer_: Optional["Tracer"] = None,
    ):
        self.name = name
        self.category = category
        self.scope = scope
        self.start = start
        self.end: Optional[float] = None
        self.span_id = span_id
        self.parent_id = parent_id
        self.thread_id = thread_id
        self.thread_name = thread_name
        self.attrs: Optional[Dict[str, Any]] = None
        self._tracer = tracer_
        self._annotation = None

    @property
    def duration(self) -> float:
        """Seconds from start to end (0.0 while unfinished)."""
        return 0.0 if self.end is None else max(0.0, self.end - self.start)

    def set_attr(self, key: str, value: Any) -> "Span":
        if self.attrs is None:
            self.attrs = {}
        self.attrs[key] = value
        return self

    def set_metadata(self, **counts: Any) -> None:
        """Counts a phase learns only from its own work (entries packed, bytes
        placed). Same call as ``jax.profiler.TraceAnnotation``'s — what
        ``tracer.phase`` hands back when the tracer is off — so a site writes
        it once: attrs here, stats of the profiler's event while the phase's
        annotation is open."""
        for key, value in counts.items():
            self.set_attr(key, value)
        if self._annotation is not None:
            self._annotation.set_metadata(**counts)

    # -- stack-managed lifetime -----------------------------------------------
    def __enter__(self) -> "Span":
        self._tracer._push(self)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is not None:
            self.set_attr("error", exc_type.__name__)
        self._tracer._pop(self)
        return False

    def __repr__(self) -> str:
        return (
            f"Span({self.name!r}, category={self.category!r}, scope={self.scope!r}, "
            f"id={self.span_id}, parent={self.parent_id}, "
            f"ms={self.duration * 1000.0:.3f})"
        )


class _NoopSpan:
    """The shared disabled-path span: every method is a no-op taking only
    positional arguments, so an instrumented hot site pays one attribute
    check and zero allocation when tracing is off."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False

    def set_attr(self, key: str, value: Any) -> "_NoopSpan":
        return self


_NOOP_SPAN = _NoopSpan()


class SpanRecorder:
    """Thread-safe bounded ring of finished spans: the newest ``capacity``
    spans are retained, older ones fall off (``dropped`` counts them). One
    recorder serves all scopes — exporters group by scope."""

    def __init__(self, capacity: Optional[int] = None):
        if capacity is None:
            capacity = int(config.get(Options.OBSERVABILITY_TRACE_CAPACITY))
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._spans: Deque[Span] = deque(maxlen=capacity)
        self._recorded = 0
        self._lock = threading.Lock()

    def record(self, span: Span) -> None:
        with self._lock:
            self._spans.append(span)
            self._recorded += 1

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)

    @property
    def recorded(self) -> int:
        """Total spans ever recorded (retained + dropped)."""
        with self._lock:
            return self._recorded

    @property
    def dropped(self) -> int:
        """Spans that fell off the ring."""
        with self._lock:
            return self._recorded - len(self._spans)

    def snapshot(self) -> List[Span]:
        """The retained spans, oldest first (a consistent copy)."""
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._recorded = 0

    # -- exporters (offline/cold surface) -------------------------------------
    def goodput_report(self) -> "GoodputReport":  # graftcheck: cold
        """Aggregate the retained spans into per-scope category totals."""
        return GoodputReport.from_spans(self.snapshot())

    def export_chrome_trace(self, path: str) -> int:  # graftcheck: cold
        """Write the retained spans as Chrome trace-event JSON (loadable in
        Perfetto / chrome://tracing): one pid per scope (named via
        ``process_name`` metadata), one tid per recording thread, category on
        the event's ``cat`` plus span/parent ids and attrs under ``args``.
        Returns the number of span events written."""
        spans = self.snapshot()
        pids: Dict[str, int] = {}
        events: List[Dict[str, Any]] = []
        threads_seen: set = set()
        for span in spans:
            pid = pids.setdefault(span.scope, len(pids) + 1)
            if (pid, span.thread_id) not in threads_seen:
                threads_seen.add((pid, span.thread_id))
                events.append(
                    {
                        "ph": "M",
                        "pid": pid,
                        "tid": span.thread_id,
                        "name": "thread_name",
                        "args": {"name": span.thread_name},
                    }
                )
            args: Dict[str, Any] = {"span_id": span.span_id}
            if span.parent_id is not None:
                args["parent_id"] = span.parent_id
            if span.attrs:
                args.update(span.attrs)
            events.append(
                {
                    "ph": "X",
                    "pid": pid,
                    "tid": span.thread_id,
                    "name": span.name,
                    "cat": span.category,
                    "ts": span.start * 1e6,  # trace-event timestamps are µs
                    "dur": span.duration * 1e6,
                    "args": args,
                }
            )
        for scope, pid in pids.items():
            events.append(
                {
                    "ph": "M",
                    "pid": pid,
                    "name": "process_name",
                    "args": {"name": scope},
                }
            )
        payload = {"traceEvents": events, "displayTimeUnit": "ms"}
        with open(path, "w", encoding="utf-8") as f:
            json.dump(payload, f)
        return len(spans)


class GoodputReport:
    """Per-scope goodput category totals (seconds), built either from spans
    (:meth:`from_spans` — self-time attribution) or from an externally kept
    ledger of category seconds (:class:`ContinuousLearningLoop` keeps one so
    its ``ml.loop.goodput.fraction`` works with tracing off).

    Within one scope the category totals sum to the scope's root-span wall
    time — the invariant tests assert and ``tools/traceview.py`` prints.
    Scopes are accounted independently: a cross-scope child (a serving warmup
    span under a loop swap span) counts fully in BOTH scopes, because each
    scope's report answers "where did *this* scope's wall time go".
    """

    def __init__(self, totals: Dict[str, Dict[str, float]]):
        self.totals = {
            scope: {cat: s for cat, s in cats.items() if s > 0.0}
            for scope, cats in totals.items()
        }

    @classmethod
    def from_spans(cls, spans: Iterable[Span]) -> "GoodputReport":  # graftcheck: cold
        by_scope: Dict[str, List[Span]] = {}
        for span in spans:
            if span.end is not None:
                by_scope.setdefault(span.scope, []).append(span)
        totals: Dict[str, Dict[str, float]] = {}
        for scope, group in by_scope.items():
            ids = {s.span_id for s in group}
            child_s: Dict[int, float] = {}
            for s in group:
                if s.parent_id is not None and s.parent_id in ids:
                    child_s[s.parent_id] = child_s.get(s.parent_id, 0.0) + s.duration
            cats = totals.setdefault(scope, {})
            for s in group:
                self_s = max(0.0, s.duration - child_s.get(s.span_id, 0.0))
                if self_s == 0.0:
                    continue
                pad_share = _padding_share(s)
                if pad_share > 0.0:
                    cats[CAT_PADDING] = cats.get(CAT_PADDING, 0.0) + self_s * pad_share
                    self_s *= 1.0 - pad_share
                cats[s.category] = cats.get(s.category, 0.0) + self_s
        return cls(totals)

    def scopes(self) -> List[str]:
        return sorted(self.totals)

    def category_s(self, scope: str, category: str) -> float:
        return self.totals.get(scope, {}).get(category, 0.0)

    def wall_s(self, scope: str) -> float:
        """Total attributed seconds for ``scope`` (== its root-span wall)."""
        return sum(self.totals.get(scope, {}).values())

    def fraction(self, scope: Optional[str] = None) -> Optional[float]:
        """Goodput fraction — productive / total attributed — for one scope,
        or over every scope when ``scope`` is None. None when nothing is
        attributed."""
        if scope is not None:
            cats = self.totals.get(scope, {})
            total = sum(cats.values())
            return cats.get(CAT_PRODUCTIVE, 0.0) / total if total > 0.0 else None
        productive = total = 0.0
        for cats in self.totals.values():
            productive += cats.get(CAT_PRODUCTIVE, 0.0)
            total += sum(cats.values())
        return productive / total if total > 0.0 else None

    def publish(self, registry=metrics) -> None:
        """Write the ``ml.goodput.*`` gauges: per scope, one
        ``ml.goodput.<category>.ms`` gauge per attributed category plus
        ``ml.goodput.fraction``."""
        for scope, cats in self.totals.items():
            for category, seconds in cats.items():
                registry.gauge(scope, MLMetrics.goodput_ms(category), seconds * 1000.0)
            fraction = self.fraction(scope)
            if fraction is not None:
                registry.gauge(scope, MLMetrics.GOODPUT_FRACTION, fraction)

    def __repr__(self) -> str:
        return f"GoodputReport(scopes={self.scopes()}, fraction={self.fraction()})"


def _padding_share(span: Span) -> float:
    """Fraction of a span's self time attributed to bucket padding: spans
    carrying ``rows``/``bucket`` attrs executed a padded batch, and
    ``(bucket - rows) / bucket`` of their work fed pad rows.

    Spans that additionally carry ``nnz``/``nnz_cap`` attrs executed a
    sparse-convention batch (docs/sparse.md): the program computed
    ``bucket × nnz_cap`` entry cells of which only ``nnz`` (the true
    entries of the true rows) were real. That single ratio covers BOTH the
    row round-up and the ELL slot padding, and REPLACES the rows/bucket
    split for such spans — each padded cell is counted exactly once, the
    same discipline as the PR 9 DP round-up accounting."""
    attrs = span.attrs or {}
    nnz = attrs.get("nnz")
    cap = attrs.get("nnz_cap")
    bucket = attrs.get("bucket")
    if (
        isinstance(nnz, int)
        and isinstance(cap, int)
        and isinstance(bucket, int)
        and cap > 0
        and bucket > 0
    ):
        cells = bucket * cap
        if nnz < 0 or nnz >= cells:
            return 0.0
        return (cells - nnz) / cells
    attrs = span.attrs
    if not attrs:
        return 0.0
    rows = attrs.get("rows")
    bucket = attrs.get("bucket")
    if not isinstance(rows, int) or not isinstance(bucket, int) or bucket <= 0:
        return 0.0
    if rows >= bucket or rows < 0:
        return 0.0
    return (bucket - rows) / bucket


class Tracer:
    """The process tracer: one recorder, one enabled flag, per-thread span
    stacks. ``enabled`` is read on every instrumented site — keep it a plain
    attribute (the whole point of the no-op contract)."""

    #: Injectable monotonic clock; MUST share a timebase with
    #: ``time.perf_counter`` because retro-recorded spans (queue wait) reuse
    #: timestamps the serving tier already took from it.
    clock: Callable[[], float] = staticmethod(time.perf_counter)

    def __init__(self, recorder: Optional[SpanRecorder] = None, enabled: bool = False):
        # Deliberately single-writer fields: only the main (caller/API) role
        # flips them via enable()/disable(); every instrumented thread reads
        # them raw — a benign-stale read costs at most one span. Keeping
        # `enabled` a plain unlocked attribute IS the disabled-path contract.
        self.enabled = bool(enabled)  # graftcheck: owned-by=main
        self.xprof = bool(config.get(Options.OBSERVABILITY_TRACE_XPROF))  # graftcheck: owned-by=main
        self.recorder = recorder if recorder is not None else SpanRecorder()  # graftcheck: owned-by=main
        self._tls = threading.local()

    # -- span stack -----------------------------------------------------------
    def _stack(self) -> List[Span]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def current(self) -> Optional[Span]:
        """The innermost open stack-managed span on this thread."""
        stack = self._stack()
        return stack[-1] if stack else None

    def _push(self, span: Span) -> None:
        self._stack().append(span)
        if span._annotation is None and self.xprof:
            span._annotation = _annotation(span.name)
        if span._annotation is not None:  # a phase span brings its own
            span._annotation.__enter__()

    def _pop(self, span: Span) -> None:
        if span._annotation is not None:
            span._annotation.__exit__(None, None, None)
            span._annotation = None
        stack = self._stack()
        if stack and stack[-1] is span:
            stack.pop()
        elif span in stack:  # mis-nested exit: drop it and everything above
            del stack[stack.index(span) :]
        span.end = self.clock()
        self.recorder.record(span)

    # -- creating spans -------------------------------------------------------
    def _make(self, name: str, category: str, scope: str, parent: Optional[Span]) -> Span:
        if parent is not None:
            parent_id = parent.span_id
        else:
            top = self.current()
            parent_id = top.span_id if top is not None else None
        current_thread = threading.current_thread()
        return Span(
            name,
            category,
            scope,
            self.clock(),
            _next_id(),
            parent_id,
            current_thread.ident or 0,
            current_thread.name,
            tracer_=self,
        )

    def span(self, name: str, category: str = CAT_PRODUCTIVE, scope: str = "ml", parent: Optional[Span] = None):
        """Context-manager span. THE hot-path entry point: when disabled this
        is one attribute check returning the shared no-op span."""
        if not self.enabled:
            return _NOOP_SPAN
        return self._make(name, category, scope, parent)

    def phase(self, name: str, category: str = CAT_PRODUCTIVE, scope: str = "ml.train", **counts):
        """Context manager for one phase of a job, visible to ANY profiler
        session without a switch: it always enters
        ``jax.profiler.TraceAnnotation(name, **counts)``, whose events land on
        the host plane of the profiler's trace, on the ``XLA Ops`` line's
        clock, with ``counts`` as their stats. Tracer enabled: an ordinary
        recorded :class:`Span` (parent from the thread's stack, ``counts`` as
        its attrs) that holds the annotation open, whatever ``xprof`` says.
        Tracer disabled: the bare annotation, a no-op outside a profiler
        session (under a microsecond, but an allocation — unlike ``span``).

        ONLY for sites that run a bounded number of times per job (the phases
        of a fit, a chunk of steps): never per step, per row, per request or
        per layout unit — hot sites use :meth:`span`. A site takes its
        decision (reuse or build) first and opens the phase with it among
        ``counts`` (ints); what only the phase's own work can count is added
        before it closes with ``set_metadata(**counts)``, which both returned
        forms have."""
        annotation = _annotation(name, counts)
        if not self.enabled:
            return annotation
        span = self._make(name, category, scope, None)
        if counts:
            span.attrs = counts
        span._annotation = annotation
        return span

    def begin(self, name: str, category: str = CAT_PRODUCTIVE, scope: str = "ml", parent: Optional[Span] = None) -> Optional[Span]:
        """Manual span: starts now, is NOT pushed on the thread stack, and
        must be finished with :meth:`end` (possibly on another thread). None
        when disabled, so call sites store-and-forward the handle blindly."""
        if not self.enabled:
            return None
        return self._make(name, category, scope, parent)

    def end(self, span: Optional[Span]) -> None:
        """Finish a manual span (None-safe — pairs with :meth:`begin`)."""
        if span is None or span.end is not None:
            return
        span.end = self.clock()
        self.recorder.record(span)

    def record(
        self,
        name: str,
        category: str,
        scope: str,
        start: float,
        end: float,
        parent: Optional[Span] = None,
        attrs: Optional[Dict[str, Any]] = None,
    ) -> None:
        """Retro-record a completed span from already-measured monotonic
        timestamps (``time.perf_counter`` timebase). The span inherits the
        parent's thread identity when given — a queue-wait span belongs to
        the thread that enqueued, not the batcher thread recording it."""
        if not self.enabled:
            return
        if parent is not None:
            parent_id, thread_id, thread_name = parent.span_id, parent.thread_id, parent.thread_name
        else:
            current_thread = threading.current_thread()
            parent_id, thread_id, thread_name = None, current_thread.ident or 0, current_thread.name
        span = Span(name, category, scope, start, _next_id(), parent_id, thread_id, thread_name)
        span.end = max(start, end)
        if attrs:
            span.attrs = dict(attrs)
        self.recorder.record(span)

    # -- lifecycle ------------------------------------------------------------
    def enable(self, capacity: Optional[int] = None, xprof: Optional[bool] = None) -> "Tracer":
        if capacity is not None:
            self.recorder = SpanRecorder(capacity)
        if xprof is not None:
            self.xprof = bool(xprof)
        self.enabled = True
        return self

    def disable(self) -> "Tracer":
        self.enabled = False
        return self

    def goodput_report(self) -> GoodputReport:  # graftcheck: cold
        return self.recorder.goodput_report()


def _annotation(name: str, counts: Optional[Dict[str, Any]] = None):  # graftcheck: cold
    """A ``jax.profiler.TraceAnnotation``, not yet entered: a no-op outside a
    profiler session, an event on the host plane of the profiler's trace
    inside one (``counts`` become the event's stats). The import is lazy:
    this module is imported by tiers that never touch the profiler."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name, **(counts or {}))


#: The process tracer. ``observability.trace`` (env:
#: FLINK_ML_TPU_OBSERVABILITY_TRACE=1) arms it at import; ``enable()`` /
#: ``disable()`` flip it at runtime.
tracer = Tracer(enabled=bool(config.get(Options.OBSERVABILITY_TRACE)))


def enable(capacity: Optional[int] = None, xprof: Optional[bool] = None) -> Tracer:
    """Turn the process tracer on (optionally with a fresh ring of
    ``capacity`` and/or xprof mirroring)."""
    return tracer.enable(capacity=capacity, xprof=xprof)


def disable() -> Tracer:
    return tracer.disable()


@contextlib.contextmanager
def capture(capacity: Optional[int] = None, xprof: Optional[bool] = None):
    """Trace a region into a fresh recorder and restore the previous tracer
    state after — the test and smoke harness entry point:

        with trace.capture() as recorder:
            server.predict(df)
        recorder.export_chrome_trace("/tmp/trace.json")
    """
    prev_enabled, prev_recorder, prev_xprof = tracer.enabled, tracer.recorder, tracer.xprof
    tracer.recorder = SpanRecorder(capacity)
    if xprof is not None:
        tracer.xprof = bool(xprof)
    tracer.enabled = True
    try:
        yield tracer.recorder
    finally:
        tracer.enabled = prev_enabled
        tracer.recorder = prev_recorder
        tracer.xprof = prev_xprof
