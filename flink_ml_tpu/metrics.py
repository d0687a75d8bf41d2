"""ML observability metrics.

Reference: ``flink-ml-servable-core/.../MLMetrics.java`` — the metric-name constants
(``ml.model.timestamp``, ``ml.model.version``) that online models register as gauges
(OnlineStandardScalerModel.java:206-211, OnlineKMeansModel), scraped in tests via
Flink's InMemoryReporter (OnlineKMeansTest.java:152-156).

Here: a process-local registry of named gauges, grouped per stage instance. Tests
scrape ``MetricsRegistry`` exactly like InMemoryReporter; production wiring can
mirror the gauges to any sink.
"""
from __future__ import annotations

import re
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

__all__ = ["MLMetrics", "Histogram", "MetricsRegistry", "metrics"]


class MLMetrics:
    """Ref MLMetrics.java constants, extended with the supervised-execution
    counters (restart strategies / checkpoint failover — docs/fault_tolerance.md)."""

    ML_GROUP = "ml"
    TIMESTAMP = "ml.model.timestamp"
    VERSION = "ml.model.version"

    # Supervisor counters (scope = "ml.execution[<supervisor name>]").
    EXECUTION_GROUP = "ml.execution"
    NUM_ATTEMPTS = "ml.execution.attempts"
    NUM_RESTARTS = "ml.execution.restarts"
    NUM_FATAL = "ml.execution.fatal"
    RECOVERY_MS = "ml.execution.recovery.ms"  # downtime of the last recovery
    TOTAL_RECOVERY_MS = "ml.execution.recovery.total.ms"

    # Checkpoint-failover counters (scope = CHECKPOINT_GROUP, process-global).
    CHECKPOINT_GROUP = "ml.checkpoint"
    CHECKPOINT_QUARANTINED = "ml.checkpoint.quarantined"
    CHECKPOINT_FALLBACKS = "ml.checkpoint.fallbacks"
    CHECKPOINT_TMP_SWEPT = "ml.checkpoint.tmp.swept"
    CHECKPOINT_SHARD_PIECES = "ml.checkpoint.shard.pieces"  # per-shard leaves written, counter

    # Sharded-training counters (scope = TRAIN_GROUP, process-global —
    # parallel/train_sharding.py, docs/distributed_training.md).
    TRAIN_GROUP = "ml.train"
    TRAIN_SHARD_INGEST_ROWS = "ml.train.shard.ingest.rows"  # rows dealt onto the mesh, counter
    TRAIN_SHARD_PAD_ROWS = "ml.train.shard.pad.rows"  # zero-mask padding rows, counter
    TRAIN_SHARDED_FITS = "ml.train.sharded.fits"  # fits run on the deterministic tier, counter
    # The fit prologue, counted where its train.* phases are opened (trace.py
    # Tracer.phase; docs/observability.md "The fit span tree").
    TRAIN_LAYOUT_BUILDS = "ml.train.layout.builds"  # one-hot layouts built on the host, counter
    TRAIN_LAYOUT_REUSES = "ml.train.layout.reuses"  # fits answered by a cache's layout memo, counter
    TRAIN_LAYOUT_CHUNKS = "ml.train.layout.chunks"  # chunks of heavy feature blocks in the layouts built, counter
    TRAIN_PREMAT_BUILDS = "ml.train.premat.builds"  # premat one-hot materializations on device, counter
    TRAIN_H2D_BYTES = "ml.train.h2d.bytes"  # bytes handed to device_put by caches and layouts, counter
    # The decoder LM's fit (models/lm/decoder_lm.py), counted where train.drain closes.
    TRAIN_LM_TOKENS = "ml.train.lm.tokens"  # tokens the fit's steps consumed, counter
    TRAIN_LM_FOLD_CHUNKS = "ml.train.lm.fold.chunks"  # (query tile, key chunk) pairs of the fit's causal folds, counter
    TRAIN_LM_FOLD_CHUNKS_VISITED = "ml.train.lm.fold.chunks_visited"  # those the mask does not hide: the ones computed, counter
    TRAIN_LM_FOLD_WIN_CHUNKS = "ml.train.lm.fold.win_chunks"  # the windowed layers' share of lm.fold.chunks, counter
    TRAIN_LM_FOLD_WIN_CHUNKS_VISITED = "ml.train.lm.fold.win_chunks_visited"  # those neither the mask nor the window hides, counter
    TRAIN_LM_FOLD_BD_CHUNKS = "ml.train.lm.fold.bd_chunks"  # the share of lm.fold.chunks of folds under the block-diffusion mask (doubled sequences), counter
    TRAIN_LM_FOLD_BD_CHUNKS_VISITED = "ml.train.lm.fold.bd_chunks_visited"  # those the block-diffusion mask does not hide, counter
    TRAIN_LM_DIFFUSION_TARGETS = "ml.train.lm.diffusion.targets"  # masked positions the block-diffusion objective scored, counter
    TRAIN_LM_LOOP_TRIPS = "ml.train.lm.loop.trips"  # passes of the whole stack the fit's steps ran (steps x numLoops), counter
    TRAIN_LM_LOOP_LAYER_APPLICATIONS = "ml.train.lm.loop.layer_applications"  # block applications (layers x passes x steps), counter
    TRAIN_LM_SCAN_CHUNKS = "ml.train.lm.scan.chunks"  # chunks of the state-space scan (chunks x heads x sequences x Mamba-2 layers x steps), counter
    TRAIN_LM_SCAN_KERNEL_CHUNKS = "ml.train.lm.scan.kernel_chunks"  # those of them that passed through the scan's kernel pair (parallel/ssd.py), counter
    TRAIN_LM_CONV_POSITIONS = "ml.train.lm.conv.positions"  # positions x channels of the Mamba-2 layers' causal convolutions (x layers x steps, forward), counter
    TRAIN_LM_CONV_KERNEL_POSITIONS = "ml.train.lm.conv.kernel_positions"  # those of them that the convolution's kernel pair covered (parallel/causal_conv.py), counter
    TRAIN_LM_SCAN_LAYERS = "ml.train.lm.scan.layers"  # Mamba-2 layer applications (layers x steps), counter
    TRAIN_LM_KDA_CHUNKS = "ml.train.lm.kda.chunks"  # chunks of the gated delta rule (chunks x heads x sequences x delta-rule layers x steps), counter
    TRAIN_LM_KDA_KERNEL_CHUNKS = "ml.train.lm.kda.kernel_chunks"  # those of them that passed through the delta rule's kernel pair (parallel/kda.py), counter
    TRAIN_LM_KDA_SCALAR_CHUNKS = "ml.train.lm.kda.scalar_chunks"  # those of them that took the kernel pair's one-decay-a-head form (a [chunk, chunk] decay factor, no sub-chunks), counter
    TRAIN_LM_KDA_LAYERS = "ml.train.lm.kda.layers"  # delta-rule layer applications (layers x steps), counter
    TRAIN_LM_MLA_LAYERS = "ml.train.lm.mla.layers"  # latent-attention layer applications (layers, a multi-token-prediction module's among them, x steps), counter
    TRAIN_LM_MTP_TARGETS = "ml.train.lm.mtp.targets"  # positions the multi-token-prediction module scored (sequences x (length - 2) x steps), counter
    TRAIN_MOE_ROWS = "ml.train.moe.rows"  # (token, expert) rows the experts held here ran, counter
    TRAIN_MOE_ROWS_ABSENT = "ml.train.moe.rows_absent"  # rows routed to experts held elsewhere, counter
    TRAIN_MOE_LAYER_STEPS = "ml.train.moe.layer_steps"  # expert layers x steps of fits that take the routed rows in windows, counter
    TRAIN_MOE_LAYER_STEPS_COMPACT = "ml.train.moe.layer_steps_compact"  # those that carried fewer rows than were routed, counter
    TRAIN_MOE_ROWS_CARRIED = "ml.train.moe.rows_carried"  # rows those layer-steps' windows took through the experts, counter

    # Online-serving runtime (scope = "ml.serving[<server name>]" — see
    # docs/serving.md for the full table).
    SERVING_GROUP = "ml.serving"
    SERVING_QUEUE_DEPTH = "ml.serving.queue.depth"  # rows waiting, gauge
    SERVING_REQUESTS = "ml.serving.requests"  # admitted, counter
    SERVING_BATCHES = "ml.serving.batches"  # executed batches, counter
    SERVING_REJECTED = "ml.serving.rejected"  # ServingOverloadedError, counter
    SERVING_TIMEOUTS = "ml.serving.timeouts"  # deadline expiries, counter
    SERVING_SWAPS = "ml.serving.swaps"  # hot model swaps, counter
    SERVING_SWAP_FAILURES = "ml.serving.swap.failures"  # rejected versions, counter
    SERVING_POLL_ERRORS = "ml.serving.poll.errors"  # poller scan failures, counter
    SERVING_BATCH_SIZE = "ml.serving.batch.size"  # pre-padding rows, histogram
    SERVING_LATENCY_MS = "ml.serving.latency.ms"  # enqueue→response, histogram
    SERVING_LATENCY_P50_MS = "ml.serving.latency.p50.ms"  # gauge from histogram
    SERVING_LATENCY_P99_MS = "ml.serving.latency.p99.ms"  # gauge from histogram

    # Serving fast path (serving/plan.py — fused per-bucket executables).
    SERVING_FUSED_STAGES = "ml.serving.fastpath.fused.stages"  # stages fused, gauge
    SERVING_FALLBACK_STAGES = "ml.serving.fastpath.fallback.stages"  # per-stage, gauge
    SERVING_FUSED_BATCHES = "ml.serving.fastpath.fused.batches"  # fused executions, counter
    SERVING_FALLBACK_BATCHES = "ml.serving.fastpath.fallback.batches"  # ineligible batches, counter
    SERVING_FASTPATH_COMPILES = "ml.serving.fastpath.compiles"  # post-warmup compiles (0 = healthy), counter
    SERVING_WARMUP_COMPILE_MS = "ml.serving.fastpath.warmup.compile.ms"  # AOT warmup wall time minus cache loads, gauge
    SERVING_WARMUP_CACHE_LOAD_MS = "ml.serving.fastpath.warmup.cache.load.ms"  # warmup time spent loading cached executables, gauge
    SERVING_INFLIGHT_DEPTH = "ml.serving.inflight.depth"  # dispatched-not-finalized batches, gauge

    # SLO-adaptive controller (serving/controller.py — docs/serving.md
    # "Load shedding & adaptive control").
    SERVING_SHED = "ml.serving.shed"  # priority sheds under sustained overload, counter
    SERVING_DEADLINE_DISPATCH = "ml.serving.deadline.dispatch"  # expired-in-window fail-fasts before dispatch, counter
    SERVING_CONTROLLER_DEPTH = "ml.serving.controller.depth"  # live pipeline-depth setting, gauge
    SERVING_CONTROLLER_ACTIONS = "ml.serving.controller.actions"  # controller actions fired, counter
    SERVING_CONTROLLER_DOWNSHIFTS = "ml.serving.controller.downshifts"  # deadline-aware bucket caps applied, counter
    SERVING_CONTROLLER_MESH_RECOMMEND = "ml.serving.controller.mesh.recommend"  # next mesh width on the ladder, gauge

    # Mesh-sharded serving (serving.mesh > 1 — docs/serving.md).
    SERVING_SHARD_COUNT = "ml.serving.shard.count"  # data-axis width of the plan's mesh, gauge
    SERVING_SHARD_MODEL_AXIS = "ml.serving.shard.model.axis"  # tensor-parallel width, gauge
    SERVING_SHARD_ROWS = "ml.serving.shard.rows"  # per-shard rows through fused batches, counter

    # Continuous learning loop (loop/ — closed train → publish → serve loop;
    # scope = "ml.loop[<loop name>]", docs/continuous.md has the table).
    LOOP_GROUP = "ml.loop"
    LOOP_PUBLISHED = "ml.loop.versions.published"  # servable versions published, counter
    LOOP_SWAPPED = "ml.loop.versions.swapped"  # versions flipped into serving, counter
    LOOP_ROLLBACKS = "ml.loop.rollbacks"  # regressions reverted to N-1, counter
    LOOP_QUARANTINED = "ml.loop.versions.quarantined"  # bad versions set aside, counter
    LOOP_PUBLISH_TO_SERVE_MS = "ml.loop.publish.to.serve.ms"  # publish→flip, histogram
    LOOP_WARM_MS = "ml.loop.warm.ms"  # last pre-flip AOT warm compile time (cache loads excluded), gauge
    LOOP_WARM_CACHE_MS = "ml.loop.warm.cache.ms"  # last pre-flip warm time spent loading cached executables, gauge
    LOOP_STEPS = "ml.loop.steps"  # loop turns completed, counter
    LOOP_GOODPUT_FRACTION = "ml.loop.goodput.fraction"  # productive/total time, gauge
    LOOP_DRIFT_SCORE = "ml.loop.drift.score"  # live model rolling score, gauge
    LOOP_DRIFT_BASELINE = "ml.loop.drift.baseline"  # reference version score, gauge
    LOOP_DRIFT_REGRESSIONS = "ml.loop.drift.regressions"  # threshold trips, counter

    # Fleet serving (flink_ml_tpu/fleet — supervised replica pool + router;
    # scope = "ml.fleet[<fleet name>]", docs/fleet.md has the table).
    FLEET_GROUP = "ml.fleet"
    FLEET_DISPATCHES = "ml.fleet.dispatches"  # requests dispatched to a replica, counter
    FLEET_RETRIES = "ml.fleet.retries"  # overload retries to a different replica, counter
    FLEET_FAILOVERS = "ml.fleet.failovers"  # redispatches after a replica connection loss, counter
    FLEET_HEDGES = "ml.fleet.hedges"  # duplicate tail-latency dispatches, counter
    FLEET_HEDGE_WINS = "ml.fleet.hedge.wins"  # hedged duplicate answered first, counter
    FLEET_FAILFAST = "ml.fleet.failfast"  # whole-fleet-shedding fail-fasts, counter
    FLEET_EJECTS = "ml.fleet.ejects"  # replicas taken out of rotation, counter
    FLEET_RESPAWNS = "ml.fleet.respawns"  # respawn attempts started, counter
    FLEET_READMITS = "ml.fleet.readmits"  # respawned replicas back in rotation, counter
    FLEET_DEAD = "ml.fleet.replicas.dead"  # slots whose restart budget exhausted, counter
    FLEET_LIVE = "ml.fleet.replicas.live"  # in-rotation replicas, gauge
    FLEET_SIZE = "ml.fleet.replicas.total"  # pool slots, gauge
    FLEET_CANARY_STARTED = "ml.fleet.canary.started"  # canary evaluations begun, counter
    FLEET_CANARY_PROMOTED = "ml.fleet.canary.promoted"  # versions promoted fleet-wide, counter
    FLEET_CANARY_QUARANTINED = "ml.fleet.canary.quarantined"  # regressed canaries set aside, counter
    FLEET_CANARY_DISPATCHES = "ml.fleet.canary.dispatches"  # slice-gated canary dispatches, counter
    FLEET_LATENCY_MS = "ml.fleet.latency.ms"  # router-observed submit->response, histogram

    # Goodput attribution (flink_ml_tpu.trace — the ML Productivity Goodput
    # accounting; one gauge set per traced scope, docs/observability.md).
    GOODPUT_GROUP = "ml.goodput"
    GOODPUT_FRACTION = "ml.goodput.fraction"  # productive / total traced, gauge

    @staticmethod
    def goodput_ms(category: str) -> str:
        """Gauge name for one goodput category's attributed milliseconds
        (``ml.goodput.productive.ms``, ``ml.goodput.queue.ms``, ...)."""
        return f"{MLMetrics.GOODPUT_GROUP}.{category}.ms"

    #: Reason labels of the per-reason fast-path fallback counters
    #: (docs/sparse.md): why a batch/segment left the compiled plan.
    FALLBACK_REASONS = ("sparse", "ragged", "off_ladder", "signature", "specless")

    @staticmethod
    def fallback_reason(tier: str, reason: str) -> str:
        """Counter name for one reason-labelled fast-path fallback —
        ``ml.serving.fastpath.fallback.sparse``,
        ``ml.batch.fastpath.fallback.off_ladder``, ... ``tier`` is
        ``"serving"`` or ``"batch"``. The unlabelled aggregate counters
        (``...fallback.batches`` / ``...fallback.segments``) keep counting
        every fallback; the labelled ones attribute each to its cause."""
        return f"ml.{tier}.fastpath.fallback.{reason}"

    # Batch transform fast path (builder/batch_plan.py — fused chunked plans;
    # scope = "ml.batch[plan]" unless the caller names its own).
    BATCH_FUSED_STAGES = "ml.batch.fastpath.fused.stages"  # stages fused, gauge
    BATCH_FALLBACK_STAGES = "ml.batch.fastpath.fallback.stages"  # per-stage, gauge
    BATCH_FUSED_CHUNKS = "ml.batch.fastpath.fused.chunks"  # chunk executions, counter
    BATCH_FUSED_ROWS = "ml.batch.fastpath.fused.rows"  # rows through fused chains, counter
    BATCH_FALLBACK_SEGMENTS = "ml.batch.fastpath.fallback.segments"  # ineligible segment runs, counter
    BATCH_COMPILES = "ml.batch.fastpath.compiles"  # chain compiles (per new chunk signature), counter
    BATCH_PLAN_BUILD_MS = "ml.batch.fastpath.plan.build.ms"  # build + model upload wall time, gauge
    BATCH_CHUNK_MS = "ml.batch.fastpath.chunk.ms"  # dispatch→readback per chunk, histogram

    # Fusion tier of the compiled plans (fusion.mode — docs/fusion.md).
    # Published under the owning plan's scope, like the fastpath metrics.
    FUSION_MODE = "ml.fusion.mode"  # 0 = exact, 1 = fast (the plan's tier), gauge
    FUSION_PROGRAMS_EXACT = "ml.fusion.programs.exact"  # exact-partition program compiles, counter
    FUSION_PROGRAMS_FUSED = "ml.fusion.programs.fused"  # cross-reduction XLA program compiles, counter
    FUSION_PROGRAMS_MEGAKERNEL = "ml.fusion.programs.megakernel"  # Pallas megakernel compiles, counter
    FUSION_MEGAKERNEL_FALLBACKS = "ml.fusion.megakernel.fallbacks"  # megakernels the backend rejected at compile time (merged XLA program served instead), counter
    FUSION_PLAN_CHOICE = "ml.fusion.plan.choice"  # most aggressive tier last compiled: 0 exact / 1 fused / 2 megakernel, gauge
    FUSION_PLAN_SCORE = "ml.fusion.plan.score"  # cost-model score of the last compiled chain, gauge

    # Precision tier of the compiled plans (precision.mode — docs/precision.md).
    # Published under the owning plan's scope, like the fusion metrics.
    PRECISION_MODE = "ml.precision.mode"  # 0 = f32, 1 = bf16, 2 = int8 (the plan's tier), gauge
    PRECISION_FALLBACKS = "ml.precision.fallbacks"  # drift-triggered falls back to the warm f32 plan, counter
    PRECISION_FALLBACK_ACTIVE = "ml.precision.fallback.active"  # 1 while serving the f32 fallback plan, gauge
    PRECISION_QUANTIZED_ARRAYS = "ml.precision.quantized.arrays"  # weight arrays int8-quantized at publish, counter

    # Mesh-sharded batch transform (batch.mesh > 1 — docs/batch_transform.md).
    BATCH_SHARD_COUNT = "ml.batch.shard.count"  # data-axis width of the plan's mesh, gauge
    BATCH_SHARD_ROWS = "ml.batch.shard.rows"  # per-shard rows through sharded chunks, counter
    BATCH_SHARD_PAD_ROWS = "ml.batch.shard.pad.rows"  # DP round-up pad rows on ragged chunks, counter
    BATCH_SHARD_REPLICATED_CHUNKS = "ml.batch.shard.replicated.chunks"  # tails run replicated, counter

    # Persistent compiled-plan cache (servable/plancache.py — serialized AOT
    # executables on disk; scope = "ml.plancache", docs/plancache.md).
    PLANCACHE_GROUP = "ml.plancache"
    PLANCACHE_HITS = "ml.plancache.hits"  # executables served from disk, counter
    PLANCACHE_MISSES = "ml.plancache.misses"  # entry absent -> live compile, counter
    PLANCACHE_STORES = "ml.plancache.stores"  # entries written, counter
    PLANCACHE_STORE_ERRORS = "ml.plancache.store.errors"  # serialize/write failures (fail-open), counter
    PLANCACHE_QUARANTINED = "ml.plancache.quarantined"  # corrupt/mismatched entries set aside, counter
    PLANCACHE_EVICTED = "ml.plancache.evicted"  # LRU evictions past plancache.max.bytes, counter
    PLANCACHE_BYTES = "ml.plancache.bytes"  # bytes of *.plan entries on disk, gauge
    PLANCACHE_LOAD_MS = "ml.plancache.load.ms"  # read+verify+deserialize per hit, histogram
    PLANCACHE_TMP_SWEPT = "ml.plancache.tmp.swept"  # orphaned .tmp files swept at init, counter

    # Flight recorder + incident bundles (flink_ml_tpu.telemetry — the
    # always-on decision journal; scope = "ml.telemetry", docs/observability.md).
    TELEMETRY_GROUP = "ml.telemetry"
    TELEMETRY_EVENTS = "ml.telemetry.journal.events"  # records written to disk, counter
    TELEMETRY_DROPPED = "ml.telemetry.journal.dropped"  # queue-overflow drops, counter
    TELEMETRY_WRITE_ERRORS = "ml.telemetry.journal.write.errors"  # failed/torn writes, counter
    TELEMETRY_SEQ = "ml.telemetry.journal.seq"  # last written sequence number, gauge
    TELEMETRY_INCIDENTS = "ml.telemetry.incidents"  # bundles written, counter
    TELEMETRY_INCIDENTS_SUPPRESSED = "ml.telemetry.incidents.suppressed"  # rate-limited, counter
    TELEMETRY_HTTP_REQUESTS = "ml.telemetry.http.requests"  # endpoint hits, counter


class Histogram:
    """Bounded-window observation histogram (the DescriptiveStatisticsHistogram
    role of Flink's metric system): keeps the last ``window`` observations and
    answers quantiles over them. Thread-safe; cheap enough for per-request use."""

    def __init__(self, window: int = 4096):
        self._window = int(window)
        self._values: List[float] = []
        self._pos = 0
        self._count = 0
        self._sum = 0.0
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        value = float(value)
        with self._lock:
            if len(self._values) < self._window:
                self._values.append(value)
            else:  # ring overwrite: oldest observation drops out
                self._values[self._pos] = value
                self._pos = (self._pos + 1) % self._window
            self._count += 1
            self._sum += value

    @property
    def count(self) -> int:
        """Total observations ever (not just those still in the window)."""
        with self._lock:
            return self._count

    @property
    def sum(self) -> float:
        with self._lock:
            return self._sum

    def quantile(self, q: float) -> Optional[float]:
        """Nearest-rank quantile over the retained window; None when empty."""
        return self.quantiles((q,))[0]

    def quantiles(self, qs: Sequence[float]) -> List[Optional[float]]:
        """Nearest-rank quantiles over the retained window with ONE sort for
        the whole batch — the per-batch p50/p99 gauge refresh on the serving
        hot path sorts the 4096-entry window once instead of once per
        quantile. All-None when empty."""
        for q in qs:
            if not 0.0 <= q <= 1.0:
                raise ValueError(f"quantile must be in [0, 1], got {q}")
        with self._lock:
            if not self._values:
                return [None for _ in qs]
            ordered = sorted(self._values)
        n = len(ordered)
        return [ordered[min(int(q * n), n - 1)] for q in qs]

    def values(self) -> List[float]:
        """The retained observations (unordered), for test scraping."""
        with self._lock:
            return list(self._values)

    def __repr__(self) -> str:
        return f"Histogram(count={self.count}, p50={self.quantile(0.5)})"


class MetricsRegistry:
    """Named gauges per scope (scope ≈ the operator's metric group)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._gauges: Dict[str, Dict[str, Any]] = {}
        # Names incremented via counter() — the Prometheus exposition needs
        # the distinction (counters render as `# TYPE ... counter` with the
        # `_total` suffix real scrapers expect; everything else is a gauge).
        self._counter_names: set = set()

    def gauge(self, scope: str, name: str, value: Any) -> None:
        with self._lock:
            self._gauges.setdefault(scope, {})[name] = value

    def counter(self, scope: str, name: str, inc: int = 1) -> int:
        """Increment-and-get a monotonically growing gauge (restart counts,
        quarantine events). Reads go through ``get`` like any gauge."""
        with self._lock:
            group = self._gauges.setdefault(scope, {})
            group[name] = int(group.get(name, 0)) + inc
            self._counter_names.add(name)
            return group[name]

    def histogram(self, scope: str, name: str, window: int = 4096) -> Histogram:
        """Get-or-create the named Histogram (scraped via ``get`` like any
        gauge — the stored value IS the Histogram object)."""
        with self._lock:
            group = self._gauges.setdefault(scope, {})
            hist = group.get(name)
            if not isinstance(hist, Histogram):
                hist = Histogram(window)
                group[name] = hist
            return hist

    def observe(self, scope: str, name: str, value: float) -> None:
        """Record one observation into the named histogram."""
        self.histogram(scope, name).observe(value)

    def get(self, scope: str, name: str, default: Any = None) -> Any:
        with self._lock:
            return self._gauges.get(scope, {}).get(name, default)

    def scope(self, scope: str) -> Dict[str, Any]:
        with self._lock:
            return dict(self._gauges.get(scope, {}))

    def scopes(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            return {k: dict(v) for k, v in self._gauges.items()}

    def clear(self) -> None:
        with self._lock:
            self._gauges.clear()
            self._counter_names.clear()

    def is_counter(self, name: str) -> bool:
        """Whether ``name`` has ever been incremented via :meth:`counter`."""
        with self._lock:
            return name in self._counter_names

    def render_prometheus(self) -> str:  # graftcheck: cold
        """The whole registry in Prometheus text exposition format (0.0.4).

        Metric names sanitize to ``[a-zA-Z_:][a-zA-Z0-9_:]*`` (dots become
        underscores); the scope rides as a ``scope`` label. Values grown via
        :meth:`counter` render as ``# TYPE ... counter`` with the ``_total``
        suffix real Prometheus scrapers expect; every other numeric renders
        as ``gauge``; ``Histogram``s render as ``summary`` — p50/p90/p99 via
        one :meth:`Histogram.quantiles` sort, plus ``_count``/``_sum``.
        Non-numeric gauge values are skipped.
        """
        numeric: Dict[str, List[Tuple[str, float]]] = {}
        hists: Dict[str, List[Tuple[str, Histogram]]] = {}
        for scope, group in sorted(self.scopes().items()):
            for name, value in sorted(group.items()):
                if isinstance(value, Histogram):
                    hists.setdefault(name, []).append((scope, value))
                elif isinstance(value, bool):
                    numeric.setdefault(name, []).append((scope, float(value)))
                elif isinstance(value, (int, float)):
                    numeric.setdefault(name, []).append((scope, float(value)))
        lines: List[str] = []
        for name in sorted(set(numeric) | set(hists)):
            san = _prometheus_name(name)
            if name in numeric:
                if self.is_counter(name):
                    # Counters take the conventional `_total` suffix; in the
                    # 0.0.4 text format the TYPE line names the sample
                    # itself, so the suffix appears in both.
                    san_sample = f"{san}_total"
                    lines.append(f"# TYPE {san_sample} counter")
                else:
                    san_sample = san
                    lines.append(f"# TYPE {san} gauge")
                for scope, value in numeric[name]:
                    lines.append(f"{san_sample}{{scope={_prometheus_label(scope)}}} {_prometheus_value(value)}")
            if name in hists:
                lines.append(f"# TYPE {san} summary")
                for scope, hist in hists[name]:
                    label = _prometheus_label(scope)
                    for q, v in zip((0.5, 0.9, 0.99), hist.quantiles((0.5, 0.9, 0.99))):
                        if v is not None:
                            lines.append(
                                f'{san}{{scope={label},quantile="{q}"}} {_prometheus_value(v)}'
                            )
                    lines.append(f"{san}_count{{scope={label}}} {hist.count}")
                    lines.append(f"{san}_sum{{scope={label}}} {_prometheus_value(hist.sum)}")
        return "\n".join(lines) + ("\n" if lines else "")


def _prometheus_name(name: str) -> str:
    """Sanitize a dotted metric name to the Prometheus grammar."""
    san = re.sub(r"[^a-zA-Z0-9_:]", "_", name)
    if san and san[0].isdigit():
        san = "_" + san
    return san


def _prometheus_label(value: str) -> str:
    """A quoted, escaped label value."""
    escaped = value.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")
    return f'"{escaped}"'


def _prometheus_value(value: float) -> str:
    """Render a sample value (integers without a trailing .0 for stability)."""
    if float(value).is_integer():
        return str(int(value))
    return repr(float(value))


metrics = MetricsRegistry()
