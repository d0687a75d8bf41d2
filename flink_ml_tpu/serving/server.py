"""InferenceServer — the online serving front end.

Wraps any ``TransformerServable``/``ModelServable`` (or a whole
``PipelineModelServable``) behind:

- a **dynamic micro-batcher** (batcher.py) — concurrent ``predict`` calls
  coalesce into padded power-of-two buckets so jitted transforms see a small
  fixed shape set;
- a **versioned registry** (registry.py) — ``swap``/``attach_poller`` replace
  the model with zero unavailability; every batch executes against one
  snapshotted ``(version, servable)`` pair;
- **admission control** — bounded queue, typed ``ServingOverloadedError``
  rejection, per-request deadlines, graceful drain on ``close``;
- **observability** — the ``ml.serving.*`` metrics under scope
  ``ml.serving[<name>]`` (docs/serving.md has the table).

This is the third pillar of the framework (train → supervise → serve): the
inference half of the north star lives here, and it is runtime-free in the L1
sense — importing it never pulls the training stack
(graftcheck's ``layer-deps`` rule enforces that).
"""
from __future__ import annotations

import threading
from typing import List, Optional, Tuple

import flink_ml_tpu.telemetry as telemetry
from flink_ml_tpu.api.dataframe import DataFrame
from flink_ml_tpu.config import Options, config
from flink_ml_tpu.metrics import MLMetrics, metrics
from flink_ml_tpu.serving.batcher import MicroBatcher, pad_to
from flink_ml_tpu.serving.controller import AdaptiveController
from flink_ml_tpu.serving.errors import NoModelError, ServingClosedError
from flink_ml_tpu.serving.plan import CompiledServingPlan
from flink_ml_tpu.serving.registry import ModelRegistry, ModelVersionPoller
from flink_ml_tpu.servable.fusion import resolve_fusion_tier
from flink_ml_tpu.servable.precision import (
    PRECISION_F32,
    PRECISION_GAUGE_VALUE,
    PrecisionTier,
    resolve_precision_tier,
)
from flink_ml_tpu.servable.sharding import resolve_plan_sharding
from flink_ml_tpu.servable.sparse import resolve_sparse_hints
from flink_ml_tpu.trace import CAT_COMPILE, CAT_PRODUCTIVE, CAT_SWAP, tracer

__all__ = ["ServingConfig", "ServingResponse", "InferenceServer"]

#: "plan not built yet" marker distinct from "built, and it is None".
_PLAN_UNSET = object()


class _DispatchHandle:
    """A dispatched fast-path batch: pairs the plan's in-flight execution with
    the model version snapshotted at dispatch time."""

    __slots__ = ("_execution", "_version")

    def __init__(self, execution, version: int):
        self._execution = execution
        self._version = version

    def result(self) -> Tuple[DataFrame, int]:
        return self._execution.finalize(), self._version


class ServingConfig:
    """Resolved serving knobs. Every unset field falls back to the runtime
    config tier (``flink_ml_tpu.config``), so deployments tune the server via
    ``FLINK_ML_TPU_SERVING_*`` env vars without code changes."""

    def __init__(
        self,
        max_batch_size: Optional[int] = None,
        max_delay_ms: Optional[float] = None,
        queue_capacity_rows: Optional[int] = None,
        default_timeout_ms: Optional[float] = None,
        poll_interval_ms: Optional[float] = None,
        fastpath: Optional[bool] = None,
        pipeline_depth: Optional[int] = None,
        mesh: Optional[int] = None,
        mesh_model: Optional[int] = None,
        fusion_mode: Optional[str] = None,
        precision_mode: Optional[str] = None,
        controller: Optional[bool] = None,
        http_port: Optional[int] = None,
        shed_watermark: Optional[float] = None,
        shed_sustain_ms: Optional[float] = None,
        shed_priority: Optional[int] = None,
        controller_window_ms: Optional[float] = None,
        controller_queue_fraction: Optional[float] = None,
        controller_depth_max: Optional[int] = None,
        deadline_safety: Optional[float] = None,
    ):
        self.max_batch_size = (
            int(max_batch_size) if max_batch_size is not None
            else config.get(Options.SERVING_MAX_BATCH_SIZE)
        )
        self.max_delay_ms = (
            float(max_delay_ms) if max_delay_ms is not None
            else config.get(Options.SERVING_MAX_DELAY_MS)
        )
        self.queue_capacity_rows = (
            int(queue_capacity_rows) if queue_capacity_rows is not None
            else config.get(Options.SERVING_QUEUE_CAPACITY_ROWS)
        )
        self.default_timeout_ms = (
            float(default_timeout_ms) if default_timeout_ms is not None
            else config.get(Options.SERVING_DEFAULT_TIMEOUT_MS)
        )
        self.poll_interval_ms = (
            float(poll_interval_ms) if poll_interval_ms is not None
            else config.get(Options.SERVING_POLL_INTERVAL_MS)
        )
        self.fastpath = (
            bool(fastpath) if fastpath is not None
            else config.get(Options.SERVING_FASTPATH)
        )
        self.pipeline_depth = (
            int(pipeline_depth) if pipeline_depth is not None
            else config.get(Options.SERVING_PIPELINE_DEPTH)
        )
        self.mesh = (
            int(mesh) if mesh is not None else config.get(Options.SERVING_MESH)
        )
        self.mesh_model = (
            int(mesh_model) if mesh_model is not None
            else config.get(Options.SERVING_MESH_MODEL)
        )
        self.fusion_mode = (
            str(fusion_mode) if fusion_mode is not None
            else config.get(Options.FUSION_MODE)
        )
        self.precision_mode = (
            str(precision_mode) if precision_mode is not None
            else config.get(Options.PRECISION_MODE)
        )
        self.controller = (
            bool(controller) if controller is not None
            else config.get(Options.SERVING_CONTROLLER)
        )
        # Live telemetry endpoint (telemetry/http.py): None = no HTTP
        # thread (the default); 0 = ephemeral port (tests read
        # server.telemetry.port).
        self.http_port = (
            int(http_port) if http_port is not None
            else config.get(Options.OBSERVABILITY_HTTP_PORT)
        )
        # Controller knobs: kept un-defaulted here (None = "resolve through
        # the config tier at AdaptiveController construction") so a server
        # built before a config.set still picks the deployment's values.
        self.shed_watermark = shed_watermark
        self.shed_sustain_ms = shed_sustain_ms
        self.shed_priority = shed_priority
        self.controller_window_ms = controller_window_ms
        self.controller_queue_fraction = controller_queue_fraction
        self.controller_depth_max = controller_depth_max
        self.deadline_safety = deadline_safety

    def __repr__(self) -> str:
        return (
            f"ServingConfig(max_batch_size={self.max_batch_size}, "
            f"max_delay_ms={self.max_delay_ms}, "
            f"queue_capacity_rows={self.queue_capacity_rows}, "
            f"default_timeout_ms={self.default_timeout_ms}, "
            f"poll_interval_ms={self.poll_interval_ms}, "
            f"fastpath={self.fastpath}, pipeline_depth={self.pipeline_depth}, "
            f"mesh={self.mesh}, mesh_model={self.mesh_model}, "
            f"fusion_mode={self.fusion_mode}, "
            f"precision_mode={self.precision_mode}, controller={self.controller})"
        )


class ServingResponse:
    """One request's result: the transformed rows, the model version that
    served them (exactly one — see ModelRegistry.current), the enqueue→response
    latency, and the padded ``bucket`` the batch executed at.

    The bit-exactness contract (tested by the soak test): within one bucket
    shape a row's result is invariant to its position and to the other rows in
    the batch, so each response row is bit-identical to
    ``servable.transform(pad_to(request_df, response.bucket))`` of the serving
    version. Across *different* shapes XLA may legally differ by 1 ulp (a
    [1,d] and a [64,d] matmul are different executables), which is why the
    bucket rides on the response.
    """

    __slots__ = ("dataframe", "model_version", "latency_ms", "bucket")

    def __init__(self, dataframe: DataFrame, model_version: int, latency_ms: float, bucket: int):
        self.dataframe = dataframe
        self.model_version = model_version
        self.latency_ms = latency_ms
        self.bucket = bucket

    def __repr__(self) -> str:
        return (
            f"ServingResponse(rows={len(self.dataframe)}, "
            f"model_version={self.model_version}, latency_ms={self.latency_ms:.2f}, "
            f"bucket={self.bucket})"
        )


class InferenceServer:
    """Concurrent, versioned, micro-batched serving for one servable slot.

    >>> server = InferenceServer(servable, name="ctr")
    >>> out = server.predict(one_row_df)          # blocks; batched under the hood
    >>> out.dataframe["prediction"], out.model_version

    Hot swap: ``server.swap(version, new_servable)`` (programmatic) or
    ``server.attach_poller(model_dir)`` (watch a publish directory). Both warm
    the incoming servable on every batch bucket *before* it starts serving.
    """

    def __init__(
        self,
        servable=None,
        *,
        version: int = 1,
        name: str = "default",
        serving_config: Optional[ServingConfig] = None,
        warmup_template: Optional[DataFrame] = None,
    ):
        self.name = name
        self.scope = f"{MLMetrics.SERVING_GROUP}[{name}]"
        self.config = serving_config or ServingConfig()
        self.registry = ModelRegistry(self.scope)
        self._warmup_template = warmup_template
        self._template_lock = threading.Lock()
        # Lifecycle state shared between client threads (submit/health — a
        # fleet worker serves them from per-connection threads) and whoever
        # drives attach_poller/close: one lock, consistent everywhere.
        self._state_lock = threading.Lock()
        self._poller: Optional[ModelVersionPoller] = None
        self._closed = False
        # Mesh-sharded serving (serving.mesh > 1, docs/serving.md): one
        # placement for the server's whole life — every version's plan
        # compiles SPMD per-bucket executables against it, with weights
        # device-put per shard at swap time. Resolving here (not lazily)
        # makes a mesh the host cannot satisfy fail at construction.
        self._sharding = (
            resolve_plan_sharding(self.config.mesh, self.config.mesh_model)
            if self.config.fastpath
            else None
        )
        # Fusion tier, resolved once like the mesh: every version's plan
        # compiles under it, and a plan a servable carries from elsewhere
        # (another server, a flipped config) rebuilds on key mismatch —
        # flipping fusion.mode must never silently serve the old tier.
        # Resolving here also fail-fasts a bad mode at construction.
        self._fusion = (
            resolve_fusion_tier(self.config.fusion_mode)
            if self.config.fastpath
            else None
        )
        # Precision tier, resolved once like the fusion tier (fail-fast on a
        # typo at construction). On a low-precision tier every version keeps
        # TWO warm plans: the configured tier's and the f32 twin of the SAME
        # version — the landing zone of the drift-triggered fallback
        # (docs/precision.md). The fallback flag flips which one _plan_for
        # returns; flipping it is selection between already-warm plans, never
        # a compile.
        self._precision = (
            resolve_precision_tier(self.config.precision_mode)
            if self.config.fastpath
            else None
        )
        self._precision_fallback = False
        # SLO-adaptive controller (serving.controller, default on): priority
        # shedding before the hard queue bound, deadline-aware bucket caps,
        # pipeline-depth stepping from its live goodput ledger. With default
        # knobs it only ever acts under sustained overload, so steady-state
        # serving is unchanged.
        self.controller = (
            AdaptiveController(
                self.scope,
                self.config.queue_capacity_rows,
                self.config.max_batch_size,
                base_depth=self.config.pipeline_depth,
                mesh=self.config.mesh,
                shed_watermark=self.config.shed_watermark,
                shed_sustain_ms=self.config.shed_sustain_ms,
                shed_priority=self.config.shed_priority,
                window_ms=self.config.controller_window_ms,
                queue_fraction=self.config.controller_queue_fraction,
                depth_max=self.config.controller_depth_max,
                deadline_safety=self.config.deadline_safety,
            )
            if self.config.controller
            else None
        )
        self._batcher = MicroBatcher(
            self._execute,
            max_batch_size=self.config.max_batch_size,
            max_delay_ms=self.config.max_delay_ms,
            queue_capacity_rows=self.config.queue_capacity_rows,
            scope=self.scope,
            response_factory=ServingResponse,
            dispatch=self._dispatch if self.config.fastpath else None,
            pipeline_depth=self.config.pipeline_depth,
            buckets=(
                self._sharding.serving_buckets(self.config.max_batch_size)
                if self._sharding is not None
                else None
            ),
            shards=self._sharding.n_data if self._sharding is not None else 1,
            controller=self.controller,
        )
        # Live per-replica endpoint (/metrics, /healthz, /events) — off
        # unless observability.http.port / ServingConfig(http_port=) is set.
        self.telemetry = (
            telemetry.TelemetryServer(self.config.http_port, health=self.health)
            if self.config.http_port is not None
            else None
        )
        if servable is not None:
            self.swap(version, servable)

    # -- the one place a batch meets a model ----------------------------------
    def _plan_stale(self, plan, sparse_hints, tier) -> bool:
        """Whether a cached plan was compiled under a different placement,
        fusion tier, sparseness, or precision tier than this server's — a
        plan carried from elsewhere (another server, a flipped config) has
        the wrong committed buffers / program partition / numerics contract
        and must rebuild (the same bug class the batch fingerprint covers
        for batch.mesh / fusion.mode / precision.mode, docs/fusion.md,
        docs/precision.md)."""
        return plan is not None and (
            getattr(plan.sharding, "key", None)
            != (self._sharding.key if self._sharding is not None else None)
            or getattr(plan.fusion, "key", None) != self._fusion.key
            or getattr(plan, "sparse_hints", None) != sparse_hints
            or getattr(getattr(plan, "precision", None), "key", None) != tier.key
        )

    def _plans_for(self, servable) -> Tuple[Optional[CompiledServingPlan], Optional[CompiledServingPlan]]:
        """``(plan, f32_twin)`` for the servable — the configured tier's plan
        plus, on a low-precision tier, the f32 plan of the SAME version that
        the drift fallback lands on (``None`` twin on the f32 tier). Both
        cached on the servable so the registry's ``(version, servable)``
        snapshot carries them. Normally built by ``warmup`` off the serving
        path; a server that never saw a warmup template builds lazily on the
        first batch instead — visible as ``ml.serving.fastpath.compiles``."""
        if not self.config.fastpath:
            return None, None
        # Sparse hints from the warmup template (docs/sparse.md): columns the
        # template shows sparse build sparse-convention segments; a template
        # whose sparseness differs from the cached plan's is a rebuild key,
        # like the mesh, the fusion tier, and the precision tier.
        with self._template_lock:
            template = self._warmup_template
        sparse_hints = resolve_sparse_hints(template)
        plan = getattr(servable, "_fastpath_plan", _PLAN_UNSET)
        if plan is _PLAN_UNSET or self._plan_stale(plan, sparse_hints, self._precision):
            plan = CompiledServingPlan.build(
                servable,
                scope=self.scope,
                sharding=self._sharding,
                fusion=self._fusion,
                sparse=sparse_hints,
                precision=self._precision,
            )
            try:
                servable._fastpath_plan = plan
            except AttributeError:  # __slots__ servable: serve without a plan
                return None, None
        if plan is None or not self._precision.lowp:
            return plan, None
        f32 = PrecisionTier(PRECISION_F32)
        twin = getattr(servable, "_fastpath_plan_f32", _PLAN_UNSET)
        if twin is _PLAN_UNSET or self._plan_stale(twin, sparse_hints, f32):
            twin = CompiledServingPlan.build(
                servable,
                scope=self.scope,
                sharding=self._sharding,
                fusion=self._fusion,
                sparse=sparse_hints,
                precision=f32,
            )
            # The twin's build gauged the scope's precision mode at 0; the
            # plan actually serving (fallback aside) is the configured tier.
            metrics.gauge(
                self.scope,
                MLMetrics.PRECISION_MODE,
                PRECISION_GAUGE_VALUE[self._precision.mode],
            )
            try:
                servable._fastpath_plan_f32 = twin
            except AttributeError:
                twin = None
        return plan, twin

    def _plan_for(self, servable) -> Optional[CompiledServingPlan]:
        """The plan a batch should execute NOW: the configured tier's, or —
        while a drift-triggered precision fallback is active — the warm f32
        twin of the same version. Selection between already-built plans; the
        flag flip is the whole fallback (docs/precision.md)."""
        plan, twin = self._plans_for(servable)
        with self._state_lock:
            fallback = self._precision_fallback
        if twin is not None and fallback:
            return twin
        return plan

    def _execute(self, padded_df: DataFrame) -> Tuple[DataFrame, int]:  # graftcheck: hot-root
        version, servable = self.registry.current()  # one snapshot per batch
        plan = self._plan_for(servable)
        if plan is not None:
            return plan.execute(padded_df), version
        return servable.transform(padded_df), version

    def _dispatch(self, padded_df: DataFrame):  # graftcheck: hot-root
        """Async seam for the batcher's pipelined window: returns a handle
        whose ``result()`` is the single blocking readback, or None to serve
        this batch synchronously (no plan — per-stage path)."""
        version, servable = self.registry.current()  # one snapshot per batch
        plan = self._plan_for(servable)
        if plan is None:
            return None
        return _DispatchHandle(plan.dispatch(padded_df), version)

    # -- client API ------------------------------------------------------------
    def predict(
        self,
        df: DataFrame,
        timeout_ms: Optional[float] = None,
        priority: int = 0,
        shape_key=None,
    ) -> ServingResponse:
        """Serve ``df`` (1..max_batch_size rows), blocking until the response.

        ``priority`` (0 = most important, the default) feeds the adaptive
        controller: under sustained overload, priorities >=
        ``serving.shed.priority`` are shed with backoff context before the
        queue hard-rejects anyone.

        Raises ``ServingOverloadedError`` (queue full or shed — immediately,
        with ``retry_after_ms``), ``ServingDeadlineError`` (deadline passed
        while queued or in the pre-dispatch window), ``ServingClosedError``
        (after close), or ``NoModelError`` via the batch when no version is
        loaded.
        """
        return self.submit(df, timeout_ms, priority=priority, shape_key=shape_key).result()

    def submit(
        self,
        df: DataFrame,
        timeout_ms: Optional[float] = None,
        priority: int = 0,
        shape_key=None,
    ):
        """Async variant of ``predict``: returns a handle with ``.result()``.

        ``shape_key`` is the optional batch-affinity hint (the retrieval
        client passes the request's top-K ladder rung): requests with
        different keys never coalesce into one batch. Grouping only — a mixed
        batch would still be correct."""
        with self._state_lock:
            closed = self._closed
        if closed:
            raise ServingClosedError("server is closed")
        self._remember_template(df)
        timeout_s = (
            timeout_ms if timeout_ms is not None else self.config.default_timeout_ms
        ) / 1000.0
        return self._batcher.submit(df, timeout_s, priority=priority, shape_key=shape_key)

    def _remember_template(self, df: DataFrame) -> None:
        """First request doubles as the warmup template for later swaps when
        the caller didn't provide one at construction. Check-and-set in ONE
        lock region (no double-checked unlocked read): the poller thread
        reads the template mid-warmup, so every access shares the lock — an
        uncontended acquire per submit is noise next to the queue lock."""
        with self._template_lock:
            if self._warmup_template is None:
                self._warmup_template = df.take([0])

    # -- model lifecycle -------------------------------------------------------
    def warmup(self, servable) -> None:
        """Compile every serving shape on ``servable``: one dummy batch per
        bucket, built from the warmup template. Runs on the CALLER's thread
        (poller or swapper), never the serving path — the in-service model
        keeps answering while the incoming one warms.

        On the fast path this is also where the incoming version's
        ``CompiledServingPlan`` is built (one ``device_put`` per model array)
        and every (version, bucket) executable is AOT-compiled — all before
        the atomic version flip, so the hot path never traces, compiles, or
        uploads weights."""
        with tracer.span("serving.warmup", CAT_COMPILE, scope=self.scope):
            # device-puts model arrays off-path; on a low-precision tier this
            # also builds the f32 twin the drift fallback lands on.
            plan, twin = self._plans_for(servable)
            with self._template_lock:
                template = self._warmup_template
            if template is None:
                telemetry.emit("serving.warmup", self.scope, {"buckets": 0})
                return  # nothing seen yet: the first real batch compiles lazily
            if plan is not None:
                plan.warmup(template, self._batcher.buckets)
                if twin is not None:
                    # The fallback contract: flipping to f32 mid-burst is a
                    # selection between warm plans with ZERO compiles — so
                    # the twin AOT-warms on every bucket too, before the flip.
                    twin.warmup(template, self._batcher.buckets)
            else:
                for bucket in self._batcher.buckets:
                    servable.transform(pad_to(template, bucket))
            payload = {
                "buckets": len(self._batcher.buckets),
                "fastpath": plan is not None,
            }
            if twin is not None:
                payload["precision"] = self._precision.mode
                payload["f32_twin_warm"] = True
            if plan is not None and plan.last_warmup_cache is not None:
                # The incarnation's cold-start story in one record: how much
                # of this flip's warm came off the plan cache vs live XLA
                # (docs/plancache.md — the zero-compile-resume contract).
                payload["plancache"] = plan.last_warmup_cache
            telemetry.emit("serving.warmup", self.scope, payload)

    def swap(self, version: int, servable) -> None:
        """Warm then atomically install ``servable`` as ``version``. The
        version must advance (monotonic — a response's ``model_version`` is
        unambiguous forever)."""
        with tracer.span("serving.swap", CAT_SWAP, scope=self.scope) as sp:
            sp.set_attr("version", version)
            previous = self.registry.version
            self.warmup(servable)
            self.registry.swap(version, servable)
            telemetry.emit(
                "serving.swap", self.scope, {"version": version, "from": previous}
            )

    def rollback(self, version: int, servable) -> None:
        """Warm then atomically REVERT serving to an older ``version`` — the
        drift-rollback path (loop/rollback.py). Same discipline as ``swap``:
        the restored version's plan is rebuilt and AOT-warmed on the caller's
        thread before the flip, so the rollback itself never puts a compile on
        the serving path."""
        with tracer.span("serving.rollback", CAT_SWAP, scope=self.scope) as sp:
            sp.set_attr("version", version)
            previous = self.registry.version
            self.warmup(servable)
            self.registry.swap(version, servable, allow_rollback=True)
            telemetry.emit(
                "serving.rollback", self.scope, {"version": version, "from": previous}
            )

    def precision_fallback(self, reason: str = "drift") -> bool:
        """Switch serving to the warm f32 twin of the CURRENT version — a
        fallback, not a rollback: the model version does not change, only the
        precision tier of the plan answering requests. Idempotent; returns
        whether a fallback is (now) active. No-op (False) on an f32 tier.

        The flip is a boolean the hot path's plan selection reads — every
        in-flight batch finishes on whichever plan it dispatched with and
        every later batch selects the f32 twin, so no request is ever dropped
        or resolved twice. Zero compiles by construction: the twin was built
        and AOT-warmed at swap time (``warmup``). One journaled decision per
        activation (``precision.fallback`` in the flight recorder)."""
        if self._precision is None or not self._precision.lowp:
            return False
        with self._state_lock:
            if self._precision_fallback:
                return True
            self._precision_fallback = True
        metrics.counter(self.scope, MLMetrics.PRECISION_FALLBACKS)
        metrics.gauge(self.scope, MLMetrics.PRECISION_FALLBACK_ACTIVE, 1)
        telemetry.emit(
            "precision.fallback",
            self.scope,
            {
                "from": self._precision.mode,
                "to": PRECISION_F32,
                "reason": reason,
                "version": self.registry.version,
            },
        )
        return True

    def precision_restore(self) -> None:
        """Clear an active precision fallback (operator action after the
        regression is understood): the next batch selects the configured
        low-precision plan again — still warm, still zero compiles."""
        with self._state_lock:
            if not self._precision_fallback:
                return
            self._precision_fallback = False
        metrics.gauge(self.scope, MLMetrics.PRECISION_FALLBACK_ACTIVE, 0)
        telemetry.emit(
            "precision.restore",
            self.scope,
            {"to": self._precision.mode, "version": self.registry.version},
        )

    @property
    def precision_fallback_active(self) -> bool:
        with self._state_lock:
            return self._precision_fallback

    def attach_poller(
        self,
        directory: str,
        *,
        loader=None,
        interval_ms: Optional[float] = None,
        start: bool = True,
    ) -> ModelVersionPoller:
        """Watch ``directory`` for published versions (see
        ``registry.publish_servable``) and hot-swap them in as they appear."""
        poller = ModelVersionPoller(
            directory,
            self.registry,
            loader=loader,
            warmup=self.warmup,
            interval_ms=interval_ms if interval_ms is not None else self.config.poll_interval_ms,
        )
        with self._state_lock:
            if self._poller is not None:
                raise RuntimeError("a poller is already attached")
            self._poller = poller
        if start:
            poller.start()
        return poller

    @property
    def model_version(self) -> Optional[int]:
        return self.registry.version

    def health(self) -> Tuple[bool, dict]:  # graftcheck: cold
        """The /healthz snapshot: ``(ok, payload)``. ``ok`` is False —
        rendered as HTTP 503 by the telemetry endpoint — while the server is
        draining or closed (the load-balancer takes the replica out before
        in-flight work finishes). A live server with no model yet reports
        ``status="no-model"`` but stays 200: it is healthy, just unwarmed."""
        draining = self._batcher.draining
        with self._state_lock:
            closed_flag = self._closed
            poller = self._poller
            precision_fallback = self._precision_fallback
        closed = closed_flag or self._batcher.closed
        version = self.registry.version
        payload = {
            "status": (
                "closed" if closed
                else "draining" if draining
                else "no-model" if version is None
                else "serving"
            ),
            "name": self.name,
            "version": version,
            "queue_depth_rows": metrics.get(self.scope, MLMetrics.SERVING_QUEUE_DEPTH, 0),
            "queue_capacity_rows": self.config.queue_capacity_rows,
            "pipeline_depth": self._batcher.pipeline_depth,
            "goodput_fraction": (
                self.controller.ledger.share(CAT_PRODUCTIVE)
                if self.controller is not None
                else None
            ),
            "controller": (
                self.controller.state() if self.controller is not None else None
            ),
            # A poller stuck backing off on an unreadable publish dir is a
            # replica that silently stops taking model updates — /healthz is
            # where an operator (or the fleet supervisor) sees it.
            "poller": poller.backoff_state() if poller is not None else None,
            # A low-precision replica serving its f32 fallback is quality-
            # safe but not at configured speed — surfaced here so the fleet
            # view shows it without grepping journals.
            "precision": (
                {"mode": self._precision.mode, "fallback": precision_fallback}
                if self._precision is not None and self._precision.lowp
                else None
            ),
        }
        return (not closed and not draining), payload

    @property
    def executed_batch_sizes(self) -> List[Tuple[int, int]]:
        """(rows, bucket) per executed batch — the compile-counting hook the
        recompile tests assert on."""
        return list(self._batcher.executed_batch_sizes)

    # -- shutdown --------------------------------------------------------------
    def close(self, drain: bool = True) -> None:
        """Stop the poller and the batcher. ``drain=True`` (default) serves
        everything already queued before returning — graceful; ``drain=False``
        fails queued requests with ``ServingClosedError``."""
        with self._state_lock:
            if self._closed:
                return
            self._closed = True
            poller = self._poller
        if poller is not None:
            poller.stop()  # joins the poll thread — must run outside the lock
        self._batcher.close(drain=drain)
        # The endpoint outlives the batcher drain so /healthz answers 503
        # through the whole shutdown window, then stops last.
        if self.telemetry is not None:
            self.telemetry.close()

    def __enter__(self) -> "InferenceServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close(drain=True)
