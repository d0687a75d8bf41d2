"""CompiledServingPlan — the serving fast path: fused per-bucket executables,
device-resident model state, AOT warmup, deferred readback.

The per-stage serving path pays three per-request costs the servable tier's
generality forces but a hot path never should:

1. every stage re-uploads its model arrays (``jnp.asarray(self.centroids)``
   inside ``transform``) — host→device traffic for bytes that never change;
2. every stage materializes a full host DataFrame between stages — a
   device→host→device round trip per pipeline edge;
3. every call goes through Python jit dispatch (trace-cache lookup, pytree
   flatten) instead of a pre-compiled executable.

The plan removes all three for stages that expose a
:class:`~flink_ml_tpu.servable.kernel_spec.KernelSpec`. The chain compiler —
fusion into per-stage AOT programs with device-resident model buffers and
device-to-device stage handoff — is the shared planner
(``servable/planner.py``, also behind the batch tier's
``builder/batch_plan.py``); this module adds the *serving* policy:

- **Per-bucket programs** (the operator-fusion win of "On Optimizing Operator
  Fusion Plans for Large-Scale Machine Learning in SystemML", PAPERS.md):
  chains are keyed by the micro-batcher's padded bucket sizes, so the
  executable set is fixed and small.
- **AOT warmup** (the warmup discipline of "Fine-Tuning and Serving Gemma on
  Cloud TPU", PAPERS.md): ``warmup`` lowers and compiles every
  (segment, bucket) executable before the version flip, so the hot path never
  traces or compiles. A bucket the warmup did not cover compiles lazily and
  bumps ``ml.serving.fastpath.compiles`` — the alarm that warmup coverage is
  wrong.
- **Fallback**: stages without a spec run their ordinary ``transform`` on a
  materialized DataFrame, so mixed pipelines serve bit-exactly; a batch whose
  input columns do not match the compiled signature (sparse features, changed
  width) falls back to per-stage ``transform`` for that segment and bumps
  ``ml.serving.fastpath.fallback.batches``.

``dispatch`` returns a :class:`PlanExecution` whose trailing fused outputs are
still device arrays — JAX async dispatch means the device is already working
while the caller's host thread goes back to claim/pad/scatter the next batch;
``finalize`` performs the single blocking readback. The micro-batcher's
pipelined window (``serving/server.py``) is built on exactly this split.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from flink_ml_tpu.api.dataframe import DataFrame
from flink_ml_tpu.metrics import MLMetrics, metrics
from flink_ml_tpu.servable.builder import PipelineModelServable
from flink_ml_tpu.servable.fusion import (
    fallback_recorder,
    plan_recorder,
    resolve_fusion_tier,
)
from flink_ml_tpu.servable.plancache import resolve_plan_cache
from flink_ml_tpu.servable.precision import (
    PRECISION_GAUGE_VALUE,
    resolve_precision_tier,
)
from flink_ml_tpu.servable.planner import (
    FallbackStage,
    FusedSegment,
    IneligibleBatch,
    PlanExecution,
    build_segments,
    run_segment,
)
from flink_ml_tpu.servable.shapes import resolve_k_cap_max, resolve_warm_ks
from flink_ml_tpu.servable.sparse import resolve_nnz_cap_max, resolve_warm_caps
from flink_ml_tpu.serving.batcher import pad_to
from flink_ml_tpu.trace import CAT_COMPILE, CAT_SWAP, tracer

__all__ = ["CompiledServingPlan", "PlanExecution"]

# Back-compat aliases — the private names tests and tooling grew up with.
_IneligibleBatch = IneligibleBatch
_FusedSegment = FusedSegment
_FallbackStage = FallbackStage


class CompiledServingPlan:
    """Compiled form of one servable (or ``PipelineModelServable``) for a
    fixed bucket set. Build via :meth:`build`; ``None`` means no stage has a
    kernel spec and the classic per-stage path should serve."""

    def __init__(
        self,
        stages: Sequence[Any],
        segments: List[Any],
        scope: str,
        sharding: Optional[Any] = None,
        fusion: Optional[Any] = None,
        sparse: Optional[Dict[str, int]] = None,
        precision: Optional[Any] = None,
    ):
        self._stages = list(stages)
        self.segments = segments
        self.scope = scope
        self.sharding = sharding
        self.fusion = fusion if fusion is not None else resolve_fusion_tier()
        #: The precision tier the segments were built under — part of the
        #: server's rebuild key exactly like the mesh and the fusion tier
        #: (docs/precision.md): a config flip rebuilds, never silently
        #: re-rounds.
        self.precision = precision if precision is not None else resolve_precision_tier()
        #: The sparse hints the segments were built under (None = convention
        #: off) — part of the server's rebuild key, like the mesh and the
        #: fusion tier: a template whose sparseness differs must rebuild.
        self.sparse_hints = sparse
        # Persistent compiled-plan cache (docs/plancache.md): None unless
        # plancache.dir is configured. Resolved at build time like the mesh
        # and the fusion tier — warmup/swap/rollback then load serialized
        # executables instead of compiling, and a restarted incarnation
        # reaches first response in O(load) not O(XLA).
        self.plancache = resolve_plan_cache()
        #: Cache outcome of the last ``warmup`` (hits/misses/load ms) — the
        #: server's swap telemetry reports it per version flip.
        self.last_warmup_cache: Optional[Dict[str, Any]] = None
        self._on_plan = plan_recorder(scope)
        self._on_mega_fallback = fallback_recorder(scope)
        n_fused = sum(len(s.specs) for s in segments if isinstance(s, FusedSegment))
        n_fallback = sum(1 for s in segments if isinstance(s, FallbackStage))
        metrics.gauge(scope, MLMetrics.SERVING_FUSED_STAGES, n_fused)
        metrics.gauge(scope, MLMetrics.SERVING_FALLBACK_STAGES, n_fallback)
        metrics.gauge(scope, MLMetrics.FUSION_MODE, 1 if self.fusion.fast else 0)
        metrics.gauge(
            scope,
            MLMetrics.PRECISION_MODE,
            PRECISION_GAUGE_VALUE[self.precision.mode],
        )
        if sharding is not None:
            metrics.gauge(scope, MLMetrics.SERVING_SHARD_COUNT, sharding.n_data)
            metrics.gauge(scope, MLMetrics.SERVING_SHARD_MODEL_AXIS, sharding.n_model)

    # -- construction ---------------------------------------------------------
    @staticmethod
    def build(  # graftcheck: cold
        servable,
        *,
        scope: str = "ml.serving[plan]",
        sharding: Optional[Any] = None,
        fusion: Optional[Any] = None,
        sparse: Optional[Dict[str, int]] = None,
        precision: Optional[Any] = None,
    ) -> Optional["CompiledServingPlan"]:
        """Group the servable's consecutive kernel-spec stages into fused
        segments. Raises whatever ``kernel_spec()`` raises (an unloaded model
        must fail closed at warmup, before it could ever serve). With a
        ``sharding`` (``serving.mesh`` > 1), segments commit weights per
        shard and compile SPMD per-bucket executables — hot swap and rollback
        pay the per-device placement here, at warmup, never on the serving
        path. ``fusion`` is the resolved
        :class:`~flink_ml_tpu.servable.fusion.FusionTier`; default: the
        ``fusion.mode`` config (docs/fusion.md). The plan snapshots the tier
        — a config flip after build is a REBUILD key, never a silent
        repartition (``serving/server.py`` compares ``fusion.key``).

        Build-time work (one device_put per model array, jit wrapper
        construction per program): normally runs at warmup/swap time, off the
        serving path. The ``graftcheck: cold`` mark documents the one lazy
        exception — a server that never saw a warmup template builds on the
        first batch, visible as ``ml.serving.fastpath.compiles``."""
        stages = (
            list(servable.servables)
            if isinstance(servable, PipelineModelServable)
            else [servable]
        )
        if fusion is None:
            fusion = resolve_fusion_tier()
        if precision is None:
            precision = resolve_precision_tier()
        segments = build_segments(stages, sharding, fusion, sparse, precision)
        if not any(isinstance(s, FusedSegment) for s in segments):
            return None
        return CompiledServingPlan(
            stages, segments, scope, sharding, fusion, sparse, precision
        )

    # -- warmup / AOT ---------------------------------------------------------
    def warmup(self, template: DataFrame, buckets: Sequence[int]) -> None:
        """AOT-compile every (segment, bucket) executable and run every
        fallback stage once per bucket (warming its own jit caches) — all on
        the caller's thread, before the atomic version flip. With a plan
        cache, chain programs load their serialized executables instead of
        compiling; the warm wall splits between
        ``ml.serving.fastpath.warmup.compile.ms`` (true compile + trace time)
        and ``ml.serving.fastpath.warmup.cache.load.ms`` (cache loads), and a
        bucket warmed entirely from cache reclassifies its span from the
        ``compile`` goodput category to ``swap`` — goodput reports must not
        count cache loads as compile seconds (docs/plancache.md)."""
        t0 = time.perf_counter()
        totals = {"hits": 0, "misses": 0, "load_ms": 0.0}
        # Sparse segments key executables by (bucket, nnz cap): warm the
        # configured cap ladder per bucket so zero-post-warmup-compiles
        # holds for every on-ladder batch, not just the template's cap.
        warm_caps: Tuple[Optional[int], ...] = (None,)
        if any(
            isinstance(s, FusedSegment) and s.has_sparse_inputs for s in self.segments
        ):
            warm_caps = resolve_warm_caps()
        # Retrieval segments key executables by (bucket[, cap], K rung): warm
        # the configured K ladder too, so zero-post-warmup-compiles holds for
        # every on-ladder per-request K (docs/retrieval.md).
        warm_ks: Tuple[Optional[int], ...] = (None,)
        if any(
            isinstance(s, FusedSegment) and s.has_shape_inputs for s in self.segments
        ):
            warm_ks = resolve_warm_ks()
        for bucket in buckets:
            for cap in warm_caps:
                for krung in warm_ks:
                    with tracer.span("serving.plan.warmup", CAT_COMPILE, scope=self.scope) as sp:
                        sp.set_attr("bucket", bucket)
                        sp.set_attr("fusion", self.fusion.mode)
                        if self.precision.lowp:
                            sp.set_attr("precision", self.precision.mode)
                        if cap is not None:
                            sp.set_attr("nnz_cap", cap)
                        if krung is not None:
                            sp.set_attr("k_rung", krung)
                        if self.sharding is not None:
                            sp.set_attr("shards", self.sharding.n_data)
                        bucket_cache = {"hits": 0, "misses": 0}

                        def on_cache(outcome: str, ms: float, _b=bucket_cache) -> None:
                            _b["hits" if outcome == "hit" else "misses"] += 1
                            totals["hits" if outcome == "hit" else "misses"] += 1
                            if outcome == "hit":
                                totals["load_ms"] += ms

                        df = pad_to(template, bucket)
                        for segment in self.segments:
                            if isinstance(segment, FallbackStage):
                                df = segment.stage.transform(df)
                                continue
                            try:
                                inputs, key, _cap, _nnz = self._ingest(
                                    segment,
                                    df,
                                    bucket,
                                    cap=cap if segment.has_sparse_inputs else None,
                                    warm=True,
                                    k_rung=krung if segment.has_shape_inputs else None,
                                )
                            except IneligibleBatch:
                                # e.g. a sparse features template where the
                                # spec expects dense: this segment will serve
                                # through the per-stage path (as dispatch
                                # falls back), so warm the stages' own jit
                                # kernels instead of compiling a fused chain
                                # the traffic can never hit.
                                for stage in segment.stages:
                                    df = stage.transform(df)
                                continue
                            outputs = run_segment(
                                segment,
                                key,
                                inputs,
                                on_plan=self._on_plan,
                                cache=self.plancache,
                                on_cache=on_cache if self.plancache is not None else None,
                                on_mega_fallback=self._on_mega_fallback,
                            )
                            # The cost model's per-bucket choice (may be
                            # "fast+mega") — goodput attribution splits
                            # compile time by tier.
                            sp.set_attr("fusion", segment.plan_label(key))
                            df = self._materialize(df, segment.pending(outputs))
                        if self.plancache is not None:
                            sp.set_attr(
                                "plancache",
                                f"{bucket_cache['hits']}h/{bucket_cache['misses']}m",
                            )
                            if (
                                bucket_cache["hits"]
                                and not bucket_cache["misses"]
                                and hasattr(sp, "category")  # tracing-off: _NoopSpan
                            ):
                                # Every chain program of this bucket loaded
                                # from disk: the span's time is version-
                                # lifecycle work, not XLA compilation — keep
                                # the compile goodput category honest for the
                                # zero-compile-resume story.
                                sp.category = CAT_SWAP
        wall_ms = (time.perf_counter() - t0) * 1000.0
        cache_ms = totals["load_ms"]
        metrics.gauge(
            self.scope,
            MLMetrics.SERVING_WARMUP_COMPILE_MS,
            max(0.0, wall_ms - cache_ms),
        )
        if self.plancache is not None:
            metrics.gauge(
                self.scope, MLMetrics.SERVING_WARMUP_CACHE_LOAD_MS, cache_ms
            )
            self.last_warmup_cache = {
                "hits": totals["hits"],
                "misses": totals["misses"],
                "load_ms": round(cache_ms, 3),
            }

    def _run_segment(self, segment: FusedSegment, key: Any, inputs: Dict[str, Any]):
        """Hot-path execution: compiling here means warmup coverage was wrong
        — the ``ml.serving.fastpath.compiles`` alarm counts it. The plan
        cache rides along so even that uncovered bucket builds from a
        serialized executable when a previous incarnation compiled it."""
        return run_segment(
            segment,
            key,
            inputs,
            on_compile=lambda: metrics.counter(
                self.scope, MLMetrics.SERVING_FASTPATH_COMPILES
            ),
            on_plan=self._on_plan,
            cache=self.plancache,
            on_mega_fallback=self._on_mega_fallback,
        )

    # -- the hot path ---------------------------------------------------------
    def _ingest(
        self,
        segment: FusedSegment,
        df: DataFrame,
        bucket: int,
        cap: Optional[int] = None,
        warm: bool = False,
        k_rung: Optional[int] = None,
    ) -> Tuple[Dict[str, np.ndarray], Any, int, int]:
        """One host-side gather of the segment's input columns, exactly the
        way each stage's ``transform`` would read them (dense f32; sparse
        columns as the convention triple on the nnz-cap ladder; shape columns
        as the top-K rung carrier), checked against the compiled signature.
        Returns ``(inputs, key, nnz_cap, true_nnz)`` — the key is the padded
        bucket, extended with the shared nnz cap when the segment has sparse
        inputs and with the K ladder rung when it has shape inputs, so the
        executable set is ≤ 1 per (bucket, cap, rung). ``cap`` / ``k_rung``
        force the rungs (warmup walks the configured ladders; ``warm`` packs
        shape-only, truncating rows a small rung cannot hold)."""
        if self.sharding is not None and bucket % self.sharding.row_multiple:
            # A bucket off the mesh ladder cannot shard bit-exactly (local
            # shapes would gain remainder rows) — only reachable when a
            # caller bypasses the mesh bucket ladder; fall back per-stage
            # rather than serve different bits.
            raise IneligibleBatch(
                f"bucket {bucket} not a multiple of the sharded bucket "
                f"quantum {self.sharding.row_multiple}",
                reason="off_ladder",
            )
        inputs: Dict[str, np.ndarray] = {}
        sparse_packed: Dict[str, Dict[str, np.ndarray]] = {}
        shape_cols: List[str] = []
        shared_cap = cap if cap is not None else 0  # forced rung is an int
        true_nnz = 0
        cap_max = resolve_nnz_cap_max()
        for name in segment.external_inputs:
            kind = segment.input_kind(name)
            if kind in ("sparse", "entries"):
                arrays, col_cap, col_nnz = segment.gather_sparse(
                    df, name, cap=cap, cap_max=cap_max, truncate=warm
                )
                sparse_packed[name] = arrays
                shared_cap = max(shared_cap, col_cap)
                true_nnz += col_nnz
            elif kind == "shape":
                shape_cols.append(name)
            else:
                inputs[name] = segment.gather(df, name)
        shape_rung = None
        if shape_cols:
            # Per-request output width (the retrieval top-K convention): one
            # rung for the whole batch — the max requested K across the shape
            # columns, on the power-of-two K ladder (servable/shapes.py).
            arrays, shape_rung = segment.gather_shape(
                df,
                shape_cols,
                rung=k_rung,
                cap_max=resolve_k_cap_max() if k_rung is None else None,
            )
            inputs.update(arrays)
        for arrays in sparse_packed.values():
            for pname, arr in arrays.items():
                if arr.ndim == 2 and arr.shape[1] < shared_cap:
                    # All sparse columns of one batch share the widest rung
                    # (one key per batch, the warmed set stays one-per-rung);
                    # the extra slots are id-0/value-0 padding — exact
                    # identity terms under segment_sum.
                    arr = np.pad(arr, ((0, 0), (0, shared_cap - arr.shape[1])))
                inputs[pname] = arr
        key: Any = (bucket, shared_cap) if sparse_packed else bucket
        if shape_rung is not None:
            # The K rung joins the key (like the nnz cap): one executable per
            # (bucket[, cap], rung), with the rung tagged so a rung can never
            # collide with a sparse cap in the key space.
            key = (key, f"k{shape_rung}")
        signature = segment.signatures.get(key)
        if signature is not None:
            for name, arr in inputs.items():
                if (tuple(arr.shape), arr.dtype) != signature[name]:
                    raise IneligibleBatch(
                        f"column {name!r} shape {arr.shape} != compiled {signature[name]}",
                        reason="signature",
                    )
        return inputs, key, shared_cap, true_nnz

    @staticmethod
    def _materialize(df: DataFrame, pending: List[Tuple[str, Any, Any, Any]]) -> DataFrame:
        return PlanExecution(df, pending).finalize()

    def dispatch(self, padded_df: DataFrame) -> PlanExecution:  # graftcheck: hot-root
        """Run the plan on an already-padded batch. Fused segments execute
        their pre-compiled per-bucket program against the committed device
        buffers; the TRAILING fused outputs stay on device (JAX async
        dispatch) until ``finalize``. Any non-final segment boundary
        materializes, which also forces the readback there — the window the
        pipelined batcher exploits is the trailing one."""
        bucket = len(padded_df)
        df = padded_df
        pending: List[Tuple[str, Any, Any, Any]] = []
        fused_ran = False
        for segment in self.segments:
            if isinstance(segment, FallbackStage):
                metrics.counter(
                    self.scope, MLMetrics.fallback_reason("serving", "specless")
                )
                df = self._materialize(df, pending)
                pending = []
                df = segment.stage.transform(df)
                continue
            # Consecutive fused stages share a segment, so entering a fused
            # segment always finds pending drained by a fallback stage.
            try:
                inputs, key, nnz_cap, true_nnz = self._ingest(segment, df, bucket)
            except IneligibleBatch as e:
                metrics.counter(self.scope, MLMetrics.SERVING_FALLBACK_BATCHES)
                metrics.counter(
                    self.scope, MLMetrics.fallback_reason("serving", e.reason)
                )
                df = self._materialize(df, pending)
                pending = []
                for stage in segment.stages:
                    df = stage.transform(df)
                continue
            if nnz_cap:
                # ELL padding attribution: the enclosing dispatch/exec span
                # (the batcher's, carrying rows/bucket) learns the cap and
                # the true entries of the TRUE rows (pad rows repeat row 0 —
                # their entries are padding work, not carried work) —
                # graftscope's padding split then counts every padded cell
                # exactly once (docs/observability.md).
                sp = tracer.current()
                if sp is not None:
                    rows_attr = sp.attrs.get("rows") if sp.attrs else None
                    if isinstance(rows_attr, int) and 0 < rows_attr < bucket:
                        true_nnz = int(
                            sum(
                                int(arr[:rows_attr].sum())
                                for pname, arr in inputs.items()
                                if pname.endswith("!nnz")
                            )
                        )
                    sp.set_attr("nnz", true_nnz)
                    sp.set_attr("nnz_cap", nnz_cap)
            outputs = self._run_segment(segment, key, inputs)
            pending = segment.pending(outputs)
            fused_ran = True
        if fused_ran:
            metrics.counter(self.scope, MLMetrics.SERVING_FUSED_BATCHES)
            if self.sharding is not None:
                metrics.counter(
                    self.scope,
                    MLMetrics.SERVING_SHARD_ROWS,
                    bucket // self.sharding.n_data,
                )
        return PlanExecution(df, pending)

    def execute(self, padded_df: DataFrame) -> DataFrame:
        """Synchronous convenience: dispatch + finalize."""
        return self.dispatch(padded_df).finalize()
