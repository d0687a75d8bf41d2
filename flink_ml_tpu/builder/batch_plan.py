"""CompiledBatchPlan — the batch transform fast path.

``PipelineModel.transform`` classically executes one jit call per column per
stage with an immediate blocking ``np.asarray`` readback and a full host
DataFrame materialization between stages. For chains of elementwise/feature
operators that is pure overhead — the fusion-plan win SystemML's optimizer
documents (Boehm et al., PAPERS.md) and Flare applies to whole Spark
pipelines (Essertel et al., PAPERS.md). This plan extends PR 4's serving fast
path to offline data, on the shared chain compiler (``servable/planner.py``):

- **Fusion**: consecutive stages exposing a
  :class:`~flink_ml_tpu.servable.kernel_spec.KernelSpec` run as an executable
  chain — one AOT program per reduction-bearing stage, with runs of
  ``elementwise`` specs merged into single programs (bit-exact with the
  per-stage path by construction, see the planner docstring), columns
  flowing between programs as device arrays: one host→device ingest and one
  device→host readback per chunk, zero inter-stage DataFrame
  materialization.
- **Chunked, double-buffered ingest**: inputs larger than
  ``batch.chunk.rows`` stream through the chain in chunks with a prefetch
  window (``batch.prefetch.depth``): the host gather + ``device_put`` of
  chunk j+1 overlaps the device execution of chunk j — the streamed-SGD
  prefetch-gap design of ``ops/optimizer.py`` / ``iteration/streaming.py``,
  applied to inference. At most ``depth`` chunks are dispatched-unfinalized,
  so HBM residency stays bounded regardless of input size.
- **Chain-boundary fallback**: a stage without a spec (or whose params make
  it unfusable — e.g. a row-dropping Bucketizer) materializes the full
  DataFrame at the segment boundary and runs today's per-stage path; a
  column a compiled chain cannot take (sparse features, ragged lists) makes
  the *whole segment* fall back for that call, bit-exactly.

Programs are keyed by the ingest signature itself (chunk rows × column
shapes/dtypes) and compile lazily on first sight — a batch tier has no
version flip to warm up against; ``ml.batch.fastpath.compiles`` counts the
signatures seen.

**Mesh sharding** (``batch.mesh`` > 1, docs/batch_transform.md): chunks
ingest through the plan tier's blessed boundary
(``PlanSharding.put_batch`` — one ``device_put`` per chunk, split by the
runtime into one transfer per shard) and the fused programs run SPMD with
rows split over the data axis; columns still flow device-to-device between
stages, never through the host. A ragged final chunk rounds up to a mesh
multiple (pad rows repeat row 0 and are sliced off at readback, counted by
``ml.batch.shard.pad.rows``); a tail too small to keep every shard in the
row-count-invariant regime (see MIN_SHARD_ROWS in ``servable/sharding.py``)
runs **replicated** instead — the same local program shape mesh=1 compiles —
so per-row results stay bit-identical to the single-device path either way.
"""
from __future__ import annotations

import os
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Dict, Hashable, List, Optional, Sequence, Tuple

import jax
import numpy as np

from flink_ml_tpu.api.dataframe import DataFrame
from flink_ml_tpu.api.types import BasicType, DataTypes
from flink_ml_tpu.config import Options, config
from flink_ml_tpu.metrics import MLMetrics, metrics
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
    build_segments,
    run_segment,
)
from flink_ml_tpu.servable.sharding import resolve_plan_sharding
from flink_ml_tpu.servable.sparse import (
    ids_name,
    nnz_name,
    rebuild_sparse_column,
    resolve_nnz_cap_max,
    values_name,
)
from flink_ml_tpu.trace import CAT_PRODUCTIVE, CAT_READBACK, tracer

__all__ = ["BatchPlanInapplicable", "CompiledBatchPlan"]

_POOL_LOCK = threading.Lock()
_POOL: Optional[Any] = None


class _InlineExecutor:
    """Degenerate executor for single-core hosts: thread hops buy no overlap
    there, only scheduling overhead, so tasks run on the submitting thread."""

    def submit(self, fn, *args):
        future: Future = Future()
        try:
            future.set_result(fn(*args))
        except BaseException as e:  # noqa: BLE001 — mirror executor semantics
            future.set_exception(e)
        return future


def _readback_pool() -> Any:
    """Process-wide pool for chunk readbacks (lazy: plain transforms that
    never fuse must not spawn threads). Tasks are pure disjoint slice writes,
    so plans can share it freely; single-core hosts get the inline executor
    instead of threads."""
    global _POOL
    with _POOL_LOCK:
        if _POOL is None:
            workers = min(4, os.cpu_count() or 1)
            _POOL = (
                ThreadPoolExecutor(
                    max_workers=workers, thread_name_prefix="batch-readback"
                )
                if workers > 1
                else _InlineExecutor()
            )
        return _POOL


class BatchPlanInapplicable(Exception):
    """The plan met a pipeline shape it cannot chain (a fallback stage
    returned multiple DataFrames) — the caller should rerun the classic
    per-stage path."""


class CompiledBatchPlan:
    """Compiled form of a PipelineModel's stage chain for offline data.
    Build via :meth:`build`; ``None`` means no stage has a kernel spec and
    the classic per-stage path should run."""

    def __init__(
        self,
        stages: Sequence[Any],
        segments: List[Any],
        scope: str,
        sharding: Optional[Any] = None,
        fusion: Optional[Any] = None,
        precision: Optional[Any] = None,
    ):
        self._stages = list(stages)
        self.segments = segments
        self.scope = scope
        self.sharding = sharding
        self.fusion = fusion if fusion is not None else resolve_fusion_tier()
        #: The precision tier the segments carry their rounding under — part
        #: of the pipeline fingerprint's rebuild key (docs/precision.md).
        self.precision = precision if precision is not None else resolve_precision_tier()
        # Persistent compiled-plan cache (docs/plancache.md): chain programs
        # for chunk signatures a previous plan (or a previous process) ever
        # compiled load their serialized executables instead of compiling.
        self.plancache = resolve_plan_cache()
        self._on_plan = plan_recorder(scope)
        self._on_mega_fallback = fallback_recorder(scope)
        n_fused = sum(len(s.specs) for s in segments if isinstance(s, FusedSegment))
        n_fallback = sum(1 for s in segments if isinstance(s, FallbackStage))
        metrics.gauge(scope, MLMetrics.BATCH_FUSED_STAGES, n_fused)
        metrics.gauge(scope, MLMetrics.BATCH_FALLBACK_STAGES, n_fallback)
        metrics.gauge(scope, MLMetrics.FUSION_MODE, 1 if self.fusion.fast else 0)
        metrics.gauge(
            scope,
            MLMetrics.PRECISION_MODE,
            PRECISION_GAUGE_VALUE[self.precision.mode],
        )
        if sharding is not None:
            metrics.gauge(scope, MLMetrics.BATCH_SHARD_COUNT, sharding.n_data)

    # -- construction ---------------------------------------------------------
    @staticmethod
    def build(
        stages: Sequence[Any],
        *,
        scope: str = "ml.batch[plan]",
        sharding: Optional[Any] = None,
        fusion: Optional[Any] = None,
        sparse: Optional[Dict[str, int]] = None,
        precision: Optional[Any] = None,
    ) -> Optional["CompiledBatchPlan"]:
        """Group consecutive kernel-spec stages into fused segments and
        commit their model arrays to the device (the once-per-plan upload —
        per shard when a mesh is configured). Raises whatever
        ``kernel_spec()`` raises — an unloaded model fails closed here
        exactly as its ``transform`` would. Publishes
        ``ml.batch.fastpath.plan.build.ms``. ``sharding`` defaults to the
        ``batch.mesh`` / ``batch.mesh.model`` config options (1 = the
        single-device path); ``fusion`` to the ``fusion.mode`` config
        (docs/fusion.md) — the plan snapshots the tier, and
        ``builder/pipeline.py`` fingerprints the config so a flip rebuilds
        the cached plan instead of silently serving the old tier."""
        t0 = time.perf_counter()
        if sharding is None:
            sharding = resolve_plan_sharding(
                config.get(Options.BATCH_MESH), config.get(Options.BATCH_MESH_MODEL)
            )
        if fusion is None:
            fusion = resolve_fusion_tier()
        if precision is None:
            precision = resolve_precision_tier()
        segments = build_segments(stages, sharding, fusion, sparse, precision)
        if not any(isinstance(s, FusedSegment) for s in segments):
            return None
        plan = CompiledBatchPlan(stages, segments, scope, sharding, fusion, precision)
        metrics.gauge(
            scope, MLMetrics.BATCH_PLAN_BUILD_MS, (time.perf_counter() - t0) * 1000.0
        )
        return plan

    # -- execution ------------------------------------------------------------
    def transform(self, df: DataFrame) -> DataFrame:
        """Run the chain. Fused segments stream chunk-wise with the prefetch
        window; spec-less stages run their ordinary ``transform`` on the full
        materialized DataFrame at the chain boundary."""
        with tracer.span("batch.transform", CAT_PRODUCTIVE, scope=self.scope) as span:
            span.set_attr("input_rows", len(df))
            for segment in self.segments:
                if isinstance(segment, FallbackStage):
                    metrics.counter(
                        self.scope, MLMetrics.fallback_reason("batch", "specless")
                    )
                    out = segment.stage.transform(df)
                    if isinstance(out, (list, tuple)):
                        if len(out) != 1:
                            raise BatchPlanInapplicable(
                                f"stage {type(segment.stage).__name__} returned "
                                f"{len(out)} outputs"
                            )
                        out = out[0]
                    df = out
                    continue
                df = self._run_fused(segment, df)
            return df

    def _run_fused(self, segment: FusedSegment, df: DataFrame) -> DataFrame:  # graftcheck: hot-root
        n = len(df)
        if n == 0:
            return self._fallback(segment, df, count=False)
        try:
            # One host-side gather per external input for the WHOLE call, at
            # the column's own float dtype: chunk ingest below device_puts a
            # contiguous row view, and the f64→f32 canonicalization happens
            # inside that single C++ convert+copy pass (bit-identical to a
            # host astype — both are IEEE round-to-nearest — and one full
            # memory pass cheaper). Non-float columns cast to f32 once, the
            # same float math the per-stage kernels apply. Sparse-convention
            # inputs pack ONCE for the whole call at their ladder cap
            # (docs/sparse.md) — the triple's [n, K]/[n] arrays then slice
            # per chunk exactly like dense columns.
            full: Dict[str, np.ndarray] = {}
            nnz_cap = 0
            cap_max = resolve_nnz_cap_max()
            for name in segment.external_inputs:
                kind = segment.input_kind(name)
                if kind == "shape":
                    # Per-request output-shape columns (retrieval top-K) need
                    # the serving ingest's K ladder; the offline builder has
                    # none — the per-stage path owns these stages.
                    raise IneligibleBatch(
                        f"column {name!r} rides the shape kind", reason="shape_kind"
                    )
                if kind in ("sparse", "entries"):
                    arrays, col_cap, _col_nnz = segment.gather_sparse(
                        df, name, cap_max=cap_max
                    )
                    full.update(arrays)
                    nnz_cap = max(nnz_cap, col_cap)
                    continue
                arr = segment.gather(df, name, raw=True)
                if arr.dtype not in (np.float32, np.float64):
                    arr = np.asarray(arr, np.float32)
                elif not arr.flags.c_contiguous:
                    arr = np.ascontiguousarray(arr)
                full[name] = arr
            nnz_names = [n for n in full if n.endswith("!nnz")]
        except IneligibleBatch as e:
            metrics.counter(self.scope, MLMetrics.fallback_reason("batch", e.reason))
            return self._fallback(segment, df, count=True)

        chunk_rows = max(1, int(config.get(Options.BATCH_CHUNK_ROWS)))
        depth = max(1, int(config.get(Options.BATCH_PREFETCH_DEPTH)))
        starts = list(range(0, n, chunk_rows))
        chunk_hist = metrics.histogram(self.scope, MLMetrics.BATCH_CHUNK_MS)

        sharding = self.sharding

        def pad_rows_block(view: np.ndarray, padded: int) -> np.ndarray:
            # DP round-up: repeat row 0 (row-independent programs — pad rows
            # influence nothing and are sliced off at readback).
            pad = padded - view.shape[0]
            return np.concatenate(
                [view, np.broadcast_to(view[:1], (pad,) + view.shape[1:])]
            )

        def ingest(lo: int) -> Tuple[Hashable, Dict[str, Any], int, bool]:  # graftcheck: ingest
            hi = min(lo + chunk_rows, n)
            rows = hi - lo
            # device_put of a contiguous row view — host gather + upload of
            # chunk j+1 runs on the host thread while the device executes
            # the chunks still in flight (the double-buffer overlap), and
            # the programs then take committed device arrays, the fast
            # intake path (a numpy arg costs an extra conversion pass per
            # program call). On a mesh, PlanSharding.put_batch is the
            # blessed ingest boundary: one device_put per chunk, one
            # transfer per shard; a tail below the shardable floor goes
            # replicated so its local program shape matches mesh=1 exactly.
            replicated = sharding is not None and not sharding.shardable_rows(rows)
            padded = rows if sharding is None or replicated else sharding.padded_rows(rows)
            with tracer.span("batch.ingest", CAT_PRODUCTIVE, scope=self.scope) as sp:
                sp.set_attr("rows", rows)
                sp.set_attr("bucket", padded)
                if sharding is not None:
                    sp.set_attr("shards", 1 if replicated else sharding.n_data)
                inputs = {}
                for name, arr in full.items():
                    view = arr[lo:hi]
                    if sharding is None:
                        inputs[name] = jax.device_put(view)
                    elif replicated:
                        inputs[name] = sharding.put_replicated(view)
                    else:
                        if padded != rows:
                            view = pad_rows_block(view, padded)
                        inputs[name] = sharding.put_batch(view)
            key = tuple(
                (name, tuple(inputs[name].shape), str(inputs[name].dtype))
                for name in sorted(inputs)  # program-level names (sparse
                # columns expand to their values/ids/nnz triples)
            ) + ((("replicated",) if replicated else ()))
            return key, inputs, rows, replicated

        def on_compile() -> None:
            metrics.counter(self.scope, MLMetrics.BATCH_COMPILES)

        # Declared outputs land in preallocated full-length host buffers —
        # buffers are disjoint per chunk, so each chunk readback is an
        # independent slice assignment (``buf[lo:hi] = view``): a single-pass
        # device-view → storage-dtype cast, no per-chunk intermediate array
        # and no final concatenate. Readbacks run on the shared pool (numpy
        # releases the GIL for the cast), overlapping the host dispatch of
        # later chunks; the prefetch window keeps at most ``depth`` chunks
        # dispatched-unfinalized so host/HBM residency stays bounded.
        out_bufs: Dict[str, np.ndarray] = {}
        out_decl: Dict[str, Any] = {}
        inflight: List[Tuple[float, List[Any]]] = []

        # Plan-cache outcome of the chunk currently compiling — the chunk
        # span publishes it on the shared `plancache` attr (compile-path
        # only: a signature already chained never reaches the cache).
        span_holder: Dict[str, Any] = {}

        def on_cache(outcome: str, ms: float) -> None:
            sp = span_holder.get("sp")
            if sp is not None:
                sp.set_attr("plancache", outcome)

        def readback_one(buf: np.ndarray, lo: int, hi: int, arr: Any) -> None:  # graftcheck: readback
            # THE designated sync point of the batch fast path: np.asarray
            # blocks until the device value is ready (zero-copy view on the
            # CPU backend); the widening cast (f32→f64) in the slice
            # assignment is value-exact. The [:hi-lo] slice drops the DP
            # round-up pad rows of a sharded ragged chunk (a no-op when
            # unpadded). Runs on the readback pool, behind the prefetch
            # window — never serially with dispatch.
            buf[lo:hi] = np.asarray(arr)[: hi - lo]

        def finalize_oldest() -> None:
            t_dispatch, futures = inflight.pop(0)
            with tracer.span("batch.readback", CAT_READBACK, scope=self.scope):
                for f in futures:
                    f.result()
            chunk_hist.observe((time.perf_counter() - t_dispatch) * 1000.0)

        pool = _readback_pool()
        nxt = ingest(starts[0])
        for i, lo in enumerate(starts):
            key, inputs, rows, replicated = nxt
            padded = next(iter(inputs.values())).shape[0] if inputs else rows
            t_dispatch = time.perf_counter()
            with tracer.span("batch.chunk", CAT_PRODUCTIVE, scope=self.scope) as sp:
                # rows = true chunk rows, bucket = the DP-padded shape the
                # program ran at — the goodput padding split counts the
                # round-up exactly once, here and nowhere else.
                sp.set_attr("rows", rows)
                sp.set_attr("bucket", padded)
                if nnz_cap:
                    # ELL attribution: entries the chunk's TRUE rows carry vs
                    # the bucket×cap cells the program computes — graftscope
                    # counts ELL + row padding exactly once from these
                    # (docs/observability.md).
                    hi_ = min(lo + chunk_rows, n)
                    sp.set_attr(
                        "nnz", int(sum(int(full[m][lo:hi_].sum()) for m in nnz_names))
                    )
                    sp.set_attr("nnz_cap", nnz_cap)
                if sharding is not None:
                    sp.set_attr("shards", 1 if replicated else sharding.n_data)
                span_holder["sp"] = sp
                outputs = run_segment(
                    segment,
                    key,
                    inputs,
                    on_compile=on_compile,
                    on_plan=self._on_plan,
                    replicated=replicated,
                    cache=self.plancache,
                    on_cache=on_cache if self.plancache is not None else None,
                    on_mega_fallback=self._on_mega_fallback,
                )
                # The fusion tier this chunk's compiled chain runs at
                # ("exact" / "fast" / "fast+mega") — goodput attribution
                # distinguishes the tiers by this attr.
                sp.set_attr("fusion", segment.plan_label(key))
                pending = segment.pending(outputs)
            if sharding is not None:
                if replicated:
                    metrics.counter(self.scope, MLMetrics.BATCH_SHARD_REPLICATED_CHUNKS)
                else:
                    metrics.counter(
                        self.scope, MLMetrics.BATCH_SHARD_ROWS, padded // sharding.n_data
                    )
                    if padded != rows:
                        metrics.counter(
                            self.scope, MLMetrics.BATCH_SHARD_PAD_ROWS, padded - rows
                        )
            if not out_bufs:  # shapes are fixed by the programs: alloc once
                for name, dtype, arr, np_dtype in pending:
                    out_bufs[name] = np.empty((n,) + tuple(arr.shape[1:]), np_dtype)
                    out_decl[name] = dtype
            hi = min(lo + chunk_rows, n)
            inflight.append(
                (
                    t_dispatch,
                    [
                        pool.submit(readback_one, out_bufs[name], lo, hi, arr)
                        for name, _dtype, arr, _np_dtype in pending
                    ],
                )
            )
            if i + 1 < len(starts):
                nxt = ingest(starts[i + 1])  # overlaps the async device exec
            while len(inflight) >= depth:
                finalize_oldest()
        while inflight:
            finalize_oldest()

        metrics.counter(self.scope, MLMetrics.BATCH_FUSED_CHUNKS, len(starts))
        metrics.counter(self.scope, MLMetrics.BATCH_FUSED_ROWS, n)
        out = df.clone()
        for name, _ in segment.outputs:
            if name in segment.sparse_outputs:
                # A sparse-convention output: the three part buffers rebuild
                # the SparseVector column (leading-nnz slots, sorted-unique
                # by the kernels' compaction invariant) — the same column the
                # per-stage path would have added.
                out.add_column(
                    name,
                    DataTypes.vector(BasicType.DOUBLE),
                    rebuild_sparse_column(
                        segment.sparse_outputs[name],
                        out_bufs[values_name(name)],
                        out_bufs[ids_name(name)],
                        out_bufs[nnz_name(name)],
                    ),
                )
                continue
            host = out_bufs[name]
            dtype = out_decl[name]
            if dtype is None:  # shape-following output: infer like transform
                dtype = (
                    DataTypes.vector(BasicType.DOUBLE)
                    if host.ndim == 2
                    else DataTypes.DOUBLE
                )
            out.add_column(name, dtype, host)
        return out

    def _fallback(self, segment: FusedSegment, df: DataFrame, *, count: bool) -> DataFrame:
        """Per-stage execution of a fused segment's stages (sparse/ragged
        input, or an empty frame not worth compiling for)."""
        if count:
            metrics.counter(self.scope, MLMetrics.BATCH_FALLBACK_SEGMENTS)
        for stage in segment.stages:
            out = stage.transform(df)
            df = out[0] if isinstance(out, (list, tuple)) else out
        return df
