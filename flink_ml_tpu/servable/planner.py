"""Shared stage-chain planner — the compiler both fast paths are built on.

PR 4's serving fast path (``serving/plan.py``) introduced the machinery:
consecutive :class:`~flink_ml_tpu.servable.kernel_spec.KernelSpec` stages
compose into an **executable chain** — one AOT program per stage, stage
outputs flowing between programs as device arrays, a single host→device
ingest and a single device→host readback, zero inter-stage DataFrame
materialization. The batch tier (``builder/batch_plan.py``) needs exactly the
same compiler over the same specs, so the chain machinery lives here, at the
servable layer, metric-free and policy-free; the two plan classes add their
own policy on top:

- the serving plan keys programs by padded *bucket*, AOT-warms them before a
  version flip, and falls back per batch on any signature mismatch;
- the batch plan keys programs by the ingest *signature* itself (chunk rows ×
  column widths), compiles lazily on first sight, and streams chunks through
  with a double-buffered prefetch window.

Program granularity — the bit-exactness contract:

Whole-pipeline programs are NOT bit-stable — XLA legally fuses one stage's
elementwise math into the next stage's dot reduction, which reorders the
accumulation (measured: 100s of ulps on a scaler→logistic margin at widths
≥ 8, and an ``optimization_barrier`` does not pin the dot emitter's choice).
So any spec containing a reduction (Normalizer's row norm, DCT's matmul, a
model head's dot) keeps its OWN program: on the same input bits it reproduces
the per-stage path's numerics by construction.

Consecutive specs that declare ``elementwise=True`` (no cross-element FP
accumulation at all — comparisons, gathers, concats, per-element arithmetic)
DO merge into one program: a reduction-free graph has no accumulation order
for XLA to reorder, each merged stage's output is still a program output (a
single HLO value feeds both the readback and the next stage — identical to
handing the same device array to a separate program), and every elementwise
op computes per element exactly as it would alone. Merging saves one HBM
round-trip and one program dispatch per interior boundary, which is most of
the fused win on short chains.

Fusion tiers (``fusion.mode``, resolved in ``servable/fusion.py``): the
partition above is the **exact** tier — the default, bit-identical to the
per-stage path. A segment built with a fast :class:`FusionTier` instead
partitions into maximal ``fusable`` runs (``_partition_fast``): one XLA
program per run, *crossing* reduction boundaries, so XLA may fuse a scaler's
elementwise math straight into the following dot — the relaxed-numerics tier
whose movement is bounded by the documented ulp envelope
(``fusion.ULP_ENVELOPE``). At compile time (rows known, per key) the cost
model may lower a hot run as a hand-fused Pallas megakernel instead
(``servable/megakernels.py``) — intermediates VMEM-resident for the whole
chain. Megakernels require an unsharded segment; sharded fast-tier segments
lower their merged programs through the same SPMD machinery below.

Mesh sharding (``servable/sharding.py``): a segment built with a
:class:`~flink_ml_tpu.servable.sharding.PlanSharding` commits its model
arrays **per shard** (replicated, or TP-split for wide heads) and lowers its
programs with batch rows sharded over the mesh's data axis — the same
per-stage program partition, now SPMD. Row-independence means no program
here contains a cross-row accumulation for the shard boundary to cut, and
the callers' padding discipline (buckets/chunks keep every shard in the
row-count-invariant regime — see the MIN_SHARD_ROWS note in
``servable/sharding.py``) keeps per-row results bit-identical to the
single-device path. The planner stays policy-free: WHERE the rows come from
and how they are padded belongs to the serving/batch tiers; WHICH fusion
tier applies belongs to the resolved ``FusionTier`` the caller passes.
"""
from __future__ import annotations

import time
import warnings
from typing import Any, Callable, Dict, Hashable, List, Optional, Sequence, Tuple

import jax
import numpy as np

from flink_ml_tpu.api.dataframe import DataFrame
from flink_ml_tpu.api.types import BasicType, DataTypes
from flink_ml_tpu.servable.fusion import chain_score
from flink_ml_tpu.servable.shapes import k_rung, shape_array, shape_name
from flink_ml_tpu.servable.sparse import (
    SPARSE_MARK,
    OffLadderError,
    pack_sparse_column,
    rebuild_sparse_column,
    sparse_names,
)

__all__ = [
    "IneligibleBatch",
    "FusedSegment",
    "FallbackStage",
    "PlanExecution",
    "build_segments",
    "run_segment",
]

#: Program kinds a compiled chain may carry (the plan-choice vocabulary the
#: ``ml.fusion.*`` metrics and the ``fusion`` span attribute report).
PLAN_EXACT = "exact"
PLAN_FUSED = "fused"
PLAN_MEGAKERNEL = "megakernel"


class IneligibleBatch(Exception):
    """This batch cannot ride a fused executable — fall back to per-stage.

    ``reason`` labels the per-reason fallback counters
    (``ml.<tier>.fastpath.fallback.<reason>``): ``"sparse"`` (a sparse column
    where the spec expects a dense kind), ``"ragged"`` (list column / shape
    the convention cannot take), ``"off_ladder"`` (nnz above
    ``sparse.nnz.cap.max``, or a bucket off the mesh row ladder),
    ``"signature"`` (shape/dim differing from the compiled signature)."""

    def __init__(self, message: str, reason: str = "ragged"):
        super().__init__(message)
        self.reason = reason


class _Program:
    """One XLA program of a segment's chain: a single spec, a merged run of
    consecutive ``elementwise`` specs (exact tier), or a maximal ``fusable``
    run crossing reduction boundaries (fast tier — ``kind`` records which;
    see module docstring)."""

    __slots__ = ("specs", "models", "inputs", "jitted", "kind")

    def __init__(
        self,
        specs: Sequence[Any],
        models: Sequence[Dict[str, Any]],
        kind: str = PLAN_EXACT,
        precision: Optional[Any] = None,
    ):
        self.specs = tuple(specs)
        self.models = tuple(models)
        self.kind = kind
        needed: List[str] = []
        produced: set = set()
        for spec in self.specs:
            for col in spec.input_cols:
                for name in spec.program_input_names(col):
                    if name not in produced and name not in needed:
                        needed.append(name)
            produced.update(spec.program_outputs)
        self.inputs: Tuple[str, ...] = tuple(needed)

        # Low-precision transport (servable/precision.py): round every float
        # value to the bf16 grid at program ENTRY and at every stage EXIT,
        # keeping the kernel bodies — and every reduction inside them —
        # untouched f32 (bf16 transport, f32 accumulation). bf16_round is
        # idempotent, so a boundary the fused partition elides and the
        # per-stage partition materializes sees identical bits — the
        # within-tier fused-vs-per-stage parity contract. f32 tier
        # (precision None or mode f32): no rounding anywhere, bit-identical
        # to the pre-precision planner.
        lowp = precision is not None and precision.lowp
        if lowp:
            from flink_ml_tpu.servable.precision import bf16_round

            def program_fn(models, cols):
                cols = {n: bf16_round(v) for n, v in cols.items()}
                outs: Dict[str, Any] = {}
                for spec, model in zip(self.specs, models):
                    stage_out = spec.kernel_fn(model, cols)
                    stage_out = {n: bf16_round(v) for n, v in stage_out.items()}
                    cols.update(stage_out)
                    outs.update(stage_out)
                return outs

        else:

            def program_fn(models, cols):
                cols = dict(cols)
                outs: Dict[str, Any] = {}
                for spec, model in zip(self.specs, models):
                    stage_out = spec.kernel_fn(model, cols)
                    cols.update(stage_out)
                    outs.update(stage_out)
                return outs

        self.jitted = jax.jit(program_fn)


class _MegaProgram:
    """A hot fast-tier run lowered as one hand-fused Pallas megakernel
    (``servable/megakernels.py``) — same calling convention as
    :class:`_Program`, so ``run_segment`` lowers/compiles/executes it through
    the identical machinery. Built only behind the fast tier (see
    ``_fast_megakernels``); the cost model decides per compiled key whether
    the chain is hot enough to use it."""

    __slots__ = ("specs", "models", "inputs", "jitted", "kind")

    def __init__(self, program: _Program, mega_fn: Callable):
        self.specs = program.specs
        self.models = program.models
        self.inputs = program.inputs
        self.kind = PLAN_MEGAKERNEL
        self.jitted = jax.jit(mega_fn)


def _partition_exact(specs: Sequence[Any]) -> List[Tuple[int, int]]:
    """The exact tier's program partition: one program per spec, except
    consecutive ``elementwise`` specs, which merge (a reduction-free graph
    has no accumulation order to reorder — the bit-exactness contract in the
    module docstring). No program here ever spans a reduction boundary; the
    graftcheck ``fusion-tier`` rule pins this function to that shape."""
    runs: List[Tuple[int, int]] = []
    i = 0
    while i < len(specs):
        j = i + 1
        if specs[i].elementwise:
            while j < len(specs) and specs[j].elementwise:
                j += 1
        runs.append((i, j))
        i = j
    return runs


def _partition_fast(specs: Sequence[Any]) -> List[Tuple[int, int]]:
    """The fast tier's program partition: maximal runs of ``fusable`` specs
    become ONE program each, crossing reduction boundaries — XLA fuses the
    whole run (ulp-envelope numerics, docs/fusion.md). A spec with
    ``fusable=False`` keeps its own program in every tier."""
    runs: List[Tuple[int, int]] = []
    i = 0
    while i < len(specs):
        j = i + 1
        if specs[i].fusable:
            while j < len(specs) and specs[j].fusable:
                j += 1
        runs.append((i, j))
        i = j
    return runs


def _fast_megakernels(
    programs: Sequence[_Program], sharding: Optional[Any]
) -> Dict[int, _MegaProgram]:
    """Megakernel candidates per fast-tier program index: built only for
    unsharded segments (a megakernel is a single-device program; sharded
    fast-tier segments keep the merged SPMD XLA programs) and only for runs
    whose every spec carries a megakernel-safe ``fusion_op``. Whether a
    candidate is actually USED is the cost model's per-key call in
    ``run_segment`` — building the candidate here costs one closure, no
    compile."""
    if sharding is not None:
        return {}
    from flink_ml_tpu.servable.megakernels import build_megakernel_fn, chain_eligible

    # Mosaic compiles the kernel on every accelerator backend; only the CPU
    # backend (tests) has no Mosaic target and runs the interpreter.
    interpret = jax.default_backend() == "cpu"
    out: Dict[int, _MegaProgram] = {}
    for idx, prog in enumerate(programs):
        if chain_eligible(prog.specs):
            mega_fn = build_megakernel_fn(
                prog.specs, prog.models, prog.inputs, interpret
            )
            out[idx] = _MegaProgram(prog, mega_fn)
    return out


class FusedSegment:
    """A maximal run of consecutive kernel-spec stages, compiled as one
    executable chain per key: one AOT program per reduction-bearing stage
    (merged programs for elementwise runs), stage outputs flowing between
    programs as device arrays (never through the host)."""

    __slots__ = (
        "stages", "specs", "external_inputs", "device_models", "programs",
        "compiled", "signatures", "sharding", "fusion", "precision", "mega",
        "plan_kinds", "sparse_outputs", "has_sparse_inputs", "has_shape_inputs",
    )

    def __init__(
        self,
        staged: Sequence[Tuple[Any, Any]],
        sharding: Optional[Any] = None,
        fusion: Optional[Any] = None,
        precision: Optional[Any] = None,
    ):
        self.stages = [stage for stage, _ in staged]
        self.specs = [spec for _, spec in staged]
        self.sharding = sharding
        self.fusion = fusion  # resolved FusionTier, or None ≡ exact
        self.precision = precision  # resolved PrecisionTier, or None ≡ f32
        produced: set = set()
        external: List[str] = []
        for spec in self.specs:
            for col in spec.input_cols:
                expanded = spec.program_input_names(col)
                if all(n in produced for n in expanded):
                    continue
                if col not in external:
                    external.append(col)
            produced.update(spec.program_outputs)
        self.external_inputs: Tuple[str, ...] = tuple(external)
        #: Sparse-convention outputs of the whole segment: column -> dim
        #: (the readback rebuilds SparseVector columns from the triples).
        self.sparse_outputs: Dict[str, int] = {}
        for spec in self.specs:
            self.sparse_outputs.update(spec.sparse_outputs)
        #: Whether any external input rides the sparse convention — such
        #: segments key their compiled chains by (bucket, nnz cap) and the
        #: serving warmup covers the configured cap ladder.
        self.has_sparse_inputs = any(
            self.input_kind(name) in ("sparse", "entries")
            for name in self.external_inputs
        )
        #: Whether any external input is a per-request output-width column
        #: (the retrieval top-K convention, ``servable/shapes.py``) — such
        #: segments extend their compiled key with the K ladder rung and the
        #: serving warmup covers the configured K ladder.
        self.has_shape_inputs = any(
            self.input_kind(name) == "shape" for name in self.external_inputs
        )
        # One upload per model array, at construction — the committed buffers
        # the hot path closes over. On a mesh this is the per-shard weight
        # placement (replicated or TP-split), paid at build/warmup time —
        # for serving, at swap time before the version flip. A low-precision
        # tier rounds the committed float buffers to the bf16 grid HERE, once
        # (the model-side half of the transport contract) — never per call.
        lowp = precision is not None and precision.lowp
        if lowp:
            from flink_ml_tpu.servable.precision import bf16_round

        def _commit(v):
            arr = sharding.put_model(v) if sharding is not None else jax.device_put(v)
            return bf16_round(arr) if lowp else arr

        self.device_models = tuple(
            {k: _commit(v) for k, v in spec.model_arrays.items()}
            for spec in self.specs
        )
        # Program partition (see module docstring): the exact tier merges
        # only consecutive elementwise specs, so no accumulation can cross a
        # per-stage-path boundary; the fast tier merges maximal fusable runs
        # across reductions and builds Pallas megakernel candidates for the
        # cost model to pick per compiled key.
        if fusion is not None and fusion.fast:
            runs = _partition_fast(self.specs)
            kind = PLAN_FUSED
        else:
            runs = _partition_exact(self.specs)
            kind = PLAN_EXACT
        self.programs: List[_Program] = [
            _Program(self.specs[i:j], self.device_models[i:j], kind, precision)
            for i, j in runs
        ]
        #: fast tier only: program index -> megakernel candidate. A
        #: low-precision segment builds NONE: megakernels compose raw f32
        #: kernel bodies with no stage-boundary hook, so they cannot honor
        #: the bf16 transport contract — lowp fast-tier runs keep the merged
        #: XLA programs (which carry the rounding in-graph).
        self.mega: Dict[int, _MegaProgram] = {}
        if fusion is not None and fusion.fast and fusion.megakernel and not lowp:
            self.mega = _fast_megakernels(self.programs, sharding)
        #: key -> [(program-or-megakernel, jax.stages.Compiled), ...] in order
        self.compiled: Dict[Hashable, List[Tuple[Any, Any]]] = {}
        #: key -> {input name: (shape, dtype)} recorded at compile time
        self.signatures: Dict[Hashable, Dict[str, Tuple[Tuple[int, ...], Any]]] = {}
        #: key -> tuple of program kinds chosen at compile time (the span
        #: attribute / plan-choice vocabulary)
        self.plan_kinds: Dict[Hashable, Tuple[str, ...]] = {}

    def input_kind(self, name: str) -> str:
        """The ingest accessor for an external input — the first consuming
        spec's declared kind (specs sharing a column agree by construction:
        they all read it the way ``transform`` would)."""
        for spec in self.specs:
            if name in spec.input_cols:
                return spec.input_kind(name)
        return "vector"

    def gather(self, df: DataFrame, name: str, *, raw: bool = False) -> np.ndarray:
        """One host-side gather of an external input column, exactly the way
        the consuming stage's ``transform`` would read it, as float32 (the
        dtype JAX canonicalizes device arrays to — host astype and jit-time
        canonicalization round identically). ``raw=True`` skips the float32
        cast so a caller can do its own (the batch tier casts large inputs in
        parallel row blocks — block-wise astype is the same value-exact cast).
        Raises :class:`IneligibleBatch` for anything a fused program cannot
        take."""
        try:
            if df.is_sparse(name):
                # A sparse column where this spec expects a dense kind: the
                # sparse calling convention covers only declared-sparse specs
                # (docs/sparse.md) — everything else keeps the bit-exact
                # per-stage fallback, reason-labelled.
                raise IneligibleBatch(f"column {name!r} is sparse", reason="sparse")
            kind = self.input_kind(name)
            if kind == "scalar":
                arr = df.scalars(name)
            elif kind == "dense":
                col = df.column(name)
                if not isinstance(col, np.ndarray):
                    raise IneligibleBatch(
                        f"column {name!r} is ragged — per-stage path owns list columns"
                    )
                arr = col
            else:
                arr = df.vectors(name)
            if raw:
                return arr
            return np.asarray(arr, np.float32)
        except IneligibleBatch:
            raise
        except Exception as e:  # ragged / non-numeric / missing column
            raise IneligibleBatch(f"column {name!r} not fusable: {e}") from e

    def gather_sparse(
        self,
        df: DataFrame,
        name: str,
        *,
        cap: Optional[int] = None,
        cap_max: Optional[int] = None,
        truncate: bool = False,
    ) -> Tuple[Dict[str, Any], int, int]:
        """One host-side gather of a sparse-convention external input:
        ``"sparse"`` columns pack through the ELL ladder
        (``servable/sparse.py``), ``"entries"`` columns run the consuming
        spec's host featurizer. Returns ``(arrays, nnz_cap, true_nnz)``.
        Raises :class:`IneligibleBatch` (reason-labelled) for anything the
        convention cannot take — off-ladder rows, dim mismatches, columns
        that are not actually sparse."""
        kind = self.input_kind(name)
        try:
            if kind == "entries":
                for spec in self.specs:
                    fn = spec.host_ingests.get(name)
                    if fn is not None:
                        return fn(df, cap, cap_max, truncate)
                raise IneligibleBatch(f"no host ingest for column {name!r}")
            if not df.is_sparse(name):
                raise IneligibleBatch(
                    f"column {name!r} is not sparse — compiled signature expects "
                    "the sparse convention",
                    reason="signature",
                )
            dim = None
            for spec in self.specs:
                if name in spec.sparse_input_dims:
                    dim = spec.sparse_input_dims[name]
                    break
            arrays, used_cap, _dim, total = pack_sparse_column(
                df, name, dim=dim, cap=cap, cap_max=cap_max, truncate=truncate
            )
            return arrays, used_cap, total
        except IneligibleBatch:
            raise
        except OffLadderError as e:
            raise IneligibleBatch(str(e), reason="off_ladder") from e
        except ValueError as e:  # dim mismatch / malformed column
            raise IneligibleBatch(
                f"column {name!r} not packable: {e}", reason="signature"
            ) from e
        except Exception as e:
            raise IneligibleBatch(f"column {name!r} not packable: {e}") from e

    def gather_shape(
        self,
        df: DataFrame,
        names: Sequence[str],
        *,
        rung: Optional[int] = None,
        cap_max: Optional[int] = None,
    ) -> Tuple[Dict[str, np.ndarray], int]:
        """One host-side read of the segment's ``"shape"``-kind columns (the
        per-request top-K widths): the batch's K ladder rung is the max true
        K across every shape column, rounded up to a power of two — or the
        forced ``rung`` (warmup walks the configured K ladder). Returns the
        ``({col!shape: zeros [n, rung]}, rung)`` carrier arrays the programs
        key their static output width on. Raises :class:`IneligibleBatch`
        (``off_ladder``) when the batch asks for more than ``cap_max``."""
        kmax = 1
        if rung is None:
            for name in names:
                try:
                    ks = df.scalars(name)
                except Exception as e:
                    raise IneligibleBatch(
                        f"column {name!r} not usable as a top-K width: {e}"
                    ) from e
                if len(ks):
                    kmax = max(kmax, int(np.max(ks)))
            rung = k_rung(kmax)
            if cap_max is not None and rung > cap_max:
                raise IneligibleBatch(
                    f"per-request K {kmax} — ladder rung {rung} exceeds "
                    f"retrieval.k.cap.max={cap_max}",
                    reason="off_ladder",
                )
        return (
            {shape_name(name): shape_array(len(df), rung) for name in names},
            rung,
        )

    @property
    def outputs(self) -> List[Tuple[str, Any]]:
        out: List[Tuple[str, Any]] = []
        for spec in self.specs:
            out.extend(spec.outputs)
        return out

    def plan_label(self, key: Hashable) -> str:
        """The fusion tier the compiled chain for ``key`` actually runs at —
        ``"exact"``, ``"fast"`` (merged XLA programs), or ``"fast+mega"``
        (at least one program lowered as a Pallas megakernel). The value the
        callers put on their trace spans' ``fusion`` attribute."""
        kinds = self.plan_kinds.get(key, ())
        if PLAN_MEGAKERNEL in kinds:
            return "fast+mega"
        if PLAN_FUSED in kinds:
            return "fast"
        return PLAN_EXACT

    def pending(self, outputs: Dict[str, Any]) -> List[Tuple[str, Any, Any, Any]]:
        """Readback-ready (name, declared DataType, device array, numpy dtype)
        tuples for every declared stage output, in ``add_column`` order. A
        sparse-convention output expands to its three parts, the DataType
        slot carrying the ``(SPARSE_MARK, column, dim, part)`` marker the
        readback paths rebuild the SparseVector column from."""
        out = []
        for spec in self.specs:
            for name, dtype in spec.outputs:
                if name in spec.sparse_outputs:
                    dim = spec.sparse_outputs[name]
                    vn, idn, zn = sparse_names(name)
                    out.append((vn, (SPARSE_MARK, name, dim, "values"), outputs[vn], np.dtype(np.float64)))
                    out.append((idn, (SPARSE_MARK, name, dim, "ids"), outputs[idn], np.dtype(np.int64)))
                    out.append((zn, (SPARSE_MARK, name, dim, "nnz"), outputs[zn], np.dtype(np.int64)))
                else:
                    out.append((name, dtype, outputs[name], spec.readback_dtype(name)))
        return out


class FallbackStage:
    """A stage served through its ordinary ``transform`` (no kernel spec)."""

    __slots__ = ("stage",)

    def __init__(self, stage):
        self.stage = stage


def build_segments(
    stages: Sequence[Any],
    sharding: Optional[Any] = None,
    fusion: Optional[Any] = None,
    sparse: Optional[Dict[str, int]] = None,
    precision: Optional[Any] = None,
) -> List[Any]:
    """Group consecutive kernel-spec stages into :class:`FusedSegment` runs,
    everything else into :class:`FallbackStage`. Raises whatever
    ``kernel_spec()`` raises (an unloaded model must fail closed at plan
    build, before it could ever run); a stage whose ``kernel_spec()`` returns
    None falls back. With a ``sharding``
    (:class:`~flink_ml_tpu.servable.sharding.PlanSharding`), fused segments
    commit their model arrays per shard and compile SPMD programs. With a
    fast ``fusion`` (:class:`~flink_ml_tpu.servable.fusion.FusionTier`),
    segments partition across reduction boundaries (module docstring);
    ``None`` is the exact tier.

    ``sparse`` enables the sparse calling convention (docs/sparse.md):
    a ``{column: dim}`` map of inputs KNOWN to arrive sparse (the caller's
    hints — the serving template, the batch call's DataFrame), or ``None``
    when ``sparse.fastpath`` is off. Sparseness then propagates statically:
    before asking each stage for a spec, the planner offers the known-sparse
    set to the stage's ``sparse_kernel_spec(known)`` hook; a stage whose
    inputs arrive sparse (or that featurizes ragged data — HashingTF,
    CountVectorizer) returns a sparse-convention spec, and its
    ``sparse_outputs`` join the known set for downstream stages. Stages
    without the hook (or returning None) fall back to their dense
    ``kernel_spec()``, exactly as before.

    With a low-precision ``precision``
    (:class:`~flink_ml_tpu.servable.precision.PrecisionTier`), fused
    segments commit bf16-rounded model buffers and their programs carry the
    bf16 transport rounding in-graph; ``None`` is the f32 tier,
    bit-identical to the pre-precision planner."""
    segments: List[Any] = []
    run: List[Tuple[Any, Any]] = []
    known: Dict[str, int] = dict(sparse or {})
    for stage in stages:
        spec = None
        if sparse is not None and hasattr(stage, "sparse_kernel_spec"):
            spec = stage.sparse_kernel_spec(dict(known))
        if spec is None and hasattr(stage, "kernel_spec"):
            spec = stage.kernel_spec()
        if spec is not None:
            run.append((stage, spec))
            known.update(spec.sparse_outputs)
            for name in spec.output_names:
                if name not in spec.sparse_outputs:
                    known.pop(name, None)  # densely overwritten column
        else:
            if run:
                segments.append(FusedSegment(run, sharding, fusion, precision))
                run = []
            segments.append(FallbackStage(stage))
            # A fallback stage's outputs are opaque — any column it may
            # overwrite stays whatever the DataFrame says at run time; the
            # static known-set keeps only the caller's original hints for
            # columns a spec never touched. (Conservative: a fallback stage
            # that densifies a hinted column surfaces as a per-batch
            # signature fallback, never a wrong result.)
    if run:
        segments.append(FusedSegment(run, sharding, fusion, precision))
    return segments


def _compile_lowered(lowered: Any) -> Any:
    """THE XLA-compile seam of the chain executor — every live compile of a
    chain program goes through this one call, so the zero-compile-resume
    proof (tests/test_plancache.py, tools/ci/restart_smoke.py) can poison it
    and assert a cache-warmed incarnation never reaches it."""
    return lowered.compile()


def _load_or_compile(  # graftcheck: cold
    prog: Any,
    structs: Dict[str, jax.ShapeDtypeStruct],
    segment: FusedSegment,
    replicated: bool,
    cache: Optional[Any],
    on_cache: Optional[Callable[[str, float], None]],
    sparse_key: Optional[int] = None,
) -> Any:
    """One program's executable: lower always (cheap — the tracing term),
    then load the serialized executable from the plan cache by its content
    digest, falling back to the live XLA compile on a miss (and storing the
    result for the next incarnation). With no cache this is exactly the old
    ``lower().compile()``."""
    lowered = prog.jitted.lower(prog.models, structs)
    if cache is None:
        return _compile_lowered(lowered)
    from flink_ml_tpu.servable.plancache import program_digest

    digest = program_digest(
        lowered,
        kind=prog.kind,
        sharding_key=segment.sharding.key if segment.sharding is not None else None,
        fusion_key=segment.fusion.key if segment.fusion is not None else None,
        replicated=replicated,
        sparse_key=sparse_key,
        precision_key=(
            segment.precision.cache_key if segment.precision is not None else None
        ),
    )
    t0 = time.perf_counter()
    compiled = cache.load(digest)
    if compiled is not None:
        if on_cache is not None:
            on_cache("hit", (time.perf_counter() - t0) * 1000.0)
        return compiled
    if on_cache is not None:
        on_cache("miss", (time.perf_counter() - t0) * 1000.0)
    compiled = _compile_lowered(lowered)
    cache.store(
        digest,
        compiled,
        meta={"kind": prog.kind, "inputs": sorted(structs)},
    )
    return compiled


def _lowering_struct(segment: FusedSegment, arr: Any, replicated: bool) -> jax.ShapeDtypeStruct:
    """Aval for one program input at lowering time. Device arrays (program
    intermediates, pre-committed ingests) carry their own placement; host
    arrays take the segment's batch sharding (or full replication for the
    sub-floor ragged-tail path); the unsharded path keeps today's plain
    structs."""
    if segment.sharding is None:
        return jax.ShapeDtypeStruct(arr.shape, arr.dtype)
    if isinstance(arr, jax.Array):
        return jax.ShapeDtypeStruct(arr.shape, arr.dtype, sharding=arr.sharding)
    return segment.sharding.input_struct(arr.shape, arr.dtype, replicated=replicated)


def run_segment(
    segment: FusedSegment,
    key: Hashable,
    inputs: Dict[str, Any],
    *,
    on_compile: Optional[Callable[[], None]] = None,
    on_plan: Optional[Callable[[str, float], None]] = None,
    replicated: bool = False,
    cache: Optional[Any] = None,
    on_cache: Optional[Callable[[str, float], None]] = None,
    on_mega_fallback: Optional[
        Callable[[Sequence[str], int, BaseException], None]
    ] = None,
) -> Dict[str, Any]:
    """Execute the segment's executable chain for ``key``: each program runs
    on the committed device model buffers and the (device-resident) outputs
    of the programs before it. Compiles the chain first if ``key`` was never
    seen — calling ``on_compile`` once so the caller can count it (the
    serving tier's warmup-coverage alarm, the batch tier's chunk-shape
    accounting), and ``on_plan(kind, score)`` once per program with the plan
    choice the cost model made (exact / fused / megakernel — the
    ``ml.fusion.*`` accounting). On a fast-tier segment the choice is
    per-key: a run with a megakernel candidate lowers it only when the
    cost-model score at this key's rows clears the tier's bar and the chain
    fits the kernel's VMEM. A megakernel the backend still rejects at
    compile time falls back to the merged XLA program LOUDLY: one warning,
    and ``on_mega_fallback(ops, rows, error)`` so the caller can count and
    journal it (``fusion.fallback_recorder``). On a sharded
    segment the chain lowers SPMD — batch rows split over the data axis, or
    fully ``replicated`` for a sub-floor ragged tail (the caller bakes the
    mode into ``key``: the two compile different executables).

    With a ``cache`` (:class:`~flink_ml_tpu.servable.plancache.PlanCache`),
    the compile becomes load-or-compile: each program's serialized
    executable is fetched by content digest — a restarted incarnation
    reaches a ready chain in O(load) not O(XLA) — and ``on_cache(outcome,
    ms)`` reports "hit"/"miss" per program so callers can split warm time
    between cache loads and true compiles (docs/plancache.md)."""
    chain = segment.compiled.get(key)
    if chain is None:
        if on_compile is not None:
            on_compile()
        rows = next(iter(inputs.values())).shape[0] if inputs else 0
        # Expanded sparse-convention names carry a `!` — their [n, K] shapes
        # feed the cost model's nnz-cap term, not the dense ingest width.
        width = max(
            (
                int(a.shape[1])
                for name, a in inputs.items()
                if getattr(a, "ndim", 1) == 2 and "!" not in name
            ),
            default=0,
        )
        nnz_cap = max(
            (
                int(a.shape[1])
                for name, a in inputs.items()
                if name.endswith("!ids") and getattr(a, "ndim", 1) == 2
            ),
            default=0,
        )
        if segment.sharding is not None and not replicated:
            if rows % segment.sharding.n_data:
                raise IneligibleBatch(
                    f"{rows} rows not divisible by the {segment.sharding.n_data}-way "
                    "data axis — pad to a mesh multiple or run replicated"
                )
        chain = []
        kinds: List[str] = []
        cols: Dict[str, Any] = dict(inputs)
        for idx, xla_prog in enumerate(segment.programs):
            prog = xla_prog
            stage_inputs = {n: cols[n] for n in prog.inputs}
            structs = {
                n: _lowering_struct(segment, a, replicated)
                for n, a in stage_inputs.items()
            }
            compiled = None
            mega = segment.mega.get(idx)
            if mega is not None and segment.fusion.megakernel_hot(
                prog.specs, rows, width, nnz_cap, precision=segment.precision
            ):
                try:
                    compiled = _load_or_compile(
                        mega, structs, segment, replicated, cache, on_cache,
                        sparse_key=nnz_cap or None,
                    )
                    prog = mega
                except Exception as e:  # noqa: BLE001 — Pallas/Mosaic raise private types
                    # A megakernel the backend's Pallas lowering rejects must
                    # not take the fast tier down — the merged XLA program
                    # computes the same chain inside the same ulp envelope —
                    # but the chip must not hide behind it either.
                    ops = [spec.fusion_op for spec in prog.specs]
                    warnings.warn(
                        f"megakernel {'+'.join(ops)} rejected by the "
                        f"{jax.default_backend()} backend; serving the merged "
                        f"XLA program instead: {type(e).__name__}: "
                        f"{str(e)[:500]}",
                        RuntimeWarning,
                        stacklevel=2,
                    )
                    if on_mega_fallback is not None:
                        on_mega_fallback(ops, rows, e)
            if compiled is None:
                compiled = _load_or_compile(
                    prog, structs, segment, replicated, cache, on_cache,
                    sparse_key=nnz_cap or None,
                )
            if on_plan is not None:
                on_plan(
                    prog.kind,
                    chain_score(
                        prog.specs, rows, width, nnz_cap,
                        precision=segment.precision,
                    ),
                )
            kinds.append(prog.kind)
            chain.append((prog, compiled))
            cols.update(compiled(prog.models, stage_inputs))
        segment.compiled[key] = chain
        segment.plan_kinds[key] = tuple(kinds)
        segment.signatures[key] = {
            name: (tuple(arr.shape), arr.dtype) for name, arr in inputs.items()
        }
    cols = dict(inputs)
    outs: Dict[str, Any] = {}
    for prog, compiled in chain:
        prog_out = compiled(prog.models, {n: cols[n] for n in prog.inputs})
        cols.update(prog_out)
        outs.update(prog_out)
    return outs


class PlanExecution:
    """An in-flight dispatched batch: host DataFrame so far plus trailing
    fused outputs still resident on device. ``finalize`` is the single
    blocking readback."""

    __slots__ = ("_df", "_pending")

    def __init__(self, df: DataFrame, pending: List[Tuple[str, Any, Any, Any]]):
        self._df = df
        self._pending = pending

    def finalize(self) -> DataFrame:  # graftcheck: readback
        # THE designated sync point of the serving fast path — the single
        # blocking readback the pipelined batcher defers until the next
        # batch is already dispatched.
        if not self._pending:
            return self._df
        out = self._df.clone()
        sparse_parts: Dict[str, Dict[str, Any]] = {}
        for name, dtype, arr, np_dtype in self._pending:
            host = np.asarray(arr, np_dtype)
            if isinstance(dtype, tuple) and dtype and dtype[0] == SPARSE_MARK:
                # One part of a sparse-convention output: rebuild the
                # SparseVector column once all three have arrived — the
                # parts are adjacent in pending order, so insertion order
                # matches the per-stage path's add_column order.
                _mark, col, dim, part = dtype
                parts = sparse_parts.setdefault(col, {})
                parts[part] = host
                if len(parts) == 3:
                    out.add_column(
                        col,
                        DataTypes.vector(BasicType.DOUBLE),
                        rebuild_sparse_column(
                            dim, parts["values"], parts["ids"], parts["nnz"]
                        ),
                    )
                continue
            if dtype is None:  # shape-following output: infer like transform would
                dtype = (
                    DataTypes.vector(BasicType.DOUBLE)
                    if host.ndim == 2
                    else DataTypes.DOUBLE
                )
            out.add_column(name, dtype, host)
        return out
