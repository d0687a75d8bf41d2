"""FusionTier — the cost-based fusion policy of the compiled plans.

PR 4/5 deliberately stopped fusion at reduction boundaries: whole-pipeline XLA
programs are not bit-stable (XLA legally fuses one stage's elementwise math
into the next stage's dot reduction and reorders the accumulation), so the
exact tier compiles one program per reduction-bearing spec and merges only
``elementwise`` runs. That preserves bit-equality with the per-stage path but
leaves the biggest single-device lever on the table: keeping intermediates
VMEM-resident across exactly such a boundary, as the fused attention fold
(``parallel/flash.py``) does. What it gains for a serving chain is not
measured on a chip (ROADMAP D2).

``fusion.mode`` names the trade:

- ``exact`` (default) — today's behavior, unchanged: per-stage programs,
  elementwise-only merges, bit-exact with the per-stage ``transform`` path.
- ``fast`` — fuse *across* reduction boundaries into single XLA programs
  (maximal ``fusable`` runs become one program each), and for the chains the
  cost model marks hottest, lower hand-fused Pallas megakernels
  (``servable/megakernels.py``) that keep every inter-stage intermediate
  VMEM-resident. Results carry a documented **ulp envelope** per chain
  (:data:`ULP_ENVELOPE`, asserted by tests/test_fusion.py) instead of
  bit-equality.

The plan choice is *cost-based*, not greedy (the SystemML fusion-plan lesson,
PAPERS.md): a chain's hotness is its arithmetic intensity per row — estimated
from the stage shapes the specs already carry (model-array sizes + the ingest
width known at compile time) — times the rows the compiled key will run at.
Only chains whose score clears ``fusion.megakernel.min.score`` pay the
megakernel lowering; everything else in fast mode rides the single merged XLA
program (Flare's whole-pipeline native compilation, PAPERS.md). The score is
monotone in both rows and widths, so the chosen plan is shape-monotone:
growing a workload never *de*-fuses it.

This module is the one place the plan tier reads the ``fusion.*`` config — the
planner itself (``servable/planner.py``) stays policy-free and takes a
resolved :class:`FusionTier`.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import numpy as np

from flink_ml_tpu.config import Options, config
from flink_ml_tpu.metrics import MLMetrics, metrics

__all__ = [
    "FUSION_EXACT",
    "FUSION_FAST",
    "ULP_ENVELOPE",
    "FusionTier",
    "chain_score",
    "fallback_recorder",
    "plan_recorder",
    "resolve_fusion_tier",
    "spec_flops_per_row",
    "ulp_diff",
]

FUSION_EXACT = "exact"
FUSION_FAST = "fast"

#: Documented fast-tier accuracy contract, in float32 ulps, per documented chain
#: (docs/fusion.md has the table with the measured values behind each bound).
#: Exact mode is bit-identical (0 ulps) by construction and is not listed.
#: Keys are the chain names the tests use; values bound the max
#: elementwise ulp distance between the fast-tier output and the exact-tier
#: output of the same chain on the same input bits, both read back as the
#: float32 the programs computed. The bounds hold for BOTH fast sub-tiers
#: (merged XLA program and Pallas megakernel) — each reassociates the same
#: per-stage sums at most once.
ULP_ENVELOPE = {
    # StandardScaler → LogisticRegression head: the scaler's elementwise math
    # fuses into the margin dot and reorders its accumulation — the widest
    # movement of the shipped chains (measured on XLA CPU: ≤ 9/20/421 ulps
    # on the probabilities at widths 8/16/256 with unit-variance data).
    # The bound is sized for SATURATED sigmoid tails: a margin error of k
    # ulps becomes ≈ k·|margin| ulps of relative movement on a p ≈ e^margin
    # tail (measured 4096 at width 128 on N(0,1) margins ≈ ±25 — differences
    # on probabilities ≤ e-20, numerically meaningless but ulp-expensive).
    # The thresholded class prediction stays identical.
    "scale_logistic": 32_768,
    # The 6-stage feature chain (scaler → normalizer → product → idf →
    # rescale → binarizer): the row-norm reduction fuses with its
    # neighbours; measured 0 ulps at widths 8/16/256 on XLA CPU (the fused
    # row norm happened to keep the exact tier's accumulation order), but
    # the order is NOT contractual — the envelope is what the fast tier
    # promises.
    "feature6": 1024,
    # StandardScaler → MLP head (256→512→512→8): three matmul reductions may
    # reassociate; softmax renormalizes, keeping probabilities tight
    # (measured 0 ulps on XLA CPU at batch 64). Sized with tail headroom
    # like scale_logistic — saturated softmax tails amplify logit error.
    "scale_mlp": 16_384,
    # Sparse IDF → logistic head (docs/sparse.md): the idf gather-scale fuses
    # with the gather-scale-segment-sum margin. The margin fold is a
    # sequential lax.scan — XLA cannot reassociate it — so the fused form
    # measured 0 ulps at dims 8/64/256 and caps 1..64 on XLA CPU; the bound
    # carries the scale_logistic tail headroom because the contract is the
    # envelope, not the measured order.
    "sparse_idf_logistic": 32_768,
}


#: Default FLOPs one entry slot pays in a sparse kernel (gather + multiply +
#: segment-add + compaction bookkeeping) — the per-nnz analogue of the dense
#: model-array estimate, override per spec via
#: ``KernelSpec(sparse_flops_per_nnz=...)``.
SPARSE_FLOPS_PER_NNZ = 8.0


def spec_flops_per_row(spec: Any, nnz_cap: int = 0) -> float:
    """Estimated FLOPs one row pays in ``spec``'s kernel, from the stage
    shapes the spec already carries. A spec may pin the estimate exactly via
    ``KernelSpec(flops_per_row=...)``; otherwise 2-D model arrays count as
    matmul operands (2·size FLOPs/row — the dominant term for model heads)
    and 1-D arrays as broadcast operands (1·size).

    Sparse specs (docs/sparse.md) are costed by what they TOUCH, not what
    they address: a gather-scale-segment-sum over a 2^18-dim coefficient
    reads ``nnz_cap`` entries per row, not 2^18 — so the per-row term is
    ``sparse_flops_per_nnz × nnz_cap``, using the compile-time **cap** (the
    padded ELL width) rather than the true nnz. The cap−nnz slack IS the
    padding-waste term: a chain packed at a wasteful cap scores hotter only
    because it genuinely computes the padding, keeping the score monotone in
    the cap exactly as it is in rows and widths (SystemML's sparsity-aware
    fusion costing, PAPERS.md)."""
    if getattr(spec, "is_sparse", False):
        declared = getattr(spec, "sparse_flops_per_nnz", None)
        per_nnz = SPARSE_FLOPS_PER_NNZ if declared is None else float(declared)
        return 8.0 + per_nnz * float(max(0, nnz_cap))
    declared = getattr(spec, "flops_per_row", None)
    if declared is not None:
        return float(declared)
    total = 8.0  # floor: every kernel pays at least a few elementwise ops
    for arr in spec.model_arrays.values():
        a = np.asarray(arr)
        total += (2.0 if a.ndim >= 2 else 1.0) * float(a.size)
    return total


def chain_score(
    specs: Sequence[Any],
    rows: int,
    width: int = 0,
    nnz_cap: int = 0,
    precision: Optional[Any] = None,
) -> float:
    """Hotness of compiling ``specs`` as one chain at ``rows``: arithmetic
    intensity per row × rows. ``width`` (the widest dense ingest column at
    compile time) adds the elementwise traffic model-array sizes cannot see —
    the per-element/stage constant covers the load/op/store of a merged
    stage and is the **bytes-moved** precision term: 4 for f32, 2 for bf16,
    1 for int8 (``PrecisionTier.bytes_per_value``; ``precision=None`` keeps
    the historical f32 constant, so f32 scores — and therefore f32 plan
    choices — never move). ``nnz_cap`` (the ELL ladder cap of a sparse
    chain's columns) feeds the sparse specs' per-entry term. Monotone in
    ``rows``, ``width``, ``nnz_cap`` and every model-array size (the
    shape-monotonicity tests pin this)."""
    traffic = 4.0 if precision is None else float(precision.bytes_per_value)
    per_row = sum(spec_flops_per_row(s, nnz_cap) for s in specs) + traffic * width * len(specs)
    return rows * per_row  # per_row is a host float: plain int × float math


class FusionTier:
    """Resolved fusion policy for one compiled plan — immutable, so a plan's
    programs and a rebuilt plan under a flipped config can never mix tiers."""

    __slots__ = ("mode", "megakernel", "min_score")

    def __init__(self, mode: str, megakernel: bool = True, min_score: float = 1e6):
        if mode not in (FUSION_EXACT, FUSION_FAST):
            raise ValueError(
                f"fusion.mode must be {FUSION_EXACT!r} or {FUSION_FAST!r}; got {mode!r}"
            )
        self.mode = mode
        self.megakernel = bool(megakernel)
        self.min_score = float(min_score)

    @property
    def fast(self) -> bool:
        return self.mode == FUSION_FAST

    @property
    def key(self) -> Tuple[str, bool, float]:
        """Cache identity of this policy — plans compiled under one key are
        stale under another (different program partitions, different
        numerics contract). The plan-cache fingerprints
        (``builder/pipeline.py``) and the serving rebuild check
        (``serving/server.py``) both compare it."""
        return (self.mode, self.megakernel, self.min_score)

    def megakernel_hot(
        self,
        specs: Sequence[Any],
        rows: int,
        width: int = 0,
        nnz_cap: int = 0,
        precision: Optional[Any] = None,
    ) -> bool:
        """Whether the cost model marks this chain hot enough for the Pallas
        megakernel lowering at ``rows`` (fast mode only; the planner also
        requires every spec to carry a megakernel-safe ``fusion_op``) AND its
        operands fit the kernel's VMEM at that row count — a chain that
        cannot fit is never chosen, so it can never fall back.
        ``precision`` feeds the bytes-moved traffic term of the score — a
        low-precision chain moves fewer bytes and clears the bar later."""
        if not (self.fast and self.megakernel):
            return False
        from flink_ml_tpu.servable.megakernels import fits_vmem

        return (
            fits_vmem(specs, rows, width)
            and chain_score(specs, rows, width, nnz_cap, precision=precision)
            >= self.min_score
        )

    def __repr__(self) -> str:
        return (
            f"FusionTier(mode={self.mode!r}, megakernel={self.megakernel}, "
            f"min_score={self.min_score:g})"
        )


def resolve_fusion_tier(mode: Optional[str] = None) -> FusionTier:
    """The fusion policy of the current config (``fusion.mode`` /
    ``fusion.megakernel`` / ``fusion.megakernel.min.score``), or of an
    explicit ``mode`` override. Raises ``ValueError`` on an unknown mode —
    a deployment typo must fail at plan build, not silently serve exact."""
    return FusionTier(
        mode if mode is not None else config.get(Options.FUSION_MODE),
        megakernel=config.get(Options.FUSION_MEGAKERNEL),
        min_score=config.get(Options.FUSION_MEGAKERNEL_MIN_SCORE),
    )


#: Program kind -> ml.fusion.plan.choice gauge value (most aggressive wins).
_PLAN_CHOICE = {"exact": 0, "fused": 1, "megakernel": 2}
_PLAN_COUNTER = {
    "exact": MLMetrics.FUSION_PROGRAMS_EXACT,
    "fused": MLMetrics.FUSION_PROGRAMS_FUSED,
    "megakernel": MLMetrics.FUSION_PROGRAMS_MEGAKERNEL,
}


def plan_recorder(scope: str):
    """The ``on_plan`` callback both plan tiers hand to
    ``planner.run_segment``: counts each compiled program under its kind
    (``ml.fusion.programs.*``) and publishes the plan-choice gauge (the kind
    of the last compiled program) plus the cost-model score behind the
    choice. The counters are the precise per-kind accounting; the gauges are
    the at-a-glance "what did the cost model just decide" view — and every
    choice lands in the flight recorder (one record per compiled program,
    at compile/warmup time, never the dispatch path)."""
    import flink_ml_tpu.telemetry as telemetry

    def on_plan(kind: str, score: float) -> None:
        metrics.counter(scope, _PLAN_COUNTER[kind])
        metrics.gauge(scope, MLMetrics.FUSION_PLAN_CHOICE, _PLAN_CHOICE[kind])
        metrics.gauge(scope, MLMetrics.FUSION_PLAN_SCORE, score)
        telemetry.emit(
            "fusion.plan", scope, {"choice": kind, "score": float(score)}
        )

    return on_plan


def fallback_recorder(scope: str):
    """The ``on_mega_fallback`` callback both plan tiers hand to
    ``planner.run_segment``: a megakernel the backend rejected at compile
    time is counted (``ml.fusion.megakernel.fallbacks``) and journaled with
    the compiler's message — the merged XLA program serves the chain, but
    never silently."""
    import flink_ml_tpu.telemetry as telemetry

    def on_mega_fallback(ops: Sequence[str], rows: int, error: BaseException) -> None:
        metrics.counter(scope, MLMetrics.FUSION_MEGAKERNEL_FALLBACKS)
        telemetry.emit(
            "fusion.megakernel.fallback",
            scope,
            {
                "ops": list(ops),
                "rows": int(rows),
                "error": f"{type(error).__name__}: {error}"[:2000],
            },
        )

    return on_mega_fallback


def ulp_diff(a, b) -> int:
    """Max elementwise ulp distance between two arrays compared as float32
    (the dtype the device programs computed; the readback's f64 widening is
    value-exact, so comparing the f32 re-cast loses nothing). NaNs must
    match positionally; ±0 compare equal. The unit the fast tier's
    :data:`ULP_ENVELOPE` contract is stated (and tested) in."""
    fa = np.asarray(a, np.float32).ravel()
    fb = np.asarray(b, np.float32).ravel()
    if fa.shape != fb.shape:
        raise ValueError(f"shape mismatch: {fa.shape} vs {fb.shape}")
    nan_a, nan_b = np.isnan(fa), np.isnan(fb)
    if not np.array_equal(nan_a, nan_b):
        return np.iinfo(np.int32).max
    ia = fa.view(np.int32).astype(np.int64)
    ib = fb.view(np.int32).astype(np.int64)
    # Fold the sign-magnitude float encoding onto a monotone integer line
    # (negatives become the negated magnitude) so the distance across ±0 is
    # 0, not 2**31.
    ia = np.where(ia >= 0, ia, -(ia & 0x7FFFFFFF))
    ib = np.where(ib >= 0, ib, -(ib & 0x7FFFFFFF))
    ok = ~nan_a
    if not ok.any():
        return 0
    return int(np.max(np.abs(ia[ok] - ib[ok])))
