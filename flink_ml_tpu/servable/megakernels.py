"""Hand-fused Pallas megakernels — the hottest fast-tier chains as ONE kernel.

The fast fusion tier (``fusion.mode=fast``, docs/fusion.md) merges a chain of
kernel specs into a single XLA program; for the chains the cost model marks
hottest it goes one level lower: the whole chain becomes **one Pallas kernel**
with a row-tiled grid, so every inter-stage intermediate lives its entire life
in VMEM — never written back to HBM between stages, the lever the fused
attention fold uses. The kernel body composes the SAME
``ops/kernels.py`` ``*_fn`` math the specs' ``kernel_fn``s are built from
(the kernel-spec-consistency contract), on values read once from the tile's
refs; model arrays ride along as full (untiled) operands.

Safety vocabulary: a chain is megakernel-eligible only when EVERY spec names
its body in the **megakernel-safe op set** via ``KernelSpec(fusion_op=...)``
(:data:`MEGAKERNEL_OPS`) — ops Mosaic lowers (elementwise math, row-local
reductions, matmuls). Anything else (table gathers, ``searchsorted``
bucketizers, vmapped per-dim bins) stays on the merged-XLA fast path. The
graftcheck ``fusion-tier`` rule pins the other direction: this module is the
ONLY plan-tier module that may touch Pallas, and the planner may reach it only
behind the fast tier.

Backends: on a TPU backend the kernel is compiled by Mosaic, never
interpreted. Only the CPU backend (tests) runs it under ``interpret=True`` —
the same ``pallas_call`` machinery, grid walk and body trace, executed by the
interpreter, which enforces none of Mosaic's lowering rules. Eligibility
(:func:`chain_eligible`, :func:`fits_vmem`) is therefore stated for the chip:
the op vocabulary holds only bodies Mosaic lowers, and a chain whose
operands cannot fit the scoped-VMEM limit is never built as a candidate.

Precision: megakernels are **f32-only**. The low-precision tiers
(``precision.mode=bf16|int8``, ``servable/precision.py``) apply their bf16
transport rounding at program ingest and at every stage boundary — a seam
the raw Pallas body, which composes the ``*_fn`` math directly in VMEM with
no materialized stage boundaries, simply does not have. Rather than grow an
in-kernel rounding variant (which the graftcheck cast rule would flag as an
accumulator downcast), the planner builds NO megakernel candidates for a
low-precision segment: its fast-tier chains stay merged-XLA programs, which
carry the rounding in-graph.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.experimental import pallas as pl

__all__ = [
    "MEGAKERNEL_OPS",
    "MAX_TILE_ROWS",
    "VMEM_BUDGET_BYTES",
    "build_megakernel_fn",
    "chain_eligible",
    "fits_vmem",
    "vmem_bytes",
]

#: Op ids (``KernelSpec.fusion_op``) whose kernel bodies Mosaic lowers
#: (compiled for TPU v5e under jax 0.9.0): per-element math, row-local
#: reductions (norms, softmax, argmax/argmin) and matmuls against model
#: operands. The sparse-convention bodies (``sparse_idf``,
#: ``sparse_logistic``) are NOT here: their ``coef[indices]`` table gather
#: fails Pallas TPU lowering ("Only 2D gather is supported"), so sparse
#: fast-tier chains stay merged-XLA programs.
#: docs/fusion.md documents the vocabulary next to the megakernel list.
MEGAKERNEL_OPS = frozenset(
    {
        "scale",  # scale_fn: shift + inv-std multiply
        "normalize",  # normalize_fn: row p-norm + divide
        "elementwise_product",  # elementwise_product_fn: Hadamard product
        "idf",  # idf_scale_fn: per-term scaling
        "binarize",  # binarize_fn: threshold compare
        "impute",  # impute_fn: isnan/where fill
        "logistic",  # dot + logistic_from_dots_fn head
        "kmeans",  # distance pairwise + argmin assignment
        "mlp",  # mlp_predict_fn: matmul/relu layers + softmax head
    }
)

#: Upper bound on the megakernel row tile: serving buckets (≤ max batch, a
#: power of two) run as one tile; batch chunks split into row tiles. Whether
#: a tile of this many rows FITS is :func:`fits_vmem`'s call, per chain.
MAX_TILE_ROWS = 4096

#: What one kernel's operands and intermediates may claim of Mosaic's
#: scoped-VMEM limit — 16 MiB per kernel on TPU v5e ("Scoped allocation with
#: size 23.32M and limit 16.00M exceeded scoped vmem limit"), less headroom
#: for the compiler's own scratch.
VMEM_BUDGET_BYTES = 14 << 20


def _padded_bytes(shape: Tuple[int, ...]) -> int:
    """f32 bytes of an array in VMEM's (8, 128)-tiled layout."""
    dims = list(shape) or [1]
    dims[-1] = -(-dims[-1] // 128) * 128
    if len(dims) >= 2:
        dims[-2] = -(-dims[-2] // 8) * 8
    return 4 * int(np.prod(dims))


def vmem_bytes(specs: Sequence[Any], rows: int, width: int = 0) -> int:
    """Estimated scoped-VMEM bytes of ``specs`` as one megakernel at
    ``rows``, from operand shapes alone: every model array resident whole,
    plus four row tiles as wide as the chain's widest operand (``width`` is
    the widest ingest column) — the input, the output and two live
    intermediates. Past one grid step Pallas double-buffers every operand.
    An upper bound, not a fit: against the compiler's own allocation on
    v5e it reads 33.8 MiB for scaler→MLP 256-512-512-8 at a 4096-row tile
    (23.32 MiB allocated — over the limit either way) and 18.5 MiB for MLP
    1024-2048-1024-8 at 64 rows (17.10 MiB allocated). A chain it declines
    serves as the merged XLA program."""
    shapes = [
        np.shape(arr) for spec in specs for arr in spec.model_arrays.values()
    ]
    model = sum(_padded_bytes(s) for s in shapes)
    widest = max([width] + [s[-1] for s in shapes if s])
    tile = _row_tile(rows)
    row_tile = _padded_bytes((tile, widest))
    buffers = 2 if rows > tile else 1
    return buffers * (model + 2 * row_tile) + 2 * row_tile


def fits_vmem(specs: Sequence[Any], rows: int, width: int = 0) -> bool:
    """Whether the chain's megakernel fits the scoped-VMEM budget at
    ``rows`` — the per-key half of eligibility (``FusionTier.megakernel_hot``
    asks before the cost model does)."""
    return vmem_bytes(specs, rows, width) <= VMEM_BUDGET_BYTES


def chain_eligible(specs: Sequence[Any]) -> bool:
    """Whether this spec run may lower as one megakernel: every spec's body
    is in the op vocabulary Mosaic lowers, every model operand has at least
    one axis (0-d scalars would need an SMEM path the vocabulary doesn't),
    and the model operands fit VMEM at the smallest row tile — a chain that
    can fit at no row count is never built as a candidate."""
    if not specs:
        return False
    for spec in specs:
        if getattr(spec, "fusion_op", None) not in MEGAKERNEL_OPS:
            return False
        for arr in spec.model_arrays.values():
            if np.asarray(arr).ndim == 0:
                return False
    return fits_vmem(specs, rows=8)


def _row_tile(rows: int) -> int:
    """The grid's row tile: the whole batch when it fits, else the largest
    power-of-two divisor ≤ MAX_TILE_ROWS (bucketed serving shapes and the
    default chunk rows always have one). A ragged row count with no such
    divisor (an odd final chunk) runs as a single tile — those are small by
    construction (they are a chunk remainder)."""
    if rows <= MAX_TILE_ROWS:
        return rows
    tile = MAX_TILE_ROWS
    while tile >= 128 and rows % tile:
        tile //= 2
    return tile if tile >= 128 and rows % tile == 0 else rows


def _block(shape: Tuple[int, ...], tile_rows: Optional[int]):
    """BlockSpec for one operand: row-tiled over the grid's only axis when
    ``tile_rows`` is given (batch rows lead the shape), else the full array
    replicated to every grid step (model operands)."""
    if tile_rows is None:
        return pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    block = (tile_rows,) + tuple(shape[1:])
    return pl.BlockSpec(block, lambda i: (i,) + (0,) * (len(shape) - 1))


def build_megakernel_fn(
    specs: Sequence[Any],
    models: Sequence[Dict[str, Any]],
    input_names: Sequence[str],
    interpret: bool,
) -> Callable[[Sequence[Dict[str, Any]], Dict[str, Any]], Dict[str, Any]]:
    """Compose ``specs`` into one Pallas program.

    Returns ``mega(models, cols) -> {output name: array}`` with the same
    calling convention as the planner's merged-program body, so the planner
    lowers and AOT-compiles it through the identical ``jit().lower()``
    machinery. ``models`` here is only used to freeze the operand order; the
    returned function takes the committed device buffers per call.

    The kernel: a 1-D grid over row tiles; per step, every external input
    column's tile and every model array land in VMEM refs, the chain of
    ``kernel_fn`` bodies runs on the ref VALUES (intermediates stay VMEM
    register values — never re-materialized), and each declared output's
    tile is written once.
    """
    specs = tuple(specs)
    input_names = tuple(input_names)
    model_items: List[Tuple[int, str]] = [
        (si, k) for si, m in enumerate(models) for k in sorted(m)
    ]
    # Program-level names: a sparse-convention output expands to its
    # values/ids/nnz triple (the kernel body writes the expanded names).
    out_names: List[str] = [n for spec in specs for n in spec.program_outputs]

    def chain(model_seq, cols):
        cols = dict(cols)
        outs: Dict[str, Any] = {}
        for spec, m in zip(specs, model_seq):
            o = spec.kernel_fn(m, cols)
            cols.update(o)
            outs.update(o)
        return outs

    def mega(model_seq, cols):
        rows = cols[input_names[0]].shape[0]
        tile = _row_tile(rows)
        col_vals = [cols[n] for n in input_names]
        model_vals = [model_seq[si][k] for si, k in model_items]
        out_avals = jax.eval_shape(chain, model_seq, cols)

        n_cols, n_models = len(col_vals), len(model_vals)

        def body(*refs):
            col_refs = refs[:n_cols]
            model_refs = refs[n_cols : n_cols + n_models]
            out_refs = refs[n_cols + n_models :]
            tile_cols = {n: r[...] for n, r in zip(input_names, col_refs)}
            tile_models: List[Dict[str, Any]] = [{} for _ in specs]
            for (si, k), r in zip(model_items, model_refs):
                tile_models[si][k] = r[...]
            outs = chain(tile_models, tile_cols)
            for name, ref in zip(out_names, out_refs):
                ref[...] = outs[name]

        call = pl.pallas_call(
            body,
            grid=(rows // tile,) if rows else (1,),
            in_specs=[_block(tuple(v.shape), tile) for v in col_vals]
            + [_block(tuple(v.shape), None) for v in model_vals],
            out_specs=[
                _block(tuple(out_avals[n].shape), tile) for n in out_names
            ],
            out_shape=[
                jax.ShapeDtypeStruct(out_avals[n].shape, out_avals[n].dtype)
                for n in out_names
            ],
            interpret=interpret,
            name="serving_megakernel",
        )
        results = call(*col_vals, *model_vals)
        return dict(zip(out_names, results))

    return mega
