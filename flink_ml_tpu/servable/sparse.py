"""The sparse calling convention of the compiled plans (docs/sparse.md).

Every fast path built on the chain compiler — serving buckets, batch chunks,
mesh sharding, the fusion tiers, the plan cache — moves columns as dense
device arrays with static shapes. A sparse or ragged column historically
disqualified the whole segment (``IneligibleBatch: column is sparse``); this
module is the convention that makes such columns first-class instead:

**Layout.** A sparse column ``c`` crosses a program boundary as three dense
arrays built on the padded-CSR/ELL structs of ``linalg/sparse_batch.py``:

    ``c!values [n, K] f32`` · ``c!ids [n, K] i32`` · ``c!nnz [n] i32``

with real entries compacted to each row's leading slots in sorted-unique id
order, and padding slots carrying id 0 / value 0.0 (they contribute exact
identity terms to every segment reduce — see ``ops/kernels.segment_sum``).
Host-featurized inputs (token lists, hashed feature rows) enter as raw
**entries** — the same triple (duplicates allowed, device combine pending)
plus ``c!len [n] i32``, the raw per-row element count some kernels need
(CountVectorizer's fractional minTF).

**Bucket ladder.** K is never the batch's natural max row length: it pads up
to a power-of-two **nnz cap** (``linalg.sparse_batch.ladder_cap``), mirroring
PR 2's dense serving buckets and PR 9's 8·N row quantum, so every sparse
shape compiles to ≤ 1 executable per (row bucket, nnz cap) and the serving
tier can AOT-warm the whole ladder. A batch whose rows exceed
``sparse.nnz.cap.max`` is **off-ladder** and falls back per-stage (reason-
labelled in the fallback counters).

**Precision.** Under the int8 tier (``precision.mode=int8``,
``servable/precision.py``) a published artifact's model-side ``*values``
payloads are weight-quantized at ``publish_servable`` time like any other
eligible head array — int8 values halve what a wasteful ELL nnz cap pads
(ROADMAP) while the on-disk format stays dequantized f32, so nothing in this
module changes shape or dtype. Dynamic request-side ``!values`` ingest rides
the ordinary bf16 transport contract at the program boundary; it is never
quantized on the serving path.

The planner (``servable/planner.py``) owns WHERE these arrays flow; the spec
(``servable/kernel_spec.py``) owns WHICH columns use the convention; this
module owns the names, the packing/readback discipline, and the config.
"""
from __future__ import annotations

from itertools import chain
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from flink_ml_tpu.config import Options, config
from flink_ml_tpu.linalg.sparse_batch import SparseBatch, ladder_cap, place_rows
from flink_ml_tpu.linalg.vectors import SparseVector

__all__ = [
    "OffLadderError",
    "SPARSE_MARK",
    "entries_names",
    "ids_name",
    "len_name",
    "nnz_name",
    "pack_entry_rows",
    "pack_sparse_column",
    "rebuild_sparse_column",
    "resolve_nnz_cap_max",
    "resolve_sparse_hints",
    "resolve_warm_caps",
    "sparse_names",
    "values_name",
]

#: Marker heading the DataType slot of a sparse output's readback parts:
#: ``(SPARSE_MARK, column, dim, "values" | "ids" | "nnz")`` — the plan tiers
#: rebuild the SparseVector column from the three parts instead of adding
#: them as columns.
SPARSE_MARK = "__sparse__"


def values_name(col: str) -> str:
    return f"{col}!values"


def ids_name(col: str) -> str:
    return f"{col}!ids"


def nnz_name(col: str) -> str:
    return f"{col}!nnz"


def len_name(col: str) -> str:
    return f"{col}!len"


def sparse_names(col: str) -> Tuple[str, str, str]:
    """Program-level names of a ``"sparse"``-kind column, in convention order."""
    return (values_name(col), ids_name(col), nnz_name(col))


def entries_names(col: str) -> Tuple[str, str, str, str]:
    """Program-level names of an ``"entries"``-kind (host-featurized) column."""
    return (values_name(col), ids_name(col), nnz_name(col), len_name(col))


class OffLadderError(ValueError):
    """A row's nnz exceeds ``sparse.nnz.cap.max`` — the batch cannot ride the
    compiled nnz-cap ladder and must fall back per-stage."""


def resolve_nnz_cap_max() -> int:
    """Top rung of the nnz-cap ladder (``sparse.nnz.cap.max``)."""
    return max(1, int(config.get(Options.SPARSE_NNZ_CAP_MAX)))


def resolve_warm_caps() -> Tuple[int, ...]:
    """The nnz caps serving warmup AOT-compiles per bucket:
    ``sparse.warmup.caps`` when set (comma-separated), else the full
    power-of-two ladder up to ``sparse.nnz.cap.max`` — zero post-warmup
    compiles then holds for every on-ladder batch."""
    raw = config.get(Options.SPARSE_WARMUP_CAPS)
    cap_max = resolve_nnz_cap_max()
    if raw:
        caps = sorted({ladder_cap(int(c)) for c in str(raw).split(",") if str(c).strip()})
        return tuple(c for c in caps if c <= cap_max) or (cap_max,)
    caps, c = [], 1
    while c <= cap_max:
        caps.append(c)
        c *= 2
    return tuple(caps)


def _resolve_cap(max_nnz: int, cap: Optional[int], cap_max: Optional[int], truncate: bool) -> int:
    natural = ladder_cap(max_nnz)
    if cap is not None:  # a forced rung is already a ladder int by contract
        if natural > cap and not truncate:
            raise OffLadderError(
                f"rows carry up to {max_nnz} entries > forced nnz cap {cap}"
            )
        return cap
    if cap_max is not None and natural > cap_max:
        raise OffLadderError(
            f"rows carry up to {max_nnz} entries — ladder cap {natural} exceeds "
            f"sparse.nnz.cap.max={cap_max}"
        )
    return natural


def pack_sparse_column(
    df: Any,
    col: str,
    *,
    dim: Optional[int] = None,
    cap: Optional[int] = None,
    cap_max: Optional[int] = None,
    truncate: bool = False,
) -> Tuple[Dict[str, np.ndarray], int, int, int]:
    """Pack a SparseVector column into the convention triple at a ladder cap.

    Returns ``(arrays, cap, dim, nnz_total)`` where ``arrays`` maps the three
    program names. ``cap`` forces the rung (warmup compiles each ladder rung;
    ``truncate=True`` then clips rows that exceed it — shape-only warmup,
    results discarded); otherwise the rung is ``ladder_cap(max row nnz)``,
    raising :class:`OffLadderError` above ``cap_max``."""
    raw = df.column(col)
    vecs: List[SparseVector] = [
        v if isinstance(v, SparseVector) else v.to_sparse() for v in raw
    ]
    dims = {int(v.size()) for v in vecs}
    if dim is None:
        if len(dims) != 1:
            raise ValueError(f"column {col!r} has inconsistent dims {dims}")
        (dim,) = dims
    elif dims and dims != {dim}:
        raise ValueError(f"column {col!r} dims {dims} != expected {dim}")
    max_nnz = max((len(v.indices) for v in vecs), default=0)
    use = _resolve_cap(max_nnz, cap, cap_max, truncate)
    if vecs:
        batch = SparseBatch.from_vectors(vecs, dim=dim, width=use, truncate=truncate)
        ids, values, nnz = batch.indices, batch.values, batch.nnz
    else:
        ids, values = np.zeros((0, use), np.int32), np.zeros((0, use), np.float32)
        nnz = np.zeros(0, np.int32)
    arrays = {values_name(col): values, ids_name(col): ids, nnz_name(col): nnz}
    return arrays, use, dim, int(nnz.sum())


def pack_entry_rows(
    col: str,
    rows: Sequence[Sequence[Tuple[int, float]]],
    lengths: Sequence[int],
    *,
    cap: Optional[int] = None,
    cap_max: Optional[int] = None,
    truncate: bool = False,
) -> Tuple[Dict[str, np.ndarray], int, int]:
    """Pack host-featurized raw entries (id, value pairs, duplicates allowed)
    into the ``"entries"`` quadruple at a ladder cap — the shared tail of
    every host ingest (HashingTF term hashing, CountVectorizer vocabulary
    lookup, FeatureHasher row hashing). Returns ``(arrays, cap, nnz_total)``."""
    lens = np.fromiter(map(len, rows), np.int32, len(rows))
    use = _resolve_cap(int(lens.max(initial=0)), cap, cap_max, truncate)
    entries = int(lens.sum())
    ids, values, nnz = place_rows(
        np.fromiter((e[0] for e in chain.from_iterable(rows)), np.int32, entries),
        np.fromiter((e[1] for e in chain.from_iterable(rows)), np.float32, entries),
        lens,
        use,
    )
    arrays = {
        values_name(col): values,
        ids_name(col): ids,
        nnz_name(col): nnz,
        len_name(col): np.asarray(lengths, np.int32),
    }
    return arrays, use, int(nnz.sum())


def resolve_sparse_hints(df: Optional[Any]) -> Optional[Dict[str, int]]:
    """The sparse-convention policy one plan build snapshots: ``None`` when
    ``sparse.fastpath`` is off (the planner then never asks a stage for its
    sparse spec — pre-sparse behavior), else the columns of ``df`` that
    arrive sparse, mapped to their dimension. The hints seed the planner's
    static sparseness inference (``build_segments``): columns produced by
    sparse-output specs mid-chain propagate from there without hints."""
    if not config.get(Options.SPARSE_FASTPATH):
        return None
    hints: Dict[str, int] = {}
    if df is not None:
        for name in df.get_column_names():
            if df.is_sparse(name):
                col = df.column(name)
                hints[name] = int(col[0].size())
    return hints


def rebuild_sparse_column(  # graftcheck: readback
    dim: int, values: np.ndarray, ids: np.ndarray, nnz: np.ndarray
) -> List[SparseVector]:
    """Readback: the convention triple back into a SparseVector column —
    each row's leading ``nnz`` slots, already sorted-unique by the kernels'
    compaction invariant. The inverse of :func:`pack_sparse_column`, shared
    by ``PlanExecution.finalize`` and the batch tier's buffer assembly.
    This is a designated sync boundary (the ``readback`` mark): a sparse
    output's parts materialize on the host exactly here."""
    values = np.asarray(values, np.float64)
    ids = np.asarray(ids, np.int64)
    nnz = np.asarray(nnz, np.int64)
    out: List[SparseVector] = []
    for i in range(values.shape[0]):
        k = int(nnz[i])
        out.append(SparseVector(dim, ids[i, :k], values[i, :k]))
    return out
