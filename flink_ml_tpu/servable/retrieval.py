"""Runtime-free retrieval servables — the device-resident top-K serving heads.

A published :class:`~flink_ml_tpu.retrieval.index.CandidateIndex` loads in a
serving process as one of these servables (docs/retrieval.md). Both answer the
same request shape — a per-row query column plus a per-request ``K`` riding the
``"shape"`` input kind (``servable/shapes.py``) — and produce the typed top-K
pair:

- ``<output>_rows`` — ``[n, rung]`` candidate ROW indices into the index's
  candidate axis, best-first, int64 on readback (``vector(LONG)``). Row → item
  id translation is the client's job (``retrieval/client.py``) against the
  index's ``item_ids`` array: keeping int64 item ids out of the kernels avoids
  the f32 mantissa loss a device-side translation would take.
- ``<output>_scores`` — ``[n, rung]`` f32 scores widened to f64
  (``vector(DOUBLE)``): Swing similarity (descending) or 1 − Jaccard distance
  (ascending, nearest-first).

Slots past a row's true result set carry row −1 / score ∓inf — the typed
empty-result convention; a query with no history (or sharing no LSH bucket
with any candidate) yields a fully −1 row instead of erroring.

The L1 guarantee (graftcheck's ``layer-deps`` rule): nothing
here imports the training stack — the MinHash constants the LSH head needs are
mirrored here and ``models/feature/lsh.py`` imports them FROM this module, so
the two can never drift. Parity between the fused head and the per-stage
``transform`` fallback comes from jitting the exact same ``ops/kernels.py``
bodies at the same K ladder rung.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from flink_ml_tpu.api.dataframe import DataFrame
from flink_ml_tpu.api.types import BasicType, DataTypes
from flink_ml_tpu.config import Options, config
from flink_ml_tpu.linalg.vectors import SparseVector, Vector
from flink_ml_tpu.ops.kernels import (
    lsh_topk_fn,
    lsh_topk_kernel,
    swing_topk_fn,
    swing_topk_kernel,
)
from flink_ml_tpu.params.param import IntParam, ParamValidators, StringParam
from flink_ml_tpu.params.shared import HasInputCol, HasOutputCol, WithParams
from flink_ml_tpu.servable.api import ModelServable
from flink_ml_tpu.servable.kernel_spec import KernelSpec
from flink_ml_tpu.servable.shapes import k_rung, shape_name
from flink_ml_tpu.servable.sparse import (
    entries_names,
    pack_entry_rows,
    pack_sparse_column,
    sparse_names,
)

__all__ = [
    "HASH_PRIME",
    "HasKCol",
    "index_sets",
    "LSHTopKServable",
    "SwingTopKServable",
    "minhash_lanes",
    "minhash_values",
    "resolve_lsh_prune_cap",
]

#: The MinHash affine-family modulus (ref MinHashLSHModelData.java:125) —
#: defined HERE (L1) so the serving tier never imports the training-side
#: ``models/feature/lsh.py``, which imports it back from this module.
HASH_PRIME = 2038074743


def resolve_lsh_prune_cap() -> int:
    """Static candidate count the LSH bucket-prune phase keeps for the exact
    rank phase (``retrieval.lsh.prune.cap``)."""
    return max(1, int(config.get(Options.RETRIEVAL_LSH_PRUNE_CAP)))


def minhash_values(indices: np.ndarray, coeff_a: np.ndarray, coeff_b: np.ndarray) -> np.ndarray:
    """Exact MinHash values of one non-empty index set: ``min over idx of
    ((1+idx)·a + b) mod HASH_PRIME`` per hash function, int64 host math —
    bit-identical to the reference's per-row loop. Returns ``[T·F]`` int64
    (row-major ``t·F + f``, the coefficient order)."""
    idx = np.asarray(indices, np.int64)
    h = ((1 + idx[:, None]) * coeff_a[None, :] + coeff_b[None, :]) % HASH_PRIME
    return h.min(axis=0)


def minhash_lanes(
    sets: Sequence[np.ndarray], coeff_a: np.ndarray, coeff_b: np.ndarray
) -> np.ndarray:
    """MinHash values as exact f32 wire lanes, ``[n, T·F·2]``: each int64 hash
    (< 2^31, which does NOT fit f32's 24-bit mantissa) splits into its hi/lo
    16-bit halves at lanes ``2j`` / ``2j+1`` — both < 2^16, exact in f32, so
    lane equality on device is hash equality. An empty index set hashes to the
    sentinel lane −1 on every function: it matches no candidate lane (real
    lanes are ≥ 0), the typed empty-result path."""
    a = np.asarray(coeff_a, np.int64)
    b = np.asarray(coeff_b, np.int64)
    n, width = len(sets), 2 * len(a)
    lanes = np.full((n, width), -1.0, np.float32)
    for i, idx in enumerate(sets):
        if len(idx) == 0:
            continue
        h = minhash_values(idx, a, b)
        lanes[i, 0::2] = (h >> 16).astype(np.float32)
        lanes[i, 1::2] = (h & 0xFFFF).astype(np.float32)
    return lanes


def index_sets(raw) -> List[np.ndarray]:
    """The sorted-unique nonzero index set of each row of a vector column —
    the LSH query's set view (SparseVector indices are already sorted-unique
    by construction)."""
    out: List[np.ndarray] = []
    for v in raw:
        if isinstance(v, SparseVector):
            out.append(np.asarray(v.indices, np.int64))
        else:
            arr = v.to_array() if isinstance(v, Vector) else np.asarray(v)
            out.append(np.nonzero(arr)[0].astype(np.int64))
    return out


class HasKCol(WithParams):
    K_COL = StringParam(
        "kCol",
        "Scalar column carrying each request's top-K width (the per-request "
        "output-shape convention, servable/shapes.py).",
        "k",
        ParamValidators.not_null(),
    )

    def get_k_col(self) -> str:
        return self.get(self.K_COL)

    def set_k_col(self, value: str):
        return self.set(self.K_COL, value)


class _TopKServable(ModelServable, HasOutputCol, HasKCol):
    """Shared top-K head plumbing: output column pair + batch rung resolution."""

    def output_cols(self) -> Tuple[str, str]:
        out = self.get_output_col()
        return f"{out}_rows", f"{out}_scores"

    def _batch_rung(self, df: DataFrame) -> int:
        """The K ladder rung this batch's outputs compile at — max requested K
        across the batch, on the power-of-two ladder. The per-stage path uses
        the same formula as the serving ingest (``gather_shape``) so fallback
        results land at the fused path's exact widths."""
        ks = df.scalars(self.get_k_col())
        kmax = int(np.max(ks)) if len(ks) else 1
        return k_rung(kmax)

    def _emit(self, df: DataFrame, rows, scores) -> DataFrame:
        rows_col, scores_col = self.output_cols()
        out = df.clone()
        out.add_column(
            rows_col, DataTypes.vector(BasicType.LONG), np.asarray(rows, np.int64)
        )
        out.add_column(
            scores_col, DataTypes.vector(BasicType.DOUBLE), np.asarray(scores, np.float64)
        )
        return out

    def _topk_outputs(self) -> Tuple[Tuple[str, object], ...]:
        rows_col, scores_col = self.output_cols()
        return (
            (rows_col, DataTypes.vector(BasicType.LONG)),
            (scores_col, DataTypes.vector(BasicType.DOUBLE)),
        )


class SwingTopKServable(_TopKServable):
    """The Swing full-score retrieval head: segment-reduce a sparse user
    history (weights over candidate ROWS, dim = candidate count) through the
    index's ELL neighbor table, then ``top_k`` at the K ladder rung. Built by
    ``CandidateIndex.from_swing_output`` and loaded runtime-free via
    ``load_servable`` (docs/retrieval.md)."""

    _MODEL_ARRAY_NAMES = ("item_ids", "sim_values", "sim_ids")

    HISTORY_COL = StringParam(
        "historyCol",
        "Sparse column of consumed-candidate weights over the index's "
        "candidate-row space (dim = candidate count).",
        "history",
        ParamValidators.not_null(),
    )

    def __init__(self):
        super().__init__()
        self.item_ids = None
        self.sim_values = None
        self.sim_ids = None

    def get_history_col(self) -> str:
        return self.get(self.HISTORY_COL)

    def set_history_col(self, value: str):
        return self.set(self.HISTORY_COL, value)

    @property
    def candidate_count(self) -> int:
        return int(np.asarray(self.item_ids).shape[0])

    def transform(self, df: DataFrame) -> DataFrame:
        """Per-stage reference path — jits the SAME ``swing_topk_fn`` body the
        fused head composes, at the same batch rung, so fallback and fused
        results are bit-identical (the sequential history fold makes scores
        invariant to the nnz cap the batch packed at)."""
        if self.sim_values is None:
            raise RuntimeError("set_model_data must be called before transform")
        hist = self.get_history_col()
        C = self.candidate_count
        arrays, _cap, _dim, _nnz = pack_sparse_column(df, hist, dim=C)
        in_v, in_i, in_z = sparse_names(hist)
        rung = self._batch_rung(df)
        rows, scores = swing_topk_kernel(rung)(
            arrays[in_v],
            arrays[in_i],
            arrays[in_z],
            np.asarray(self.sim_values, np.float32),
            np.asarray(self.sim_ids, np.int32),
        )
        return self._emit(df, rows, scores)

    def sparse_kernel_spec(self, known) -> Optional[KernelSpec]:
        """The fused retrieval head (docs/retrieval.md): history rides the
        sparse convention at the index's candidate dim, K rides the shape
        kind, and the program is score + ``top_k`` in one XLA graph.
        ``fusable=False`` — the ranking must stay pinned in every fusion
        tier; a ulp of fast-mode drift could reorder ties."""
        if self.sim_values is None:
            raise RuntimeError("set_model_data must be called before kernel_spec")
        hist = self.get_history_col()
        kcol = self.get_k_col()
        C = self.candidate_count
        if known.get(hist) != C:
            return None  # dense or wrong-dim history: the per-stage path owns it
        in_v, in_i, in_z = sparse_names(hist)
        kshape = shape_name(kcol)
        rows_col, scores_col = self.output_cols()
        M = int(np.asarray(self.sim_ids).shape[1])

        def kernel_fn(model, cols):
            rung = cols[kshape].shape[1]  # static: the batch's K ladder rung
            rows, scores = swing_topk_fn(
                cols[in_v], cols[in_i], cols[in_z],
                model["sim_values"], model["sim_ids"], rung,
            )
            return {rows_col: rows, scores_col: scores}

        return KernelSpec(
            input_cols=(hist, kcol),
            outputs=self._topk_outputs(),
            model_arrays={
                "sim_values": np.asarray(self.sim_values, np.float32),
                "sim_ids": np.asarray(self.sim_ids, np.int32),
            },
            kernel_fn=kernel_fn,
            input_kinds={hist: "sparse", kcol: "shape"},
            sparse_input_dims={hist: C},
            readback_dtypes={rows_col: np.int64},
            fusable=False,
            sparse_flops_per_nnz=2.0 * M,  # one scatter-add fan-out per slot
        )


class LSHTopKServable(_TopKServable, HasInputCol):
    """The two-phase MinHash LSH retrieval head: bucket-prune (count full
    hash-table agreements, keep the ``retrieval.lsh.prune.cap`` best) then
    exact 1 − Jaccard rank on the pruned set — the reference
    ``approxNearestNeighbors`` semantics as one device program. Query MinHash
    values are computed HOST-side (exact int64) and travel as hi/lo f32 lanes
    through an ``"entries"``-kind pseudo-column."""

    _MODEL_ARRAY_NAMES = (
        "item_ids", "cand_lanes", "cand_ids", "cand_nnz", "coeff_a", "coeff_b",
    )

    NUM_HASH_TABLES = IntParam(
        "numHashTables", "Number of hash tables.", 1, ParamValidators.gt_eq(1)
    )
    NUM_HASH_FUNCTIONS_PER_TABLE = IntParam(
        "numHashFunctionsPerTable",
        "Number of hash functions per hash table.",
        1,
        ParamValidators.gt_eq(1),
    )

    def __init__(self):
        super().__init__()
        self.item_ids = None
        self.cand_lanes = None
        self.cand_ids = None
        self.cand_nnz = None
        self.coeff_a = None
        self.coeff_b = None

    def get_num_hash_tables(self) -> int:
        return self.get(self.NUM_HASH_TABLES)

    def set_num_hash_tables(self, value: int):
        return self.set(self.NUM_HASH_TABLES, value)

    def get_num_hash_functions_per_table(self) -> int:
        return self.get(self.NUM_HASH_FUNCTIONS_PER_TABLE)

    def set_num_hash_functions_per_table(self, value: int):
        return self.set(self.NUM_HASH_FUNCTIONS_PER_TABLE, value)

    @property
    def candidate_count(self) -> int:
        return int(np.asarray(self.item_ids).shape[0])

    @property
    def lane_width(self) -> int:
        """Wire lanes per row: 2 per hash function (hi/lo 16-bit halves)."""
        return 2 * self.get_num_hash_tables() * self.get_num_hash_functions_per_table()

    def _hash_col(self) -> str:
        """The entries-kind pseudo-column the query lanes travel under — not a
        DataFrame column; its host ingest reads the real input column."""
        return f"{self.get_input_col()}#minhash"

    def _query_lanes(self, df: DataFrame) -> np.ndarray:
        return minhash_lanes(
            index_sets(df.column(self.get_input_col())),
            np.asarray(self.coeff_a, np.int64),
            np.asarray(self.coeff_b, np.int64),
        )

    def transform(self, df: DataFrame) -> DataFrame:
        """Per-stage reference path — same jitted two-phase body as the fused
        head, at the same batch rung."""
        if self.cand_lanes is None:
            raise RuntimeError("set_model_data must be called before transform")
        feat = self.get_input_col()
        lanes = self._query_lanes(df)
        arrays, _cap, _dim, _nnz = pack_sparse_column(df, feat)
        in_v, in_i, in_z = sparse_names(feat)
        rung = self._batch_rung(df)
        rows, dist = lsh_topk_kernel(
            self.get_num_hash_tables(), resolve_lsh_prune_cap(), rung
        )(
            lanes,
            arrays[in_i],
            arrays[in_z],
            np.asarray(self.cand_lanes, np.float32),
            np.asarray(self.cand_ids, np.int32),
            np.asarray(self.cand_nnz, np.int32),
        )
        return self._emit(df, rows, dist)

    def sparse_kernel_spec(self, known) -> Optional[KernelSpec]:
        """The fused two-phase head: the input column rides the sparse
        convention (its index sets feed the exact Jaccard phase — any dim),
        the query MinHash lanes ride an entries-kind host ingest, and K rides
        the shape kind. ``fusable=False`` — ranking stays pinned."""
        if self.cand_lanes is None:
            raise RuntimeError("set_model_data must be called before kernel_spec")
        feat = self.get_input_col()
        if feat not in known:
            return None  # dense input: the per-stage path owns it
        kcol = self.get_k_col()
        qcol = self._hash_col()
        tables = self.get_num_hash_tables()
        prune_cap = resolve_lsh_prune_cap()
        width = self.lane_width
        in_v, in_i, in_z = sparse_names(feat)
        q_v, _q_i, _q_z, _q_l = entries_names(qcol)
        kshape = shape_name(kcol)
        rows_col, scores_col = self.output_cols()

        def host_ingest(df, cap, cap_max, truncate):
            lanes = self._query_lanes(df)
            rows = [[(j, float(v)) for j, v in enumerate(r)] for r in lanes]
            return pack_entry_rows(
                qcol, rows, [width] * len(rows),
                cap=cap, cap_max=cap_max, truncate=truncate,
            )

        def kernel_fn(model, cols):
            import jax.numpy as jnp

            rung = cols[kshape].shape[1]
            lanes = cols[q_v]  # [n, cap] — lanes in slots 0..width-1
            if lanes.shape[1] < width:  # shape-only warm rung below the lane count
                lanes = jnp.pad(
                    lanes, ((0, 0), (0, width - lanes.shape[1])), constant_values=-1.0
                )
            rows, dist = lsh_topk_fn(
                lanes[:, :width], cols[in_i], cols[in_z],
                model["cand_lanes"], model["cand_ids"], model["cand_nnz"],
                tables, prune_cap, rung,
            )
            return {rows_col: rows, scores_col: dist}

        return KernelSpec(
            input_cols=(feat, qcol, kcol),
            outputs=self._topk_outputs(),
            model_arrays={
                "cand_lanes": np.asarray(self.cand_lanes, np.float32),
                "cand_ids": np.asarray(self.cand_ids, np.int32),
                "cand_nnz": np.asarray(self.cand_nnz, np.int32),
            },
            kernel_fn=kernel_fn,
            input_kinds={feat: "sparse", qcol: "entries", kcol: "shape"},
            host_ingests={qcol: host_ingest},
            readback_dtypes={rows_col: np.int64},
            fusable=False,
            sparse_flops_per_nnz=2.0 * prune_cap,  # pairwise set compare fan-out
        )
