"""Shared model-building infrastructure for the algorithm library.

Covers what the reference spreads across ``LabeledPointWithWeight``, per-model
ModelData classes and the broadcast-the-model transform pattern (KnnModel.java:87,
LogisticRegressionModel.transform): here a fitted model holds small host/device
arrays, transform pulls a columnar batch from the DataFrame, runs one jit'd kernel,
and appends prediction columns.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

from flink_ml_tpu.api.dataframe import DataFrame
from flink_ml_tpu.api.types import BasicType, DataTypes
from flink_ml_tpu.trace import CAT_INGEST, tracer
from flink_ml_tpu.utils import read_write as rw

__all__ = ["extract_labeled_data", "ModelArraysMixin"]


def extract_labeled_data(
    df: DataFrame,
    features_col: str,
    label_col: Optional[str],
    weight_col: Optional[str],
    dtype=np.float32,
    allow_sparse: bool = False,
) -> Dict[str, np.ndarray]:
    """DataFrame → columnar {features [n,d], labels [n], weights [n]} host batch.

    The analogue of the reference's ``tEnv.toDataStream(...).map(new
    LabeledPointWithWeight(...))`` boundary (LogisticRegression.java:60-80), minus the
    per-row object: columns come out as whole arrays.

    With ``allow_sparse`` and a SparseVector column, features come out in the
    padded-CSR layout instead — ``indices``/``values`` [n, K] plus ``dim`` —
    so wide sparse training (the SparseVector.java path) never densifies.
    """
    sparse = allow_sparse and df.is_sparse(features_col)
    with tracer.phase("train.pack", CAT_INGEST, rows=df.num_rows, sparse=int(sparse)) as phase:
        if sparse:
            batch = df.sparse_batch(features_col)
            out = {
                "indices": batch.indices,
                "values": batch.values.astype(dtype, copy=False),
                "dim": batch.dim,
            }
            n = batch.n
            counts = {
                "nnz": int(batch.nnz.sum()),  # stored entries, padding left out
                "width": batch.width,
                "ragged_rows": int(np.count_nonzero(batch.nnz < batch.width)),
            }
        else:
            out = {"features": df.vectors(features_col).astype(dtype)}
            n = out["features"].shape[0]
            counts = {"nnz": int(out["features"].size)}
        if label_col:
            out["labels"] = np.asarray(df.column(label_col), dtype=dtype)
        out["weights"] = (
            df.scalars(weight_col, dtype) if weight_col else np.ones(n, dtype)
        )
        phase.set_metadata(**counts)
    return out


class ModelArraysMixin:
    """Save/load + get/set model data for models whose state is named arrays.

    Persistence layout matches the framework contract (metadata JSON +
    ``data/model_data.npz``, see utils/read_write.py); ``get_model_data`` exposes the
    same arrays as a single-row DataFrame — the reference's model-data Table.
    """

    _MODEL_ARRAY_NAMES: Tuple[str, ...] = ()

    def _model_arrays(self) -> Dict[str, np.ndarray]:
        missing = [n for n in self._MODEL_ARRAY_NAMES if getattr(self, n, None) is None]
        if missing:
            raise RuntimeError(
                f"{type(self).__name__} has no model data yet (missing {missing}); "
                "fit or set_model_data first"
            )
        return {n: np.asarray(getattr(self, n)) for n in self._MODEL_ARRAY_NAMES}

    def _set_model_arrays(self, arrays: Dict[str, np.ndarray]):
        for n in self._MODEL_ARRAY_NAMES:
            setattr(self, n, np.asarray(arrays[n]))
        return self

    # --- Model API (Model.java:38,48) ---------------------------------------
    def get_model_data(self):
        arrays = self._model_arrays()
        names = list(arrays)
        return [
            DataFrame(
                names,
                [DataTypes.vector(BasicType.DOUBLE)] * len(names),
                [[_to_row_value(arrays[n])] for n in names],
            )
        ]

    def set_model_data(self, *model_data: DataFrame):
        df = model_data[0]
        arrays = {}
        for name in self._MODEL_ARRAY_NAMES:
            col = df.column(name)
            value = col[0] if not isinstance(col, np.ndarray) else col[0]
            arrays[name] = _from_row_value(value)
        return self._set_model_arrays(arrays)

    # --- persistence ---------------------------------------------------------
    def save(self, path: str) -> None:
        rw.save_metadata(self, path)
        rw.save_model_arrays(path, self._model_arrays())

    @classmethod
    def load(cls, path: str):
        metadata = rw.load_metadata(path, rw.stage_class_name(cls))
        model = cls()
        model.load_param_map_from_json(metadata["paramMap"])
        model._set_model_arrays(rw.load_model_arrays(path))
        return model


def _to_row_value(array: np.ndarray):
    from flink_ml_tpu.linalg.vectors import DenseVector

    if array.ndim == 1:
        return DenseVector(array)
    return array  # matrices stay raw arrays inside the cell


def _from_row_value(value) -> np.ndarray:
    from flink_ml_tpu.linalg.vectors import Vector

    if isinstance(value, Vector):
        return value.to_array()
    return np.asarray(value)
