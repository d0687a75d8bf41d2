"""K-nearest-neighbors classifier.

Reference: ``flink-ml-lib/.../classification/knn/`` — the model IS the dataset
(features + labels + cached norms, KnnModelData); prediction broadcasts the model
(KnnModel.java:87) and for each query finds the k nearest by euclidean distance
(|a|²+|b|²−2ab with cached norm squares) and takes the majority label
(KnnModel.java:133-180). ``k`` default 5.

TPU-native: the whole query batch against the whole model is one [n,d]×[d,m]
matmul + top-k — the per-row PriorityQueue disappears into ``lax.top_k``. For
reference sets large enough that the [q, m] distance matrix would not fit
(m > _BLOCK_ROWS), a streaming variant scans the model in blocks carrying a
running top-k per query — O(q·(k + block)) memory, same results. The
majority vote is a vectorized one-hot count (ties break to the smallest
label, like the reference's sorted-unique argmax).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from flink_ml_tpu.api.core import Estimator, Model
from flink_ml_tpu.api.types import DataTypes
from flink_ml_tpu.models.common import ModelArraysMixin, extract_labeled_data
from flink_ml_tpu.params.param import IntParam, ParamValidators, update_existing_params
from flink_ml_tpu.params.shared import HasFeaturesCol, HasLabelCol, HasPredictionCol

__all__ = ["Knn", "KnnModel"]


class _KnnParams(HasFeaturesCol, HasPredictionCol):
    K = IntParam("k", "The number of nearest neighbors.", 5, ParamValidators.gt(0))

    def get_k(self) -> int:
        return self.get(self.K)

    def set_k(self, value: int):
        return self.set(self.K, value)


_BLOCK_ROWS = 8192  # reference rows per streamed block (and the switch point)


@functools.cache
def _neighbors_kernel(k: int):
    @jax.jit
    def nearest(X, model_x, model_norm2):
        d2 = jnp.sum(X * X, axis=1, keepdims=True) + model_norm2[None, :] - 2.0 * X @ model_x.T
        neg_dist, idx = jax.lax.top_k(-d2, k)
        return idx

    return nearest


@functools.cache
def _blockwise_neighbors_kernel(k: int, block: int):
    """Streaming top-k: scan the reference set block-by-block, merging each
    block's distances into a running per-query top-k — never materializes the
    [q, m] distance matrix. ``model_norm2`` must be +inf on padding rows (they
    then sort behind every real neighbor)."""

    @jax.jit
    def nearest(X, model_x, model_norm2):
        q = X.shape[0]
        n_blocks = model_x.shape[0] // block
        xnorm = jnp.sum(X * X, axis=1, keepdims=True)

        def body(carry, i):
            best_d, best_i = carry
            mx = jax.lax.dynamic_slice_in_dim(model_x, i * block, block)
            mn = jax.lax.dynamic_slice_in_dim(model_norm2, i * block, block)
            d2 = xnorm + mn[None, :] - 2.0 * X @ mx.T
            cand_d = jnp.concatenate([best_d, -d2], axis=1)
            cand_i = jnp.concatenate(
                [best_i, jnp.broadcast_to(i * block + jnp.arange(block), (q, block))],
                axis=1,
            )
            nd, pos = jax.lax.top_k(cand_d, k)
            ni = jnp.take_along_axis(cand_i, pos, axis=1)
            return (nd, ni), None

        init = (
            jnp.full((q, k), -jnp.inf, jnp.float32),
            jnp.zeros((q, k), jnp.int32),
        )
        (best_d, best_i), _ = jax.lax.scan(body, init, jnp.arange(n_blocks))
        return best_i

    return nearest


def _nearest_indices(X: np.ndarray, mx: np.ndarray, k: int) -> np.ndarray:
    norm2 = (mx * mx).sum(axis=1).astype(np.float32)
    m = mx.shape[0]
    if m <= _BLOCK_ROWS:
        return np.asarray(_neighbors_kernel(k)(X, mx, norm2))
    pad = (-m) % _BLOCK_ROWS
    if pad:
        mx = np.concatenate([mx, np.zeros((pad, mx.shape[1]), np.float32)])
        norm2 = np.concatenate([norm2, np.full(pad, np.inf, np.float32)])
    return np.asarray(_blockwise_neighbors_kernel(k, _BLOCK_ROWS)(X, mx, norm2))


class KnnModel(ModelArraysMixin, Model, _KnnParams):
    """Ref KnnModel.java."""

    _MODEL_ARRAY_NAMES = ("model_features", "model_labels")

    def __init__(self):
        super().__init__()
        self.model_features: Optional[np.ndarray] = None
        self.model_labels: Optional[np.ndarray] = None

    def transform(self, *inputs):
        (df,) = inputs
        X = df.vectors(self.get_features_col()).astype(np.float32)
        mx = np.asarray(self.model_features, np.float32)
        k = min(self.get_k(), mx.shape[0])
        idx = _nearest_indices(X, mx, k)
        neighbor_labels = self.model_labels[idx]  # [n, k]
        # Vectorized k-bounded majority vote (each row has only k candidate
        # labels, so memory stays O(n·k²) regardless of global label
        # cardinality); first argmax over the sorted row breaks ties to the
        # smallest label, matching the per-row sorted-unique argmax.
        sorted_lab = np.sort(neighbor_labels, axis=1)
        votes = (sorted_lab[:, :, None] == sorted_lab[:, None, :]).sum(axis=2)
        best = votes.argmax(axis=1)
        pred = sorted_lab[np.arange(len(X)), best].astype(np.float64)
        out = df.clone()
        out.add_column(self.get_prediction_col(), DataTypes.DOUBLE, pred)
        return out


class Knn(Estimator, _KnnParams, HasLabelCol):
    """Ref Knn.java — fit materializes the dataset as model data."""

    def fit(self, *inputs) -> KnnModel:
        (df,) = inputs
        data = extract_labeled_data(
            df, self.get_features_col(), self.get_label_col(), None, dtype=np.float64
        )
        model = KnnModel()
        update_existing_params(model, self)
        model.model_features = data["features"]
        model.model_labels = np.array(data["labels"])  # the model's own, not the column
        return model
