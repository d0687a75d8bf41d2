"""Concrete servables.

Reference: ``flink-ml-servable-lib/.../LogisticRegressionModelServable.java:44`` —
``transform:62`` (dot + sigmoid per row), ``setModelData(InputStream):81``,
``load:89``. The reference ships exactly one servable-lib model; the pattern is
that any Model can have a runtime-free replica (SURVEY.md §2.6) — here the lib
also covers the clustering and feature-scaling families.

The L1 guarantee (enforced by graftcheck's ``layer-deps`` rule): nothing in
this module imports the training stack (``iteration/``, ``execution/``,
``builder/``, ``models/``). Numeric parity with the training-side Models comes
from sharing the exact jit'd kernels in ``ops/kernels.py`` — the same compiled
executable serves both surfaces, so results are bit-identical by construction.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from flink_ml_tpu.api.dataframe import DataFrame
from flink_ml_tpu.api.types import BasicType, DataTypes
from flink_ml_tpu.ops.kernels import (
    dot_kernel,
    kmeans_assign_fn,
    kmeans_predict_kernel,
    logistic_from_dots_fn,
    logistic_from_dots_kernel,
    mlp_predict_fn,
    mlp_predict_kernel,
    scale_fn,
    scale_kernel,
    sparse_dot_fn,
    sparse_dot_kernel,
)
from flink_ml_tpu.params.param import BoolParam
from flink_ml_tpu.params.shared import (
    HasDistanceMeasure,
    HasFeaturesCol,
    HasInputCol,
    HasK,
    HasOutputCol,
    HasPredictionCol,
    HasRawPredictionCol,
)
from flink_ml_tpu.servable.api import ModelServable
from flink_ml_tpu.servable.kernel_spec import KernelSpec
from flink_ml_tpu.servable.sparse import pack_sparse_column, sparse_names

__all__ = [
    "LogisticRegressionModelServable",
    "KMeansModelServable",
    "MLPClassifierModelServable",
    "StandardScalerModelServable",
]



class LogisticRegressionModelServable(
    ModelServable, HasFeaturesCol, HasPredictionCol, HasRawPredictionCol
):
    """Ref LogisticRegressionModelServable.java:44."""

    _MODEL_ARRAY_NAMES = ("coefficient",)

    def __init__(self):
        super().__init__()
        self.coefficient = None

    def transform(self, df: DataFrame) -> DataFrame:
        """Ref transform:62 — prediction = dot ≥ 0, rawPrediction = [1−p, p].

        Sparse features stay in the padded-CSR layout: margins come from the
        ``sparse_dot`` gather-scale-segment-sum kernel — the same body the
        fused sparse spec composes, and its sequential fold makes the margin
        bit-invariant to the nnz cap the batch packed at (docs/sparse.md) —
        so the per-stage and fused paths agree bit for bit. Dense features
        take the matmul kernel, exactly ``compute_dots``'s split."""
        if self.coefficient is None:
            raise RuntimeError("set_model_data must be called before transform")
        features_col = self.get_features_col()
        coef = jnp.asarray(np.asarray(self.coefficient), jnp.float32)
        if df.is_sparse(features_col):
            arrays, _cap, _dim, _nnz = pack_sparse_column(
                df, features_col, dim=int(coef.shape[0])
            )
            in_v, in_i, _ = sparse_names(features_col)
            dots = sparse_dot_kernel()(arrays[in_i], arrays[in_v], coef)
        else:
            X = df.vectors(features_col).astype(np.float32)
            dots = dot_kernel()(X, coef)
        pred, raw = logistic_from_dots_kernel()(dots)
        out = df.clone()
        out.add_column(self.get_prediction_col(), DataTypes.DOUBLE, np.asarray(pred, np.float64))
        out.add_column(
            self.get_raw_prediction_col(),
            DataTypes.vector(BasicType.DOUBLE),
            np.asarray(raw, np.float64),
        )
        return out

    def kernel_spec(self) -> KernelSpec:
        """Dense fast-path spec: margin matmul + logistic, the same math
        ``transform`` jits (``dot_kernel`` + ``logistic_from_dots_fn``). The
        serving plan falls back to ``transform`` per batch when the features
        column arrives sparse — ``compute_dots``'s padded-CSR branch stays the
        per-stage path."""
        if self.coefficient is None:
            raise RuntimeError("set_model_data must be called before kernel_spec")
        features_col = self.get_features_col()

        def kernel_fn(model, cols):
            pred, raw = logistic_from_dots_fn(cols[features_col] @ model["coefficient"])
            return {
                self.get_prediction_col(): pred,
                self.get_raw_prediction_col(): raw,
            }

        return KernelSpec(
            input_cols=(features_col,),
            outputs=(
                (self.get_prediction_col(), DataTypes.DOUBLE),
                (self.get_raw_prediction_col(), DataTypes.vector(BasicType.DOUBLE)),
            ),
            model_arrays={"coefficient": np.asarray(self.coefficient, np.float32)},
            kernel_fn=kernel_fn,
            fusion_op="logistic",  # dot + sigmoid head: megakernel-safe
        )

    def sparse_kernel_spec(self, known):
        """Sparse-convention head (docs/sparse.md): when the features column
        is statically known sparse, the margin is the gather-scale-segment-
        sum ``sparse_dot_fn`` — the body ``transform``'s sparse path jits —
        feeding the shared logistic head. ``segment_sum`` is a reduction:
        the spec never claims elementwise, and chains end here."""
        if self.coefficient is None:
            raise RuntimeError("set_model_data must be called before kernel_spec")
        features_col = self.get_features_col()
        dim = int(np.asarray(self.coefficient).shape[0])
        if known.get(features_col) != dim:
            return None  # dense features (or wrong dim): the dense spec serves
        in_v, in_i, _in_z = sparse_names(features_col)

        def kernel_fn(model, cols):
            pred, raw = logistic_from_dots_fn(
                sparse_dot_fn(cols[in_v], cols[in_i], model["coefficient"])
            )
            return {
                self.get_prediction_col(): pred,
                self.get_raw_prediction_col(): raw,
            }

        return KernelSpec(
            input_cols=(features_col,),
            outputs=(
                (self.get_prediction_col(), DataTypes.DOUBLE),
                (self.get_raw_prediction_col(), DataTypes.vector(BasicType.DOUBLE)),
            ),
            model_arrays={"coefficient": np.asarray(self.coefficient, np.float32)},
            kernel_fn=kernel_fn,
            input_kinds={features_col: "sparse"},
            sparse_input_dims={features_col: dim},
            fusion_op="sparse_logistic",  # table gather: merged XLA only
        )


class KMeansModelServable(
    ModelServable, HasFeaturesCol, HasPredictionCol, HasDistanceMeasure, HasK
):
    """Runtime-free KMeansModel replica — prediction = closest centroid index
    (ref KMeansModel.java predict), same ``kmeans_predict_kernel`` as the
    training-side model."""

    _MODEL_ARRAY_NAMES = ("centroids", "weights")

    def __init__(self):
        super().__init__()
        self.centroids = None  # [k, d]
        self.weights = None  # [k]

    def transform(self, df: DataFrame) -> DataFrame:
        if self.centroids is None:
            raise RuntimeError("set_model_data must be called before transform")
        X = df.vectors(self.get_features_col()).astype(np.float32)
        pred = kmeans_predict_kernel(self.get_distance_measure())(
            X, jnp.asarray(self.centroids, jnp.float32)
        )
        out = df.clone()
        out.add_column(
            self.get_prediction_col(), DataTypes.DOUBLE, np.asarray(pred, np.float64)
        )
        return out

    def kernel_spec(self) -> KernelSpec:
        """Closest-centroid assignment as a fusable spec — the same
        ``find_closest`` body ``kmeans_predict_kernel`` jits, with the
        centroids device-resident instead of re-uploaded per call."""
        if self.centroids is None:
            raise RuntimeError("set_model_data must be called before kernel_spec")
        features_col = self.get_features_col()
        assign = kmeans_assign_fn(self.get_distance_measure())

        def kernel_fn(model, cols):
            return {
                self.get_prediction_col(): assign(cols[features_col], model["centroids"])
            }

        return KernelSpec(
            input_cols=(features_col,),
            outputs=((self.get_prediction_col(), DataTypes.DOUBLE),),
            model_arrays={"centroids": np.asarray(self.centroids, np.float32)},
            kernel_fn=kernel_fn,
            fusion_op="kmeans",  # pairwise distance + argmin: megakernel-safe
        )


class MLPClassifierModelServable(
    ModelServable, HasFeaturesCol, HasPredictionCol, HasRawPredictionCol
):
    """Runtime-free MLPClassifierModel replica — the weight-resident
    throughput serving shape: relu MLP
    forward + softmax head through the same ``mlp_predict_fn`` body the
    per-stage kernel jits, with every layer's weights device-resident at
    swap/build time on the fast path instead of re-uploaded per call.

    Model data: ``W0``/``b0`` … ``W{L-1}``/``b{L-1}`` layer pairs plus the
    ``labels`` class-value table (prediction = ``labels[argmax]``, exactly the
    training-side head). Class labels are exact in float32 (class values are
    small integers), so the device-side gather of the fused path and the
    host-side gather of the per-stage path agree bit for bit.
    """

    def __init__(self):
        super().__init__()
        self.layers = None  # [(W [d_in, d_out], b [d_out]), ...]
        self.labels = None  # [classes] class values

    def _apply_model_arrays(self, arrays) -> "MLPClassifierModelServable":
        layers = []
        i = 0
        while f"W{i}" in arrays:
            layers.append(
                (
                    np.asarray(arrays[f"W{i}"], np.float32),
                    np.asarray(arrays[f"b{i}"], np.float32),
                )
            )
            i += 1
        if not layers:
            raise ValueError(
                "MLP model data must carry at least one W0/b0 layer pair; got "
                f"arrays {sorted(arrays)}"
            )
        self.layers = layers
        self.labels = np.asarray(arrays["labels"])
        return self

    def transform(self, df: DataFrame) -> DataFrame:
        if self.layers is None:
            raise RuntimeError("set_model_data must be called before transform")
        X = df.vectors(self.get_features_col()).astype(np.float32)
        pred_idx, probs = mlp_predict_kernel()(
            tuple((jnp.asarray(W), jnp.asarray(b)) for W, b in self.layers), X
        )
        pred = self.labels[np.asarray(pred_idx, np.int64)]
        out = df.clone()
        out.add_column(
            self.get_prediction_col(), DataTypes.DOUBLE, np.asarray(pred, np.float64)
        )
        out.add_column(
            self.get_raw_prediction_col(),
            DataTypes.vector(BasicType.DOUBLE),
            np.asarray(probs, np.float64),
        )
        return out

    def kernel_spec(self) -> KernelSpec:
        """Weight-resident MLP forward as a fusable spec — the same
        ``mlp_predict_fn`` body ``transform`` jits, with the label gather on
        device (exact for class-value labels, see class docstring)."""
        if self.layers is None:
            raise RuntimeError("set_model_data must be called before kernel_spec")
        features_col = self.get_features_col()
        n_layers = len(self.layers)
        model_arrays = {"labels": np.asarray(self.labels, np.float32)}
        for i, (W, b) in enumerate(self.layers):
            model_arrays[f"W{i}"] = W
            model_arrays[f"b{i}"] = b

        def kernel_fn(model, cols):
            layers = tuple(
                (model[f"W{i}"], model[f"b{i}"]) for i in range(n_layers)
            )
            pred_idx, probs = mlp_predict_fn(layers, cols[features_col])
            # labels[argmax] as a select-sum, not a gather: Mosaic lowers no
            # 1-D gather ("Only 2D gather is supported"), and the body must
            # lower inside the Pallas megakernel too. One nonzero term per
            # row, so the sum is exact for any label values.
            classes = jax.lax.broadcasted_iota(jnp.int32, probs.shape, 1)
            pred = jnp.sum(
                jnp.where(
                    classes == pred_idx.astype(jnp.int32)[:, None],
                    model["labels"][None, :],
                    0.0,
                ),
                axis=1,
            )
            return {
                self.get_prediction_col(): pred,
                self.get_raw_prediction_col(): probs,
            }

        return KernelSpec(
            input_cols=(features_col,),
            outputs=(
                (self.get_prediction_col(), DataTypes.DOUBLE),
                (self.get_raw_prediction_col(), DataTypes.vector(BasicType.DOUBLE)),
            ),
            model_arrays=model_arrays,
            kernel_fn=kernel_fn,
            fusion_op="mlp",  # matmul/relu layers + softmax head: megakernel-safe
        )


class StandardScalerModelServable(ModelServable, HasInputCol, HasOutputCol):
    """Runtime-free StandardScalerModel replica (ref
    StandardScalerModel.java:60-97), same ``scale_kernel`` as the batch and
    online training-side models."""

    # Param names match the training-side _ScalerParams so a saved
    # StandardScalerModel's metadata restores them directly.
    WITH_MEAN = BoolParam("withMean", "Whether centers the data with mean before scaling.", False)
    WITH_STD = BoolParam("withStd", "Whether scales the data with standard deviation.", True)

    _MODEL_ARRAY_NAMES = ("mean", "std")

    def __init__(self):
        super().__init__()
        self.mean = None
        self.std = None

    def get_with_mean(self) -> bool:
        return self.get(self.WITH_MEAN)

    def set_with_mean(self, value: bool):
        return self.set(self.WITH_MEAN, value)

    def get_with_std(self) -> bool:
        return self.get(self.WITH_STD)

    def set_with_std(self, value: bool):
        return self.set(self.WITH_STD, value)

    def _inv_std(self) -> np.ndarray:
        """0-std features scale to 0 (the reference's guard), never divide."""
        std = np.asarray(self.std, np.float32)
        return np.where(std == 0.0, 0.0, 1.0 / np.where(std == 0.0, 1.0, std))

    def transform(self, df: DataFrame) -> DataFrame:
        if self.mean is None:
            raise RuntimeError("set_model_data must be called before transform")
        X = df.vectors(self.get_input_col()).astype(np.float32)
        out_vals = scale_kernel(self.get_with_mean(), self.get_with_std())(
            X, np.asarray(self.mean, np.float32), self._inv_std()
        )
        out = df.clone()
        out.add_column(
            self.get_output_col(),
            DataTypes.vector(BasicType.DOUBLE),
            np.asarray(out_vals, np.float64),
        )
        return out

    def kernel_spec(self) -> KernelSpec:
        """Standardization as a fusable spec (``scale_fn``, the body of
        ``scale_kernel``); mean and the precomputed inverse std become
        device-resident model arrays."""
        if self.mean is None:
            raise RuntimeError("set_model_data must be called before kernel_spec")
        input_col = self.get_input_col()
        with_mean, with_std = self.get_with_mean(), self.get_with_std()

        def kernel_fn(model, cols):
            return {
                self.get_output_col(): scale_fn(
                    cols[input_col],
                    model["mean"],
                    model["inv_std"],
                    with_mean=with_mean,
                    with_std=with_std,
                )
            }

        return KernelSpec(
            input_cols=(input_col,),
            outputs=((self.get_output_col(), DataTypes.vector(BasicType.DOUBLE)),),
            model_arrays={
                "mean": np.asarray(self.mean, np.float32),
                "inv_std": self._inv_std(),
            },
            kernel_fn=kernel_fn,
            elementwise=True,  # shift + scale: no FP accumulation
            fusion_op="scale",  # megakernel-safe (docs/fusion.md vocabulary)
        )
