"""Shared base for the SGD-trained linear family (LogisticRegression, LinearSVC,
LinearRegression).

The reference repeats the same fit shape in three places (e.g.
``LogisticRegression.java:60-124``): map the Table to LabeledPointWithWeight, build an
initial zero coefficient, run ``SGD.optimize`` with the model-specific loss, wrap the
resulting coefficient table in the model class. This base factors that once; each
concrete estimator supplies the loss and its model class.
"""
from __future__ import annotations

from typing import Optional, Type

import numpy as np

from flink_ml_tpu.api.core import Estimator, Model
from flink_ml_tpu.models.common import ModelArraysMixin, extract_labeled_data
from flink_ml_tpu.ops.lossfunc import LossFunc
from flink_ml_tpu.ops.optimizer import SGD
from flink_ml_tpu.params.param import update_existing_params
from flink_ml_tpu.params.shared import (
    HasElasticNet,
    HasFeaturesCol,
    HasGlobalBatchSize,
    HasLabelCol,
    HasLearningRate,
    HasMaxIter,
    HasPredictionCol,
    HasReg,
    HasTol,
    HasWeightCol,
)
from flink_ml_tpu.trace import CAT_PRODUCTIVE, tracer

from flink_ml_tpu.ops.kernels import compute_dots  # canonical home: ops/kernels.py
# (re-exported here for backward compatibility — the servable tier must reach
# it without importing models/, the L1 "runtime-free" guarantee)

__all__ = ["LinearEstimatorBase", "LinearModelBase", "compute_dots"]


class LinearModelBase(ModelArraysMixin, Model, HasFeaturesCol, HasPredictionCol):
    """A fitted linear model: state is the ``coefficient`` vector."""

    _MODEL_ARRAY_NAMES = ("coefficient",)

    def __init__(self):
        super().__init__()
        self.coefficient: Optional[np.ndarray] = None


class LinearEstimatorBase(
    Estimator,
    HasFeaturesCol,
    HasLabelCol,
    HasWeightCol,
    HasPredictionCol,
    HasMaxIter,
    HasLearningRate,
    HasGlobalBatchSize,
    HasTol,
    HasReg,
    HasElasticNet,
):
    """fit = extract columns → SGD with the subclass loss → model carrying coef."""

    _LOSS: LossFunc = None
    _MODEL_CLASS: Type[LinearModelBase] = None

    def _make_optimizer(self) -> SGD:
        return SGD(
            max_iter=self.get_max_iter(),
            learning_rate=self.get_learning_rate(),
            global_batch_size=self.get_global_batch_size(),
            tol=self.get_tol(),
            reg=self.get_reg(),
            elastic_net=self.get_elastic_net(),
        )

    def fit(self, *inputs) -> LinearModelBase:
        (df,) = inputs
        with tracer.phase("train.fit", CAT_PRODUCTIVE, rows=df.num_rows) as phase:
            data = extract_labeled_data(
                df,
                self.get_features_col(),
                self.get_label_col(),
                self.get_weight_col(),
                allow_sparse=True,
            )
            self._validate_labels(data["labels"])
            dim = data.pop("dim", None) or data["features"].shape[1]
            phase.set_metadata(dim=int(dim))
            optimizer = self._make_optimizer()
            coefficient = optimizer.optimize(np.zeros(dim, np.float32), data, self._LOSS)
            # kept for callers: the per-epoch losses, and the optimizer, which
            # records the route the fit took (onehot_premat_active)
            self.loss_history = list(optimizer.loss_history)
            self.optimizer = optimizer
            model = self._MODEL_CLASS()
            update_existing_params(model, self)
            model.coefficient = np.asarray(coefficient)
        return model

    def _validate_labels(self, labels: np.ndarray) -> None:
        pass
