"""System builder ``sparse_lr_fit``: ``LogisticRegression`` through
``Estimator.fit`` on Criteo-shape sparse rows made from the seed.

The benchmark makes the inputs (ids, labels, the ``SparseVector`` column) and
holds the plain reference's inputs; everything between ``fit()`` and the
fitted coefficient is the program's.
"""
from __future__ import annotations

import gc

import numpy as np

from perfbench.references import sparse_lr_sgd

#: Host functions of the program the traced run wraps in a span of the
#: benchmark's own, one per layer boundary below ``fit()``: (module, class,
#: attribute, span name). Off in an untraced run.
LAYER_SPANS = (
    ("flink_ml_tpu.api.dataframe", "DataFrame", "sparse_batch", "fit.pack"),
    ("flink_ml_tpu.iteration.datacache", "DeviceDataCache", "__init__", "fit.device_cache"),
    ("flink_ml_tpu.linalg.onehot_sparse", "OneHotSparseLayout", "build", "fit.layout_build"),
)


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer: the hash that stands for the hashing trick."""
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def _hashed_field_ids(rng, n: int, cardinalities, alpha: float, dim: int) -> np.ndarray:
    """``[n, fields]`` sorted feature ids: in each field a value's rank is the
    floor of a bounded Pareto(``alpha``) draw on ``[1, cardinality + 1)`` - a
    few values (the missing value, the popular ones) take a large share of
    the rows and the rest form a long tail - and ``(field, rank)`` is hashed
    into ``dim`` features."""
    card = np.asarray(cardinalities, np.float64)
    e = 1.0 - alpha
    u = rng.random((n, len(card)))
    rank = np.floor((1.0 + u * ((card + 1.0) ** e - 1.0)) ** (1.0 / e))
    rank = np.minimum(rank, card).astype(np.uint64)
    field = np.arange(len(card), dtype=np.uint64)[None, :] << np.uint64(40)
    idx = (_mix64(field | rank) % np.uint64(dim)).astype(np.int64)
    idx.sort(axis=1)
    return idx


def make_rows(seed: int, n: int, dim: int, cardinalities, alpha: float, structure_seed: int = 0):
    """Click-log rows through the hashing trick: one value per field, drawn
    heavy-tailed from the field's published cardinality, hashed into ``dim``
    features; ``len(cardinalities)`` distinct sorted ids per row, value 1.0.
    Labels are the side of the median that a planted dense coefficient's
    margin falls on, so the classes are balanced.

    Every seed gives the same work in another order. The program sizes its
    one-hot layout, and so its compiled step, by how many entries of a row
    range fall into each block of 128 feature ids; rows drawn afresh per seed
    give each seed a program of its own (28 s of compilation and more) where
    a user's data set is one and its program is compiled once. So which rows
    hold which values comes from ``structure_seed``, fixed in the
    configuration, and the seed renames the ids - it permutes the blocks and
    the 128 lanes inside them, as another hash function would - and plants
    the labels."""
    base = np.random.default_rng(structure_seed)
    idx = _hashed_field_ids(base, n, cardinalities, alpha, dim)
    while True:  # the few rows in which two fields hashed to one id draw again
        dup = np.flatnonzero((np.diff(idx, axis=1) == 0).any(axis=1))
        if not len(dup):
            break
        idx[dup] = _hashed_field_ids(base, len(dup), cardinalities, alpha, dim)
    rng = np.random.default_rng(seed)
    nblk, lanes = -(-dim // 128), min(dim, 128)
    if nblk * lanes == dim:
        idx = rng.permutation(nblk)[idx // lanes] * lanes + rng.permutation(lanes)[idx % lanes]
        idx.sort(axis=1)
    planted = rng.standard_normal(dim).astype(np.float32)
    margin = planted[idx].sum(axis=1)
    y = (margin > np.median(margin)).astype(np.float64)
    return idx, y


def import_program() -> None:
    """The program's modules this system drives, imported during ``import_s``."""
    import flink_ml_tpu.api.dataframe  # noqa: F401
    import flink_ml_tpu.linalg.vectors  # noqa: F401
    import flink_ml_tpu.models.classification.logistic_regression  # noqa: F401


class SparseLrFit:
    LAYER_SPANS = LAYER_SPANS

    def __init__(self, config: dict, seed: int, n_devices: int):
        self.cfg = config
        self.n_devices = n_devices
        self.seed = seed
        self.n = int(config["num_rows"])
        self.dim = int(config["num_features"])
        self.cards = list(config["field_cardinalities"])
        self.nnz = len(self.cards)
        if self.nnz != int(config["nnz_per_row"]):
            raise ValueError(f"{self.nnz} fields, but nnz_per_row {config['nnz_per_row']}")
        self.batch = int(config["global_batch_size"])
        self.steps = int(config["max_iter"])
        self.lr = float(config["learning_rate"])
        self.idx = self.y = self.df = None
        self.layout_dims = None  # filled by the traced run's layout span

    # -- set-up ---------------------------------------------------------------
    def make_data(self) -> None:
        self.idx, self.y = make_rows(
            self.seed, self.n, self.dim, self.cards, float(self.cfg["field_zipf_alpha"]),
            int(self.cfg.get("id_structure_seed", 0)),
        )

    def build(self) -> None:
        """The ``SparseVector`` column ``Estimator.fit`` takes. One object per
        row is the program's interface (api/dataframe.py:253-264); the rows
        are sorted and distinct by construction, so the objects are filled
        directly, and the collector is held off while 10^5 of them appear."""
        from flink_ml_tpu.api.dataframe import DataFrame
        from flink_ml_tpu.linalg.vectors import SparseVector

        ones = np.ones(self.nnz)
        gc.disable()
        try:
            new = SparseVector.__new__
            vectors = []
            for row in self.idx:
                v = new(SparseVector)
                v.n, v.indices, v.values = self.dim, row, ones
                vectors.append(v)
            self.df = DataFrame.from_dict({"features": vectors, "label": self.y})
        finally:
            gc.enable()
        gc.collect()
        gc.freeze()

    def rows_per_job(self) -> int:
        return sparse_lr_sgd.rows_consumed(self.n, self.n_devices, self.batch, self.steps)

    # -- the job ----------------------------------------------------------------
    def fit(self):
        """One whole fit job; returns ``(coefficient, loss history)``."""
        from flink_ml_tpu.models.classification.logistic_regression import (
            LogisticRegression,
        )

        est = (
            LogisticRegression()
            .set_max_iter(self.steps)
            .set_global_batch_size(self.batch)
            .set_learning_rate(self.lr)
            .set_tol(0.0)
        )
        model = est.fit(self.df)
        if not est.optimizer.onehot_premat_active:
            raise RuntimeError("the fit left the one-hot premat route")
        return np.asarray(model.coefficient, np.float64), list(est.loss_history)

    def note_layout(self, span: str, result) -> None:
        """The traced run's view of the layout the program built: the shapes
        the crossing kernels' operations and bytes are computed from."""
        if span == "fit.layout_build" and result is not None:
            self.layout_dims = {
                "n_sub": int(result.n_sub), "n_flat": int(result.n_flat),
                "sub_batch": int(result.sub_batch), "row_hi": int(result.row_hi),
                "n_windows": int(result.n_windows), "n_shards": int(result.n_shards),
            }

    # -- the output check ---------------------------------------------------------
    def reference(self, precision: str = "f64"):
        ones = np.ones((self.n, self.nnz))
        return sparse_lr_sgd.reference_fit(
            self.idx, ones, self.y, self.dim, self.n_devices, self.batch,
            self.steps, self.lr, precision=precision,
        )

    @staticmethod
    def compare(got, want) -> dict:
        """The numbers the check holds to their limits: program (or control)
        ``got`` against the float64 reference ``want``, both ``(coef, losses)``."""
        coef, losses = got
        ref_coef, ref_losses = want
        n = min(len(losses), len(ref_losses))
        return {
            "coef_rel_err": float(np.max(np.abs(coef - ref_coef)) / np.max(np.abs(ref_coef))),
            "loss_rel_err": float(
                np.max(np.abs(np.asarray(losses[:n]) - ref_losses[:n]) / ref_losses[:n])
            ) if n else float("inf"),
            "steps_missing": float(len(ref_losses) - len(losses)),
        }


def create(config: dict, seed: int, n_devices: int):
    return SparseLrFit(config, seed, n_devices)
