"""Sparse (padded-CSR) training and inference.

Ref SparseVector.java + BLAS.java:30-179 sparse branches: the reference's
linear models consume SparseVector end-to-end. Here the contract under test is
(a) sparse training/inference agrees with the densified path on narrow data,
and (b) Criteo-width data (d = 2^20) trains and serves without ever
materializing an [n, d] array.
"""
import numpy as np
import pytest

from flink_ml_tpu.api.dataframe import DataFrame
from flink_ml_tpu.iteration import IterationListener
from flink_ml_tpu.linalg.sparse_batch import SparseBatch
from flink_ml_tpu.linalg.vectors import SparseVector
from flink_ml_tpu.ops import SGD, BinaryLogisticLoss, HingeLoss, LeastSquareLoss


def _to_sparse_rows(X):
    rows = []
    for r in X:
        nz = np.nonzero(r)[0]
        rows.append(SparseVector(X.shape[1], nz, r[nz]))
    return rows


def _sparse_data(n, d, nnz, seed=0):
    """Random sparse rows; labels from a sparse ground-truth coefficient."""
    rng = np.random.default_rng(seed)
    idx = np.stack([rng.choice(d, nnz, replace=False) for _ in range(n)]).astype(np.int32)
    vals = rng.standard_normal((n, nnz)).astype(np.float32)
    w_true = np.zeros(d, np.float32)
    hot = rng.choice(d, 64, replace=False)
    w_true[hot] = rng.standard_normal(64)
    dots = np.sum(vals * w_true[idx], axis=1)
    y = (dots > 0).astype(np.float32)
    return idx, vals, y


def _raw(cls, n, indices, values):
    """A row filled through ``__new__``, as the benchmark's builder fills its
    rows: whatever arrays it is handed, no sort, no cast, no copy."""
    v = cls.__new__(cls)
    v.n, v.indices, v.values = n, indices, values
    return v


class _TaggedSparseVector(SparseVector):
    __slots__ = ("tag",)


def _loop_pack(vectors, pad_to=8, width=None):
    """The row loop that ``from_vectors`` was until PR 27 (and, with a forced
    ``width`` that clips, ``pack_sparse_column``'s): the oracle."""
    max_nnz = max(1, max(len(v.indices) for v in vectors))
    K = -(-max_nnz // pad_to) * pad_to if width is None else width
    indices = np.zeros((len(vectors), K), np.int32)
    values = np.zeros((len(vectors), K), np.float32)
    nnz = np.zeros(len(vectors), np.int32)
    for i, v in enumerate(vectors):
        k = min(len(v.indices), K)
        indices[i, :k] = v.indices[:k]
        values[i, :k] = v.values[:k]
        nnz[i] = k
    return indices, values, nnz


def _col_uniform39():
    rng = np.random.default_rng(0)
    idx = np.sort(rng.integers(0, 1 << 22, (64, 39)), axis=1)
    ones = np.ones(39)  # one values object for every row, as the builder has it
    return [_raw(SparseVector, 1 << 22, row, ones) for row in idx]


def _col_ragged():
    rng = np.random.default_rng(1)
    return [
        SparseVector(500, rng.choice(500, k, replace=False), rng.standard_normal(k))
        for k in rng.integers(0, 21, 50)
    ]


def _col_no_entries():
    return [SparseVector(7, [], []) for _ in range(5)]


def _col_some_empty():
    return [SparseVector(7, [], []), SparseVector(7, [2, 4], [1.0, -1.0]), SparseVector(7, [], [])]


def _col_explicit_zeros():
    return [SparseVector(10, [3, 5], [0.0, 2.0]), SparseVector(10, [0, 1, 9], [0.0, 0.0, 0.0])]


def _col_one_row():
    return [SparseVector(12, [1, 11], [0.5, -0.25])]


def _col_straddles_pad_to():
    rng = np.random.default_rng(2)
    return [
        SparseVector(64, np.arange(k), rng.standard_normal(k)) for k in (7, 8, 9, 8, 7, 1, 9, 8)
    ]


def _col_other_dtypes():
    rng = np.random.default_rng(3)
    f64 = rng.standard_normal(6) * 1e3 + 1e-9  # rounds on the way to float32
    return [
        _raw(SparseVector, 300, np.arange(6, dtype=np.int32), f64.astype(np.float32)),
        _raw(SparseVector, 300, np.arange(6, dtype=np.uint8) * 40, f64.astype(np.float16)),
        _raw(SparseVector, 300, np.arange(6, dtype=np.int16), np.arange(6, dtype=np.int64)),
        _raw(SparseVector, 300, np.arange(6, dtype=np.float64) * 7.0, f64),
        _raw(SparseVector, 300, np.array([1, 2, (1 << 32) + 5]), np.array([True, False, True])),
        _raw(SparseVector, 300, [3, 4], [1.5, 2.5]),  # plain lists
    ]


def _col_non_contiguous():
    rng = np.random.default_rng(4)
    idx = np.sort(rng.integers(0, 1000, (6, 20)), axis=1)
    val = rng.standard_normal((20, 6))
    return [
        _raw(SparseVector, 1000, idx[i, ::2], val[::2, i]) for i in range(6)
    ] + [_raw(SparseVector, 1000, idx[0, ::-1][:5], val[::-3, 0][:5])]


def _col_mixed_dense_sparse():
    from flink_ml_tpu.linalg.vectors import DenseVector

    return [
        SparseVector(4, [0], [1.0]),
        DenseVector([0.0, 1.0, 0.0, 2.0]),
        SparseVector(4, [1, 3], [0.0, -1.0]),
        DenseVector([0.0, 0.0, 0.0, 0.0]),
    ]


def _col_subclass():
    rows = [_TaggedSparseVector(9, [1, 4], [1.0, 2.0]), SparseVector(9, [0], [3.0])]
    rows[0].tag = "kept"
    return rows


_COLUMNS = [
    _col_uniform39, _col_ragged, _col_no_entries, _col_some_empty, _col_explicit_zeros,
    _col_one_row, _col_straddles_pad_to, _col_other_dtypes, _col_non_contiguous,
    _col_mixed_dense_sparse, _col_subclass,
]


def _assert_same_bits(batch, want):
    indices, values, nnz = want
    for got, ref in ((batch.indices, indices), (batch.values, values), (batch.nnz, nnz)):
        assert got.dtype == ref.dtype and got.shape == ref.shape
        assert got.tobytes() == ref.tobytes()


class TestSparseBatch:
    def test_from_vectors_pads_and_round_trips(self):
        vecs = [
            SparseVector(10, [1, 7], [2.0, -1.0]),
            SparseVector(10, [0], [3.0]),
            SparseVector(10, [], []),
        ]
        batch = SparseBatch.from_vectors(vecs)
        assert batch.dim == 10 and batch.n == 3 and batch.width == 8  # padded to lane
        np.testing.assert_array_equal(batch.densify(), np.stack([v.to_array() for v in vecs]))
        got = batch.row(0)
        np.testing.assert_array_equal(got.indices, [1, 7])
        np.testing.assert_array_equal(got.values, [2.0, -1.0])

    def test_inconsistent_dims_rejected(self):
        with pytest.raises(ValueError, match="sizes"):
            SparseBatch.from_vectors([SparseVector(5, [0], [1.0]), SparseVector(6, [0], [1.0])])

    def test_explicit_zero_entries_round_trip(self):
        batch = SparseBatch.from_vectors([SparseVector(10, [3, 5], [0.0, 2.0])])
        got = batch.row(0)
        np.testing.assert_array_equal(got.indices, [3, 5])
        np.testing.assert_array_equal(got.values, [0.0, 2.0])

    def test_mixed_dense_sparse_column_packs(self):
        from flink_ml_tpu.linalg.vectors import DenseVector

        df = DataFrame.from_dict(
            {"features": [SparseVector(4, [0], [1.0]), DenseVector([0.0, 1.0, 0.0, 2.0])]}
        )
        assert df.is_sparse("features")
        batch = df.sparse_batch("features")
        np.testing.assert_array_equal(
            batch.densify(), [[1.0, 0, 0, 0], [0, 1.0, 0, 2.0]]
        )


    # -- PR 27: the column becomes the batch by whole-column numpy; what the
    # -- row loop gave, it gives, to the bit
    @pytest.mark.parametrize("make", _COLUMNS, ids=lambda f: f.__name__[5:])
    def test_equals_the_row_loop(self, make):
        col = make()
        rows = [v if isinstance(v, SparseVector) else v.to_sparse() for v in col]
        want = _loop_pack(rows)
        batch = DataFrame.from_dict({"f": col}).sparse_batch("f")
        _assert_same_bits(batch, want)
        assert batch.dim == rows[0].n and batch.width == want[0].shape[1]
        assert batch.width % 8 == 0 and batch.n == len(col)
        if all(isinstance(v, SparseVector) for v in col):  # straight in, too
            _assert_same_bits(SparseBatch.from_vectors(col), want)
            _assert_same_bits(SparseBatch.from_vectors(col, pad_to=5), _loop_pack(rows, 5))

    @pytest.mark.parametrize("make", [_col_ragged, _col_straddles_pad_to, _col_some_empty],
                             ids=lambda f: f.__name__[5:])
    @pytest.mark.parametrize("width", [1, 8, 32])
    def test_forced_width_clips_as_the_loop_did(self, make, width):
        col = make()
        longest = max(len(v.indices) for v in col)
        if longest > width:
            with pytest.raises(ValueError, match="forced width"):
                SparseBatch.from_vectors(col, width=width)
        batch = SparseBatch.from_vectors(col, width=width, truncate=True)
        _assert_same_bits(batch, _loop_pack(col, width=width))
        assert batch.width == width

    @pytest.mark.parametrize(
        "pack, error, match",
        [
            (lambda: SparseBatch.from_vectors([]), ValueError, "^empty batch$"),
            (
                lambda: SparseBatch.from_vectors(
                    [SparseVector(5, [0], [1.0]), SparseVector(6, [0], [1.0])]
                ),
                ValueError,
                r"^inconsistent vector sizes \{5, 6\}$",
            ),
            (
                lambda: SparseBatch.from_vectors([SparseVector(5, [0], [1.0])], dim=6),
                ValueError,
                r"^vector sizes \{5\} != requested dim 6$",
            ),
            (
                lambda: DataFrame.from_dict({"f": ["a", "b"]}).sparse_batch("f"),
                TypeError,
                "^column 'f' is not a vector column$",
            ),
            (
                lambda: DataFrame.from_dict({"f": np.zeros((3, 2))}).sparse_batch("f"),
                TypeError,
                "^column 'f' is not a vector column$",
            ),
            (
                lambda: DataFrame.from_dict(
                    {"f": [SparseVector(3, [0], [1.0]), None]}
                ).sparse_batch("f"),
                TypeError,
                "^column 'f' is not a vector column$",
            ),
        ],
        ids=["empty", "inconsistent_sizes", "dim_mismatch", "strings", "array", "none_row"],
    )
    def test_errors_keep_type_and_message(self, pack, error, match):
        with pytest.raises(error, match=match):
            pack()

    def test_pack_counts_say_which_shape_of_column(self):
        from flink_ml_tpu import trace
        from flink_ml_tpu.models.common import extract_labeled_data

        col = _col_straddles_pad_to()
        df = DataFrame.from_dict({"f": col, "y": np.zeros(len(col), np.float32)})
        with trace.capture() as recorder:
            data = extract_labeled_data(df, "f", "y", None, allow_sparse=True)
        (span,) = [s for s in recorder.snapshot() if s.name == "train.pack"]
        assert span.attrs == {
            "rows": 8, "sparse": 1, "nnz": 57, "width": 16, "ragged_rows": 8,
        }
        # columns already in the asked dtype come through without a copy
        assert data["labels"] is df.column("y")
        assert data["values"].dtype == np.float32 and data["values"].base is None

    def test_rows_filled_through_new_fit_the_same_coefficients(self):
        """Two fits of the toy sparse LR, one on rows ``SparseVector(...)``
        made (int64 / float64 copies), one on the same rows filled through
        ``__new__`` with narrower arrays and views: the same batch, hence
        the same coefficients, to the bit."""
        from flink_ml_tpu.models.classification.logistic_regression import LogisticRegression

        d = 256
        idx, vals, y = _sparse_data(n=128, d=d, nnz=7, seed=21)
        order = np.argsort(idx, axis=1)
        idx = np.take_along_axis(idx, order, axis=1)
        vals = np.take_along_axis(vals, order, axis=1)
        label = y.astype(np.float64)
        made = [SparseVector(d, r, v) for r, v in zip(idx, vals)]
        wide = np.zeros((128, 14), np.float32)
        wide[:, ::2] = vals
        filled = [_raw(SparseVector, d, r, v) for r, v in zip(idx, wide[:, ::2])]
        est = LogisticRegression().set_max_iter(12).set_global_batch_size(64).set_tol(0.0)
        a = est.fit(DataFrame.from_dict({"features": made, "label": label}))
        b = est.fit(DataFrame.from_dict({"features": filled, "label": label}))
        assert np.any(a.coefficient != 0)
        assert np.asarray(a.coefficient).tobytes() == np.asarray(b.coefficient).tobytes()


class TestLossAndMult:
    @pytest.mark.parametrize(
        "loss", [BinaryLogisticLoss.INSTANCE, HingeLoss.INSTANCE, LeastSquareLoss.INSTANCE]
    )
    def test_mult_reproduces_gradient(self, loss):
        """X.T @ mult (the dot-level primitive) must equal loss_and_grad_sum."""
        import jax.numpy as jnp

        rng = np.random.default_rng(3)
        X = rng.standard_normal((32, 6)).astype(np.float32)
        y = rng.integers(0, 2, 32).astype(np.float32)
        w = rng.uniform(0.5, 2.0, 32).astype(np.float32)
        coef = rng.standard_normal(6).astype(np.float32)
        want_loss, want_grad = loss.loss_and_grad_sum(
            jnp.asarray(coef), jnp.asarray(X), jnp.asarray(y), jnp.asarray(w)
        )
        got_loss, mult = loss.loss_and_mult(jnp.asarray(X @ coef), jnp.asarray(y), jnp.asarray(w))
        np.testing.assert_allclose(got_loss, want_loss, rtol=1e-6)
        np.testing.assert_allclose(X.T @ np.asarray(mult), np.asarray(want_grad), rtol=1e-5, atol=1e-6)


class TestSparseSGD:
    def _narrow(self, seed=0):
        rng = np.random.default_rng(seed)
        X = rng.standard_normal((96, 12)).astype(np.float32)
        X[rng.random(X.shape) < 0.6] = 0.0  # sparsify
        y = (X @ rng.standard_normal(12) > 0).astype(np.float32)
        return X, y

    @pytest.mark.parametrize("tol", [0.0, 0.3])
    def test_sparse_matches_dense_fused(self, tol):
        X, y = self._narrow()
        batch = SparseBatch.from_vectors(_to_sparse_rows(X))
        kwargs = dict(max_iter=25, global_batch_size=32, tol=tol, learning_rate=0.4,
                      reg=0.01, elastic_net=0.5)
        dense = SGD(**kwargs).optimize(
            np.zeros(12, np.float32), {"features": X, "labels": y}, BinaryLogisticLoss.INSTANCE
        )
        sparse = SGD(**kwargs).optimize(
            np.zeros(12, np.float32),
            {"indices": batch.indices, "values": batch.values, "labels": y},
            BinaryLogisticLoss.INSTANCE,
        )
        np.testing.assert_allclose(sparse, dense, rtol=1e-4, atol=1e-6)

    def test_sparse_host_loop_matches_fused(self):
        X, y = self._narrow(seed=5)
        batch = SparseBatch.from_vectors(_to_sparse_rows(X))
        cols = {"indices": batch.indices, "values": batch.values, "labels": y}
        kwargs = dict(max_iter=10, global_batch_size=32, tol=0.0, learning_rate=0.4)
        fused = SGD(**kwargs).optimize(np.zeros(12, np.float32), cols, BinaryLogisticLoss.INSTANCE)
        # A listener forces the per-epoch host loop; same math, same result.
        host = SGD(listeners=[IterationListener()], **kwargs).optimize(
            np.zeros(12, np.float32), cols, BinaryLogisticLoss.INSTANCE
        )
        np.testing.assert_allclose(host, fused, rtol=1e-5, atol=1e-6)

    def test_sparse_streamed_matches_resident(self, tmp_path):
        from flink_ml_tpu.iteration import HostDataCache

        idx, vals, y = _sparse_data(n=128, d=512, nnz=8, seed=2)
        cols = {"indices": idx, "values": vals, "labels": y}
        kwargs = dict(max_iter=13, global_batch_size=32, tol=0.0, learning_rate=0.3)
        want = SGD(**kwargs).optimize(np.zeros(512, np.float32), cols, BinaryLogisticLoss.INSTANCE)
        cache = HostDataCache(memory_budget_bytes=2000, spill_dir=str(tmp_path))
        for a in range(0, 128, 24):
            cache.append({k: v[a : a + 24] for k, v in cols.items()})
        cache.finish()
        assert any("files" in e for e in cache._log), "budget should force spill"
        got = SGD(stream_window_rows=8, **kwargs).optimize(
            np.zeros(512, np.float32), cache, BinaryLogisticLoss.INSTANCE
        )
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


class TestSparseLinearModels:
    def test_logistic_regression_sparse_end_to_end_wide(self):
        """Criteo-shaped: d = 2^20 would be ~2 GB densified at n=512; the sparse
        path trains and serves it without ever building [n, d]."""
        from flink_ml_tpu.models.classification.logistic_regression import LogisticRegression

        d = 1 << 20
        idx, vals, y = _sparse_data(n=512, d=d, nnz=8, seed=4)
        rows = [SparseVector(d, np.sort(r), v[np.argsort(r)]) for r, v in zip(idx, vals)]
        df = DataFrame.from_dict({"features": rows, "label": y.astype(np.float64)})
        est = (
            LogisticRegression()
            .set_max_iter(60)
            .set_global_batch_size(256)
            .set_learning_rate(1.0)
            .set_tol(0.0)
        )
        model = est.fit(df)
        assert model.coefficient.shape == (d,)
        out = model.transform(df)
        acc = np.mean(out.column("prediction") == y)
        assert acc > 0.8, f"sparse LR failed to learn: acc={acc}"
        raw = out.column("rawPrediction")
        assert raw.shape == (512, 2)

    def test_sparse_dense_transform_parity(self):
        """The same model must produce identical margins for a sparse column and
        its densified twin (LinearSVC + LinearRegression + LR servable)."""
        from flink_ml_tpu.models.classification.linearsvc import LinearSVCModel
        from flink_ml_tpu.models.regression.linear_regression import LinearRegressionModel

        rng = np.random.default_rng(9)
        X = rng.standard_normal((40, 16)).astype(np.float32)
        X[rng.random(X.shape) < 0.5] = 0.0
        coef = rng.standard_normal(16).astype(np.float32)
        df_dense = DataFrame.from_dict({"features": X})
        df_sparse = DataFrame.from_dict({"features": _to_sparse_rows(X)})
        assert df_sparse.is_sparse("features") and not df_dense.is_sparse("features")

        svc = LinearSVCModel()
        svc.coefficient = coef
        np.testing.assert_allclose(
            svc.transform(df_sparse).column("rawPrediction"),
            svc.transform(df_dense).column("rawPrediction"),
            rtol=1e-5,
            atol=1e-6,
        )
        lin = LinearRegressionModel()
        lin.coefficient = coef
        np.testing.assert_allclose(
            lin.transform(df_sparse).column("prediction"),
            lin.transform(df_dense).column("prediction"),
            rtol=1e-5,
            atol=1e-6,
        )

    def test_lr_sparse_fit_matches_dense_fit(self):
        from flink_ml_tpu.models.classification.logistic_regression import LogisticRegression

        rng = np.random.default_rng(11)
        X = rng.standard_normal((64, 10)).astype(np.float32)
        X[rng.random(X.shape) < 0.5] = 0.0
        y = (X @ rng.standard_normal(10) > 0).astype(np.float64)
        est = LogisticRegression().set_max_iter(15).set_global_batch_size(32).set_tol(0.0)
        dense_model = est.fit(DataFrame.from_dict({"features": X, "label": y}))
        sparse_model = est.fit(
            DataFrame.from_dict({"features": _to_sparse_rows(X), "label": y})
        )
        np.testing.assert_allclose(
            sparse_model.coefficient, dense_model.coefficient, rtol=1e-4, atol=1e-6
        )


class TestModelAxisSharding:
    """Tensor-parallel sparse SGD: coefficient sharded over the mesh's model
    axis, per-shard range-masked gather/scatter, margins psum'd over the model
    axis. Must match the replicated-coefficient result on the same data axis."""

    def _data(self, n=96, d=100, nnz=6, seed=13):
        rng = np.random.default_rng(seed)
        idx = np.stack([rng.choice(d, nnz, replace=False) for _ in range(n)]).astype(np.int32)
        vals = rng.standard_normal((n, nnz)).astype(np.float32)
        y = (np.sum(vals * rng.standard_normal(d).astype(np.float32)[idx], axis=1) > 0).astype(
            np.float32
        )
        return idx, vals, y

    @pytest.mark.parametrize("n_model", [2, 4])
    def test_tp_matches_replicated(self, n_model):
        import jax

        from flink_ml_tpu.parallel.mesh import MeshContext, mesh_context

        d = 100  # deliberately NOT divisible by n_model: exercises coef padding
        idx, vals, y = self._data(d=d)
        cols = {"indices": idx, "values": vals, "labels": y}
        kwargs = dict(max_iter=15, global_batch_size=32, tol=0.0, learning_rate=0.4,
                      reg=0.01, elastic_net=0.5)
        n_data = 8 // n_model
        devices = jax.devices()[:8]

        with mesh_context(MeshContext(devices=devices[:n_data], n_data=n_data)) as ctx:
            want = SGD(ctx=ctx, **kwargs).optimize(
                np.zeros(d, np.float32), cols, BinaryLogisticLoss.INSTANCE
            )
        with mesh_context(
            MeshContext(devices=devices, n_data=n_data, n_model=n_model)
        ) as ctx:
            got = SGD(ctx=ctx, **kwargs).optimize(
                np.zeros(d, np.float32), cols, BinaryLogisticLoss.INSTANCE
            )
        assert got.shape == (d,)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def test_tp_with_tol_early_stop(self):
        import jax

        from flink_ml_tpu.parallel.mesh import MeshContext, mesh_context

        idx, vals, y = self._data(d=64, seed=21)
        cols = {"indices": idx, "values": vals, "labels": y}
        kwargs = dict(max_iter=300, global_batch_size=96, tol=0.45, learning_rate=0.5)
        with mesh_context(MeshContext(devices=jax.devices()[:8], n_data=4, n_model=2)) as ctx:
            sgd = SGD(ctx=ctx, **kwargs)
            coef = sgd.optimize(np.zeros(64, np.float32), cols, BinaryLogisticLoss.INSTANCE)
        assert len(sgd.loss_history) < 300, "tol should stop early on the TP path"
        assert np.all(np.isfinite(coef))

    def test_tp_host_loop_matches_fused(self):
        # Listeners force the host loop; under n_model > 1 it must produce the
        # fused TP path's exact trajectory (same epoch math, same psums) —
        # the reference checkpoints/observes every training path (SGD.java:308).
        import jax

        from flink_ml_tpu.iteration import IterationListener
        from flink_ml_tpu.parallel.mesh import MeshContext, mesh_context

        idx, vals, y = self._data(d=64)
        cols = {"indices": idx, "values": vals, "labels": y}
        kwargs = dict(max_iter=12, global_batch_size=32, tol=0.0, learning_rate=0.4)
        with mesh_context(MeshContext(devices=jax.devices()[:8], n_data=4, n_model=2)) as ctx:
            fused = SGD(ctx=ctx, **kwargs).optimize(
                np.zeros(64, np.float32), cols, BinaryLogisticLoss.INSTANCE
            )
            host = SGD(ctx=ctx, listeners=[IterationListener()], **kwargs).optimize(
                np.zeros(64, np.float32), cols, BinaryLogisticLoss.INSTANCE
            )
        assert host.shape == (64,)
        np.testing.assert_allclose(host, fused, rtol=1e-5, atol=1e-7)

    def test_tp_streamed_matches_dp_streamed(self, tmp_path):
        import jax

        from flink_ml_tpu.iteration import HostDataCache
        from flink_ml_tpu.parallel.mesh import MeshContext, mesh_context

        d = 100  # not divisible by n_model: exercises streamed coef padding
        idx, vals, y = self._data(d=d, seed=31)
        cache = HostDataCache(memory_budget_bytes=2000, spill_dir=str(tmp_path))
        for a in range(0, len(y), 24):
            cache.append(
                {"indices": idx[a : a + 24], "values": vals[a : a + 24], "labels": y[a : a + 24]}
            )
        cache.finish()
        kwargs = dict(max_iter=11, global_batch_size=32, tol=0.0, learning_rate=0.3,
                      stream_window_rows=8)
        devices = jax.devices()[:8]
        with mesh_context(MeshContext(devices=devices[:4], n_data=4)) as ctx:
            want = SGD(ctx=ctx, **kwargs).optimize(
                np.zeros(d, np.float32), cache, BinaryLogisticLoss.INSTANCE
            )
        with mesh_context(MeshContext(devices=devices, n_data=4, n_model=2)) as ctx:
            got = SGD(ctx=ctx, **kwargs).optimize(
                np.zeros(d, np.float32), cache, BinaryLogisticLoss.INSTANCE
            )
        assert got.shape == (d,)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
