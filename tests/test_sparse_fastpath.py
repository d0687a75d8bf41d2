"""Sparse/ragged fast path (docs/sparse.md) — the sparse calling convention:

- **bit-exact parity**: text (tokenize→hashingTF→IDF→logistic) and CTR
  (one-hot→interaction→logistic) chains run fused — serving and batch tiers —
  bit-identical to the per-stage fallback in exact mode, at the
  reduction-sensitive widths and across the nnz-cap ladder;
- **bucket ladder**: every ragged batch packs at a power-of-two nnz cap;
  ≤ 1 executable per (bucket, cap); off-ladder batches fall back per-stage,
  reason-labelled;
- **zero hot-path cost**: after warmup (which covers the configured cap
  ladder) the serving path never XLA-compiles, including across a hot swap;
- **sparse-aware fusion**: the cost model prices sparse specs by nnz cap,
  the fast tier's sparse chain serves as one merged XLA program inside the
  documented ulp envelope (its table gathers do not lower through Mosaic,
  so it is never a megakernel candidate);
- **mesh sharding**: sparse segments shard over the data axis bit-identically
  to mesh=1;
- **edge cases**: empty rows, all-padding batches, dim mismatches.
"""
import numpy as np
import pytest

from flink_ml_tpu.api.dataframe import DataFrame
from flink_ml_tpu.builder.pipeline import Pipeline, PipelineModel
from flink_ml_tpu.config import Options, config
from flink_ml_tpu.linalg.sparse_batch import ladder_cap
from flink_ml_tpu.linalg.vectors import SparseVector
from flink_ml_tpu.metrics import MLMetrics, metrics
from flink_ml_tpu.models.classification.logistic_regression import LogisticRegression
from flink_ml_tpu.models.feature.hashing_tf import HashingTF
from flink_ml_tpu.models.feature.idf import IDF, IDFModel
from flink_ml_tpu.models.feature.interaction import Interaction
from flink_ml_tpu.models.feature.one_hot_encoder import OneHotEncoder
from flink_ml_tpu.models.feature.tokenizer import Tokenizer
from flink_ml_tpu.servable.fusion import FusionTier, chain_score, ulp_diff
from flink_ml_tpu.servable.lib import LogisticRegressionModelServable
from flink_ml_tpu.servable.builder import PipelineModelServable
from flink_ml_tpu.servable.planner import IneligibleBatch
from flink_ml_tpu.servable.sharding import PlanSharding
from flink_ml_tpu.servable.sparse import (
    OffLadderError,
    pack_entry_rows,
    pack_sparse_column,
    resolve_warm_caps,
    sparse_names,
)
from flink_ml_tpu.serving.batcher import pad_to
from flink_ml_tpu.serving.plan import CompiledServingPlan

RNG = np.random.default_rng(71)
SCOPE = "ml.batch[plan]"


@pytest.fixture(autouse=True)
def _reset_sparse_config():
    yield
    for opt in (
        Options.BATCH_FASTPATH,
        Options.SPARSE_FASTPATH,
        Options.SPARSE_NNZ_CAP_MAX,
        Options.SPARSE_WARMUP_CAPS,
        Options.BATCH_CHUNK_ROWS,
    ):
        config.unset(opt)


# ---------------------------------------------------------------------------
# builders
# ---------------------------------------------------------------------------
WORDS = ["alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta", "theta"]


def _text_df(n, max_tokens=10, seed=5):
    rng = np.random.default_rng(seed)
    docs = [
        " ".join(rng.choice(WORDS, size=rng.integers(1, max_tokens + 1)))
        for _ in range(n)
    ]
    labels = rng.integers(0, 2, n).astype(np.float64)
    return DataFrame.from_dict({"text": docs, "label": labels})


def _text_model(dim=128, n=64):
    df = _text_df(n)
    pipe = Pipeline(
        [
            Tokenizer().set_input_col("text").set_output_col("tokens"),
            HashingTF().set_input_col("tokens").set_output_col("tf").set_num_features(dim),
            IDF().set_input_col("tf").set_output_col("feat"),
            LogisticRegression()
            .set_features_col("feat")
            .set_label_col("label")
            .set_prediction_col("pred")
            .set_raw_prediction_col("raw")
            .set_max_iter(3),
        ]
    )
    return pipe.fit(df), df


def _ctr_model(n=96, cats=(7, 5)):
    rng = np.random.default_rng(9)
    a = rng.integers(0, cats[0], n).astype(np.float64)
    b = rng.integers(0, cats[1], n).astype(np.float64)
    y = ((a + b) % 2).astype(np.float64)
    df = DataFrame.from_dict({"ad": a, "user": b, "label": y})
    pipe = Pipeline(
        [
            OneHotEncoder()
            .set_input_cols("ad", "user")
            .set_output_cols("ad_v", "user_v")
            .set_handle_invalid("keep")
            .set_drop_last(False),
            Interaction().set_input_cols("ad_v", "user_v").set_output_col("cross"),
            LogisticRegression()
            .set_features_col("cross")
            .set_label_col("label")
            .set_prediction_col("pred")
            .set_raw_prediction_col("raw")
            .set_max_iter(3),
        ]
    )
    return pipe.fit(df), df


def _sparse_rows(n, dim, max_nnz, seed=11):
    rng = np.random.default_rng(seed)
    rows = []
    for _ in range(n):
        k = int(rng.integers(0, max_nnz + 1))
        idx = np.sort(rng.choice(dim, size=k, replace=False))
        rows.append(SparseVector(dim, idx, rng.standard_normal(k)))
    return rows


def _assert_same_bits(arrays, col, *want):
    """The packed triple of ``col`` equals ``(values, ids, nnz)`` to the bit."""
    for name, ref in zip(sparse_names(col), want):
        got = arrays[name]
        assert got.dtype == ref.dtype and got.shape == ref.shape
        assert got.tobytes() == ref.tobytes()


def _sparse_serving_pipe(dim, seed=13):
    rng = np.random.default_rng(seed)
    idf_m = IDFModel().set_input_col("features").set_output_col("scaled")
    idf_m.idf = np.abs(rng.standard_normal(dim))
    idf_m.doc_freq = np.ones(dim)
    idf_m.num_docs = np.asarray([4])
    lr = (
        LogisticRegressionModelServable()
        .set_features_col("scaled")
        .set_prediction_col("pred")
        .set_raw_prediction_col("raw")
    )
    lr.coefficient = rng.standard_normal(dim).astype(np.float32)
    return PipelineModelServable([idf_m, lr])


def _assert_bitexact(a: DataFrame, b: DataFrame):
    assert a.get_column_names() == b.get_column_names()
    for name in a.get_column_names():
        ca, cb = a.column(name), b.column(name)
        if isinstance(ca, np.ndarray) or isinstance(cb, np.ndarray):
            ca, cb = np.asarray(ca), np.asarray(cb)
            assert ca.dtype == cb.dtype, (name, ca.dtype, cb.dtype)
            if ca.dtype.kind == "f":
                np.testing.assert_array_equal(
                    ca.view(np.int64), cb.view(np.int64), err_msg=name
                )
            else:
                np.testing.assert_array_equal(ca, cb, err_msg=name)
        else:
            for va, vb in zip(ca, cb):
                if isinstance(va, SparseVector):
                    assert isinstance(vb, SparseVector), name
                    assert va.size() == vb.size(), name
                    np.testing.assert_array_equal(va.indices, vb.indices, err_msg=name)
                    np.testing.assert_array_equal(
                        np.asarray(va.values).view(np.int64),
                        np.asarray(vb.values).view(np.int64),
                        err_msg=name,
                    )
                else:
                    assert va == vb or va is vb, name


def _transform_both(model: PipelineModel, df: DataFrame):
    config.set(Options.BATCH_FASTPATH, False)
    slow = model.transform(df)
    config.set(Options.BATCH_FASTPATH, True)
    model.invalidate_batch_plan()
    before = metrics.get(SCOPE, MLMetrics.BATCH_FUSED_ROWS, 0)
    fast = model.transform(df)
    assert metrics.get(SCOPE, MLMetrics.BATCH_FUSED_ROWS, 0) >= before + len(df)
    return slow, fast


# ---------------------------------------------------------------------------
# the nnz-cap bucket ladder
# ---------------------------------------------------------------------------
class TestLadder:
    def test_ladder_cap_rounds_to_powers_of_two(self):
        assert [ladder_cap(k) for k in (0, 1, 2, 3, 4, 5, 63, 64, 65)] == [
            1, 1, 2, 4, 4, 8, 64, 64, 128,
        ]

    def test_pack_selects_the_ladder_rung(self):
        df = DataFrame.from_dict({"f": _sparse_rows(8, 32, max_nnz=5, seed=1)})
        arrays, cap, dim, total = pack_sparse_column(df, "f")
        max_nnz = max(len(v.indices) for v in df.column("f"))
        assert cap == ladder_cap(max_nnz)
        vn, idn, zn = sparse_names("f")
        assert arrays[vn].shape == (8, cap) and arrays[idn].dtype == np.int32
        assert dim == 32 and total == sum(len(v.indices) for v in df.column("f"))

    def test_off_ladder_raises(self):
        df = DataFrame.from_dict({"f": _sparse_rows(4, 64, max_nnz=40, seed=2)})
        with pytest.raises(OffLadderError):
            pack_sparse_column(df, "f", cap_max=16)

    @pytest.mark.parametrize(
        "kwargs", [{}, {"cap": 4, "truncate": True}, {"cap": 16}, {"dim": 32, "cap_max": 8}],
        ids=["natural", "forced_clipping", "forced_wide", "dim_given"],
    )
    def test_pack_equals_the_row_loop_it_was(self, kwargs):
        """``pack_sparse_column`` now calls the one whole-column pack
        (``SparseBatch.from_vectors``); its own row loop, kept here as the
        oracle, gave these arrays to the bit."""
        rows = _sparse_rows(9, 32, max_nnz=7, seed=5)
        arrays, cap, dim, total = pack_sparse_column(
            DataFrame.from_dict({"f": rows}), "f", **kwargs
        )
        ids = np.zeros((len(rows), cap), np.int32)
        values = np.zeros((len(rows), cap), np.float32)
        nnz = np.zeros(len(rows), np.int32)
        for i, v in enumerate(rows):
            k = min(len(v.indices), cap)
            ids[i, :k], values[i, :k], nnz[i] = v.indices[:k], v.values[:k], k
        _assert_same_bits(arrays, "f", values, ids, nnz)
        assert dim == 32 and total == int(nnz.sum())
        assert cap == kwargs.get("cap", ladder_cap(max(len(v.indices) for v in rows)))

    def test_pack_errors_keep_type_and_message(self):
        df = DataFrame.from_dict({"f": _sparse_rows(4, 32, max_nnz=5, seed=6)})
        with pytest.raises(ValueError, match=r"^column 'f' dims \{32\} != expected 64$"):
            pack_sparse_column(df, "f", dim=64)
        with pytest.raises(OffLadderError, match="forced nnz cap 1$"):
            pack_sparse_column(df, "f", cap=1)
        mixed = DataFrame.from_dict({"f": [SparseVector(3, [0], [1.0]), SparseVector(4, [0], [1.0])]})
        with pytest.raises(ValueError, match="^column 'f' has inconsistent dims"):
            pack_sparse_column(mixed, "f")
        # a column of no rows packs to no rows, as it did
        arrays, cap, dim, total = pack_sparse_column(DataFrame.from_dict({"f": []}), "f", dim=32)
        assert arrays["f!ids"].shape == (0, 1) and (cap, dim, total) == (1, 32, 0)

    @pytest.mark.parametrize("kwargs", [{}, {"cap": 2, "truncate": True}],
                             ids=["natural", "forced_clipping"])
    def test_entry_rows_equal_the_entry_loop_they_were(self, kwargs):
        rng = np.random.default_rng(8)
        rows = [
            [(int(rng.integers(0, 1 << 20)), float(rng.standard_normal())) for _ in range(k)]
            for k in (3, 0, 5, 1, 5, 2)
        ]
        rows[2] = [(np.int64(7), np.float32(0.1)), (7, 2), (9, 0.0), (1, 1e-9), (0, -3.5)]
        arrays, cap, total = pack_entry_rows("t", rows, [len(r) for r in rows], **kwargs)
        ids = np.zeros((len(rows), cap), np.int32)
        values = np.zeros((len(rows), cap), np.float32)
        nnz = np.zeros(len(rows), np.int32)
        for i, row in enumerate(rows):
            nnz[i] = min(len(row), cap)
            for j in range(nnz[i]):
                ids[i, j], values[i, j] = row[j]
        _assert_same_bits(arrays, "t", values, ids, nnz)
        assert total == int(nnz.sum()) and cap == kwargs.get("cap", 8)
        assert arrays["t!len"].tolist() == [3, 0, 5, 1, 5, 2]
        empty, cap0, total0 = pack_entry_rows("t", [], [])
        assert empty["t!ids"].shape == (0, 1) and cap0 == 1 and total0 == 0

    def test_warm_caps_default_full_ladder_and_override(self):
        config.set(Options.SPARSE_NNZ_CAP_MAX, 16)
        assert resolve_warm_caps() == (1, 2, 4, 8, 16)
        config.set(Options.SPARSE_WARMUP_CAPS, "1,5,16")
        assert resolve_warm_caps() == (1, 8, 16)  # 5 rounds up to its rung

    def test_serving_keys_are_bucket_cap_pairs(self):
        dim = 32
        pipe = _sparse_serving_pipe(dim)
        config.set(Options.SPARSE_WARMUP_CAPS, "1,4")
        plan = CompiledServingPlan.build(pipe, scope="t-keys", sparse={"features": dim})
        template = DataFrame.from_dict({"features": _sparse_rows(1, dim, 3, seed=3)})
        plan.warmup(template, (4, 8))
        seg = plan.segments[0]
        assert set(seg.compiled) == {(4, 1), (4, 4), (8, 1), (8, 4)}


# ---------------------------------------------------------------------------
# fused-vs-per-stage parity — batch tier
# ---------------------------------------------------------------------------
class TestBatchParity:
    @pytest.mark.parametrize("dim", [8, 16, 256])
    def test_text_pipeline_bitexact(self, dim):
        model, df = _text_model(dim=dim)
        slow, fast = _transform_both(model, df)
        _assert_bitexact(slow, fast)

    @pytest.mark.parametrize("max_nnz,cap", [(1, 1), (4, 4), (33, 64)])
    def test_nnz_cap_sweep_bitexact(self, max_nnz, cap):
        """Margins are bit-invariant to the packed cap (the sequential
        segment-sum fold), so every rung of the ladder gives per-stage bits."""
        model, _ = _text_model(dim=64)
        df = _text_df(48, max_tokens=max_nnz, seed=max_nnz)
        slow, fast = _transform_both(model, df)
        _assert_bitexact(slow, fast)
        plan = model._batch_plan(df)
        seg = next(s for s in plan.segments if hasattr(s, "compiled"))
        caps = {
            shape[1]
            for key in seg.compiled
            for name, shape, _dt in key
            if isinstance(name, str) and name.endswith("!ids")
        }
        assert caps == {ladder_cap(max_nnz)} == {cap} or max_nnz == 33

    def test_ctr_pipeline_bitexact_and_fully_fused(self):
        model, df = _ctr_model()
        before = metrics.get(SCOPE, MLMetrics.BATCH_FALLBACK_SEGMENTS, 0)
        slow, fast = _transform_both(model, df)
        _assert_bitexact(slow, fast)
        assert metrics.get(SCOPE, MLMetrics.BATCH_FALLBACK_SEGMENTS, 0) == before
        assert metrics.get(SCOPE, MLMetrics.BATCH_FUSED_STAGES, 0) == 3

    def test_chunked_sparse_ingest(self):
        model, _ = _text_model(dim=64)
        df = _text_df(130, seed=17)
        config.set(Options.BATCH_CHUNK_ROWS, 32)  # 4 full chunks + remainder
        slow, fast = _transform_both(model, df)
        _assert_bitexact(slow, fast)

    def test_mixed_dense_sparse_chain_partitions(self):
        """A chain holding dense and sparse specs partitions into programs
        without merging a sparse reduction into an elementwise run."""
        model, df = _text_model(dim=32)
        plan = model._batch_plan(df)
        seg = next(s for s in plan.segments if hasattr(s, "programs"))
        # hashingTF (combine: reduction) | idf (elementwise) | head (reduction)
        assert len(seg.programs) == 3
        kinds = [
            [getattr(s, "elementwise", False) for s in prog.specs]
            for prog in seg.programs
        ]
        assert kinds == [[False], [True], [False]]

    def test_off_ladder_falls_back_reason_labelled(self):
        model, _ = _text_model(dim=64)
        df = _text_df(16, max_tokens=30, seed=19)
        config.set(Options.SPARSE_NNZ_CAP_MAX, 8)
        reason = MLMetrics.fallback_reason("batch", "off_ladder")
        before = metrics.get(SCOPE, reason, 0)
        config.set(Options.BATCH_FASTPATH, False)
        slow = model.transform(df)
        config.set(Options.BATCH_FASTPATH, True)
        model.invalidate_batch_plan()
        fast = model.transform(df)
        _assert_bitexact(slow, fast)
        assert metrics.get(SCOPE, reason, 0) == before + 1

    def test_sparse_fastpath_off_restores_per_stage(self):
        """With sparse.fastpath off the convention disappears: the hashing
        and head stages fall back (no dense specs), IDF's dense-only segment
        meets the sparse column and takes the counted sparse fallback —
        exactly the pre-sparse contract, bit-exactly."""
        model, df = _text_model(dim=32)
        config.set(Options.SPARSE_FASTPATH, False)
        config.set(Options.BATCH_FASTPATH, True)
        model.invalidate_batch_plan()
        plan = model._batch_plan(df)
        assert plan is None or not any(
            getattr(s, "has_sparse_inputs", False) for s in plan.segments
        )
        reason = MLMetrics.fallback_reason("batch", "sparse")
        before = metrics.get(SCOPE, reason, 0)
        out = model.transform(df)
        assert metrics.get(SCOPE, reason, 0) >= before + 1
        config.set(Options.BATCH_FASTPATH, False)
        _assert_bitexact(model.transform(df), out)

    def test_empty_rows_and_all_padding(self):
        """Rows with zero tokens (and a batch where EVERY row is empty) ride
        the fused path: cap floor 1, nnz 0, padding contributes identity."""
        model, _ = _text_model(dim=32)
        docs = ["", "alpha beta", ""]
        df = DataFrame.from_dict({"text": docs})
        slow, fast = _transform_both(model, df)
        _assert_bitexact(slow, fast)
        df_all_empty = DataFrame.from_dict({"text": ["", "", "", ""]})
        slow2, fast2 = _transform_both(model, df_all_empty)
        _assert_bitexact(slow2, fast2)
        for v in fast2.column("tf"):
            assert len(v.indices) == 0


# ---------------------------------------------------------------------------
# serving tier: warmup ladder, zero compiles, hot swap, fallback reasons
# ---------------------------------------------------------------------------
class TestServingSparse:
    def test_dispatch_matches_warmed_key_zero_compiles(self, monkeypatch):
        dim = 32
        pipe = _sparse_serving_pipe(dim)
        ref = _sparse_serving_pipe(dim)
        config.set(Options.SPARSE_NNZ_CAP_MAX, 8)
        plan = CompiledServingPlan.build(pipe, scope="t-zc", sparse={"features": dim})
        template = DataFrame.from_dict({"features": _sparse_rows(1, dim, 3, seed=23)})
        plan.warmup(template, (8,))
        import flink_ml_tpu.servable.planner as planner_mod

        def poisoned(lowered):
            raise AssertionError("compile after warmup")

        monkeypatch.setattr(planner_mod, "_compile_lowered", poisoned)
        for max_nnz in (1, 2, 5, 8):
            df = DataFrame.from_dict(
                {"features": _sparse_rows(8, dim, max_nnz, seed=max_nnz)}
            )
            out = plan.execute(pad_to(df, 8))
            expected = ref.transform(pad_to(df, 8))
            _assert_bitexact(
                out.select(["pred", "raw"]), expected.select(["pred", "raw"])
            )

    def test_zero_compiles_across_hot_swap(self, monkeypatch):
        """A swapped-in version warms its own sparse ladder before the flip;
        traffic on every rung then never compiles."""
        from flink_ml_tpu.serving import InferenceServer, ServingConfig

        dim = 24
        config.set(Options.SPARSE_WARMUP_CAPS, "1,4")
        config.set(Options.SPARSE_NNZ_CAP_MAX, 4)
        v1, v2 = _sparse_serving_pipe(dim, seed=1), _sparse_serving_pipe(dim, seed=2)
        template = DataFrame.from_dict({"features": _sparse_rows(1, dim, 2, seed=3)})
        cfg = ServingConfig(max_batch_size=8, max_delay_ms=0.0)
        with InferenceServer(
            v1, name="t-sparse-swap", serving_config=cfg, warmup_template=template
        ) as server:
            df = DataFrame.from_dict({"features": _sparse_rows(5, dim, 4, seed=4)})
            server.predict(df)
            server.swap(2, v2)
            compiles_before = metrics.get(
                "ml.serving[t-sparse-swap]", MLMetrics.SERVING_FASTPATH_COMPILES, 0
            )
            resp = server.predict(df)
            assert resp.model_version == 2
            assert (
                metrics.get(
                    "ml.serving[t-sparse-swap]", MLMetrics.SERVING_FASTPATH_COMPILES, 0
                )
                == compiles_before
            )
            expected = v2.transform(pad_to(df, resp.bucket)).take(list(range(5)))
            _assert_bitexact(
                resp.dataframe.select(["pred", "raw"]),
                expected.select(["pred", "raw"]),
            )

    def test_dense_template_sparse_traffic_falls_back_reason_labelled(self):
        from flink_ml_tpu.serving import InferenceServer, ServingConfig

        dim = 16
        lr = (
            LogisticRegressionModelServable()
            .set_features_col("features")
            .set_prediction_col("pred")
            .set_raw_prediction_col("raw")
        )
        lr.coefficient = np.random.default_rng(0).normal(size=dim)
        dense_template = DataFrame.from_dict(
            {"features": np.zeros((1, dim), np.float64)}
        )
        cfg = ServingConfig(max_batch_size=4, max_delay_ms=0.0)
        with InferenceServer(
            lr, name="t-sparse-fb", serving_config=cfg, warmup_template=dense_template
        ) as server:
            scope = "ml.serving[t-sparse-fb]"
            reason = MLMetrics.fallback_reason("serving", "sparse")
            before = metrics.get(scope, reason, 0)
            rows = _sparse_rows(2, dim, 3, seed=7)
            resp = server.predict(DataFrame.from_dict({"features": rows}))
            assert metrics.get(scope, reason, 0) == before + 1
            ref = (
                lr.transform(pad_to(DataFrame.from_dict({"features": rows}), resp.bucket))
                .take([0, 1])
            )
            _assert_bitexact(
                resp.dataframe.select(["pred", "raw"]), ref.select(["pred", "raw"])
            )

    def test_sparse_template_serves_fused(self):
        """PR 4's 'sparse always falls back' contract is retired: a sparse
        template builds sparse-convention segments and traffic rides them."""
        from flink_ml_tpu.serving import InferenceServer, ServingConfig

        dim = 16
        config.set(Options.SPARSE_WARMUP_CAPS, "4")
        pipe = _sparse_serving_pipe(dim)
        template = DataFrame.from_dict({"features": _sparse_rows(1, dim, 3, seed=2)})
        cfg = ServingConfig(max_batch_size=4, max_delay_ms=0.0)
        with InferenceServer(
            pipe, name="t-sparse-fused", serving_config=cfg, warmup_template=template
        ) as server:
            scope = "ml.serving[t-sparse-fused]"
            fused_before = metrics.get(scope, MLMetrics.SERVING_FUSED_BATCHES, 0)
            server.predict(DataFrame.from_dict({"features": _sparse_rows(3, dim, 4, seed=5)}))
            assert metrics.get(scope, MLMetrics.SERVING_FUSED_BATCHES, 0) == fused_before + 1


# ---------------------------------------------------------------------------
# sparse-aware fusion: cost model, fast tier
# ---------------------------------------------------------------------------
class TestSparseFusion:
    def test_cost_model_prices_by_cap_not_dim(self):
        dim = 1 << 18
        pipe = _sparse_serving_pipe(64)
        spec = pipe.servables[1].sparse_kernel_spec({"scaled": 64})
        assert spec is not None and spec.is_sparse
        lo = chain_score([spec], rows=64, nnz_cap=4)
        hi = chain_score([spec], rows=64, nnz_cap=64)
        assert 0 < lo < hi  # monotone in the cap (the padding-waste term)
        # a dense spec of the same model would be priced by the coef size
        dense = pipe.servables[1].kernel_spec()
        assert chain_score([dense], rows=64) > lo

    def test_fast_tier_stays_merged_xla_inside_envelope(self):
        """The sparse bodies gather from the coefficient table, which Mosaic
        does not lower ("Only 2D gather is supported") — so a sparse chain is
        never a megakernel candidate, even forced hot, and the fast tier
        serves it as the merged XLA program."""
        dim = 64
        pipe = _sparse_serving_pipe(dim)
        hints = {"features": dim}
        df = DataFrame.from_dict({"features": _sparse_rows(16, dim, 5, seed=6)})
        exact = CompiledServingPlan.build(pipe, scope="t-sx", sparse=hints)
        out_exact = exact.execute(pad_to(df, 16))
        fast = CompiledServingPlan.build(
            pipe,
            scope="t-sf",
            fusion=FusionTier("fast", megakernel=True, min_score=0.0),
            sparse=hints,
        )
        seg = fast.segments[0]
        assert not seg.mega, "a sparse chain must not be a megakernel candidate"
        out_fast = fast.execute(pad_to(df, 16))
        key = next(iter(seg.compiled))
        assert seg.plan_label(key) == "fast"
        assert metrics.get("t-sf", MLMetrics.FUSION_MEGAKERNEL_FALLBACKS, 0) == 0
        from flink_ml_tpu.servable.fusion import ULP_ENVELOPE

        assert (
            ulp_diff(np.asarray(out_fast.column("raw")), np.asarray(out_exact.column("raw")))
            <= ULP_ENVELOPE["sparse_idf_logistic"]
        )
        assert np.array_equal(
            np.asarray(out_fast.column("pred")), np.asarray(out_exact.column("pred"))
        )


# ---------------------------------------------------------------------------
# mesh sharding
# ---------------------------------------------------------------------------
class TestShardedSparse:
    @pytest.mark.parametrize("mesh", [2, 4])
    def test_sharded_parity_bitexact(self, mesh):
        dim = 48
        pipe = _sparse_serving_pipe(dim)
        hints = {"features": dim}
        rows = mesh * 16
        df = DataFrame.from_dict({"features": _sparse_rows(rows, dim, 6, seed=mesh)})
        single = CompiledServingPlan.build(pipe, scope=f"t-sh1-{mesh}", sparse=hints)
        sharded = CompiledServingPlan.build(
            pipe, scope=f"t-shN-{mesh}", sharding=PlanSharding(mesh), sparse=hints
        )
        out1 = single.execute(pad_to(df, rows))
        outN = sharded.execute(pad_to(df, rows))
        _assert_bitexact(
            out1.select(["pred", "raw"]), outN.select(["pred", "raw"])
        )

    def test_sharded_batch_text_pipeline(self):
        model, _ = _text_model(dim=32)
        df = _text_df(64, seed=31)
        config.set(Options.BATCH_FASTPATH, False)
        slow = model.transform(df)
        config.set(Options.BATCH_FASTPATH, True)
        config.set(Options.BATCH_MESH, 2)
        try:
            model.invalidate_batch_plan()
            fast = model.transform(df)
        finally:
            config.unset(Options.BATCH_MESH)
        _assert_bitexact(slow, fast)


# ---------------------------------------------------------------------------
# plan cache: sparse programs serialize/restore, digest keyed by cap
# ---------------------------------------------------------------------------
class TestSparsePlanCache:
    def test_sparse_programs_resume_with_zero_compiles(self, tmp_path, monkeypatch):
        dim = 32
        config.set(Options.SPARSE_WARMUP_CAPS, "1,4")
        config.set(Options.SPARSE_NNZ_CAP_MAX, 4)
        from flink_ml_tpu.servable.plancache import PlanCache

        cache_dir = tmp_path / "plans"
        template = DataFrame.from_dict({"features": _sparse_rows(1, dim, 2, seed=2)})
        df = DataFrame.from_dict({"features": _sparse_rows(8, dim, 4, seed=3)})

        pipe1 = _sparse_serving_pipe(dim)
        plan1 = CompiledServingPlan.build(pipe1, scope="t-pc1", sparse={"features": dim})
        plan1.plancache = PlanCache(str(cache_dir), 1 << 30)
        plan1.warmup(template, (8,))
        assert plan1.last_warmup_cache["misses"] > 0
        out1 = plan1.execute(pad_to(df, 8))

        # a new incarnation: same model shapes → every program loads from disk
        import flink_ml_tpu.servable.planner as planner_mod

        pipe2 = _sparse_serving_pipe(dim)
        plan2 = CompiledServingPlan.build(pipe2, scope="t-pc2", sparse={"features": dim})
        plan2.plancache = PlanCache(str(cache_dir), 1 << 30)

        def poisoned(lowered):
            raise AssertionError("live XLA compile despite a warm plan cache")

        monkeypatch.setattr(planner_mod, "_compile_lowered", poisoned)
        plan2.warmup(template, (8,))
        assert plan2.last_warmup_cache["misses"] == 0
        assert plan2.last_warmup_cache["hits"] > 0
        out2 = plan2.execute(pad_to(df, 8))
        _assert_bitexact(
            out1.select(["pred", "raw"]), out2.select(["pred", "raw"])
        )

    def test_digest_distinct_per_cap(self):
        import jax

        from flink_ml_tpu.servable.plancache import program_digest

        fn = jax.jit(lambda x: x * 2.0)
        lowered = fn.lower(np.zeros((4, 4), np.float32))
        a = program_digest(lowered, kind="exact", sparse_key=4)
        b = program_digest(lowered, kind="exact", sparse_key=8)
        c = program_digest(lowered, kind="exact")
        assert len({a, b, c}) == 3


# ---------------------------------------------------------------------------
# goodput attribution: ELL padding counted exactly once
# ---------------------------------------------------------------------------
class TestPaddingAttribution:
    def test_padding_share_uses_cells_once(self):
        from flink_ml_tpu.trace import Span, _padding_share

        span = Span("x", "productive", "t", 0.0, 1, None, 0, "main")
        span.set_attr("rows", 8)
        span.set_attr("bucket", 16)
        span.set_attr("nnz", 24)
        span.set_attr("nnz_cap", 4)
        # 16 rows × cap 4 = 64 cells, 24 real → 40/64 padding (row round-up
        # and ELL slots in ONE ratio, never double-counted)
        assert _padding_share(span) == pytest.approx(40 / 64)
        dense = Span("y", "productive", "t", 0.0, 2, None, 0, "main")
        dense.set_attr("rows", 8)
        dense.set_attr("bucket", 16)
        assert _padding_share(dense) == pytest.approx(0.5)

    def test_chunk_spans_carry_nnz_attrs(self):
        from flink_ml_tpu.trace import capture

        model, _ = _text_model(dim=32)
        df = _text_df(24, seed=37)
        config.set(Options.BATCH_FASTPATH, True)
        model.invalidate_batch_plan()
        with capture() as recorder:
            model.transform(df)
        chunk = [s for s in recorder.snapshot() if s.name == "batch.chunk"]
        assert chunk and all(
            isinstance(s.attrs.get("nnz"), int) and s.attrs["nnz_cap"] >= 1
            for s in chunk
        )


# ---------------------------------------------------------------------------
# ineligibility reasons
# ---------------------------------------------------------------------------
class TestReasons:
    def test_dim_mismatch_is_signature_reason(self):
        dim = 16
        pipe = _sparse_serving_pipe(dim)
        plan = CompiledServingPlan.build(pipe, scope="t-dim", sparse={"features": dim})
        seg = plan.segments[0]
        wrong = DataFrame.from_dict({"features": _sparse_rows(4, dim * 2, 3, seed=41)})
        with pytest.raises(IneligibleBatch) as ei:
            seg.gather_sparse(wrong, "features")
        assert ei.value.reason == "signature"

    def test_sparse_reason_on_dense_spec(self):
        from flink_ml_tpu.servable.lib import StandardScalerModelServable

        sc = StandardScalerModelServable().set_input_col("features").set_output_col("s")
        sc.mean = np.zeros(8)
        sc.std = np.ones(8)
        plan = CompiledServingPlan.build(sc, scope="t-r")
        seg = plan.segments[0]
        df = DataFrame.from_dict({"features": _sparse_rows(4, 8, 2, seed=43)})
        with pytest.raises(IneligibleBatch) as ei:
            seg.gather(df, "features")
        assert ei.value.reason == "sparse"
