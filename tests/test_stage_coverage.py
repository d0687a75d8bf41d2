"""Registry-wide coverage: every stage's param JSON round-trip, every
estimator's fit → save → load → identical-transform contract, sparse-input
parity for vector transforms, weighted evaluation, and empty-input errors.

The reference tests each algorithm in its own *Test.java with the same
quartet (defaults/param-set/fit-transform/save-load); this file pins the two
contracts that are uniform across stages so no stage can silently miss them.
"""
import numpy as np
import pytest

from flink_ml_tpu.api.dataframe import DataFrame
from flink_ml_tpu.linalg.vectors import SparseVector
from flink_ml_tpu.models import STAGE_REGISTRY, get_stage_class
from flink_ml_tpu.utils.read_write import load_stage

RNG = np.random.default_rng(101)


# --------------------------------------------------------------------------- #
# 1. Param JSON round-trip for every registered stage
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("name", sorted(set(STAGE_REGISTRY)))
def test_param_json_round_trip(name):
    cls = get_stage_class(name)
    stage = cls()
    payload = stage.param_map_to_json()
    fresh = cls()
    fresh.load_param_map_from_json(payload)
    for p in stage.get_param_map():
        got = fresh.get(p)
        want = stage.get(p)
        if isinstance(got, float) and isinstance(want, float):
            assert got == want or (np.isnan(got) and np.isnan(want)), (name, p.name)
        else:
            assert got == want or (got is None and want is None), (name, p.name)


# --------------------------------------------------------------------------- #
# 2. fit -> save -> load -> identical transform for every Estimator family
# --------------------------------------------------------------------------- #
def _vec_df(n=24, d=4, seed=3):
    return DataFrame.from_dict({"input": RNG.normal(size=(n, d))})


def _labeled_df(n=32, d=4):
    X = RNG.normal(size=(n, d))
    y = (X @ np.linspace(1.0, -1.0, d) > 0).astype(np.float64)
    return DataFrame.from_dict({"features": X, "label": y})


def _docs_df():
    docs = [["a", "b", "c"], ["a", "b"], ["c", "d"], ["a", "c", "c"]]
    return DataFrame(["input"], None, [docs])


ESTIMATOR_CASES = {
    "CountVectorizer": (lambda c: c(), _docs_df),
    "IDF": (lambda c: c(), _vec_df),
    "Imputer": (
        lambda c: c().set_input_cols("a").set_output_cols("out"),
        lambda: DataFrame.from_dict({"a": np.asarray([1.0, np.nan, 3.0, 4.0])}),
    ),
    "KBinsDiscretizer": (lambda c: c().set_num_bins(3), _vec_df),
    "KMeans": (lambda c: c().set_k(2).set_seed(0), lambda: DataFrame.from_dict({"features": RNG.normal(size=(20, 3))})),
    "Knn": (lambda c: c().set_k(3), _labeled_df),
    "LinearRegression": (lambda c: c().set_max_iter(5), _labeled_df),
    "LinearSVC": (lambda c: c().set_max_iter(5), _labeled_df),
    "LogisticRegression": (lambda c: c().set_max_iter(5), _labeled_df),
    "MLPClassifier": (
        lambda c: c().set_max_iter(5).set_hidden_layers(4).set_seed(1),
        _labeled_df,
    ),
    "MaxAbsScaler": (lambda c: c(), _vec_df),
    "MinHashLSH": (
        lambda c: c().set_input_col("vec").set_num_hash_tables(3).set_seed(7),
        lambda: DataFrame(
            ["vec"],
            None,
            [[SparseVector(10, [0, 1], [1.0, 1.0]), SparseVector(10, [2, 3], [1.0, 1.0])]],
        ),
    ),
    "MinMaxScaler": (lambda c: c(), _vec_df),
    "NaiveBayes": (
        lambda c: c(),
        lambda: DataFrame.from_dict(
            {
                "features": RNG.integers(0, 3, size=(24, 3)).astype(np.float64),
                "label": RNG.integers(0, 2, 24).astype(np.float64),
            }
        ),
    ),
    "OneHotEncoder": (
        lambda c: c().set_input_cols("c").set_output_cols("vec"),
        lambda: DataFrame.from_dict({"c": np.asarray([0.0, 1.0, 2.0, 1.0])}),
    ),
    "RobustScaler": (lambda c: c(), _vec_df),
    "DecoderLM": (
        lambda c: c().set_num_layers(1).set_hidden_size(32).set_num_heads(2).set_num_experts(4)
        .set_expert_width(16).set_max_iter(2).set_global_batch_size(2),
        lambda: DataFrame.from_dict({"features": RNG.integers(0, 32, (4, 256))}),
    ),
    "SelfAttentionClassifier": (
        lambda c: c().set_max_iter(2).set_embedding_dim(8).set_num_heads(2).set_seed(1),
        lambda: DataFrame.from_dict(
            {
                "features": RNG.integers(0, 6, size=(8, 16)).astype(np.float64),
                "label": RNG.integers(0, 2, 8).astype(np.float64),
            }
        ),
    ),
    "StandardScaler": (lambda c: c().set_with_mean(True), _vec_df),
    "StringIndexer": (
        lambda c: c().set_input_cols("s").set_output_cols("idx"),
        lambda: DataFrame(["s"], None, [["b", "a", "b", "c"]]),
    ),
    "UnivariateFeatureSelector": (
        lambda c: c()
        .set_feature_type("continuous")
        .set_label_type("categorical")
        .set_selection_threshold(2),
        _labeled_df,
    ),
    "VarianceThresholdSelector": (lambda c: c(), _vec_df),
    "VectorIndexer": (
        lambda c: c().set_max_categories(3),
        lambda: DataFrame.from_dict(
            {"input": np.stack([RNG.integers(0, 2, 20).astype(np.float64), RNG.normal(size=20)], axis=1)}
        ),
    ),
}


def _outputs_equal(a: DataFrame, b: DataFrame):
    assert a.get_column_names() == b.get_column_names()
    for name in a.get_column_names():
        ca, cb = a.column(name), b.column(name)
        if isinstance(ca, np.ndarray) and ca.dtype.kind in "biufc":
            np.testing.assert_allclose(ca, np.asarray(cb, ca.dtype), rtol=1e-6, atol=1e-7)
        else:
            for va, vb in zip(ca, cb):
                if hasattr(va, "to_array"):
                    np.testing.assert_allclose(va.to_array(), vb.to_array(), rtol=1e-6)
                else:
                    assert np.array_equal(va, vb) if isinstance(va, np.ndarray) else va == vb


@pytest.mark.parametrize("name", sorted(ESTIMATOR_CASES))
def test_estimator_save_load_transform_identity(name, tmp_path):
    configure, make_df = ESTIMATOR_CASES[name]
    est = configure(get_stage_class(name))
    df = make_df()
    model = est.fit(df)
    want = model.transform(df)
    path = str(tmp_path / name)
    model.save(path)
    loaded = load_stage(path)
    assert type(loaded) is type(model)
    got = loaded.transform(df)
    _outputs_equal(want, got)


def test_every_estimator_family_in_cases():
    """The case table must cover every fitting Estimator in the registry
    (online estimators train on streams and are covered in test_online.py)."""
    from flink_ml_tpu.api.core import Estimator

    skip = {
        "OnlineKMeans",
        "OnlineLogisticRegression",
        "OnlineStandardScaler",
        "Swing",  # AlgoOperator
        "AgglomerativeClustering",  # AlgoOperator
    }
    missing = []
    for name in sorted(set(STAGE_REGISTRY)):
        cls = get_stage_class(name)
        if not isinstance(cls, type) or not issubclass(cls, Estimator):
            continue
        if name in skip or name in ESTIMATOR_CASES:
            continue
        missing.append(name)
    assert not missing, f"estimators without a save/load case: {missing}"


# --------------------------------------------------------------------------- #
# 3. Sparse-input parity for dense-vector transforms
# --------------------------------------------------------------------------- #
def _to_sparse(X):
    rows = []
    for r in X:
        nz = np.nonzero(r)[0]
        rows.append(SparseVector(len(r), nz, r[nz]))
    return rows


@pytest.mark.parametrize("stage_name", ["Normalizer", "DCT", "PolynomialExpansion"])
def test_sparse_input_matches_densified(stage_name):
    X = RNG.normal(size=(12, 4))
    X[RNG.random(X.shape) < 0.5] = 0.0
    stage = get_stage_class(stage_name)()
    dense_out = stage.transform(DataFrame.from_dict({"input": X}))["output"]
    sparse_out = stage.transform(DataFrame(["input"], None, [_to_sparse(X)]))["output"]
    np.testing.assert_allclose(np.asarray(sparse_out), np.asarray(dense_out), rtol=1e-6)


def test_fitted_scaler_sparse_input_matches_densified():
    from flink_ml_tpu.models.feature.scalers import MinMaxScaler

    X = RNG.normal(size=(16, 3))
    X[RNG.random(X.shape) < 0.4] = 0.0
    model = MinMaxScaler().fit(DataFrame.from_dict({"input": X}))
    dense_out = model.transform(DataFrame.from_dict({"input": X}))["output"]
    sparse_out = model.transform(DataFrame(["input"], None, [_to_sparse(X)]))["output"]
    np.testing.assert_allclose(np.asarray(sparse_out), np.asarray(dense_out), rtol=1e-6)


# --------------------------------------------------------------------------- #
# 4. Weighted evaluation (ref BinaryClassificationEvaluator weightCol)
# --------------------------------------------------------------------------- #
def test_evaluator_weight_col_changes_auc():
    y = np.asarray([0.0, 0.0, 1.0, 1.0])
    score = np.asarray([0.1, 0.6, 0.4, 0.8])  # one inversion: (0.6 neg > 0.4 pos)
    from flink_ml_tpu.models.evaluation.binary_classification_evaluator import (
        BinaryClassificationEvaluator,
    )

    df = DataFrame.from_dict({"label": y, "rawPrediction": score})
    auc = BinaryClassificationEvaluator().transform(df)["areaUnderROC"][0]
    np.testing.assert_allclose(auc, 0.75)  # 3 of 4 pairs ordered correctly

    # Upweighting the correctly-ordered negative (0.1, w=3) raises weighted
    # AUC: correctly ordered pair weight = (0.4,0.1):1*3 + (0.8,0.1):1*3 +
    # (0.8,0.6):1*1 = 7 over W_pos*W_neg = 2*4 = 8.
    w = np.asarray([3.0, 1.0, 1.0, 1.0])
    df_w = DataFrame.from_dict({"label": y, "rawPrediction": score, "weight": w})
    auc_w = (
        BinaryClassificationEvaluator()
        .set_weight_col("weight")
        .transform(df_w)["areaUnderROC"][0]
    )
    np.testing.assert_allclose(auc_w, 7.0 / 8.0)


# --------------------------------------------------------------------------- #
# 5. Empty-input error branches
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("name", ["StandardScaler", "MinMaxScaler", "KMeans"])
def test_empty_training_set_raises(name):
    est = get_stage_class(name)()
    col = "features" if name == "KMeans" else "input"
    empty = DataFrame([col], None, [np.zeros((0, 3))])
    with pytest.raises((RuntimeError, ValueError)):
        est.fit(empty)
