"""The cost-based fusion planner (``fusion.mode``, docs/fusion.md):

- **exact stays exact**: the default tier's program partition and outputs are
  bit-identical to the pre-fusion-tier behavior — per-stage programs,
  elementwise-only merges;
- **fast holds its envelope**: cross-reduction XLA fusion and Pallas
  megakernels reproduce the exact tier within the documented per-chain ulp
  envelope (``fusion.ULP_ENVELOPE``) at reduction-sensitive widths 8/16/256;
- **the cost model is shape-monotone**: growing rows/widths never de-fuses a
  chain, and the per-key plan choice upgrades from merged-XLA to megakernel
  exactly at the score bar;
- **mode flips rebuild**: a ``fusion.mode`` change rebuilds cached batch
  plans (fingerprint) and serving plans (rebuild key) instead of silently
  serving the old tier;
- **sharding composes**: the fast tier's merged programs lower through the
  same PlanSharding ingest boundaries at mesh 2/4, inside the same envelope;
- **warmup still covers**: a fast-tier server serves with zero post-warmup
  compiles, megakernels included.
"""
import numpy as np
import pytest

import jax

from flink_ml_tpu.api.dataframe import DataFrame
from flink_ml_tpu.builder import CompiledBatchPlan, PipelineModel
from flink_ml_tpu.config import Options, config
from flink_ml_tpu.metrics import MLMetrics, metrics
from flink_ml_tpu.models.feature.binarizer import Binarizer
from flink_ml_tpu.models.feature.elementwise_product import ElementwiseProduct
from flink_ml_tpu.models.feature.idf import IDFModel
from flink_ml_tpu.models.feature.normalizer import Normalizer
from flink_ml_tpu.models.feature.standard_scaler import StandardScalerModel
from flink_ml_tpu.servable.builder import PipelineModelServable
from flink_ml_tpu.servable.fusion import (
    ULP_ENVELOPE,
    FusionTier,
    chain_score,
    resolve_fusion_tier,
    spec_flops_per_row,
    ulp_diff,
)
from flink_ml_tpu.servable.lib import (
    LogisticRegressionModelServable,
    MLPClassifierModelServable,
    StandardScalerModelServable,
)
from flink_ml_tpu.servable.megakernels import MEGAKERNEL_OPS, chain_eligible
from flink_ml_tpu.servable.planner import (
    PLAN_EXACT,
    PLAN_FUSED,
    PLAN_MEGAKERNEL,
    build_segments,
    run_segment,
)
from flink_ml_tpu.servable.sharding import PlanSharding
from flink_ml_tpu.serving.plan import CompiledServingPlan
from flink_ml_tpu.serving.server import InferenceServer, ServingConfig

WIDTHS = (8, 16, 256)
N = 203  # odd on purpose: exercises the single-tile megakernel tail path


@pytest.fixture(autouse=True)
def _reset_fusion_config():
    yield
    config.unset(Options.FUSION_MODE)
    config.unset(Options.FUSION_MEGAKERNEL)
    config.unset(Options.FUSION_MEGAKERNEL_MIN_SCORE)
    config.unset(Options.BATCH_FASTPATH)
    config.unset(Options.BATCH_MESH)


# ---------------------------------------------------------------------------
# chain builders (the three benched/documented chains)
# ---------------------------------------------------------------------------


def _feature6_stages(d, seed=9):
    """The 6-stage feature chain of docs/fusion.md."""
    rng = np.random.default_rng(seed)
    scaler = StandardScalerModel().set_input_col("input").set_output_col("scaled")
    scaler.set_with_mean(True)
    scaler.mean = rng.standard_normal(d)
    scaler.std = np.abs(rng.standard_normal(d)) + 0.5
    idf = IDFModel().set_input_col("weighted").set_output_col("tfidf")
    idf.idf = np.abs(rng.standard_normal(d)) + 0.2
    idf.doc_freq = np.ones(d)
    idf.num_docs = np.asarray(100.0)
    rescale = StandardScalerModel().set_input_col("tfidf").set_output_col("rescaled")
    rescale.set_with_mean(False)
    rescale.mean = np.zeros(d)
    rescale.std = np.abs(rng.standard_normal(d)) + 0.5
    return [
        scaler,
        Normalizer().set_input_col("scaled").set_output_col("norm"),
        ElementwiseProduct()
        .set_scaling_vec(np.abs(rng.standard_normal(d)) + 0.1)
        .set_input_col("norm")
        .set_output_col("weighted"),
        idf,
        rescale,
        Binarizer().set_input_cols("rescaled").set_output_cols("bin").set_thresholds(0.05),
    ]


def _scale_logistic_servable(d, seed=3):
    rng = np.random.default_rng(seed)
    sc = StandardScalerModelServable().set_input_col("features").set_output_col("scaled")
    sc.set_with_mean(True)
    sc.mean = rng.normal(size=d)
    sc.std = np.abs(rng.normal(size=d)) + 0.5
    lr = LogisticRegressionModelServable().set_features_col("scaled")
    lr.coefficient = rng.normal(size=d)
    return PipelineModelServable([sc, lr])


def _scale_mlp_servable(d=256, hidden=64, classes=8, seed=5):
    rng = np.random.default_rng(seed)
    sc = StandardScalerModelServable().set_input_col("features").set_output_col("scaled")
    sc.set_with_mean(True)
    sc.mean = rng.normal(size=d)
    sc.std = np.abs(rng.normal(size=d)) + 0.5
    mlp = MLPClassifierModelServable().set_features_col("scaled")
    dims = [d, hidden, classes]
    arrays = {"labels": np.arange(float(classes))}
    for i in range(len(dims) - 1):
        arrays[f"W{i}"] = (
            rng.normal(size=(dims[i], dims[i + 1])) / np.sqrt(dims[i])
        ).astype(np.float32)
        arrays[f"b{i}"] = rng.normal(size=dims[i + 1]).astype(np.float32)
    mlp._apply_model_arrays(arrays)
    return PipelineModelServable([sc, mlp])


def _vec_df(n, d, col="input", seed=7):
    return DataFrame.from_dict({col: np.random.default_rng(seed).normal(size=(n, d))})


def _assert_envelope(exact: DataFrame, other: DataFrame, envelope: int, what: str):
    assert exact.get_column_names() == other.get_column_names()
    for name in exact.get_column_names():
        u = ulp_diff(exact.column(name), other.column(name))
        assert u <= envelope, f"{what}: column {name} moved {u} ulps > {envelope}"


# ---------------------------------------------------------------------------
# exact mode: the default, bit-identical to the pre-tier behavior
# ---------------------------------------------------------------------------


def test_default_tier_is_exact_with_unchanged_partition():
    assert resolve_fusion_tier().mode == "exact"
    plan = CompiledBatchPlan.build(_feature6_stages(16), scope="t-def")
    assert not plan.fusion.fast
    assert metrics.get("t-def", MLMetrics.FUSION_MODE) == 0
    (seg,) = plan.segments
    # the PR 5 partition: scaler+norm? no — norm is a reduction: programs are
    # [scaled], [norm], [weighted+tfidf? idf is elementwise...] — assert the
    # invariant rather than the exact grouping: no exact program may contain
    # both an elementwise=False spec and any other spec.
    for prog in seg.programs:
        assert prog.kind == PLAN_EXACT
        if len(prog.specs) > 1:
            assert all(s.elementwise for s in prog.specs)
    assert seg.mega == {}


def test_exact_mode_output_bit_identical_to_per_stage():
    stages = _feature6_stages(16)
    df = _vec_df(N, 16)
    config.set(Options.BATCH_FASTPATH, False)
    model = PipelineModel(stages)
    per_stage = model.transform(df)
    config.set(Options.BATCH_FASTPATH, True)
    model.invalidate_batch_plan()
    fused = model.transform(df)
    for name in per_stage.get_column_names():
        np.testing.assert_array_equal(
            np.asarray(per_stage.column(name)), np.asarray(fused.column(name)), err_msg=name
        )


# ---------------------------------------------------------------------------
# fast tier parity: ulp envelope at reduction-sensitive widths
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("width", WIDTHS)
def test_feature6_fast_within_envelope(width):
    stages = _feature6_stages(width)
    df = _vec_df(N, width)
    exact = CompiledBatchPlan.build(stages, scope=f"t-e{width}").transform(df)
    fast_plan = CompiledBatchPlan.build(
        stages, scope=f"t-f{width}", fusion=FusionTier("fast", megakernel=False)
    )
    fast = fast_plan.transform(df)
    _assert_envelope(exact, fast, ULP_ENVELOPE["feature6"], f"feature6 fast d={width}")
    # the whole fusable chain became ONE cross-reduction program
    (seg,) = fast_plan.segments
    assert [p.kind for p in seg.programs] == [PLAN_FUSED]
    assert len(seg.programs[0].specs) == 6
    assert metrics.get(f"t-f{width}", MLMetrics.FUSION_PROGRAMS_FUSED, 0) >= 1


@pytest.mark.parametrize("width", WIDTHS)
def test_feature6_megakernel_within_envelope(width):
    stages = _feature6_stages(width)
    df = _vec_df(N, width)
    exact = CompiledBatchPlan.build(stages, scope=f"t-me{width}").transform(df)
    mega_plan = CompiledBatchPlan.build(
        stages, scope=f"t-mm{width}", fusion=FusionTier("fast", min_score=1.0)
    )
    mega = mega_plan.transform(df)
    _assert_envelope(exact, mega, ULP_ENVELOPE["feature6"], f"feature6 mega d={width}")
    (seg,) = mega_plan.segments
    assert list(seg.mega) == [0]  # the candidate exists for the whole chain
    assert metrics.get(f"t-mm{width}", MLMetrics.FUSION_PROGRAMS_MEGAKERNEL, 0) >= 1
    assert all(label == "fast+mega" for label in (seg.plan_label(k) for k in seg.compiled))


@pytest.mark.parametrize("width", WIDTHS)
def test_scale_logistic_fast_within_envelope(width):
    servable = _scale_logistic_servable(width)
    df = _vec_df(64, width, col="features")
    exact = CompiledServingPlan.build(servable, scope=f"s-e{width}").execute(df)
    fast = CompiledServingPlan.build(
        servable, scope=f"s-f{width}", fusion=FusionTier("fast", megakernel=False)
    ).execute(df)
    mega = CompiledServingPlan.build(
        servable, scope=f"s-m{width}", fusion=FusionTier("fast", min_score=1.0)
    ).execute(df)
    _assert_envelope(exact, fast, ULP_ENVELOPE["scale_logistic"], f"logistic fast d={width}")
    _assert_envelope(exact, mega, ULP_ENVELOPE["scale_logistic"], f"logistic mega d={width}")
    # prediction (the thresholded class) must not flip inside the envelope
    np.testing.assert_array_equal(
        np.asarray(exact.column("prediction")), np.asarray(fast.column("prediction"))
    )


def test_scale_mlp_megakernel_within_envelope():
    servable = _scale_mlp_servable()
    df = _vec_df(64, 256, col="features")
    exact = CompiledServingPlan.build(servable, scope="mlp-e").execute(df)
    mega_plan = CompiledServingPlan.build(
        servable, scope="mlp-m", fusion=FusionTier("fast", min_score=1.0)
    )
    mega = mega_plan.execute(df)
    _assert_envelope(exact, mega, ULP_ENVELOPE["scale_mlp"], "scale_mlp mega")
    assert metrics.get("mlp-m", MLMetrics.FUSION_PROGRAMS_MEGAKERNEL, 0) >= 1


def test_megakernel_disabled_falls_back_to_fused_program():
    stages = _feature6_stages(16)
    plan = CompiledBatchPlan.build(
        stages, scope="t-nomega", fusion=FusionTier("fast", megakernel=False, min_score=1.0)
    )
    (seg,) = plan.segments
    assert seg.mega == {}
    plan.transform(_vec_df(64, 16))
    assert metrics.get("t-nomega", MLMetrics.FUSION_PROGRAMS_MEGAKERNEL, 0) == 0
    assert metrics.get("t-nomega", MLMetrics.FUSION_PROGRAMS_FUSED, 0) >= 1


# ---------------------------------------------------------------------------
# cost model: shape-monotone plan choice
# ---------------------------------------------------------------------------


def test_chain_score_is_monotone_in_rows_width_and_model_size():
    servable = _scale_logistic_servable(16)
    specs = [s.kernel_spec() for s in servable.servables]
    assert chain_score(specs, 64) < chain_score(specs, 128)
    assert chain_score(specs, 64, width=16) < chain_score(specs, 64, width=64)
    wide = [s.kernel_spec() for s in _scale_logistic_servable(256).servables]
    assert chain_score(specs, 64) < chain_score(wide, 64)
    # an explicit hint pins the estimate exactly
    specs[0].flops_per_row = 123.0
    assert spec_flops_per_row(specs[0]) == 123.0


def test_plan_choice_upgrades_with_rows_never_downgrades():
    """The per-key choice is monotone: below the score bar the chain compiles
    as the merged XLA program, above it as the megakernel — and a row count
    that cleared the bar stays cleared at every larger count."""
    servable = _scale_logistic_servable(16)
    specs = [s.kernel_spec() for s in servable.servables]
    # pick a bar between the score at 8 rows and at 512 rows
    bar = (chain_score(specs, 8, 16) + chain_score(specs, 512, 16)) / 2
    tier = FusionTier("fast", min_score=bar)
    seg = build_segments(list(servable.servables), None, tier)[0]
    kinds = {}
    for rows in (8, 512):
        df = _vec_df(rows, 16, col="features", seed=rows)
        inputs = {n: seg.gather(df, n) for n in seg.external_inputs}
        run_segment(seg, rows, inputs, on_plan=lambda k, s: kinds.setdefault(rows, k))
    assert kinds[8] == PLAN_FUSED
    assert kinds[512] == PLAN_MEGAKERNEL
    chosen = [tier.megakernel_hot(specs, rows, 16) for rows in (1, 8, 64, 512, 4096)]
    assert chosen == sorted(chosen)  # False... then True...: monotone in rows


def test_megakernel_lowering_failure_falls_back_to_fused_program():
    """A backend whose Pallas lowering rejects the megakernel (Mosaic tiling
    rules are stricter than interpret mode) must not take the fast tier
    down: the chain compiles as the merged XLA program instead."""
    servable = _scale_logistic_servable(16)
    tier = FusionTier("fast", min_score=1.0)
    seg = build_segments(list(servable.servables), None, tier)[0]
    assert list(seg.mega) == [0]

    class _Boom:
        def lower(self, *a, **k):
            raise RuntimeError("mosaic says no")

    seg.mega[0].jitted = _Boom()
    df = _vec_df(8, 16, col="features")
    inputs = {n: seg.gather(df, n) for n in seg.external_inputs}
    kinds = []
    outs = run_segment(seg, 8, inputs, on_plan=lambda k, s: kinds.append(k))
    assert kinds == [PLAN_FUSED]
    assert seg.plan_label(8) == "fast"
    ref = build_segments(list(servable.servables), None, None)[0]
    ref_outs = run_segment(ref, 8, {n: ref.gather(df, n) for n in ref.external_inputs})
    assert ulp_diff(outs["rawPrediction"], ref_outs["rawPrediction"]) <= ULP_ENVELOPE[
        "scale_logistic"
    ]


def test_megakernel_fallback_is_counted_journaled_and_warned(tmp_path, monkeypatch):
    """The fallback keeps serving alive but is never silent: the new counter
    moves, the flight recorder carries the compiler's message, and one
    warning is raised (the chip must not hide behind the XLA program)."""
    import flink_ml_tpu.servable.megakernels as megakernels
    import flink_ml_tpu.telemetry as telemetry

    def rejected_by_mosaic(specs, models, input_names, interpret):
        def mega(model_seq, cols):
            raise NotImplementedError("Only 2D gather is supported")

        return mega

    monkeypatch.setattr(megakernels, "build_megakernel_fn", rejected_by_mosaic)
    scope = "t-mega-fallback"
    rec = telemetry.configure(str(tmp_path))
    try:
        plan = CompiledServingPlan.build(
            _scale_logistic_servable(16), scope=scope,
            fusion=FusionTier("fast", min_score=1.0),
        )
        with pytest.warns(RuntimeWarning, match="Only 2D gather is supported"):
            out = plan.execute(_vec_df(8, 16, col="features"))
        assert rec.flush(10.0)
        journaled = [
            r for r in telemetry.read_journal(str(tmp_path))
            if r["kind"] == "fusion.megakernel.fallback"
        ]
    finally:
        telemetry.configure(None)
    assert metrics.get(scope, MLMetrics.FUSION_MEGAKERNEL_FALLBACKS, 0) == 1
    assert metrics.get(scope, MLMetrics.FUSION_PROGRAMS_MEGAKERNEL, 0) == 0
    assert metrics.get(scope, MLMetrics.FUSION_PROGRAMS_FUSED, 0) == 1
    (record,) = journaled
    assert record["scope"] == scope
    assert record["data"]["ops"] == ["scale", "logistic"]
    assert record["data"]["rows"] == 8
    assert "NotImplementedError: Only 2D gather is supported" in record["data"]["error"]
    assert np.all(np.isfinite(np.asarray(out.column("rawPrediction"))))


def test_megakernel_never_a_candidate_past_the_vmem_budget():
    """Eligibility is stated for the chip: a chain whose model operands
    cannot fit the kernel's VMEM is never built as a candidate, and a chain
    that fits is declined at the row counts where its tiles do not."""
    from flink_ml_tpu.servable.megakernels import VMEM_BUDGET_BYTES, fits_vmem, vmem_bytes

    small = [s.kernel_spec() for s in _scale_mlp_servable().servables]
    assert chain_eligible(small)
    assert fits_vmem(small, rows=64, width=256)
    # the repo's MLP training widths: 2048x4096 f32 is 32 MiB of weights alone
    wide = [s.kernel_spec() for s in _scale_mlp_servable(d=2048, hidden=4096).servables]
    assert vmem_bytes(wide, rows=8) > VMEM_BUDGET_BYTES
    assert not chain_eligible(wide)
    # monotone in rows, and the tier asks before the cost model does
    tall = [s.kernel_spec() for s in _scale_mlp_servable(hidden=512).servables]
    assert fits_vmem(tall, rows=64, width=256) and not fits_vmem(tall, rows=4096, width=256)
    tier = FusionTier("fast", min_score=0.0)
    assert tier.megakernel_hot(tall, 64, 256) and not tier.megakernel_hot(tall, 4096, 256)


def test_megakernel_vocabulary_and_eligibility():
    assert {"scale", "logistic", "mlp", "normalize", "binarize"} <= MEGAKERNEL_OPS
    servable = _scale_logistic_servable(8)
    specs = [s.kernel_spec() for s in servable.servables]
    assert chain_eligible(specs)
    specs[0].fusion_op = None  # one unregistered body poisons the chain
    assert not chain_eligible(specs)
    assert not chain_eligible([])


def test_resolve_fusion_tier_validates_mode():
    config.set(Options.FUSION_MODE, "turbo")
    with pytest.raises(ValueError, match="fusion.mode"):
        resolve_fusion_tier()


# ---------------------------------------------------------------------------
# mode flips rebuild cached plans (the batch.mesh bug class, PR 9)
# ---------------------------------------------------------------------------


def test_fusion_mode_flip_rebuilds_cached_batch_plan():
    model = PipelineModel(_feature6_stages(16))
    df = _vec_df(64, 16)
    exact_out = model.transform(df)
    exact_plan = model._plan_cache[1]
    assert not exact_plan.fusion.fast
    config.set(Options.FUSION_MODE, "fast")
    fast_out = model.transform(df)
    fast_plan = model._plan_cache[1]
    assert fast_plan is not exact_plan and fast_plan.fusion.fast
    _assert_envelope(exact_out, fast_out, ULP_ENVELOPE["feature6"], "mode flip")
    config.set(Options.FUSION_MODE, "exact")
    again = model.transform(df)
    assert model._plan_cache[1] is not fast_plan
    for name in exact_out.get_column_names():  # back to bit-identical
        np.testing.assert_array_equal(
            np.asarray(exact_out.column(name)), np.asarray(again.column(name))
        )
    # the megakernel knobs are fingerprinted too
    config.set(Options.FUSION_MEGAKERNEL_MIN_SCORE, 17.0)
    model.transform(df)
    assert model._plan_cache[1].fusion.min_score == 17.0


def test_fusion_mode_flip_rebuilds_serving_plan():
    servable = _scale_logistic_servable(16)
    df = _vec_df(4, 16, col="features")
    with InferenceServer(
        servable,
        name="flip-exact",
        serving_config=ServingConfig(max_delay_ms=0.1, fusion_mode="exact"),
        warmup_template=df.take([0]),
    ) as server:
        server.predict(df)
        exact_plan = servable._fastpath_plan
        assert not exact_plan.fusion.fast
    with InferenceServer(
        servable,
        name="flip-fast",
        serving_config=ServingConfig(max_delay_ms=0.1, fusion_mode="fast"),
        warmup_template=df.take([0]),
    ) as server:
        server.predict(df)
        fast_plan = servable._fastpath_plan
        assert fast_plan is not exact_plan and fast_plan.fusion.fast


# ---------------------------------------------------------------------------
# sharding composes: fast-tier merged programs through PlanSharding, mesh 2/4
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mesh", (2, 4))
def test_sharded_fast_tier_parity(mesh):
    if len(jax.devices()) < mesh:
        pytest.skip(f"needs {mesh} devices")
    stages = _feature6_stages(16)
    df = _vec_df(64, 16)  # 64 rows: multiple of MIN_SHARD_ROWS * mesh
    exact = CompiledBatchPlan.build(stages, scope=f"sh-e{mesh}").transform(df)
    fast_sharded_plan = CompiledBatchPlan.build(
        stages,
        scope=f"sh-f{mesh}",
        sharding=PlanSharding(mesh),
        fusion=FusionTier("fast"),
    )
    (seg,) = fast_sharded_plan.segments
    assert seg.mega == {}  # megakernels are single-device; merged XLA shards
    assert [p.kind for p in seg.programs] == [PLAN_FUSED]
    fast_sharded = fast_sharded_plan.transform(df)
    _assert_envelope(
        exact, fast_sharded, ULP_ENVELOPE["feature6"], f"sharded fast mesh={mesh}"
    )
    assert metrics.get(f"sh-f{mesh}", MLMetrics.BATCH_SHARD_COUNT) == mesh
    # sharded fast == unsharded fast bit-for-bit would be ideal, but the fast
    # tier's contract is the envelope vs EXACT — assert the sharded leg also
    # matches the unsharded fast leg inside the same envelope.
    fast_unsharded = CompiledBatchPlan.build(
        stages, scope=f"sh-u{mesh}", fusion=FusionTier("fast")
    ).transform(df)
    _assert_envelope(
        fast_unsharded, fast_sharded, ULP_ENVELOPE["feature6"], f"fast-vs-fast mesh={mesh}"
    )


# ---------------------------------------------------------------------------
# serving: fast tier serves with zero post-warmup compiles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ("exact", "fast"))
def test_serving_zero_compiles_after_warmup(mode):
    servable = _scale_logistic_servable(16)
    df = _vec_df(4, 16, col="features")
    config.set(Options.FUSION_MEGAKERNEL_MIN_SCORE, 1.0)  # megakernels engage
    with InferenceServer(
        servable,
        name=f"warm-{mode}",
        serving_config=ServingConfig(max_delay_ms=0.1, fusion_mode=mode),
        warmup_template=df.take([0]),
    ) as server:
        scope = server.scope
        before = metrics.get(scope, MLMetrics.SERVING_FASTPATH_COMPILES, 0)
        for i in range(4):
            out = server.predict(_vec_df(4, 16, col="features", seed=i))
            assert len(out.dataframe) == 4
        assert metrics.get(scope, MLMetrics.SERVING_FASTPATH_COMPILES, 0) == before
        if mode == "fast":
            assert metrics.get(scope, MLMetrics.FUSION_PROGRAMS_MEGAKERNEL, 0) >= 1
            assert metrics.get(scope, MLMetrics.FUSION_MODE) == 1


# ---------------------------------------------------------------------------
# ulp_diff itself (the envelope's measuring stick)
# ---------------------------------------------------------------------------


def test_ulp_diff_basics():
    a = np.asarray([1.0, -2.0, 0.0], np.float32)
    assert ulp_diff(a, a) == 0
    assert ulp_diff(np.float32(1.0), np.nextafter(np.float32(1.0), np.float32(2.0))) == 1
    assert ulp_diff(np.float32(0.0), -np.float32(0.0)) == 0
    tiny = np.nextafter(np.float32(0.0), np.float32(1.0))
    assert ulp_diff(np.float32(0.0), tiny) == 1
    assert ulp_diff(tiny, -tiny) == 2  # crosses zero monotonically
    assert ulp_diff(np.float32(np.nan), np.float32(np.nan)) == 0
    assert ulp_diff(np.float32(np.nan), np.float32(1.0)) == np.iinfo(np.int32).max
    with pytest.raises(ValueError):
        ulp_diff(np.zeros(2), np.zeros(3))
