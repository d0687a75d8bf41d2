"""The span tree of one ``DecoderLM.fit`` (scope ``ml.train``) at toy size on
the CPU: each phase once, under ``train.fit``, with the counts
docs/observability.md ("The LM fit") promises, and the two registry counters
at the same site."""
import numpy as np
import pytest

from flink_ml_tpu import trace
from flink_ml_tpu.api.dataframe import DataFrame
from flink_ml_tpu.metrics import MLMetrics, metrics
from flink_ml_tpu.models.lm import DecoderLM
from flink_ml_tpu.models.lm.config import num_params, param_shapes
from flink_ml_tpu.trace import CAT_COMPILE, CAT_INGEST, CAT_PRODUCTIVE, CAT_READBACK, tracer

N, T, BATCH, STEPS, K, EXPERTS, LAYERS = 4, 256, 2, 3, 2, 4, 2
TREE = [
    ("train.fit", None, CAT_PRODUCTIVE),
    ("train.tokens_put", "train.fit", CAT_INGEST),
    ("train.init", "train.fit", CAT_COMPILE),
    ("train.program", "train.fit", CAT_COMPILE),
    ("train.dispatch", "train.fit", CAT_PRODUCTIVE),
    ("train.drain", "train.fit", CAT_PRODUCTIVE),
    ("train.readback", "train.fit", CAT_READBACK),
]


@pytest.fixture(autouse=True)
def _tracer_off():
    tracer.disable()
    yield
    tracer.disable()


def _estimator():
    return (
        DecoderLM().set_num_layers(LAYERS).set_hidden_size(32).set_num_heads(2)
        .set_num_experts(EXPERTS).set_experts_per_token(K).set_expert_width(16).set_vocab_size(64)
        .set_max_iter(STEPS).set_global_batch_size(BATCH).set_seed(1)
    )


@pytest.fixture(scope="module")
def traced_fit():
    df = DataFrame.from_dict({"features": np.random.default_rng(0).integers(0, 64, (N, T))})
    _estimator().fit(df)  # so that nothing compiles in the traced one
    est = _estimator()
    tokens0 = metrics.get(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LM_TOKENS) or 0
    rows0 = metrics.get(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_MOE_ROWS) or 0
    with trace.capture() as recorder:
        est.fit(df)
    counted = (
        metrics.get(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LM_TOKENS) - tokens0,
        metrics.get(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_MOE_ROWS) - rows0,
    )
    return est, sorted(recorder.snapshot(), key=lambda s: s.span_id), counted


def test_each_phase_once_under_train_fit(traced_fit):
    _, spans, _ = traced_fit
    names = {s.span_id: s.name for s in spans}
    assert [(s.name, names.get(s.parent_id), s.category) for s in spans] == TREE
    assert {s.scope for s in spans} == {"ml.train"}


def test_counts_are_what_the_job_says(traced_fit):
    est, spans, _ = traced_fit
    one = {s.name: s.attrs for s in spans}
    cfg = est.lm_config()
    assert one["train.fit"] == {"rows": N, "tokens": N * T}
    assert one["train.tokens_put"] == {"rows": N, "tokens": N * T, "bytes": N * T * 4}
    assert one["train.init"] == {"params": num_params(cfg), "bytes": 4 * num_params(cfg)}
    # 256 keys are one chunk, taken whole: the forward's one tile and the backward kernel's, nothing to hide
    pairs = (1 + 1) * LAYERS * 2 * BATCH
    # AdamW's state: mu and nu, a float32 leaf a parameter each, and the int32 count; every layer's fold in the
    # one-block form, two row statistics through its two kernels, one of them the backward
    assert one["train.program"] == {"built": 0, "fold_chunks": pairs, "fold_chunks_visited": pairs,
                                    "loop_trips": 1, "layer_applications": LAYERS,
                                    "state_leaves": 2 * len(param_shapes(cfg)) + 1,
                                    "state_bytes": 8 * num_params(cfg) + 4, "head_logit_matmuls": 1,
                                    "fold_one_block": LAYERS, "fold_row_stats": 2, "fold_bwd_kernels": 1}
    assert one["train.dispatch"] == {"steps": STEPS}
    drain = one["train.drain"]
    assert drain["steps"] == STEPS and drain["tokens"] == STEPS * BATCH * T
    assert drain["expert_rows_mean"] == BATCH * T * K // EXPERTS
    assert drain["expert_rows_max"] == int(est.expert_rows_history.max()) >= drain["expert_rows_mean"]
    assert drain["dropped"] == 0
    # every expert is held here: the held counts are the whole routing's
    assert drain["rows_held"] == STEPS * BATCH * T * K * LAYERS and drain["rows_absent"] == 0
    assert drain["held_rows_max"] == drain["expert_rows_max"]
    assert drain["held_rows_mean"] == pytest.approx(BATCH * T * K / EXPERTS)
    assert one["train.readback"] == {"bytes": 4 * STEPS * (1 + len(param_shapes(cfg)))}


def test_registry_counters_count_at_the_same_site(traced_fit):
    _, _, (tokens, rows) = traced_fit
    assert tokens == STEPS * BATCH * T
    assert rows == STEPS * BATCH * T * K * LAYERS


def test_the_causal_fold_reports_the_chunks_it_skips():
    """At a length of several chunks the mask hides part of every fold: the
    counts on ``train.program`` are one step's, all layers, heads and sequences,
    and the registry counters the whole fit's."""
    from flink_ml_tpu.parallel.flash import fold_chunk_counts

    t, steps, heads, layers = 2048, 1, 2, 1
    df = DataFrame.from_dict({"features": np.random.default_rng(1).integers(0, 64, (1, t))})
    est = (
        DecoderLM().set_num_layers(layers).set_hidden_size(32).set_num_heads(heads)
        .set_num_experts(2).set_experts_per_token(1).set_expert_width(16).set_vocab_size(64)
        .set_max_iter(steps).set_global_batch_size(1).set_seed(1)
    )
    names = (MLMetrics.TRAIN_LM_FOLD_CHUNKS, MLMetrics.TRAIN_LM_FOLD_CHUNKS_VISITED)
    before = [metrics.get(MLMetrics.TRAIN_GROUP, name) or 0 for name in names]
    with trace.capture() as recorder:
        est.fit(df)
    (program,) = [s.attrs for s in recorder.snapshot() if s.name == "train.program"]
    visited, pairs = fold_chunk_counts(t, t, 0, True, one_block=True)
    assert 0 < program["fold_chunks_visited"] < program["fold_chunks"]
    assert (program["fold_chunks_visited"], program["fold_chunks"]) == (layers * heads * visited, layers * heads * pairs)
    counted = [metrics.get(MLMetrics.TRAIN_GROUP, name) - b for name, b in zip(names, before)]
    assert counted == [steps * program["fold_chunks"], steps * program["fold_chunks_visited"]]
    assert np.isfinite(est.loss_history).all()


def test_a_looped_stack_reports_its_trips_and_its_exits():
    """``blockKind`` ``ouro``: the fold's chunks and the two loop counters are
    counted over ``layers x passes`` block applications; ``train.drain`` carries
    the exits' sums and each pass's loss and, the block having no experts, no
    expert count; nothing is counted on ``ml.train.moe.*``."""
    layers, loops, heads, steps = 2, 3, 2, 2
    df = DataFrame.from_dict({"features": np.random.default_rng(2).integers(0, 64, (N, T))})
    est = (
        DecoderLM().set_block_kind("ouro").set_num_layers(layers).set_hidden_size(32).set_num_heads(heads)
        .set_expert_width(48).set_vocab_size(64).set_num_loops(loops)
        .set_max_iter(steps).set_global_batch_size(BATCH).set_seed(1)
    )
    names = (MLMetrics.TRAIN_LM_LOOP_TRIPS, MLMetrics.TRAIN_LM_LOOP_LAYER_APPLICATIONS,
             MLMetrics.TRAIN_LM_FOLD_CHUNKS, MLMetrics.TRAIN_MOE_ROWS, MLMetrics.TRAIN_MOE_ROWS_ABSENT)
    before = [metrics.get(MLMetrics.TRAIN_GROUP, name) or 0 for name in names]
    with trace.capture() as recorder:
        est.fit(df)
    spans = sorted(recorder.snapshot(), key=lambda s: s.span_id)
    parents = {s.span_id: s.name for s in spans}
    assert [(s.name, parents.get(s.parent_id), s.category) for s in spans] == TREE
    one = {s.name: s.attrs for s in spans}
    pairs = (1 + 1) * layers * loops * heads * BATCH  # T 256 is one chunk, as above
    cfg = est.lm_config()
    assert one["train.program"] == {"built": 1, "fold_chunks": pairs, "fold_chunks_visited": pairs,
                                    "loop_trips": loops, "layer_applications": layers * loops,
                                    "state_leaves": 2 * len(param_shapes(cfg)) + 1,
                                    "state_bytes": 8 * num_params(cfg) + 4, "head_logit_matmuls": 1,
                                    "fold_one_block": layers, "fold_row_stats": 2,  # the traced pass's layers, once
                                    "fold_bwd_kernels": 1}
    drain = one["train.drain"]
    assert set(drain) == {"steps", "tokens", "exit_trip_sum", "exit_last_mass", "gate_entropy_sum", "trip_nll"}
    tokens = steps * BATCH * T
    assert drain["tokens"] == tokens
    # a gate two steps old (lambda still near 1/2): p near 1/2, 1/4, 1/4
    assert 1.5 < drain["exit_trip_sum"] / tokens < 2.0
    assert 0.15 < drain["exit_last_mass"] / tokens < 0.35
    assert 0.9 < drain["gate_entropy_sum"] / tokens <= 1.5 * np.log(2) + 1e-6
    assert drain["trip_nll"] == pytest.approx(list(est.trip_loss_history.mean(axis=0)))
    assert est.trip_loss_history.shape == (steps, loops) and est.expert_rows_history.shape == (steps, layers, 0)
    counted = [metrics.get(MLMetrics.TRAIN_GROUP, name) or 0 for name in names]
    assert [c - b for c, b in zip(counted, before)] == [steps * loops, steps * layers * loops, steps * pairs, 0, 0]
