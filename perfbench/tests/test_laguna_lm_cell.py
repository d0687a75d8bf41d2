"""The ``laguna_xs2`` configuration's own pieces, on the CPU at its ``toy``
sizes: the configuration against the catalog row, the benchmark's plain
reference against the program's, the cost module's counts against a hand
count at the published widths, the new reducers on recorded counts, and a
timed path with part of the mathematics missing coming out not correct."""
import json
import os
import types

import numpy as np
import pytest

from perfbench import laguna_costs
from perfbench.manifest import Manifest
from perfbench.systems import laguna_lm_fit

CELL = "laguna_xs2.fit_swa4k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def config():
    return Manifest().config("laguna_xs2")


@pytest.fixture(scope="module")
def toy(config):
    return {**config, **config["toy"]}


@pytest.fixture(scope="module")
def system(toy):
    s = laguna_lm_fit.create(toy, 2**31 + 5, 1)
    s.make_data()
    s.build()
    return s


@pytest.fixture(scope="module")
def want(system):
    return system.reference()


def test_the_configuration_is_the_catalog_row_cut_in_depth_experts_and_vocabulary(config):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Laguna-XS.2")
    published = row["config"]
    differs = sorted(k for k, v in published.items() if config.get(k, "absent") != v)
    assert differs == sorted(config["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    entry = Manifest().configs["laguna_xs2"]
    assert sorted(entry["reduced"]) == differs and entry["source"] == row["source_url"]
    # the floors of the model-configs guide, and what is stated beside each cut
    kinds = laguna_lm_fit.reference.layer_kinds(config)
    assert [(h, bool(w), d) for h, w, d in kinds] == [(48, False, True), (64, True, False), (64, True, False),
                                                      (64, True, False), (48, False, False)]
    assert config["num_experts"] >= 8 and config["vocab_size"] * 8 >= published["vocab_size"]
    assert published["vocab_size"] == config["vocab_size_published"]
    assert config["num_experts_published"] == config["router_outputs"] == published["num_experts"]
    assert set(config["reduced"]) <= set(config["reduced_why"])
    assert set(config["check_limits"]) == set(config["toy"]["check_limits"])
    assert set(config["check_limits"]) <= set(config["check_limits_why"])  # every limit with its readings
    cell = Manifest().cell(CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "fit_swa4k")
    assert config["global_batch_size"] * config["max_iter"] == config["num_sequences"]  # one pass a job


def test_benchmark_reference_agrees_with_the_programs(system, want, toy):
    """Two independent writings of the same equations, one seed: the program's
    reference (whole ``[T, T]`` scores, held experts in a Python loop, full
    AdamW) and the benchmark's (blocks, rematerialised, the first step's update
    from the gradient alone)."""
    import jax.numpy as jnp

    from flink_ml_tpu.models.lm import reference_laguna as program_reference
    from flink_ml_tpu.models.lm.decoder_lm import _flat_names, _ordered, init_params

    cfg = laguna_lm_fit.lm_config(toy)
    params = init_params(cfg, system.seed % 2**31)
    batches = [jnp.asarray(system.tok[:2]), jnp.asarray(system.tok[2:4])]
    _, grads = program_reference.loss_and_grads(params, batches[0], cfg)
    _, losses, norms = program_reference.train_steps(
        params, batches, cfg, system.hyper["learning_rate"],
        weight_decay=system.hyper["weight_decay"], clip=system.hyper["clip_norm"])
    np.testing.assert_allclose(want["losses"], losses, rtol=2e-6)
    np.testing.assert_allclose(want["grad_norms"][0], norms[0], rtol=2e-5)
    assert set(want["group_norms"]) == set(_flat_names(cfg))
    for name, g in zip(_flat_names(cfg), _ordered(grads, cfg)):
        np.testing.assert_allclose(want["group_norms"][name], float(jnp.sqrt(jnp.sum(g * g))),
                                   rtol=1e-4, err_msg=name)


def test_the_sound_program_is_correct_and_the_control_is_not(system, want, toy):
    limits = toy["check_limits"]
    got = system.fit()
    sound = system.compare(got, want)
    assert all(sound[k] <= limits[k] for k in limits), sound
    np.testing.assert_array_equal(got["expert_rows"], want["expert_rows"])
    assert got["expert_rows"].shape == (4, toy["num_experts_published"])  # the sparse layers alone
    control = system.compare(system.reference("bf16"), want)
    assert any(control[k] > limits[k] for k in limits), control


@pytest.mark.parametrize("defect", ["no_window", "absent_experts_served", "softmax_gates", "no_shared_expert",
                                    "no_head_gate", "half_the_steps"])
def test_a_broken_timed_path_is_not_correct(system, want, toy, defect, monkeypatch):
    """This system's own class with its timed path broken underneath."""
    import jax.numpy as jnp

    from flink_ml_tpu.models.lm import decoder_lm
    from flink_ml_tpu.parallel import moe

    decoder_lm._train_program.cache_clear()
    if defect == "no_window":
        sound_fold = decoder_lm._fold
        monkeypatch.setattr(decoder_lm, "_fold", lambda q, k, v, cd, interpret, window=None: sound_fold(
            q, k, v, cd, interpret))
    elif defect == "absent_experts_served":  # rows routed elsewhere fold onto the held experts
        sound = moe.route_sigmoid_top_k

        def folded(x, router, k, routed_scale, select_bias=None):
            p, top_p, top_e = sound(x, router, k, routed_scale, select_bias)
            return p, top_p, toy["first_expert_held"] + top_e % toy["num_experts"]

        monkeypatch.setattr(moe, "route_sigmoid_top_k", folded)
    elif defect == "softmax_gates":
        monkeypatch.setattr(moe, "route_sigmoid_top_k", lambda x, router, k, routed_scale, select_bias=None: (
            moe.route_top_k(x, router, k)))
    elif defect == "no_shared_expert":
        sound = decoder_lm.dense_swiglu
        monkeypatch.setattr(decoder_lm, "dense_swiglu", lambda x, g, u, d, cd: (
            0.0 if g.shape[1] == toy["shared_expert_intermediate_size"] else 1.0) * sound(x, g, u, d, cd))
    elif defect == "no_head_gate":
        sound = decoder_lm._matmul
        monkeypatch.setattr(decoder_lm, "_matmul", lambda a, w, cd: (
            jnp.full(a.shape[:-1] + w.shape[1:], 30.0) if w.shape[1] in (4, 8) else sound(a, w, cd)))
    got = system.fit()
    if defect == "half_the_steps":
        got["losses"] = got["losses"][:1]
    decoder_lm._train_program.cache_clear()
    limits = toy["check_limits"]
    result = system.compare(got, want)
    assert any(result[k] > limits[k] for k in limits), result


def test_cost_module_against_a_hand_count(config):
    """Published widths, 2 x 4,096 tokens a step (the issue's arithmetic a token)."""
    from flink_ml_tpu.models.lm.config import num_params

    shapes = laguna_lm_fit.create(config, 1, 1).layout_dims
    per_token = {k: v for k, v in shapes.items() if k not in ("tokens", "batch", "experts_held", "width")}
    layers, head = laguna_costs.forward_flops_per_token(**per_token)
    band = (512 * 4096 - 512 * 511 / 2) / 4096  # keys a query sees on average through a window of 512
    full = 2 * 2048 * (6144 + 2048) + 2 * 6144 * 2048 + 2 * 2048 * 48 + 2 * 2 * 2048 * 128 * 48  # 58.7 + 0.2 + 50.3 M
    windowed = 2 * 2048 * (8192 + 2048) + 2 * 8192 * 2048 + 2 * 2048 * 64 + 2 * 2 * band * 128 * 64  # 75.5 + .. + 15.7
    sparse = 2 * 2048 * 256 + 3 * 2 * 2048 * 512  # router 1.0, shared expert 6.3
    assert layers == pytest.approx(2 * full + 3 * windowed + 4 * sparse + 3 * 2 * 2048 * 8192)
    assert head == 2 * 2048 * 12544  # 51.4 M: the sliced untied head
    expert = 3 * 2 * 2048 * 512  # 6.3 M a held (token, expert) row
    assert 0.065 < head / (layers + head + 4 * expert) < 0.08  # the head is 7% of this cut
    rows = 4 * 8192 * 8 // 8
    flops, nbytes = laguna_costs.model(rows_held=rows, **shapes)
    assert flops == pytest.approx(3 * (8192 * (layers + head) + rows * expert))
    cfg = laguna_lm_fit.lm_config(config)
    assert nbytes == num_params(cfg) * 28 and num_params(cfg) == 691_624_960 == laguna_costs.params(**shapes)
    held_flops, held_bytes = laguna_costs.held_experts(rows_held=rows, **shapes)
    assert held_flops == 3 * 2 * rows * 3 * 2048 * 512
    assert held_bytes == 4 * 32 * 3 * 2048 * 512 * 8 + rows * (2 * 2048 + 3 * 512) * 2 * 3
    win_flops, win_bytes = laguna_costs.window_fold(**shapes)
    assert win_flops == 6 * 2 * (512 * 4096 - 512 * 511 / 2) * 128 * 64 * 2 * 3
    assert win_bytes == 2 * 4096 * 128 * 2 * 3 * (4 * 64 + 4 * 8)  # k, v once per key/value head


def test_the_new_reducers_on_recorded_counts():
    """The visited share from ``train.program``'s counts; a program that
    writes none (a stack without windowed layers) gives nothing to read."""
    from perfbench import program_spans
    from perfbench.reducers import program_span_pct

    def ctx_of(stats):
        table = program_spans.Table([program_spans.Span("train.program", 10.0 + i, 1.0, stats=s)
                                     for i, s in enumerate(stats)])
        return types.SimpleNamespace(run=types.SimpleNamespace(program_spans=table), w0=0.0, w1=100.0)

    counted = ctx_of([{"fold_win_chunks": 30720, "fold_win_chunks_visited": 11136}] * 3)
    assert program_span_pct.reduce(counted, "train.program", "fold_win_chunks_visited", "fold_win_chunks") == 36.25
    assert program_span_pct.reduce(ctx_of([{"fold_chunks": 9, "fold_chunks_visited": 5}]), "train.program",
                                   "fold_win_chunks_visited", "fold_win_chunks") is None
