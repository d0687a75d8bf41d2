"""The ``olmoe_1b_7b`` configuration's own pieces, on the CPU at its ``toy``
sizes: the benchmark's plain reference against the program's, the cost
module's operation counts against a hand count at the published widths, the
data generator, and a timed path with part of the mathematics missing coming
out not correct. (``test_rehearse.py`` drives the whole cell through
``--rehearse-on-cpu``; its broken-path cases patch ``sparse_lr_fit`` and so
cannot break this system - this file does.)"""
import json
import os

import numpy as np
import pytest

from perfbench import lm_costs
from perfbench.manifest import HERE, Manifest
from perfbench.systems import decoder_lm_fit

CELL = "olmoe_1b_7b.fit_packed4k"


@pytest.fixture(scope="module")
def config():
    return Manifest().config("olmoe_1b_7b")


@pytest.fixture(scope="module")
def toy(config):
    return {**config, **config["toy"]}


@pytest.fixture(scope="module")
def system(toy):
    s = decoder_lm_fit.create(toy, 2**31 + 5, 1)
    s.make_data()
    s.build()
    return s


@pytest.fixture(scope="module")
def want(system):
    return system.reference()


def test_the_configuration_is_the_catalog_row_cut_in_depth_only(config):
    published = {
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 1024, "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": False, "vocab_size": 50304,
    }
    differs = sorted(k for k, v in published.items() if config.get(k, "absent") != v)
    assert differs == config["reduced"] == ["num_hidden_layers"]
    assert config["sequence_length"] == published["max_position_embeddings"]
    assert set(config["check_limits"]) == set(config["toy"]["check_limits"])
    assert set(config["check_limits"]) <= set(config["check_limits_why"])  # every limit with its readings


def test_benchmark_reference_agrees_with_the_programs(system, want):
    """Two independent writings of the same equations, one seed: the program's
    reference (whole batch, whole ``[T, T]`` scores, dense experts in a Python
    loop, full AdamW) and the benchmark's (a sequence at a time, blocks, the
    first step's update from the gradient alone)."""
    import jax.numpy as jnp

    from flink_ml_tpu.models.lm import reference as program_reference
    from flink_ml_tpu.models.lm.config import LMConfig
    from flink_ml_tpu.models.lm.decoder_lm import _flat_names, _ordered, init_params

    d = system.dims
    cfg = LMConfig(d["num_hidden_layers"], d["hidden_size"], d["num_attention_heads"], d["num_experts"],
                   d["num_experts_per_tok"], d["intermediate_size"], d["vocab_size"],
                   float(d["rope_theta"]), float(d["rms_norm_eps"]), d["aux_coef"])
    params = init_params(cfg, system.seed % 2**31)
    batches = [jnp.asarray(system.tok[:2]), jnp.asarray(system.tok[2:4])]
    _, grads = program_reference.loss_and_grads(params, batches[0], cfg)
    _, losses, norms = program_reference.train_steps(
        params, batches, cfg, system.hyper["learning_rate"],
        weight_decay=system.hyper["weight_decay"], clip=system.hyper["clip_norm"])
    np.testing.assert_allclose(want["losses"], losses, rtol=2e-6)
    np.testing.assert_allclose(want["grad_norms"][0], norms[0], rtol=2e-5)
    for name, g in zip(_flat_names(cfg), _ordered(grads, cfg)):
        np.testing.assert_allclose(want["group_norms"][name], float(jnp.sqrt(jnp.sum(g * g))),
                                   rtol=1e-4, err_msg=name)


def test_the_sound_program_is_correct_and_the_control_is_not(system, want, toy):
    limits = toy["check_limits"]
    sound = system.compare(system.fit(), want)
    assert all(sound[k] <= limits[k] for k in limits), sound
    control = system.compare(system.reference("bf16"), want)
    assert control["loss_after_update_rel_err"] > limits["loss_after_update_rel_err"]
    assert control["grad_norm_rel_err"] > limits["grad_norm_rel_err"]


@pytest.mark.parametrize("defect", ["no_aux_term", "renormalised_top_k", "half_the_steps"])
def test_a_broken_timed_path_is_not_correct(system, want, toy, defect, monkeypatch):
    from flink_ml_tpu.models.lm import decoder_lm
    from flink_ml_tpu.parallel import moe

    decoder_lm._train_program.cache_clear()
    if defect == "no_aux_term":
        monkeypatch.setattr(decoder_lm, "_load_balancing", lambda routed, cfg: 0.0)
    elif defect == "renormalised_top_k":
        sound = moe.route_top_k

        def renormalised(x, router, k):
            p, top_p, top_e = sound(x, router, k)
            return p, top_p / top_p.sum(axis=-1, keepdims=True), top_e

        monkeypatch.setattr(moe, "route_top_k", renormalised)
    else:
        monkeypatch.setattr(system, "steps", system.steps)  # restored after the test
        got = system.fit()
        got["losses"] = got["losses"][:1]
    if defect != "half_the_steps":
        got = system.fit()
    decoder_lm._train_program.cache_clear()
    limits = toy["check_limits"]
    result = system.compare(got, want)
    assert any(result[k] > limits[k] for k in limits), result


def test_tokens_from_the_seed(config):
    d = config["documents"]
    args = (32, 4096, config["vocab_size"], config["eot_token_id"], d["median_tokens"],
            d["lognormal_sigma"], d["token_zipf_alpha"])
    a = decoder_lm_fit.make_tokens(2**31 + 7, *args)
    assert a.shape == (32, 4096) and a.dtype == np.int32
    assert a.min() >= 0 and a.max() < config["vocab_size"]
    np.testing.assert_array_equal(a, decoder_lm_fit.make_tokens(2**31 + 7, *args))
    assert (a != decoder_lm_fit.make_tokens(2**31 + 8, *args)).mean() > 0.5
    ends = np.flatnonzero(a.ravel() == config["eot_token_id"])
    lengths = np.diff(ends)
    assert 50 < len(ends) < 600 and 200 < np.median(lengths) < 1500  # median 600, heavy tail
    top = np.bincount(a.ravel()).max() / a.size
    assert 0.03 < top < 0.3  # Zipf: the commonest id takes a large share


def test_cost_module_against_a_hand_count(config):
    """Published widths, 16,384 tokens a step (the issue's arithmetic)."""
    shapes = decoder_lm_fit.create(config, 1, 1).layout_dims
    layer, head = lm_costs.forward_flops_per_token(**{k: v for k, v in shapes.items() if k != "tokens"})
    projections = 4 * 2 * 2048 * 2048  # 33.6 M
    scores = 2 * 2 * 2048 * 128 * 16  # QK^T and PV over half of 4,096 keys: 16.8 M
    router = 2 * 2048 * 64
    experts = 8 * 3 * 2 * 2048 * 1024  # 100.7 M
    assert layer == projections + scores + router + experts
    assert head == 2 * 2048 * 50304  # 206 M
    assert abs(head / (layer + head) - 0.58) < 0.01  # the head's share of this cut
    flops, _ = lm_costs.model(**shapes)
    assert flops == 3 * 16384 * (layer + head) and abs(flops / 17.6e12 - 1) < 0.01
    moe_flops, _ = lm_costs.moe_experts(**shapes)
    assert moe_flops == 3 * 2 * 131072 * 3 * 2048 * 1024
    attn_flops, attn_bytes = lm_costs.attention_fold(**shapes)
    assert attn_flops == 6 * 2 * (4096 * 4096 / 2) * 128 * 16 * 4
    assert attn_bytes == 8 * 4 * 16 * 4096 * 128 * 2


def test_every_new_metric_file_matches_its_entry():
    m = Manifest()
    names = [n for n, e in m.per_layer.items() if CELL in e.get("workloads", [])]
    assert {"lm_step_ms", "lm_mfu_pct", "attn_ms", "attn_roofline", "moe_expert_ms", "moe_expert_roofline",
            "lm_init_s", "lm_readback_s", "moe_load_max_over_mean"} <= set(names)  # the nine PR 26 brought
    assert [n for n in names if m.per_layer[n]["workloads"] == [CELL]] == \
        ["lm_init_s", "lm_readback_s", "moe_load_max_over_mean"]  # the other six are shared since PR 53
    for name in names:
        spec = json.load(open(os.path.join(HERE, "layer_metrics", f"{name}.json")))
        entry = m.per_layer[name]
        assert {k: spec[k] for k in ("unit", "better", "source", "layer", "moves")} == \
            {k: entry[k] for k in ("unit", "better", "source", "layer", "moves")}
