"""The ``zaya1_8b`` configuration's own pieces, on the CPU at its ``toy``
sizes: the configuration against the catalog row, the benchmark's plain
reference against the program's, the cost module's counts against a hand
count at the published widths, the new reducers on recorded counts, the whole
cell through ``--rehearse-on-cpu``, and a timed path with part of the
mathematics missing coming out not correct. (``test_rehearse.py``'s
broken-path cases patch ``sparse_lr_fit`` and so cannot break this system's
class - this file does.)"""
import json
import os
import types

import numpy as np
import pytest

from perfbench import zaya_costs
from perfbench.manifest import HERE, Manifest
from perfbench.systems import zaya_lm_fit
from perfbench.tests.test_rehearse import rehearsal_of, run_cell

CELL = "zaya1_8b.fit_packed8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def config():
    return Manifest().config("zaya1_8b")


@pytest.fixture(scope="module")
def toy(config):
    return {**config, **config["toy"]}


@pytest.fixture(scope="module")
def system(toy):
    s = zaya_lm_fit.create(toy, 2**31 + 5, 1)
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
        row = next(r for r in map(json.loads, f) if r["name"] == "ZAYA1-8B")
    published = row["config"]
    differs = sorted(k for k, v in published.items() if config.get(k, "absent") != v)
    assert differs == sorted(config["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    entry = Manifest().configs["zaya1_8b"]
    assert sorted(entry["reduced"]) == differs and entry["source"].startswith(row["source_url"])
    # the floors of the model-configs guide, and what is stated beside each cut
    assert config["num_hidden_layers"] >= 5 and config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"] == config["vocab_size_published"]
    assert config["num_experts_published"] == config["router_outputs"] == published["num_experts"]
    assert set(config["reduced"]) <= set(config["reduced_why"])
    assert set(config["check_limits"]) == set(config["toy"]["check_limits"])
    assert set(config["check_limits"]) <= set(config["check_limits_why"])  # every limit with its readings


def test_benchmark_reference_agrees_with_the_programs(system, want):
    """Two independent writings of the same equations, one seed: the program's
    reference (whole ``[T, T]`` scores, held experts in a Python loop, full
    AdamW) and the benchmark's (blocks, rematerialised, the first step's update
    from the gradient alone)."""
    import jax.numpy as jnp

    from flink_ml_tpu.models.lm import reference_zaya as program_reference
    from flink_ml_tpu.models.lm.config import LMConfig
    from flink_ml_tpu.models.lm.decoder_lm import _flat_names, _ordered, init_params

    d = system.dims
    cfg = LMConfig(d["num_hidden_layers"], d["hidden_size"], d["num_attention_heads"],
                   d["num_experts_published"], d["num_experts_per_tok"], d["moe_intermediate_size"],
                   d["vocab_size"], d["rope_theta"], float(d["rms_norm_eps"]), 0.0, "zaya", True,
                   d["num_experts"], d["first_expert_held"], d["num_key_value_heads"], d["head_dim"],
                   d["partial_rotary_factor"], d["router_hidden_size"])
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
    share, fullest = system.held_share(got["expert_rows"])
    assert 0.25 < share < 0.75 and fullest < 4.0
    control = system.compare(system.reference("bf16"), want)
    assert any(control[k] > limits[k] for k in limits), control


@pytest.mark.parametrize("defect", ["absent_experts_served", "gate_left_out", "no_depth_averaging",
                                    "untied_gradient", "half_the_steps"])
def test_a_broken_timed_path_is_not_correct(system, want, toy, defect, monkeypatch):
    """This system's own class with its timed path broken underneath."""
    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.models.lm import decoder_lm
    from flink_ml_tpu.parallel import moe

    decoder_lm._train_program.cache_clear()
    if defect == "absent_experts_served":  # rows routed elsewhere fold onto the held experts
        sound = moe.route_top_k

        def folded(x, router, k):
            p, top_p, top_e = sound(x, router, k)
            return p, top_p, top_e % system.dims["num_experts"]

        monkeypatch.setattr(moe, "route_top_k", folded)
    elif defect == "gate_left_out":
        sound = moe.route_top_k
        monkeypatch.setattr(moe, "route_top_k",
                            lambda x, router, k: (lambda p, tp, te: (p, jnp.ones_like(tp), te))(*sound(x, router, k)))
    elif defect == "no_depth_averaging":
        sound = decoder_lm._router_state
        monkeypatch.setattr(decoder_lm, "_router_state", lambda u, layer, carry: sound(u, layer, None))
    elif defect == "untied_gradient":  # the head's matmuls leave the table's gradient
        monkeypatch.setattr(decoder_lm, "_head",
                            lambda params, cfg: jax.lax.stop_gradient(params["embed"]).T)
    got = system.fit()
    if defect == "half_the_steps":
        got["losses"] = got["losses"][:1]
    decoder_lm._train_program.cache_clear()
    limits = toy["check_limits"]
    result = system.compare(got, want)
    assert any(result[k] > limits[k] for k in limits), result


def test_cost_module_against_a_hand_count(config):
    """Published widths, 2 x 8,192 tokens a step (the issue's arithmetic)."""
    from flink_ml_tpu.models.lm.config import LMConfig, num_params

    shapes = zaya_lm_fit.create(config, 1, 1).layout_dims
    per_token = {k: v for k, v in shapes.items() if k not in ("tokens", "batch", "layers", "experts_held", "width")}
    layer, head = zaya_costs.forward_flops_per_token(**per_token)
    projections = 2 * 2048 * (1024 + 256 + 256) + 2 * 1024 * 2048  # wq, wk, two value heads; wo: 10.5 M
    convolution = 2 * 2 * 10 * 128 * 128  # two taps over each head's channels
    scores = 2 * 2 * 4096 * 128 * 8  # QK^T and PV over half of 8,192 keys at 8 heads: 16.8 M
    router = 2 * 2048 * 256 + 2 * 2 * 256 * 256 + 2 * 256 * 16
    assert layer == projections + convolution + scores + router
    assert head == 2 * 2048 * 32784  # 134.3 M: the sliced tied head
    expert = 3 * 2 * 2048 * 2048  # 25.2 M where the token's expert is held
    assert abs(head / (6 * (layer + expert / 2) + head) - 0.35) < 0.01  # at a held share of a half
    rows = 6 * 16384 // 2
    flops, nbytes = zaya_costs.model(rows_held=rows, **shapes)
    assert flops == 3 * (16384 * (6 * layer + head) + rows * expert)
    cfg = LMConfig(6, 2048, 8, 16, 1, 2048, 32784, block="zaya", tied=True, experts_held=8, n_kv_heads=2,
                   head_size=128, rope_fraction=0.5, router_width=256)
    assert nbytes == num_params(cfg) * 28 and num_params(cfg) == 708_659_980
    held_flops, held_bytes = zaya_costs.held_experts(rows_held=rows, **shapes)
    assert held_flops == 3 * 2 * rows * 3 * 2048 * 2048
    assert held_bytes == 6 * 8 * 3 * 2048 * 2048 * 8 + rows * (2 * 2048 + 3 * 2048) * 2 * 3
    attn_flops, attn_bytes = zaya_costs.attention_fold(**shapes)
    assert attn_flops == 6 * 2 * (8192 * 8192 / 2) * 128 * 8 * 2 * 6
    # q, o and their gradients at 8 heads; k, v and theirs at 2: not 8 x 8 as a repeated K and V would be
    assert attn_bytes == 2 * 8192 * 128 * 2 * 6 * (4 * 8 + 4 * 2)


def test_the_new_reducers_on_recorded_counts(config):
    """``rows_held`` a step and the held share from ``train.drain`` counts; a
    program that writes none (the parent) gives nothing to read and no error."""
    from perfbench import program_spans
    from perfbench.reducers import lm_roofline_pct, program_span_ratio_max, program_span_share_pct

    def ctx_of(stats):
        table = program_spans.Table([program_spans.Span("train.drain", 10.0 + i, 1.0, stats=s)
                                     for i, s in enumerate(stats)])
        run = types.SimpleNamespace(program_spans=table)
        return types.SimpleNamespace(run=run, w0=0.0, w1=100.0)

    counted = ctx_of([{"steps": 8, "rows_held": 400_000, "rows_absent": 386_432, "held_rows_max": 3000,
                       "held_rows_mean": 1041.7},
                      {"steps": 8, "rows_held": 380_000, "rows_absent": 406_432, "held_rows_max": 2500,
                       "held_rows_mean": 989.6}])
    assert lm_roofline_pct.rows_held_per_step(counted) == 780_000 / 16
    share = program_span_share_pct.reduce(counted, "train.drain", "rows_held", "rows_absent")
    assert abs(share - 100 * 780_000 / (2 * 786_432)) < 1e-9
    ratio = program_span_ratio_max.reduce(counted, "train.drain", "held_rows_max", "held_rows_mean")
    assert abs(ratio - 3000 / 1041.7) < 1e-9
    parent = ctx_of([{"steps": 8, "expert_rows_max": 9, "expert_rows_mean": 3}])
    assert lm_roofline_pct.rows_held_per_step(parent) is None
    assert program_span_share_pct.reduce(parent, "train.drain", "rows_held", "rows_absent") is None
    assert program_span_ratio_max.reduce(parent, "train.drain", "held_rows_max", "held_rows_mean") is None


def test_the_cell_reports_the_shared_quantities_under_their_one_name():
    """(``test_manifest.py`` holds every listed metric to its file, its
    reducer and this cell's configuration keys.)"""
    m = Manifest()
    assert {"lm_step_ms", "lm_mfu_pct", "attn_ms", "attn_roofline", "moe_expert_ms", "moe_expert_roofline",
            "moe_held_share_pct", "held_load_max_over_mean"} <= set(m.cell_metrics("per_layer", CELL))
    assert m.config("zaya1_8b")["perf"]["costs"] == "zaya_costs"
    assert len(m.cells[CELL]["why"]) <= 200 and "twice its share" in m.cells[CELL]["why"]
    assert CELL in m.end_to_end["fit_rows_per_s"]["workloads"]


#: ``perfbench.run`` with this cell's own system class broken underneath: the
#: warm-up fit is sound, every fit of the window reports one step too few.
BREAK = """
import sys
from perfbench.systems import zaya_lm_fit
sound, calls = zaya_lm_fit.ZayaLmFit.fit, []
def fit(self):
    calls.append(1)
    out = sound(self)
    if len(calls) > 1:
        out["losses"] = out["losses"][:-1]
    return out
zaya_lm_fit.ZayaLmFit.fit = fit
from perfbench import run
sys.exit(run.main(sys.argv[1:]))
"""


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell_and_of_its_broken_class(trace, tmp_path):
    from perfbench.manifest import ROOT

    args = ("--workload", CELL, "--seed", str(2**31 + 11), "--seconds", "1", "--trace", str(trace),
            "--rehearse-on-cpu")
    out = rehearsal_of(run_cell(ROOT, *args))
    assert out["correct"] is True and out["attempted"] >= 1 and out["failed"] == 0
    if trace:
        assert {"moe_held_share_pct", "held_load_max_over_mean"} <= set(out["metrics"])
        return
    assert set(out["metrics"]) == {"fit_rows_per_s", "setup_s"}
    import subprocess
    import sys

    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    broken = subprocess.run([sys.executable, "-c", BREAK, *args], cwd=ROOT, env=env, capture_output=True,
                            text=True, timeout=900)
    assert rehearsal_of(broken)["correct"] is False
