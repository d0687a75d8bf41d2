"""The ``ouro_2_6b`` configuration's own pieces, on the CPU at its ``toy``
sizes: the configuration against the catalog row, the benchmark's plain
reference (a chain of single layer applications with the chain rule written
out) against the program's (``jax.grad`` of the whole loop), the cost module's
counts against a hand count at the published widths, the new reducers on
recorded counts, the whole cell through ``--rehearse-on-cpu``, and a reference
or a timed path with part of the mathematics missing coming out not correct."""
import json
import os
import types

import numpy as np
import pytest

from perfbench import ouro_costs
from perfbench.manifest import HERE, Manifest
from perfbench.systems import ouro_lm_fit
from perfbench.tests.test_rehearse import rehearsal_of, run_cell

CELL = "ouro_2_6b.fit_looped4k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SHARED = ("lm_step_ms", "lm_mfu_pct", "attn_ms", "attn_roofline")  # before PR 53: ouro_step_ms, ..., loop_attn_roofline


@pytest.fixture(scope="module")
def config():
    return Manifest().config("ouro_2_6b")


@pytest.fixture(scope="module")
def toy(config):
    return {**config, **config["toy"]}


@pytest.fixture(scope="module")
def system(toy):
    s = ouro_lm_fit.create(toy, 2**31 + 5, 1)
    s.make_data()
    s.build()
    return s


@pytest.fixture(scope="module")
def want(system):
    return system.reference()


@pytest.fixture(scope="module")
def got(system):
    return system.fit()


def test_the_configuration_is_the_catalog_row_cut_in_depth_alone(config):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    published = row["config"]
    differs = sorted(k for k, v in published.items() if config.get(k, "absent") != v)
    assert differs == config["reduced"] == ["num_hidden_layers"]
    entry = Manifest().configs["ouro_2_6b"]
    assert entry["reduced"] == differs and entry["source"] == row["source_url"]
    # the floor of the model-configs guide, the loop and the vocabulary as published, the arithmetic written down
    assert config["num_hidden_layers"] == 6 >= 4 and config["num_hidden_layers_published"] == published["num_hidden_layers"]
    assert config["total_ut_steps"] == 4 and config["vocab_size"] == 49152 and not config["tie_word_embeddings"]
    assert "509,661,185" in config["reduced_why"]["num_hidden_layers"] and "8.15 GB" in config["reduced_why"]["num_hidden_layers"]
    assert {"stands_for", "assumed", "toy"} <= set(config) and "what_the_cut_costs" in config["reduced_why"]
    assert {"sandwich_norm", "qk_norm", "loop_state", "exit_gate", "exit_distribution", "loss", "optimizer",
            "init_std", "sequence_length", "packing", "documents"} <= set(config["assumed"])
    assert set(config["check_limits"]) == set(config["toy"]["check_limits"])
    assert set(config["check_limits"]) <= set(config["check_limits_why"])  # every limit with its readings
    traffic = Manifest().traffic("fit_looped4k")
    assert traffic["kind"] == "fit_loop"
    assert (config["max_iter"], config["global_batch_size"], config["sequence_length"], config["num_sequences"]) == \
        (4, 2, 4096, 8)


def _program_side(system, **changed):
    import jax.numpy as jnp

    from flink_ml_tpu.models.lm import reference_ouro as program_reference
    from flink_ml_tpu.models.lm.config import LMConfig
    from flink_ml_tpu.models.lm.decoder_lm import init_params

    d = system.dims
    cfg = LMConfig(d["num_hidden_layers"], d["hidden_size"], d["num_attention_heads"], 0, 0,
                   d["intermediate_size"], d["vocab_size"], float(d["rope_theta"]), float(d["rms_norm_eps"]), 0.0,
                   "ouro", loops=d["total_ut_steps"], exit_beta=d["exit_entropy_coef"])._replace(**changed)
    params = init_params(cfg, system.seed % 2**31)
    batches = [jnp.asarray(system.tok[:2]), jnp.asarray(system.tok[2:4])]
    return program_reference, cfg, params, batches


def test_benchmark_reference_agrees_with_the_programs(system, want):
    """Two independent writings of the same equations, one seed: the program's
    reference (whole ``[T, T]`` scores, ``jax.grad`` through the Python loop of
    passes, full AdamW) and the benchmark's (a layer application at a time, the
    chain rule written out, the first step's update from the gradient alone)."""
    import jax.numpy as jnp

    from flink_ml_tpu.models.lm.decoder_lm import _flat_names, _ordered

    program_reference, cfg, params, batches = _program_side(system)
    _, grads = program_reference.loss_and_grads(params, batches[0], cfg)
    _, losses, norms, trips = program_reference.train_steps(
        params, batches, cfg, system.hyper["learning_rate"],
        weight_decay=system.hyper["weight_decay"], clip=system.hyper["clip_norm"])
    np.testing.assert_allclose(want["losses"], losses, rtol=2e-6)
    np.testing.assert_allclose(want["trip_losses"], trips[0], rtol=2e-6)
    np.testing.assert_allclose(want["grad_norms"][0], norms[0], rtol=2e-5)
    assert set(want["group_norms"]) == set(_flat_names(cfg))
    for name, g in zip(_flat_names(cfg), _ordered(grads, cfg)):
        np.testing.assert_allclose(want["group_norms"][name], float(jnp.sqrt(jnp.sum(g * g))),
                                   rtol=1e-4, err_msg=name)


def test_the_sound_program_is_correct_and_the_control_is_not(system, want, got, toy):
    limits = toy["check_limits"]
    sound = system.compare(got, want)
    assert all(sound[k] <= limits[k] for k in limits), sound
    assert len(got["trip_losses"]) == toy["total_ut_steps"] == len(want["trip_losses"])
    control = system.compare(system.reference("bf16"), want)
    assert any(control[k] > limits[k] for k in limits), control


@pytest.mark.parametrize("defect", ["three_passes", "no_entropy_term", "uniform_exits"])
def test_a_reference_with_part_of_the_mathematics_missing_is_not_correct(system, got, toy, defect, monkeypatch):
    """The sound program held against a reference that runs one pass fewer, or
    whose loss drops the gate's term (the entropy, or the gate's weights
    altogether): not ``correct``."""
    from perfbench.references import ouro_lm as reference

    if defect == "three_passes":
        monkeypatch.setitem(system.dims, "total_ut_steps", toy["total_ut_steps"] - 1)
    elif defect == "no_entropy_term":
        monkeypatch.setitem(system.dims, "exit_entropy_coef", 0.0)
    else:
        import jax

        monkeypatch.setattr(jax.nn, "sigmoid", lambda z: 0.0 * z + 0.5)  # the gate's logits no longer weigh the exits
        for piece in (reference._objective_fwd, reference._objective_bwd):
            piece.clear_cache()
    try:
        result = system.compare(got, system.reference())
    finally:
        for piece in (reference._objective_fwd, reference._objective_bwd):
            piece.clear_cache()
    limits = toy["check_limits"]
    assert any(result[k] > limits[k] for k in limits), result


@pytest.mark.parametrize("defect", ["a_pass_left_out", "gate_without_gradient", "norms_one_percent_off",
                                    "last_pass_in_every_exit", "half_the_steps"])
def test_a_broken_timed_path_is_not_correct(system, want, toy, defect, monkeypatch):
    """This system's own class with its timed path broken underneath."""
    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.models.lm import decoder_lm

    decoder_lm._train_program.cache_clear()
    if defect == "a_pass_left_out":
        sound = jax.lax.scan
        monkeypatch.setattr(jax.lax, "scan", lambda f, init, xs=None, length=None, **kw: sound(
            f, init, xs, length=length if xs is not None or length != toy["total_ut_steps"] else length - 1, **kw))
    elif defect == "gate_without_gradient":
        sound = decoder_lm._exit_distribution
        monkeypatch.setattr(decoder_lm, "_exit_distribution", lambda gate: sound(jax.lax.stop_gradient(gate)))
    elif defect == "norms_one_percent_off":
        sound = decoder_lm._rms_norm
        monkeypatch.setattr(decoder_lm, "_rms_norm", lambda x, w, eps: 1.01 * sound(x, w, eps))
    elif defect == "last_pass_in_every_exit":  # the head fed the last pass's state four times over
        sound = decoder_lm._next_token_nll
        monkeypatch.setattr(decoder_lm, "_next_token_nll", lambda h, w, tok, cd: sound(
            jnp.tile(h[-2:], (h.shape[0] // 2, 1, 1)), w, tok, cd))
    got = system.fit()
    if defect == "half_the_steps":
        got["losses"] = got["losses"][:1]
    decoder_lm._train_program.cache_clear()
    limits = toy["check_limits"]
    result = system.compare(got, want)
    assert any(result[k] > limits[k] for k in limits), result
    if defect == "last_pass_in_every_exit":  # the weighted sum hides less than a pass's own loss shows
        assert result["trip_loss_rel_err"] > result["loss_rel_err"]


def test_cost_module_against_a_hand_count(config):
    """Published widths, 2 x 4,096 tokens a step, six layers four times (the
    issue's arithmetic)."""
    from flink_ml_tpu.models.lm.config import LMConfig, num_params

    shapes = ouro_lm_fit.create(config, 1, 1).layout_dims
    per_token = {k: v for k, v in shapes.items() if k not in ("tokens", "batch", "layers", "loops")}
    block, head = ouro_costs.forward_flops_per_token(**per_token)
    projections = 4 * 2 * 2048 * 2048  # wq, wk, wv, wo: 33.6 M
    scores = 2 * 2 * 2048 * 128 * 16  # QK^T and PV over half of 4,096 keys at 16 heads: 16.8 M
    swiglu = 3 * 2 * 2048 * 5632  # 69.2 M
    assert block == projections + scores + swiglu and abs(block - 119.5e6) < 0.1e6
    assert head == 2 * 2048 * 49152 + 2 * 2048  # the head and the gate, once a pass
    assert abs(24 * block / 1e9 - 2.87) < 0.01 and abs(4 * head / 1e9 - 0.81) < 0.01
    assert abs(4 * head / (24 * block + 4 * head) - 0.22) < 0.005  # the heads' share of this cut
    assert abs(4 * head / (192 * block + 4 * head) - 0.034) < 0.001  # and of the published 48 layers
    flops, nbytes = ouro_costs.model(**shapes)
    assert flops == 3 * 8192 * 4 * (6 * block + head) and abs(flops / 1e12 - 90.3) < 0.1
    cfg = LMConfig(6, 2048, 16, 0, 0, 5632, 49152, block="ouro", loops=4)
    assert nbytes == num_params(cfg) * 28 and num_params(cfg) == 509_661_185 == ouro_costs.params(**shapes)
    attn_flops, attn_bytes = ouro_costs.attention_fold(**shapes)
    assert attn_flops == 6 * 2 * (4096 * 4096 / 2) * 128 * 16 * 2 * 24  # 24 applications, not 6 layers
    assert attn_bytes == 8 * 2 * 16 * 4096 * 128 * 2 * 24
    # one pass of six layers is OLMoE's fold shape per sequence and layer
    from perfbench import lm_costs

    olmoe = lm_costs.attention_fold(batch=2, heads=16, seq=4096, hidden=2048, layers=6)
    assert (attn_flops, attn_bytes) == (4 * olmoe[0], 4 * olmoe[1])


def test_the_new_reducers_on_recorded_counts():
    """The mean exit pass from ``train.drain`` sums; a program that writes none
    (the parent) gives nothing to read and no error; the two cost reducers give
    nothing for a layout without ``loops``."""
    from perfbench import program_spans
    from perfbench.reducers import lm_mfu_pct, lm_roofline_pct, program_span_ratio

    config = Manifest().config("ouro_2_6b")

    def ctx_of(stats, layout=None):
        table = program_spans.Table([program_spans.Span("train.drain", 10.0 + i, 1.0, stats=s)
                                     for i, s in enumerate(stats)])
        run = types.SimpleNamespace(program_spans=table)
        return types.SimpleNamespace(run=run, config=config, w0=0.0, w1=100.0, facts={"layout": layout, "steps": 4},
                                     peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
                                     per=lambda unit: 4, ops=lambda: [], trace=types.SimpleNamespace(modules={}),
                                     dev=None)

    counted = ctx_of([{"steps": 4, "tokens": 32768, "exit_trip_sum": 61440.0, "exit_last_mass": 4096.0},
                      {"steps": 4, "tokens": 32768, "exit_trip_sum": 63488.0, "exit_last_mass": 4100.0}])
    assert program_span_ratio.reduce(counted, "train.drain", "exit_trip_sum", "tokens") == 124928.0 / 65536
    parent = ctx_of([{"steps": 8, "tokens": 131072, "expert_rows_max": 9, "expert_rows_mean": 3}])
    assert program_span_ratio.reduce(parent, "train.drain", "exit_trip_sum", "tokens") is None
    other = ctx_of([], layout={"tokens": 16384, "layers": 1})
    assert lm_mfu_pct.reduce(other, "flash_fold_fwd") is None
    assert lm_roofline_pct.reduce(other, "attention_fold", pattern="flash_fold_(fwd|bwd_dq|bwd_dkv)") is None


def test_every_new_metric_file_matches_its_entry():
    m = Manifest()
    names = [n for n, e in m.per_layer.items() if e.get("workloads") == [CELL]]
    assert names == ["loop_exit_mean_trip"]  # what only a looped stack has; the rest it shares
    for name in names + list(SHARED):
        with open(os.path.join(HERE, "layer_metrics", f"{name}.json"), encoding="utf-8") as f:
            spec = json.load(f)
        entry = m.per_layer[name]
        assert {k: spec[k] for k in ("unit", "better", "source", "layer", "moves")} == \
            {k: entry[k] for k in ("unit", "better", "source", "layer", "moves")}
    assert m.problems() == []
    assert len(m.cells[CELL]["why"]) <= 200 and m.cells[CELL]["chips"] == 1
    assert CELL in m.end_to_end["fit_rows_per_s"]["workloads"]
    assert set(SHARED) | {"loop_exit_mean_trip", "fit_idle_pct", "fit_peak_hbm_gb"} <= set(m.cell_metrics("per_layer", CELL))


#: ``perfbench.run`` with this cell's own system class broken underneath: the
#: warm-up fit is sound, every fit of the window reports a pass's loss that is another's.
BREAK = """
import sys
from perfbench.systems import ouro_lm_fit
sound, calls = ouro_lm_fit.OuroLmFit.fit, []
def fit(self):
    calls.append(1)
    out = sound(self)
    if len(calls) > 1:
        out["trip_losses"][0] = out["trip_losses"][-1]
    return out
ouro_lm_fit.OuroLmFit.fit = fit
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
        assert 1.0 < out["metrics"]["loop_exit_mean_trip"]["value"] < 3.0  # toy: three passes
        return
    assert set(out["metrics"]) == {"fit_rows_per_s", "setup_s"}
    import subprocess
    import sys

    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    broken = subprocess.run([sys.executable, "-c", BREAK, *args], cwd=ROOT, env=env, capture_output=True,
                            text=True, timeout=900)
    assert rehearsal_of(broken)["correct"] is False
