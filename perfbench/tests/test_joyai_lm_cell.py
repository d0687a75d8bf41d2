"""The ``joyai_llm_flash`` configuration's own pieces, on the CPU at its ``toy``
sizes: the configuration against the catalog row, the manifest and every new
metric file against its entry, the benchmark's plain reference against the
program's, the cost module's counts against the program's parameter tree and a
hand count at the published widths, the new reducers on recorded counts, the
cell's rehearsal, and a timed path with part of the mathematics missing - the
multi-token-prediction term among them - coming out not correct."""
import json
import os
import types

import numpy as np
import pytest

from perfbench import joyai_costs
from perfbench.manifest import Manifest
from perfbench.systems import joyai_lm_fit

CELL = "joyai_llm_flash.fit_mla8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
#: What the cell reports: its own three, and the quantities it shares (before PR 53 under ``joyai_*`` and ``mla_attn_*``).
OWN = ("mla_latent_ms", "mtp_ms", "mtp_targets_pct")
SHARED = ("lm_step_ms", "lm_mfu_pct", "attn_ms", "attn_roofline", "moe_expert_ms", "moe_expert_roofline",
          "moe_held_share_pct", "moe_rows_carried_pct", "lm_scope_coverage_pct")
PUBLISHED = dict(seq=8192, hidden=2048, layers=5, dense_layers=1, dense_width=7168, heads=32, q_rank=1536,
                 kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128, experts=256, experts_held=16, width=768,
                 shared_width=768, mtp_depth=1, vocab=16160)


@pytest.fixture(scope="module")
def config():
    return Manifest().config("joyai_llm_flash")


@pytest.fixture(scope="module")
def toy(config):
    return {**config, **config["toy"]}


@pytest.fixture(scope="module")
def system(toy):
    s = joyai_lm_fit.create(toy, 2**31 + 5, 1)
    s.make_data()
    s.build()
    return s


@pytest.fixture(scope="module")
def want(system):
    return system.reference()


def test_the_manifest_has_no_problems_and_every_new_metric_file_matches_its_entry():
    manifest = Manifest()
    assert manifest.problems() == []
    assert manifest.cell_metrics("end_to_end", CELL) == ["fit_rows_per_s", "setup_s"]
    listed = manifest.cell_metrics("per_layer", CELL)
    assert set(listed) == {"fit_idle_pct", "fit_peak_hbm_gb", *OWN, *SHARED}
    for name in OWN + SHARED:
        entry, own = manifest.per_layer[name], manifest.layer_metric(name)
        assert (entry["workloads"] == [CELL]) == (name in OWN) and entry["moves"] == "fit_rows_per_s"
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert own[key] == entry[key], (name, key)
        assert os.path.exists(os.path.join(manifest.dir, "reducers", own["reducer"] + ".py"))
        assert name.endswith("_roofline") == (own["unit"] == "%" and "cost" in own["params"])
    assert len(manifest.cell(CELL)["why"]) <= 200 and len(manifest.configs["joyai_llm_flash"]["source"]) <= 200


def test_the_configuration_is_the_catalog_row_cut_in_depth_experts_and_vocabulary(config):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "JoyAI-LLM-Flash")
    published = row["config"]
    differs = sorted(k for k, v in published.items() if config.get(k, "absent") != v)
    assert differs == sorted(config["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    entry = Manifest().configs["joyai_llm_flash"]
    assert sorted(entry["reduced"]) == differs and entry["source"].startswith(row["source_url"])
    # the floors of the model-configs guide, and what is stated beside each cut
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4 and config["num_nextn_predict_layers"] == 1
    assert config["n_routed_experts"] >= 8 and config["vocab_size"] * 8 >= published["vocab_size"]
    assert published["vocab_size"] == config["vocab_size_published"]
    assert config["n_routed_experts_published"] == config["router_outputs"] == published["n_routed_experts"]
    assert config["num_hidden_layers_published"] == published["num_hidden_layers"]
    assert set(config["reduced"]) <= set(config["reduced_why"])
    assert set(config["check_limits"]) == set(config["toy"]["check_limits"])
    assert set(config["check_limits"]) <= set(config["check_limits_why"])  # every limit with its readings
    assert "mtp_loss_coef" in config["assumed"] and config["mtp_loss_coef"] == 0.3
    cell = Manifest().cell(CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "fit_mla8k")
    assert config["global_batch_size"] * config["max_iter"] == config["num_sequences"]  # one pass a job
    assert config["sequence_length"] == 8192


def test_the_costs_count_the_programs_tree_and_the_folds_work(config):
    """``joyai_costs.params`` is the program's own count; the fold's operations
    and bytes by hand at the published widths; the experts' on held rows."""
    from flink_ml_tpu.models.lm.config import num_params

    cfg = joyai_lm_fit.lm_config(config)
    assert joyai_costs.params(**PUBLISHED) == num_params(cfg) == 680_441_088
    flops, nbytes = joyai_costs.mla_fold(batch=1, **PUBLISHED)
    pairs = 8192 * 8192 / 2
    assert flops == 6 * 6 * 32 * pairs * (192 + 128)  # six folds, 32 heads, three matmuls' worth over 192 and over 128
    # q, dq at 192; o, do at 128; the 128-wide keys, values and their gradients a head; the rotary key a token
    assert nbytes == 6 * 2 * 8192 * (32 * (2 * 192 + 2 * 128 + 2 * 128 + 2 * 128) + 2 * 64)
    flops, nbytes = joyai_costs.held_experts(rows_held=1000, **PUBLISHED)
    assert flops == 3 * 2 * 1000 * 3 * 2048 * 768
    assert nbytes == 3 * 16 * 2048 * 768 * 5 * 8 + 1000 * (2 * 2048 + 3 * 768) * 2 * 3
    layers, head = joyai_costs.forward_flops_per_token(**PUBLISHED)
    attention = joyai_costs._attention_flops_per_token(**PUBLISHED)
    assert head == 2 * 2 * 2048 * 16160  # both passes
    assert attention == pytest.approx(136.577e6, rel=1e-4) and layers == pytest.approx(
        6 * attention + 3 * 2 * 2048 * 7168 + 5 * (2 * 2048 * 256 + 3 * 2 * 2048 * 768) + 2 * 4096 * 2048)
    flops, _ = joyai_costs.model(tokens=8192, rows_held=0, **PUBLISHED)
    assert flops == 3 * 8192 * (layers + head)


def test_the_new_reducers_on_recorded_counts(capsys):
    """The fold's and the held experts' share of their roofline from a
    recorded kernel time and ``train.drain``'s held rows; the module's targets
    over the tokens; a run of another layout, or of a program that writes no
    such count, gives nothing to read."""
    from perfbench import program_spans
    from perfbench.reducers import lm_roofline_pct, program_span_pct

    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    layout = dict(PUBLISHED, tokens=8192, batch=1)
    config = Manifest().config("joyai_llm_flash")

    def ctx_of(shapes, stats, op="flash_fold_bwd_dq.7"):
        table = program_spans.Table([program_spans.Span("train.drain", 10.0, 1.0, stats=stats)])
        return types.SimpleNamespace(
            run=types.SimpleNamespace(program_spans=table), config=config, w0=0.0, w1=100.0,
            facts={"layout": shapes, "steps": 4},
            peaks=peaks, per=lambda unit: 4, ops=lambda: [(op, 20.0, 800e6)])  # 0.8 s over 4 steps

    fold = "flash_fold_(fwd|bwd_dq|bwd_dkv)"
    drained = {"rows_held": 4 * 20_000, "steps": 4, "tokens": 4 * 8192, "mtp_targets": 4 * 8190}
    got = got_fold = lm_roofline_pct.reduce(ctx_of(layout, drained), "mla_fold", pattern=fold)
    flops, nbytes = joyai_costs.mla_fold(**layout)
    assert got == pytest.approx(100 * (flops / 197e12) / 0.2) and 0 < got < 100 and flops / 197e12 > nbytes / 819e9
    assert "bound by mxu" in capsys.readouterr().out
    got = lm_roofline_pct.reduce(ctx_of(layout, drained, "ragged-dot-none.3"), "held_experts", pattern="^ragged-dot")
    flops, nbytes = joyai_costs.held_experts(rows_held=20_000, **layout)
    assert got == pytest.approx(100 * max(flops / 197e12, nbytes / 819e9) / 0.2) and 0 < got < 100
    assert lm_roofline_pct.reduce(ctx_of({"tokens": 8192, "ssm_heads": 64}, drained), "mla_fold", pattern=fold) is None
    # no held-row count: nothing for the count that takes it; the fold's takes none and reads
    assert lm_roofline_pct.reduce(ctx_of(layout, {"steps": 4}, "ragged-dot-none.3"), "held_experts",
                                  pattern="^ragged-dot") is None
    assert lm_roofline_pct.reduce(ctx_of(layout, {"steps": 4}), "mla_fold", pattern=fold) == pytest.approx(got_fold)
    assert lm_roofline_pct.reduce(ctx_of(layout, drained), "mla_fold", pattern="^no_such_kernel") is None
    assert program_span_pct.reduce(ctx_of(layout, drained), "train.drain", "mtp_targets", "tokens") == \
        pytest.approx(100 * 8190 / 8192)
    assert program_span_pct.reduce(ctx_of(layout, {"steps": 4, "tokens": 9}), "train.drain", "mtp_targets",
                                   "tokens") is None


def test_benchmark_reference_agrees_with_the_programs(system, want, toy):
    """Two independent writings of the same equations, one seed: the program's
    reference (whole ``[T, T]`` scores, held experts in a Python loop, full
    AdamW) and the benchmark's (blocks, rematerialised, the first step's update
    from the gradient alone)."""
    import jax.numpy as jnp

    from flink_ml_tpu.models.lm import reference_joyai as program_reference
    from flink_ml_tpu.models.lm.decoder_lm import _flat_names, _ordered, init_params

    cfg = joyai_lm_fit.lm_config(toy)
    params = init_params(cfg, system.seed % 2**31)
    batches = [jnp.asarray(system.tok[:2]), jnp.asarray(system.tok[2:4])]
    _, grads = program_reference.loss_and_grads(params, batches[0], cfg)
    _, ahead = program_reference.losses(params, batches[0], cfg)
    _, losses, norms = program_reference.train_steps(
        params, batches, cfg, system.hyper["learning_rate"],
        weight_decay=system.hyper["weight_decay"], clip=system.hyper["clip_norm"])
    np.testing.assert_allclose(want["losses"], losses, rtol=2e-6)
    np.testing.assert_allclose(want["mtp_losses"][0], float(ahead), rtol=2e-6)
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
    stack = toy["num_hidden_layers"] - toy["first_k_dense_replace"]
    assert got["expert_rows"].shape == (stack + 1, toy["n_routed_experts_published"])  # the module's layer last
    # the loads agree but for a row at a tie's reach (float32, two orders of summation): one row here, two counts
    assert np.abs(got["expert_rows"][:stack] - want["expert_rows"][:stack]).sum() <= 4
    # the module's loads hold its filler's rows on the program's side: top-k a sequence more
    assert got["expert_rows"][stack].sum() - want["expert_rows"][stack].sum() == \
        toy["global_batch_size"] * toy["num_experts_per_tok"]
    control = system.compare(system.reference("bf16"), want)
    assert any(control[k] > limits[k] for k in limits), control


@pytest.mark.parametrize("case, fails", [
    ("one_layers_router_far_off", None),  # a row that changed expert: one layer, a heavy tail
    ("every_router_without_a_gradient", "routed_grad_norm_rel_err"),
    ("a_latent_leaf_scaled", "group_grad_norm_rel_err"),
    ("a_held_experts_leaf_missing", "expert_grad_norm_bias"),
    ("twice_the_rows_change_expert", "rows_changed_expert_pct"),
])
def test_the_routed_leaves_are_held_apart_from_the_rest(system, config, case, fails):
    """``compare`` on the reference's own numbers at the cell's limits, one
    thing off: the leaves a changed row moves in steps are held by the median
    over the expert layers, every other leaf by the worst."""
    layers = ["layers.1", "layers.2", "mtp.layer"]
    rows = np.full((3, 16), 512, np.int64)
    want = {"losses": [13.0, 15.0], "mtp_losses": [10.0], "grad_norms": [1.0], "expert_rows": rows,
            "group_norms": {"embed": 0.5, "layers.1.wkv_b": 0.1, **{f"{layer}.{leaf}": 0.01 for layer in layers
                            for leaf in ("router", "w_gate", "w_up", "w_down")}}}
    got = {**want, "group_norms": dict(want["group_norms"]), "expert_rows": rows.copy(),
           "steps_expected": 2, "rows_missing": 0}
    if case == "one_layers_router_far_off":
        got["group_norms"]["layers.2.router"] *= 1.6
    elif case == "every_router_without_a_gradient":
        got["group_norms"].update({f"{layer}.router": 0.0 for layer in layers})
    elif case == "a_latent_leaf_scaled":
        got["group_norms"]["layers.1.wkv_b"] *= 1.02
    elif case == "a_held_experts_leaf_missing":
        got["group_norms"]["mtp.layer.w_up"] = 0.0
    else:  # 1.2% of the stack's rows on another expert
        got["expert_rows"][:2, 0] -= 98
        got["expert_rows"][:2, 1] += 98
    values = system.compare(got, want)
    limits = config["check_limits"]
    assert {k for k in limits if values[k] > limits[k]} == ({fails} if fails else set()), values


@pytest.mark.parametrize("defect", ["no_module", "module_not_shifted", "rotate_half", "one_head_size",
                                    "absent_experts_served", "no_shared_expert", "half_the_steps"])
def test_a_broken_timed_path_is_not_correct(system, want, toy, defect, monkeypatch):
    """This system's own class with its timed path broken underneath."""
    import jax.numpy as jnp

    from flink_ml_tpu.models.lm import decoder_lm
    from flink_ml_tpu.parallel import moe

    decoder_lm._train_program.cache_clear()
    if defect == "no_module":  # the whole objective without its second term
        monkeypatch.setitem(system.dims, "mtp_loss_coef", 0.0)
    elif defect == "module_not_shifted":  # e_i beside h_i, scored on token i + 1: what the main head does
        sound = jnp.roll
        monkeypatch.setattr(jnp, "roll", lambda a, shift, axis=None: (
            a if jnp.issubdtype(a.dtype, jnp.integer) else sound(a, shift, axis=axis)))
    elif defect == "rotate_half":  # channel j with j + 32, the repo's other convention
        def half(x, cos, sin):
            r = toy["qk_rope_head_dim"]
            part = x[..., -r:]
            turned = part * cos[..., -r:] + jnp.concatenate([-part[..., r // 2:], part[..., : r // 2]], axis=-1) \
                * jnp.abs(sin[..., -r:])
            return jnp.concatenate([x[..., :-r], turned], axis=-1)

        monkeypatch.setattr(decoder_lm, "_rope_pairs", half)
    elif defect == "one_head_size":  # the scores scaled by the value head's size
        sound = decoder_lm._fold
        monkeypatch.setattr(decoder_lm, "_fold", lambda q, k, v, cd, interpret, window=None: sound(
            q * (q.shape[-1] / v.shape[-1]) ** 0.5, k, v, cd, interpret, window))
    elif defect == "absent_experts_served":  # rows routed elsewhere fold onto the held experts
        sound = moe.route_sigmoid_top_k

        def folded(x, router, k, routed_scale, select_bias=None):
            p, top_p, top_e = sound(x, router, k, routed_scale, select_bias)
            return p, top_p, toy["first_expert_held"] + top_e % toy["n_routed_experts"]

        monkeypatch.setattr(moe, "route_sigmoid_top_k", folded)
    elif defect == "no_shared_expert":
        sound = decoder_lm.dense_swiglu
        monkeypatch.setattr(decoder_lm, "dense_swiglu", lambda x, g, u, d, cd: (
            0.0 if g.shape[1] == toy["moe_intermediate_size"] else 1.0) * sound(x, g, u, d, cd))
    try:
        got = system.fit()
    finally:
        decoder_lm._train_program.cache_clear()
    if defect == "half_the_steps":
        got["losses"] = got["losses"][:1]
    limits = toy["check_limits"]
    values = system.compare(got, want)
    assert any(values[k] > limits[k] for k in limits), (defect, values)
    if defect == "no_module":  # the sum tells; the module still ran and its own loss is sound
        assert values["loss_rel_err"] > limits["loss_rel_err"] >= values["mtp_loss_rel_err"]
    if defect == "module_not_shifted":  # whatever the 0.3 hides in the sum, the module's own loss tells
        assert values["mtp_loss_rel_err"] > limits["mtp_loss_rel_err"]


def test_the_cell_rehearses_on_the_cpu(capsys):
    """The harness's own command at the configuration's ``toy`` sizes: set-up,
    a window, the check against the reference, the result line's shape."""
    from perfbench import run

    assert run.main(["--workload", CELL, "--seed", str(2**31 + 17), "--seconds", "0.5", "--trace", "0",
                     "--rehearse-on-cpu"]) == 0
    out = capsys.readouterr().out
    result = json.loads(next(line for line in out.splitlines() if line.startswith("rehearsal ")).split(" ", 1)[1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"fit_rows_per_s", "setup_s"}
    checks = [json.loads(line.split(" ", 1)[1]) for line in out.splitlines() if line.startswith("check ")]
    assert {c["name"] for c in checks} == set(Manifest().config("joyai_llm_flash")["check_limits"])
    assert all(c["ok"] for c in checks)
