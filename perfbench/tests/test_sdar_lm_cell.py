"""The ``sdar_30b_a3b`` configuration's own pieces, on the CPU at its ``toy``
sizes: the configuration against the catalog row, the manifest and every new
metric file against its entry, the benchmark's plain reference against the
program's (the same masks from the same seed among the rest), the cost
module's counts against the program's parameter tree and a hand count at the
published widths, the new reducers on recorded counts, the cell's rehearsal,
and a timed path with part of the mathematics missing - each rule of the mask,
the target, the weight, the positions, the gates, the QK-norm - coming out not
correct."""
import json
import os
import types

import numpy as np
import pytest

from perfbench import sdar_costs
from perfbench.manifest import Manifest
from perfbench.systems import sdar_lm_fit

CELL = "sdar_30b_a3b.fit_bd4k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
#: What the cell reports: what only block diffusion has, and the quantities it shares (before PR 53 under ``sdar_*``).
OWN = ("bd_attn_ms", "bd_attn_roofline", "bd_chunks_visited_pct", "bd_noise_ms", "bd_targets_pct", "lm_proj_ms")
SHARED = ("lm_step_ms", "lm_mfu_pct", "moe_expert_ms", "moe_expert_roofline", "moe_held_share_pct",
          "moe_rows_carried_pct", "lm_scope_coverage_pct", "lm_permute_ms", "lm_head_ms", "lm_opt_ms")
PUBLISHED = dict(seq=4096, block=4, hidden=2048, layers=6, heads=32, kv_heads=4, head_dim=128, experts=128,
                 experts_held=16, width=768, vocab=18992)


@pytest.fixture(scope="module")
def config():
    return Manifest().config("sdar_30b_a3b")


@pytest.fixture(scope="module")
def toy(config):
    return {**config, **config["toy"]}


@pytest.fixture(scope="module")
def system(toy):
    s = sdar_lm_fit.create(toy, 2**31 + 5, 1)
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
    # the new kernels' names are read by this cell's metrics alone: no accepted pattern finds them
    for name, entry in manifest.per_layer.items():
        pattern = manifest.layer_metric(name)["params"].get("pattern", "")
        if name not in OWN and "flash_fold" in str(pattern):
            import re

            assert not any(re.search(pattern, f"flash_fold_bd_{part}") for part in ("fwd", "bwd_dq", "bwd_dkv")), name
    assert len(manifest.cell(CELL)["why"]) <= 200 and len(manifest.configs["sdar_30b_a3b"]["source"]) <= 200
    assert "1/8 load" in manifest.cell(CELL)["why"]


def test_the_configuration_is_the_catalog_row_cut_in_depth_experts_and_vocabulary(config):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "SDAR-30B-A3B-Chat")
    published = row["config"]
    differs = sorted(k for k, v in published.items() if config.get(k, "absent") != v)
    assert differs == sorted(config["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    entry = Manifest().configs["sdar_30b_a3b"]
    assert sorted(entry["reduced"]) == differs and entry["source"].startswith(row["source_url"])
    # the floors of the model-configs guide, and what is stated beside each cut
    assert config["num_hidden_layers"] >= 4 and config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"] == config["vocab_size_published"]
    assert config["num_experts_published"] == config["router_outputs"] == published["num_experts"]
    assert config["num_hidden_layers_published"] == published["num_hidden_layers"]
    assert config["num_experts"] * config["chips_a_layer"] == published["num_experts"]
    assert set(config["reduced"]) <= set(config["reduced_why"])
    assert set(config["check_limits"]) == set(config["toy"]["check_limits"])
    assert set(config["check_limits"]) <= set(config["check_limits_why"])  # every limit with its readings
    # what the row does not give is assumed, by name
    assert set(row["not_given"]) == {"block length", "noise schedule"}
    assert {"block_length", "noise_schedule", "target", "mask_token_id", "corruption_draws"} <= set(config["assumed"])
    assert (config["block_length"], config["mask_token_id"], config["noise_eps"]) == (4, config["vocab_size"] - 1, 1e-3)
    cell = Manifest().cell(CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "fit_bd4k")
    assert config["global_batch_size"] * config["max_iter"] == config["num_sequences"]  # one pass a job
    assert (config["sequence_length"], config["global_batch_size"], config["max_iter"]) == (4096, 2, 4)


def test_the_costs_count_the_programs_tree_and_the_folds_work(config):
    """``sdar_costs.params`` is the program's own count; the fold's operations
    and bytes by hand at the published widths, at the pairs the mask KEEPS (a
    brute-force count of the dense mask at a smaller size); the experts' on
    held rows; the model's at the doubled positions, the head over T rows."""
    from flink_ml_tpu.models.lm.config import num_params
    from flink_ml_tpu.models.lm.reference_sdar import mask

    cfg = sdar_lm_fit.lm_config(config)
    assert sdar_costs.params(**PUBLISHED) == num_params(cfg) == 645_623_296 - (6 - cfg.n_layers) * 94_638_336
    for t, block in ((256, 4), (384, 32)):
        assert sdar_costs.kept_pairs(t, block) == mask(t, block).sum() == t * t + t * block
    pairs = 4096 * 4096 + 4096 * 4
    flops, nbytes = sdar_costs.block_diffusion_fold(batch=2, **PUBLISHED)
    assert flops == 6 * 2 * 32 * 6 * pairs * (128 + 128)  # six layers, two sequences, 32 heads, six matmuls' worth
    assert nbytes == 6 * 2 * 2 * 8192 * (32 * 4 * 128 + 4 * 4 * 128)  # q, dq, o, do a query head; k, dk, v, dv a kv head
    assert flops / 197e12 > nbytes / 819e9  # bound by the MXU
    flops, nbytes = sdar_costs.held_experts(rows_held=1000, **PUBLISHED)
    assert flops == 3 * 2 * 1000 * 3 * 2048 * 768
    assert nbytes == 3 * 16 * 2048 * 768 * 6 * 8 + 1000 * (2 * 2048 + 3 * 768) * 2 * 3
    layers, head = sdar_costs.forward_flops_per_sequence(**PUBLISHED)
    assert head == 4096 * 2 * 2048 * 18992  # the noised half's T rows alone
    per_position = 2 * (2048 * 4096 * 2 + 2 * 2048 * 512) + 2 * 2048 * 128
    assert per_position == 2 * 18_874_368 + 2 * 262_144
    assert layers == 6 * (8192 * per_position + 2 * pairs * 2 * 128 * 32)
    flops, _ = sdar_costs.model(batch=2, rows_held=0, **PUBLISHED)
    assert flops == 3 * 2 * (layers + head)
    # ISSUE 47's shares: the kept pairs' 275 G against the other matmuls' 391 G a layer and sequence
    fold = 2 * pairs * 2 * 128 * 32
    rest = 8192 * (per_position + 8 * 3 * 2 * 2048 * 768 / 8)
    assert fold == pytest.approx(275.1e9, rel=1e-3) and rest == pytest.approx(390.8e9, rel=1e-3)


def test_the_new_reducers_on_recorded_counts(capsys):
    """The fold's and the held experts' share of their roofline from a
    recorded kernel time and ``train.drain``'s held rows; the scored positions
    over the tokens; a run of another layout, of a program whose kernels carry
    other names (the parent), or of one that writes no such count, gives
    nothing to read and does not raise."""
    from perfbench import program_spans
    from perfbench.reducers import lm_mfu_pct, lm_roofline_pct, program_span_pct

    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    layout = dict(PUBLISHED, tokens=8192, batch=2)
    config = Manifest().config("sdar_30b_a3b")

    def ctx_of(shapes, stats, op="flash_fold_bd_bwd_dq.7"):
        table = program_spans.Table([program_spans.Span("train.drain", 10.0, 1.0, stats=stats)])
        return types.SimpleNamespace(
            run=types.SimpleNamespace(program_spans=table), config=config, w0=0.0, w1=100.0,
            facts={"layout": shapes, "steps": 4}, peaks=peaks, per=lambda unit: 4, ops=lambda: [(op, 20.0, 800e6)],  # 0.8 s over 4 steps
            trace=types.SimpleNamespace(modules={0: [("jit_step", 19.0, 4000e6)]}), dev=0)

    fold = "flash_fold_bd_(fwd|bwd_dq|bwd_dkv)"
    drained = {"rows_held": 4 * 100_000, "steps": 4, "tokens": 4 * 8192, "targets_masked": 4 * 4000}
    got = lm_roofline_pct.reduce(ctx_of(layout, drained), "block_diffusion_fold", pattern=fold)
    flops, nbytes = sdar_costs.block_diffusion_fold(**layout)
    assert got == pytest.approx(100 * (flops / 197e12) / 0.2) and 0 < got < 100
    assert "bound by mxu" in capsys.readouterr().out
    got = lm_roofline_pct.reduce(ctx_of(layout, drained, "ragged-dot-none.3"), "held_experts", pattern="^ragged-dot")
    flops, nbytes = sdar_costs.held_experts(rows_held=100_000, **layout)
    assert got == pytest.approx(100 * max(flops / 197e12, nbytes / 819e9) / 0.2) and 0 < got < 100
    got = lm_mfu_pct.reduce(ctx_of(layout, drained, "flash_fold_bd_fwd.2"), "flash_fold_bd_fwd")
    flops, _ = sdar_costs.model(rows_held=100_000, **layout)
    assert got == pytest.approx(100 * flops / 197e12 / 1.0) and 0 < got < 100  # a 4 s module over 4 steps
    # another layout; the parent's kernel names; no held-row count: nothing to read
    assert lm_roofline_pct.reduce(ctx_of({"tokens": 8192, "q_rank": 1536}, drained), "block_diffusion_fold", pattern=fold) is None
    assert lm_roofline_pct.reduce(ctx_of(layout, drained, "flash_fold_bwd_dq.7"), "block_diffusion_fold", pattern=fold) is None
    assert lm_roofline_pct.reduce(ctx_of(layout, {"steps": 4}, "ragged-dot-none.3"), "held_experts",
                                  pattern="^ragged-dot") is None
    assert lm_mfu_pct.reduce(ctx_of(layout, drained, "flash_fold_fwd.2"), "flash_fold_bd_fwd") is None
    assert program_span_pct.reduce(ctx_of(layout, drained), "train.drain", "targets_masked", "tokens") == \
        pytest.approx(100 * 4000 / 8192)
    assert program_span_pct.reduce(ctx_of(layout, {"steps": 4, "tokens": 9}), "train.drain", "targets_masked",
                                   "tokens") is None


def test_benchmark_reference_agrees_with_the_programs(system, want, toy):
    """Two independent writings of the same equations and of the same draws,
    one seed: the program's reference (the whole dense mask, held experts in a
    Python loop, full AdamW) and the benchmark's (blocks of query rows whose
    mask rows are built there, rematerialised, the first step's update from the
    gradient alone)."""
    import jax.numpy as jnp

    from flink_ml_tpu.models.lm import reference_sdar as program_reference
    from flink_ml_tpu.models.lm.decoder_lm import _flat_names, _ordered, init_params
    from perfbench.references import sdar_lm

    cfg = sdar_lm_fit.lm_config(toy)
    seed = system.seed % 2**31
    params = init_params(cfg, seed)
    batches = [jnp.asarray(system.tok[:2]), jnp.asarray(system.tok[2:4])]
    for step, batch in enumerate(batches):  # the same masks, bit for bit
        ours, theirs = sdar_lm.corrupt(batch, seed, step, system.dims), program_reference.corrupt(batch, seed, step, cfg)
        for a, b in zip(ours, theirs):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(sdar_lm._kept(jnp.arange(2 * 384), 384, 4)), program_reference.mask(384, 4))
    _, grads, (scored, rows) = program_reference.loss_and_grads(params, batches[0], seed, 0, cfg)
    _, losses, norms = program_reference.train_steps(
        params, batches, seed, cfg, system.hyper["learning_rate"],
        weight_decay=system.hyper["weight_decay"], clip=system.hyper["clip_norm"])
    np.testing.assert_allclose(want["losses"], losses, rtol=2e-6)
    np.testing.assert_allclose(want["grad_norms"][0], norms[0], rtol=2e-5)
    assert want["targets_masked"][0] == int(scored) and (system.tok != toy["mask_token_id"]).all()
    np.testing.assert_array_equal(want["expert_rows"], np.asarray(rows))
    assert set(want["group_norms"]) == set(_flat_names(cfg))
    for name, g in zip(_flat_names(cfg), _ordered(grads, cfg)):
        np.testing.assert_allclose(want["group_norms"][name], float(jnp.sqrt(jnp.sum(g * g))),
                                   rtol=1e-4, err_msg=name)


def test_the_sound_program_is_correct_and_the_control_is_not(system, want, toy):
    limits = toy["check_limits"]
    got = system.fit()
    sound = system.compare(got, want)
    assert all(sound[k] <= limits[k] for k in limits), sound
    assert got["expert_rows"].shape == (toy["num_hidden_layers"], toy["num_experts_published"])
    assert got["targets_masked"][:2] == want["targets_masked"] and got["rows_missing"] == 0
    # the loads agree but for a row at a tie's reach (float32, two orders of summation)
    assert np.abs(got["expert_rows"] - want["expert_rows"]).sum() <= 4
    control = system.compare(system.reference("bf16"), want)
    assert any(control[k] > limits[k] for k in limits), control
    assert control["targets_masked_mismatch"] == 0  # the draws are float32 in the control too


def test_the_recorded_chip_readings_pass_sound_and_fail_as_the_control(config):
    """At the TIMED size, where no CPU test can run: the check's numbers as
    the chip read them through ``compare`` (``check_readings``: the program
    and, on two of its seeds, the bfloat16 control), held to ``check_limits``
    by the harness's own rule (``correct`` = every value <= its limit). Every
    sound run is correct, every control run is not, and what tells them
    apart is the rows' drift toward the lower expert ids (a bfloat16 router's
    ties), whose limit has three times of room on the sound side, which is
    the side fresh seeds test, and half again on the control's."""
    limits, runs = config["check_limits"], config["check_readings"]["runs"]
    sound = [r["values"] for r in runs if r["side"] == "sound"]
    control = [r["values"] for r in runs if r["side"] == "control"]
    assert len(sound) >= 4 and len(control) >= 2
    assert {r["seed"] for r in runs if r["side"] == "control"} <= {r["seed"] for r in runs if r["side"] == "sound"}
    for values in sound + control:
        assert set(values) == set(limits)
    assert all(values[k] <= limits[k] for values in sound for k in limits)
    for values in control:
        assert {k for k in limits if values[k] > limits[k]} == {"expert_id_drift"}
    high, low = max(v["expert_id_drift"] for v in sound), min(v["expert_id_drift"] for v in control)
    assert high * 2.99 <= limits["expert_id_drift"] <= low / 1.5, (high, limits["expert_id_drift"], low)
    assert max(v["update_rel_err"] for v in sound) * 3 <= limits["update_rel_err"] < 1.0  # toward 1: a state unchanged


@pytest.mark.parametrize("case, fails", [
    ("one_layers_router_far_off", None),  # a row that changed expert: one layer, a heavy tail
    ("every_router_without_a_gradient", "routed_grad_norm_rel_err"),
    ("a_qk_norm_scaled", "group_grad_norm_rel_err"),
    ("a_held_experts_leaf_missing", "expert_grad_norm_bias"),
    ("many_rows_change_expert", "rows_changed_expert_pct"),
    ("one_position_more_scored", "targets_masked_mismatch"),
    ("ties_given_to_the_lower_id", "expert_id_drift"),
    ("a_block_of_rows_crosses_between_two_experts", None),  # the collapsed rows of one layer: two capped terms
    ("no_update", "update_rel_err"),
])
def test_the_routed_leaves_are_held_apart_from_the_rest(system, config, case, fails):
    """``compare`` on the reference's own numbers at the cell's limits, one
    thing off: the leaves a changed row moves in steps are held by the median
    over the layers, every other leaf by the worst, the scored positions
    exactly, a drift of the rows toward the lower ids by the layers' mean
    with each expert's change capped (a few rows off every expert, all DOWN,
    are seen whole; one block between two experts is not), the first update
    element by element."""
    layers = [f"layers.{i}" for i in range(6)]
    rows = np.full((6, 128), 1024, np.int64)
    want = {"losses": [9.9, 9.8], "grad_norms": [1.0], "expert_rows": rows, "targets_masked": [4000, 4100],
            "group_norms": {"embed": 0.5, "layers.1.q_norm": 0.1, **{f"{layer}.{leaf}": 0.01 for layer in layers
                            for leaf in ("ffn_norm", "router", "w_gate", "w_up", "w_down")}}}
    # a first update of lr x sign(g) from zero: four leaves of a layer's sizes in the cell's proportions
    lr, sizes = 4e-4, {"layers.0.wq": 8192, "layers.0.wk": 1024, "layers.0.wo": 8192, "layers.0.w_up": 24576}
    rng = np.random.default_rng(3)
    want["params_after"] = {k: lr * np.sign(rng.standard_normal(n)).astype(np.float32) for k, n in sizes.items()}
    want["update_norms"] = {k: lr * n ** 0.5 for k, n in sizes.items()}
    got = {**want, "group_norms": dict(want["group_norms"]), "expert_rows": rows.copy(),
           "targets_masked": [4000, 4100, 3900, 4050], "steps_expected": 4, "losses": [9.9, 9.8, 9.7, 9.6],
           "rows_missing": 0, "params_after": dict(want["params_after"])}
    limits = config["check_limits"]
    if case == "one_layers_router_far_off":
        got["group_norms"]["layers.2.router"] *= 1.0 + 3 * limits["routed_grad_norm_rel_err"]
    elif case == "every_router_without_a_gradient":
        got["group_norms"].update({f"{layer}.router": 0.0 for layer in layers})
    elif case == "a_qk_norm_scaled":
        got["group_norms"]["layers.1.q_norm"] *= 1.0 + 1.5 * limits["group_grad_norm_rel_err"]
    elif case == "a_held_experts_leaf_missing":
        got["group_norms"].update({f"{layer}.w_up": 0.0 for layer in layers[:2]})
    elif case == "many_rows_change_expert":
        moved = int(2 * limits["rows_changed_expert_pct"] / 100 * 128 * 1024) + 1
        got["expert_rows"][:, 0] -= moved
        got["expert_rows"][:, 1] += moved
    elif case == "one_position_more_scored":
        got["targets_masked"][1] += 1
    elif case == "ties_given_to_the_lower_id":  # in every layer 31 rows of each of experts 64.. move down 43 ids
        got["expert_rows"][:, 64:] -= 31
        got["expert_rows"][:, 21:85] += 31
    elif case == "a_block_of_rows_crosses_between_two_experts":  # 0.6 of an id a row of that layer, uncapped
        got["expert_rows"][3, 100] -= 1000
        got["expert_rows"][3, 20] += 1000
    else:  # the state left as it was
        got["params_after"] = {k: np.zeros_like(v) for k, v in want["params_after"].items()}
    values = system.compare(got, want)
    assert {k for k in limits if values[k] > limits[k]} == set((fails or "").split()), values
    if case == "ties_given_to_the_lower_id":  # 64 x 31 rows x 43 ids of 131,072 rows a layer
        assert values["expert_id_drift"] == pytest.approx(64 * 31 * 43 / (128 * 1024))
    elif case == "no_update":
        assert values["update_rel_err"] == pytest.approx(1.0)


@pytest.mark.parametrize("defect", ["shifted_target", "noised_sees_its_own_clean_block", "clean_is_strictly_causal",
                                    "no_one_over_p", "positions_run_on", "gates_not_renormalised",
                                    "qk_norm_over_the_projection", "absent_experts_served", "one_level_a_batch",
                                    "half_the_steps"])
def test_a_broken_timed_path_is_not_correct(system, want, toy, defect, monkeypatch):
    """This system's own class with its timed path broken underneath."""
    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.models.lm import decoder_lm
    from flink_ml_tpu.parallel import flash, moe

    decoder_lm._train_program.cache_clear()
    if defect == "shifted_target":  # the noised position scored on the NEXT token, as next-token prediction would
        sound = decoder_lm._target_nll
        monkeypatch.setattr(decoder_lm, "_target_nll", lambda h, head, targets, cd, last=True: sound(
            h, head, jnp.roll(targets, -1, axis=1), cd, last))
    elif defect in ("noised_sees_its_own_clean_block", "clean_is_strictly_causal"):
        def keep(q_pos, k_pos, blocks):
            tokens, block = blocks
            half = tokens // block
            qb, kb = q_pos // block, k_pos // block
            if defect == "clean_is_strictly_causal":
                clean = (q_pos < tokens) & (k_pos <= q_pos)
                return clean | ((qb >= half) & ((kb < qb - half) | (kb == qb)))
            newest = jnp.where(qb >= half, qb - half, qb)  # b(j) <= b(i) from the noised half too
            return (kb <= newest) | (kb == qb)

        monkeypatch.setattr(flash, "_bd_keep", keep)
    elif defect == "no_one_over_p":
        sound = decoder_lm._corrupt

        def unweighted(tok, noise, cfg):
            both, masked, p = sound(tok, noise, cfg)
            return both, masked, jnp.ones_like(p)

        monkeypatch.setattr(decoder_lm, "_corrupt", unweighted)
    elif defect == "positions_run_on":  # 0 .. 2T - 1 where each half has 0 .. T - 1
        sound = decoder_lm._rope_part
        monkeypatch.setattr(decoder_lm, "_rope_part", lambda x, cos, sin: sound(
            x, *decoder_lm._rope_tables(x.shape[2], cos.shape[-1], float(toy["rope_theta"]))))
    elif defect == "gates_not_renormalised":
        sound = decoder_lm.moe_dropless
        monkeypatch.setattr(decoder_lm, "moe_dropless", lambda *args: sound(*args[:-1], False))  # ``renormalise`` is last
    elif defect == "qk_norm_over_the_projection":  # one mean square over all heads' channels
        sound = decoder_lm._rms_norm

        def whole(x, w, eps):
            if x.ndim != 4:
                return sound(x, w, eps)
            x = x.astype(jnp.float32)
            return w * (x * jax.lax.rsqrt(jnp.mean(x * x, axis=(-2, -1), keepdims=True) + eps))

        monkeypatch.setattr(decoder_lm, "_rms_norm", whole)
    elif defect == "absent_experts_served":  # rows routed elsewhere fold onto the held experts
        sound = moe.route_top_k

        def folded(x, router, k):
            p, top_p, top_e = sound(x, router, k)
            return p, top_p, toy["first_expert_held"] + top_e % toy["num_experts"]

        monkeypatch.setattr(moe, "route_top_k", folded)
    elif defect == "one_level_a_batch":  # every sequence at the first one's level
        sound = jax.random.uniform
        monkeypatch.setattr(jax.random, "uniform", lambda key, shape=(), dtype=jnp.float32, **kw: (
            jnp.broadcast_to(sound(key, (1,), dtype), shape) if len(shape) == 1 else sound(key, shape, dtype, **kw)))
    try:
        got = system.fit()
    finally:
        decoder_lm._train_program.cache_clear()
    if defect == "half_the_steps":
        got["losses"] = got["losses"][:1]
    limits = toy["check_limits"]
    values = system.compare(got, want)
    assert any(values[k] > limits[k] for k in limits), (defect, values)
    if defect == "one_level_a_batch":  # the count of scored positions tells before any norm
        assert values["targets_masked_mismatch"] > 0


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
    assert {c["name"] for c in checks} == set(Manifest().config("sdar_30b_a3b")["check_limits"])
    assert all(c["ok"] for c in checks)
