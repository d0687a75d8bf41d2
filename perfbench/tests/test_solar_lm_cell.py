"""The ``solar_open2_250b`` configuration's own pieces, on the CPU at its
``toy`` sizes: the configuration against the catalog row, the benchmark's plain
reference against the program's and its recurrence against a loop written out
by hand, the cost module's counts against a brute-force count and a hand count
at the published widths, the delta rule's roofline cost not depending on the
chunk, and a timed path with part of the mathematics missing coming out not
correct."""
import json
import os

import numpy as np
import pytest

from perfbench import solar_costs
from perfbench.manifest import Manifest
from perfbench.systems import solar_lm_fit

CELL = "solar_open2_250b.fit_kda4k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
#: What only a delta-rule stack has, and the quantities the cell shares with the others (before PR 53 under ``solar_*``
#: and ``gated_attn_*``; the held share joined ``moe_held_share_pct`` at PR 51 already, when ``per_layer`` was full).
NEW_METRICS = ("kda_scan_ms", "kda_scan_roofline", "kda_scan_kernel_pct", "kda_conv_ms", "kda_gate_ms")
JOINED_METRICS = ("lm_step_ms", "lm_mfu_pct", "attn_ms", "attn_roofline", "moe_expert_ms", "moe_expert_roofline",
                  "lm_head_ms", "moe_rows_carried_pct", "lm_scope_coverage_pct", "moe_held_share_pct")


@pytest.fixture(scope="module")
def config():
    return Manifest().config("solar_open2_250b")


@pytest.fixture(scope="module")
def toy(config):
    return {**config, **config["toy"]}


@pytest.fixture(scope="module")
def system(toy):
    s = solar_lm_fit.create(toy, 2**31 + 5, 1)
    s.make_data()
    s.build()
    return s


@pytest.fixture(scope="module")
def want(system):
    return system.reference()


def test_the_configuration_is_the_catalog_row_cut_in_depth_heads_experts_and_vocabulary(config):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Solar-Open2-250B")
    published = row["config"]
    differs = sorted(k for k, v in published.items() if config.get(k, "absent") != v)
    assert differs == sorted(config["reduced"]) == [
        "linear_attn_config", "n_routed_experts", "num_attention_heads", "num_hidden_layers", "num_key_value_heads",
        "vocab_size"]
    entry = Manifest().configs["solar_open2_250b"]
    assert sorted(entry["reduced"]) == differs and entry["source"] == row["source_url"]
    # inside the nested group only the COUNT of heads changed: no width
    inside = {k for k, v in published["linear_attn_config"].items() if config["linear_attn_config"][k] != v}
    assert inside == {"num_heads"} and config["linear_attn_config"]["head_dim"] == 128
    # the floors of the model-configs guide, and what is stated beside each cut
    assert solar_lm_fit.reference.attending(config) == [0] and config["gqa_layers"] == published["gqa_layers"]
    assert config["num_hidden_layers"] >= 4 and config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"] == config["vocab_size_published"]
    assert config["n_routed_experts_published"] == config["router_outputs"] == published["n_routed_experts"]
    assert config["num_hidden_layers_published"] == published["num_hidden_layers"] == 48
    assert config["num_attention_heads_published"] == published["num_attention_heads"] == 64
    assert config["num_key_value_heads_published"] == published["num_key_value_heads"] == 8
    assert config["linear_attn_num_heads_published"] == published["linear_attn_config"]["num_heads"] == 64
    # a chip's share: a group of eight holds a layer's heads, forty chips its experts
    group, chips = config["chips_a_head_group"], config["chips_a_layer"]
    assert (group, chips) == (8, 40) and chips % group == 0
    assert config["num_attention_heads"] * group == 64 and config["num_key_value_heads"] * group == 8
    assert config["linear_attn_config"]["num_heads"] * group == 64
    assert config["n_routed_experts"] * chips == 320 and config["vocab_size"] * group == 196_608
    assert set(config["reduced"]) <= set(config["reduced_why"])
    assert set(config["check_limits"]) == set(config["toy"]["check_limits"])
    assert set(config["check_limits"]) <= set(config["check_limits_why"])  # every limit with its readings
    cell = Manifest().cell(CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "fit_kda4k")
    assert config["global_batch_size"] * config["max_iter"] == config["num_sequences"]  # one pass a job


def test_the_cell_reports_every_new_metric_and_nothing_else_changed():
    manifest = Manifest()
    assert CELL in manifest.end_to_end["fit_rows_per_s"]["workloads"]
    for name in NEW_METRICS:
        entry, spec = manifest.per_layer[name], manifest.layer_metric(name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "fit_rows_per_s", name
        assert (entry["unit"], entry["better"], entry["source"], entry["layer"]) == \
            (spec["unit"], spec["better"], spec["source"], spec["layer"]), name
    assert set(NEW_METRICS + JOINED_METRICS) | {"fit_idle_pct", "fit_peak_hbm_gb"} == \
        set(manifest.cell_metrics("per_layer", CELL))
    assert all(CELL in manifest.per_layer[name]["workloads"] and len(manifest.per_layer[name]["workloads"]) > 1
               for name in JOINED_METRICS)
    assert len(manifest.per_layer) <= 128
    held = manifest.layer_metric("moe_held_share_pct")
    assert (held["reducer"], held["params"]) == (
        "program_span_share_pct", {"span": "train.drain", "part": "rows_held", "rest": "rows_absent"})
    assert manifest.layer_metric("kda_scan_ms")["params"]["scopes"] == ["lm.block/kda"]
    assert manifest.layer_metric("kda_scan_roofline")["params"]["cost"] == "kda_scan"
    # what reaches the trace without a name and is billed to the experts' scope by instruction name
    assert manifest.layer_metric("lm_scope_coverage_pct")["params"]["renamed"] == {"perf": "renamed"}
    assert set(manifest.config("solar_open2_250b")["perf"]["renamed"]) == {"^ragged-dot", "^broadcast\\.\\d+"}


def test_the_nameless_zero_fills_are_billed_to_the_experts_scope():
    """``op_scopes.step_ops`` with the cell's ``renamed`` map: a grouped matmul and a constant's broadcast that carry no
    ``op_name`` take the experts' scope; a nameless copy stays unscoped; nothing is renamed in a program without
    scopes."""
    from perfbench import op_scopes

    renamed = Manifest().config("solar_open2_250b")["perf"]["renamed"]
    rows = [("fusion.7", 1.0, 5.0, "jit(step)/jvp(lm.block)/experts/mul"), ("ragged-dot-none.3", 7.0, 2.0, None),
            ("broadcast.306.clone.4", 10.0, 3.0, None), ("copy-done.12", 14.0, 4.0, None)]
    ops = {op.name: op.scope for op in op_scopes.step_ops(rows, [(0.0, 100.0)], "lm.", renamed)}
    assert ops["ragged-dot-none.3"] == ops["broadcast.306.clone.4"] == ("lm.block", "experts")
    assert ops["copy-done.12"] is None
    bare = op_scopes.step_ops([r[:3] + (None,) for r in rows], [(0.0, 100.0)], "lm.", renamed)
    assert all(op.scope is None for op in bare)


def test_benchmark_reference_agrees_with_the_programs(system, want, toy):
    """Two independent writings of the same equations, one seed: the program's
    reference (whole ``[T, T]`` scores, a Python loop over the held experts,
    full AdamW) and the benchmark's (blocks, rematerialised, the first step's
    update from the gradient alone)."""
    import jax.numpy as jnp

    from flink_ml_tpu.models.lm import reference_solar as program_reference
    from flink_ml_tpu.models.lm.decoder_lm import _flat_names, _ordered, init_params

    cfg = solar_lm_fit.lm_config(toy)
    params = init_params(cfg, system.seed % 2**31)
    b = system.batch
    batches = [jnp.asarray(system.tok[:b]), jnp.asarray(system.tok[b: 2 * b])]
    _, grads = program_reference.loss_and_grads(params, batches[0], cfg)
    _, losses, norms = program_reference.train_steps(
        params, batches, cfg, system.hyper["learning_rate"],
        weight_decay=system.hyper["weight_decay"], clip=system.hyper["clip_norm"])
    np.testing.assert_allclose(want["losses"], losses, rtol=2e-6)
    np.testing.assert_allclose(want["grad_norms"][0], norms[0], rtol=2e-5)
    assert set(want["group_norms"]) == set(_flat_names(cfg))
    for name, g in zip(_flat_names(cfg), _ordered(grads, cfg)):
        np.testing.assert_allclose(want["group_norms"][name], float(jnp.sqrt(jnp.sum(g * g))),
                                   rtol=2e-4, atol=1e-12, err_msg=name)


def test_the_references_recurrence_is_the_loop_written_out():
    """``references/solar_lm.py::recurrence`` (blocks of positions under
    ``lax.scan``, rematerialised) against the delta rule as a Python loop over
    16 positions in float64 numpy: ``S <- (I - beta k k^T) Diag(exp(g)) S +
    beta k v^T``, ``o = S^T q``."""
    import jax.numpy as jnp

    from perfbench.references import solar_lm as reference

    rng = np.random.default_rng(3)
    t, heads, d = 16, 3, 5
    q, k, v = (rng.standard_normal((t, heads, d)) for _ in range(3))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g, beta = -rng.uniform(0.001, 1.6, (t, heads, d)), rng.uniform(0.0, 2.0, (t, heads))
    state, want = np.zeros((heads, d, d)), np.zeros((t, heads, d))
    for i in range(t):
        for h in range(heads):
            decayed = np.exp(g[i, h])[:, None] * state[h]
            state[h] = (np.eye(d) - beta[i, h] * np.outer(k[i, h], k[i, h])) @ decayed \
                + beta[i, h] * np.outer(k[i, h], v[i, h])
            want[i, h] = state[h].T @ q[i, h]
    got = reference.recurrence(*(jnp.asarray(m, jnp.float32) for m in (q, k, v, g, beta)))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-6)


def test_the_sound_program_is_correct_and_the_control_is_not(system, want, toy):
    limits = toy["check_limits"]
    got = system.fit()
    sound = system.compare(got, want)
    assert all(sound[k] <= limits[k] for k in limits), sound
    np.testing.assert_array_equal(got["expert_rows"], want["expert_rows"])
    assert got["expert_rows"].shape == (toy["num_hidden_layers"], toy["n_routed_experts_published"])
    control = system.compare(system.reference("bf16"), want)
    assert any(control[k] > limits[k] for k in limits), control


@pytest.mark.parametrize("defect", ["state_forgotten_at_chunks", "decays_in_bfloat16", "beta_not_doubled",
                                    "q_and_k_not_normalised", "no_gqa_gate", "absent_experts_served",
                                    "no_shared_expert", "half_the_steps"])
def test_a_broken_timed_path_is_not_correct(system, want, toy, defect, monkeypatch):
    """This system's own class with its timed path broken underneath."""
    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.models.lm import decoder_lm
    from flink_ml_tpu.parallel import moe

    decoder_lm._train_program.cache_clear()
    sound_rule, sound_dense = decoder_lm.kda_scan, decoder_lm.dense_swiglu
    if defect == "state_forgotten_at_chunks":  # every chunk a sequence of its own
        def forgetful(q, k, v, g, beta, chunk, cd):
            cut = lambda m: m.reshape(-1, chunk, *m.shape[2:])  # noqa: E731
            return sound_rule(cut(q), cut(k), cut(v), cut(g), cut(beta), chunk, cd).reshape(q.shape)

        monkeypatch.setattr(decoder_lm, "kda_scan", forgetful)
    elif defect == "decays_in_bfloat16":  # the log-decays rounded on their way into the rule
        monkeypatch.setattr(decoder_lm, "kda_scan", lambda q, k, v, g, beta, chunk, cd: sound_rule(
            q, k, v, g.astype(jnp.bfloat16).astype(g.dtype), beta, chunk, cd))
    elif defect == "beta_not_doubled":  # the correction's strength in (0, 1)
        monkeypatch.setattr(decoder_lm, "kda_scan", lambda q, k, v, g, beta, chunk, cd: sound_rule(
            q, k, v, g, beta / 2.0, chunk, cd))
    elif defect == "q_and_k_not_normalised":
        monkeypatch.setattr(decoder_lm, "UNIT_EPS", 1e6)
    elif defect == "no_gqa_gate":  # the attention layer's output gate left out
        sound_sigmoid = jax.nn.sigmoid
        width = toy["num_attention_heads"] * toy["head_dim"]
        monkeypatch.setattr(jax.nn, "sigmoid", lambda z: (
            jnp.ones_like(z) if z.shape[-1] == width and z.ndim == 3 else sound_sigmoid(z)))
    elif defect == "absent_experts_served":  # rows routed elsewhere fold onto the held experts
        sound = moe.route_sigmoid_top_k

        def folded(x, router, k, routed_scale, select_bias=None):
            p, top_p, top_e = sound(x, router, k, routed_scale, select_bias)
            return p, top_p, toy["first_expert_held"] + top_e % toy["n_routed_experts"]

        monkeypatch.setattr(moe, "route_sigmoid_top_k", folded)
    elif defect == "no_shared_expert":
        monkeypatch.setattr(decoder_lm, "dense_swiglu", lambda x, g, u, d, cd: 0.0 * sound_dense(x, g, u, d, cd))
    got = system.fit()
    if defect == "half_the_steps":
        got["losses"] = got["losses"][:1]
    decoder_lm._train_program.cache_clear()
    limits = toy["check_limits"]
    result = system.compare(got, want)
    assert any(result[k] > limits[k] for k in limits), result


def _brute_force(d):
    """Forward multiply-adds (x 2) a token of a layer of each kind and of the
    head, and the parameters, counted matrix by matrix from the shapes."""
    hidden, heads, hd = d["hidden"], d["kda_heads"], d["kda_head_dim"]
    inner = heads * hd
    mats = {
        "kda": [(hidden, inner)] * 3 + [(hidden, hd), (hd, inner)] * 2 + [(hidden, heads), (inner, hidden)],
        "gqa": [(hidden, d["heads"] * d["head_dim"])] * 2 + [(hidden, d["kv_heads"] * d["head_dim"])] * 2
               + [(d["heads"] * d["head_dim"], hidden)],
        "ffn": [(hidden, d["experts"])] + [(hidden, d["shared_width"])] * 2 + [(d["shared_width"], hidden)],
    }
    flops = {k: sum(2 * r * c for r, c in v) for k, v in mats.items()}
    # the rule, a head a position: decay the state (D^2), S^T k (2 D^2), the correction added (2 D^2), S^T q (2 D^2)
    flops["kda"] += heads * 7 * hd * hd
    # scores and values against the causal half of the keys: 2 matmuls x 2 x (T / 2) x D a head
    flops["gqa"] += d["heads"] * 2 * 2 * (d["seq"] / 2) * d["head_dim"]
    small = {"kda": 3 * d["conv_kernel"] * inner + heads + inner + hd, "gqa": 0, "ffn": d["experts"]}
    params = {k: sum(r * c for r, c in v) + small[k] + hidden for k, v in mats.items()}
    params["ffn"] += 3 * d["experts_held"] * hidden * d["width"]
    return flops, params


@pytest.mark.parametrize("sizes", ["toy", "published"])
def test_cost_module_against_a_brute_force_count(config, toy, sizes):
    from flink_ml_tpu.models.lm.config import num_params

    cfg = toy if sizes == "toy" else config
    shapes = solar_lm_fit.create(cfg, 1, 1).layout_dims
    n, attends = shapes["layers"], shapes["layers_gqa"]
    flops, params = _brute_force(shapes)
    layers, head = solar_costs.forward_flops_per_token(**shapes)
    assert layers == pytest.approx((n - attends) * flops["kda"] + attends * flops["gqa"] + n * flops["ffn"])
    assert head == 2 * shapes["hidden"] * shapes["vocab"]
    want_params = ((n - attends) * params["kda"] + attends * params["gqa"] + n * params["ffn"]
                   + 2 * shapes["vocab"] * shapes["hidden"] + shapes["hidden"])
    assert solar_costs.params(**shapes) == want_params == num_params(solar_lm_fit.lm_config(cfg))
    rows = 1000
    expert = 3 * 2 * shapes["hidden"] * shapes["width"]  # three matrices a held (token, expert) row
    got, nbytes = solar_costs.model(rows_held=rows, **shapes)
    assert got == pytest.approx(3 * (shapes["tokens"] * (layers + head) + rows * expert))
    assert nbytes == want_params * 28
    held_flops, held_bytes = solar_costs.held_experts(rows_held=rows, **shapes)
    assert held_flops == 3 * rows * expert
    assert held_bytes == n * shapes["experts_held"] * 3 * shapes["hidden"] * shapes["width"] * 8 \
        + rows * (2 * shapes["hidden"] + 3 * shapes["width"]) * 2 * 3
    fold_flops, fold_bytes = solar_costs.gated_fold(**shapes)
    assert fold_flops == 6 * 2 * (shapes["seq"] ** 2 / 2) * shapes["head_dim"] * shapes["heads"] * shapes["batch"] \
        * attends
    assert fold_bytes == 4 * shapes["batch"] * (shapes["heads"] + shapes["kv_heads"]) * shapes["seq"] \
        * shapes["head_dim"] * 2 * attends
    if sizes == "published":  # the issue's arithmetic a token, and a step's
        assert want_params == 840_872_600
        assert (params["kda"], params["gqa"], params["ffn"]) == (18_138_248, 13_635_584, 142_872_896)
        # the sliced head: 40% of the cut's forward matmul operations a token (the published model's head: 5%)
        assert 0.39 < head / (layers + head + 8 / 40 * expert * n) < 0.42
        rule_flops, rule_bytes = solar_costs.kda_scan(**shapes)
        assert rule_flops == 3 * 3 * shapes["tokens"] * 8 * 7 * 128 * 128
        # q, k, v, o at 128 channels x 2 bytes, the log-decays' 128 floats and beta's one, a head a position
        assert rule_bytes == 3 * 3 * shapes["tokens"] * 8 * (4 * 128 * 2 + 128 * 4 + 4)
        assert rule_bytes / 819e9 > rule_flops / 197e12  # bound by HBM: 0.55 ms a step against 0.17


def test_the_delta_rules_cost_does_not_depend_on_the_chunk(config):
    """``kda_scan_roofline`` divides by the recurrence's own work: no chunk
    size is among the shapes the cost reads, so a later kernel, or another
    chunk, is judged on one yardstick."""
    base = solar_lm_fit.create(config, 1, 1).layout_dims
    assert "chunk" not in base and "chunk_size" not in base
    costs = {chunk: solar_costs.kda_scan(**solar_lm_fit.create({**config, "chunk_size": chunk}, 1, 1).layout_dims)
             for chunk in (32, 64, 128)}
    assert len(set(costs.values())) == 1


def test_the_reducers_on_recorded_counts(config):
    """The expert kernels' share and the whole step's from a recorded run's
    numbers; a run whose layout names no delta-rule heads (a parent commit
    without the block kind, another configuration) or whose fits wrote no held
    rows reads as no metric, not as an error."""
    import types

    from perfbench import program_spans
    from perfbench.reducers import lm_roofline_pct

    shapes = solar_lm_fit.create(config, 1, 1).layout_dims
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}

    def ctx_of(layout, stats):
        table = program_spans.Table([program_spans.Span("train.drain", 10.0, 1.0, stats=stats)])
        return types.SimpleNamespace(
            run=types.SimpleNamespace(program_spans=table), config=config, w0=0.0, w1=100.0,
            facts={"layout": layout, "steps": 8}, peaks=peaks, per=lambda unit: 8, ops=lambda: [("ragged-dot-none.3", 20.0, 160e6)])

    drained = {"rows_held": 8 * 3_000, "steps": 8}
    got = lm_roofline_pct.reduce(ctx_of(shapes, drained), "held_experts", pattern="^ragged-dot")
    flops, nbytes = solar_costs.held_experts(rows_held=3_000, **shapes)
    assert got == pytest.approx(100 * max(flops / 197e12, nbytes / 819e9) / 0.020) and 0 < got < 100
    assert lm_roofline_pct.reduce(ctx_of({"tokens": 4096}, drained), "held_experts", pattern="^ragged-dot") is None
    assert lm_roofline_pct.reduce(ctx_of(shapes, {"steps": 8}), "held_experts", pattern="^ragged-dot") is None
    assert lm_roofline_pct.reduce(ctx_of(shapes, drained), "held_experts", pattern="^no_such_kernel") is None
