"""The ``olmo_hybrid_7b`` configuration's own pieces, on the CPU at its ``toy``
sizes: the configuration against the catalog row, every new metric's file
against its entry, the benchmark's plain reference against the program's and
its recurrence against a loop written out by hand, the cost module's counts
against a brute-force count and ISSUE 54's arithmetic at the published widths,
the delta rule's roofline cost not depending on the chunk, and a timed path
with part of the mathematics missing coming out not correct."""
import json
import os

import numpy as np
import pytest

from perfbench import olmo_hybrid_costs
from perfbench.manifest import Manifest
from perfbench.systems import olmo_hybrid_lm_fit

CELL = "olmo_hybrid_7b.fit_gdn8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
#: What this PR adds to ``per_layer``, and the quantities the cell shares with the cells that were there.
NEW_METRICS = ("kda_scalar_path_pct", "lm_ffn_ms", "lm_ffn_roofline")
JOINED_METRICS = ("lm_step_ms", "lm_mfu_pct", "lm_scope_coverage_pct", "lm_head_ms", "lm_opt_ms", "lm_block_remat_ms",
                  "attn_ms", "attn_roofline", "kda_scan_ms", "kda_scan_roofline", "kda_scan_kernel_pct", "kda_conv_ms",
                  "kda_gate_ms")


@pytest.fixture(scope="module")
def config():
    return Manifest().config("olmo_hybrid_7b")


@pytest.fixture(scope="module")
def toy(config):
    return {**config, **config["toy"]}


@pytest.fixture(scope="module")
def system(toy):
    s = olmo_hybrid_lm_fit.create(toy, 2**31 + 5, 1)
    s.make_data()
    s.build()
    return s


@pytest.fixture(scope="module")
def want(system):
    return system.reference()


def test_the_configuration_is_the_catalog_row_cut_in_depth_heads_and_vocabulary(config):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Olmo-Hybrid-7B")
    published = row["config"]
    differs = sorted(k for k, v in published.items() if config.get(k, "absent") != v)
    assert differs == sorted(config["reduced"]) == [
        "linear_num_key_heads", "linear_num_value_heads", "num_attention_heads", "num_hidden_layers",
        "num_key_value_heads", "vocab_size"]
    entry = Manifest().configs["olmo_hybrid_7b"]
    assert sorted(entry["reduced"]) == differs and entry["source"] == row["source_url"]
    # every width as published: no key that names one is among the cuts
    for width in ("hidden_size", "intermediate_size", "linear_key_head_dim", "linear_value_head_dim",
                  "linear_conv_kernel_dim"):
        assert config[width] == published[width] and width not in config["reduced"], width
    assert config["head_dim"] * published["num_attention_heads"] == published["hidden_size"]  # 128, as published
    # the floors of the model-configs guide, and what is stated beside each cut
    assert config["layer_types"] == published["layer_types"] and len(config["layer_types"]) == 32
    assert config["layer_types"][: config["num_hidden_layers"]] == ["linear_attention"] * 3 + ["full_attention"]
    assert olmo_hybrid_lm_fit.reference.attending(config) == [3] and config["num_hidden_layers"] >= 4
    assert config["vocab_size"] * 8 >= published["vocab_size"] == config["vocab_size_published"]
    for key in ("num_hidden_layers", "num_attention_heads", "num_key_value_heads", "linear_num_key_heads",
                "linear_num_value_heads"):
        assert config[key + "_published"] == published[key], key
    # a chip's share: two chips hold a layer's heads, eight the vocabulary
    chips = config["chips_a_layer"]
    assert chips == 2 and config["chips_a_vocabulary"] == 8
    for key in ("num_attention_heads", "num_key_value_heads", "linear_num_key_heads", "linear_num_value_heads"):
        assert config[key] * chips == published[key], key
    assert config["vocab_size"] * config["chips_a_vocabulary"] == published["vocab_size"]
    assert set(config["reduced"]) <= set(config["reduced_why"])
    assert set(config["check_limits"]) == set(config["toy"]["check_limits"])
    assert set(config["check_limits"]) <= set(config["check_limits_why"])  # every limit with its readings
    cell = Manifest().cell(CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "fit_gdn8k")
    assert config["global_batch_size"] * config["max_iter"] == config["num_sequences"]  # one pass a job
    assert (config["sequence_length"], config["global_batch_size"], config["max_iter"]) == (8192, 1, 4)


def test_the_cell_reports_every_new_metric_and_joins_the_shared_quantities():
    manifest = Manifest()
    assert manifest.problems() == []
    assert manifest.end_to_end["fit_rows_per_s"]["workloads"][-1] == CELL
    for name in NEW_METRICS:
        entry, spec = manifest.per_layer[name], manifest.layer_metric(name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "fit_rows_per_s", name
        assert (entry["name"], entry["unit"], entry["better"], entry["source"], entry["layer"]) == \
            (spec["name"], spec["unit"], spec["better"], spec["source"], spec["layer"]), name
        assert os.path.exists(os.path.join(manifest.dir, "reducers", spec["reducer"] + ".py")), name
    assert [m["name"] for m in manifest.data["per_layer"][-3:]] == list(NEW_METRICS)  # at the end of their list
    assert set(NEW_METRICS + JOINED_METRICS) | {"fit_idle_pct", "fit_peak_hbm_gb"} == \
        set(manifest.cell_metrics("per_layer", CELL))
    for name in JOINED_METRICS:  # appended, after the cells that were there
        assert manifest.per_layer[name]["workloads"][-1] == CELL and len(manifest.per_layer[name]["workloads"]) > 1
    assert len(manifest.per_layer) == 77 and len(manifest.cells) == 11 == len(manifest.configs)
    path = manifest.layer_metric("kda_scalar_path_pct")
    assert (path["reducer"], path["params"]) == (
        "program_span_pct", {"span": "train.program", "stat": "kda_chunks_scalar", "over": "kda_chunks"})
    assert manifest.layer_metric("lm_ffn_ms")["params"]["scopes"] == ["lm.block/ffn"]
    roofline = manifest.layer_metric("lm_ffn_roofline")["params"]
    assert (roofline["cost"], roofline["scopes"]) == ("dense_ffn", ["lm.block/ffn"])
    perf = manifest.config("olmo_hybrid_7b")["perf"]
    assert perf == {"costs": "olmo_hybrid_costs", "step_holds": "kda_scan_fwd", "renamed": {},
                    "cost_of": {"attn": "nope_fold"}}
    assert callable(getattr(olmo_hybrid_costs, perf["cost_of"]["attn"]))


def test_a_program_without_the_count_leaves_the_guard_out():
    """``kda_scalar_path_pct`` on a parent that writes ``kda_chunks`` and no ``kda_chunks_scalar`` (Solar's program
    before this PR), and on one that writes both."""
    import types

    from perfbench import program_spans
    from perfbench.reduce import metric_value

    spec = Manifest().layer_metric("kda_scalar_path_pct")

    def ctx_of(stats):
        table = program_spans.Table([program_spans.Span("train.program", 10.0, 1.0, stats=stats)])
        return types.SimpleNamespace(run=types.SimpleNamespace(program_spans=table), config={}, w0=0.0, w1=100.0)

    assert metric_value(ctx_of({"kda_chunks": 5760, "kda_chunks_kernel": 5760}), spec) is None
    assert metric_value(ctx_of({"kda_chunks": 5760, "kda_chunks_scalar": 5760}), spec) == 100.0
    assert metric_value(ctx_of({"kda_chunks": 1536, "kda_chunks_scalar": 0}), spec) == 0.0


def test_benchmark_reference_agrees_with_the_programs(system, want, toy):
    """Two independent writings of the same equations, one seed: the program's
    reference (whole ``[T, T]`` scores, full AdamW) and the benchmark's (blocks,
    rematerialised, the first step's update from the gradient alone)."""
    import jax.numpy as jnp

    from flink_ml_tpu.models.lm import reference_olmo_hybrid as program_reference
    from flink_ml_tpu.models.lm.decoder_lm import _flat_names, _ordered, init_params

    cfg = olmo_hybrid_lm_fit.lm_config(toy)
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
    """``references/olmo_hybrid_lm.py::recurrence`` (blocks of positions under
    ``lax.scan``, rematerialised) against the rule as a Python loop over 16
    positions in float64 numpy at heads that are not square: ``S <- a (I - beta
    k k^T) S + beta k v^T`` with ONE decay ``a = exp(g)`` a head, ``o = S^T
    q``."""
    import jax.numpy as jnp

    from perfbench.references import olmo_hybrid_lm as reference

    rng = np.random.default_rng(3)
    t, heads, dk, dv = 16, 3, 5, 7
    q, k = (rng.standard_normal((t, heads, dk)) for _ in range(2))
    v = rng.standard_normal((t, heads, dv))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g, beta = -rng.uniform(0.001, 1.6, (t, heads)), rng.uniform(0.0, 2.0, (t, heads))
    state, want = np.zeros((heads, dk, dv)), np.zeros((t, heads, dv))
    for i in range(t):
        for h in range(heads):
            decayed = np.exp(g[i, h]) * state[h]
            state[h] = (np.eye(dk) - beta[i, h] * np.outer(k[i, h], k[i, h])) @ decayed \
                + beta[i, h] * np.outer(k[i, h], v[i, h])
            want[i, h] = state[h].T @ q[i, h]
    got = reference.recurrence(*(jnp.asarray(m, jnp.float32) for m in (q, k, v, g, beta)))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-6)


def test_the_sound_program_is_correct_and_the_control_is_not(system, want, toy):
    limits = toy["check_limits"]
    got = system.fit()
    sound = system.compare(got, want)
    assert all(sound[k] <= limits[k] for k in limits), sound
    control = system.compare(system.reference("bf16"), want)
    assert any(control[k] > limits[k] for k in limits), control


@pytest.mark.parametrize("defect", ["state_forgotten_at_chunks", "decays_in_bfloat16", "beta_not_doubled",
                                    "q_and_k_not_normalised", "sigmoid_gate", "a_pre_norm_slipped_in",
                                    "no_qk_norm", "half_the_steps"])
def test_a_broken_timed_path_is_not_correct(system, want, toy, defect, monkeypatch):
    """This system's own class with its timed path broken underneath."""
    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.models.lm import decoder_lm

    decoder_lm._train_program.cache_clear()
    sound_rule = decoder_lm.kda_scan
    if defect == "state_forgotten_at_chunks":  # every chunk a sequence of its own
        def forgetful(q, k, v, g, beta, chunk, cd):
            cut = lambda m: m.reshape(-1, chunk, *m.shape[2:])  # noqa: E731
            return sound_rule(cut(q), cut(k), cut(v), cut(g), cut(beta), chunk, cd).reshape(v.shape)

        monkeypatch.setattr(decoder_lm, "kda_scan", forgetful)
    elif defect == "decays_in_bfloat16":  # the log-decays rounded on their way into the rule
        monkeypatch.setattr(decoder_lm, "kda_scan", lambda q, k, v, g, beta, chunk, cd: sound_rule(
            q, k, v, g.astype(jnp.bfloat16).astype(g.dtype), beta, chunk, cd))
    elif defect == "beta_not_doubled":  # the correction's strength in (0, 1)
        monkeypatch.setattr(decoder_lm, "kda_scan", lambda q, k, v, g, beta, chunk, cd: sound_rule(
            q, k, v, g, beta / 2.0, chunk, cd))
    elif defect == "q_and_k_not_normalised":
        monkeypatch.setattr(decoder_lm, "UNIT_EPS", 1e6)
    elif defect == "sigmoid_gate":  # Solar's output gate for this family's silu
        monkeypatch.setattr(jax.nn, "silu", lambda z, sound=jax.nn.silu: (
            jax.nn.sigmoid(z) if z.ndim == 3 and z.shape[-1] == toy["linear_num_value_heads"]
            * toy["linear_value_head_dim"] else sound(z)))
    elif defect == "a_pre_norm_slipped_in":  # the sublayers read a normed stream, as every other kind's do
        monkeypatch.setattr(decoder_lm, "_read", lambda x, layer, part, eps: decoder_lm._rms_norm(
            x, jnp.ones((x.shape[-1],), jnp.float32), eps))
    elif defect == "no_qk_norm":  # the layer that attends takes q and k as projected; the two weights read nothing
        import dataclasses

        from flink_ml_tpu.models.lm.config import Attention

        sound_layers = decoder_lm.layers
        monkeypatch.setattr(decoder_lm, "layers", lambda cfg: tuple(
            dataclasses.replace(s, mixer=dataclasses.replace(s.mixer, qk_norm="")) if isinstance(s.mixer, Attention)
            else s for s in sound_layers(cfg)))
    got = system.fit()
    if defect == "half_the_steps":
        got["losses"] = got["losses"][:1]
    decoder_lm._train_program.cache_clear()
    limits = toy["check_limits"]
    result = system.compare(got, want)
    assert any(result[k] > limits[k] for k in limits), (defect, result)


def _brute_force(d):
    """Forward multiply-adds (x 2) a token of a layer of each kind and of the
    head, and the parameters, counted matrix by matrix from the shapes."""
    hidden, heads, dk, dv = d["hidden"], d["kda_heads"], d["key_dim"], d["value_dim"]
    keys, values, a = heads * dk, heads * dv, d["heads"] * d["head_dim"]
    kv = d["kv_heads"] * d["head_dim"]
    mats = {
        "delta": [(hidden, keys)] * 2 + [(hidden, values)] * 2 + [(hidden, heads)] * 2 + [(values, hidden)],
        "full": [(hidden, a), (hidden, kv), (hidden, kv), (a, hidden)],
        "ffn": [(hidden, d["width"])] * 2 + [(d["width"], hidden)],
    }
    flops = {k: sum(2 * r * c for r, c in v) for k, v in mats.items()}
    # the rule, a head a position: decay the state (Dk Dv), S^T k (2 Dk Dv), the correction added (2 Dk Dv), S^T q
    flops["delta"] += heads * 7 * dk * dv
    # scores and values against the causal half of the keys: 2 matmuls x 2 x (T / 2) x D a head
    flops["full"] += d["heads"] * 2 * 2 * (d["seq"] / 2) * d["head_dim"]
    small = {"delta": d["conv_kernel"] * (2 * keys + values) + 2 * heads + dv + hidden, "full": a + kv + hidden,
             "ffn": hidden}  # the convolutions, A_log, dt_bias and o_norm; the QK-norms; each sublayer's output norm
    params = {k: sum(r * c for r, c in v) + small[k] for k, v in mats.items()}
    return flops, params


@pytest.mark.parametrize("sizes", ["toy", "published"])
def test_cost_module_against_a_brute_force_count(config, toy, sizes):
    from flink_ml_tpu.models.lm.config import num_params

    cfg = toy if sizes == "toy" else config
    shapes = olmo_hybrid_lm_fit.create(cfg, 1, 1).layout_dims
    n, full = shapes["layers"], shapes["layers_full"]
    flops, params = _brute_force(shapes)
    layers, head = olmo_hybrid_costs.forward_flops_per_token(**shapes)
    assert layers == pytest.approx((n - full) * flops["delta"] + full * flops["full"] + n * flops["ffn"])
    assert head == 2 * shapes["hidden"] * shapes["vocab"]
    want_params = ((n - full) * params["delta"] + full * params["full"] + n * params["ffn"]
                   + 2 * shapes["vocab"] * shapes["hidden"] + shapes["hidden"])
    assert olmo_hybrid_costs.params(**shapes) == want_params == num_params(olmo_hybrid_lm_fit.lm_config(cfg))
    got, nbytes = olmo_hybrid_costs.model(**shapes)
    assert got == pytest.approx(3 * shapes["tokens"] * (layers + head)) and nbytes == want_params * 28
    ffn_flops, ffn_bytes = olmo_hybrid_costs.dense_ffn(**shapes)
    assert ffn_flops == 3 * shapes["tokens"] * n * flops["ffn"]
    assert ffn_bytes == n * 3 * shapes["hidden"] * shapes["width"] * 8 \
        + n * shapes["tokens"] * (2 * shapes["hidden"] + 2 * shapes["width"]) * 2 * 3
    fold_flops, fold_bytes = olmo_hybrid_costs.nope_fold(**shapes)
    assert fold_flops == 6 * 2 * (shapes["seq"] ** 2 / 2) * shapes["head_dim"] * shapes["heads"] * shapes["batch"] * full
    assert fold_bytes == 4 * shapes["batch"] * (shapes["heads"] + shapes["kv_heads"]) * shapes["seq"] \
        * shapes["head_dim"] * 2 * full
    if sizes == "published":  # the issue's arithmetic a token, and a step's
        assert want_params == 766_241_946
        assert (params["delta"], params["full"], params["ffn"]) == (44_375_262 + 3_840, 29_495_040 + 3_840,
                                                                    126_812_160 + 3_840)
        assert 4.41e9 < 3 * (layers + head) < 4.43e9 and 36.1e12 < got < 36.3e12  # 4.42 GFLOP a token, 36 TFLOP a step
        assert 0.68 < n * flops["ffn"] / (layers + head) < 0.70  # the whole feed-forward beside half the heads
        rule_flops, rule_bytes = olmo_hybrid_costs.kda_scan(**shapes)
        assert rule_flops == 3 * 3 * shapes["tokens"] * 15 * 7 * 96 * 192
        # q, k at 96 and v, o at 192 channels x 2 bytes, ONE float32 log-decay and beta's one, a head a position
        assert rule_bytes == 3 * 3 * shapes["tokens"] * 15 * ((2 * 96 + 2 * 192) * 2 + 4 + 4)
        assert 1.27e9 < rule_bytes < 1.29e9 and 0.14e12 < rule_flops < 0.15e12
        assert rule_bytes / 819e9 > rule_flops / 197e12  # bound by HBM: 1.57 ms a step against 0.72


def test_the_delta_rules_cost_does_not_depend_on_the_chunk(config):
    """``kda_scan_roofline`` divides by the recurrence's own work: no chunk
    size is among the shapes the cost reads, so a later kernel, or another
    chunk, is judged on one yardstick."""
    base = olmo_hybrid_lm_fit.create(config, 1, 1).layout_dims
    assert "chunk" not in base and "chunk_size" not in base
    costs = {chunk: olmo_hybrid_costs.kda_scan(
        **olmo_hybrid_lm_fit.create({**config, "chunk_size": chunk}, 1, 1).layout_dims) for chunk in (32, 64, 128)}
    assert len(set(costs.values())) == 1


def test_the_reducers_on_recorded_counts(config):
    """The feed-forward's share of its roofline from a recorded step's numbers;
    a run whose layout names no such shapes (another configuration's) reads as
    no metric, not as an error."""
    import types

    from perfbench.reducers import lm_roofline_pct

    shapes = olmo_hybrid_lm_fit.create(config, 1, 1).layout_dims
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}

    def ctx_of(layout):
        return types.SimpleNamespace(config=config, w0=0.0, w1=100.0, facts={"layout": layout, "steps": 4}, peaks=peaks,
                                     per=lambda unit: 4, ops=lambda: [("fusion.7", 20.0, 800e6)])

    got = lm_roofline_pct.reduce(ctx_of(shapes), "dense_ffn", pattern="^fusion")
    flops, nbytes = olmo_hybrid_costs.dense_ffn(**shapes)
    assert got == pytest.approx(100 * max(flops / 197e12, nbytes / 819e9) / 0.2) and 60 < got < 65
    assert lm_roofline_pct.reduce(ctx_of({"tokens": 8192}), "dense_ffn", pattern="^fusion") is None
    assert lm_roofline_pct.reduce(ctx_of(shapes), "dense_ffn", pattern="^no_such_kernel") is None
