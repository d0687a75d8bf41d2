"""The ``nemotron3_nano_30b`` configuration's own pieces, on the CPU at its
``toy`` sizes: the configuration against the catalog row, the benchmark's
plain reference against the program's and its recurrence against a loop
written out by hand, the cost module's counts against a brute-force count and
a hand count at the published widths, the scan's roofline cost not depending
on the chunk, and a timed path with part of the mathematics missing coming
out not correct."""
import json
import os

import numpy as np
import pytest

from perfbench import nemotron_costs
from perfbench.manifest import Manifest
from perfbench.systems import nemotron_lm_fit

CELL = "nemotron3_nano_30b.fit_scan8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def config():
    return Manifest().config("nemotron3_nano_30b")


@pytest.fixture(scope="module")
def toy(config):
    return {**config, **config["toy"]}


@pytest.fixture(scope="module")
def system(toy):
    s = nemotron_lm_fit.create(toy, 2**31 + 5, 1)
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
        row = next(r for r in map(json.loads, f) if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    published = row["config"]
    differs = sorted(k for k, v in published.items() if config.get(k, "absent") != v)
    assert differs == sorted(config["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    entry = Manifest().configs["nemotron3_nano_30b"]
    assert sorted(entry["reduced"]) == differs and entry["source"] == row["source_url"]
    # the floors of the model-configs guide, and what is stated beside each cut
    kinds = nemotron_lm_fit.reference.layer_kinds(config)
    assert kinds == "MEMEM*EME" == published["hybrid_override_pattern"][:9]  # the period the pattern opens with
    assert config["hybrid_override_pattern"] == published["hybrid_override_pattern"]  # as published, 52 letters
    assert config["n_routed_experts"] >= 8 and config["vocab_size"] * 8 >= published["vocab_size"]
    assert published["vocab_size"] == config["vocab_size_published"]
    assert config["n_routed_experts_published"] == config["router_outputs"] == published["n_routed_experts"]
    assert config["num_hidden_layers_published"] == published["num_hidden_layers"] == 52
    assert set(config["reduced"]) <= set(config["reduced_why"])
    assert set(config["check_limits"]) == set(config["toy"]["check_limits"])
    assert set(config["check_limits"]) <= set(config["check_limits_why"])  # every limit with its readings
    cell = Manifest().cell(CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "fit_scan8k")
    assert config["global_batch_size"] * config["max_iter"] == config["num_sequences"]  # one pass a job


def test_benchmark_reference_agrees_with_the_programs(system, want, toy):
    """Two independent writings of the same equations, one seed: the program's
    reference (whole ``[T, T]`` scores, a Python loop over the held experts,
    full AdamW) and the benchmark's (blocks, rematerialised, the first step's
    update from the gradient alone)."""
    import jax.numpy as jnp

    from flink_ml_tpu.models.lm import reference_nemotron as program_reference
    from flink_ml_tpu.models.lm.decoder_lm import _flat_names, _ordered, init_params

    cfg = nemotron_lm_fit.lm_config(toy)
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
    """``references/nemotron_lm.py::recurrence`` (blocks of positions under
    ``lax.scan``, rematerialised) against the recurrence as a Python loop over
    16 positions in float64 numpy: ``S <- exp(delta A) S + delta x B^T``, ``y =
    S C``."""
    import jax.numpy as jnp

    from perfbench.references import nemotron_lm as reference

    rng = np.random.default_rng(3)
    t, heads, p, n = 16, 3, 4, 5
    x, b, c = rng.standard_normal((t, heads, p)), rng.standard_normal((t, heads, n)), rng.standard_normal((t, heads, n))
    delta, a = rng.uniform(0.001, 0.1, (t, heads)), -rng.uniform(1.0, 16.0, heads)
    state, want = np.zeros((heads, p, n)), np.zeros((t, heads, p))
    for i in range(t):
        for h in range(heads):
            state[h] = np.exp(delta[i, h] * a[h]) * state[h] + delta[i, h] * np.outer(x[i, h], b[i, h])
            want[i, h] = state[h] @ c[i, h]
    got = reference.recurrence(*(jnp.asarray(m, jnp.float32) for m in (x, b, c, delta, a)))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-6)


def test_the_sound_program_is_correct_and_the_control_is_not(system, want, toy):
    limits = toy["check_limits"]
    got = system.fit()
    sound = system.compare(got, want)
    assert all(sound[k] <= limits[k] for k in limits), sound
    np.testing.assert_array_equal(got["expert_rows"], want["expert_rows"])
    assert got["expert_rows"].shape == (4, toy["n_routed_experts_published"])  # the expert layers alone
    control = system.compare(system.reference("bf16"), want)
    assert any(control[k] > limits[k] for k in limits), control


@pytest.mark.parametrize("defect", ["state_forgotten_at_chunks", "decays_in_bfloat16", "rope_on_attention",
                                    "silu_experts", "absent_experts_served", "no_shared_expert", "half_the_steps"])
def test_a_broken_timed_path_is_not_correct(system, want, toy, defect, monkeypatch):
    """This system's own class with its timed path broken underneath."""
    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.models.lm import decoder_lm
    from flink_ml_tpu.parallel import moe

    decoder_lm._train_program.cache_clear()
    sound_scan, sound_fold, sound_dense = decoder_lm.ssd_scan, decoder_lm._fold, decoder_lm.dense_swiglu
    if defect == "state_forgotten_at_chunks":  # every chunk a sequence of its own
        def forgetful(x, dt, a, b, c, chunk, cd):
            cut = lambda m: m.reshape(-1, chunk, *m.shape[2:])  # noqa: E731
            return sound_scan(cut(x), cut(dt), a, cut(b), cut(c), chunk, cd).reshape(x.shape)

        monkeypatch.setattr(decoder_lm, "ssd_scan", forgetful)
    elif defect == "decays_in_bfloat16":  # step sizes and decay rates rounded on their way into the scan
        monkeypatch.setattr(decoder_lm, "ssd_scan", lambda x, dt, a, b, c, chunk, cd: sound_scan(
            x, dt.astype(jnp.bfloat16).astype(dt.dtype), a.astype(jnp.bfloat16).astype(a.dtype), b, c, chunk, cd))
    elif defect == "rope_on_attention":  # the file's rope_theta applied after all (rotate-half, every channel)
        def turned(q, k, v, cd, interpret, window=None):
            half = q.shape[-1] // 2
            angle = jnp.arange(q.shape[2], dtype=jnp.float32)[:, None] * 1e4 ** (-jnp.arange(half) / half)[None, :]

            def turn(m):
                a, b = m[..., :half], m[..., half:]
                return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                                        b * jnp.cos(angle) + a * jnp.sin(angle)], axis=-1)

            return sound_fold(turn(q), turn(k), v, cd, interpret)

        monkeypatch.setattr(decoder_lm, "_fold", turned)
    elif defect == "silu_experts":  # the shared expert's activation: silu for relu²
        monkeypatch.setattr(decoder_lm, "dense_swiglu", lambda x, g, u, d, cd: (
            jax.nn.silu(x @ u) @ d if g is None else sound_dense(x, g, u, d, cd)))
    elif defect == "absent_experts_served":  # rows routed elsewhere fold onto the held experts
        sound = moe.route_sigmoid_top_k

        def folded(x, router, k, routed_scale, select_bias=None):
            p, top_p, top_e = sound(x, router, k, routed_scale, select_bias)
            return p, top_p, toy["first_expert_held"] + top_e % toy["n_routed_experts"]

        monkeypatch.setattr(moe, "route_sigmoid_top_k", folded)
    elif defect == "no_shared_expert":
        monkeypatch.setattr(decoder_lm, "dense_swiglu", lambda x, g, u, d, cd: (
            0.0 if g is None else 1.0) * sound_dense(x, g, u, d, cd))
    got = system.fit()
    if defect == "half_the_steps":
        got["losses"] = got["losses"][:1]
    decoder_lm._train_program.cache_clear()
    limits = toy["check_limits"]
    result = system.compare(got, want)
    assert any(result[k] > limits[k] for k in limits), result


def _brute_force(d):
    """Forward multiply-adds (x 2) a token of one layer of each kind and of the
    head, and the parameters, counted matrix by matrix from the toy shapes."""
    hidden, heads, p, groups, n = d["hidden"], d["ssm_heads"], d["ssm_head_dim"], d["ssm_groups"], d["ssm_state"]
    inner, conv = heads * p, heads * p + 2 * groups * n
    mats = {
        "M": [(hidden, inner + conv + heads), (inner, hidden)],
        "*": [(hidden, d["heads"] * d["head_dim"]), (hidden, d["kv_heads"] * d["head_dim"]),
              (hidden, d["kv_heads"] * d["head_dim"]), (d["heads"] * d["head_dim"], hidden)],
        "E": [(hidden, d["experts"]), (hidden, d["shared_width"]), (d["shared_width"], hidden)],
    }
    flops = {k: sum(2 * r * c for r, c in v) for k, v in mats.items()}
    # the recurrence, a head a position: decay the state (P N), delta x B^T (P N) added (P N), S C (2 P N)
    # and the read-out's sum: 6 P N in all, as the issue counts it
    flops["M"] += heads * 6 * p * n
    # scores and values against the causal half of the keys: 2 matmuls x 2 x (T / 2) x D a head
    flops["*"] += d["heads"] * 2 * 2 * (d["seq"] / 2) * d["head_dim"]
    small = {"M": (d["conv_kernel"] + 1) * conv + 3 * heads + inner, "*": 0, "E": d["experts"]}
    params = {k: sum(r * c for r, c in v) + small[k] + hidden for k, v in mats.items()}
    params["E"] += 2 * d["experts_held"] * hidden * d["width"]
    return flops, params


@pytest.mark.parametrize("sizes", ["toy", "published"])
def test_cost_module_against_a_brute_force_count(config, toy, sizes):
    from flink_ml_tpu.models.lm.config import num_params

    cfg = toy if sizes == "toy" else config
    shapes = nemotron_lm_fit.create(cfg, 1, 1).layout_dims
    kinds = shapes["layer_kinds"]
    flops, params = _brute_force(shapes)
    per_token = {k: v for k, v in shapes.items() if k not in ("tokens", "batch", "experts_held", "width",
                                                              "conv_kernel")}
    layers, head = nemotron_costs.forward_flops_per_token(**per_token)
    assert layers == pytest.approx(sum(flops[k] for k in kinds))
    assert head == 2 * shapes["hidden"] * shapes["vocab"]
    want_params = sum(params[k] for k in kinds) + 2 * shapes["vocab"] * shapes["hidden"] + shapes["hidden"]
    assert nemotron_costs.params(**shapes) == want_params == num_params(nemotron_lm_fit.lm_config(cfg))
    rows = 1000
    expert = 2 * 2 * shapes["hidden"] * shapes["width"]  # two matrices a held (token, expert) row
    got, nbytes = nemotron_costs.model(rows_held=rows, **shapes)
    assert got == pytest.approx(3 * (shapes["tokens"] * (layers + head) + rows * expert))
    assert nbytes == want_params * 28
    held_flops, held_bytes = nemotron_costs.held_experts(rows_held=rows, **shapes)
    assert held_flops == 3 * rows * expert
    assert held_bytes == kinds.count("E") * shapes["experts_held"] * 2 * shapes["hidden"] * shapes["width"] * 8 \
        + rows * (2 * shapes["hidden"] + 2 * shapes["width"]) * 2 * 3
    fold_flops, fold_bytes = nemotron_costs.nope_fold(**shapes)
    assert fold_flops == 6 * 2 * (shapes["seq"] ** 2 / 2) * shapes["head_dim"] * shapes["heads"] * shapes["batch"] \
        * kinds.count("*")
    assert fold_bytes == 4 * shapes["batch"] * (shapes["heads"] + shapes["kv_heads"]) * shapes["seq"] \
        * shapes["head_dim"] * 2 * kinds.count("*")
    if sizes == "published":  # the issue's arithmetic a token, and a step's
        assert want_params == 666_963_456
        # the sliced head: 13.8% of the cut's matmul parameters a token (the issue's count), 12.3% of its forward
        # operations once the attention layer's scores at T 8,192 and the scans' recurrences are in
        assert 0.12 < head / (layers + head + 6 / 16 * expert * 4) < 0.13
        assert 0.45 < 4 * flops["M"] / (layers + head) < 0.55  # the Mamba-2 layers: half the step's operations
        scan_flops, scan_bytes = nemotron_costs.ssd_scan(**shapes)
        assert scan_flops == 3 * 4 * 2 * 8192 * 64 * 6 * 64 * 128
        # x, z, y at 4,096 channels and B, C at 1,024, 2 bytes each, delta's 64 floats: 0.58 ms a layer forward
        assert scan_bytes == 3 * 4 * 2 * 8192 * ((3 * 4096 + 2 * 1024) * 2 + 64 * 4)
        assert scan_bytes / 819e9 > scan_flops / 197e12  # bound by HBM


def test_the_scans_cost_does_not_depend_on_the_chunk(config):
    """``ssm_scan_roofline`` divides by the recurrence's own work: no chunk
    size is among the shapes the cost reads, so a later kernel, or another
    chunk, is judged on one yardstick."""
    base = nemotron_lm_fit.create(config, 1, 1).layout_dims
    assert "chunk" not in base and "chunk_size" not in base
    costs = {chunk: nemotron_costs.ssd_scan(**nemotron_lm_fit.create({**config, "chunk_size": chunk}, 1, 1).layout_dims)
             for chunk in (64, 128, 256)}
    assert len(set(costs.values())) == 1
    spec = Manifest().layer_metric("ssm_scan_roofline")
    assert spec["params"]["cost"] == "ssd_scan" and spec["params"]["scopes"] == ["lm.block/scan"]


def test_the_roofline_reducer_on_recorded_counts(config):
    """The expert kernels' share from a recorded run's numbers: 40 ms of
    ``ragged-dot`` a step against the two matmuls an expert on the rows
    ``train.drain`` counted; a run whose layout names no scan (a parent commit
    without the block kind, another configuration) or whose fits wrote no held
    rows reads as no metric, not as an error."""
    import types

    from perfbench import program_spans
    from perfbench.reducers import lm_roofline_pct

    shapes = nemotron_lm_fit.create(config, 1, 1).layout_dims
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}

    def ctx_of(layout, stats):
        table = program_spans.Table([program_spans.Span("train.drain", 10.0, 1.0, stats=stats)])
        return types.SimpleNamespace(
            run=types.SimpleNamespace(program_spans=table), config=config, w0=0.0, w1=100.0,
            facts={"layout": layout, "steps": 4}, peaks=peaks, per=lambda unit: 4, ops=lambda: [("ragged-dot-none.3", 20.0, 160e6)])

    drained = {"rows_held": 4 * 12_288, "steps": 4}
    got = lm_roofline_pct.reduce(ctx_of(shapes, drained), "held_experts", pattern="^ragged-dot")
    flops, nbytes = nemotron_costs.held_experts(rows_held=12_288, **shapes)
    assert got == pytest.approx(100 * max(flops / 197e12, nbytes / 819e9) / 0.040) and 0 < got < 100
    assert lm_roofline_pct.reduce(ctx_of({"tokens": 8192}, drained), "held_experts", pattern="^ragged-dot") is None
    assert lm_roofline_pct.reduce(ctx_of(shapes, {"steps": 4}), "held_experts", pattern="^ragged-dot") is None
    assert lm_roofline_pct.reduce(ctx_of(shapes, drained), "held_experts", pattern="^no_such_kernel") is None
