"""``DecoderLM`` with ``blockKind`` ``nemotron_h`` (a stack whose layers are
ONE mixer each: a Mamba-2 scan, attention without a position encoding, or
relu² experts beside a shared one) against its plain reference
(models/lm/reference_nemotron.py) on seeded random weights at toy size: the
published period ``MEMEM*EME`` (9 layers), hidden 64; Mamba-2 with 8 heads of
8 channels in 2 groups, a state of 16, 4 taps, chunks of 64 (T 256: four
chunks, so the chunk states' recurrence is real); 4 query heads of 16 on 2
key/value heads; 16 experts of width 32 (top-2, scaled 2.5; experts 2..3 held,
an eighth: the expert layers take their 1,024 routed rows through the experts
in windows of 512, ``parallel/moe.py``) beside a shared one of width 48; an
untied vocabulary of 512, batch 2, 2 steps. The same fit loop, head, loss
chunking, clip and AdamW program as the other kinds, chosen by a stage
parameter.

Tolerances. float32: stage and reference compute the same mathematics in
different orders (the scan in chunks against one position at a time), so they
differ by float32 rounding; read here the loss by 3e-7 relative, the gradient
norm by 2e-6, a leaf's gradient by 5e-5 of its largest entry (the limits: 1e-5
on the losses, 1e-4 on the norms, 2e-4 on the leaves). bfloat16 matmul inputs:
the loss by 3e-4, the gradient norm by 6e-3; the bands are 2e-3 and 3e-2.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu import trace
from flink_ml_tpu.api.dataframe import DataFrame
from flink_ml_tpu.metrics import MLMetrics, metrics
from flink_ml_tpu.models.lm import DecoderLM, DecoderLMModel, decoder_lm
from flink_ml_tpu.models.lm import reference_nemotron as ref
from flink_ml_tpu.models.lm.config import A_RANGE, DT_FLOOR, DT_RANGE, LMConfig, layers, num_params, param_shapes
from flink_ml_tpu.models.lm.decoder_lm import _flat_names, _ordered, init_params
from flink_ml_tpu.parallel import flash
from flink_ml_tpu.utils.read_write import load_stage

CFG = LMConfig(n_layers=9, hidden=64, n_heads=4, n_experts=16, top_k=2, expert_width=32, vocab=512,
               norm_eps=1e-5, aux_coef=0.0, block="nemotron_h", experts_held=2, first_held=2, n_kv_heads=2,
               head_size=16, shared_width=48, routed_scale=2.5, layer_kinds=tuple("MEMEM*EME"), ssm_heads=8,
               ssm_head_dim=8, ssm_groups=2, ssm_state=16, conv_kernel=4, chunk=64)
#: a stack that starts with experts and ends in a scan, each kind once
SHORT = CFG._replace(n_layers=3, layer_kinds=tuple("E*M"))
N, T, BATCH, STEPS, LR, SEED = 4, 256, 2, 2, 1e-3, 7
F32 = jnp.dtype("float32")


def _estimator(compute_type="float32", cfg=CFG):
    return (
        DecoderLM().set_block_kind("nemotron_h")
        .set_num_layers(cfg.n_layers).set_layer_pattern("".join(cfg.layer_kinds)).set_hidden_size(cfg.hidden)
        .set_ssm_num_heads(cfg.ssm_heads).set_ssm_head_size(cfg.ssm_head_dim).set_ssm_num_groups(cfg.ssm_groups)
        .set_ssm_state_size(cfg.ssm_state).set_ssm_conv_kernel(cfg.conv_kernel).set_ssm_chunk_size(cfg.chunk)
        .set_num_heads(cfg.n_heads).set_num_kv_heads(cfg.n_kv_heads).set_head_size(cfg.head_size)
        .set_num_experts(cfg.n_experts).set_experts_per_token(cfg.top_k).set_expert_width(cfg.expert_width)
        .set_experts_held(cfg.experts_held).set_first_expert_held(cfg.first_held)
        .set_shared_expert_width(cfg.shared_width).set_routed_scale(cfg.routed_scale)
        .set_vocab_size(cfg.vocab).set_norm_eps(cfg.norm_eps).set_compute_type(compute_type)
        .set_max_iter(STEPS).set_global_batch_size(BATCH).set_learning_rate(LR).set_seed(SEED)
    )


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, CFG.vocab, (N, T))


@pytest.fixture(scope="module")
def df(tokens):
    return DataFrame.from_dict({"features": tokens})


@pytest.fixture(scope="module")
def fitted(df):
    est = _estimator()
    with trace.capture() as recorder:
        model = est.fit(df)
    return est, model, {s.name: s.attrs for s in recorder.snapshot()}


def _moved(cfg, seed=SEED):
    """The seed's weights with every leaf that starts at a constant moved off
    it (the selection bias among them: it then changes which experts are chosen)."""
    leaves = _ordered(init_params(cfg, seed), cfg)
    key = jax.random.key(99)
    # the bias by a hundredth of the scores' spread: it changes some choices, not all of them
    step = {"normal": 0.0, "ones": 0.1, "zeros": 0.002, "dt_bias": 0.0, "a_log": 0.0}
    moved = [leaf + step[kind] * jax.random.normal(jax.random.fold_in(key, i), leaf.shape)
             for i, (leaf, (_, _, kind)) in enumerate(zip(leaves, param_shapes(cfg)))]
    return decoder_lm._build_tree(cfg, moved)


def _batches(tokens):
    return [jnp.asarray(tokens[lo: lo + BATCH]) for lo in (0, 2)]


@pytest.fixture(scope="module")
def reference_run(tokens):
    return ref.train_steps(init_params(CFG, SEED), _batches(tokens), CFG, LR)


def _rel(a, b):
    return float(np.max(np.abs(np.asarray(a) - np.asarray(b)) / np.abs(np.asarray(b))))


def _norm(g):
    return jnp.sqrt(jnp.sum(g * g))


def test_the_stage_config_is_the_tests(fitted):
    est, _, _ = fitted
    assert est.lm_config(CFG.vocab) == CFG


def _cell_config():
    """The ``nemotron3_nano_30b`` configuration's ``LMConfig`` as the benchmark's system builds it."""
    from perfbench.manifest import Manifest
    from perfbench.systems import nemotron_lm_fit

    return nemotron_lm_fit.lm_config(Manifest().config("nemotron3_nano_30b"))


def test_parameter_count_at_the_cells_sizes():
    """ISSUE 40's arithmetic from the program's own ``param_shapes``, at 16
    bytes a parameter: a Mamba-2 layer, the attention layer, an expert layer
    with its 8 held experts, the 16,384-row slice of the untied embedding and
    head; and the published 31.6 B over all 52 layers and 128 experts."""
    cfg = _cell_config()
    assert "".join(cfg.layer_kinds) == "MEMEM*EME"
    per_layer = {}
    for path, shape, _ in param_shapes(cfg):
        if path[0] == "layers":
            per_layer[path[1]] = per_layer.get(path[1], 0) + int(np.prod(shape))
    assert [per_layer[i] for i in range(9)] == [
        38_744_896, 100_125_440, 38_744_896, 100_125_440, 38_744_896, 23_399_040, 100_125_440, 38_744_896,
        100_125_440]
    assert num_params(cfg) == 666_963_456  # 10.67 GB at 16 B
    assert num_params(cfg) - sum(per_layer.values()) == 2 * 16_384 * 2_688 + 2_688
    published = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    whole = cfg._replace(n_layers=52, layer_kinds=tuple(published), experts_held=0, vocab=131_072)
    assert (published.count("M"), published.count("E"), published.count("*")) == (23, 23, 6)
    assert round(num_params(whole) / 1e9, 1) == 31.6


def test_loss_and_gradient_norm_of_every_step(fitted, reference_run):
    est, _, _ = fitted
    _, losses, norms = reference_run
    assert len(est.loss_history) == STEPS
    assert _rel(est.loss_history, losses) < 1e-5
    assert _rel(est.grad_norm_history, norms) < 1e-4


def test_every_leafs_gradient_norm_in_the_fit(fitted, tokens):
    est, _, _ = fitted
    _, grads = ref.loss_and_grads(init_params(CFG, SEED), _batches(tokens)[0], CFG)
    assert est.param_names == _flat_names(CFG)
    for name, got, w in zip(est.param_names, est.param_grad_norm_history[0], _ordered(grads, CFG)):
        if name.endswith("router_bias"):
            assert got == 0.0 == float(_norm(w)), name
        else:
            assert _rel(got, _norm(w)) < 1e-4, name


def test_every_parameter_after_two_steps(fitted, reference_run):
    _, model, _ = fitted
    want = reference_run[0]
    for name, a, b in zip(_flat_names(CFG), _ordered(model.params, CFG), _ordered(want, CFG)):
        assert float(jnp.max(jnp.abs(a - b))) < 0.05 * STEPS * LR, name


@pytest.mark.parametrize("cfg", [CFG, SHORT], ids=["MEMEM*EME", "E*M"])
@pytest.mark.parametrize("compute_type,leaf_tol,norm_tol,faint_tol",
                         [("float32", 2e-4, 1e-4, 1e-4), ("bfloat16", None, 2e-2, 8e-2)])
def test_every_parameters_gradient(cfg, tokens, compute_type, leaf_tol, norm_tol, faint_tol):
    """Forward, loss and the gradient of every leaf - the scan's ``A_log``,
    ``dt_bias``, ``D`` and convolution, the gated norm, the router, the shared
    expert - against ``jax.grad`` of the plain reference (the scan one
    position at a time), from weights with nothing at a constant. The
    selection bias has no gradient on either side. ``faint_tol`` holds the
    leaves whose gradient is under 1e-8 of the whole gradient's norm: here
    ``A_log`` and ``dt_bias`` alone, at 8e-10 .. 1e-8 of it, sums of cancelling
    terms over every position. In bfloat16 the worst of them, the first layer's
    ``dt_bias``, reads 6.4e-2 through the scan's kernels (5.7e-2 through AD of
    the ``jax.numpy`` chunked form they replaced: the same roundings in another
    order) and the next 2.4e-2; every other leaf is 7e-4 of the whole or more
    and reads under 1.4e-2."""
    params = _moved(cfg)
    tok = _batches(tokens)[0]
    want_loss, want = ref.loss_and_grads(params, tok, cfg)
    (loss, stats), got = jax.value_and_grad(decoder_lm._loss, has_aux=True)(
        params, tok, cfg, jnp.dtype(compute_type), True)
    assert _rel(loss, want_loss) < (1e-5 if leaf_tol else 2e-3)
    layers = cfg.layer_kinds.count("E")
    assert stats["rows"].shape == (layers, cfg.n_experts)  # only the expert layers report
    # every expert layer took one window of its sorted rows through the experts, not all 1,024 of them
    assert stats["carried"].tolist() == [512] * layers
    whole = float(jnp.sqrt(sum(jnp.sum(jnp.square(w)) for w in _ordered(want, cfg))))
    for name, g, w in zip(_flat_names(cfg), _ordered(got, cfg), _ordered(want, cfg)):
        if name.endswith("router_bias"):
            assert float(jnp.max(jnp.abs(g))) == 0.0 == float(jnp.max(jnp.abs(w))), name
            continue
        assert float(jnp.max(jnp.abs(w))) > 0, name
        if leaf_tol:
            assert float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w))) < leaf_tol, name
        assert _rel(_norm(g), _norm(w)) < (faint_tol if float(_norm(w)) < 1e-8 * whole else norm_tol), name


def test_bfloat16_fit_within_its_bands(df, reference_run):
    est = _estimator("bfloat16")
    est.fit(df)
    _, losses, norms = reference_run
    assert _rel(est.loss_history, losses) < 2e-3
    assert _rel(est.grad_norm_history, norms) < 3e-2


def test_fits_scores_saves_and_loads(fitted, df, tokens, tmp_path):
    """The same entry points as the other kinds: ``fit``'s histories,
    ``transform``, ``save``/``load`` and the model-data round trip."""
    est, model, _ = fitted
    assert est.expert_rows_history.shape == (STEPS, 4, CFG.n_experts)
    assert (est.expert_rows_history.sum(axis=2) == BATCH * T * CFG.top_k).all()  # routed = held + absent
    got = np.asarray(model.transform(df).scalars("prediction"))
    want = np.asarray(ref.log_likelihood(model.params, jnp.asarray(tokens), CFG))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    path = str(tmp_path / "lm")
    model.save(path)
    loaded = load_stage(path)
    assert isinstance(loaded, DecoderLMModel) and loaded.get_layer_pattern() == "MEMEM*EME"
    assert loaded.lm_config() == CFG
    np.testing.assert_array_equal(np.asarray(loaded.transform(df).scalars("prediction")), got)
    other = DecoderLMModel()
    other.load_param_map_from_json(model.param_map_to_json())
    other.set_model_data(*model.get_model_data())
    np.testing.assert_array_equal(np.asarray(other.transform(df).scalars("prediction")), got)


def test_the_fit_counts_its_mixers_its_chunks_and_its_held_rows(fitted, df):
    """``train.program``'s counts of the three kinds of layer, the scan's
    chunks and the fold's (the one attention layer's alone), ``train.drain``'s
    held and absent rows and what the four expert layers carried, and the
    counters."""
    est, _, spans = fitted
    program, drain = spans["train.program"], spans["train.drain"]
    assert (program["layers_scan"], program["layers_attn"], program["layers_moe"]) == (4, 1, 4)
    assert program["scan_chunks"] == 4 * BATCH * CFG.ssm_heads * (T // CFG.chunk)
    assert program["scan_state_bytes"] == 4 * BATCH * (T // CFG.chunk) * CFG.ssm_heads * CFG.ssm_head_dim * CFG.ssm_state
    full = np.asarray(flash.fold_chunk_counts(T, T, 0, True, one_block=True))
    assert (program["fold_chunks_visited"], program["fold_chunks"]) == tuple(CFG.n_heads * BATCH * full)
    assert "layers_windowed" not in program
    layers = 4
    held = est.expert_rows_history[:, :, CFG.first_held: CFG.first_held + CFG.held]
    assert drain["dropped"] == 0 and drain["rows_held"] == int(held.sum())
    assert drain["rows_held"] + drain["rows_absent"] == STEPS * BATCH * T * CFG.top_k * layers
    assert drain["held_rows_max"] == int(held.max()) and drain["held_rows_mean"] == pytest.approx(held.mean())
    assert drain["moe_layer_steps"] == STEPS * layers == drain["moe_layer_steps_compact"]
    assert drain["moe_rows_routed"] == drain["rows_held"] + drain["rows_absent"]
    assert drain["rows_held"] <= drain["moe_rows_carried"] == 512 * STEPS * layers
    counters = (MLMetrics.TRAIN_LM_SCAN_CHUNKS, MLMetrics.TRAIN_LM_SCAN_LAYERS, MLMetrics.TRAIN_MOE_LAYER_STEPS,
                MLMetrics.TRAIN_MOE_ROWS_CARRIED)
    before = [metrics.get(MLMetrics.TRAIN_GROUP, name) for name in counters]
    _estimator().set_max_iter(1).fit(df)
    assert [metrics.get(MLMetrics.TRAIN_GROUP, name) - was for name, was in zip(counters, before)] == \
        [program["scan_chunks"], 4, layers, 512 * layers]


def test_every_chunk_of_the_scan_passes_through_its_kernels(fitted, df):
    """``train.program``'s ``scan_chunks_kernel`` - the kernels' grid cells
    times the heads of a cell, every Mamba-2 layer - is all of ``scan_chunks``,
    and the registry counts ``steps x`` it."""
    from flink_ml_tpu.parallel.ssd import scan_kernel_chunks

    program = fitted[2]["train.program"]
    assert program["scan_chunks_kernel"] == program["scan_chunks"] > 0
    assert program["scan_chunks_kernel"] == 4 * scan_kernel_chunks(BATCH, T, CFG.ssm_heads, CFG.ssm_groups, CFG.chunk)
    before = metrics.get(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LM_SCAN_KERNEL_CHUNKS)
    _estimator().set_max_iter(2).fit(df)
    assert metrics.get(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LM_SCAN_KERNEL_CHUNKS) - before == \
        2 * program["scan_chunks_kernel"]


def test_every_position_of_the_convolution_passes_through_its_kernels(fitted, df):
    """``train.program``'s ``conv_positions_kernel`` - what the convolution's
    forward kernels cover, each call's grid cells times its block, added up
    over the kernel calls of the step as traced - is all of
    ``conv_positions`` (positions x convolved channels, every Mamba-2 layer),
    and the registry counts ``steps x`` both. A second fit finds the step
    traced and reads the same count."""
    program = fitted[2]["train.program"]
    channels = CFG.ssm_heads * CFG.ssm_head_dim + 2 * CFG.ssm_groups * CFG.ssm_state
    assert program["conv_positions_kernel"] == program["conv_positions"] == 4 * BATCH * T * channels > 0
    counters = (MLMetrics.TRAIN_LM_CONV_POSITIONS, MLMetrics.TRAIN_LM_CONV_KERNEL_POSITIONS)
    before = [metrics.get(MLMetrics.TRAIN_GROUP, name) for name in counters]
    with trace.capture() as recorder:
        _estimator().set_max_iter(2).fit(df)
    again = {s.name: s.attrs for s in recorder.snapshot()}["train.program"]
    assert again["conv_positions_kernel"] == program["conv_positions_kernel"] and not again["built"]
    assert [metrics.get(MLMetrics.TRAIN_GROUP, name) - was for name, was in zip(counters, before)] == \
        [2 * program["conv_positions"]] * 2


def test_the_scan_leaves_start_where_the_published_ranges_say():
    """``dt_bias`` is the inverse softplus of a step size in ``time_step_min ..
    time_step_max`` (log-uniform, floored), ``A_log`` the log of a decay rate
    in 1 .. 16, ``D`` ones, each from its own leaf's stream of the seed."""
    cfg = CFG._replace(ssm_heads=512, ssm_groups=2, n_layers=1, layer_kinds=("M",))
    (w,) = init_params(cfg, SEED)["layers"]
    dt = np.asarray(jax.nn.softplus(w["dt_bias"]))
    assert DT_RANGE[0] * (1 - 1e-5) <= dt.min() < 2 * DT_RANGE[0] and DT_RANGE[1] / 2 < dt.max() <= DT_RANGE[1] * (1 + 1e-5)
    assert dt.min() >= DT_FLOOR and abs(np.median(np.log(dt)) - np.log(1e-2)) < 0.3
    a = np.exp(np.asarray(w["A_log"]))
    assert A_RANGE[0] <= a.min() < 1.5 and 15.5 < a.max() <= A_RANGE[1] and abs(a.mean() - 8.5) < 0.6
    assert not np.array_equal(np.asarray(w["A_log"]), np.asarray(init_params(cfg, SEED + 1)["layers"][0]["A_log"]))
    np.testing.assert_array_equal(np.asarray(w["D"]), 1.0)
    np.testing.assert_array_equal(np.asarray(w["conv_b"]), 0.0)


# -- the share and the model ------------------------------------------------------------


def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """One chip of sixteen (toy: of four) holds a range of a layer's experts;
    every chip computes the shared expert alike. At a small size (16 experts,
    4 a share: a quarter held, so each share takes its rows in windows): a
    share's layer output is ``x + routed_s + S``, so the four outputs less
    three times ``x + S`` - the routed parts of all four shares, the shared
    expert ONCE - are the uncut reference's layer output."""
    uncut = CFG._replace(n_layers=1, layer_kinds=("E",), experts_held=0, first_held=0)
    (w,) = _moved(uncut, seed=3)["layers"]
    x = 0.5 * jax.random.normal(jax.random.key(8), (BATCH, T, CFG.hidden))
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.layer(row, w, uncut, "E")[0] for row in x])
        shared = ref.relu2(ref.rms_norm(x, w["norm"], CFG.norm_eps), w["shared_up"], w["shared_down"])
    total = 0.0
    for first in range(0, 16, 4):
        share = uncut._replace(experts_held=4, first_held=first)
        held = dict(w, **{name: w[name][first: first + 4] for name in ("w_up", "w_down")})
        out, _, stats = decoder_lm._layer(x, None, held, layers(share)[0], F32, True)
        assert int(stats["rows"].sum()) == BATCH * T * CFG.top_k  # routed = held + absent, whatever is held
        assert int(stats["rows"][first: first + 4].sum()) <= int(stats["carried"]) <= 1024
        total = total + out
    assert float(jnp.max(jnp.abs(want - x - shared))) > 0.01  # the routed part is not nothing
    np.testing.assert_allclose(np.asarray(total - 3 * (x + shared)), np.asarray(want), rtol=2e-4, atol=2e-5)


def _silu_experts(u, up, down):
    return jax.nn.silu(u @ up) @ down


def _softmax_experts(u, w, cfg):
    p = jax.nn.softmax(u @ w["router"], axis=-1)
    picked, chosen = jax.lax.top_k(p, cfg.top_k)
    y = jnp.zeros_like(u)
    for j in range(cfg.held):
        w_j = jnp.sum(jnp.where(chosen == cfg.first_held + j, picked, 0.0), axis=1)
        y = y + w_j[:, None] * ref.relu2(u, w["w_up"][j], w["w_down"][j])
    return y, chosen


def _rope_attention(u, w, cfg):
    """The reference's attention with rotate-half RoPE at the file's unused ``rope_theta`` on q and k."""
    from flink_ml_tpu.models.lm.reference_laguna import turn

    t, heads, kv, d = u.shape[0], cfg.n_heads, cfg.kv_heads, cfg.head_dim
    inv_freq = 1.0 / (1e4 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    q = turn((u @ w["wq"]).reshape(t, heads, d), inv_freq)
    k = jnp.repeat(turn((u @ w["wk"]).reshape(t, kv, d), inv_freq), heads // kv, axis=1)
    v = jnp.repeat((u @ w["wv"]).reshape(t, kv, d), heads // kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * (d ** -0.5)
    keep = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1), v)
    return o.reshape(t, heads * d) @ w["wo"]


@pytest.mark.parametrize("defect", ["rope_on_attention", "silu_experts", "softmax_gates", "no_routed_scale",
                                    "no_shared_expert", "no_skip", "taps_reversed", "state_forgotten_at_chunks"])
def test_a_defect_is_told_apart(defect, fitted, tokens, monkeypatch):
    """The defect, planted in the reference, moves the first step's loss or a
    leaf's gradient norm past the limits the sound stage is held to (1e-5,
    1e-4)."""
    est, _, _ = fitted
    cfg, mamba, layer = CFG, ref.mamba, ref.layer
    if defect == "rope_on_attention":
        monkeypatch.setattr(ref, "attention", _rope_attention)
    elif defect == "silu_experts":
        monkeypatch.setattr(ref, "relu2", _silu_experts)
    elif defect == "softmax_gates":
        monkeypatch.setattr(ref, "experts", _softmax_experts)
    elif defect == "no_routed_scale":
        cfg = CFG._replace(routed_scale=1.0)
    elif defect == "no_shared_expert":
        monkeypatch.setattr(ref, "layer", lambda x, w, c, kind: (
            (x + ref.experts(ref.rms_norm(x, w["norm"], c.norm_eps), w, c)[0], None) if kind == "E"
            else layer(x, w, c, kind)))
    elif defect == "no_skip":
        monkeypatch.setattr(ref, "mamba", lambda u, w, c: mamba(u, dict(w, D=jnp.zeros_like(w["D"])), c))
    elif defect == "taps_reversed":  # tap 0 reads the position itself
        monkeypatch.setattr(ref, "mamba", lambda u, w, c: mamba(u, dict(w, conv_w=w["conv_w"][::-1]), c))
    else:  # the scan's state forgotten at every chunk's edge: each chunk of 64 positions run on its own
        # (with the three positions before it, which the convolution reads: they reach the kept rows through
        # the scan too, so this is a milder defect than a state never carried)
        monkeypatch.setattr(ref, "mamba", lambda u, w, c: jnp.concatenate(
            [mamba(u[max(lo - 3, 0): lo + c.chunk], w, c)[-c.chunk:] for lo in range(0, u.shape[0], c.chunk)]))
    loss, grads = ref.loss_and_grads(init_params(CFG, SEED), _batches(tokens)[0], cfg)
    leaves = [_rel(got, _norm(w)) for name, got, w in zip(est.param_names, est.param_grad_norm_history[0],
                                                         _ordered(grads, CFG)) if not name.endswith("router_bias")]
    assert _rel(est.loss_history[0], float(loss)) > 1e-5 or max(leaves) > 1e-4, defect


def test_bad_sizes_are_refused(df):
    with pytest.raises(ValueError, match="names each of the 9 layers"):
        _estimator().set_layer_pattern("MEM").fit(df)
    with pytest.raises(ValueError, match="names each of the 9 layers"):
        _estimator().set_layer_pattern("MEMEM-EME").fit(df)
    with pytest.raises(ValueError, match="whole ssmNumGroups"):
        _estimator().set_ssm_num_groups(3).fit(df)
    with pytest.raises(ValueError, match="divide evenly over numKvHeads"):
        _estimator().set_num_heads(3).fit(df)
    with pytest.raises(ValueError, match="sharedExpertWidth"):
        _estimator().set_shared_expert_width(0).fit(df)
    with pytest.raises(ValueError, match="belong to blockKind 'nemotron_h'"):
        DecoderLM().set_layer_pattern("ME").set_vocab_size(512).fit(df)
    with pytest.raises(ValueError, match="the scan's chunk"):
        _estimator().set_ssm_chunk_size(96).fit(df)
