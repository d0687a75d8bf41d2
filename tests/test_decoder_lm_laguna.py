"""``DecoderLM`` with ``blockKind`` ``laguna`` (windowed and full attention
layers of different head counts in one stack, a per-head output gate, a
leading dense layer, sigmoid-gated experts beside a shared one) against its
plain reference (models/lm/reference_laguna.py) on seeded random weights at
toy size: 4 layers (full + dense, windowed, windowed, full), hidden 64, 4 or 8
query heads of 16 on 2 key/value heads, a window of 96 keys, YaRN on the full
layers' 8 turned channels, a dense SwiGLU of width 96, 16 experts of width 32
(top-2; experts 2..3 held, an eighth as in the cell: the expert layers take
their 1,024 routed rows through the experts in windows of 512,
``parallel/moe.py``) beside a shared one of width 32, an untied vocabulary of
512, T 256, batch 2, 2 steps. The same fit loop, head, loss chunking, clip and
AdamW program as the other kinds, chosen by a stage parameter. And the
windowed fold itself, forward and both backward kernels (interpreted), against
``reference_fold`` / ``reference_fold_bwd`` with the same window at lengths of
several key chunks.

Tolerances. float32: stage and reference compute the same mathematics in
different orders, so they differ by float32 rounding; read here the loss by
2e-7 relative, the gradient norm by 1e-6, a leaf's gradient by 3e-5 of its
largest entry (the limits: 1e-5 on the losses, 1e-4 on the norms and leaves).
bfloat16 matmul inputs: the loss by 2e-4, the gradient norm by 4e-3; the bands
are 2e-3 and 3e-2.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu import trace
from flink_ml_tpu.api.dataframe import DataFrame
from flink_ml_tpu.metrics import MLMetrics, metrics
from flink_ml_tpu.models.lm import DecoderLM, DecoderLMModel, decoder_lm
from flink_ml_tpu.models.lm import reference_laguna as ref
from flink_ml_tpu.models.lm.config import LMConfig, layers, num_params, param_shapes
from flink_ml_tpu.models.lm.decoder_lm import _flat_names, _ordered, init_params
from flink_ml_tpu.parallel import flash
from flink_ml_tpu.utils.read_write import load_stage

YARN = (4.0, 64.0, 8.0, 1.0, 1.1386)
CFG = LMConfig(n_layers=4, hidden=64, n_heads=4, n_experts=16, top_k=2, expert_width=32, vocab=512,
               rope_theta=5e5, norm_eps=1e-6, aux_coef=0.0, block="laguna", experts_held=2, first_held=2,
               n_kv_heads=2, head_size=16, rope_fraction=0.5, layer_heads=(4, 8, 8, 4),
               layer_windows=(0, 96, 96, 0), n_dense=1, dense_width=96, shared_width=32, routed_scale=2.5,
               window_rope_theta=1e4, yarn=YARN)
N, T, BATCH, STEPS, LR, SEED = 4, 256, 2, 2, 1e-3, 7
F32 = jnp.dtype("float32")


def _estimator(compute_type="float32", cfg=CFG):
    return (
        DecoderLM().set_block_kind("laguna")
        .set_num_layers(cfg.n_layers).set_hidden_size(cfg.hidden).set_num_heads(cfg.n_heads)
        .set_num_kv_heads(cfg.n_kv_heads).set_head_size(cfg.head_size)
        .set_num_heads_per_layer(list(cfg.layer_heads)).set_window_per_layer(list(cfg.layer_windows))
        .set_rope_theta(cfg.rope_theta).set_rope_fraction(cfg.rope_fraction).set_rope_yarn(list(cfg.yarn))
        .set_window_rope_theta(cfg.window_rope_theta)
        .set_dense_layers(cfg.n_dense).set_dense_width(cfg.dense_width)
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
    step = {"normal": 0.0, "ones": 0.1, "zeros": 0.002}
    moved = [leaf + step[kind] * jax.random.normal(jax.random.fold_in(key, i), leaf.shape)
             for i, (leaf, (_, _, kind)) in enumerate(zip(leaves, param_shapes(cfg)))]
    return decoder_lm._build_tree(cfg, moved)


@pytest.fixture(scope="module")
def params():
    return _moved(CFG)


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
    """The ``laguna_xs2`` configuration's ``LMConfig`` as the benchmark's system builds it."""
    from perfbench.manifest import Manifest
    from perfbench.systems import laguna_lm_fit

    return laguna_lm_fit.lm_config(Manifest().config("laguna_xs2"))


def test_parameter_count_at_the_cells_sizes():
    """The issue's arithmetic from the program's own ``param_shapes``, at 16
    bytes a parameter: layer 0 (full attention on 48 heads, the dense SwiGLU),
    a windowed layer on 64 heads with the 32 held experts, layer 4, the
    12,544-row slice of the untied embedding and head; and the published
    33.4 B over all 40 layers and 256 experts."""
    cfg = _cell_config()
    per_layer = {}
    for path, shape, _ in param_shapes(cfg):
        if path[0] == "layers":
            per_layer[path[1]] = per_layer.get(path[1], 0) + int(np.prod(shape))
    assert per_layer == {0: 79_794_176, 1: 142_217_472, 2: 142_217_472, 3: 142_217_472, 4: 133_796_096}
    assert num_params(cfg) == 691_624_960 and 11.0e9 < 16 * num_params(cfg) < 11.1e9
    assert 2 * 12_544 * 2048 == 51_380_224
    period = ((0, 512, 512, 512) * 10, (48, 64, 64, 64) * 10)
    whole = cfg._replace(n_layers=40, experts_held=0, vocab=100_352, layer_windows=period[0], layer_heads=period[1])
    assert 33.4e9 < num_params(whole) < 33.5e9
    names = _flat_names(cfg)
    assert "layers.0.w_gate" in names and "layers.0.router" not in names and "layers.1.shared_down" in names


def test_loss_and_gradient_norm_of_every_step(fitted, reference_run):
    """The loss and the global gradient norm of both steps: the second step's
    loss is the loss after one clipped AdamW update."""
    est, _, _ = fitted
    _, losses, norms = reference_run
    assert len(est.loss_history) == STEPS == len(est.grad_norm_history)
    assert _rel(est.loss_history, losses) < 1e-5
    assert _rel(est.grad_norm_history, norms) < 1e-4
    assert est.param_names == _flat_names(CFG)
    assert est.param_grad_norm_history.shape == (STEPS, len(param_shapes(CFG)))


def test_every_leafs_gradient_norm_in_the_fit(fitted, tokens):
    est, _, _ = fitted
    _, want = ref.loss_and_grads(init_params(CFG, SEED), _batches(tokens)[0], CFG)
    for name, got, w in zip(est.param_names, est.param_grad_norm_history[0], _ordered(want, CFG)):
        if name.endswith("router_bias"):  # it enters the choice of experts alone
            assert got == 0.0 == float(_norm(w)), name
        else:
            assert _rel(got, _norm(w)) < 1e-4, name


def test_every_parameter_after_two_steps(fitted, reference_run):
    _, model, _ = fitted
    want = reference_run[0]
    for name, a, b in zip(_flat_names(CFG), _ordered(model.params, CFG), _ordered(want, CFG)):
        assert float(jnp.max(jnp.abs(a - b))) < 0.05 * STEPS * LR, name


@pytest.mark.parametrize("compute_type,leaf_tol,norm_tol", [("float32", 1e-4, 1e-4), ("bfloat16", None, 6e-2)])
def test_every_parameters_gradient(params, tokens, compute_type, leaf_tol, norm_tol):
    """Forward, loss and the gradient of every leaf - each layer's own ``wq``,
    ``wo`` and head gate at its own head count, the router, the shared expert -
    against ``jax.grad`` of the plain reference, from weights with nothing at
    a constant. The selection bias has no gradient on either side."""
    tok = _batches(tokens)[0]
    want_loss, want = ref.loss_and_grads(params, tok, CFG)
    (loss, stats), got = jax.value_and_grad(decoder_lm._loss, has_aux=True)(
        params, tok, CFG, jnp.dtype(compute_type), True)
    assert _rel(loss, want_loss) < (1e-5 if leaf_tol else 2e-3)
    assert stats["rows"].shape == (CFG.n_layers - CFG.n_dense, CFG.n_experts)
    # every expert layer took one window of its sorted rows through the experts, not all 1,024 of them
    assert stats["carried"].tolist() == [512] * (CFG.n_layers - CFG.n_dense)
    for name, g, w in zip(_flat_names(CFG), _ordered(got, CFG), _ordered(want, CFG)):
        if name.endswith("router_bias"):
            assert float(jnp.max(jnp.abs(g))) == 0.0 == float(jnp.max(jnp.abs(w))), name
            continue
        assert float(jnp.max(jnp.abs(w))) > 0, name
        if leaf_tol:
            assert float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w))) < leaf_tol, name
        assert _rel(_norm(g), _norm(w)) < norm_tol, name


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
    assert est.expert_rows_history.shape == (STEPS, CFG.n_layers - CFG.n_dense, CFG.n_experts)
    assert (est.expert_rows_history.sum(axis=2) == BATCH * T * CFG.top_k).all()  # routed = held + absent
    got = np.asarray(model.transform(df).scalars("prediction"))
    want = np.asarray(ref.log_likelihood(model.params, jnp.asarray(tokens), CFG))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    path = str(tmp_path / "lm")
    model.save(path)
    loaded = load_stage(path)
    assert isinstance(loaded, DecoderLMModel) and loaded.get_window_per_layer() == list(CFG.layer_windows)
    assert loaded.lm_config() == CFG
    np.testing.assert_array_equal(np.asarray(loaded.transform(df).scalars("prediction")), got)
    other = DecoderLMModel()
    other.load_param_map_from_json(model.param_map_to_json())
    other.set_model_data(*model.get_model_data())
    np.testing.assert_array_equal(np.asarray(other.transform(df).scalars("prediction")), got)


def test_the_fit_counts_its_windowed_layers_and_its_held_rows(fitted, df):
    """``train.program``'s counts beside the fold's chunks, ``train.drain``'s
    held and absent rows and what the three expert layers carried, and the counters."""
    est, _, spans = fitted
    program, drain = spans["train.program"], spans["train.drain"]
    full = np.asarray(flash.fold_chunk_counts(T, T, 0, True, one_block=True))
    win = np.asarray(flash.fold_chunk_counts(T, T, 0, True, 96, one_block=True))
    assert (program["layers_windowed"], program["layers_full"]) == (2, 2)
    assert (program["fold_win_chunks_visited"], program["fold_win_chunks"]) == tuple(2 * 8 * BATCH * win)
    assert (program["fold_chunks_visited"], program["fold_chunks"]) == tuple(2 * 8 * BATCH * win + 2 * 4 * BATCH * full)
    layers = CFG.n_layers - CFG.n_dense
    held = est.expert_rows_history[:, :, CFG.first_held: CFG.first_held + CFG.held]
    assert drain["dropped"] == 0 and drain["rows_held"] == int(held.sum())
    assert drain["rows_held"] + drain["rows_absent"] == STEPS * BATCH * T * CFG.top_k * layers
    assert drain["held_rows_max"] == int(held.max()) and drain["held_rows_mean"] == pytest.approx(held.mean())
    # what the expert layers carried: a window of 512 sorted rows where 1,024 were routed, every layer-step
    assert drain["moe_layer_steps"] == STEPS * layers == drain["moe_layer_steps_compact"]
    assert drain["moe_rows_routed"] == drain["rows_held"] + drain["rows_absent"]
    assert drain["rows_held"] <= drain["moe_rows_carried"] == 512 * STEPS * layers
    counters = (MLMetrics.TRAIN_LM_FOLD_WIN_CHUNKS_VISITED, MLMetrics.TRAIN_MOE_LAYER_STEPS,
                MLMetrics.TRAIN_MOE_LAYER_STEPS_COMPACT, MLMetrics.TRAIN_MOE_ROWS_CARRIED)
    before = [metrics.get(MLMetrics.TRAIN_GROUP, name) for name in counters]
    _estimator().set_max_iter(1).fit(df)
    assert [metrics.get(MLMetrics.TRAIN_GROUP, name) - was for name, was in zip(counters, before)] == \
        [program["fold_win_chunks_visited"], layers, layers, 512 * layers]


def test_yarn_tables_against_the_formula_in_numpy():
    """The full layers' ``cos``/``sin`` at the published settings (64 turned
    channels, theta 5e5, factor 64 over 4,096 positions, beta 64 and 1)
    against YaRN written out in float64: the first 5 pairs keep their
    frequency, pairs 16.. are divided by 64, the ramp between."""
    rot, theta, factor, original, fast, slow, scale = 64, 5e5, 64.0, 4096.0, 64.0, 1.0, 1.4158883083359672
    t = 512
    cos, sin = decoder_lm._yarn_tables(t, rot, theta, (factor, original, fast, slow, scale))
    j = np.arange(rot // 2, dtype=np.float64)
    f = theta ** (-2 * j / rot)
    low = np.floor(rot * np.log(original / (fast * 2 * np.pi)) / (2 * np.log(theta)))
    high = np.ceil(rot * np.log(original / (slow * 2 * np.pi)) / (2 * np.log(theta)))
    assert (low, high) == (5, 16)
    g = np.clip((j - low) / (high - low), 0, 1)
    used = g * f / factor + (1 - g) * f
    np.testing.assert_array_equal(used[:6], f[:6])
    np.testing.assert_allclose(used[16:], f[16:] / 64, rtol=1e-15)
    angle = np.arange(t)[:, None] * np.concatenate([used, used])[None, :]
    np.testing.assert_allclose(np.asarray(cos), scale * np.cos(angle), atol=2e-4)
    np.testing.assert_allclose(np.asarray(sin), scale * np.sin(angle), atol=2e-4)
    assert scale == pytest.approx(0.1 * np.log(factor) + 1.0)
    np.testing.assert_allclose(ref.yarn_inv_freq(rot, theta, factor, original, fast, slow), used, rtol=1e-12)
    # and without YaRN the table is the plain one
    plain = decoder_lm._yarn_tables(t, rot, theta, ())
    np.testing.assert_array_equal(np.asarray(plain[0]), np.asarray(decoder_lm._rope_tables(t, rot, theta)[0]))


# -- the windowed fold ---------------------------------------------------------------


def _fold_inputs(t, h, h_kv, d=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    q = jax.random.normal(ks[0], (1, h, t, d))
    k, v = jax.random.normal(ks[1], (1, h_kv, t, d)), jax.random.normal(ks[2], (1, h_kv, t, d))
    state = (jnp.full((1, h, t), -jnp.inf), jnp.zeros((1, h, t)), jnp.zeros((1, h, t, d)))
    cot = (jax.random.normal(ks[3], (1, h, t)), jax.random.normal(ks[4], (1, h, t)),
           jax.random.normal(ks[5], (1, h, t, d)))
    return q, k, v, state, cot


#: ``(T, window)``: a window under, equal to and over the 1,024-key chunk (and
#: the 512-row tile its lower edge crosses), one that is no multiple of a
#: tile, one on a single chunk (the whole-block kernels), one wider than T
WINDOWS = [(2048, 512), (2048, 1024), (2048, 1536), (3072, 700), (256, 100), (2048, 4096)]


@pytest.mark.parametrize("t,window", WINDOWS)
def test_the_windowed_fold_forward_and_backward(t, window):
    """All three kernels, grouped queries (2 on 1), against the jnp references
    with the same window; ``dk``, ``dv`` summed over the group."""
    h, h_kv, scale = 2, 1, 0.25
    q, k, v, state, cot = _fold_inputs(t, h, h_kv, seed=t + window)
    zero = jnp.int32(0)
    rep = lambda a: jnp.repeat(a, h // h_kv, axis=1)  # noqa: E731
    got = flash._fold_pallas(q, k, v, *state, zero, zero, True, None, scale, interpret=True, window=window)
    want = flash.reference_fold(q, rep(k), rep(v), *state, zero, zero, True, None, scale, window=window)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5, atol=2e-5)
    got = flash._fold_bwd_pallas(q, k, v, *state, zero, zero, True, None, scale, *cot, interpret=True, window=window)
    want = list(flash.reference_fold_bwd(q, rep(k), rep(v), *state, zero, zero, True, None, scale, *cot, window=window))
    want[1], want[2] = (a.reshape(1, h_kv, h // h_kv, t, -1).sum(2) for a in want[1:3])
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-4)
    # and the mask is a window: the first query past it no longer sees key 0
    if window < t:
        s = flash.reference_fold(q, rep(k), rep(v), *state, zero, zero, True, None, scale, window=window)[1]
        assert not np.allclose(np.asarray(s), np.asarray(
            flash.reference_fold(q, rep(k), rep(v), *state, zero, zero, True, None, scale)[1]))


def test_a_window_of_the_whole_sequence_is_the_causal_fold_bit_for_bit():
    t, scale = 2048, 0.25
    q, k, v, state, cot = _fold_inputs(t, 2, 1, seed=3)
    zero = jnp.int32(0)
    for window in (t, 3 * t):
        a = flash._fold_pallas(q, k, v, *state, zero, zero, True, None, scale, interpret=True, window=window)
        b = flash._fold_pallas(q, k, v, *state, zero, zero, True, None, scale, interpret=True)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        a = flash._fold_bwd_pallas(q, k, v, *state, zero, zero, True, None, scale, *cot, interpret=True, window=window)
        b = flash._fold_bwd_pallas(q, k, v, *state, zero, zero, True, None, scale, *cot, interpret=True)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_fused_fold_differentiates_through_its_window():
    """``jax.grad`` through ``fused_fold`` with the static window: the custom
    VJP hands the window to both backward kernels."""
    t, window, scale = 2048, 512, 0.25
    q, k, v, state, _ = _fold_inputs(t, 2, 2, seed=5)
    zero = jnp.int32(0)

    def through(fold):
        def loss(q, k, v):
            _, l, acc = fold(q, k, v)
            return jnp.sum(jnp.sin(acc / l[..., None]))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    got = through(lambda q, k, v: flash.fused_fold(q, k, v, *state, zero, zero, True, False, zero, scale, True, window))
    want = through(lambda q, k, v: flash.reference_fold(q, k, v, *state, zero, zero, True, None, scale, window=window))
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-5)
    with pytest.raises(ValueError, match="under the causal mask"):
        flash.fused_fold(q, k, v, *state, zero, zero, False, False, zero, scale, True, window)


@pytest.mark.parametrize("t,window,q_off", [(4096, 512, 0), (2048, 512, 0), (3072, 700, 0), (2048, 512, 1024),
                                            (2048, 1536, -1024), (8192, 512, 0)])
def test_fold_chunk_counts_with_a_window_is_a_count_of_the_mask(t, window, q_off):
    """A (query tile, key chunk) pair is visited iff the causal mask under the
    window keeps an entry of it, for each of the three kernels' tiles."""
    tq_fwd, tq_dq, tq_dkv, tk_dkv, kc = flash._fold_tiles(t, t, True)
    keep = np.asarray(flash._kept(t, t, q_off, 0, True, None, window))
    visited = total = 0
    for rows, keys in ((tq_fwd, kc), (tq_dq, kc), (tq_dkv, tk_dkv)):
        tiles = keep.reshape(t // rows, rows, t // keys, keys).any(axis=(1, 3))
        visited, total = visited + int(tiles.sum()), total + tiles.size
    assert flash.fold_chunk_counts(t, t, q_off, True, window) == (visited, total)
    if (t, window, q_off) == (4096, 512, 0):  # the cell's shape: 36% of the pairs where the causal walk visits 62.5%
        assert (visited, total) == (29, 80) and flash.fold_chunk_counts(t, t, 0, True) == (50, 80)
    assert flash.fold_chunk_counts(t, t, q_off, True, 4 * t) == flash.fold_chunk_counts(t, t, q_off, True)


# -- the share and the model ------------------------------------------------------------


def test_the_eight_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """One chip of eight holds a range of a layer's experts; every chip
    computes attention and the shared expert alike. At a small size (16
    experts, 2 a share): a share's layer output is ``x' + routed_s + S`` with
    ``x'`` the stream after attention, so the eight outputs less seven times
    ``x' + S`` - the routed parts of all eight shares, the shared expert ONCE -
    are the uncut reference's layer output."""
    uncut = CFG._replace(n_layers=2, layer_heads=(4, 8), layer_windows=(0, 96), n_experts=16, experts_held=0,
                         first_held=0)
    w = _moved(uncut, seed=3)["layers"][1]
    x = 0.5 * jax.random.normal(jax.random.key(8), (BATCH, T, CFG.hidden))
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.layer(row, w, uncut, 96)[0] for row in x])
        after = jnp.stack([row + ref.attention(ref.rms_norm(row, w["attn_norm"], 1e-6), w, uncut, 96) for row in x])
        shared = ref.swiglu(ref.rms_norm(after, w["ffn_norm"], 1e-6), w["shared_gate"], w["shared_up"], w["shared_down"])
    total = 0.0
    for first in range(0, 16, 2):
        share = uncut._replace(experts_held=2, first_held=first)
        held = dict(w, **{name: w[name][first: first + 2] for name in ("w_gate", "w_up", "w_down")})
        out, _, stats = decoder_lm._layer(x, None, held, layers(share)[1], F32, True)
        assert int(stats["rows"].sum()) == BATCH * T * CFG.top_k  # routed = held + absent, whatever is held
        assert int(stats["rows"][first: first + 2].sum()) <= int(stats["carried"]) == 512  # one window of the 1,024
        total = total + out
    assert float(jnp.max(jnp.abs(want - after - shared))) > 0.01  # the routed part is not nothing
    np.testing.assert_allclose(np.asarray(total - 7 * (after + shared)), np.asarray(want), rtol=2e-4, atol=2e-5)


def _without_shared(x, w, cfg, window):
    x = x + ref.attention(ref.rms_norm(x, w["attn_norm"], cfg.norm_eps), w, cfg, window)
    u = ref.rms_norm(x, w["ffn_norm"], cfg.norm_eps)
    if "router" not in w:
        return x + ref.swiglu(u, w["w_gate"], w["w_up"], w["w_down"]), None
    y, chosen = ref.moe(u, w, cfg)
    return x + y, chosen


def _softmax_moe(u, w, cfg):
    p = jax.nn.softmax(u @ w["router"], axis=-1)
    picked, chosen = jax.lax.top_k(p, cfg.top_k)
    y = jnp.zeros_like(u)
    for j in range(cfg.held):
        w_j = jnp.sum(jnp.where(chosen == cfg.first_held + j, picked, 0.0), axis=1)
        y = y + w_j[:, None] * ref.swiglu(u, w["w_gate"][j], w["w_up"][j], w["w_down"][j])
    return y, chosen


@pytest.mark.parametrize("defect", ["no_window", "no_head_gate", "softmax_gates", "no_routed_scale",
                                    "no_shared_expert", "no_yarn", "window_rope_on_full_layers"])
def test_a_defect_is_told_apart(defect, fitted, tokens, monkeypatch):
    """The defect, planted in the reference, moves the first step's loss or a
    leaf's gradient norm past the limits the sound stage is held to (1e-5,
    1e-4): from the seed's weights attention is near uniform, and a position
    encoding shows in the gradients of ``wq`` and ``wk`` before it shows in
    the loss."""
    est, _, _ = fitted
    cfg, attention = CFG, ref.attention
    if defect == "no_window":  # every causal key, at the windowed layers' own position encoding
        monkeypatch.setattr(ref, "attention", lambda a, w, c, window: attention(a, w, c, T if window else 0))
    elif defect == "no_head_gate":  # sigmoid(0) = 1/2, doubled: every head passes whole
        monkeypatch.setattr(ref, "attention", lambda a, w, c, window: 2.0 * attention(
            a, dict(w, head_gate=jnp.zeros_like(w["head_gate"])), c, window))
    elif defect == "softmax_gates":
        monkeypatch.setattr(ref, "moe", _softmax_moe)
    elif defect == "no_routed_scale":
        cfg = CFG._replace(routed_scale=1.0)
    elif defect == "no_shared_expert":
        monkeypatch.setattr(ref, "layer", _without_shared)
    elif defect == "no_yarn":
        cfg = CFG._replace(yarn=())
    else:
        cfg = CFG._replace(rope_fraction=1.0, rope_theta=CFG.window_rope_theta, yarn=())
    loss, grads = ref.loss_and_grads(init_params(CFG, SEED), _batches(tokens)[0], cfg)
    leaves = [_rel(got, _norm(w)) for name, got, w in zip(est.param_names, est.param_grad_norm_history[0],
                                                         _ordered(grads, CFG)) if not name.endswith("router_bias")]
    assert _rel(est.loss_history[0], float(loss)) > 1e-5 or max(leaves) > 1e-4, defect


def test_bad_sizes_are_refused(df):
    with pytest.raises(ValueError, match="name each of the 4 layers"):
        _estimator().set_window_per_layer([0, 96]).fit(df)
    with pytest.raises(ValueError, match="divide evenly over numKvHeads"):
        _estimator().set_num_heads_per_layer([4, 8, 7, 4]).fit(df)
    with pytest.raises(ValueError, match="belong to blockKind 'laguna'"):
        DecoderLM().set_window_per_layer([0, 0]).set_vocab_size(512).fit(df)
    with pytest.raises(ValueError, match="sharedExpertWidth"):
        _estimator().set_shared_expert_width(0).fit(df)
    with pytest.raises(ValueError, match="five numbers or none"):
        _estimator().set_rope_yarn([4.0, 64.0]).fit(df)
