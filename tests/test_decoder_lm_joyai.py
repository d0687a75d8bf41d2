"""``DecoderLM`` with ``blockKind`` ``joyai`` (latent attention whose heads are
wider in their keys than in their values, one rotary key a token under every
head, RoPE on interleaved pairs; a leading dense layer, sigmoid-gated experts
beside a shared one; a multi-token-prediction module behind the stack) against
its plain reference (models/lm/reference_joyai.py) on seeded random weights at
toy size: 3 layers (dense, expert, expert) and the module's one, hidden 64, 4
heads of 16 + 8 query and key channels and 12 value channels through latents
of 48 (queries) and 32 (keys and values), a dense SwiGLU of width 96, 16
experts of width 32 (top-2; experts 2..3 held, an eighth: the expert layers
take their 1,024 routed rows through the experts in windows of 512,
``parallel/moe.py``) beside a shared one of width 32, an untied vocabulary of
512, T 256, batch 2, 2 steps. The same fit loop, head, loss chunking, clip and
AdamW program as the other kinds, chosen by a stage parameter.

Tolerances. float32: stage and reference compute the same mathematics in
different orders (the stage turns the pairs with two lane rolls, runs the
module's layer over all T positions with a filler at the last, sums the
module's loss under a mask), so they differ by float32 rounding; read here the
loss by 3e-7 relative, the module's own loss by 3e-7, the gradient norm by
2e-6, a leaf's gradient by 4e-5 of its largest entry (the limits: 1e-5 on the
losses, 1e-4 on the norms and leaves). bfloat16 matmul inputs: the loss by
3e-4, the gradient norm by 6e-3; the bands are 2e-3 and 3e-2.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu import trace
from flink_ml_tpu.api.dataframe import DataFrame
from flink_ml_tpu.metrics import MLMetrics, metrics
from flink_ml_tpu.models.lm import DecoderLM, DecoderLMModel, decoder_lm
from flink_ml_tpu.models.lm import reference_joyai as ref
from flink_ml_tpu.models.lm.config import LatentAttention, LMConfig, layers, mtp_layer, num_params, param_shapes
from flink_ml_tpu.models.lm.decoder_lm import _flat_names, _ordered, init_params
from flink_ml_tpu.parallel import flash
from flink_ml_tpu.utils.read_write import load_stage

CFG = LMConfig(n_layers=3, hidden=64, n_heads=4, n_experts=16, top_k=2, expert_width=32, vocab=512,
               rope_theta=3.2e7, norm_eps=1e-6, aux_coef=0.0, block="joyai", experts_held=2, first_held=2,
               n_dense=1, dense_width=96, shared_width=32, routed_scale=2.5, q_rank=48, kv_rank=32, nope_dim=16,
               rope_dim=8, v_dim=12, mtp_depth=1, mtp_coef=0.3)
N, T, BATCH, STEPS, LR, SEED = 4, 256, 2, 2, 1e-3, 7
F32 = jnp.dtype("float32")
SPARSE = CFG.n_layers - CFG.n_dense + CFG.mtp_depth  # layers with experts, the module's among them


def _estimator(compute_type="float32", cfg=CFG):
    return (
        DecoderLM().set_block_kind("joyai")
        .set_num_layers(cfg.n_layers).set_hidden_size(cfg.hidden).set_num_heads(cfg.n_heads)
        .set_q_lora_rank(cfg.q_rank).set_kv_lora_rank(cfg.kv_rank).set_qk_nope_head_size(cfg.nope_dim)
        .set_qk_rope_head_size(cfg.rope_dim).set_v_head_size(cfg.v_dim).set_rope_theta(cfg.rope_theta)
        .set_dense_layers(cfg.n_dense).set_dense_width(cfg.dense_width)
        .set_num_experts(cfg.n_experts).set_experts_per_token(cfg.top_k).set_expert_width(cfg.expert_width)
        .set_experts_held(cfg.experts_held).set_first_expert_held(cfg.first_held)
        .set_shared_expert_width(cfg.shared_width).set_routed_scale(cfg.routed_scale)
        .set_mtp_depth(cfg.mtp_depth).set_mtp_loss_coef(cfg.mtp_coef)
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


def test_the_tree_holds_the_module_after_the_head():
    names = _flat_names(CFG)
    at = names.index("lm_head")
    assert names[at + 1: at + 4] == ["mtp.enorm", "mtp.hnorm", "mtp.eh_proj"] and names[-1] == "mtp.norm"
    own = [n.split(".", 2)[2] for n in names if n.startswith("mtp.layer.")]
    assert own == [n.split(".", 2)[2] for n in names if n.startswith("layers.2.")]  # one more expert layer
    assert own[:8] == ["attn_norm", "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wkv_b", "wo"]
    shapes = {".".join(map(str, path)): shape for path, shape, _ in param_shapes(CFG)}
    assert shapes["layers.0.wq_b"] == (48, 4 * 24) and shapes["layers.0.wkv_a"] == (64, 32 + 8)
    assert shapes["layers.0.wkv_b"] == (32, 4 * 28) and shapes["layers.0.wo"] == (4 * 12, 64)
    assert shapes["mtp.eh_proj"] == (128, 64)
    assert mtp_layer(CFG) == layers(CFG)[-1] and mtp_layer(CFG._replace(mtp_depth=0)) is None
    assert "mtp.norm" not in _flat_names(CFG._replace(mtp_depth=0))


def _cell_config():
    """The ``joyai_llm_flash`` configuration's ``LMConfig`` as the benchmark's system builds it."""
    from perfbench.manifest import Manifest
    from perfbench.systems import joyai_lm_fit

    return joyai_lm_fit.lm_config(Manifest().config("joyai_llm_flash"))


def test_parameter_count_at_the_cells_sizes():
    """The issue's table from the program's own ``param_shapes``, at 16 bytes
    a parameter: latent attention with its norm, layer 0 with the dense SwiGLU,
    an expert layer with 16 of 256 held, the module, the 16,160-row slice of
    the untied embedding and head; and the published 48 B over all 40 layers,
    256 experts and the whole vocabulary (48.95 B; 50.19 B with the module's 256 experts)."""
    cfg = _cell_config()
    parts = {}
    for path, shape, _ in param_shapes(cfg):
        key = path[1] if path[0] == "layers" else path[0]
        parts[key] = parts.get(key, 0) + int(np.prod(shape))
    assert parts == {"embed": 16_160 * 2048, 0: 70_391_808, 1: 107_092_224, 2: 107_092_224, 3: 107_092_224,
                     4: 107_092_224, "final_norm": 2048, "lm_head": 16_160 * 2048, "mtp": 115_486_976}
    mla = sum(int(np.prod(shape)) for path, shape, _ in param_shapes(cfg)
              if path[:2] == ("layers", 0) and path[2] not in ("ffn_norm", "w_gate", "w_up", "w_down"))
    assert mla == 26_349_568 and 2 * 16_160 * 2048 + 2048 == 66_193_408
    assert num_params(cfg) == 680_441_088 and 10.88e9 < 16 * num_params(cfg) < 10.89e9
    whole = cfg._replace(n_layers=40, experts_held=0, vocab=129_280)
    assert 48.9e9 < num_params(whole._replace(mtp_depth=0)) < 49.0e9 and 50.1e9 < num_params(whole) < 50.2e9
    assert isinstance(layers(cfg)[0].mixer, LatentAttention) and len(set(layers(cfg))) == 2


def test_loss_and_gradient_norm_of_every_step(fitted, reference_run, tokens):
    """The loss (both terms) and the global gradient norm of both steps: the
    second step's loss is the loss after one clipped AdamW update; and the
    module's own mean loss at step 1."""
    est, _, _ = fitted
    _, losses, norms = reference_run
    assert len(est.loss_history) == STEPS == len(est.grad_norm_history) == len(est.mtp_loss_history)
    assert _rel(est.loss_history, losses) < 1e-5
    assert _rel(est.grad_norm_history, norms) < 1e-4
    main, ahead = ref.losses(init_params(CFG, SEED), _batches(tokens)[0], CFG)
    assert _rel(est.mtp_loss_history[0], ahead) < 1e-5
    assert _rel(est.loss_history[0], main + CFG.mtp_coef * ahead) < 1e-5 and float(ahead) > 1.0
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
    """Forward, loss and the gradient of every leaf - the low-rank leaves and
    both latent norms, the module's three of its own, its layer's, and the
    embedding and head, whose gradients are the sum of both uses - against
    ``jax.grad`` of the plain reference, from weights with nothing at a
    constant. The selection bias has no gradient on either side."""
    tok = _batches(tokens)[0]
    want_loss, want = ref.loss_and_grads(params, tok, CFG)
    (loss, stats), got = jax.value_and_grad(decoder_lm._loss, has_aux=True)(
        params, tok, CFG, jnp.dtype(compute_type), True)
    assert _rel(loss, want_loss) < (1e-5 if leaf_tol else 2e-3)
    assert stats["rows"].shape == (SPARSE, CFG.n_experts)
    assert stats["carried"].tolist() == [512] * SPARSE  # one window of the 1,024 sorted rows a layer
    assert int(stats["mtp_targets"]) == BATCH * (T - 2)
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


def test_the_module_scores_token_i_plus_two_and_the_last_two_positions_nothing(params, tokens):
    """Position ``i`` of the module is scored on token ``i + 2``: the summed
    loss is the reference's mean times its ``T - 2`` targets a sequence; and no
    token after a sequence's first enters through the filler, nor does the
    last position's own target exist: the last token changed, only the one
    target that names it moves; the module's output at the last two positions
    has no gradient."""
    tok = _batches(tokens)[0]
    _, stats = decoder_lm._loss(params, tok, CFG, F32, True)
    _, want = ref.losses(params, tok, CFG)
    assert int(stats["mtp_targets"]) == BATCH * (T - 2)
    assert _rel(stats["mtp_nll_sum"] / stats["mtp_targets"], want) < 1e-5

    def module_sum(ahead, head=params["lm_head"]):  # the module's term as ``_loss`` writes it
        scored = jnp.broadcast_to((jnp.arange(T) < T - 2).astype(jnp.float32), tok.shape)
        return decoder_lm._next_token_nll(ahead, head, jnp.roll(tok, -1, axis=1), F32)(scored)[0]

    _, _, _, ahead = decoder_lm._hidden(params, tok, CFG, F32, True, mtp=True)
    np.testing.assert_allclose(float(module_sum(ahead)), float(stats["mtp_nll_sum"]), rtol=1e-6)
    d_ahead = np.asarray(jax.grad(module_sum)(ahead))
    assert not d_ahead[:, -2:].any() and np.abs(d_ahead[:, :-2]).sum(axis=-1).min() > 0
    # position i's target is token i + 2: with logits that put all their mass on token i + 2 the loss is nothing
    onehot = 50.0 * jax.nn.one_hot(jnp.roll(tok, -2, axis=1), CFG.vocab)
    assert float(module_sum(onehot, jnp.eye(CFG.vocab))) < 1e-3
    assert float(module_sum(50.0 * jax.nn.one_hot(jnp.roll(tok, -1, axis=1), CFG.vocab), jnp.eye(CFG.vocab))) > 1e3


def test_fits_scores_saves_and_loads_and_transform_ignores_the_module(fitted, df, tokens, tmp_path):
    """The same entry points as the other kinds: ``fit``'s histories,
    ``transform`` (the main head alone: a model whose module was overwritten
    scores the same), ``save``/``load`` and the model-data round trip."""
    est, model, _ = fitted
    assert est.expert_rows_history.shape == (STEPS, SPARSE, CFG.n_experts)
    assert (est.expert_rows_history.sum(axis=2) == BATCH * T * CFG.top_k).all()  # the module's with its filler's
    got = np.asarray(model.transform(df).scalars("prediction"))
    want = np.asarray(ref.log_likelihood(model.params, jnp.asarray(tokens), CFG))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    other = DecoderLMModel()
    other.load_param_map_from_json(model.param_map_to_json())
    other.params = dict(model.params, mtp=jax.tree_util.tree_map(lambda a: a * 0.0 + 3.0, model.params["mtp"]))
    np.testing.assert_array_equal(np.asarray(other.transform(df).scalars("prediction")), got)
    path = str(tmp_path / "lm")
    model.save(path)
    loaded = load_stage(path)
    assert isinstance(loaded, DecoderLMModel) and loaded.get_mtp_depth() == 1 and loaded.lm_config() == CFG
    np.testing.assert_array_equal(np.asarray(loaded.transform(df).scalars("prediction")), got)
    for a, b in zip(_ordered(loaded.params, CFG), _ordered(model.params, CFG)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    other = DecoderLMModel()
    other.load_param_map_from_json(model.param_map_to_json())
    other.set_model_data(*model.get_model_data())
    np.testing.assert_array_equal(np.asarray(other.transform(df).scalars("prediction")), got)


def test_the_fit_counts_its_latent_layers_and_the_modules_targets(fitted, df):
    """``train.program``'s counts beside the fold's chunks (the module's layer
    folds too), ``train.drain``'s module counts and held rows, the counters."""
    est, _, spans = fitted
    program, drain = spans["train.program"], spans["train.drain"]
    full = np.asarray(flash.fold_chunk_counts(T, T, 0, True, one_block=True))
    assert (program["layers_latent"], program["mtp_depth"]) == (CFG.n_layers + 1, 1)
    assert (program["fold_chunks_visited"], program["fold_chunks"]) == tuple((CFG.n_layers + 1) * 4 * BATCH * full)
    assert program["latent_bytes"] == (CFG.n_layers + 1) * BATCH * T * (32 + 8) * 4
    assert "layers_windowed" not in program and "layers_scan" not in program
    assert drain["mtp_targets"] == STEPS * BATCH * (T - 2)
    assert drain["mtp_nll_sum"] == pytest.approx(sum(est.mtp_loss_history) * BATCH * (T - 2), rel=1e-6)
    held = est.expert_rows_history[:, :, CFG.first_held: CFG.first_held + CFG.held]
    assert drain["dropped"] == 0 and drain["rows_held"] == int(held.sum())
    assert drain["rows_held"] + drain["rows_absent"] == STEPS * BATCH * T * CFG.top_k * SPARSE
    assert drain["moe_layer_steps"] == STEPS * SPARSE and drain["moe_rows_carried"] == 512 * STEPS * SPARSE
    counters = (MLMetrics.TRAIN_LM_MLA_LAYERS, MLMetrics.TRAIN_LM_MTP_TARGETS, MLMetrics.TRAIN_LM_FOLD_CHUNKS)
    before = [metrics.get(MLMetrics.TRAIN_GROUP, name) for name in counters]
    _estimator().set_max_iter(1).fit(df)
    assert [metrics.get(MLMetrics.TRAIN_GROUP, name) - was for name, was in zip(counters, before)] == \
        [CFG.n_layers + 1, BATCH * (T - 2), program["fold_chunks"]]
    # a stack without the module writes none of its counts
    with trace.capture() as recorder:
        plain = _estimator().set_mtp_depth(0).set_max_iter(1)
        plain.fit(df)
    spans = {s.name: s.attrs for s in recorder.snapshot()}
    assert "mtp_targets" not in spans["train.drain"] and spans["train.program"]["mtp_depth"] == 0
    assert plain.mtp_loss_history == [] and plain.expert_rows_history.shape[1] == SPARSE - 1


def test_the_pair_tables_against_the_formula_in_numpy():
    """Interleaved pairs at the published settings (64 channels behind 128
    unturned ones, theta 3.2e7) against RoPE written out in float64; the two
    lane rolls are the pairs' swap; the reference's ``turn_pairs`` agrees."""
    t, lead, rot, theta = 512, 128, 64, 3.2e7
    cos, sin = decoder_lm._pair_tables(t, rot, theta, lead=lead)
    assert cos.shape == sin.shape == (t, lead + rot)
    np.testing.assert_array_equal(np.asarray(cos[:, :lead]), 1.0)
    np.testing.assert_array_equal(np.asarray(sin[:, :lead]), 0.0)
    angle = np.arange(t)[:, None] * theta ** (-2.0 * np.arange(rot // 2) / rot)[None, :]
    x = np.random.default_rng(3).normal(size=(2, 3, t, lead + rot))
    want = x.copy()
    want[..., lead::2] = x[..., lead::2] * np.cos(angle) - x[..., lead + 1::2] * np.sin(angle)
    want[..., lead + 1::2] = x[..., lead + 1::2] * np.cos(angle) + x[..., lead::2] * np.sin(angle)
    got = decoder_lm._rope_pairs(jnp.asarray(x, jnp.float32), cos, sin)
    np.testing.assert_allclose(np.asarray(got), want, atol=3e-4)  # float32 angles up to 511 radians
    by_ref = ref.turn_pairs(jnp.asarray(np.moveaxis(x[0, :, :, lead:], 1, 0), jnp.float32), theta)  # [T, H, D]
    np.testing.assert_allclose(np.asarray(jnp.moveaxis(by_ref, 0, 1)), want[0, :, :, lead:], atol=3e-4)
    # rotate-half is another convention: the same angles on other pairs
    half = decoder_lm._rope(jnp.asarray(x[..., lead:], jnp.float32), *decoder_lm._rope_tables(t, rot, theta))
    assert float(jnp.max(jnp.abs(half - got[..., lead:]))) > 0.1


# -- the share and the model ------------------------------------------------------------


def test_the_sixteen_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """One chip of sixteen holds a range of a layer's experts; every chip
    computes latent attention and the shared expert alike. At a small size (16
    experts, 1 a share): a share's layer output is ``x' + routed_s + S`` with
    ``x'`` the stream after attention, so the sixteen outputs less fifteen
    times ``x' + S`` - the routed parts of all sixteen shares, the shared
    expert ONCE - are the uncut reference's layer output."""
    uncut = CFG._replace(experts_held=0, first_held=0)
    w = _moved(uncut, seed=3)["layers"][1]
    x = 0.5 * jax.random.normal(jax.random.key(8), (BATCH, T, CFG.hidden))
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.layer(row, w, uncut)[0] for row in x])
        after = jnp.stack([row + ref.attention(ref.rms_norm(row, w["attn_norm"], 1e-6), w, uncut) for row in x])
        shared = ref.laguna.swiglu(ref.rms_norm(after, w["ffn_norm"], 1e-6), w["shared_gate"], w["shared_up"],
                                   w["shared_down"])
    total = 0.0
    for first in range(16):
        share = uncut._replace(experts_held=1, first_held=first)
        held = dict(w, **{name: w[name][first: first + 1] for name in ("w_gate", "w_up", "w_down")})
        out, _, stats = decoder_lm._layer(x, None, held, layers(share)[1], F32, True)
        assert int(stats["rows"].sum()) == BATCH * T * CFG.top_k  # routed = held + absent, whatever is held
        assert int(stats["rows"][first]) <= int(stats["carried"])
        total = total + out
    assert float(jnp.max(jnp.abs(want - after - shared))) > 0.01  # the routed part is not nothing
    np.testing.assert_allclose(np.asarray(total - 15 * (after + shared)), np.asarray(want), rtol=2e-4, atol=4e-5)


def _half_turned(x, theta):
    d = x.shape[-1]
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * (theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = jnp.concatenate([angle, angle], axis=-1).reshape(x.shape[0], *(1,) * (x.ndim - 2), d)
    return x * jnp.cos(angle) + jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1) * jnp.sin(angle)


@pytest.mark.parametrize("defect", ["no_module", "module_scores_the_next_token", "embedding_first", "after_final_norm",
                                    "rotate_half", "no_rotation", "no_latent_norms", "scale_by_the_value_head",
                                    "no_routed_scale", "no_shared_expert"])
def test_a_defect_is_told_apart(defect, fitted, tokens, monkeypatch):
    """The defect, planted in the reference, moves the first step's loss, the
    module's own loss or a leaf's gradient norm past the limits the sound stage
    is held to (1e-5, 1e-4): from the seed's weights attention is near
    uniform, and a position encoding shows in the gradients of the rotary
    leaves before it shows in the loss."""
    est, _, _ = fitted
    cfg, forward, attention, layer = CFG, ref.forward, ref.attention, ref.layer
    if defect == "no_module":
        cfg = CFG._replace(mtp_coef=0.0)
    elif defect == "module_scores_the_next_token":  # position i on token i + 1, what the main head scores
        def losses(p, tok, c):
            logits, ahead, _, _ = forward(p, tok, c)
            return (-jnp.mean(ref.olmoe.token_log_probs(logits, tok)),
                    -jnp.mean(ref.olmoe.token_log_probs(ahead, tok[:, :-1])))
        monkeypatch.setattr(ref, "losses", losses)
    elif defect == "embedding_first":
        monkeypatch.setattr(ref, "forward", lambda p, tok, c: forward(
            dict(p, mtp=dict(p["mtp"], eh_proj=jnp.roll(p["mtp"]["eh_proj"], c.hidden, axis=0),
                             hnorm=p["mtp"]["enorm"], enorm=p["mtp"]["hnorm"])), tok, c))
    elif defect == "after_final_norm":
        monkeypatch.setattr(ref, "forward", lambda p, tok, c: forward(
            dict(p, mtp=dict(p["mtp"], hnorm=p["mtp"]["hnorm"] * 1.5)), tok, c))
    elif defect == "rotate_half":
        monkeypatch.setattr(ref, "turn_pairs", _half_turned)
    elif defect == "no_rotation":
        monkeypatch.setattr(ref, "turn_pairs", lambda x, theta: x)
    elif defect == "no_latent_norms":
        monkeypatch.setattr(ref, "attention", lambda a, w, c: attention(
            a, dict(w, q_a_norm=2.0 * w["q_a_norm"], kv_a_norm=2.0 * w["kv_a_norm"]), c))
    elif defect == "scale_by_the_value_head":  # 12^-1/2 for 24^-1/2: the scores times sqrt(2)
        monkeypatch.setattr(ref, "attention", lambda a, w, c: attention(
            a, dict(w, wq_b=w["wq_b"] * (c.nope_dim + c.rope_dim) ** 0.5 / c.v_dim ** 0.5), c))
    elif defect == "no_routed_scale":
        cfg = CFG._replace(routed_scale=1.0)
    else:
        def without_shared(x, w, c):
            zero = {k: jnp.zeros_like(v) for k, v in w.items() if k.startswith("shared_")}
            return layer(x, dict(w, **zero), c)
        monkeypatch.setattr(ref, "layer", without_shared)
    p0, tok = init_params(CFG, SEED), _batches(tokens)[0]
    loss, grads = ref.loss_and_grads(p0, tok, cfg)
    _, ahead = ref.losses(p0, tok, cfg)
    leaves = [_rel(got, _norm(w)) for name, got, w in zip(est.param_names, est.param_grad_norm_history[0],
                                                         _ordered(grads, CFG))
              if not name.endswith("router_bias") and float(_norm(w)) > 0]
    missing = any(float(_norm(w)) == 0 and not name.endswith("router_bias")
                  for name, w in zip(est.param_names, _ordered(grads, CFG)))
    assert (_rel(est.loss_history[0], float(loss)) > 1e-5 or _rel(est.mtp_loss_history[0], float(ahead)) > 1e-5
            or max(leaves) > 1e-4 or missing), defect


def test_bad_sizes_are_refused(df):
    with pytest.raises(ValueError, match="kvLoraRank"):
        _estimator().set_kv_lora_rank(0).fit(df)
    with pytest.raises(ValueError, match="even number of channels"):
        _estimator().set_qk_rope_head_size(7).fit(df)
    with pytest.raises(ValueError, match="belong to blockKind 'joyai'"):
        DecoderLM().set_mtp_depth(1).set_vocab_size(512).fit(df)
    with pytest.raises(ValueError, match="belong to blockKind 'joyai'"):
        DecoderLM().set_block_kind("laguna").set_v_head_size(128).set_vocab_size(512).fit(df)
    with pytest.raises(ValueError, match="sharedExpertWidth"):
        _estimator().set_shared_expert_width(0).fit(df)
    with pytest.raises(ValueError, match="denseWidth"):
        _estimator().set_dense_width(0).fit(df)
    with pytest.raises(ValueError):
        _estimator().set_mtp_depth(2)
