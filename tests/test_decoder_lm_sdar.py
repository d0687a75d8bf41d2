"""``DecoderLM`` with ``blockKind`` ``sdar`` (the Qwen3-MoE layer: grouped
queries under a QK-norm over each head's channels, softmax gates renormalised
over the chosen experts; trained by BLOCK DIFFUSION over the doubled sequence
``[x ; x~]``) against its plain reference (models/lm/reference_sdar.py) on
seeded random weights at toy size: 2 layers, hidden 64, 4 query heads on 2
key/value heads of 16, 16 experts of width 32 (top-2; experts 2..3 held, an
eighth: the 1,536 x 2 routed rows pass the experts in windows of 1,024), an
untied vocabulary of 512 whose last id is the mask, T 384 in blocks of 4 (768
positions through the stack: three query tiles of 256, the middle one half
clean and half noised), batch 2, 2 steps. The same fit loop, head, loss
chunking, clip and AdamW program as the other kinds, chosen by a stage
parameter.

Tolerances. float32: stage and reference compute the same mathematics in
different orders, so they differ by float32 rounding; read here the loss by
2e-7 relative, the gradient norm by 1e-6, a leaf's gradient by 3e-5 of its
largest entry (the limits: 1e-5 on the losses, 1e-4 on the norms and leaves).
bfloat16 matmul inputs: the loss by 4e-4, the gradient norm by 5e-3; the
bands are 2e-3 and 3e-2.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu import trace
from flink_ml_tpu.api.dataframe import DataFrame
from flink_ml_tpu.metrics import MLMetrics, metrics
from flink_ml_tpu.models.lm import DecoderLM, DecoderLMModel, decoder_lm
from flink_ml_tpu.models.lm import reference_sdar as ref
from flink_ml_tpu.models.lm.config import Attention, Experts, LMConfig, layers, num_params, param_shapes
from flink_ml_tpu.models.lm.decoder_lm import _flat_names, _ordered, init_params
from flink_ml_tpu.parallel import flash
from flink_ml_tpu.utils.read_write import load_stage
from tests.test_fused_attention import _bd_rules

CFG = LMConfig(n_layers=2, hidden=64, n_heads=4, n_experts=16, top_k=2, expert_width=32, vocab=512, rope_theta=1e6,
               norm_eps=1e-6, aux_coef=0.0, block="sdar", experts_held=2, first_held=2, n_kv_heads=2, head_size=16,
               block_length=4, mask_id=511)
N, T, BATCH, STEPS, LR, SEED = 4, 384, 2, 2, 1e-3, 7
F32 = jnp.dtype("float32")


def _estimator(compute_type="float32", cfg=CFG):
    return (
        DecoderLM().set_block_kind("sdar")
        .set_num_layers(cfg.n_layers).set_hidden_size(cfg.hidden).set_num_heads(cfg.n_heads)
        .set_num_kv_heads(cfg.n_kv_heads).set_head_size(cfg.head_size).set_rope_theta(cfg.rope_theta)
        .set_num_experts(cfg.n_experts).set_experts_per_token(cfg.top_k).set_expert_width(cfg.expert_width)
        .set_experts_held(cfg.experts_held).set_first_expert_held(cfg.first_held)
        .set_block_length(cfg.block_length)
        .set_vocab_size(cfg.vocab).set_norm_eps(cfg.norm_eps).set_compute_type(compute_type)
        .set_max_iter(STEPS).set_global_batch_size(BATCH).set_learning_rate(LR).set_seed(SEED)
    )


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, CFG.vocab - 1, (N, T))  # documents draw from the ids that are not the mask


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
    """The seed's weights with every leaf that starts at a constant moved off it."""
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


def _noise(step=0, seed=SEED):
    return decoder_lm._noise_key(seed), jnp.int32(step)


@pytest.fixture(scope="module")
def reference_run(tokens):
    return ref.train_steps(init_params(CFG, SEED), _batches(tokens), SEED, CFG, LR)


def _rel(a, b):
    return float(np.max(np.abs(np.asarray(a) - np.asarray(b)) / np.abs(np.asarray(b))))


def _norm(g):
    return jnp.sqrt(jnp.sum(g * g))


def test_the_stage_config_is_the_tests(fitted):
    est, _, _ = fitted
    assert est.lm_config(CFG.vocab) == CFG  # maskTokenId -1: the vocabulary's last id
    assert est.set_mask_token_id(17).lm_config(CFG.vocab).mask_id == 17
    est.set_mask_token_id(-1)


def test_the_layer_record_and_its_leaves():
    (spec,) = set(layers(CFG))
    assert spec.mixer == Attention(4, 2, 16, spec.mixer.rotation, qk_norm="head", diffusion_block=4)
    assert spec.mixer.rotation.channels == 16 and spec.mixer.rotation.theta == 1e6
    assert isinstance(spec.ffn, Experts) and spec.ffn.renormalise and not spec.ffn.routed_scale
    assert spec.ffn.shared_width is None
    shapes = {".".join(map(str, path)): shape for path, shape, _ in param_shapes(CFG)}
    assert shapes["layers.0.q_norm"] == shapes["layers.0.k_norm"] == (16,)  # a head's channels, not the projection's
    assert shapes["layers.0.wq"] == (64, 64) and shapes["layers.0.wk"] == shapes["layers.0.wv"] == (64, 32)
    assert shapes["layers.0.w_gate"] == (2, 64, 32) and shapes["layers.0.router"] == (64, 16)
    assert [n.split(".", 2)[2] for n in _flat_names(CFG) if n.startswith("layers.0.")] == [
        "attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "ffn_norm", "router", "w_gate", "w_up", "w_down"]
    assert _flat_names(CFG)[-2:] == ["final_norm", "lm_head"]


def _cell_config():
    """The ``sdar_30b_a3b`` configuration's ``LMConfig`` as the benchmark's system builds it."""
    from perfbench.manifest import Manifest
    from perfbench.systems import sdar_lm_fit

    return sdar_lm_fit.lm_config(Manifest().config("sdar_30b_a3b"))


def test_parameter_count_at_the_cells_sizes():
    """The issue's table from the program's own ``param_shapes``, at 16 bytes a
    parameter: a layer with 16 of 128 experts held, the 18,992-row slice of the
    untied embedding and head; and the published 30.5 B over all 48 layers, 128
    experts and the whole vocabulary."""
    cfg = _cell_config()
    parts = {}
    for path, shape, _ in param_shapes(cfg):
        key = path[1] if path[0] == "layers" else path[0]
        parts[key] = parts.get(key, 0) + int(np.prod(shape))
    layer = 18_874_368 + 256 + 4_096 + 262_144 + 75_497_472
    assert layer == 94_638_336
    assert parts == {"embed": 18_992 * 2048, **{i: layer for i in range(cfg.n_layers)}, "final_norm": 2048,
                     "lm_head": 18_992 * 2048}
    six = cfg._replace(n_layers=6)
    assert num_params(six) == 645_623_296 and 10.32e9 < 16 * num_params(six) < 10.34e9
    assert num_params(cfg) == 645_623_296 - (6 - cfg.n_layers) * layer
    whole = cfg._replace(n_layers=48, experts_held=0, vocab=151_936)
    assert 30.5e9 < num_params(whole) < 30.6e9
    assert cfg.mask_id == 18_991 and cfg.block_length == 4 and (cfg.held, cfg.first_held) == (16, 0)


def test_the_references_masks_equal_the_programs_bit_for_bit(tokens):
    """One seed, one step index: the corruption the step draws on the device
    and the one the reference draws by the stated recipe (the key, the order
    and shapes of the draws) are the same tokens, mask and probabilities; the
    doubled input is ``[x ; x~]``; another step draws another mask."""
    tok = _batches(tokens)[0]
    for step in (0, 1, 5):
        both, masked, p = decoder_lm._corrupt(tok, _noise(step), CFG)
        noised, m, p_ref = ref.corrupt(tok, SEED, step, CFG)
        np.testing.assert_array_equal(np.asarray(both[:, :T]), np.asarray(tok))
        np.testing.assert_array_equal(np.asarray(both[:, T:]), np.asarray(noised))
        np.testing.assert_array_equal(np.asarray(masked), np.asarray(m))
        np.testing.assert_array_equal(np.asarray(p), np.asarray(p_ref))
        assert ((np.asarray(noised) == CFG.mask_id) == np.asarray(m)).all()  # no document token is the mask's id
        assert (np.asarray(p) >= ref.NOISE_EPS).all() and (np.asarray(p) < 1.0).all()
    again = decoder_lm._corrupt(tok, _noise(0), CFG)[1]
    assert not np.array_equal(np.asarray(again), np.asarray(masked)) and 0 < int(again.sum()) < tok.size
    assert not np.array_equal(np.asarray(decoder_lm._corrupt(tok, _noise(0, SEED + 1), CFG)[1]), np.asarray(again))


def test_loss_and_gradient_norm_of_every_step(fitted, reference_run):
    """The loss and the global gradient norm of both steps: the second step's
    loss is the loss after one clipped AdamW update, under the second step's
    own draw."""
    est, _, _ = fitted
    _, losses, norms = reference_run
    assert len(est.loss_history) == STEPS == len(est.grad_norm_history) == len(est.targets_masked_history)
    assert _rel(est.loss_history, losses) < 1e-5
    assert _rel(est.grad_norm_history, norms) < 1e-4
    assert est.param_names == _flat_names(CFG)
    assert est.param_grad_norm_history.shape == (STEPS, len(param_shapes(CFG)))


def test_every_leafs_gradient_norm_in_the_fit(fitted, tokens):
    est, _, _ = fitted
    _, want, (scored, _) = ref.loss_and_grads(init_params(CFG, SEED), _batches(tokens)[0], SEED, 0, CFG)
    assert est.targets_masked_history[0] == int(scored)
    for name, got, w in zip(est.param_names, est.param_grad_norm_history[0], _ordered(want, CFG)):
        assert _rel(got, _norm(w)) < 1e-4, name


def test_every_parameter_after_two_steps(fitted, reference_run):
    _, model, _ = fitted
    want = reference_run[0]
    for name, a, b in zip(_flat_names(CFG), _ordered(model.params, CFG), _ordered(want, CFG)):
        assert float(jnp.max(jnp.abs(a - b))) < 0.05 * STEPS * LR, name


@pytest.mark.parametrize("compute_type,leaf_tol,norm_tol", [("float32", 1e-4, 1e-4), ("bfloat16", None, 6e-2)])
def test_every_parameters_gradient(params, tokens, compute_type, leaf_tol, norm_tol):
    """Forward, loss and the gradient of every leaf - both QK-norms' 16
    weights, the router through the renormalised gates' numerator and
    denominator, the embedding (both halves' lookups) and the head (the noised
    half's rows alone) - against ``jax.grad`` of the plain reference, from
    weights with nothing at a constant."""
    tok = _batches(tokens)[0]
    want_loss, want, (scored, rows) = ref.loss_and_grads(params, tok, SEED, 3, CFG)
    (loss, stats), got = jax.value_and_grad(decoder_lm._loss, has_aux=True)(
        params, tok, CFG, jnp.dtype(compute_type), True, _noise(3))
    assert _rel(loss, want_loss) < (1e-5 if leaf_tol else 2e-3)
    assert int(stats["targets_masked"]) == int(scored)
    assert stats["rows"].shape == (CFG.n_layers, CFG.n_experts)
    assert int(stats["rows"].sum()) == CFG.n_layers * BATCH * 2 * T * CFG.top_k  # both halves' positions are routed
    if leaf_tol:
        np.testing.assert_array_equal(np.asarray(stats["rows"]), np.asarray(rows))
    assert stats["carried"].tolist() == [1024] * CFG.n_layers  # one window of the 3,072 sorted rows a layer
    for name, g, w in zip(_flat_names(CFG), _ordered(got, CFG), _ordered(want, CFG)):
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


def test_nothing_is_read_from_the_clean_halfs_head_rows_or_from_unmasked_positions(params, tokens):
    """The head takes the noised half's ``T`` rows (the clean half's are not
    even final-normed): the gradient of the loss by those states is zero on
    every unmasked position, and not zero on a masked one; the head's
    matmul is ``[B T, d] @ [d, V]``, not the doubled rows'."""
    tok = _batches(tokens)[0]
    both, masked, p = decoder_lm._corrupt(tok, _noise(), CFG)
    h, _, _, _ = decoder_lm._hidden(params, both, CFG, F32, True, tail=T)
    assert h.shape == (BATCH, T, CFG.hidden)  # the noised half's states alone are final-normed
    whole, _, _, _ = decoder_lm._hidden(params, both, CFG, F32, True)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(whole[:, T:]))

    def through_head(h):
        weight = masked.astype(jnp.float32) / (p[:, None] * tok.size)
        return decoder_lm._target_nll(h, params["lm_head"], tok, F32)(weight)[0]

    d_h = np.abs(np.asarray(jax.grad(through_head)(h))).sum(axis=-1)
    assert not d_h[~np.asarray(masked)].any() and d_h[np.asarray(masked)].min() > 0
    jaxpr = jax.make_jaxpr(lambda p_: decoder_lm._loss(p_, tok, CFG, F32, True, _noise())[0])(params)
    assert decoder_lm._head_logit_matmuls(jaxpr.jaxpr, CFG.vocab) == 1
    text = str(jaxpr)
    assert f"f32[{T},{CFG.vocab}]" in text and f"f32[{2 * T},{CFG.vocab}]" not in text  # a chunk is one sequence's T rows


def test_fits_scores_saves_and_loads_and_transform_reports_the_bound(fitted, df, tokens, tmp_path):
    """The same entry points as the other kinds: ``fit``'s histories,
    ``transform`` (a one-draw estimate of the bound a row, batch ``i`` of
    ``globalBatchSize`` rows corrupted as step ``i`` would be), ``save`` /
    ``load`` and the model-data round trip."""
    est, model, _ = fitted
    assert est.expert_rows_history.shape == (STEPS, CFG.n_layers, CFG.n_experts)
    assert (est.expert_rows_history.sum(axis=2) == BATCH * 2 * T * CFG.top_k).all()
    got = np.asarray(model.transform(df).scalars("prediction"))
    want = np.concatenate([np.asarray(ref.bound_estimate(model.params, jnp.asarray(tokens[lo: lo + BATCH]), SEED, i, CFG))
                           for i, lo in enumerate(range(0, N, BATCH))])
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert (got < 0).all()
    path = str(tmp_path / "lm")
    model.save(path)
    loaded = load_stage(path)
    assert isinstance(loaded, DecoderLMModel) and loaded.get_block_length() == 4 and loaded.lm_config() == CFG
    np.testing.assert_array_equal(np.asarray(loaded.transform(df).scalars("prediction")), got)
    for a, b in zip(_ordered(loaded.params, CFG), _ordered(model.params, CFG)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    other = DecoderLMModel()
    other.load_param_map_from_json(model.param_map_to_json())
    other.set_model_data(*model.get_model_data())
    np.testing.assert_array_equal(np.asarray(other.transform(df).scalars("prediction")), got)


def test_the_fit_counts_its_doubled_folds_and_the_positions_it_scored(fitted, df):
    """``train.program``'s counts of the fold under the block-diffusion mask
    (from ``fold_chunk_counts``), ``train.drain``'s scored positions and noise
    levels, the held rows of BOTH halves' positions, the counters; another kind
    writes none of them."""
    est, _, spans = fitted
    program, drain = spans["train.program"], spans["train.drain"]
    visited, total = flash.fold_chunk_counts(2 * T, 2 * T, 0, True, None, flash.BlockDiffusion(T, 4))
    scale = CFG.n_layers * CFG.n_heads * BATCH
    assert (program["layers_diffusion"], program["diffusion_block"]) == (CFG.n_layers, 4)
    assert (program["fold_bd_chunks_visited"], program["fold_bd_chunks"]) == (scale * visited, scale * total)
    assert (program["fold_chunks_visited"], program["fold_chunks"]) == (scale * visited, scale * total)
    assert program["positions"] == BATCH * 2 * T and program["fold_one_block"] >= 1 and program["fold_row_stats"] == 2
    assert program["fold_bwd_kernels"] == 1
    assert "layers_windowed" not in program and "layers_latent" not in program
    assert drain["tokens"] == STEPS * BATCH * T and drain["positions"] == 2 * drain["tokens"]
    assert drain["targets_masked"] == sum(est.targets_masked_history) and 0 < drain["targets_masked"] < drain["tokens"]
    levels = [float(jnp.sum(ref.corrupt(jnp.zeros((BATCH, T), jnp.int32), SEED, i, CFG)[2])) for i in range(STEPS)]
    assert drain["noise_level_sum"] == pytest.approx(sum(levels), rel=1e-6)
    held = est.expert_rows_history[:, :, CFG.first_held: CFG.first_held + CFG.held]
    assert drain["dropped"] == 0 and drain["rows_held"] == int(held.sum())
    assert drain["rows_held"] + drain["rows_absent"] == STEPS * BATCH * 2 * T * CFG.top_k * CFG.n_layers
    assert drain["moe_rows_routed"] == STEPS * CFG.n_layers * BATCH * 2 * T * CFG.top_k
    counters = (MLMetrics.TRAIN_LM_DIFFUSION_TARGETS, MLMetrics.TRAIN_LM_FOLD_BD_CHUNKS,
                MLMetrics.TRAIN_LM_FOLD_BD_CHUNKS_VISITED, MLMetrics.TRAIN_LM_TOKENS)
    before = [metrics.get(MLMetrics.TRAIN_GROUP, name) for name in counters]
    one = _estimator().set_max_iter(1)
    one.fit(df)
    assert [metrics.get(MLMetrics.TRAIN_GROUP, name) - was for name, was in zip(counters, before)] == \
        [one.targets_masked_history[0], scale * total, scale * visited, BATCH * T]
    with trace.capture() as recorder:  # next-token prediction under the causal mask writes none of it
        plain = DecoderLM().set_vocab_size(512).set_max_iter(1).set_global_batch_size(BATCH)
        plain.fit(DataFrame.from_dict({"features": df.vectors("features")[:, :256]}))
    spans = {s.name: s.attrs for s in recorder.snapshot()}
    assert not {"layers_diffusion", "fold_bd_chunks", "positions"} & set(spans["train.program"])
    assert not {"targets_masked", "noise_level_sum", "positions"} & set(spans["train.drain"])
    assert plain.targets_masked_history == []


# -- the share and the model ------------------------------------------------------------


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One chip of eight holds a range of a layer's experts; every chip
    computes attention alike. At a small size (16 experts, 2 a share): a
    share's layer output is ``x' + routed_s`` with ``x'`` the stream after
    attention, so the eight outputs less seven times ``x'`` - the routed parts
    of all eight shares, each expert's gate renormalised over BOTH chosen
    wherever they are held - are the uncut reference's layer output."""
    uncut = CFG._replace(experts_held=0, first_held=0)
    w = _moved(uncut, seed=3)["layers"][1]
    w = dict(w, **{name: 4.0 * w[name] for name in ("w_gate", "w_up", "w_down")})  # a routed part the stream's size
    x = 0.5 * jax.random.normal(jax.random.key(8), (BATCH, 2 * T, CFG.hidden))
    keep = jnp.asarray(ref.mask(T, CFG.block_length))
    pos = jnp.concatenate([jnp.arange(T), jnp.arange(T)])
    with jax.default_matmul_precision("highest"):
        after = x + ref.attention(ref.rms_norm(x, w["attn_norm"], 1e-6), w, keep, pos, uncut)
        routed, _ = ref.moe(ref.rms_norm(after, w["ffn_norm"], 1e-6).reshape(BATCH * 2 * T, -1), w, uncut)
        want = after + routed.reshape(after.shape)
    total = 0.0
    for first in range(0, 16, 2):
        share = uncut._replace(experts_held=2, first_held=first)
        held = dict(w, **{name: w[name][first: first + 2] for name in ("w_gate", "w_up", "w_down")})
        out, _, stats = decoder_lm._layer(x, None, held, layers(share)[1], F32, True)
        assert int(stats["rows"].sum()) == BATCH * 2 * T * CFG.top_k  # routed = held + absent, whatever is held
        total = total + out
    assert float(jnp.max(jnp.abs(want - after))) > 0.1  # the routed part is not nothing
    np.testing.assert_allclose(np.asarray(total - 7 * after), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_a_row_whose_chosen_experts_are_mostly_absent_keeps_small_gates():
    """The denominator is over ALL chosen experts: a token whose second choice
    is held elsewhere gets ``s_1 / (s_1 + s_2)`` of its held expert, not the
    whole of it; and the router's gradient flows through both."""
    from flink_ml_tpu.parallel.moe import moe_dropless, route_top_k

    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    p, top_p, top_e = route_top_k(x, router, 2)
    top_p = top_p / top_p.sum(axis=1, keepdims=True)  # what ``renormalise`` asks of ``moe_dropless``
    w = [jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32) for s in ((2, 16, 8), (2, 16, 8), (2, 8, 16))]

    def part(router, renormalise):
        return moe_dropless(x, router, *w, 2, jnp.float32, first_held=0, renormalise=renormalise)[0]

    here = np.asarray((top_e < 2).sum(axis=1))  # how many of a token's two choices are held here
    plain, renorm = np.asarray(part(router, False)), np.asarray(part(router, True))
    one = here == 1
    assert one.sum() > 5 and not np.allclose(plain[one], renorm[one])
    held_gate = np.asarray(jnp.where(top_e < 2, top_p, 0.0).sum(axis=1))
    assert (held_gate[one] < 1.0).all() and (held_gate[one] > 0.0).all()
    assert not renorm[here == 0].any()
    g = jax.grad(lambda r: jnp.sum(part(r, True) ** 2))(router)
    assert float(jnp.abs(g[:, 2:]).max()) > 0  # an absent expert's logit moves a held gate: the denominator


# -- the defects ------------------------------------------------------------------------


@pytest.mark.parametrize("defect", ["shifted_target", "noised_sees_its_own_clean_block", "clean_is_strictly_causal",
                                    "noised_is_causal_in_its_half", "clean_sees_its_noised_block", "no_one_over_p",
                                    "positions_run_on", "gates_not_renormalised", "qk_norm_over_the_projection"])
def test_a_defect_is_told_apart(defect, fitted, tokens, monkeypatch):
    """The defect, planted in the reference, moves the first step's loss or a
    leaf's gradient norm past the limits the sound stage is held to (1e-5,
    1e-4): each of the mask's four rules by itself, the target, the weight,
    the position ids, the gates and the QK-norm."""
    est, _, _ = fitted
    attention, weighted_nll = ref.attention, ref._weighted_nll
    if defect == "shifted_target":  # position i scored on token i + 1, as next-token prediction would
        monkeypatch.setattr(ref, "_weighted_nll", lambda p, tok, noised, m, lvl, c: weighted_nll(
            p, jnp.roll(tok, -1, axis=1), noised, m, lvl, c))
    elif defect in ("noised_sees_its_own_clean_block", "clean_is_strictly_causal", "noised_is_causal_in_its_half",
                    "clean_sees_its_noised_block"):
        monkeypatch.setattr(ref, "mask", lambda t, block: _bd_rules(t, block, defect))
    elif defect == "no_one_over_p":
        monkeypatch.setattr(ref, "_weighted_nll", lambda p, tok, noised, m, lvl, c: weighted_nll(
            p, tok, noised, m, jnp.ones_like(lvl), c))
    elif defect == "positions_run_on":  # 0 .. 2T - 1 where each half has 0 .. T - 1
        monkeypatch.setattr(ref, "attention", lambda a, w, keep, pos, c: attention(
            a, w, keep, jnp.arange(pos.shape[0]), c))
    elif defect == "gates_not_renormalised":
        def moe(u, layer, c):
            s = jax.nn.softmax(u @ layer["router"], axis=-1)
            top_s, top_e = jax.lax.top_k(s, c.top_k)
            weight = jnp.zeros_like(s).at[jnp.arange(u.shape[0])[:, None], top_e].set(top_s)
            y = sum(weight[:, c.first_held + e][:, None]
                    * ((jax.nn.silu(u @ layer["w_gate"][e]) * (u @ layer["w_up"][e])) @ layer["w_down"][e])
                    for e in range(c.held))
            return y, top_e
        monkeypatch.setattr(ref, "moe", moe)
    else:  # one mean square over all heads' channels (q and k arrive as [B, P, heads, channels])
        def norm(x, weight, eps):
            axes = (-2, -1) if x.ndim == 4 else (-1,)
            return weight * (x * jax.lax.rsqrt(jnp.mean(x * x, axis=axes, keepdims=True) + eps))
        monkeypatch.setattr(ref, "rms_norm", norm)
    p0, tok = init_params(CFG, SEED), _batches(tokens)[0]
    loss, grads, _ = ref.loss_and_grads(p0, tok, SEED, 0, CFG)
    leaves = [_rel(got, _norm(w)) for got, w in zip(est.param_grad_norm_history[0], _ordered(grads, CFG))]
    assert _rel(est.loss_history[0], float(loss)) > 1e-5 or max(leaves) > 1e-4, defect


@pytest.mark.parametrize("masks", [True, False], ids=["block_diffusion", "next_token"])
def test_an_inferred_vocabulary_keeps_an_id_for_the_mask_where_the_objective_has_one(tokens, masks):
    """``vocabSize`` left out: the ids seen and, where the stage's own record
    has a block length (read off ``lm_config``, not off the kind's name), one
    more id, the mask's; next-token prediction takes none."""
    few = DataFrame.from_dict({"features": tokens[:BATCH, :256] % 100})
    est = (_estimator() if masks else DecoderLM().set_global_batch_size(BATCH)).set_vocab_size(0).set_max_iter(1)
    assert (est.lm_config().block_length > 0) == masks
    model = est.fit(few)
    seen = int(few.vectors("features").max()) + 1
    assert model.get(model.VOCAB_SIZE) == seen + masks
    assert model.lm_config().mask_id == (seen if masks else 0)


def test_bad_sizes_are_refused(df, tokens):
    with pytest.raises(ValueError, match="power of two"):
        _estimator().set_block_length(6).fit(df)
    with pytest.raises(ValueError, match="whole blocks"):
        _estimator().set_block_length(256).fit(df)
    with pytest.raises(ValueError, match="maskTokenId"):
        _estimator().set_mask_token_id(512).fit(df)
    with pytest.raises(ValueError, match="belong to blockKind 'sdar'"):
        DecoderLM().set_block_length(8).set_vocab_size(512).fit(df)
    with pytest.raises(ValueError, match="multiple of 256"):
        _estimator().fit(DataFrame.from_dict({"features": tokens[:, :200]}))
    with pytest.raises(ValueError, match="numKvHeads"):
        _estimator().set_num_kv_heads(3).fit(df)
