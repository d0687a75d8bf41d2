"""``DecoderLM`` with ``blockKind`` ``olmo_hybrid`` (three layers in four run
the gated delta rule with ONE decay a head on heads wider in their values than
in their keys, the fourth attends without a position encoding under a QK-norm
over the whole projection; every layer has a dense SwiGLU; no norm before a
sublayer, each one's output normed before it joins) against its plain
reference (models/lm/reference_olmo_hybrid.py) on seeded random weights at toy
size: one published period (delta, delta, delta, full), hidden 64; the delta
rule on 3 heads of 8 key and 16 value channels, 4 taps, chunks of 64 (T 256:
four chunks, so the carried state is real); 4 heads of 16 that attend; a dense
SwiGLU of 96; an untied vocabulary of 512, batch 2, 2 steps. The same fit loop,
head, loss chunking, clip and AdamW program as the other kinds, chosen by a
stage parameter.

Tolerances. float32: stage and reference compute the same mathematics in
different orders (the delta rule in chunks through a triangular solve against
one position at a time), so they differ by float32 rounding; read here the
loss by 8e-8 relative, the gradient norm by 1e-7, a leaf's gradient norm by
1.1e-6 (the limits: 1e-5 on the losses, 1e-4 on the norms, 2e-4 on the
leaves). bfloat16 matmul inputs: the bands are Solar's, 2e-3 and 3e-2.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu import trace
from flink_ml_tpu.api.dataframe import DataFrame
from flink_ml_tpu.metrics import MLMetrics, metrics
from flink_ml_tpu.models.lm import DecoderLM, DecoderLMModel, decoder_lm
from flink_ml_tpu.models.lm import reference_olmo_hybrid as ref
from flink_ml_tpu.models.lm.config import (
    A_RANGE, DT_RANGE, Attention, GatedDelta, LMConfig, Rotation, layers, num_params, param_shapes,
)
from flink_ml_tpu.models.lm.decoder_lm import _flat_names, _ordered, init_params
from flink_ml_tpu.parallel import flash
from flink_ml_tpu.utils.read_write import load_stage

CFG = LMConfig(n_layers=4, hidden=64, n_heads=4, n_experts=0, top_k=0, expert_width=96, vocab=512, norm_eps=1e-6,
               aux_coef=0.0, block="olmo_hybrid", n_kv_heads=4, head_size=16, conv_kernel=4, chunk=64, gqa_layers=(3,),
               kda_heads=3, kda_head_dim=8, kda_value_dim=16)
#: one layer of each kind: what the tests that differentiate the whole loss themselves compile
SHORT = CFG._replace(n_layers=2, gqa_layers=(1,))
N, T, BATCH, STEPS, LR, SEED = 4, 256, 2, 2, 1e-3, 7
F32 = jnp.dtype("float32")
DECAY_LEAVES = ("A_log", "dt_bias", "Wa", "Wb")


def _estimator(compute_type="float32", cfg=CFG):
    return (
        DecoderLM().set_block_kind("olmo_hybrid")
        .set_num_layers(cfg.n_layers).set_gqa_layers(list(cfg.gqa_layers)).set_hidden_size(cfg.hidden)
        .set_kda_num_heads(cfg.kda_heads).set_kda_head_size(cfg.kda_head_dim)
        .set_kda_value_head_size(cfg.kda_value_dim)
        .set_ssm_conv_kernel(cfg.conv_kernel).set_ssm_chunk_size(cfg.chunk)
        .set_num_heads(cfg.n_heads).set_num_kv_heads(cfg.n_kv_heads).set_head_size(cfg.head_size)
        .set_expert_width(cfg.expert_width)
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
    """The seed's weights with every leaf that starts at a constant moved off it."""
    leaves = _ordered(init_params(cfg, seed), cfg)
    key = jax.random.key(99)
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
    specs = layers(CFG)
    assert [type(s.mixer) for s in specs] == [GatedDelta] * 3 + [Attention]
    # no norm before a sublayer, one on each one's output; the attention layer does not turn, and norms q and k
    assert all(s.mixer.norm == "" == s.ffn.norm for s in specs)
    assert all((s.mixer.out_norm, s.ffn.out_norm) == ("attn_out_norm", "ffn_out_norm") for s in specs)
    assert specs[3].mixer.rotation is None and specs[3].mixer.qk_norm == "projection"


def _cell_config():
    """The ``olmo_hybrid_7b`` configuration's ``LMConfig`` as the benchmark's system builds it."""
    from perfbench.manifest import Manifest
    from perfbench.systems import olmo_hybrid_lm_fit

    return olmo_hybrid_lm_fit.lm_config(Manifest().config("olmo_hybrid_7b"))


def test_parameter_count_at_the_cells_sizes():
    """ISSUE 54's arithmetic from the program's own ``param_shapes``, at 16
    bytes a parameter: a delta-rule mixer and the attention mixer at a chip's
    share of the heads (15 of 30), the dense SwiGLU whole, two output norms a
    layer, the 12,544-row slice of the untied embedding and head; with the
    heads WHOLE the cut is 14.86 GB and leaves a step no room; the fallback of
    10 heads; and the published 7B over all 32 layers, 30 heads and 100,352
    rows."""
    cfg = _cell_config()
    assert cfg.gqa_layers[:1] == (3,) and (cfg.kda_heads, cfg.n_heads, cfg.kv_heads) == (15, 15, 15)
    assert (cfg.kda_head_dim, cfg.kda_value_dim, cfg.head_dim, cfg.expert_width, cfg.hidden) == (96, 192, 128, 11_008,
                                                                                                  3_840)
    mixers, feeds, norms = {}, {}, {}
    for path, shape, _ in param_shapes(cfg):
        if path[0] == "layers":
            into = (norms if path[2].endswith("_out_norm") else feeds if path[2].startswith("w_") else mixers)
            into[path[1]] = into.get(path[1], 0) + int(np.prod(shape))
    assert [mixers[i] for i in range(4)] == [44_375_262] * 3 + [29_495_040]
    assert [feeds[i] for i in range(4)] == [126_812_160] * 4 and [norms[i] for i in range(4)] == [2 * 3_840] * 4
    assert num_params(cfg) == 766_241_946  # 12.26 GB at 16 B
    assert num_params(cfg) - sum(mixers.values()) - sum(feeds.values()) - sum(norms.values()) == \
        2 * 12_544 * 3_840 + 3_840
    heads_whole = cfg._replace(kda_heads=30, n_heads=30, n_kv_heads=30)
    assert num_params(heads_whole) == 928_862_196 and round(16 * num_params(heads_whole) / 1e9, 2) == 14.86
    ten_held = cfg._replace(kda_heads=10, n_heads=10, n_kv_heads=10)
    assert num_params(ten_held) == 712_035_196  # the fallback: 11.39 GB
    whole = heads_whole._replace(n_layers=32, gqa_layers=tuple(range(3, 32, 4)), vocab=100_352)
    assert round(num_params(whole) / 1e9, 2) == 7.43


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
        assert _rel(got, _norm(w)) < 1e-4, name


def test_every_parameter_after_two_steps(fitted, reference_run):
    _, model, _ = fitted
    want = reference_run[0]
    for name, a, b in zip(_flat_names(CFG), _ordered(model.params, CFG), _ordered(want, CFG)):
        assert float(jnp.max(jnp.abs(a - b))) < 0.05 * STEPS * LR, name


@pytest.mark.parametrize("compute_type,leaf_tol,norm_tol,decay_tol",
                         [("float32", 2e-4, 1e-4, 1e-4), ("bfloat16", None, 4e-2, 1.5e-1)])
def test_every_parameters_gradient(tokens, compute_type, leaf_tol, norm_tol, decay_tol):
    """Forward, loss and the gradient of every leaf - the delta rule's
    ``A_log``, ``dt_bias``, ``Wa``, ``Wb``, its convolutions, the full output
    gate and the head-wise norm, the QK-norms, the output norms - against
    ``jax.grad`` of the plain reference (the rule one position at a time), from
    weights with nothing at a constant. ``decay_tol`` holds ``A_log`` and
    ``dt_bias``, a number a head whose gradient sums, over every position,
    differences of terms far larger than what is left."""
    params = _moved(SHORT)
    tok = _batches(tokens)[0]
    want_loss, want = ref.loss_and_grads(params, tok, SHORT)
    (loss, stats), got = jax.value_and_grad(decoder_lm._loss, has_aux=True)(
        params, tok, SHORT, jnp.dtype(compute_type), True)
    assert _rel(loss, want_loss) < (1e-5 if leaf_tol else 2e-3)
    assert "rows" not in stats  # no layer has experts
    for name, g, w in zip(_flat_names(SHORT), _ordered(got, SHORT), _ordered(want, SHORT)):
        assert float(jnp.max(jnp.abs(w))) > 0, name
        if leaf_tol:
            assert float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w))) < leaf_tol, name
        assert _rel(_norm(g), _norm(w)) < (decay_tol if name.endswith(("A_log", "dt_bias")) else norm_tol), name


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
    assert est.expert_rows_history.shape == (STEPS, CFG.n_layers, 0)  # no experts, no columns
    got = np.asarray(model.transform(df).scalars("prediction"))
    want = np.asarray(ref.log_likelihood(model.params, jnp.asarray(tokens), CFG))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    path = str(tmp_path / "lm")
    model.save(path)
    loaded = load_stage(path)
    assert isinstance(loaded, DecoderLMModel) and list(loaded.get_gqa_layers()) == [3]
    assert loaded.lm_config() == CFG
    np.testing.assert_array_equal(np.asarray(loaded.transform(df).scalars("prediction")), got)
    other = DecoderLMModel()
    other.load_param_map_from_json(model.param_map_to_json())
    other.set_model_data(*model.get_model_data())
    np.testing.assert_array_equal(np.asarray(other.transform(df).scalars("prediction")), got)


def test_the_fit_counts_its_mixers_and_its_chunks(fitted, df):
    """``train.program``'s counts of the two kinds of mixer, the delta rule's
    chunks (all of them through the kernel pair, ALL of them in its one-decay
    form), the convolution's positions (all of them through ITS kernel pair:
    q, k and v one part) and the fold's chunks (the one attention layer's
    alone), and the counters."""
    from flink_ml_tpu.parallel.kda import kda_kernel_chunks

    _, _, spans = fitted
    program = spans["train.program"]
    assert (program["layers_kda"], program["layers_attn"], program["layers_moe"]) == (3, 1, 0)
    assert program["kda_chunks"] == 3 * BATCH * CFG.kda_heads * (T // CFG.chunk)
    assert program["kda_chunks"] == program["kda_chunks_kernel"] == program["kda_chunks_scalar"]
    assert program["kda_chunks_kernel"] == 3 * kda_kernel_chunks(BATCH, T, CFG.kda_heads, CFG.chunk)
    assert program["kda_state_bytes"] == \
        4 * BATCH * (T // CFG.chunk) * CFG.kda_heads * CFG.kda_head_dim * CFG.kda_value_dim
    assert program["conv_positions_kernel"] == program["conv_positions"] == \
        3 * BATCH * T * CFG.kda_heads * (2 * CFG.kda_head_dim + CFG.kda_value_dim)
    full = np.asarray(flash.fold_chunk_counts(T, T, 0, True, one_block=True))
    assert (program["fold_chunks_visited"], program["fold_chunks"]) == tuple(CFG.n_heads * BATCH * full)
    assert "layers_scan" not in program and "moe_layer_steps" not in spans["train.drain"]
    counters = (MLMetrics.TRAIN_LM_KDA_CHUNKS, MLMetrics.TRAIN_LM_KDA_KERNEL_CHUNKS,
                MLMetrics.TRAIN_LM_KDA_SCALAR_CHUNKS, MLMetrics.TRAIN_LM_KDA_LAYERS, MLMetrics.TRAIN_LM_CONV_POSITIONS,
                MLMetrics.TRAIN_LM_CONV_KERNEL_POSITIONS)
    before = [metrics.get(MLMetrics.TRAIN_GROUP, name) for name in counters]
    _estimator().set_max_iter(1).fit(df)
    assert [metrics.get(MLMetrics.TRAIN_GROUP, name) - was for name, was in zip(counters, before)] == \
        [program["kda_chunks"]] * 3 + [3, program["conv_positions"], program["conv_positions"]]


def test_the_decay_leaves_start_where_the_families_ranges_say():
    """``dt_bias`` and ``A_log`` are a number a HEAD each: the inverse softplus
    of a step size in 0.001 .. 0.1 (log-uniform) and the log of a decay rate in
    1 .. 16, each from its own leaf's stream of the seed; the head-wise output
    norm is ones over the value channels."""
    cfg = CFG._replace(kda_heads=512, n_layers=1, gqa_layers=())
    (w,) = init_params(cfg, SEED)["layers"]
    assert w["dt_bias"].shape == (512,) == w["A_log"].shape and w["o_norm"].shape == (CFG.kda_value_dim,)
    dt = np.asarray(jax.nn.softplus(w["dt_bias"]))
    assert DT_RANGE[0] * (1 - 1e-5) <= dt.min() < 2 * DT_RANGE[0] and DT_RANGE[1] / 2 < dt.max() <= DT_RANGE[1] * 1.00001
    a = np.exp(np.asarray(w["A_log"]))
    assert A_RANGE[0] <= a.min() < 1.5 and 15.5 < a.max() <= A_RANGE[1]
    np.testing.assert_array_equal(np.asarray(w["o_norm"]), 1.0)


# -- the shares and the model -----------------------------------------------------------


def _columns(w, names, lo, hi):
    return {name: w[name][..., lo: hi] for name in names}


@pytest.mark.parametrize("attends", [False, True], ids=["delta_rule", "attention"])
def test_the_two_head_shares_and_the_feed_forward_once_add_up_to_the_uncut_layer(attends):
    """Two chips share a layer: each holds half its heads (their columns of
    ``wq``, ``wk``, ``wv``, ``wg``, the convolutions, ``Wa``, ``Wb``, ``A_log``
    and ``dt_bias``, their rows of ``wo``) and the whole of ``o_norm``, the
    output norms and the dense SwiGLU. The two shares' ``wo`` outputs - taken
    BEFORE the output norm, where the deployment's all-reduce would be - add up
    to the uncut mixer's; normed, joined and followed by the feed-forward ONCE
    they are the uncut reference's layer. The delta rule's shares are the
    PROGRAM's (no output norm named: ``wo``'s output as it is); the attention
    layer's QK-norm takes one mean square over the whole projection, which a
    share cannot know, so its shares are the reference's, handed the whole
    projection's mean square - on the reference's side only: the program norms
    over the channels it holds, as the configuration says."""
    uncut = CFG._replace(n_layers=1, gqa_layers=(0,) if attends else (), kda_heads=4, n_heads=4, n_kv_heads=4)
    (w,) = _moved(uncut, seed=3)["layers"]
    x = 0.5 * jax.random.normal(jax.random.key(8), (BATCH, T, CFG.hidden))
    total = 0.0
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.layer(row, w, uncut, attends) for row in x])
        for s in range(2):
            if attends:
                a, share = 2 * CFG.head_dim, uncut._replace(n_heads=2, n_kv_heads=2)
                held = dict(w, **_columns(w, ("wq", "wk", "wv", "q_norm", "k_norm"), s * a, (s + 1) * a),
                            wo=w["wo"][s * a: (s + 1) * a])
                whole = [tuple(jnp.mean((row @ w[p]) ** 2, axis=-1, keepdims=True) for p in ("wq", "wk")) for row in x]
                total = total + jnp.stack([ref.attention(row, held, share, ms) for row, ms in zip(x, whole)])
            else:
                k, v = 2 * CFG.kda_head_dim, 2 * CFG.kda_value_dim
                held = dict(w, **_columns(w, ("wq", "wk", "conv_q", "conv_k"), s * k, (s + 1) * k),
                            **_columns(w, ("wv", "wg", "conv_v"), s * v, (s + 1) * v),
                            **_columns(w, ("Wa", "Wb", "A_log", "dt_bias"), 2 * s, 2 * s + 2),
                            wo=w["wo"][s * v: (s + 1) * v])
                mixer = GatedDelta(2, CFG.kda_head_dim, CFG.kda_value_dim, CFG.conv_kernel, CFG.chunk)  # no output norm
                total = total + decoder_lm._gated_delta(x, held, mixer, CFG.norm_eps, F32, True)
        joined = x + ref.rms_norm(total, w["attn_out_norm"], CFG.norm_eps)
        got = joined + ref.rms_norm(jnp.stack([ref.swiglu(row, w) for row in joined]), w["ffn_out_norm"], CFG.norm_eps)
    assert float(jnp.max(jnp.abs(want - x))) > 1e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)


def test_a_shares_partial_sum_is_normed_as_it_is(tokens):
    """The model under test IS a share (its heads may be half a layer's): the
    program norms ``wo``'s output and the held q and k channels as they are,
    and so does the reference - nothing stands in for the other chip. The
    program's layer on a share's leaves is the reference's on the same."""
    (w,) = _moved(CFG._replace(n_layers=1, gqa_layers=(0,)), seed=5)["layers"]
    spec = layers(CFG)[3]
    x = 0.5 * jax.random.normal(jax.random.key(8), (BATCH, T, CFG.hidden))
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.layer(row, w, CFG, True) for row in x])
    got, _, _ = decoder_lm._layer(x, None, w, spec, F32, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)


# -- a defect is told apart ---------------------------------------------------------------


def _rotated_attention(x, w, cfg, mean_square=None):
    """``ref.attention`` with rotate-half RoPE on q and k after all (theta 10,000)."""
    from flink_ml_tpu.models.lm import reference as olmoe

    t, heads, d = x.shape[0], cfg.n_heads, cfg.head_dim
    q = ref.rms_norm(x @ w["wq"], w["q_norm"], cfg.norm_eps).reshape(1, t, heads, d)
    k = ref.rms_norm(x @ w["wk"], w["k_norm"], cfg.norm_eps).reshape(1, t, heads, d)
    q, k = olmoe.rope(q, 10000.0)[0], olmoe.rope(k, 10000.0)[0]
    s = jnp.einsum("qhd,khd->hqk", q, k) * (d ** -0.5)
    keep = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1),
                   (x @ w["wv"]).reshape(t, heads, d))
    return o.reshape(t, heads * d) @ w["wo"]


@pytest.mark.parametrize("defect", ["beta_not_doubled", "sigmoid_gate", "a_pre_norm_slipped_in", "a_rotation_after_all",
                                    "no_qk_norm", "output_not_normed", "a_decay_a_channel_misread"])
def test_a_defect_is_told_apart(defect, fitted, tokens, monkeypatch):
    """The defect, planted in the reference, moves the first step's loss or a
    leaf's gradient norm past the limits the sound stage is held to (1e-5,
    1e-4)."""
    est, _, _ = fitted
    rule, heads, attention = ref.delta_rule, ref.gated_delta_heads, ref.attention
    if defect == "beta_not_doubled":  # beta in (0, 1): linear_allow_neg_eigval read as false
        monkeypatch.setattr(ref, "delta_rule", lambda q, k, v, g, beta: rule(q, k, v, g, beta / 2.0))
    elif defect == "sigmoid_gate":  # Solar's gate for this family's silu
        monkeypatch.setattr(ref, "gated_delta_heads", lambda x, w, c: heads(x, w, c) * jax.nn.sigmoid(x @ w["wg"])
                            / jax.nn.silu(x @ w["wg"]))
    elif defect == "a_pre_norm_slipped_in":  # the sublayers read a normed stream, as every other kind's do
        def pre_normed(x, w, cfg, attends):
            u = ref.rms_norm(x, jnp.ones_like(w["attn_out_norm"]), cfg.norm_eps)
            x = x + ref.rms_norm(attention(u, w, cfg) if attends else ref.gated_delta(u, w, cfg), w["attn_out_norm"],
                                 cfg.norm_eps)
            u = ref.rms_norm(x, jnp.ones_like(w["ffn_out_norm"]), cfg.norm_eps)
            return x + ref.rms_norm(ref.swiglu(u, w), w["ffn_out_norm"], cfg.norm_eps)

        monkeypatch.setattr(ref, "layer", pre_normed)
    elif defect == "a_rotation_after_all":
        monkeypatch.setattr(ref, "attention", _rotated_attention)
    elif defect == "no_qk_norm":
        ones = (jnp.ones((T, 1)),) * 2  # a mean square of one, less eps: q and k as projected
        monkeypatch.setattr(ref, "attention", lambda x, w, c, ms=None: attention(x, w, c, ones))
    elif defect == "output_not_normed":
        def bare(x, w, cfg, attends):  # the sublayers' outputs join as they are
            x = x + (attention(x, w, cfg) if attends else ref.gated_delta(x, w, cfg))
            return x + ref.swiglu(x, w)

        monkeypatch.setattr(ref, "layer", bare)
    else:  # the one decay a head taken for the FIRST key channel's alone: the other channels do not decay
        def first_channel(q, k, v, g, beta):
            from flink_ml_tpu.parallel.kda import reference_delta

            wide = jnp.zeros(q.shape).at[..., 0].set(g)
            return reference_delta(q[None], k[None], v[None], wide[None], beta[None])[0]

        monkeypatch.setattr(ref, "delta_rule", first_channel)
    loss, grads = ref.loss_and_grads(init_params(CFG, SEED), _batches(tokens)[0], CFG)
    leaves = [_rel(got, _norm(w)) for got, w in zip(est.param_grad_norm_history[0], _ordered(grads, CFG))]
    assert _rel(est.loss_history[0], float(loss)) > 1e-5 or max(leaves) > 1e-4, defect


def test_decays_rounded_to_bfloat16_inside_the_program_are_told_apart(tokens, monkeypatch):
    """The defect planted in the PROGRAM: the log-decays handed to the delta
    rule rounded to bfloat16 moves a decay leaf's gradient norm past 1e-4 of
    the reference's."""
    sound = decoder_lm.kda_scan
    monkeypatch.setattr(decoder_lm, "kda_scan", lambda q, k, v, g, beta, chunk, cd: sound(
        q, k, v, g.astype(jnp.bfloat16).astype(jnp.float32), beta, chunk, cd))
    params, tok = init_params(SHORT, SEED), _batches(tokens)[0]
    want_loss, want = ref.loss_and_grads(params, tok, SHORT)
    (loss, _), got = jax.value_and_grad(decoder_lm._loss, has_aux=True)(params, tok, SHORT, F32, True)
    decay = [_rel(_norm(g), _norm(w)) for name, g, w in zip(_flat_names(SHORT), _ordered(got, SHORT), _ordered(want, SHORT))
             if name.rsplit(".", 1)[-1] in DECAY_LEAVES]
    assert max(decay) > 1e-4 or _rel(loss, want_loss) > 1e-5


def test_bad_sizes_are_refused(df):
    with pytest.raises(ValueError, match="gqaLayers names layers among the 4"):
        _estimator().set_gqa_layers([3, 4]).fit(df)
    with pytest.raises(ValueError, match="kdaValueHeadSize"):
        _estimator().set_kda_value_head_size(0).fit(df)
    with pytest.raises(ValueError, match="power of two"):
        _estimator().set_ssm_chunk_size(96).fit(df)
    with pytest.raises(ValueError, match="kdaValueHeadSize belongs to blockKind 'olmo_hybrid'"):
        DecoderLM().set_kda_value_head_size(16).set_vocab_size(512).fit(df)
    with pytest.raises(ValueError, match="do not belong to blockKind 'ouro' or 'olmo_hybrid'"):
        _estimator().set_experts_held(2).fit(df)
    with pytest.raises(ValueError, match="the scan's chunk"):
        _estimator().set_ssm_chunk_size(128).fit(DataFrame.from_dict({"features": np.zeros((2, 320), np.int64)}))


def test_the_rotation_record_is_not_this_kinds():
    """No layer of the stack names a rotation: ``ropeTheta`` is read by none."""
    assert not any(isinstance(getattr(s.mixer, "rotation", None), Rotation) for s in layers(CFG))
    assert _estimator().set_rope_theta(5e5).lm_config(CFG.vocab)._replace(rope_theta=CFG.rope_theta) == CFG
