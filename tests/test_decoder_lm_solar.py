"""``DecoderLM`` with ``blockKind`` ``solar_open2`` (three layers in four run
the gated delta rule with a decay a key channel, the fourth attends on grouped
queries without a position encoding under an element-wise output gate; every
layer has sigmoid-gated experts beside a shared one) against its plain
reference (models/lm/reference_solar.py) on seeded random weights at toy size:
one published period (GQA, KDA, KDA, KDA), hidden 64; the delta rule on 2 heads
of 16 channels, 4 taps, chunks of 64 (T 256: four chunks, so the carried state
is real); 4 query heads of 16 on 2 key/value heads; 16 experts of width 32
(top-2; experts 4..7 held, a quarter: the layers take their 1,024 routed rows
through the experts in windows of 512, ``parallel/moe.py``) beside a shared one
of width 32; an untied vocabulary of 512, batch 2, 2 steps. The same fit loop,
head, loss chunking, clip and AdamW program as the other kinds, chosen by a
stage parameter.

Tolerances. float32: stage and reference compute the same mathematics in
different orders (the delta rule in chunks through a triangular solve against
one position at a time), so they differ by float32 rounding; read here the
loss by 2e-7 relative, the gradient norm by 1e-7, a leaf's gradient by 9e-6 of
its largest entry (the limits: 1e-5 on the losses, 1e-4 on the norms, 2e-4 on
the leaves). bfloat16 matmul inputs: the loss by 3e-4, the gradient norm by
5e-3; the bands are 2e-3 and 3e-2.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu import trace
from flink_ml_tpu.api.dataframe import DataFrame
from flink_ml_tpu.metrics import MLMetrics, metrics
from flink_ml_tpu.models.lm import DecoderLM, DecoderLMModel, decoder_lm
from flink_ml_tpu.models.lm import reference_solar as ref
from flink_ml_tpu.models.lm.config import (
    A_RANGE, DT_FLOOR, DT_RANGE, KDA, Attention, LMConfig, layers, num_params, param_shapes,
)
from flink_ml_tpu.models.lm.decoder_lm import _flat_names, _ordered, init_params
from flink_ml_tpu.parallel import flash
from flink_ml_tpu.utils.read_write import load_stage

CFG = LMConfig(n_layers=4, hidden=64, n_heads=4, n_experts=16, top_k=2, expert_width=32, vocab=512, norm_eps=1e-5,
               aux_coef=0.0, block="solar_open2", experts_held=4, first_held=4, n_kv_heads=2, head_size=16,
               shared_width=32, routed_scale=1.0, conv_kernel=4, chunk=64, gqa_layers=(0,), kda_heads=2,
               kda_head_dim=16)
#: a stack that ends in the layer that attends
SHORT = CFG._replace(n_layers=2, gqa_layers=(1,))
N, T, BATCH, STEPS, LR, SEED = 4, 256, 2, 2, 1e-3, 7
F32 = jnp.dtype("float32")
KDA_LEAVES = ("A_log", "dt_bias", "Fa", "Fb", "Wb")


def _estimator(compute_type="float32", cfg=CFG):
    return (
        DecoderLM().set_block_kind("solar_open2")
        .set_num_layers(cfg.n_layers).set_gqa_layers(list(cfg.gqa_layers)).set_hidden_size(cfg.hidden)
        .set_kda_num_heads(cfg.kda_heads).set_kda_head_size(cfg.kda_head_dim)
        .set_ssm_conv_kernel(cfg.conv_kernel).set_ssm_chunk_size(cfg.chunk)
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
    """The ``solar_open2_250b`` configuration's ``LMConfig`` as the benchmark's system builds it."""
    from perfbench.manifest import Manifest
    from perfbench.systems import solar_lm_fit

    return solar_lm_fit.lm_config(Manifest().config("solar_open2_250b"))


def test_parameter_count_at_the_cells_sizes():
    """ISSUE 51's arithmetic from the program's own ``param_shapes``, at 16
    bytes a parameter: the attention layer and a delta-rule layer at a chip's
    share of the heads (8 of 64; 8 query heads on 1 of 8 key/value heads), a
    layer's feed-forward with its 8 held experts, the 24,576-row slice of the
    untied embedding and head; with the heads WHOLE the cut is 20.7 GB and does
    not fit; and the published 250 B over all 48 layers, 64 heads, 320 experts
    and 196,608 rows."""
    cfg = _cell_config()
    assert cfg.gqa_layers == (0,) and (cfg.kda_heads, cfg.n_heads, cfg.kv_heads) == (8, 8, 1)
    mixers, feeds = {}, {}
    for path, shape, _ in param_shapes(cfg):
        if path[0] == "layers":
            mixer = path[2] in ("attn_norm", "wq", "wk", "wv", "wg", "wo", "conv_q", "conv_k", "conv_v", "Fa", "Fb",
                                "A_log", "dt_bias", "Wb", "Ga", "Gb", "o_norm")
            into = mixers if mixer else feeds
            into[path[1]] = into.get(path[1], 0) + int(np.prod(shape))
    assert [mixers[i] for i in range(4)] == [13_635_584, 18_138_248, 18_138_248, 18_138_248]
    assert [feeds[i] for i in range(4)] == [142_872_896] * 4
    assert num_params(cfg) == 840_872_600  # 13.45 GB at 16 B
    assert num_params(cfg) - sum(mixers.values()) - sum(feeds.values()) == 2 * 24_576 * 4_096 + 4_096
    heads_whole = cfg._replace(kda_heads=64, n_heads=64, n_kv_heads=8)
    assert round(16 * num_params(heads_whole) / 1e9, 1) == 20.7  # the chip has 16
    ten_held = cfg._replace(experts_held=10)
    assert round(16 * num_params(ten_held) / 1e9, 2) == 15.47
    whole = heads_whole._replace(n_layers=48, gqa_layers=tuple(range(0, 48, 4)), experts_held=0, vocab=196_608)
    assert round(num_params(whole) / 1e9, 1) == 250.3


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


@pytest.mark.parametrize("cfg", [CFG, SHORT], ids=["GQA-KDA-KDA-KDA", "KDA-GQA"])
@pytest.mark.parametrize("compute_type,leaf_tol,norm_tol,decay_tol",
                         [("float32", 2e-4, 1e-4, 1e-4), ("bfloat16", None, 4e-2, 1.5e-1)])
def test_every_parameters_gradient(cfg, tokens, compute_type, leaf_tol, norm_tol, decay_tol):
    """Forward, loss and the gradient of every leaf - the delta rule's
    ``A_log``, ``dt_bias``, low-rank gates, ``Wb`` and convolutions, the output
    norm, the attention layer's gate, the router, the shared expert - against
    ``jax.grad`` of the plain reference (the rule one position at a time), from
    weights with nothing at a constant. The selection bias has no gradient on
    either side. ``decay_tol`` holds ``A_log`` and ``dt_bias``: a number a head
    (two here) and a number a key channel whose gradients sum, over every
    position, differences of terms far larger than what is left (a pair's
    gradient with respect to the running log-decay at its row less that at its
    column); with bfloat16 matmul inputs the worst reads 7.4e-2, every other
    leaf under 2e-2."""
    params = _moved(cfg)
    tok = _batches(tokens)[0]
    want_loss, want = ref.loss_and_grads(params, tok, cfg)
    (loss, stats), got = jax.value_and_grad(decoder_lm._loss, has_aux=True)(
        params, tok, cfg, jnp.dtype(compute_type), True)
    assert _rel(loss, want_loss) < (1e-5 if leaf_tol else 2e-3)
    assert stats["rows"].shape == (cfg.n_layers, cfg.n_experts)  # every layer has experts
    # every layer took one window of its sorted rows through the experts, not all 1,024 of them
    assert stats["carried"].tolist() == [512] * cfg.n_layers
    for name, g, w in zip(_flat_names(cfg), _ordered(got, cfg), _ordered(want, cfg)):
        if name.endswith("router_bias"):
            assert float(jnp.max(jnp.abs(g))) == 0.0 == float(jnp.max(jnp.abs(w))), name
            continue
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
    assert est.expert_rows_history.shape == (STEPS, CFG.n_layers, CFG.n_experts)
    assert (est.expert_rows_history.sum(axis=2) == BATCH * T * CFG.top_k).all()  # routed = held + absent
    got = np.asarray(model.transform(df).scalars("prediction"))
    want = np.asarray(ref.log_likelihood(model.params, jnp.asarray(tokens), CFG))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    path = str(tmp_path / "lm")
    model.save(path)
    loaded = load_stage(path)
    assert isinstance(loaded, DecoderLMModel) and list(loaded.get_gqa_layers()) == [0]
    assert loaded.lm_config() == CFG
    np.testing.assert_array_equal(np.asarray(loaded.transform(df).scalars("prediction")), got)
    other = DecoderLMModel()
    other.load_param_map_from_json(model.param_map_to_json())
    other.set_model_data(*model.get_model_data())
    np.testing.assert_array_equal(np.asarray(other.transform(df).scalars("prediction")), got)


def test_the_fit_counts_its_mixers_its_chunks_and_its_held_rows(fitted, df):
    """``train.program``'s counts of the two kinds of mixer, the delta rule's
    chunks (all of them through the kernel pair), the convolution's positions
    (all of them through ITS kernel pair) and the fold's chunks (the one
    attention layer's alone), ``train.drain``'s held and absent rows and what
    the four expert layers carried, and the counters."""
    from flink_ml_tpu.parallel.kda import kda_kernel_chunks

    est, _, spans = fitted
    program, drain = spans["train.program"], spans["train.drain"]
    assert (program["layers_kda"], program["layers_attn"], program["layers_moe"]) == (3, 1, 4)
    assert program["kda_chunks"] == 3 * BATCH * CFG.kda_heads * (T // CFG.chunk) == program["kda_chunks_kernel"]
    assert program["kda_chunks_kernel"] == 3 * kda_kernel_chunks(BATCH, T, CFG.kda_heads, CFG.chunk)
    assert program["kda_state_bytes"] == 4 * BATCH * (T // CFG.chunk) * CFG.kda_heads * CFG.kda_head_dim ** 2
    assert program["conv_positions_kernel"] == program["conv_positions"] == \
        3 * BATCH * T * 3 * CFG.kda_heads * CFG.kda_head_dim
    full = np.asarray(flash.fold_chunk_counts(T, T, 0, True, one_block=True))
    assert (program["fold_chunks_visited"], program["fold_chunks"]) == tuple(CFG.n_heads * BATCH * full)
    assert "layers_scan" not in program and "scan_chunks" not in program
    n = CFG.n_layers
    held = est.expert_rows_history[:, :, CFG.first_held: CFG.first_held + CFG.held]
    assert drain["dropped"] == 0 and drain["rows_held"] == int(held.sum())
    assert drain["rows_held"] + drain["rows_absent"] == STEPS * BATCH * T * CFG.top_k * n
    assert drain["moe_layer_steps"] == STEPS * n == drain["moe_layer_steps_compact"]
    assert drain["rows_held"] <= drain["moe_rows_carried"] == 512 * STEPS * n
    counters = (MLMetrics.TRAIN_LM_KDA_CHUNKS, MLMetrics.TRAIN_LM_KDA_KERNEL_CHUNKS, MLMetrics.TRAIN_LM_KDA_LAYERS,
                MLMetrics.TRAIN_LM_CONV_POSITIONS, MLMetrics.TRAIN_LM_CONV_KERNEL_POSITIONS,
                MLMetrics.TRAIN_MOE_LAYER_STEPS)
    before = [metrics.get(MLMetrics.TRAIN_GROUP, name) for name in counters]
    _estimator().set_max_iter(1).fit(df)
    assert [metrics.get(MLMetrics.TRAIN_GROUP, name) - was for name, was in zip(counters, before)] == \
        [program["kda_chunks"], program["kda_chunks"], 3, program["conv_positions"], program["conv_positions"], n]


def test_the_decay_leaves_start_where_the_families_ranges_say():
    """``dt_bias`` (a number a key channel) is the inverse softplus of a step
    size in 0.001 .. 0.1 (log-uniform, floored), ``A_log`` (a number a head)
    the log of a decay rate in 1 .. 16, the output norm ones, each from its
    own leaf's stream of the seed: the strongest initial decay is 1.6 a
    position."""
    cfg = CFG._replace(kda_heads=256, n_layers=1, gqa_layers=())
    (w,) = init_params(cfg, SEED)["layers"]
    assert w["dt_bias"].shape == (256 * 16,) and w["A_log"].shape == (256,)
    dt = np.asarray(jax.nn.softplus(w["dt_bias"]))
    assert DT_RANGE[0] * (1 - 1e-5) <= dt.min() < 2 * DT_RANGE[0]
    assert DT_RANGE[1] / 2 < dt.max() <= DT_RANGE[1] * (1 + 1e-5)
    assert dt.min() >= DT_FLOOR and abs(np.median(np.log(dt)) - np.log(1e-2)) < 0.3
    a = np.exp(np.asarray(w["A_log"]))
    assert A_RANGE[0] <= a.min() < 1.5 and 15.5 < a.max() <= A_RANGE[1] and abs(a.mean() - 8.5) < 0.8
    assert float(a.max() * dt.max()) <= 1.6 * (1 + 1e-5)
    np.testing.assert_array_equal(np.asarray(w["o_norm"]), 1.0)


# -- the shares and the model -----------------------------------------------------------


def _columns(w, names, lo, hi):
    return {name: w[name][..., lo: hi] for name in names}


def test_the_eight_head_shares_of_a_delta_rule_layer_add_up_to_the_uncut_layer():
    """One chip of a group of eight holds an eighth of a delta-rule layer's
    heads: their columns of ``wq``, ``wk``, ``wv``, the convolutions, ``Fb``,
    ``Gb``, ``Wb``, ``dt_bias`` and ``A_log`` and their rows of ``wo``; ``Fa``,
    ``Ga`` and the norms whole. At a small size (8 heads of 16, a head a
    share): the eight shares' mixer outputs - each the held heads' part of
    ``wo``'s sum, which is what goes on - add up to the uncut reference's
    mixer output."""
    heads, d = 8, 16
    uncut = CFG._replace(n_layers=1, gqa_layers=(), kda_heads=heads, kda_head_dim=d)
    (w,) = _moved(uncut, seed=3)["layers"]
    x = 0.5 * jax.random.normal(jax.random.key(8), (BATCH, T, CFG.hidden))
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.kda(ref.rms_norm(row, w["attn_norm"], CFG.norm_eps), w, uncut) for row in x])
    total = 0.0
    for s in range(heads):
        held = dict(w, **_columns(w, ("wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "Fb", "Gb", "dt_bias"),
                                  s * d, (s + 1) * d),
                    **_columns(w, ("A_log", "Wb"), s, s + 1), wo=w["wo"][s * d: (s + 1) * d])
        total = total + decoder_lm._kda(x, held, KDA(1, d, CFG.conv_kernel, CFG.chunk), CFG.norm_eps, F32, True)
    assert float(jnp.max(jnp.abs(want))) > 1e-3
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=2e-4, atol=2e-6)


def test_the_eight_head_shares_of_the_attention_layer_add_up_to_the_uncut_layer():
    """The same for the layer that attends: 16 query heads on 8 key/value
    heads, a share 2 query heads on the ONE key/value head they read (the
    deployment's 8 on 1): its columns of ``wq``, ``wg``, ``wk``, ``wv`` and its
    rows of ``wo``."""
    heads, kv, d = 16, 8, 16
    uncut = CFG._replace(n_layers=1, gqa_layers=(0,), n_heads=heads, n_kv_heads=kv, head_size=d)
    (w,) = _moved(uncut, seed=4)["layers"]
    x = 0.5 * jax.random.normal(jax.random.key(9), (BATCH, T, CFG.hidden))
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.attention(ref.rms_norm(row, w["attn_norm"], CFG.norm_eps), w, uncut) for row in x])
    total, q = 0.0, heads // kv * d
    for s in range(kv):
        held = dict(w, **_columns(w, ("wq", "wg"), s * q, (s + 1) * q), **_columns(w, ("wk", "wv"), s * d, (s + 1) * d),
                    wo=w["wo"][s * q: (s + 1) * q])
        total = total + decoder_lm._attend(x, held, Attention(heads // kv, 1, d, out_gate=True), CFG.norm_eps, F32,
                                           True)
    assert float(jnp.max(jnp.abs(want))) > 1e-3
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=2e-4, atol=2e-6)


def test_the_forty_expert_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """One chip of forty holds a range of a layer's experts; every chip
    computes the shared expert alike. At a small size (40 experts, one a
    share): a share's feed-forward output is ``routed_s + S``, so the forty
    outputs less 39 times ``S`` - the routed parts of all forty shares, the
    shared expert ONCE - are the uncut reference's."""
    t = 64
    uncut = CFG._replace(n_layers=1, gqa_layers=(), n_experts=40, top_k=4, experts_held=0, first_held=0)
    (w,) = _moved(uncut, seed=5)["layers"]
    x = 0.5 * jax.random.normal(jax.random.key(8), (1, t, CFG.hidden))
    u = ref.rms_norm(x[0], w["ffn_norm"], CFG.norm_eps)
    with jax.default_matmul_precision("highest"):
        routed, _ = ref.moe(u, w, uncut)
        shared = ref.swiglu(u, w["shared_gate"], w["shared_up"], w["shared_down"])
    total = 0.0
    for first in range(40):
        share = uncut._replace(experts_held=1, first_held=first)
        held = dict(w, **{name: w[name][first: first + 1] for name in ("w_gate", "w_up", "w_down")})
        out, _, stats = decoder_lm._feed_forward(x, None, held, layers(share)[0].ffn, CFG.norm_eps, F32)
        assert int(stats["rows"].sum()) == t * uncut.top_k  # routed = held + absent, whatever is held
        total = total + out[0]
    assert float(jnp.max(jnp.abs(routed))) > 1e-4  # the routed part is not nothing
    np.testing.assert_allclose(np.asarray(total - 39 * shared), np.asarray(routed + shared), rtol=2e-4, atol=2e-6)


# -- a defect is told apart ---------------------------------------------------------------


def _softmax_moe(u, w, cfg):
    """The routed part with softmax probabilities for sigmoid scores, kept as they are."""
    picked, chosen = jax.lax.top_k(jax.nn.softmax(u @ w["router"], axis=-1), cfg.top_k)
    y = jnp.zeros_like(u)
    for j in range(cfg.held):
        w_j = jnp.sum(jnp.where(chosen == cfg.first_held + j, picked, 0.0), axis=1)
        y = y + w_j[:, None] * ref.swiglu(u, w["w_gate"][j], w["w_up"][j], w["w_down"][j])
    return y, chosen


@pytest.mark.parametrize("defect", ["beta_not_doubled", "no_output_gate", "no_gqa_gate", "state_forgotten_at_chunks",
                                    "q_and_k_not_normalised", "no_shared_expert", "softmax_gates", "taps_reversed"])
def test_a_defect_is_told_apart(defect, fitted, tokens, monkeypatch):
    """The defect, planted in the reference, moves the first step's loss or a
    leaf's gradient norm past the limits the sound stage is held to (1e-5,
    1e-4)."""
    est, _, _ = fitted
    kda, layer, rule = ref.kda, ref.layer, ref.delta_rule
    if defect == "beta_not_doubled":  # beta in (0, 1)
        monkeypatch.setattr(ref, "delta_rule", lambda q, k, v, g, beta: rule(q, k, v, g, beta / 2.0))
    elif defect == "no_output_gate":  # sigmoid(0) = 1/2 everywhere: the gate's projections read nothing
        monkeypatch.setattr(ref, "kda", lambda u, w, c: 2.0 * kda(u, dict(w, Gb=jnp.zeros_like(w["Gb"])), c))
    elif defect == "no_gqa_gate":
        monkeypatch.setattr(ref, "attention", lambda u, w, c, inner=ref.attention: 2.0 * inner(
            u, dict(w, wg=jnp.zeros_like(w["wg"])), c))
    elif defect == "state_forgotten_at_chunks":
        monkeypatch.setattr(ref, "delta_rule", lambda q, k, v, g, beta: jnp.concatenate(  # each chunk from a zero state
            [rule(*(m[lo: lo + CFG.chunk] for m in (q, k, v, g, beta))) for lo in range(0, q.shape[0], CFG.chunk)]))
    elif defect == "q_and_k_not_normalised":
        monkeypatch.setattr(ref, "UNIT_EPS", 1e6)  # the root of the squared length drowned: q and k keep their lengths
    elif defect == "no_shared_expert":
        monkeypatch.setattr(ref, "swiglu", lambda u, gate, up, down: jnp.zeros_like(u))
    elif defect == "softmax_gates":
        monkeypatch.setattr(ref, "moe", _softmax_moe)
    else:  # tap 0 reads the position itself
        monkeypatch.setattr(ref, "kda", lambda u, w, c: kda(u, dict(w, **{n: w[n][::-1] for n in (
            "conv_q", "conv_k", "conv_v")}), c))
    loss, grads = ref.loss_and_grads(init_params(CFG, SEED), _batches(tokens)[0], CFG)
    leaves = [_rel(got, _norm(w)) for name, got, w in zip(est.param_names, est.param_grad_norm_history[0],
                                                         _ordered(grads, CFG)) if not name.endswith("router_bias")]
    assert _rel(est.loss_history[0], float(loss)) > 1e-5 or max(leaves) > 1e-4, defect


def test_decays_rounded_to_bfloat16_inside_the_program_are_told_apart(tokens, monkeypatch):
    """The defect planted in the PROGRAM: the log-decays handed to the delta
    rule rounded to bfloat16 (8 bits of a decay's rate) moves a decay leaf's
    gradient norm past 1e-4 of the reference's, where the sound program reads
    2e-6."""
    sound = decoder_lm.kda_scan
    monkeypatch.setattr(decoder_lm, "kda_scan", lambda q, k, v, g, beta, chunk, cd: sound(
        q, k, v, g.astype(jnp.bfloat16).astype(jnp.float32), beta, chunk, cd))
    params, tok = init_params(CFG, SEED), _batches(tokens)[0]
    want_loss, want = ref.loss_and_grads(params, tok, CFG)
    (loss, _), got = jax.value_and_grad(decoder_lm._loss, has_aux=True)(params, tok, CFG, F32, True)
    decay = [_rel(_norm(g), _norm(w)) for name, g, w in zip(_flat_names(CFG), _ordered(got, CFG), _ordered(want, CFG))
             if name.rsplit(".", 1)[-1] in KDA_LEAVES]
    assert max(decay) > 1e-4 or _rel(loss, want_loss) > 1e-5


def test_bad_sizes_are_refused(df):
    with pytest.raises(ValueError, match="gqaLayers names layers among the 4"):
        _estimator().set_gqa_layers([0, 4]).fit(df)
    with pytest.raises(ValueError, match="kdaNumHeads"):
        _estimator().set_kda_num_heads(0).fit(df)
    with pytest.raises(ValueError, match="power of two"):
        _estimator().set_ssm_chunk_size(96).fit(df)
    with pytest.raises(ValueError, match="divide evenly over numKvHeads"):
        _estimator().set_num_heads(3).fit(df)
    with pytest.raises(ValueError, match="sharedExpertWidth"):
        _estimator().set_shared_expert_width(0).fit(df)
    with pytest.raises(ValueError, match="belong to blockKind 'solar_open2'"):
        DecoderLM().set_kda_num_heads(2).set_vocab_size(512).fit(df)
    with pytest.raises(ValueError, match="the scan's chunk"):
        _estimator().set_ssm_chunk_size(128).fit(DataFrame.from_dict({"features": np.zeros((2, 320), np.int64)}))
