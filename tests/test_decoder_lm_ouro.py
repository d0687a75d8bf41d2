"""``DecoderLM`` with ``blockKind`` ``ouro`` (a stack of dense sandwich-norm
layers run several times over the same weights, an exit gate after every pass,
a loss over all the exits) against its plain reference
(models/lm/reference_ouro.py) on seeded random weights at toy size: 2 layers,
hidden 128, 4 heads of 32, a dense SwiGLU of width 192, an untied vocabulary of
512, 3 passes, beta 0.1, T 256, batch 2, 2 steps. The same fit loop, head,
loss chunking, clip and AdamW program as the other two kinds, chosen by a
stage parameter; the passes are a ``lax.scan`` in the stage and a Python loop
in the reference.

Tolerances. float32: stage and reference compute the same mathematics in
different orders, so they differ by float32 rounding; read here the loss by
1e-7 relative, a pass's own loss by 1e-7, the gradient norm by 1e-7, a leaf's
gradient norm by 2e-6 (the limits sit 10x or more above). bfloat16 matmul
inputs: the loss by 4e-5, the gradient norm by 3e-3, a leaf's by 7e-3; the
bands are 1e-3, 2e-2 and 5e-2.
"""
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu.api.dataframe import DataFrame
from flink_ml_tpu.models.lm import DecoderLM, DecoderLMModel, decoder_lm
from flink_ml_tpu.models.lm import reference as olmoe_ref
from flink_ml_tpu.models.lm import reference_ouro as ref
from flink_ml_tpu.models.lm.config import LMConfig, num_params, param_shapes
from flink_ml_tpu.models.lm.decoder_lm import _flat_names, _ordered, init_params
from flink_ml_tpu.utils.read_write import load_stage

CFG = LMConfig(n_layers=2, hidden=128, n_heads=4, n_experts=0, top_k=0, expert_width=192, vocab=512,
               rope_theta=1e6, norm_eps=1e-6, aux_coef=0.0, block="ouro", loops=3, exit_beta=0.1)
N, T, BATCH, STEPS, LR, SEED = 4, 256, 2, 2, 1e-3, 11
F32 = jnp.dtype("float32")


def _estimator(compute_type="float32", cfg=CFG):
    return (
        DecoderLM().set_block_kind("ouro")
        .set_num_layers(cfg.n_layers).set_hidden_size(cfg.hidden).set_num_heads(cfg.n_heads)
        .set_expert_width(cfg.expert_width).set_vocab_size(cfg.vocab).set_rope_theta(cfg.rope_theta)
        .set_norm_eps(cfg.norm_eps).set_num_loops(cfg.loops).set_exit_entropy_coef(cfg.exit_beta)
        .set_compute_type(compute_type)
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
    return est, est.fit(df)


@pytest.fixture(scope="module")
def params():
    """The seed's weights with every leaf that starts at a constant moved off
    it (the gate's bias among them), so that no gradient is zero by construction."""
    leaves = _ordered(init_params(CFG, SEED), CFG)
    key = jax.random.key(99)
    step = {"normal": 0.0, "ones": 0.1, "zeros": 0.3}
    moved = [leaf + step[kind] * jax.random.normal(jax.random.fold_in(key, i), leaf.shape)
             for i, (leaf, (_, _, kind)) in enumerate(zip(leaves, param_shapes(CFG)))]
    return decoder_lm._build_tree(CFG, moved)


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
    est, _ = fitted
    assert est.lm_config(CFG.vocab) == CFG


def test_parameter_count_at_the_published_widths():
    """The issue's arithmetic, at 16 bytes a parameter: one layer, the cell's
    six with the whole vocabulary, the final norm and the gate; eight and nine
    layers; the published 48."""
    def ouro(layers):
        return LMConfig(layers, 2048, 16, 0, 0, 5632, 49152, rope_theta=1e6, norm_eps=1e-6, block="ouro", loops=4)

    layer = {path[2]: int(np.prod(shape)) for path, shape, _ in param_shapes(ouro(1)) if path[0] == "layers"}
    assert sum(layer[k] for k in ("wq", "wk", "wv", "wo")) == 4 * 2048 ** 2
    assert layer["w_gate"] + layer["w_up"] + layer["w_down"] == 3 * 2048 * 5632
    assert sorted(k for k in layer if k.endswith("norm")) == ["attn_norm", "attn_out_norm", "ffn_norm", "ffn_out_norm"]
    assert sum(layer.values()) == 51_388_416
    cut = num_params(ouro(6))
    assert cut == 509_661_185 and 8.1e9 < 16 * cut < 8.2e9
    assert 9.79e9 < 16 * num_params(ouro(8)) < 9.81e9 and 10.6e9 < 16 * num_params(ouro(9)) < 10.7e9
    assert num_params(ouro(48)) == 48 * 51_388_416 + 2 * 100_663_296 + 2048 + 2049
    names = _flat_names(ouro(1))
    assert names[-3:] == ["lm_head", "exit_gate_w", "exit_gate_b"] and names[0] == "embed"


def test_loss_and_gradient_norm_of_every_step(fitted, reference_run):
    """The whole objective and the global gradient norm of both steps: the
    second step's loss is the loss after one clipped AdamW update."""
    est, _ = fitted
    _, losses, norms, _ = reference_run
    assert len(est.loss_history) == STEPS == len(est.grad_norm_history)
    assert _rel(est.loss_history, losses) < 2e-6
    assert _rel(est.grad_norm_history, norms) < 5e-6
    assert est.param_names == _flat_names(CFG)
    assert est.param_grad_norm_history.shape == (STEPS, len(param_shapes(CFG)))


def test_every_pass_has_its_own_loss(fitted, reference_run):
    est, _ = fitted
    _, _, _, trips = reference_run
    assert est.trip_loss_history.shape == (STEPS, CFG.loops)
    assert _rel(est.trip_loss_history, trips) < 2e-6
    assert len({round(x, 4) for x in est.trip_loss_history[0]}) == CFG.loops  # no pass repeats another


def test_every_leafs_gradient_norm_in_the_fit(fitted, tokens):
    est, _ = fitted
    _, want = ref.loss_and_grads(init_params(CFG, SEED), _batches(tokens)[0], CFG)
    for name, got, w in zip(est.param_names, est.param_grad_norm_history[0], _ordered(want, CFG)):
        if name != "exit_gate_b":  # the fitted gate's bias starts at zero like the seed's; its gradient does not
            assert float(_norm(w)) > 0, name
        assert _rel(got, _norm(w)) < 5e-5, name


def test_every_parameter_after_two_steps(fitted, reference_run):
    _, model = fitted
    want = reference_run[0]
    for name, a, b in zip(_flat_names(CFG), _ordered(model.params, CFG), _ordered(want, CFG)):
        assert float(jnp.max(jnp.abs(a - b))) < 0.05 * STEPS * LR, name


@pytest.mark.parametrize("compute_type,leaf_tol,norm_tol", [("float32", 1e-4, 1e-4), ("bfloat16", None, 5e-2)])
def test_every_parameters_gradient(params, tokens, compute_type, leaf_tol, norm_tol):
    """Forward, loss and the gradient of every leaf - the gate's two, and every
    layer leaf summed over its three uses - against ``jax.grad`` of the plain
    reference, from weights with nothing at a constant."""
    tok = _batches(tokens)[0]
    want_loss, want = ref.loss_and_grads(params, tok, CFG)
    (loss, stats), got = jax.value_and_grad(decoder_lm._loss, has_aux=True)(
        params, tok, CFG, jnp.dtype(compute_type), True)
    assert _rel(loss, want_loss) < (2e-6 if leaf_tol else 1e-3)
    assert "rows" not in stats and stats["trip_nll"].shape == (CFG.loops,)
    for name, g, w in zip(_flat_names(CFG), _ordered(got, CFG), _ordered(want, CFG)):
        assert float(jnp.max(jnp.abs(w))) > 0, name
        if leaf_tol:
            assert float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w))) < leaf_tol, name
        assert _rel(_norm(g), _norm(w)) < norm_tol, name


def test_bfloat16_fit_within_its_bands(df, reference_run):
    est = _estimator("bfloat16")
    est.fit(df)
    _, losses, norms, trips = reference_run
    assert _rel(est.loss_history, losses) < 1e-3
    assert _rel(est.trip_loss_history, trips) < 1e-3
    assert _rel(est.grad_norm_history, norms) < 2e-2


def test_the_loop_is_an_untied_stack_of_copies(params, tokens):
    """The loop tied to the model: three passes over shared leaves are, in
    value, the plain reference run as an untied stack of 3 x 2 layers (and
    three final norms, heads and gates) built from copies; and each shared
    leaf's gradient is the sum of its copies'."""
    tok = _batches(tokens)[0]
    shared = {k: v for k, v in params.items() if k != "embed"}
    copies = [jax.tree_util.tree_map(lambda a, r=r: a + 0.0 * r, shared) for r in range(CFG.loops)]

    def untied(embed, copies):
        return ref.loss_and_parts({"embed": embed}, tok, CFG, passes=copies)

    (want_loss, (want_trips, _, _)), (d_embed, d_copies) = jax.value_and_grad(untied, argnums=(0, 1), has_aux=True)(
        params["embed"], copies)
    (loss, stats), got = jax.value_and_grad(decoder_lm._loss, has_aux=True)(params, tok, CFG, F32, True)
    assert _rel(loss, want_loss) < 2e-6 and _rel(stats["trip_nll"], want_trips) < 2e-6
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *d_copies)
    summed["embed"] = d_embed
    for name, g, w in zip(_flat_names(CFG), _ordered(got, CFG), _ordered(summed, CFG)):
        assert float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w))) < 1e-4, name
    # every copy has a gradient of its own to add, but the last pass's gate, which is not asked
    for r, d in enumerate(d_copies):
        for name, g in zip(_flat_names(CFG)[1:], _ordered({**d, "embed": d_embed}, CFG)[1:]):
            assert (float(_norm(g)) > 0) == (r < CFG.loops - 1 or not name.startswith("exit_gate")), (r, name)


def test_the_exit_distribution_sums_to_one():
    gate = 3.0 * jax.random.normal(jax.random.key(5), (4, 2, 16))
    log_p = decoder_lm._exit_distribution(gate)
    np.testing.assert_allclose(np.asarray(jnp.sum(jnp.exp(log_p), axis=0)), 1.0, rtol=1e-6)
    lam = jax.nn.sigmoid(gate)  # 1 - lambda cancels where lambda is near one: the plain products are the less exact side
    np.testing.assert_allclose(np.asarray(jnp.exp(log_p)), np.asarray(ref.exit_distribution(lam)), rtol=2e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(jnp.exp(log_p[1])), np.asarray(lam[1] * (1 - lam[0])), rtol=2e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(jnp.exp(log_p[3])), np.asarray(jnp.prod(1 - lam[:3], axis=0)), rtol=2e-5, atol=1e-7)
    # a gate far out: no nan, nothing lost
    far = decoder_lm._exit_distribution(jnp.array([[60.0], [-60.0], [0.0]]))
    assert np.isfinite(np.asarray(far)).all() and float(jnp.sum(jnp.exp(far))) == pytest.approx(1.0)


def test_one_pass_and_no_entropy_term_is_the_plain_cross_entropy(params, tokens):
    tok = _batches(tokens)[0]
    cfg = CFG._replace(loops=1, exit_beta=0.0)
    (loss, stats), got = jax.value_and_grad(decoder_lm._loss, has_aux=True)(params, tok, cfg, F32, True)
    with jax.default_matmul_precision("highest"):
        logits, _ = ref.forward(params, tok, cfg)
        plain = -jnp.mean(olmoe_ref.token_log_probs(logits[0], tok))
    assert _rel(loss, plain) < 2e-6 and _rel(stats["trip_nll"][0], plain) < 2e-6
    assert float(stats["exit_last_mass"]) == BATCH * T and float(stats["exit_trip_sum"]) == BATCH * T
    assert float(_norm(got["exit_gate_w"])) == 0.0  # the last pass's gate is not asked
    # and the entropy term alone moves the loss by beta x H
    (with_h, more), _ = jax.value_and_grad(decoder_lm._loss, has_aux=True)(params, tok, CFG, F32, True)
    without, _ = decoder_lm._loss(params, tok, CFG._replace(exit_beta=0.0), F32, True)
    targets = BATCH * (T - 1)
    entropy = float(more["gate_entropy_sum"]) * (T - 1) / T / targets  # all positions -> about the targets' mean
    assert float(without - with_h) == pytest.approx(CFG.exit_beta * entropy, rel=2e-2)


def test_a_block_without_experts_fits_scores_and_saves(fitted, df, tokens, tmp_path):
    """No expert, no division by zero: ``fit``, ``transform`` (the last pass's
    log-likelihood), ``save``/``load`` and the model-data round trip."""
    est, model = fitted
    assert est.expert_rows_history.shape == (STEPS, CFG.n_layers, 0)
    assert np.isfinite(est.loss_history).all() and np.isfinite(est.grad_norm_history).all()
    got = np.asarray(model.transform(df).scalars("prediction"))
    want = np.asarray(ref.log_likelihood(model.params, jnp.asarray(tokens), CFG))
    np.testing.assert_allclose(got, want, rtol=2e-6)
    path = str(tmp_path / "lm")
    model.save(path)
    loaded = load_stage(path)
    assert isinstance(loaded, DecoderLMModel) and loaded.get_num_loops() == CFG.loops
    np.testing.assert_array_equal(np.asarray(loaded.transform(df).scalars("prediction")), got)
    other = DecoderLMModel()
    other.load_param_map_from_json(model.param_map_to_json())
    other.set_model_data(*model.get_model_data())
    np.testing.assert_array_equal(np.asarray(other.transform(df).scalars("prediction")), got)


@pytest.mark.parametrize("defect", ["one_pass_fewer", "no_entropy_term", "uniform_exits", "last_norm_only"])
def test_a_defect_is_told_apart(defect, fitted, tokens, monkeypatch):
    """The defect, planted in the reference, moves the first step's loss or a
    pass's own loss past the limits the sound stage is held to."""
    est, _ = fitted
    cfg = CFG
    if defect == "one_pass_fewer":
        cfg = CFG._replace(loops=CFG.loops - 1)
    elif defect == "no_entropy_term":
        cfg = CFG._replace(exit_beta=0.0)
    elif defect == "uniform_exits":
        monkeypatch.setattr(ref, "exit_distribution", lambda gates: jnp.full_like(gates, 1.0 / len(gates)))
    else:  # the un-normed stream fed on from pass to pass, the final norm on the head's copy alone
        def forward(params, tok, cfg, passes=None):
            x, logits, gates = params["embed"][tok], [], []
            for _ in range(cfg.loops):
                for w in params["layers"]:
                    x = ref.layer(x, w, cfg)
                h = ref.rms_norm(x, params["final_norm"], cfg.norm_eps)
                logits.append(h @ params["lm_head"])
                gates.append(jax.nn.sigmoid(h @ params["exit_gate_w"] + params["exit_gate_b"])[..., 0])
            return jnp.stack(logits), jnp.stack(gates)

        monkeypatch.setattr(ref, "forward", forward)
    loss, (trips, _, _) = ref.loss_and_parts(init_params(CFG, SEED), _batches(tokens)[0], cfg)
    loss_err = _rel(est.loss_history[0], float(loss))
    trip_err = _rel(est.trip_loss_history[0][: len(trips)], trips) if len(trips) == CFG.loops else float("inf")
    assert max(loss_err, trip_err) > 1e-4, (loss_err, trip_err)


def test_bad_sizes_are_refused(df):
    with pytest.raises(ValueError, match="numLoops belongs"):
        DecoderLM().set_num_loops(2).set_vocab_size(512).fit(df)
    with pytest.raises(ValueError, match="do not belong"):
        _estimator().set_tie_embeddings(True).fit(df)
    with pytest.raises(ValueError, match="numHeads"):
        _estimator().set_num_heads(3).fit(df)


#: kind -> (configuration, compute type, sha256 of ``str(make_jaxpr(step))`` with addresses blanked, its ``while``
#: loops). All six were re-pinned at PR 48, where the one-block form's backward became ONE kernel
#: (``flash._attention_bwd_pallas``): every kind's step changed under the ``fold`` scope (a layer's two backward
#: ``pallas_call``s and the ``delta`` between them are one call) and nowhere else: flattened into the multiset of its
#: equations as below, each kind's step outside ``fold`` is the multiset of 5276c5f (PR 47), equation for equation
#: (1,739 / 908 / 3,642 / 1,646 / 1,788 / 5,128 equations: CHANGES.md, PR 48). Before that they
#: were re-pinned at PR 46, where ``decoder_lm._fold`` took the fold's one-block form
#: (``flash.fused_attention``): every kind's step changed under the ``fold`` scope (180 equations to 88 in the two-layer
#: ``olmoe``: the zero fills, ``acc / l``, the statistics' reshapes and the float32 ``dacc``, ``dl`` are gone) and
#: nowhere else: flattened into the multiset of its equations as below, each kind's step outside ``fold`` is the
#: multiset of 1de82f4 (PR 45), equation for equation (CHANGES.md, PR 46). They had all been re-pinned at PR 45, where
#: the head took to forming its gradients in the pass that holds its
#: logits (``decoder_lm._weighted_nll``): every kind's step changed under ``lm.head`` (and, the looped stack's
#: weights being ``p / targets``, in ``lm.exit``'s own arithmetic) and nowhere else. How that was shown: each kind's
#: step jaxpr flattened into the multiset of its equations (primitive, operand and result types, params; the bodies
#: of loops, checkpoints and calls walked, each equation filed under the scopes on its name stack) is, outside
#: ``lm.head`` and ``lm.exit``, the multiset of 31a0d25 (PR 44) less ONE equation a head call, the unscoped
#: ``broadcast_in_dim 0.0 -> f32[chunk]`` that ``logsumexp`` makes and the parent's ``lax.map`` hoisted out of its
#: body (CHANGES.md, PR 45). Before that ``zaya``, ``laguna`` and ``nemotron_h`` read what they read at 512ebfa
#: (PR 42) and the two ``olmoe`` cases and ``ouro`` what PR 43 pinned (one attention function for every kind).
STEP_JAXPRS = {
    "olmoe": (LMConfig(n_layers=2, hidden=128, n_heads=4, n_experts=8, top_k=2, expert_width=64, vocab=512),
              "float32", "1a13326309ee8838e98d42a672c1b5c1f19fe7838c87cfe3a4d688299a313179", 0),
    "olmoe_one_layer_bfloat16": (
        LMConfig(n_layers=1, hidden=128, n_heads=4, n_experts=8, top_k=2, expert_width=64, vocab=512),
        "bfloat16", "db859f0d20b1365949b422457da32fe857d05760d54a5e2cf01d514bd141d869", 0),
    "zaya": (LMConfig(n_layers=2, hidden=128, n_heads=4, n_experts=8, top_k=1, expert_width=64, vocab=512,
                      rope_theta=5e6, aux_coef=0.0, block="zaya", tied=True, experts_held=4, first_held=2,
                      n_kv_heads=2, head_size=16, rope_fraction=0.5, router_width=32),
             "float32", "b4c50cf761b0ddc0cf2e631af33c2f4aa258e5f8c79810330038fe6bcd32d68e", 0),
    "ouro": (CFG, "float32", "46afbb6119933b540a67af0cb711a282c28d6ad5a0332baa698a5631175f74b8", 0),
    # an eighth of the experts held, so the expert layer takes its sorted rows a window at a time, a loop of a
    # traced length a direction (``parallel/moe.py``; the recomputed forward's is unused, and gone), which the
    # kinds above, with every expert or a half of them held, must not hold
    "laguna": (LMConfig(n_layers=2, hidden=128, n_heads=4, n_experts=16, top_k=2, expert_width=64, vocab=512,
                        aux_coef=0.0, block="laguna", experts_held=2, first_held=2, n_kv_heads=2, head_size=16,
                        rope_fraction=0.5, layer_heads=(4, 8), layer_windows=(0, 96), n_dense=1, dense_width=96,
                        shared_width=32, routed_scale=2.5, window_rope_theta=1e4),
               "float32", "ecfe65ff537b1fa78beda24079f68e87b06ef78e75a56670e3c3c40d9755f0e5", 2),
    # its four expert layers take their rows in windows too: 5 ``while``s in the text, as at 512ebfa
    "nemotron_h": (LMConfig(n_layers=9, hidden=64, n_heads=4, n_experts=16, top_k=2, expert_width=32, vocab=512,
                            norm_eps=1e-5, aux_coef=0.0, block="nemotron_h", experts_held=2, first_held=2,
                            n_kv_heads=2, head_size=16, shared_width=48, routed_scale=2.5,
                            layer_kinds=tuple("MEMEM*EME"), ssm_heads=8, ssm_head_dim=8, ssm_groups=2, ssm_state=16,
                            conv_kernel=4, chunk=64),
                   "float32", "316b7c70ddc39b459032250cdf89f9bb59cffe281f90b4e058326870683b4e8c", 5),
}


@pytest.mark.parametrize("kind", sorted(STEP_JAXPRS))
def test_every_kinds_step_program_is_the_pinned_one(kind):
    cfg, compute_type, digest, loops = STEP_JAXPRS[kind]
    optimizer, step = decoder_lm._train_program(cfg, compute_type, 1e-3, 2, True)
    shapes = jax.eval_shape(lambda: decoder_lm._init_program(cfg)(jax.random.key(0)))
    text = str(jax.make_jaxpr(step)(shapes, jax.eval_shape(optimizer.init, shapes),
                                    jax.ShapeDtypeStruct((4, 256), jnp.int32), jax.ShapeDtypeStruct((), jnp.int32)))
    assert len(re.findall(r"\bwhile\[", text)) == loops
    assert hashlib.sha256(re.sub(r"0x[0-9a-f]+", "0x", text).encode()).hexdigest() == digest
