"""``DecoderLM`` against its plain reference (models/lm/reference.py) on seeded
random weights at toy size: 2 layers, hidden 128, 4 heads of 32, 8 experts
top-2 of width 64, vocabulary 512, T 256, batch 2, 3 steps, compute type
float32. The contract is docs/observability.md ("The LM fit") and the stage's
docstring.

Tolerances, and why they are where they are. Stage and reference compute the
same float32 mathematics in different orders (sorted grouped matmuls against
masked-dense experts, the fused fold against ``[T, T]`` scores, chunked loss
against whole logits), so they differ by float32 rounding: read here, the
per-step loss by 8e-8 relative and the gradient norm by 1.1e-7. The limits
(loss 1e-6, gradient norm 5e-6) sit about ten times above that and below what
each of the defects the issue names moves the FIRST step by, each tried once
in the reference at this size (``test_a_defect_is_told_apart`` keeps them):

    defect                              loss (rel)   gradient norm (rel)
    top-k probabilities renormalised    6.9e-05      1.7e-02
    router in bfloat16                  8.3e-06      7.2e-05
    ONE (token, expert) row dropped     1.6e-06      4.2e-05
    load-balancing term missing         3.2e-03      3.2e-05

Parameters after 3 steps: AdamW's early updates are ``lr * g / (|g| + eps)``,
near a sign, so where a gradient entry is within rounding of zero the two
sides may step apart by a fraction of ``lr``; the limit is 5% of the three
steps' reach (``3 * lr``), read 0.5%.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu.api.dataframe import DataFrame
from flink_ml_tpu.models.lm import DecoderLM, DecoderLMModel
from flink_ml_tpu.models.lm import reference as ref
from flink_ml_tpu.models.lm.config import LMConfig, num_params, param_shapes
from flink_ml_tpu.models.lm.decoder_lm import init_params
from flink_ml_tpu.utils.read_write import load_stage

CFG = LMConfig(n_layers=2, hidden=128, n_heads=4, n_experts=8, top_k=2, expert_width=64, vocab=512)
N, T, BATCH, STEPS, LR, SEED = 4, 256, 2, 3, 1e-3, 5
LOSS_RTOL, NORM_RTOL, PARAM_ATOL = 1e-6, 5e-6, 0.05 * STEPS * LR


def _estimator():
    return (
        DecoderLM()
        .set_num_layers(CFG.n_layers).set_hidden_size(CFG.hidden).set_num_heads(CFG.n_heads)
        .set_num_experts(CFG.n_experts).set_experts_per_token(CFG.top_k)
        .set_expert_width(CFG.expert_width).set_vocab_size(CFG.vocab)
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


def _batches(tokens):
    # the window cycles: rows 0-1, 2-3, then 0-1 again
    return [jnp.asarray(tokens[lo: lo + BATCH]) for lo in (0, 2, 0)]


@pytest.fixture(scope="module")
def reference_run(tokens):
    return ref.train_steps(init_params(CFG, SEED), _batches(tokens), CFG, LR)


def _rel(a, b):
    return np.max(np.abs(np.asarray(a) - np.asarray(b)) / np.abs(np.asarray(b)))


def test_parameter_count_is_olmoe_one_layer():
    """The issue's arithmetic: embeddings, head, one layer, final norm."""
    assert num_params(LMConfig(1, 2048, 16, 64, 8, 1024, 50304)) == 625_616_896
    assert num_params(LMConfig(16, 2048, 16, 64, 8, 1024, 50304)) == 6_919_161_856


def test_loss_and_gradient_norm_of_every_step(fitted, reference_run):
    est, _ = fitted
    _, losses, norms = reference_run
    assert len(est.loss_history) == STEPS == len(est.grad_norm_history)
    assert _rel(est.loss_history, losses) < LOSS_RTOL
    assert _rel(est.grad_norm_history, norms) < NORM_RTOL
    assert est.param_grad_norm_history.shape == (STEPS, len(param_shapes(CFG)))


def test_every_parameter_after_three_steps(fitted, reference_run):
    _, model = fitted
    want, _, _ = reference_run
    got = jax.tree_util.tree_leaves_with_path(model.params)
    for (path, a), b in zip(got, jax.tree_util.tree_leaves(want)):
        err = float(jnp.max(jnp.abs(a - b)))
        assert err < PARAM_ATOL, (jax.tree_util.keystr(path), err)


def test_transform_is_the_reference_log_likelihood(fitted, reference_run, df, tokens):
    _, model = fitted
    got = np.asarray(model.transform(df).scalars("prediction"))
    want = np.asarray(ref.log_likelihood(model.params, jnp.asarray(tokens), CFG))
    np.testing.assert_allclose(got, want, rtol=2e-6)


def test_no_token_is_dropped(fitted):
    est, _ = fitted
    loads = est.expert_rows_history
    assert loads.shape == (STEPS, CFG.n_layers, CFG.n_experts)
    assert (loads.sum(axis=2) == BATCH * T * CFG.top_k).all()


def _weighted(u, layer, cfg, denominator=None, logits=None):
    logits = u @ layer["router"] if logits is None else logits
    p = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(p, cfg.top_k)
    weight = p * jnp.sum(jax.nn.one_hot(top_e, cfg.n_experts, dtype=p.dtype), axis=1)
    if denominator is not None:
        weight = weight / denominator(p, top_p)
    y = jnp.zeros_like(u)
    for e in range(cfg.n_experts):
        hidden = jax.nn.silu(u @ layer["w_gate"][e]) * (u @ layer["w_up"][e])
        y = y + weight[:, e: e + 1] * (hidden @ layer["w_down"][e])
    return y, p, top_e


def _renormalised(u, layer, cfg):
    return _weighted(u, layer, cfg, lambda p, top_p: top_p.sum(-1, keepdims=True))


def _bf16_router(u, layer, cfg):
    logits = (u.astype(jnp.bfloat16) @ layer["router"].astype(jnp.bfloat16)).astype(jnp.float32)
    return _weighted(u, layer, cfg, logits=logits)


def _one_row_dropped(u, layer, cfg):
    y, p, top_e = _weighted(u, layer, cfg)
    e = top_e[7, 0]  # token 7 loses its first expert's contribution
    hidden = jax.nn.silu(u[7] @ layer["w_gate"][e]) * (u[7] @ layer["w_up"][e])
    return y.at[7].add(-p[7, e] * (hidden @ layer["w_down"][e])), p, top_e


DEFECTS = {
    "renormalised_top_k": dict(moe=_renormalised),
    "bf16_router": dict(moe=_bf16_router),
    "dropped_token": dict(moe=_one_row_dropped),
    "missing_aux_term": dict(cfg=CFG._replace(aux_coef=0.0)),
}


@pytest.mark.parametrize("name", sorted(DEFECTS))
def test_a_defect_is_told_apart(name, fitted, tokens, monkeypatch):
    """The defect, planted in the reference, moves the first step's loss or
    gradient norm past the limits the sound stage is held to."""
    est, _ = fitted
    defect = DEFECTS[name]
    if "moe" in defect:
        monkeypatch.setattr(ref, "moe", defect["moe"])
    loss, grads = ref.loss_and_grads(init_params(CFG, SEED), _batches(tokens)[0], defect.get("cfg", CFG))
    loss_err = _rel(est.loss_history[0], float(loss))
    norm_err = _rel(est.grad_norm_history[0], float(ref.global_norm(grads)))
    assert loss_err > LOSS_RTOL and norm_err > NORM_RTOL, (loss_err, norm_err)


def test_save_load_round_trip(fitted, df, tmp_path):
    _, model = fitted
    path = str(tmp_path / "lm")
    model.save(path)
    loaded = load_stage(path)
    assert isinstance(loaded, DecoderLMModel) and loaded.get_vocab_size() == CFG.vocab
    np.testing.assert_array_equal(
        np.asarray(loaded.transform(df).scalars("prediction")),
        np.asarray(model.transform(df).scalars("prediction")),
    )
    with pytest.raises(IOError):
        model.save(path)


def test_model_data_round_trip(fitted, df):
    _, model = fitted
    other = DecoderLMModel()
    other.load_param_map_from_json(model.param_map_to_json())
    other.set_model_data(*model.get_model_data())
    np.testing.assert_array_equal(
        np.asarray(other.transform(df).scalars("prediction")),
        np.asarray(model.transform(df).scalars("prediction")),
    )


def test_the_fit_is_a_function_of_the_seed(df, fitted):
    est, _ = fitted
    again = _estimator()
    again.fit(df)
    assert again.loss_history == est.loss_history
    other = _estimator().set_seed(SEED + 1)
    other.fit(df)
    assert other.loss_history != est.loss_history


def test_bad_inputs_are_refused(df, tokens):
    with pytest.raises(ValueError, match="multiple of 256"):
        _estimator().fit(DataFrame.from_dict({"features": tokens[:, :200]}))
    with pytest.raises(ValueError, match="vocabSize"):
        _estimator().set_vocab_size(100).fit(df)
    with pytest.raises(ValueError, match="numHeads"):
        _estimator().set_num_heads(3).fit(df)
    with pytest.raises(ValueError, match="non-negative"):
        _estimator().fit(DataFrame.from_dict({"features": tokens - 1}))


def test_fused_fold_vjp_at_sixteen_heads():
    """The fold's fused backward at H=16, causal, a ring of one (interpret
    mode, small T), float32 and bfloat16 q/k/v, against ``reference_fold_bwd``."""
    from flink_ml_tpu.parallel.flash import fused_fold, reference_fold_bwd

    B, H, Tq, D = 1, 16, 256, 32
    rng = np.random.default_rng(2)
    q, k, v, dacc = (jnp.asarray(rng.standard_normal((B, H, Tq, D)).astype(np.float32)) for _ in range(4))
    m0 = jnp.full((B, H, Tq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Tq), jnp.float32)
    acc0 = jnp.zeros((B, H, Tq, D), jnp.float32)
    dm = jnp.zeros((B, H, Tq), jnp.float32)
    dl = jnp.asarray(rng.standard_normal((B, H, Tq)).astype(np.float32))
    scale = D ** -0.5
    zero = jnp.int32(0)
    want = reference_fold_bwd(q, k, v, m0, l0, acc0, 0, 0, True, None, scale, dm, dl, dacc)[:3]

    def fold(dtype):
        return lambda q_, k_, v_: fused_fold(q_.astype(dtype), k_.astype(dtype), v_.astype(dtype),
                                             m0, l0, acc0, zero, zero, True, False, zero, scale, True)

    _, vjp = jax.vjp(fold(jnp.float32), q, k, v)
    for name, g, w in zip("qkv", vjp((dm, dl, dacc)), want):
        err = float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w)))
        assert err < 2e-5, (name, err)

    # bfloat16 q/k/v, as the LM's bfloat16 compute type calls it. Arbitrary
    # cotangents of (l, acc) put weight on the running max, whose gradient
    # jumps when rounding moves the argmax; attention's own output acc / l does
    # not depend on it, so that is what is differentiated here.
    def attend(fn):
        def loss(q_, k_, v_):
            _, l, acc = fn(q_, k_, v_)
            return jnp.sum(acc / l[..., None] * dacc)
        return jax.grad(loss, argnums=(0, 1, 2))

    for name, g, w in zip("qkv", attend(fold(jnp.bfloat16))(q, k, v), attend(fold(jnp.float32))(q, k, v)):
        err = float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w)))
        assert err < 3e-2, (name, err)
