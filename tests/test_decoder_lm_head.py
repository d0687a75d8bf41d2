"""The training head (``decoder_lm._next_token_nll``, ``_weighted_nll``): the
pass that holds a chunk's ``[chunk, V]`` logits forms that chunk's ``dh`` and
its share of ``dW`` there and then, and the backward scales them, so the
logits are computed once a step. Toy sizes on the CPU.

Against the plain per-token form (whole logits, ``logsumexp``, pick, weighted
sum; no chunks, no checkpoint, ``jax.grad``) under each caller's weights: the
mean's uniform ``1 / (B (T - 1))``, the multi-token-prediction module's
``mtp_coef / targets`` on the positions it scores, the looped stack's ``p /
targets`` from an exit gate (whose two leaves get their gradient THROUGH the
weights). float32: the two sides compute the same mathematics in another
order; read here 2e-7 relative at the most, held to 1e-6. bfloat16 matmul
inputs: a gradient's norm by 3e-3 (``d logits`` is rounded to bfloat16 before
both matmuls), held to the 3e-2 the kinds' own bfloat16 step tests hold the
global norm to.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu.models.lm import decoder_lm
from tests import test_lm_scopes

D, V, LOOPS, MTP_COEF = 32, 96, 3, 0.3
F32, BF16 = jnp.dtype("float32"), jnp.dtype("bfloat16")
HIGHEST = jax.lax.Precision.HIGHEST
#: weighting -> (B, T): the uniform case's 2,048-row chunks each hold two sequences' rows, so a sequence's last
#: position lies inside a chunk; the others' chunks are one sequence each (``B T`` no multiple of 2,048)
SHAPES = {"uniform": (4, 1024), "module": (2, 256), "exits": (2, 256)}


def _leaves(weighting, tied, seed=0):
    b, t = SHAPES[weighting]
    rows = LOOPS * b if weighting == "exits" else b
    keys = jax.random.split(jax.random.key(seed), 5)
    leaves = {"h": jax.random.normal(keys[0], (rows, t, D), jnp.float32),
              "gate_w": 0.3 * jax.random.normal(keys[2], (D, 1), jnp.float32),
              "gate_b": 0.1 * jax.random.normal(keys[3], (1,), jnp.float32)}
    # a tied head is the embedding table transposed: its gradient reaches the table through the transpose
    leaves["embed" if tied else "lm_head"] = 0.2 * jax.random.normal(keys[1], (V, D) if tied else (D, V), jnp.float32)
    tok = jax.random.randint(keys[4], (b, t), 0, V)
    return leaves, tok


def _weights(weighting, leaves, tok):
    """``(weight [rows, T], the head's token rows)`` as the three callers build them."""
    b, t = tok.shape
    if weighting == "uniform":
        return jnp.full((b, t), 1.0 / (b * (t - 1)), jnp.float32), tok
    if weighting == "module":
        scored = jnp.arange(t) < t - 2
        return jnp.broadcast_to(MTP_COEF * scored / (b * (t - 2)), (b, t)), tok
    gate = (jnp.sum(leaves["h"] * leaves["gate_w"][:, 0], axis=-1) + leaves["gate_b"][0]).reshape(LOOPS, b, t)
    p = jnp.exp(decoder_lm._exit_distribution(gate)).at[:, :, -1].set(0.0)
    return (p / (b * (t - 1))).reshape(LOOPS * b, t), jnp.tile(tok, (LOOPS, 1))


def _matrix(leaves):
    return leaves["embed"].T if "embed" in leaves else leaves["lm_head"]


def _fused(weighting, cd, scale=1.0):
    def objective(leaves, tok):
        weight, rows = _weights(weighting, leaves, tok)
        total, _ = decoder_lm._next_token_nll(leaves["h"], _matrix(leaves), rows, cd)(weight)
        return scale * total

    return objective


def _plain(weighting):
    def objective(leaves, tok):
        weight, rows = _weights(weighting, leaves, tok)
        logits = jnp.einsum("btd,dv->btv", leaves["h"], _matrix(leaves), precision=HIGHEST)[:, :-1]
        picked = jnp.take_along_axis(logits, rows[:, 1:, None], axis=-1)[..., 0]
        return jnp.sum(weight[:, :-1] * (jax.nn.logsumexp(logits, axis=-1) - picked))

    return objective


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def _norm_rel(got, want):
    return abs(float(jnp.linalg.norm(got) / jnp.linalg.norm(want)) - 1.0)


def _moved(weighting, grads):
    """The leaves the objective reaches: the gate's two only through the exits' weights."""
    return [k for k in grads if weighting == "exits" or not k.startswith("gate")]


@pytest.mark.parametrize("tied", [False, True], ids=["own_matrix", "tied"])
@pytest.mark.parametrize("weighting", sorted(SHAPES))
def test_float32_gradients_are_plain_ads(weighting, tied):
    leaves, tok = _leaves(weighting, tied)
    loss, grads = jax.jit(jax.value_and_grad(_fused(weighting, F32)))(leaves, tok)
    want_loss, want = jax.jit(jax.value_and_grad(_plain(weighting)))(leaves, tok)
    assert abs(float(loss) / float(want_loss) - 1.0) < 1e-6
    for name in _moved(weighting, grads):
        assert float(jnp.max(jnp.abs(want[name]))) > 0, name
        assert _rel(grads[name], want[name]) < 1e-6, name
    for name in set(grads) - set(_moved(weighting, grads)):
        assert not np.asarray(grads[name]).any() and not np.asarray(want[name]).any(), name


@pytest.mark.parametrize("weighting", sorted(SHAPES))
def test_bfloat16_gradients_within_the_step_tests_band(weighting):
    leaves, tok = _leaves(weighting, False)
    loss, grads = jax.jit(jax.value_and_grad(_fused(weighting, BF16)))(leaves, tok)
    want_loss, want = jax.jit(jax.value_and_grad(_plain(weighting)))(leaves, tok)
    assert abs(float(loss) / float(want_loss) - 1.0) < 2e-3
    for name in _moved(weighting, grads):
        assert _norm_rel(grads[name], want[name]) < 3e-2, name
        assert _rel(grads[name], want[name]) < 6e-2, name


@pytest.mark.parametrize("weighting", sorted(SHAPES))
def test_an_incoming_cotangent_scales_every_gradient(weighting):
    """The loss times 3: the backward rule multiplies what the forward left,
    ``dh``, ``dW`` and the weights' cotangent (the gate's leaves) alike."""
    leaves, tok = _leaves(weighting, False)
    once = jax.jit(jax.grad(_fused(weighting, F32)))(leaves, tok)
    thrice = jax.jit(jax.grad(_fused(weighting, F32, scale=3.0)))(leaves, tok)
    for name in _moved(weighting, once):
        assert _rel(thrice[name], 3.0 * once[name]) < 1e-6, name


def test_the_reported_nll_is_the_scoring_paths_and_carries_no_gradient():
    leaves, tok = _leaves("module", False)
    weight, rows = _weights("module", leaves, tok)
    head = decoder_lm._next_token_nll(leaves["h"], leaves["lm_head"], rows, F32)
    _, nll = jax.jit(lambda w: head(w))(weight)
    np.testing.assert_array_equal(np.asarray(nll), np.asarray(jax.jit(lambda: head())()))
    assert not np.asarray(nll)[:, -1].any() and (np.asarray(nll)[:, :-1] > 0).all()
    through_nll = jax.grad(lambda h: jnp.sum(decoder_lm._next_token_nll(h, leaves["lm_head"], rows, F32)(weight)[1]))
    assert not np.asarray(through_nll(leaves["h"])).any()
    # the last position's weight is not read: no gradient comes back for it, whatever it holds
    d_weight = jax.grad(lambda w: head(w)[0])(weight + 1.0)
    assert not np.asarray(d_weight)[:, -1].any() and np.asarray(d_weight)[:, :-1].all()


def _parents_nll(h, lm_head, tok, cd):
    """``_next_token_nll`` as it stood before the head formed its gradients in
    the forward (31a0d25): the scoring path's mathematics, instruction for
    instruction, under the ``jax.checkpoint`` that made the backward compute
    the logits again."""
    b, t, d = h.shape
    n = b * t
    chunk = decoder_lm._LOSS_CHUNK if n % decoder_lm._LOSS_CHUNK == 0 else t
    targets = jnp.concatenate([tok[:, 1:], jnp.zeros((b, 1), tok.dtype)], axis=1).reshape(n)
    w = lm_head.astype(cd)

    @jax.checkpoint
    def one(args):
        hc, tc = args
        logits = jnp.dot(hc.astype(cd), w, preferred_element_type=jnp.float32,
                         precision=HIGHEST if cd == jnp.float32 else None)
        picked = jnp.take_along_axis(logits, tc[:, None], axis=1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    nll = jax.lax.map(one, (h.reshape(n // chunk, chunk, d), targets.reshape(n // chunk, chunk)))
    return nll.reshape(b, t).at[:, -1].set(0.0)


@pytest.mark.parametrize("cd", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("weighting", ["uniform", "module"])
def test_scoring_is_bit_for_bit_what_it_was(weighting, cd):
    leaves, tok = _leaves(weighting, False, seed=7)
    got = jax.jit(lambda h, w: decoder_lm._next_token_nll(h, w, tok, cd)())(leaves["h"], leaves["lm_head"])
    want = jax.jit(lambda h, w: _parents_nll(h, w, tok, cd))(leaves["h"], leaves["lm_head"])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _traced_step(cfg, compute_type="float32"):
    optimizer, step = decoder_lm._train_program(cfg, compute_type, 1e-3, 2, True)
    params = jax.eval_shape(lambda: decoder_lm._init_program(cfg)(jax.random.key(0)))
    return step, params, jax.eval_shape(optimizer.init, params), jax.ShapeDtypeStruct((4, 256), jnp.int32)


def _noise(cfg):
    """What a stage that trains by block diffusion hands its step beside the others' arguments: ``((the noise key,
    the step's index),)``; another objective, nothing."""
    if not cfg.block_length:
        return ()
    return ((jax.eval_shape(lambda: jax.random.key(0)), jax.ShapeDtypeStruct((), jnp.int32)),)


@pytest.mark.parametrize("compute_type", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", sorted(test_lm_scopes.KINDS))
def test_one_logits_matmul_a_chunk_and_head_call_in_every_kinds_step(kind, compute_type):
    """``train.program``'s ``head_logit_matmuls``, read off the step as traced:
    one ``[chunk, d] @ [d, V]`` a head call, the module's head call included
    (``joyai``: two calls, two matmuls)."""
    cfg = test_lm_scopes.KINDS[kind][0]
    step, *shapes = _traced_step(cfg, compute_type)
    assert decoder_lm._traced_counts(step, *shapes, cfg, *_noise(cfg))["head_logit_matmuls"] == 1
    jaxpr = step.trace(*shapes, jax.ShapeDtypeStruct((), jnp.int32), *_noise(cfg)).jaxpr.jaxpr
    assert decoder_lm._head_logit_matmuls(jaxpr, cfg.vocab) == 1 + cfg.mtp_depth


def test_the_count_tells_a_head_that_computes_its_logits_again(monkeypatch):
    """The same count on a step whose head is the parent's (the chunk's forward
    under ``jax.checkpoint``, AD's backward): two."""
    def bound(h, lm_head, tok, cd):
        def score(weight=None):
            with jax.named_scope("lm.head"):
                nll = _parents_nll(h, lm_head, tok, cd)
                return nll if weight is None else (jnp.sum(weight * nll), jax.lax.stop_gradient(nll))
        return score

    cfg = test_lm_scopes.KINDS["olmoe_stacked"][0]
    decoder_lm._train_program.cache_clear()
    monkeypatch.setattr(decoder_lm, "_next_token_nll", bound)
    try:
        step, *shapes = _traced_step(cfg)
        assert decoder_lm._traced_counts(step, *shapes, cfg)["head_logit_matmuls"] == 2
    finally:
        decoder_lm._train_program.cache_clear()
