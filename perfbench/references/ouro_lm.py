"""The benchmark's own plain reference of the Ouro-2.6B looped decoder LM as
the ``ouro_2_6b`` configuration cuts it: the head of a fit job - the first
AdamW step's loss, per-pass losses, gradient norms and update, and the second
step's loss - in ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``. It imports nothing of the
program: the equations are written again here.

Origin of each equation: [c] the model's ``config.json``
(https://huggingface.co/ByteDance/Ouro-2.6B, ``model_type`` ``ouro``); [p] the
published description from memory ("Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741); [a] assumed, and listed under the
configuration's ``assumed``. Matrices map ``x @ W``.

- Layer on ``x [T, d]``: ``a = RMSNorm_1(x)``; ``q, k, v = a Wq, a Wk, a Wv``,
  16 heads of 128 [c], no bias, no QK-norm [a]; rotate-half RoPE on the whole
  head, theta 1e6 [c]; causal ``softmax(q k^T / sqrt(128)) v``; ``x <- x +
  RMSNorm_2((.) Wo)``; ``m = RMSNorm_3(x)``; ``x <- x + RMSNorm_4((silu(m Wg) *
  (m Wu)) Wd)``: the sandwich, four norm weights a layer [p, a].
- Stack: ``h_0 = embed[tok]``; ``h_r = RMSNorm_final(layers(h_(r-1)))`` for
  ``r = 1 .. total_ut_steps`` [c], the same leaves every pass, the normed
  state feeding the next [a]; ``lambda_r = sigmoid(h_r w_e + b_e)`` [a].
- ``p_r = lambda_r prod_(j<r) (1 - lambda_j)`` for ``r < R``, ``p_R =
  prod_(j<R) (1 - lambda_j)`` [p]. Loss: the mean over target positions of
  ``sum_r p_r nll_r - beta H(p)``, ``nll_r`` the next-token cross-entropy of
  ``h_r`` through the one untied ``lm_head`` [c], ``beta`` 0.1 [a].

Plain means ``[heads, q, T]`` scores with a causal mask, every pass's logits
through the one head, and the stack as an untied chain of ``R x n`` layer
applications and ``R`` final norms that happen to read the same leaves: no scan,
no loop primitive over the passes. What is blocked, so that it fits beside 2.0
GB of weights and 2.0 GB of summed gradients, and so that what compiles is one
layer and not twenty-four: one sequence at a time (nothing couples the
sequences: the loss is a sum over positions); the chain a piece at a time (one
layer application, the final norm, the objective over the four states), each
piece's gradient ``jax.vjp``'s from the application's held input, and the chain
rule between the pieces written out, so that a leaf's gradient is, visibly, the
sum over the applications that read it; each block of 1,024 query positions and
each block of 1,024 positions of each pass's head rematerialised in the
backward; AdamW's first step from zero moments needs no moment storage.

``precision="bf16"`` is the control, one precision below what the
configuration states: weights, activations, the gate, the softmaxes - the
head's log-softmax and the exit distribution among them - and every
accumulator's result (a matmul's, a sum's over positions, a leaf's gradient
over the applications of one sequence, the embedding's scatter) in bfloat16;
only the sum over the step's sequences is float32. It must fail the limits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.95, 1e-8
Q_BLOCK = 1024

_LAYER_LEAVES = (
    ("attn_norm", "d", 1.0), ("wq", "da", None), ("wk", "da", None), ("wv", "da", None), ("wo", "ad", None),
    ("attn_out_norm", "d", 1.0), ("ffn_norm", "d", 1.0), ("w_gate", "dh", None), ("w_up", "dh", None),
    ("w_down", "hd", None), ("ffn_out_norm", "d", 1.0),
)


def leaf_table(dims: dict) -> list:
    """``(name, shape, start)`` of every parameter in the order the
    configuration's ``init`` numbers them; ``start`` is 1.0, 0.0 or None
    (``init_std * normal``)."""
    size = {"d": dims["hidden_size"], "a": dims["num_attention_heads"] * dims["head_dim"],
            "h": dims["intermediate_size"]}
    out = [("embed", (dims["vocab_size"], size["d"]), None)]
    for i in range(dims["num_hidden_layers"]):
        out += [(f"layers.{i}.{name}", tuple(size[a] for a in axes), start) for name, axes, start in _LAYER_LEAVES]
    out += [("final_norm", (size["d"],), 1.0), ("lm_head", (size["d"], dims["vocab_size"]), None),
            ("exit_gate_w", (size["d"], 1), None), ("exit_gate_b", (1,), 0.0)]
    return out


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _init_leaf(key, i, shape, std):
    return std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)


def init_params(dims: dict, seed: int, std: float) -> dict:
    """The configuration's ``init`` rule, a flat dict by name."""
    key = jax.random.key(seed)
    return {
        name: _init_leaf(key, i, shape, std) if start is None else jnp.full(shape, start, jnp.float32)
        for i, (name, shape, start) in enumerate(leaf_table(dims))
    }


# -- the equations ---------------------------------------------------------------


def _rms_norm(x, w, eps):
    """``w * x / sqrt(mean(x^2) + eps)``."""
    return w * (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps))


def _rope(x, theta):
    """Rotate-half RoPE on ``x [T, H, D]``: ``x * cos + cat(-x2, x1) * sin``,
    angles ``t * theta^(-2i/D)`` repeated over the two halves."""
    t, _, d = x.shape
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    freqs = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    cos, sin = jnp.cos(emb).astype(x.dtype), jnp.sin(emb).astype(x.dtype)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _layer(x, w, dims):
    """One sandwich-norm layer on ``x [T, d]``; ``w`` its eleven leaves by name."""
    t = x.shape[0]
    h, hd, eps = dims["num_attention_heads"], dims["head_dim"], dims["rms_norm_eps"]
    a = _rms_norm(x, w["attn_norm"], eps)
    q = _rope((a @ w["wq"]).reshape(t, h, hd), dims["rope_theta"])
    k = _rope((a @ w["wk"]).reshape(t, h, hd), dims["rope_theta"])
    v = (a @ w["wv"]).reshape(t, h, hd)
    qb = min(Q_BLOCK, t)

    @jax.checkpoint
    def block(args):  # the query positions of one block against every key
        q_blk, pos = args
        s = jnp.einsum("qhd,khd->hqk", q_blk, k) * (hd ** -0.5)
        s = jnp.where((pos[:, None] >= jnp.arange(t)[None, :])[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, (q.reshape(t // qb, qb, h, hd), jnp.arange(t).reshape(t // qb, qb)))
    x = x + _rms_norm(o.reshape(t, h * hd) @ w["wo"], w["attn_out_norm"], eps)
    m = _rms_norm(x, w["ffn_norm"], eps)
    y = (jax.nn.silu(m @ w["w_gate"]) * (m @ w["w_up"])) @ w["w_down"]
    return x + _rms_norm(y, w["ffn_out_norm"], eps)


def _pass_nll(hidden, lm_head, targets):
    """``[T]``: minus the log-probability of each position's target through the head."""
    t = hidden.shape[0]
    qb = min(Q_BLOCK, t)

    @jax.checkpoint
    def block(args):
        h_blk, t_blk = args
        logp = jax.nn.log_softmax(h_blk @ lm_head, axis=-1)
        return -jnp.take_along_axis(logp, t_blk[:, None], axis=1)[:, 0]

    return jax.lax.map(block, (hidden.reshape(t // qb, qb, -1), targets.reshape(t // qb, qb))).reshape(t)


def _objective(tail, states, tok, dims):
    """From the passes' normed states ``[R, T, d]`` of one sequence: over its
    ``T - 1`` target positions the summed ``sum_r p_r nll_r - beta H(p)`` and
    each pass's own summed cross-entropy ``[R]``. ``tail``: the head and the gate."""
    targets = jnp.concatenate([tok[1:], tok[:1]])  # the last position has no target
    nll = [_pass_nll(h, tail["lm_head"], targets)[:-1] for h in states]
    lam = [jax.nn.sigmoid((h @ tail["exit_gate_w"])[:-1, 0] + tail["exit_gate_b"][0]) for h in states]
    prob, stayed = [], jnp.ones_like(lam[0])
    for lam_r in lam[:-1]:
        prob.append(lam_r * stayed)
        stayed = stayed * (1.0 - lam_r)
    prob.append(stayed)  # the last pass takes what is left
    expected = sum(p_r * nll_r for p_r, nll_r in zip(prob, nll))
    entropy = -sum(p_r * jnp.log(p_r) for p_r in prob)
    return jnp.sum(expected - dims["exit_entropy_coef"] * entropy), jnp.stack([jnp.sum(n) for n in nll])


# -- one sequence, a piece at a time -----------------------------------------------
# The stack is an untied chain of R x n layer applications and R final norms that
# happen to read the same leaves. Each piece below is one jitted program (a layer,
# the final norm, the objective over the R states), its gradient is jax.vjp's, and
# the chain rule between the pieces is written out: a leaf's gradient is the sum
# over the applications that read it. What is held for the backward is each
# application's input, ``R x (n + 1)`` arrays of ``[T, d]``.


def _cast(tree, dtype):
    return jax.tree_util.tree_map(lambda a: a.astype(dtype), tree)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer_fwd(w, x, dims_items, dtype):
    with jax.default_matmul_precision("highest"):
        return _layer(x.astype(dtype), _cast(w, dtype), dict(dims_items))


@functools.partial(jax.jit, static_argnums=(4, 5), donate_argnums=(0,))
def _layer_bwd(acc, w, x, ct, dims_items, dtype):
    """``(acc + dw, dx)`` of one application at input ``x`` under cotangent ``ct``."""
    with jax.default_matmul_precision("highest"):
        _, vjp = jax.vjp(lambda w32, x32: _layer(x32.astype(dtype), _cast(w32, dtype), dict(dims_items)), w, x)
        dw, dx = vjp(ct)
    return {k: acc[k] + dw[k].astype(acc[k].dtype) for k in acc}, dx


@functools.partial(jax.jit, static_argnums=(2, 3))
def _norm_fwd(w, x, eps, dtype):
    return _rms_norm(x.astype(dtype), w.astype(dtype), eps)


@functools.partial(jax.jit, static_argnums=(4, 5), donate_argnums=(0,))
def _norm_bwd(acc, w, x, ct, eps, dtype):
    _, vjp = jax.vjp(lambda w32, x32: _rms_norm(x32.astype(dtype), w32.astype(dtype), eps), w, x)
    dw, dx = vjp(ct)
    return acc + dw.astype(acc.dtype), dx


@functools.partial(jax.jit, static_argnums=(3, 4))
def _objective_fwd(tail, states, tok, dims_items, dtype):
    with jax.default_matmul_precision("highest"):
        return _objective(_cast(tail, dtype), states, tok, dict(dims_items))


@functools.partial(jax.jit, static_argnums=(4, 5, 6), donate_argnums=(0,))
def _objective_bwd(acc, tail, states, tok, dims_items, dtype, scale):
    """``(acc + d tail, d states, sums)`` of ``scale x`` the sequence's objective."""
    def scaled(tail32, states):
        total, trips = _objective(_cast(tail32, dtype), states, tok, dict(dims_items))
        return total.astype(jnp.float32) * scale, (total, trips)

    with jax.default_matmul_precision("highest"):
        (d_tail, d_states), sums = jax.grad(scaled, argnums=(0, 1), has_aux=True)(tail, states)
    return {k: acc[k] + d_tail[k].astype(acc[k].dtype) for k in acc}, d_states, sums


@functools.partial(jax.jit, donate_argnums=(0,))
def _embed_bwd(acc, tok, ct):
    return acc.at[tok].add(ct.astype(acc.dtype))


_TAIL = ("lm_head", "exit_gate_w", "exit_gate_b")


def _layer_leaves(tree, i):
    return {name: tree[f"layers.{i}.{name}"] for name, _, _ in _LAYER_LEAVES}


def _forward(p, tok, dims, dims_items, dtype):
    """The chain's inputs, application by application: per pass the input of
    each layer and of the final norm; and the passes' normed states ``[R, T, d]``."""
    x = p["embed"][tok].astype(dtype)
    held, states = [], []
    for _ in range(dims["total_ut_steps"]):
        inputs = []
        for i in range(dims["num_hidden_layers"]):
            inputs.append(x)
            x = _layer_fwd(_layer_leaves(p, i), x, dims_items, dtype)
        inputs.append(x)
        x = _norm_fwd(p["final_norm"], x, dims["rms_norm_eps"], dtype)
        held.append(inputs)
        states.append(x)
    return held, jnp.stack(states)


def _sequence_sums(p, tok, dims, dims_items, dtype):
    _, states = _forward(p, tok, dims, dims_items, dtype)
    return _objective_fwd({k: p[k] for k in _TAIL}, states, tok, dims_items, dtype)


def _add_sequence_grads(acc, p, tok, dims, dims_items, dtype, scale):
    """``acc + d/dp [objective_sum(tok) * scale]`` (in place, leaf by leaf), and
    the sequence's sums. The sequence's own gradient is summed over the
    applications in ``dtype`` (float32, or the control's bfloat16: every
    accumulator's result in the precision under test) and joins ``acc`` whole."""
    held, states = _forward(p, tok, dims, dims_items, dtype)
    own = {k: jnp.zeros(v.shape, dtype) for k, v in p.items()}
    tail, d_states, sums = _objective_bwd({k: own[k] for k in _TAIL}, {k: p[k] for k in _TAIL}, states, tok,
                                          dims_items, dtype, scale)
    own.update(tail)
    ct = jnp.zeros_like(d_states[0])  # nothing reads the last pass's state but the head and the gate
    for inputs, d_state in zip(reversed(held), reversed(d_states)):
        own["final_norm"], ct = _norm_bwd(own["final_norm"], p["final_norm"], inputs[-1], ct + d_state,
                                          dims["rms_norm_eps"], dtype)
        for i in reversed(range(dims["num_hidden_layers"])):
            layer, ct = _layer_bwd(_layer_leaves(own, i), _layer_leaves(p, i), inputs[i], ct, dims_items, dtype)
            own.update({f"layers.{i}.{name}": g for name, g in layer.items()})
    own["embed"] = _embed_bwd(own["embed"], tok, ct)
    for k in list(own):
        acc[k] = acc[k] + own.pop(k).astype(jnp.float32)
    return sums


def _mean(sums, b, t):
    total = sum(s[0].astype(jnp.float32) for s in sums) / (b * (t - 1))
    trips = sum(s[1].astype(jnp.float32) for s in sums) / (b * (t - 1))
    return float(total), [float(x) for x in trips]


def head_of_job(dims: dict, hyper: dict, seed: int, batches, precision: str = "f32") -> dict:
    """The first two steps' losses, and the first step's per-pass losses and
    gradient norms (global and per parameter), for ``batches`` (two ``[B, T]``
    int arrays) from the configuration's initial weights."""
    dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16}[precision]
    dims_items = tuple(sorted((k, v) for k, v in dims.items() if isinstance(v, (int, float))))
    p = init_params(dims, seed, hyper["init_std"])
    b, t = batches[0].shape

    grads = {k: jnp.zeros_like(v) for k, v in p.items()}
    sums = [_add_sequence_grads(grads, p, jnp.asarray(row), dims, dims_items, dtype, 1.0 / (b * (t - 1)))
            for row in batches[0]]
    loss1, trips1 = _mean(sums, b, t)
    group = {k: float(jnp.sqrt(jnp.sum(g * g))) for k, g in grads.items()}
    norm = float(np.sqrt(sum(v * v for v in group.values())))

    # clip at the global norm, then AdamW's first step from zero moments
    scale = hyper["clip_norm"] / max(norm, hyper["clip_norm"])
    lr, wd = hyper["learning_rate"], hyper["weight_decay"]
    for k in list(p):
        g = grads.pop(k) * scale
        m_hat = ((1.0 - ADAM_B1) * g) / (1.0 - ADAM_B1)
        v_hat = ((1.0 - ADAM_B2) * g * g) / (1.0 - ADAM_B2)
        p[k] = p[k] - lr * (m_hat / (jnp.sqrt(v_hat) + ADAM_EPS) + wd * p[k])
    loss2, _ = _mean([_sequence_sums(p, jnp.asarray(row), dims, dims_items, dtype) for row in batches[1]], b, t)
    return {
        "losses": [loss1, loss2],
        "trip_losses": trips1,
        "grad_norms": [norm],
        "group_norms": group,
    }
