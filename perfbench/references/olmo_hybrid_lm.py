"""The benchmark's own plain reference of the Olmo-Hybrid-7B decoder as the
``olmo_hybrid_7b`` configuration cuts it: the head of a fit job - the first
AdamW step's loss, gradient norms and update, and the second step's loss - in
``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``. It
imports nothing of the program: the equations are written again here.

Origin of each equation: [c] the model's ``config.json``
(https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json,
``model_type`` ``olmo_hybrid``); [p] the layer's paper (Gated Delta Networks,
arXiv:2412.06464 section 3), whose layer the file's ``linear_*`` keys name; [a]
assumed, and listed under the configuration's ``assumed``. Matrices map ``x @
W``. Layer ``i`` is ``x <- x + RMSNorm(mixer_i(x))`` then ``x <- x +
RMSNorm(ffn(x))``, eps ``rms_norm_eps`` 1e-6: no norm before a sublayer, each
one's output normed before it joins [a: the Olmo 2/3 family's reordered norm];
the mixer is ``layer_types[i]`` [c].

- ``linear_attention`` (``H`` heads HELD of the published 30, 96 key and 192
  value channels, 4 taps) [c]: ``q, k, v = silu(conv4(x Wq)), silu(conv4(x
  Wk)), silu(conv4(x Wv))``, a causal depthwise convolution, no bias, zeros
  before the sequence, the last tap reads the position itself [p, a]; ``q_t <-
  q_t / |q_t| / sqrt(96)``, ``k_t <- k_t / |k_t|`` a head, ``|z| = sqrt(sum
  z^2 + 1e-6)`` [p, a]; ``g_t = -exp(A_log[h]) softplus(x_t Wa + dt_bias)[h]``,
  ONE log-decay a head and position [p, a]; ``beta_t = 2 sigmoid(x_t Wb)[h]``
  (``linear_allow_neg_eigval``) [c]; the recurrence, ONE POSITION AT A TIME:
  ``S_t = exp(g_t) S_(t-1) + beta_t k_t (v_t - (exp(g_t) S_(t-1))^T k_t)^T``,
  ``o_t = S_t^T q_t``, ``S`` ``[96 x 192]`` zero at the sequence's start [p];
  ``y_t = (RMSNorm_192(o_t) * o_norm * silu(x_t Wg)) Wo`` [p, a].
- ``full_attention``: ``q, k, v = x Wq, x Wk, x Wv`` on the held heads of 128,
  no biases [c]; ``RMSNorm`` over the whole (held) q and k projections before
  the heads are split [a: Olmo 2/3's QK-norm]; NO position encoding
  (``rope_parameters.rope_theta`` null) [a]; causal softmax at ``128^-1/2``.
- Feed-forward: ``down(silu(gate(x)) * up(x))``, width 11,008 [c].
- Head: final RMSNorm, logits over the held slice of the untied head [c]; mean
  next-token cross-entropy.

The heads held here are a SHARE of each layer's (the configuration's
``stands_for``): ``Wo``'s output is the held heads' part of the layer's sum and
is normed as it is, and the QK-norm's mean square is over the held channels;
nothing stands in for the absent chip, here as in the program.

Plain means the recurrence position by position, ``[heads, q, T]`` scores with
the mask, ``jax.grad``. What is blocked, so that it fits beside 3.1 GB of
weights and 3.1 GB of summed gradients: one sequence at a time (nothing
couples the sequences); each layer, each block of 64 positions of the
recurrence (a backward through 8,192 states of 1.1 MB would hold 9 GB a
layer), each block of 512 query positions, each block of 1,024 positions of
the feed-forward and of the head rematerialised in the backward; AdamW's first
step from zero moments needs no moment storage.

``precision="bf16"`` is the control, one precision below what the
configuration states: weights, activations, softmaxes, the rule's decays, unit
vectors and state, and every accumulator's result in bfloat16. It must fail
the limits.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.95, 1e-8
Q_BLOCK, ROW_BLOCK, RULE_BLOCK = 512, 1024, 64
A_RANGE = (1.0, 16.0)
UNIT_EPS = 1e-6


def attending(dims: dict) -> list:
    """The layers that attend, of those that run: ``layer_types``' ``full_attention`` under ``num_hidden_layers``."""
    return [i for i, kind in enumerate(dims["layer_types"][: dims["num_hidden_layers"]]) if kind == "full_attention"]


def leaf_table(dims: dict) -> list:
    """``(name, shape, start)`` of every parameter in the order the
    configuration's ``init`` numbers them; ``start`` is 1.0 (a norm's weight),
    None (``init_std * normal``), ``"dt_bias"`` or ``"a_log"``."""
    d, heads, taps = dims["hidden_size"], dims["linear_num_key_heads"], dims["linear_conv_kernel_dim"]
    keys, values = heads * dims["linear_key_head_dim"], heads * dims["linear_value_head_dim"]
    head_dim = dims["head_dim"]
    a, kv = dims["num_attention_heads"] * head_dim, dims["num_key_value_heads"] * head_dim
    width = dims["intermediate_size"]
    out = [("embed", (dims["vocab_size"], d), None)]
    for i in range(dims["num_hidden_layers"]):
        if i in attending(dims):
            layer = [("wq", (d, a), None), ("wk", (d, kv), None), ("wv", (d, kv), None), ("wo", (a, d), None),
                     ("q_norm", (a,), 1.0), ("k_norm", (kv,), 1.0)]
        else:
            layer = [("wq", (d, keys), None), ("wk", (d, keys), None), ("wv", (d, values), None),
                     ("conv_q", (taps, keys), None), ("conv_k", (taps, keys), None), ("conv_v", (taps, values), None),
                     ("Wa", (d, heads), None), ("A_log", (heads,), "a_log"), ("dt_bias", (heads,), "dt_bias"),
                     ("Wb", (d, heads), None), ("wg", (d, values), None),
                     ("o_norm", (dims["linear_value_head_dim"],), 1.0), ("wo", (values, d), None)]
        layer += [("attn_out_norm", (d,), 1.0), ("w_gate", (d, width), None), ("w_up", (d, width), None),
                  ("w_down", (width, d), None), ("ffn_out_norm", (d,), 1.0)]
        out += [(f"layers.{i}.{name}", shape, start) for name, shape, start in layer]
    return out + [("final_norm", (d,), 1.0), ("lm_head", (d, dims["vocab_size"]), None)]


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal_leaf(key, i, shape, std):
    return std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _uniform_leaf(key, i, shape):
    return jax.random.uniform(jax.random.fold_in(key, i), shape, jnp.float32)


def init_params(dims: dict, seed: int, std: float) -> dict:
    key = jax.random.key(seed)
    lo, hi, floor = dims["time_step_min"], dims["time_step_max"], dims["time_step_floor"]
    out = {}
    for i, (name, shape, start) in enumerate(leaf_table(dims)):
        if start is None:
            out[name] = _normal_leaf(key, i, shape, std)
        elif start == "a_log":
            out[name] = jnp.log(A_RANGE[0] + _uniform_leaf(key, i, shape) * (A_RANGE[1] - A_RANGE[0]))
        elif start == "dt_bias":  # the inverse softplus of a log-uniform step size, floored
            dt = jnp.maximum(jnp.exp(math.log(lo) + _uniform_leaf(key, i, shape) * math.log(hi / lo)), floor)
            out[name] = dt + jnp.log(-jnp.expm1(-dt))
        else:
            out[name] = jnp.full(shape, start, jnp.float32)
    return out


# -- the equations ---------------------------------------------------------------


def _rms_norm(x, w, eps):
    return w * (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + jnp.asarray(eps, x.dtype)))


def recurrence(q, k, v, g, beta):
    """``o [T, H, D_v]`` of ``S_t = exp(g_t) S_(t-1) + beta_t k_t (v_t -
    (exp(g_t) S_(t-1))^T k_t)^T``, ``o_t = S_t^T q_t``, one position at a time
    from ``S = 0``: ``q``, ``k`` ``[T, H, D_k]``, ``v [T, H, D_v]``, ``g`` and
    ``beta`` ``[T, H]``. Blocks of ``RULE_BLOCK`` positions are rematerialised
    in the backward."""
    t, heads, dk = q.shape
    dv = v.shape[-1]

    def position(state, now):  # state [H, D_k, D_v]
        q_t, k_t, v_t, g_t, beta_t = now
        state = jnp.exp(g_t)[:, None, None] * state
        seen = jnp.sum(state * k_t[:, :, None], axis=1)  # S^T k: what the decayed state already says of this key
        state = state + (beta_t[:, None] * k_t)[:, :, None] * (v_t - seen)[:, None, :]
        return state, jnp.sum(state * q_t[:, :, None], axis=1)

    @jax.checkpoint
    def block(state, now):
        return jax.lax.scan(position, state, now)

    blk = min(RULE_BLOCK, t)
    blocks = tuple(m.reshape(t // blk, blk, *m.shape[1:]) for m in (q, k, v, g, beta))
    _, o = jax.lax.scan(block, jnp.zeros((heads, dk, dv), q.dtype), blocks)
    return o.reshape(t, heads, dv)


def _conv(z, w):
    taps, t = w.shape[0], z.shape[0]
    earlier = jnp.concatenate([jnp.zeros((taps - 1, z.shape[1]), z.dtype), z])
    return jax.nn.silu(sum(w[j] * earlier[j: j + t] for j in range(taps)))


def _unit(z):
    return z * jax.lax.rsqrt(jnp.sum(z * z, axis=-1, keepdims=True) + jnp.asarray(UNIT_EPS, z.dtype))


def _gated_delta(x, p, pre, dims):
    t = x.shape[0]
    heads, dk, dv = dims["linear_num_key_heads"], dims["linear_key_head_dim"], dims["linear_value_head_dim"]
    q = _conv(x @ p[pre + "wq"], p[pre + "conv_q"]).reshape(t, heads, dk)
    k = _conv(x @ p[pre + "wk"], p[pre + "conv_k"]).reshape(t, heads, dk)
    v = _conv(x @ p[pre + "wv"], p[pre + "conv_v"]).reshape(t, heads, dv)
    q, k = _unit(q) * jnp.asarray(dk ** -0.5, q.dtype), _unit(k)
    g = -jnp.exp(p[pre + "A_log"]) * jax.nn.softplus(x @ p[pre + "Wa"] + p[pre + "dt_bias"])  # [T, H]
    beta = jnp.asarray(2.0, x.dtype) * jax.nn.sigmoid(x @ p[pre + "Wb"])
    o = recurrence(q, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + jnp.asarray(dims["rms_norm_eps"], o.dtype))
    return ((o * p[pre + "o_norm"]).reshape(t, heads * dv) * jax.nn.silu(x @ p[pre + "wg"])) @ p[pre + "wo"]


def _attention(x, p, pre, dims):
    t = x.shape[0]
    heads, kv, d = dims["num_attention_heads"], dims["num_key_value_heads"], dims["head_dim"]
    group, eps = heads // kv, dims["rms_norm_eps"]
    q = _rms_norm(x @ p[pre + "wq"], p[pre + "q_norm"], eps).reshape(t, heads, d)
    k = _rms_norm(x @ p[pre + "wk"], p[pre + "k_norm"], eps).reshape(t, kv, d)
    v = (x @ p[pre + "wv"]).reshape(t, kv, d)
    qb = min(Q_BLOCK, t)

    @jax.checkpoint
    def block(args):  # the query positions of one block against every key
        q_blk, pos = args
        s = jnp.einsum("qjgd,kjd->jgqk", q_blk.reshape(qb, kv, group, d), k) * (d ** -0.5)
        s = jnp.where((pos[:, None] >= jnp.arange(t)[None, :])[None, None], s, -jnp.inf)
        return jnp.einsum("jgqk,kjd->qjgd", jax.nn.softmax(s, axis=-1), v).reshape(qb, heads, d)

    o = jax.lax.map(block, (q.reshape(t // qb, qb, heads, d), jnp.arange(t).reshape(t // qb, qb)))
    return o.reshape(t, heads * d) @ p[pre + "wo"]


def _swiglu(x, p, pre):
    t = x.shape[0]
    rb = min(ROW_BLOCK, t)

    @jax.checkpoint
    def block(rows):
        return (jax.nn.silu(rows @ p[pre + "w_gate"]) * (rows @ p[pre + "w_up"])) @ p[pre + "w_down"]

    return jax.lax.map(block, x.reshape(t // rb, rb, -1)).reshape(t, -1)


def _sequence(p, tok, dims):
    """One sequence ``tok [T]``: its summed next-token cross-entropy."""
    eps = dims["rms_norm_eps"]
    x = p["embed"][tok]

    def layer(x, pre, attends):
        mixed = _attention(x, p, pre, dims) if attends else _gated_delta(x, p, pre, dims)
        x = x + _rms_norm(mixed, p[pre + "attn_out_norm"], eps)
        return x + _rms_norm(_swiglu(x, p, pre), p[pre + "ffn_out_norm"], eps)

    for i in range(dims["num_hidden_layers"]):
        x = jax.checkpoint(layer, static_argnums=(1, 2))(x, f"layers.{i}.", i in attending(dims))
    hidden = _rms_norm(x, p["final_norm"], eps)
    t = tok.shape[0]
    hb = min(ROW_BLOCK, t)
    targets = jnp.concatenate([tok[1:], tok[:1]])  # the last position has no target
    head = p["lm_head"]

    @jax.checkpoint
    def block(args):
        h_blk, t_blk = args
        logp = jax.nn.log_softmax((h_blk @ head).astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, t_blk[:, None], axis=1)[:, 0]

    nll = jax.lax.map(block, (hidden.reshape(t // hb, hb, -1), targets.reshape(t // hb, hb)))
    return jnp.sum(nll.reshape(t)[:-1])


def _cast(p, dtype):
    return {k: v.astype(dtype) for k, v in p.items()}


class _Static:
    """The configuration's numbers as one hashable jit argument."""

    def __init__(self, dims: dict):
        self.dims = dims
        self.key = repr(sorted(dims.items()))

    def __hash__(self):
        return hash(self.key)

    def __eq__(self, other):
        return self.key == other.key


@functools.partial(jax.jit, static_argnums=(2, 3))
def _stats(p, tok, static, dtype):
    with jax.default_matmul_precision("highest"):
        return _sequence(_cast(p, dtype), tok, static.dims)


@functools.partial(jax.jit, static_argnums=(3, 4, 5), donate_argnums=(0,))
def _add_grads(acc, p, tok, static, dtype, scale):
    """``acc + d/dp [ce_sum(tok) * scale]``."""
    def objective(p32):
        return _sequence(_cast(p32, dtype), tok, static.dims).astype(jnp.float32) * scale

    with jax.default_matmul_precision("highest"):
        grads = jax.grad(objective)(p)
    return {k: acc[k] + grads[k].astype(jnp.float32) for k in acc}


def _step_loss(p, batch, static, dtype):
    b, t = batch.shape
    ce = 0.0
    for row in batch:
        ce = ce + _stats(p, jnp.asarray(row), static, dtype).astype(jnp.float32)
    return ce / (b * (t - 1))


def head_of_job(dims: dict, hyper: dict, seed: int, batches, precision: str = "f32") -> dict:
    """The first two steps' losses, and the first step's gradient norms (global
    and per parameter), for ``batches`` (two ``[B, T]`` int arrays) from the
    configuration's initial weights."""
    dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16}[precision]
    static = _Static(dims)
    p = init_params(dims, seed, hyper["init_std"])
    b, t = batches[0].shape

    loss1 = _step_loss(p, batches[0], static, dtype)
    grads = {k: jnp.zeros_like(v) for k, v in p.items()}
    for row in batches[0]:
        grads = _add_grads(grads, p, jnp.asarray(row), static, dtype, 1.0 / (b * (t - 1)))
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
    loss2 = _step_loss(p, batches[1], static, dtype)
    return {"losses": [float(loss1), float(loss2)], "grad_norms": [norm], "group_norms": group}
