"""The benchmark's own plain reference of the Solar-Open2-250B hybrid decoder
as the ``solar_open2_250b`` configuration cuts it: the head of a fit job - the
first AdamW step's loss, gradient norms and update, and the second step's loss
- in ``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``.
It imports nothing of the program: the equations are written again here.

Origin of each equation: [c] the model's ``config.json``
(https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json,
``model_type`` ``solar_open2``); [p] the family's paper (Kimi Delta Attention,
arXiv:2510.26692 section 3), which the file's ``kda_*`` keys name; [a]
assumed, and listed under the configuration's ``assumed``. Matrices map ``x @
W``. Layer ``i`` is ``x <- x + mixer_i(RMSNorm(x))`` then ``x <- x +
moe(RMSNorm(x))``, eps ``rms_norm_eps`` 1e-5 [c]; the mixer attends where ``i``
is in ``gqa_layers`` and runs the gated delta rule elsewhere [c].

- KDA (``H`` heads HELD of the published 64, 128 channels, 4 taps) [c]: ``q,
  k, v = silu(conv4(u Wq)), silu(conv4(u Wk)), silu(conv4(u Wv))``, a causal
  depthwise convolution, no bias, zeros before the sequence, the last tap
  reads the position itself [p, a]; ``q_t <- q_t / |q_t| / sqrt(128)``, ``k_t
  <- k_t / |k_t|`` a head, ``|z| = sqrt(sum z^2 + 1e-6)`` [p, a]; ``g_t =
  -exp(A_log[h]) softplus((u_t Fa) Fb + dt_bias)`` over the head's 128 key
  channels, ``Fa [4096, 128]``, ``Fb [128, H x 128]`` (``kda_use_full_proj``
  false: low rank) [p, a]; ``beta_t = 2 sigmoid(u_t Wb)[h]``
  (``kda_allow_neg_eigval``) [c]; the recurrence, ONE POSITION AT A TIME: ``S_t
  = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_(t-1) + beta_t k_t v_t^T``, ``o_t
  = S_t^T q_t``, ``S`` ``[128 x 128]`` zero at the sequence's start [p]; ``y_t
  = (RMSNorm_128(o_t) * o_norm * sigmoid((u_t Ga) Gb)) Wo`` [p, a].
- GQA: ``q = u Wq`` on the held query heads, ``k, v`` on the held key/value
  heads of 128, no biases; NO position encoding (``use_rope`` false) [c];
  causal softmax at ``128^-1/2``; ``y = (o * sigmoid(u Wg)) Wo``
  (``use_gqa_gate``) [c], the gate element-wise from the normed input [a].
- Experts: ``s = sigmoid(u Wr)`` over all 320 [a: the scoring function]; the 8
  largest of ``s + b``, ties to the lower id, ``b`` the correction bias at its
  initial 0 (its rule is outside the gradient and left out [a]); ``w = 1.0
  s_sel / sum(s_sel)`` [c]; ``x <- x + sum_held w_e SwiGLU_e(u) +
  SwiGLU_shared(u)`` with the experts held here (``first_expert_held .. +
  n_routed_experts`` of the published 320; what the others would add is left
  out) at width 1,280 and the shared expert whole at 1,280 [c, a].
- Head: final RMSNorm, logits over the held slice of the untied head [c]; mean
  next-token cross-entropy. No auxiliary loss [a].

The heads held here are a SHARE of each layer's (the configuration's
``stands_for``): ``Wo``'s output is the held heads' part of the layer's sum and
goes on as it is; nothing stands in for the absent chips, here as in the
program.

Plain means the recurrence position by position, ``[heads, q, T]`` scores with
the mask, every held expert on every token and masked, ``jax.grad``. What is
blocked, so that it fits beside 3.4 GB of weights and 3.4 GB of summed
gradients: one sequence at a time (nothing couples the sequences); each layer,
each block of 64 positions of the recurrence (a backward through 4,096 states
of 0.5 MB would hold 2 GB a layer), each expert's contribution, each block of
512 query positions and each block of 1,024 positions of the head
rematerialised in the backward; AdamW's first step from zero moments needs no
moment storage.

``precision="bf16"`` is the control, one precision below what the
configuration states: weights, activations, router, softmaxes, the rule's
decays, unit vectors and state, and every accumulator's result in bfloat16. It
must fail the limits.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.95, 1e-8
Q_BLOCK, HEAD_BLOCK, RULE_BLOCK = 512, 1024, 64
A_RANGE = (1.0, 16.0)
UNIT_EPS = 1e-6


def attending(dims: dict) -> list:
    """The layers that attend, of those that run: the published ``gqa_layers`` under ``num_hidden_layers``."""
    return [i for i in dims["gqa_layers"] if i < dims["num_hidden_layers"]]


def leaf_table(dims: dict) -> list:
    """``(name, shape, start)`` of every parameter in the order the
    configuration's ``init`` numbers them; ``start`` is 1.0, 0.0, None
    (``init_std * normal``), ``"dt_bias"`` or ``"a_log"``."""
    d, delta = dims["hidden_size"], dims["linear_attn_config"]
    heads, hd, taps = delta["num_heads"], delta["head_dim"], delta["short_conv_kernel_size"]
    inner = heads * hd
    a, kv = dims["num_attention_heads"] * dims["head_dim"], dims["num_key_value_heads"] * dims["head_dim"]
    held, width, routed = dims["n_routed_experts"], dims["moe_intermediate_size"], dims["n_routed_experts_published"]
    shared = dims["n_shared_experts"] * width
    out = [("embed", (dims["vocab_size"], d), None)]
    for i in range(dims["num_hidden_layers"]):
        layer = [("attn_norm", (d,), 1.0)]
        if i in attending(dims):
            layer += [("wq", (d, a), None), ("wk", (d, kv), None), ("wv", (d, kv), None), ("wg", (d, a), None),
                      ("wo", (a, d), None)]
        else:
            layer += [("wq", (d, inner), None), ("wk", (d, inner), None), ("wv", (d, inner), None),
                      ("conv_q", (taps, inner), None), ("conv_k", (taps, inner), None), ("conv_v", (taps, inner), None),
                      ("Fa", (d, hd), None), ("Fb", (hd, inner), None), ("A_log", (heads,), "a_log"),
                      ("dt_bias", (inner,), "dt_bias"), ("Wb", (d, heads), None), ("Ga", (d, hd), None),
                      ("Gb", (hd, inner), None), ("o_norm", (hd,), 1.0), ("wo", (inner, d), None)]
        layer += [("ffn_norm", (d,), 1.0), ("router", (d, routed), None), ("router_bias", (routed,), 0.0),
                  ("shared_gate", (d, shared), None), ("shared_up", (d, shared), None),
                  ("shared_down", (shared, d), None), ("w_gate", (held, d, width), None),
                  ("w_up", (held, d, width), None), ("w_down", (held, width, d), None)]
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
    """``o [T, H, D]`` of ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_(t-1)
    + beta_t k_t v_t^T``, ``o_t = S_t^T q_t``, one position at a time from ``S
    = 0``: ``q``, ``k``, ``v``, ``g`` ``[T, H, D]``, ``beta [T, H]``. Blocks of
    ``RULE_BLOCK`` positions are rematerialised in the backward."""
    t, heads, d = q.shape

    def position(state, now):  # state [H, D_k, D_v]
        q_t, k_t, v_t, g_t, beta_t = now
        state = jnp.exp(g_t)[:, :, None] * state
        seen = jnp.sum(state * k_t[:, :, None], axis=1)  # S^T k: what the state already says of this key
        state = state + (beta_t[:, None] * k_t)[:, :, None] * (v_t - seen)[:, None, :]
        return state, jnp.sum(state * q_t[:, :, None], axis=1)

    @jax.checkpoint
    def block(state, now):
        return jax.lax.scan(position, state, now)

    blk = min(RULE_BLOCK, t)
    blocks = tuple(m.reshape(t // blk, blk, *m.shape[1:]) for m in (q, k, v, g, beta))
    _, o = jax.lax.scan(block, jnp.zeros((heads, d, d), q.dtype), blocks)
    return o.reshape(t, heads, d)


def _conv(z, w):
    taps, t = w.shape[0], z.shape[0]
    earlier = jnp.concatenate([jnp.zeros((taps - 1, z.shape[1]), z.dtype), z])
    return jax.nn.silu(sum(w[j] * earlier[j: j + t] for j in range(taps)))


def _unit(z):
    return z * jax.lax.rsqrt(jnp.sum(z * z, axis=-1, keepdims=True) + jnp.asarray(UNIT_EPS, z.dtype))


def _kda(u, p, pre, dims):
    t = u.shape[0]
    delta = dims["linear_attn_config"]
    heads, d = delta["num_heads"], delta["head_dim"]
    q, k, v = (_conv(u @ p[pre + proj], p[pre + taps]).reshape(t, heads, d)
               for proj, taps in (("wq", "conv_q"), ("wk", "conv_k"), ("wv", "conv_v")))
    q, k = _unit(q) * jnp.asarray(d ** -0.5, q.dtype), _unit(k)
    dt = jax.nn.softplus((u @ p[pre + "Fa"]) @ p[pre + "Fb"] + p[pre + "dt_bias"]).reshape(t, heads, d)
    g = -jnp.exp(p[pre + "A_log"])[:, None] * dt
    beta = jnp.asarray(2.0, u.dtype) * jax.nn.sigmoid(u @ p[pre + "Wb"])
    o = recurrence(q, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + jnp.asarray(dims["rms_norm_eps"], o.dtype))
    gate = jax.nn.sigmoid((u @ p[pre + "Ga"]) @ p[pre + "Gb"])
    return ((o * p[pre + "o_norm"]).reshape(t, heads * d) * gate) @ p[pre + "wo"]


def _attention(u, p, pre, dims):
    t = u.shape[0]
    heads, kv, d = dims["num_attention_heads"], dims["num_key_value_heads"], dims["head_dim"]
    group = heads // kv
    q = (u @ p[pre + "wq"]).reshape(t, heads, d)
    k = (u @ p[pre + "wk"]).reshape(t, kv, d)
    v = (u @ p[pre + "wv"]).reshape(t, kv, d)
    qb = min(Q_BLOCK, t)

    @jax.checkpoint
    def block(args):  # the query positions of one block against every key
        q_blk, pos = args
        s = jnp.einsum("qjgd,kjd->jgqk", q_blk.reshape(qb, kv, group, d), k) * (d ** -0.5)
        s = jnp.where((pos[:, None] >= jnp.arange(t)[None, :])[None, None], s, -jnp.inf)
        return jnp.einsum("jgqk,kjd->qjgd", jax.nn.softmax(s, axis=-1), v).reshape(qb, heads, d)

    o = jax.lax.map(block, (q.reshape(t // qb, qb, heads, d), jnp.arange(t).reshape(t // qb, qb)))
    return (o.reshape(t, heads * d) * jax.nn.sigmoid(u @ p[pre + "wg"])) @ p[pre + "wo"]


def _swiglu(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def _experts(u, p, pre, dims):
    """Every held expert on every token, the unchosen masked. Returns the
    routed part and the chosen experts ``[T, k]``."""
    s = jax.nn.sigmoid(u @ p[pre + "router"])
    _, chosen = jax.lax.top_k(s + p[pre + "router_bias"], dims["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=1)
    gates = jnp.asarray(dims["routed_scaling_factor"], s.dtype) * picked / jnp.sum(picked, axis=1, keepdims=True)
    held = dims["first_expert_held"] + jnp.arange(dims["n_routed_experts"])
    weight = jnp.sum(jnp.where(chosen[None, :, :] == held[:, None, None], gates[None], jnp.zeros((), gates.dtype)),
                     axis=2)  # [held, T]

    @jax.checkpoint
    def contribution(w_e, gate, up, down):
        return w_e[:, None] * _swiglu(u, gate, up, down)

    def body(y, xs):
        return y + contribution(*xs), None

    y, _ = jax.lax.scan(body, jnp.zeros_like(u), (weight, p[pre + "w_gate"], p[pre + "w_up"], p[pre + "w_down"]))
    return y, chosen


def _sequence(p, tok, dims):
    """One sequence ``tok [T]``: its summed next-token cross-entropy and, per
    layer, how many (token, slot) choices fell on each of the router's experts."""
    eps = dims["rms_norm_eps"]
    x = p["embed"][tok]
    counts = []

    def layer(x, pre, attends):
        u = _rms_norm(x, p[pre + "attn_norm"], eps)
        x = x + (_attention(u, p, pre, dims) if attends else _kda(u, p, pre, dims))
        u = _rms_norm(x, p[pre + "ffn_norm"], eps)
        y, chosen = _experts(u, p, pre, dims)
        return x + y + _swiglu(u, p[pre + "shared_gate"], p[pre + "shared_up"], p[pre + "shared_down"]), chosen

    for i in range(dims["num_hidden_layers"]):
        x, chosen = jax.checkpoint(layer, static_argnums=(1, 2))(x, f"layers.{i}.", i in attending(dims))
        counts.append(jnp.zeros((dims["n_routed_experts_published"],), jnp.int32).at[chosen.reshape(-1)].add(1))
    hidden = _rms_norm(x, p["final_norm"], eps)
    t = tok.shape[0]
    hb = min(HEAD_BLOCK, t)
    targets = jnp.concatenate([tok[1:], tok[:1]])  # the last position has no target
    head = p["lm_head"]

    @jax.checkpoint
    def block(args):
        h_blk, t_blk = args
        logp = jax.nn.log_softmax((h_blk @ head).astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, t_blk[:, None], axis=1)[:, 0]

    nll = jax.lax.map(block, (hidden.reshape(t // hb, hb, -1), targets.reshape(t // hb, hb)))
    return jnp.sum(nll.reshape(t)[:-1]), jnp.stack(counts)


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
        ce_sum, _ = _sequence(_cast(p32, dtype), tok, static.dims)
        return ce_sum.astype(jnp.float32) * scale

    with jax.default_matmul_precision("highest"):
        grads = jax.grad(objective)(p)
    return {k: acc[k] + grads[k].astype(jnp.float32) for k in acc}


def _step_loss(p, batch, static, dtype):
    b, t = batch.shape
    ce, counts = 0.0, 0
    for row in batch:
        ce_s, c_s = _stats(p, jnp.asarray(row), static, dtype)
        ce = ce + ce_s.astype(jnp.float32)
        counts = counts + c_s
    return ce / (b * (t - 1)), counts


def head_of_job(dims: dict, hyper: dict, seed: int, batches, precision: str = "f32") -> dict:
    """The first two steps' losses, and the first step's gradient norms (global
    and per parameter) and expert loads ``[layers, published experts]``, for
    ``batches`` (two ``[B, T]`` int arrays) from the configuration's initial
    weights."""
    dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16}[precision]
    static = _Static(dims)
    p = init_params(dims, seed, hyper["init_std"])
    b, t = batches[0].shape

    loss1, counts1 = _step_loss(p, batches[0], static, dtype)
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
    loss2, _ = _step_loss(p, batches[1], static, dtype)
    return {
        "losses": [float(loss1), float(loss2)],
        "grad_norms": [norm],
        "group_norms": group,
        "expert_rows": np.asarray(counts1),
    }
