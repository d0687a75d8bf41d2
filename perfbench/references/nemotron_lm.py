"""The benchmark's own plain reference of the Nemotron-3-Nano hybrid decoder
as the ``nemotron3_nano_30b`` configuration cuts it: the head of a fit job -
the first AdamW step's loss, gradient norms and update, and the second step's
loss - in ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``. It imports nothing of the
program: the equations are written again here.

Origin of each equation: [c] the model's ``config.json``
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json,
``model_type`` ``nemotron_h``); [a] assumed, and listed under the
configuration's ``assumed``. Matrices map ``x @ W``. Layer ``i`` is ``x <- x +
mixer_i(RMSNorm(x; w_i))``, eps ``norm_eps`` 1e-5, its mixer the ``i``-th
letter of ``hybrid_override_pattern`` [c]:

- ``M``, Mamba-2 (64 heads of 64 channels, 8 groups, a state of 128, 4 taps)
  [c]: ``[z | xBC | dt] = u W_in`` (4,096 | 6,144 | 64), no bias [c]; ``xBC <-
  silu(b + sum_j w_j xBC_(t-3+j))``, a causal depthwise convolution, zeros
  before the sequence [a: the last tap reads the position itself]; ``x [T, 64,
  64]``, ``B``, ``C`` ``[T, 8, 128]``, head ``h`` reads group ``h // 8``;
  ``delta = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the recurrence, ONE
  POSITION AT A TIME: ``S_t = exp(delta_t A) S_(t-1) + delta_t x_t B_t^T``,
  ``y_t = S_t C_t + D x_t``, ``S`` zero at the sequence's start; ``y *
  silu(z)`` RMS-normed over 8 groups of 512 channels, times one ``[4,096]``
  weight [a: the gate before the norm]; ``out = y W_out``.
- ``*``, attention: 32 query heads of 128 on 2 key/value heads, no biases [c];
  NO position encoding [a: the family's attention applies none; the file's
  ``rope_theta`` is read by no layer]; causal softmax at ``128^-1/2``.
- ``E``, experts: ``s = sigmoid(u Wr)`` over all 128 [a: the scoring
  function]; the 6 largest of ``s + b``, ties to the lower id, ``b`` the
  correction bias at its initial 0 (its rule is outside the gradient and left
  out [a]); ``w = 2.5 s_sel / sum(s_sel)`` [c]; ``x <- x + sum_held w_e
  relu(u W_up,e)^2 W_down,e + relu(u S_up)^2 S_down`` with the experts held
  here (``first_expert_held .. + n_routed_experts`` of the published 128; what
  the others would add is left out) at width 1,856 and the shared expert whole
  at 3,712 [c].
- Head: final RMSNorm, logits over the held slice of the untied head [c]; mean
  next-token cross-entropy. No auxiliary loss [a].

Plain means the recurrence position by position, ``[heads, q, T]`` scores with
the mask, every held expert on every token and masked, ``jax.grad``. What is
blocked, so that it fits beside 2.7 GB of weights and 2.7 GB of summed
gradients: one sequence at a time (nothing couples the sequences); each layer,
each block of 64 positions of the recurrence (a backward through 8,192 states
of 2 MB would hold 17 GB), each expert's contribution, each block of 512 query
positions and each block of 1,024 positions of the head rematerialised in the
backward; AdamW's first step from zero moments needs no moment storage.

``precision="bf16"`` is the control, one precision below what the
configuration states: weights, activations, router, softmaxes, the scan's
step sizes, decays and state, and every accumulator's result in bfloat16. It
must fail the limits.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.95, 1e-8
Q_BLOCK, HEAD_BLOCK, SCAN_BLOCK = 512, 1024, 64
A_RANGE = (1.0, 16.0)


def layer_kinds(dims: dict) -> str:
    """The mixers of the layers that run: the first ``num_hidden_layers``
    letters of the published pattern."""
    return dims["hybrid_override_pattern"][: dims["num_hidden_layers"]]


def leaf_table(dims: dict) -> list:
    """``(name, shape, start)`` of every parameter in the order the
    configuration's ``init`` numbers them; ``start`` is 1.0, 0.0, None
    (``init_std * normal``), ``"dt_bias"`` or ``"a_log"``."""
    d = dims["hidden_size"]
    heads, p, groups, n = dims["mamba_num_heads"], dims["mamba_head_dim"], dims["n_groups"], dims["ssm_state_size"]
    inner, conv = heads * p, heads * p + 2 * groups * n
    a, kv = dims["num_attention_heads"] * dims["head_dim"], dims["num_key_value_heads"] * dims["head_dim"]
    held, width, shared = dims["n_routed_experts"], dims["moe_intermediate_size"], \
        dims["moe_shared_expert_intermediate_size"]
    routed = dims["n_routed_experts_published"]
    out = [("embed", (dims["vocab_size"], d), None)]
    for i, kind in enumerate(layer_kinds(dims)):
        layer = [("norm", (d,), 1.0)]
        if kind == "M":
            layer += [("in_proj", (d, inner + conv + heads), None), ("conv_w", (dims["conv_kernel"], conv), None),
                      ("conv_b", (conv,), 0.0), ("dt_bias", (heads,), "dt_bias"), ("A_log", (heads,), "a_log"),
                      ("D", (heads,), 1.0), ("gate_norm", (inner,), 1.0), ("out_proj", (inner, d), None)]
        elif kind == "*":
            layer += [("wq", (d, a), None), ("wk", (d, kv), None), ("wv", (d, kv), None), ("wo", (a, d), None)]
        else:
            layer += [("router", (d, routed), None), ("router_bias", (routed,), 0.0),
                      ("shared_up", (d, shared), None), ("shared_down", (shared, d), None),
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
    return w * (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps))


def recurrence(x, b, c, delta, a):
    """``y [T, H, P]`` of ``S_t = exp(delta_t a) S_(t-1) + delta_t x_t b_t^T``,
    ``y_t = S_t c_t``, one position at a time from ``S = 0``: ``x [T, H, P]``,
    ``b``, ``c`` ``[T, H, N]``, ``delta [T, H]``, ``a [H]``. Blocks of
    ``SCAN_BLOCK`` positions are rematerialised in the backward."""
    t, heads, p = x.shape
    n = b.shape[-1]

    def position(state, now):
        x_t, b_t, c_t, delta_t = now
        state = jnp.exp(delta_t * a)[:, None, None] * state + (delta_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    @jax.checkpoint
    def block(state, now):
        return jax.lax.scan(position, state, now)

    blk = min(SCAN_BLOCK, t)
    blocks = tuple(m.reshape(t // blk, blk, *m.shape[1:]) for m in (x, b, c, delta))
    _, y = jax.lax.scan(block, jnp.zeros((heads, p, n), x.dtype), blocks)
    return y.reshape(t, heads, p)


def _mamba(u, p, pre, dims):
    t = u.shape[0]
    heads, hp, groups, n = dims["mamba_num_heads"], dims["mamba_head_dim"], dims["n_groups"], dims["ssm_state_size"]
    taps, inner, bc = dims["conv_kernel"], heads * hp, groups * n
    proj = u @ p[pre + "in_proj"]
    z, xbc, dt = proj[:, :inner], proj[:, inner: 2 * inner + 2 * bc], proj[:, 2 * inner + 2 * bc:]
    earlier = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1]), xbc.dtype), xbc])
    w = p[pre + "conv_w"]
    xbc = jax.nn.silu(p[pre + "conv_b"] + sum(w[j] * earlier[j: j + t] for j in range(taps)))
    x = xbc[:, :inner].reshape(t, heads, hp)
    b = jnp.repeat(xbc[:, inner: inner + bc].reshape(t, groups, n), heads // groups, axis=1)
    c = jnp.repeat(xbc[:, inner + bc:].reshape(t, groups, n), heads // groups, axis=1)
    delta = jax.nn.softplus(dt + p[pre + "dt_bias"])
    y = recurrence(x, b, c, delta, -jnp.exp(p[pre + "A_log"])) + p[pre + "D"][:, None] * x
    y = (y.reshape(t, inner) * jax.nn.silu(z)).reshape(t, groups, inner // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + jnp.asarray(dims["norm_eps"], y.dtype))
    return (y.reshape(t, inner) * p[pre + "gate_norm"]) @ p[pre + "out_proj"]


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
    return o.reshape(t, heads * d) @ p[pre + "wo"]


def _relu2(u, up, down):
    return jnp.square(jax.nn.relu(u @ up)) @ down


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
    def contribution(w_e, up, down):
        return w_e[:, None] * _relu2(u, up, down)

    def body(y, xs):
        return y + contribution(*xs), None

    y, _ = jax.lax.scan(body, jnp.zeros_like(u), (weight, p[pre + "w_up"], p[pre + "w_down"]))
    return y, chosen


def _sequence(p, tok, dims):
    """One sequence ``tok [T]``: its summed next-token cross-entropy and, per
    expert layer, how many (token, slot) choices fell on each of the router's experts."""
    eps = dims["norm_eps"]
    x = p["embed"][tok]
    counts = []

    def layer(x, pre, kind):
        u = _rms_norm(x, p[pre + "norm"], eps)
        if kind == "M":
            return x + _mamba(u, p, pre, dims), None
        if kind == "*":
            return x + _attention(u, p, pre, dims), None
        y, chosen = _experts(u, p, pre, dims)
        return x + y + _relu2(u, p[pre + "shared_up"], p[pre + "shared_down"]), chosen

    for i, kind in enumerate(layer_kinds(dims)):
        x, chosen = jax.checkpoint(layer, static_argnums=(1, 2))(x, f"layers.{i}.", kind)
        if chosen is not None:
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
    and per parameter) and expert loads ``[expert layers, published experts]``,
    for ``batches`` (two ``[B, T]`` int arrays) from the configuration's
    initial weights."""
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
