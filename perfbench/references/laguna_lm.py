"""The benchmark's own plain reference of the Laguna-XS.2 decoder LM as the
``laguna_xs2`` configuration cuts it: the head of a fit job - the first AdamW
step's loss, gradient norms and update, and the second step's loss - in
``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``. It
imports nothing of the program: the equations are written again here.

Origin of each equation: [c] the model's ``config.json``
(https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json,
``model_type`` ``laguna``); [a] assumed, and listed under the configuration's
``assumed``. Matrices map ``x @ W``. Layer ``i`` has ``H_i =
num_attention_heads_per_layer[i]`` query heads (48 full, 64 windowed) on 8
key/value heads of 128 channels, eps 1e-6 [c].

- ``a = RMSNorm(x)``; ``q = a Wq [d, H_i 128]``, ``k = a Wk [d, 1024]``, ``v =
  a Wv [d, 1024]``, no biases [c]; no QK-norm [a: no key].
- RoPE, rotate-half [c]: a windowed layer (``layer_types[i]``
  ``sliding_attention``) turns all 128 channels at theta 1e4; a full layer the
  first 64 (``partial_rotary_factor`` 0.5) at theta 5e5 under YaRN: factor 64,
  original length 4,096, ``beta_fast`` 64, ``beta_slow`` 1; pair ``j`` of 32
  has ``f_j = theta^(-2j/64)``, the correction dimension of ``r`` rotations is
  ``64 ln(4096 / (2 pi r)) / (2 ln theta)``, ``low`` its floor at 64, ``high``
  its ceiling at 1, ``g_j = clip((j - low) / (high - low), 0, 1)``, the
  frequency used ``g_j f_j / 64 + (1 - g_j) f_j``; ``cos`` and ``sin`` times
  ``attention_factor`` 1.4158883 [a: the range truncated to whole pairs].
- Scores at ``128^-1/2``, causal; on a windowed layer key ``j`` is visible to
  query ``t`` iff ``t - 512 < j <= t`` [a: the 512 keys ending at the query];
  query head ``h`` reads key/value head ``h // (H_i / 8)``.
- ``gating`` true [c] read as a per-head output gate ``g = sigmoid(a Wg)``,
  ``Wg [d, H_i]``, head ``h``'s output times ``g_h`` before ``Wo`` [a: the
  shape follows from the published parameter count; the sigmoid is assumed].
  ``x <- x + concat(o) Wo``.
- ``u = RMSNorm(x)``. ``mlp_layer_types[i]`` ``dense`` (layer 0): ``x <- x +
  (silu(u Wg1) * u Wu1) Wd1``, width 8,192 [c]. ``sparse``: ``s = sigmoid(u
  Wr)`` over all 256 [a: the scoring function]; the 8 largest of ``s + b``,
  ties to the lower id, the balancing bias ``b`` at its initial 0 (its rule is
  outside the gradient and left out [a]); ``w = 2.5 * s_sel / sum(s_sel)``
  [c: 2.5; a: renormalised]; ``x <- x + sum_held w_e E_e(u) + S(u)`` with the
  experts held here (``first_expert_held .. + num_experts`` of the published
  256; what the others would add is left out: the chip's share, the
  ``model-configs`` guide, section 4) and the shared expert whole, each a
  SwiGLU of width 512 [c].
- Head: final RMSNorm, logits over the held slice of the untied head [c]; mean
  next-token cross-entropy. No auxiliary loss [a].

Plain means ``[heads, q, T]`` scores with the masks, every held expert on every
token and masked, ``jax.grad``. What is blocked, so that it fits beside 2.8 GB
of weights and 2.8 GB of summed gradients: one sequence at a time (nothing
couples the sequences: the loss is a sum over them); each layer, each expert's
contribution, each block of 512 query positions and each block of 1,024
positions of the head rematerialised in the backward; AdamW's first step from
zero moments needs no moment storage.

``precision="bf16"`` is the control, one precision below what the
configuration states: weights, activations, router, softmaxes and every
accumulator's result in bfloat16. It must fail the limits.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.95, 1e-8
Q_BLOCK, HEAD_BLOCK = 512, 1024


def layer_kinds(dims: dict) -> list:
    """``(query heads, window or 0, dense)`` of each layer that is run: the
    first ``num_hidden_layers`` entries of the published per-layer lists."""
    n = dims["num_hidden_layers"]
    return [(h, dims["sliding_window"] if kind == "sliding_attention" else 0, mlp == "dense")
            for h, kind, mlp in zip(dims["num_attention_heads_per_layer"][:n], dims["layer_types"][:n],
                                    dims["mlp_layer_types"][:n])]


def leaf_table(dims: dict) -> list:
    """``(name, shape, start)`` of every parameter in the order the
    configuration's ``init`` numbers them; ``start`` is 1.0, 0.0 or None
    (``init_std * normal``)."""
    d, hd, kv = dims["hidden_size"], dims["head_dim"], dims["num_key_value_heads"] * dims["head_dim"]
    held, width, routed = dims["num_experts"], dims["moe_intermediate_size"], dims["num_experts_published"]
    shared, dense = dims["shared_expert_intermediate_size"], dims["intermediate_size"]
    out = [("embed", (dims["vocab_size"], d), None)]
    for i, (heads, _, is_dense) in enumerate(layer_kinds(dims)):
        layer = [("attn_norm", (d,), 1.0), ("wq", (d, heads * hd), None), ("wk", (d, kv), None),
                 ("wv", (d, kv), None), ("head_gate", (d, heads), None), ("wo", (heads * hd, d), None),
                 ("ffn_norm", (d,), 1.0)]
        if is_dense:
            layer += [("w_gate", (d, dense), None), ("w_up", (d, dense), None), ("w_down", (dense, d), None)]
        else:
            layer += [("router", (d, routed), None), ("router_bias", (routed,), 0.0),
                      ("shared_gate", (d, shared), None), ("shared_up", (d, shared), None),
                      ("shared_down", (shared, d), None),
                      ("w_gate", (held, d, width), None), ("w_up", (held, d, width), None),
                      ("w_down", (held, width, d), None)]
        out += [(f"layers.{i}.{name}", shape, start) for name, shape, start in layer]
    return out + [("final_norm", (d,), 1.0), ("lm_head", (d, dims["vocab_size"]), None)]


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _init_leaf(key, i, shape, std):
    return std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)


def init_params(dims: dict, seed: int, std: float) -> dict:
    key = jax.random.key(seed)
    return {name: _init_leaf(key, i, shape, std) if start is None else jnp.full(shape, start, jnp.float32)
            for i, (name, shape, start) in enumerate(leaf_table(dims))}


# -- the equations ---------------------------------------------------------------


def _rms_norm(x, w, eps):
    return w * (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps))


def yarn_frequencies(rot, theta, factor, original, beta_fast, beta_slow) -> np.ndarray:
    f = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)

    def correction_dim(rotations):
        return rot * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), rot - 1)
    g = np.clip((np.arange(rot // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return g * f / factor + (1.0 - g) * f


def _turn(x, inv_freq, scale):
    """Rotate-half RoPE on the first ``2 len(inv_freq)`` channels of each head of ``x [T, H, D]``."""
    rot = 2 * len(inv_freq)
    freqs = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    cos, sin = (scale * jnp.cos(emb)).astype(x.dtype), (scale * jnp.sin(emb)).astype(x.dtype)
    part = x[..., :rot]
    turned = part * cos + jnp.concatenate([-part[..., rot // 2:], part[..., : rot // 2]], axis=-1) * sin
    return jnp.concatenate([turned, x[..., rot:]], axis=-1)


def _attention(a, p, pre, heads, window, dims):
    t = a.shape[0]
    kv, d = dims["num_key_value_heads"], dims["head_dim"]
    group = heads // kv
    q = (a @ p[pre + "wq"]).reshape(t, heads, d)
    k = (a @ p[pre + "wk"]).reshape(t, kv, d)
    v = (a @ p[pre + "wv"]).reshape(t, kv, d)
    if window:
        r = dims["rope_sliding"]
        rot = int(d * r["partial_rotary_factor"])
        inv_freq, scale = float(r["rope_theta"]) ** (-np.arange(0, rot, 2, dtype=np.float64) / rot), 1.0
    else:
        r = dims["rope_full"]
        rot = int(d * r["partial_rotary_factor"])
        inv_freq = yarn_frequencies(rot, float(r["rope_theta"]), r["factor"], r["original_max_position_embeddings"],
                                    r["beta_fast"], r["beta_slow"])
        scale = r["attention_factor"]
    q, k = _turn(q, inv_freq, scale), _turn(k, inv_freq, scale)
    qb = min(Q_BLOCK, t)

    @jax.checkpoint
    def block(args):  # the query positions of one block against every key
        q_blk, pos = args
        s = jnp.einsum("qjgd,kjd->jgqk", q_blk.reshape(qb, kv, group, d), k) * (d ** -0.5)
        keys = jnp.arange(t)[None, :]
        keep = pos[:, None] >= keys
        if window:
            keep &= keys > pos[:, None] - window
        s = jnp.where(keep[None, None], s, -jnp.inf)
        return jnp.einsum("jgqk,kjd->qjgd", jax.nn.softmax(s, axis=-1), v).reshape(qb, heads, d)

    o = jax.lax.map(block, (q.reshape(t // qb, qb, heads, d), jnp.arange(t).reshape(t // qb, qb)))
    gate = jax.nn.sigmoid(a @ p[pre + "head_gate"])  # [T, H]
    return (o.reshape(t, heads, d) * gate[:, :, None]).reshape(t, heads * d) @ p[pre + "wo"]


def _swiglu(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def _moe(u, p, pre, dims):
    """Every held expert on every token, the unchosen masked. Returns the
    routed part and the chosen experts ``[T, k]``."""
    s = jax.nn.sigmoid(u @ p[pre + "router"])
    _, chosen = jax.lax.top_k(s + p[pre + "router_bias"], dims["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=1)
    gates = jnp.asarray(dims["moe_routed_scaling_factor"], s.dtype) * picked / jnp.sum(picked, axis=1, keepdims=True)
    held = dims["first_expert_held"] + jnp.arange(dims["num_experts"])
    weight = jnp.sum(jnp.where(chosen[None, :, :] == held[:, None, None], gates[None], jnp.zeros((), gates.dtype)),
                     axis=2)  # [held, T]

    @jax.checkpoint
    def contribution(w_e, wg, wu, wd):
        return w_e[:, None] * _swiglu(u, wg, wu, wd)

    def body(y, xs):
        return y + contribution(*xs), None

    y, _ = jax.lax.scan(body, jnp.zeros_like(u), (weight, p[pre + "w_gate"], p[pre + "w_up"], p[pre + "w_down"]))
    return y, chosen


def _sequence(p, tok, dims):
    """One sequence ``tok [T]``: its summed next-token cross-entropy and, per
    sparse layer, how many (token, slot) choices fell on each of the router's experts."""
    eps = dims["rms_norm_eps"]
    x = p["embed"][tok]
    counts = []

    def layer(x, pre, heads, window, is_dense):
        x = x + _attention(_rms_norm(x, p[pre + "attn_norm"], eps), p, pre, heads, window, dims)
        u = _rms_norm(x, p[pre + "ffn_norm"], eps)
        if is_dense:
            return x + _swiglu(u, p[pre + "w_gate"], p[pre + "w_up"], p[pre + "w_down"]), None
        y, chosen = _moe(u, p, pre, dims)
        return x + y + _swiglu(u, p[pre + "shared_gate"], p[pre + "shared_up"], p[pre + "shared_down"]), chosen

    for i, (heads, window, is_dense) in enumerate(layer_kinds(dims)):
        x, chosen = jax.checkpoint(layer, static_argnums=(1, 2, 3, 4))(x, f"layers.{i}.", heads, window, is_dense)
        if chosen is not None:
            counts.append(jnp.zeros((dims["num_experts_published"],), jnp.int32).at[chosen.reshape(-1)].add(1))
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
    and per parameter) and expert loads ``[sparse layers, published experts]``,
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
