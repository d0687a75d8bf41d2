"""The benchmark's own plain reference of the OLMoE decoder LM: the head of a
fit job - the first AdamW step's loss, gradient norms and update, and the
second step's loss - in ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``. It imports nothing of the
program: the equations are written again here (origin: Hugging Face
``transformers``, ``models/olmoe/modeling_olmoe.py``, from memory; the
configuration's ``assumed`` lists what could not be checked).

Plain means: every expert is applied to every token and masked, the scores
are ``[heads, q, T]`` with a causal mask, the gradients are ``jax.grad``'s; no
kernel, no sort, no cache. What is blocked, so that it fits beside 2.5 GB of
weights and 2.5 GB of summed gradients on one chip (the ``model-configs``
guide, section 3, allows blocks):

- one sequence at a time, gradients summed. The load-balancing term couples
  the sequences (a product of two means over all the step's tokens), so a
  first forward pass per sequence collects the step's ``f`` and ``P``, and
  the gradient pass differentiates ``ce_s + coef * E * sum_e f_e * P_e^(s)``
  with the step's ``f`` held fixed - the same gradient, since ``f`` is a count;
- experts one at a time (``lax.scan``, each contribution rematerialised in
  the backward) and query positions in blocks of 1,024 (``lax.map``, each
  block rematerialised): no ``[64, T, width]`` and no ``[16, T, T]`` tensor;
- the head's logits in blocks of 1,024 positions;
- AdamW's first step needs no moment storage: from zero moments, ``m_hat =
  g`` and ``v_hat = g^2``, so each leaf is updated from its own gradient.

``precision="bf16"`` is the control, one precision below what the
configuration states: weights, activations, router, softmaxes and every
accumulator's result in bfloat16. It must fail the limits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.95, 1e-8
Q_BLOCK = 1024

_LAYER_LEAVES = (
    ("attn_norm", "d"), ("wq", "dd"), ("wk", "dd"), ("wv", "dd"), ("wo", "dd"),
    ("q_norm", "d"), ("k_norm", "d"), ("ffn_norm", "d"), ("router", "de"),
    ("w_gate", "edh"), ("w_up", "edh"), ("w_down", "ehd"),
)


def leaf_table(dims: dict) -> list:
    """``(name, shape, is_norm)`` of every parameter in the order the
    configuration's initialiser numbers them: embed, then per layer the twelve
    leaves above, final_norm, lm_head. Matrices map ``x @ W``."""
    size = {"d": dims["hidden_size"], "e": dims["num_experts"], "h": dims["intermediate_size"]}
    out = [("embed", (dims["vocab_size"], size["d"]), False)]
    for i in range(dims["num_hidden_layers"]):
        for name, axes in _LAYER_LEAVES:
            out.append((f"layers.{i}.{name}", tuple(size[a] for a in axes), name.endswith("norm")))
    out.append(("final_norm", (size["d"],), True))
    out.append(("lm_head", (size["d"], dims["vocab_size"]), False))
    return out


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _init_leaf(key, i, shape, std):
    return std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)


def init_params(dims: dict, seed: int, std: float) -> dict:
    """The configuration's ``init`` rule: leaf ``i`` is ``std * normal(fold_in(
    key(seed), i))`` in float32, norm weights are ones. A flat dict by name."""
    key = jax.random.key(seed)
    return {
        name: jnp.ones(shape, jnp.float32) if is_norm else _init_leaf(key, i, shape, std)
        for i, (name, shape, is_norm) in enumerate(leaf_table(dims))
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


def _attention(x, p, prefix, dims):
    t, d = x.shape
    h = dims["num_attention_heads"]
    hd = d // h
    eps = dims["rms_norm_eps"]
    q = _rms_norm(x @ p[prefix + "wq"], p[prefix + "q_norm"], eps)
    k = _rms_norm(x @ p[prefix + "wk"], p[prefix + "k_norm"], eps)
    v = (x @ p[prefix + "wv"]).reshape(t, h, hd)
    q = _rope(q.reshape(t, h, hd), dims["rope_theta"])
    k = _rope(k.reshape(t, h, hd), dims["rope_theta"])
    qb = min(Q_BLOCK, t)

    @jax.checkpoint
    def block(args):  # the query positions of one block against every key
        q_blk, pos = args
        s = jnp.einsum("qhd,khd->hqk", q_blk, k) * (hd ** -0.5)
        keep = pos[:, None] >= jnp.arange(t)[None, :]
        s = jnp.where(keep[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, (q.reshape(t // qb, qb, h, hd), jnp.arange(t).reshape(t // qb, qb)))
    return o.reshape(t, d) @ p[prefix + "wo"]


def _moe(u, p, prefix, dims):
    """Every expert on every token, the unchosen masked. Returns the output,
    the router's probabilities and the chosen experts."""
    n_experts, k = dims["num_experts"], dims["num_experts_per_tok"]
    probs = jax.nn.softmax(u @ p[prefix + "router"], axis=-1)
    _, top_e = jax.lax.top_k(probs, k)
    chosen = jnp.sum(jax.nn.one_hot(top_e, n_experts, dtype=probs.dtype), axis=1)
    weight = probs * chosen  # not renormalised (norm_topk_prob false)

    @jax.checkpoint
    def contribution(w_e, wg, wu, wd):
        return w_e[:, None] * ((jax.nn.silu(u @ wg) * (u @ wu)) @ wd)

    def body(y, xs):
        return y + contribution(*xs), None

    y, _ = jax.lax.scan(body, jnp.zeros_like(u), (weight.T, p[prefix + "w_gate"],
                                                   p[prefix + "w_up"], p[prefix + "w_down"]))
    return y, probs, top_e


def _sequence(p, tok, dims):
    """One sequence ``tok [T]``: its summed next-token cross-entropy and, per
    layer, the router's mean probabilities ``[L, E]`` and choice counts."""
    x = p["embed"][tok]
    eps = dims["rms_norm_eps"]
    mean_p, counts = [], []
    for i in range(dims["num_hidden_layers"]):
        prefix = f"layers.{i}."
        x = x + _attention(_rms_norm(x, p[prefix + "attn_norm"], eps), p, prefix, dims)
        y, probs, top_e = _moe(_rms_norm(x, p[prefix + "ffn_norm"], eps), p, prefix, dims)
        x = x + y
        mean_p.append(jnp.mean(probs.astype(jnp.float32), axis=0))
        counts.append(jnp.zeros((dims["num_experts"],), jnp.int32).at[top_e.reshape(-1)].add(1))
    hidden = _rms_norm(x, p["final_norm"], eps)
    t = tok.shape[0]
    qb = min(Q_BLOCK, t)
    targets = jnp.concatenate([tok[1:], tok[:1]])  # the last position has no target

    @jax.checkpoint
    def block(args):
        h_blk, t_blk = args
        logp = jax.nn.log_softmax((h_blk @ p["lm_head"]).astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, t_blk[:, None], axis=1)[:, 0]

    nll = jax.lax.map(block, (hidden.reshape(t // qb, qb, -1), targets.reshape(t // qb, qb)))
    ce_sum = jnp.sum(nll.reshape(t)[:-1])
    return ce_sum, jnp.stack(mean_p), jnp.stack(counts)


def _cast(p, dtype):
    return {k: v.astype(dtype) for k, v in p.items()}


@functools.partial(jax.jit, static_argnums=(2, 3))
def _stats(p, tok, dims_items, dtype):
    with jax.default_matmul_precision("highest"):
        return _sequence(_cast(p, dtype), tok, dict(dims_items))


@functools.partial(jax.jit, static_argnums=(4, 5, 6), donate_argnums=(0,))
def _add_grads(acc, p, tok, f_step, dims_items, dtype, scale):
    """``acc + d/dp [ce_sum(tok) * scale_ce + coef * E * sum_e f_e * P_e(tok) / B]``."""
    dims = dict(dims_items)
    scale_ce, per_seq = scale

    def objective(p32):
        ce_sum, mean_p, _ = _sequence(_cast(p32, dtype), tok, dims)
        aux = dims["num_experts"] * jnp.sum(f_step * jnp.mean(mean_p, axis=0))
        return ce_sum.astype(jnp.float32) * scale_ce + dims["aux_coef"] * aux * per_seq

    with jax.default_matmul_precision("highest"):
        grads = jax.grad(objective)(p)
    return {k: acc[k] + grads[k].astype(jnp.float32) for k in acc}


def _step_stats(p, batch, dims_items, dtype, dims):
    """A step's loss from per-sequence forward passes, with what the gradient
    pass needs of it: ``(loss, f [E], counts [L, E])``."""
    b, t = batch.shape
    ce, mean_p, counts = 0.0, 0.0, 0
    for row in batch:
        ce_s, p_s, c_s = _stats(p, jnp.asarray(row), dims_items, dtype)
        ce = ce + ce_s.astype(jnp.float32)
        mean_p = mean_p + p_s / b
        counts = counts + c_s
    # load_balancing_loss_func: all layers' tokens concatenated
    f = jnp.mean(counts.astype(jnp.float32), axis=0) / (b * t)
    aux = dims["num_experts"] * jnp.sum(f * jnp.mean(mean_p, axis=0))
    loss = ce / (b * (t - 1)) + dims["aux_coef"] * aux
    return loss, f, counts


def head_of_job(dims: dict, hyper: dict, seed: int, batches, precision: str = "f32") -> dict:
    """The first two steps' losses, and the first step's gradient norms (global
    and per parameter) and expert loads, for ``batches`` (two ``[B, T]`` int
    arrays) from the configuration's initial weights."""
    dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16}[precision]
    dims_items = tuple(sorted((k, v) for k, v in dims.items() if isinstance(v, (int, float))))
    p = init_params(dims, seed, hyper["init_std"])
    b, t = batches[0].shape

    loss1, f1, counts1 = _step_stats(p, batches[0], dims_items, dtype, dims)
    grads = {k: jnp.zeros_like(v) for k, v in p.items()}
    for row in batches[0]:
        grads = _add_grads(grads, p, jnp.asarray(row), f1, dims_items, dtype,
                           (1.0 / (b * (t - 1)), 1.0 / b))
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
    loss2, _, _ = _step_stats(p, batches[1], dims_items, dtype, dims)
    return {
        "losses": [float(loss1), float(loss2)],
        "grad_norms": [norm],
        "group_norms": group,
        "expert_rows": np.asarray(counts1),
    }
