"""The plain reference of the decoder LM: forward, loss (both terms),
gradients and one AdamW step in straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``.

A Python loop over layers, every expert applied to every token and masked,
``[T, T]`` scores with a causal mask, ``jax.grad`` for the gradients; no
kernel, no sort, no cache, no recomputation. It shares with the stage only the
parameter tree's layout (``config.py``), so that one set of weights can be
handed to both.

Origin of each equation: Hugging Face ``transformers``,
``models/olmoe/modeling_olmoe.py`` (the implementation OLMoE's ``config.json``
belongs to), written from memory - there is no network here. Departures:

- weights are ``[in, out]`` (``x @ W``), the transpose of ``nn.Linear``;
- the paper's router z-loss (arXiv:2409.02060) is not in that implementation
  and is left out here too;
- packed documents attend across their boundaries (no per-document mask);
- AdamW decays every parameter, norms and embeddings included (the recipe's
  exclusions, if any, are not known here).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from flink_ml_tpu.models.lm.config import LMConfig

__all__ = [
    "forward", "loss_and_aux", "loss_and_grads", "adamw_step", "train_steps",
    "log_likelihood", "global_norm",
]


def rms_norm(x, w, eps):
    """OlmoeRMSNorm: ``w * x / sqrt(mean(x^2) + eps)`` in float32."""
    x = x.astype(jnp.float32)
    return w * (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps))


def rope(x, theta):
    """Rotary embedding, rotate-half convention (``apply_rotary_pos_emb``):
    ``x [B, T, H, D]``, position ``t`` of the sequence, ``inv_freq_i =
    theta^(-2i/D)``, ``emb = cat(freqs, freqs)``, ``x*cos + rotate_half(x)*sin``
    with ``rotate_half(x) = cat(-x2, x1)``."""
    _, t, _, d = x.shape
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    freqs = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]  # [T, D/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    cos, sin = jnp.cos(emb)[None, :, None, :], jnp.sin(emb)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def attention(x, layer, cfg: LMConfig):
    """OlmoeAttention: QK-norm over the whole projection before the split into
    heads, RoPE after it, causal softmax attention at scale ``D^-1/2``."""
    b, t, d = x.shape
    h, hd = cfg.n_heads, cfg.head_dim
    q = rms_norm(x @ layer["wq"], layer["q_norm"], cfg.norm_eps)
    k = rms_norm(x @ layer["wk"], layer["k_norm"], cfg.norm_eps)
    v = x @ layer["wv"]
    q = rope(q.reshape(b, t, h, hd), cfg.rope_theta)
    k = rope(k.reshape(b, t, h, hd), cfg.rope_theta)
    v = v.reshape(b, t, h, hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (hd ** -0.5)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(b, t, d) @ layer["wo"]


def moe(u, layer, cfg: LMConfig):
    """OlmoeSparseMoeBlock on ``u [N, d]``: float32 router, softmax over all
    experts, the ``top_k`` largest kept as they are (``norm_topk_prob``
    false); every expert runs on every token and the unchosen are masked.
    Returns the output, the probabilities and the chosen experts."""
    p = jax.nn.softmax(u @ layer["router"], axis=-1)  # [N, E]
    top_p, top_e = jax.lax.top_k(p, cfg.top_k)
    # weight[n, e] = p[n, e] where e was chosen for n, else 0
    chosen = jnp.sum(jax.nn.one_hot(top_e, cfg.n_experts, dtype=p.dtype), axis=1)
    weight = p * chosen
    y = jnp.zeros_like(u)
    for e in range(cfg.n_experts):
        hidden = jax.nn.silu(u @ layer["w_gate"][e]) * (u @ layer["w_up"][e])
        y = y + weight[:, e: e + 1] * (hidden @ layer["w_down"][e])
    return y, p, top_e


def forward(params, tok, cfg: LMConfig):
    """Logits ``[B, T, V]`` and, per layer, the router's ``(p, top_e)``."""
    x = params["embed"][tok]
    b, t, d = x.shape
    routed = []
    for layer in params["layers"]:
        x = x + attention(rms_norm(x, layer["attn_norm"], cfg.norm_eps), layer, cfg)
        y, p, top_e = moe(rms_norm(x, layer["ffn_norm"], cfg.norm_eps).reshape(b * t, d), layer, cfg)
        x = x + y.reshape(b, t, d)
        routed.append((p, top_e))
    return rms_norm(x, params["final_norm"], cfg.norm_eps) @ params["lm_head"], routed


def load_balancing_loss(routed, cfg: LMConfig):
    """``load_balancing_loss_func``: all layers' tokens are concatenated;
    ``E * sum_e f_e * P_e`` with ``f_e`` the mean over those tokens of the
    one-hot choice of each slot, summed over the slots, and ``P_e`` the mean
    router probability."""
    p = jnp.concatenate([p for p, _ in routed], axis=0)  # [L*N, E]
    top_e = jnp.concatenate([e for _, e in routed], axis=0)  # [L*N, k]
    mask = jax.nn.one_hot(top_e, cfg.n_experts, dtype=jnp.float32)  # [L*N, k, E]
    tokens_per_expert = jnp.mean(mask, axis=0)  # [k, E]
    prob_per_expert = jnp.mean(p, axis=0)  # [E]
    return cfg.n_experts * jnp.sum(tokens_per_expert * prob_per_expert[None, :])


def token_log_probs(logits, tok):
    """``[B, T-1]``: log-probability of each next token."""
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(logp, tok[:, 1:, None], axis=-1)[..., 0]


def loss_and_aux(params, tok, cfg: LMConfig):
    """``(loss, (cross_entropy, load_balancing))``: mean next-token
    cross-entropy over every sequence's ``T - 1`` targets plus ``aux_coef``
    times the load-balancing loss."""
    with jax.default_matmul_precision("highest"):
        logits, routed = forward(params, tok, cfg)
        ce = -jnp.mean(token_log_probs(logits, tok))
        aux = load_balancing_loss(routed, cfg)
    return ce + cfg.aux_coef * aux, (ce, aux)


def loss_and_grads(params, tok, cfg: LMConfig):
    (loss, _), grads = jax.value_and_grad(loss_and_aux, has_aux=True)(params, tok, cfg)
    return loss, grads


def log_likelihood(params, tok, cfg: LMConfig):
    """Per row, the mean log-likelihood of its ``T - 1`` next tokens."""
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(params, tok, cfg)
        return jnp.mean(token_log_probs(logits, tok), axis=1)


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in jax.tree_util.tree_leaves(tree)))


def adamw_step(params, m, v, grads, step, lr, b1=0.9, b2=0.95, eps=1e-8,
               weight_decay=0.1, clip=1.0):
    """Clip at global norm ``clip`` (``g * clip / max(norm, clip)``), then
    AdamW (Loshchilov & Hutter, decoupled decay, bias-corrected moments) at
    step number ``step`` (1 for the first). Returns ``(params, m, v, norm)``
    with ``norm`` the gradient's global norm BEFORE clipping."""
    norm = global_norm(grads)
    scale = clip / jnp.maximum(norm, clip)
    c1, c2 = 1.0 - b1 ** step, 1.0 - b2 ** step

    m = jax.tree_util.tree_map(lambda m_, g: b1 * m_ + (1.0 - b1) * g * scale, m, grads)
    v = jax.tree_util.tree_map(lambda v_, g: b2 * v_ + (1.0 - b2) * (g * scale) ** 2, v, grads)
    params = jax.tree_util.tree_map(
        lambda p, m_, v_: p - lr * ((m_ / c1) / (jnp.sqrt(v_ / c2) + eps) + weight_decay * p),
        params, m, v)
    return params, m, v, norm


def train_steps(params, batches, cfg: LMConfig, lr, **adamw):
    """``len(batches)`` AdamW steps from ``params``, one ``[B, T]`` token batch
    each. Returns ``(params, losses, grad_norms)``."""
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, norms = [], []
    for i, tok in enumerate(batches):
        loss, grads = loss_and_grads(params, tok, cfg)
        params, m, v, norm = adamw_step(params, m, v, grads, i + 1, lr, **adamw)
        losses.append(float(loss))
        norms.append(float(norm))
    return params, losses, norms
