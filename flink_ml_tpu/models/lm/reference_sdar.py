"""The plain reference of the ``sdar`` block kind (SDAR-30B-A3B-Chat: the
Qwen3-MoE decoder layer trained by block diffusion): the corruption, forward,
loss, gradients and AdamW steps in straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``.

A Python loop over layers, every held expert applied to every token and
masked, ``[2T, 2T]`` scores under a dense boolean mask built from the four
rules below, ``jax.grad`` for the gradients; no kernel, no sort, no chunking,
no recomputation. It shares with the stage only the parameter tree's layout
(``config.py``); AdamW and the clip are ``reference.py``'s, which are plain
themselves.

Origin of each equation. [c]: a key of the model's ``config.json``
(https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json,
``model_type`` ``sdar_moe``: the Qwen3-MoE layer). [p]: block diffusion,
arXiv:2503.09573 section 3, as arXiv:2510.06303 (SDAR) adapts an
autoregressive model to it. [a]: assumed here, a detail neither fixes; the
benchmark's configuration lists each under ``assumed`` with these words.

The layer, residual ``x [B, P, d]`` over ``P = 2 T`` positions, eps
``rms_norm_eps`` 1e-6, no biases (``attention_bias`` false) [c]:

- ``a = RMSNorm(x; attn_norm)``; ``q = a Wq -> [P, 32, 128]``, ``k = a Wk``, ``v
  = a Wv -> [P, 4, 128]`` (``num_attention_heads`` 32, ``num_key_value_heads``
  4, ``head_dim`` 128) [c]; ``q <- RMSNorm(q; q_norm [128])``, ``k <-
  RMSNorm(k; k_norm [128])`` over EACH head's channels, one weight for every
  head (Qwen3's ``q_norm``/``k_norm``) [c: the family].
- RoPE at ``rope_theta`` 1e6, no scaling (``rope_scaling`` null) [c], on the
  whole head, rotate-half [a: the family's convention], at the position's id:
  ``pos = [0 .. T-1 ; 0 .. T-1]``, a token's clean copy and its noised copy at
  the same id [p].
- Scores at ``128^-1/2`` under the mask ``M`` below, softmax in float32, query
  head ``h`` on key/value head ``h // 8``; ``x <- x + concat(o) Wo``.
- ``u = RMSNorm(x; ffn_norm)``; ``s = softmax(u Wr)`` over all ``num_experts``
  128 in float32; the ``num_experts_per_tok`` 8 largest chosen (ties to the
  lower id); ``g = s_sel / sum(s_sel)`` over ALL EIGHT chosen, held here or not
  (``norm_topk_prob`` true) [c]; ``x <- x + sum_e g_e W_down,e (silu(u
  W_gate,e) * (u W_up,e))`` over the chosen experts held here (``first_held ..
  first_held + held``; what the others would add is left out: the chip's share
  of an expert-parallel layer, the ``model-configs`` guide, section 4), each a
  SwiGLU of width ``moe_intermediate_size`` 768 [c]. No shared expert
  (``described_as``: 0 shared), an untied head (``tie_word_embeddings``
  false) [c].

The objective [p]. A sequence ``x`` of ``T`` tokens lies in blocks of ``L``
tokens (``block_length`` [a: 4, the family's released default]). A level ``t ~
U[0, 1)`` a sequence, ``p = (1 - eps) t + eps`` (the linear schedule, ``eps``
1e-3 [a]), each token masked independently with probability ``p``: ``x~_i =
MASK`` where ``m_i = 1``, else ``x_i``. The stack runs ONCE over ``[x ; x~]``.
With ``b(i) = i // L``, query ``i``, key ``j`` (both as positions of their own
half):

1. clean -> clean keeps ``b(j) <= b(i)``;
2. noised -> clean keeps ``b(j) < b(i)``;
3. noised -> noised keeps ``b(j) = b(i)``;
4. clean -> noised keeps nothing.

The loss is ``1 / (B T) sum over masked i of (1 / p) x -log softmax(h~_i
W_head)[x_i]``: the noised half's final-normed states through the head, the
target the position's OWN token (no shift [a: the masked position predicts its
own token]). No auxiliary loss [a: the file has no coefficient]. Packed
documents attend across their boundaries [a: no document mask]. AdamW decays
every parameter [a].

How the corruption is drawn [a], written so that another implementation draws
the same masks: ``k = fold_in(fold_in(key(seed), 2^30), step)``; ``k_t, k_m =
split(k)``; ``t = uniform(k_t, [B])``; ``u = uniform(k_m, [B, T])`` (float32);
``m = u < p[:, None]``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from flink_ml_tpu.models.lm import reference as olmoe
from flink_ml_tpu.models.lm.config import LMConfig

__all__ = ["corrupt", "mask", "forward", "loss_and_stats", "loss_and_grads", "train_steps", "bound_estimate",
           "moe", "NOISE_STREAM"]

NOISE_STREAM = 2 ** 30
NOISE_EPS = 1e-3  # the linear schedule's floor [a]


def corrupt(tok, seed: int, step: int, cfg: LMConfig):
    """``(x~ [B, T], m [B, T] bool, p [B])`` of step ``step`` of the job seeded ``seed``."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(seed), NOISE_STREAM), step)
    k_t, k_m = jax.random.split(key)
    level = jax.random.uniform(k_t, (tok.shape[0],), jnp.float32)
    p = (1.0 - NOISE_EPS) * level + NOISE_EPS
    m = jax.random.uniform(k_m, tok.shape, jnp.float32) < p[:, None]
    return jnp.where(m, cfg.mask_id, tok), m, p


def mask(t: int, block: int) -> np.ndarray:
    """The dense ``[2T, 2T]`` mask from the four rules, queries down, keys across."""
    b = np.arange(t) // block
    clean_clean = b[None, :] <= b[:, None]
    noised_clean = b[None, :] < b[:, None]
    noised_noised = b[None, :] == b[:, None]
    nothing = np.zeros((t, t), bool)
    return np.block([[clean_clean, nothing], [noised_clean, noised_noised]])


def rms_norm(x, w, eps):
    return w * (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps))


def rope(x, pos, theta):
    """Rotate-half RoPE of ``x [B, P, H, D]`` at the position ids ``pos [P]``."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    freqs = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    cos, sin = jnp.cos(emb)[None, :, None, :], jnp.sin(emb)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def attention(a, layer, keep, pos, cfg: LMConfig):
    b, p, _ = a.shape
    h, kv, hd = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    q = rms_norm((a @ layer["wq"]).reshape(b, p, h, hd), layer["q_norm"], cfg.norm_eps)
    k = rms_norm((a @ layer["wk"]).reshape(b, p, kv, hd), layer["k_norm"], cfg.norm_eps)
    v = (a @ layer["wv"]).reshape(b, p, kv, hd)
    q, k = rope(q, pos, cfg.rope_theta), rope(k, pos, cfg.rope_theta)
    k, v = jnp.repeat(k, h // kv, axis=2), jnp.repeat(v, h // kv, axis=2)  # query head h on key/value head h // group
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (hd ** -0.5)
    s = jnp.where(keep[None, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(b, p, h * hd) @ layer["wo"]


def moe(u, layer, cfg: LMConfig):
    """The held experts' part of the layer on ``u [N, d]`` and the chosen experts ``[N, k]``: every held expert on
    every token, the unchosen masked; the gates renormalised over all ``top_k`` chosen."""
    s = jax.nn.softmax(u @ layer["router"], axis=-1)
    top_s, top_e = jax.lax.top_k(s, cfg.top_k)
    gates = top_s / jnp.sum(top_s, axis=1, keepdims=True)
    weight = jnp.zeros_like(s).at[jnp.arange(u.shape[0])[:, None], top_e].set(gates)  # [N, E]
    y = jnp.zeros_like(u)
    for e in range(cfg.held):
        hidden = jax.nn.silu(u @ layer["w_gate"][e]) * (u @ layer["w_up"][e])
        y = y + weight[:, cfg.first_held + e][:, None] * (hidden @ layer["w_down"][e])
    return y, top_e


def forward(params, both, cfg: LMConfig):
    """The final-normed states ``[B, 2T, d]`` of the doubled input ``both = [x ; x~]`` and each layer's chosen
    experts."""
    b, p = both.shape
    t = p // 2
    keep = jnp.asarray(mask(t, cfg.block_length))
    pos = jnp.concatenate([jnp.arange(t), jnp.arange(t)])
    x = params["embed"][both]
    chosen = []
    for layer in params["layers"]:
        x = x + attention(rms_norm(x, layer["attn_norm"], cfg.norm_eps), layer, keep, pos, cfg)
        y, top_e = moe(rms_norm(x, layer["ffn_norm"], cfg.norm_eps).reshape(b * p, -1), layer, cfg)
        x = x + y.reshape(b, p, -1)
        chosen.append(top_e)
    return rms_norm(x, params["final_norm"], cfg.norm_eps), chosen


def _weighted_nll(params, tok, noised, m, p, cfg: LMConfig):
    """``[B, T]``: ``m_i / p x -log softmax(h~_i W_head)[x_i]``, and the layers' chosen experts."""
    t = tok.shape[1]
    h, chosen = forward(params, jnp.concatenate([tok, noised], axis=1), cfg)
    logp = jax.nn.log_softmax(h[:, t:] @ params["lm_head"], axis=-1)
    nll = -jnp.take_along_axis(logp, tok[:, :, None], axis=-1)[..., 0]
    return m * nll / p[:, None], chosen


def loss_and_stats(params, tok, seed: int, step: int, cfg: LMConfig):
    """``(loss, (positions scored, each layer's rows an expert [layers, E]))`` of step ``step``."""
    with jax.default_matmul_precision("highest"):
        noised, m, p = corrupt(tok, seed, step, cfg)
        weighted, chosen = _weighted_nll(params, tok, noised, m, p, cfg)
        rows = jnp.stack([jnp.zeros((cfg.n_experts,), jnp.int32).at[c.reshape(-1)].add(1) for c in chosen])
        return jnp.sum(weighted) / tok.size, (jnp.sum(m), rows)


def loss_and_grads(params, tok, seed: int, step: int, cfg: LMConfig):
    (loss, stats), grads = jax.value_and_grad(loss_and_stats, has_aux=True)(params, tok, seed, step, cfg)
    return loss, grads, stats


def bound_estimate(params, tok, seed: int, step: int, cfg: LMConfig):
    """Per row, minus the one-draw estimate of the bound a token: what ``transform`` reports for batch ``step``."""
    with jax.default_matmul_precision("highest"):
        noised, m, p = corrupt(tok, seed, step, cfg)
        return -jnp.mean(_weighted_nll(params, tok, noised, m, p, cfg)[0], axis=1)


def train_steps(params, batches, seed: int, cfg: LMConfig, lr, **adamw):
    """``len(batches)`` AdamW steps from ``params``, batch ``i`` corrupted as step ``i``. Returns ``(params, losses,
    grad_norms)``."""
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, norms = [], []
    for i, tok in enumerate(batches):
        loss, grads, _ = loss_and_grads(params, tok, seed, i, cfg)
        params, m, v, norm = olmoe.adamw_step(params, m, v, grads, i + 1, lr, **adamw)
        losses.append(float(loss))
        norms.append(float(norm))
    return params, losses, norms
