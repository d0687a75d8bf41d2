"""The plain reference of the ``solar_open2`` block kind (Solar-Open2-250B's
hybrid decoder): forward, loss, gradients and AdamW steps in straightforward
``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``.

A Python loop over sequences and layers; the delta-rule layer as the
step-by-step recurrence, one position at a time (``lax.scan`` over positions,
nothing chunked, no triangular solve); ``[H, T, T]`` scores with the causal
mask; every held expert applied to every token and masked; ``jax.grad`` for the
gradients; no kernel, no sort, no recomputation. It shares with the stage only
the parameter tree's layout (``config.py``); AdamW, the clip and the loss's
form are ``reference.py``'s, the experts ``reference_laguna.py``'s, which are
plain themselves.

Origin of each equation. [c]: a key of the model's ``config.json``
(https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json,
``model_type`` ``solar_open2``). [p]: the family's paper, Kimi Delta Attention,
arXiv:2510.26692 section 3, which the file's ``kda_*`` keys name. [a]: assumed
here, a detail neither fixes; the benchmark's configuration lists each under
``assumed`` with these words.

Layer ``i`` is ``x <- x + mixer_i(RMSNorm(x))``, then ``x <- x +
moe(RMSNorm(x))``, eps ``rms_norm_eps`` 1e-5, the stream in float32 [c]. Its
mixer attends where ``i`` is in ``gqa_layers`` (0, 4, .., 44) and runs the
gated delta rule elsewhere [c]; every layer has experts
(``first_k_dense_replace`` 0) [c].

- KDA, the gated delta rule (``linear_attn_config``: ``num_heads`` H 64 of
  ``head_dim`` 128, ``short_conv_kernel_size`` 4) [c], per head, ``u`` the
  normed input:

  1. ``q, k, v = silu(conv(u Wq)), silu(conv(u Wk)), silu(conv(u Wv))``: a
     causal depthwise convolution of 4 taps a channel, ``out_t = sum_j w_j
     z_(t - 3 + j)``, zeros before the sequence's start [p]; no bias [a]; the
     last tap reads the position itself [a: the tap order].
  2. ``q_t <- q_t / |q_t| / sqrt(128)``, ``k_t <- k_t / |k_t|`` a head, in
     float32 [p: L2-normalised queries and keys, the scale ``d_k^-1/2`` on the
     query]; ``|z| = sqrt(sum z^2 + 1e-6)`` [a: the epsilon].
  3. ``g_t = -exp(A_log[h]) softplus((u_t Fa) Fb + dt_bias)``, a vector over
     the head's 128 key channels, ``<= 0`` [p: the fine-grained, channel-wise
     decay]; ``Fa [d, 128]``, ``Fb [128, H x 128]``: ``kda_use_full_proj``
     false read as the low-rank gate, rank ``head_dim`` [a]; ``A_log [H]`` and
     ``dt_bias [H x 128]`` parameters [a: the Mamba-2 family's parametrisation
     and initialiser, ``config.DT_BIAS`` and ``config.A_LOG``].
  4. ``beta_t = 2 sigmoid(u_t Wb)[h]`` in (0, 2): ``kda_allow_neg_eigval``
     true doubles the sigmoid, so that ``I - beta k k^T`` has an eigenvalue in
     (-1, 1) [c, p].
  5. ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_(t-1) + beta_t k_t
     v_t^T``, ``S`` ``[128 x 128]`` float32, zero at a sequence's start; ``o_t
     = S_t^T q_t`` [p, equations 1 and 2]. ONE POSITION AT A TIME here.
  6. ``y_t = (RMSNorm_128(o_t) * o_norm * sigmoid((u_t Ga) Gb)) Wo``: each
     head's 128 channels normed (eps ``rms_norm_eps``) with one ``[128]``
     weight for every head, a low-rank sigmoid output gate of rank 128 [p: the
     head-wise norm and the gate; a: its low-rank form under
     ``kda_use_full_proj`` false].

- GQA, attention (``num_attention_heads`` 64 on ``num_key_value_heads`` 8 of
  ``head_dim`` 128) [c]: ``q = u Wq``, ``k, v = u Wk, u Wv``, no biases; NO
  position encoding (``use_rope`` false) [c]; causal softmax at ``128^-1/2``;
  query head ``h`` reads key/value head ``h // 8``; ``y = (o * sigmoid(u
  Wg)) Wo`` with ``Wg [d, H x 128]`` (``use_gqa_gate``) [c]; the gate
  element-wise, a sigmoid, read from the normed input, applied before ``Wo``
  [a]; no QK-norm [a: no key].
- Experts: ``s = sigmoid(u Wr)`` in float32 over ``n_routed_experts`` 320 [a:
  the scoring function; ``norm_topk_prob`` and ``routed_scaling_factor`` mark
  the family]; the ``num_experts_per_tok`` 8 largest of ``s + b`` chosen (ties
  to the lower id), ``b`` the correction bias: it enters the choice and nothing
  else, no gradient reaches it, and the rule that moves it is left out, so it
  stays 0 [a]; gates ``w = 1.0 s_sel / sum(s_sel)`` (``norm_topk_prob``,
  ``routed_scaling_factor`` 1) [c]; an expert is SwiGLU of width
  ``moe_intermediate_size`` 1,280 [c]; the shared expert the same at
  ``n_shared_experts x moe_intermediate_size`` [a: ``intermediate_size``
  10,240 is read by no layer], added ungated [c]. Only experts ``first_held ..
  first_held + held`` are here: what the others would add is left out (the
  chip's share of an expert-parallel layer).

**A share of the heads.** ``cfg.kda_heads``, ``cfg.n_heads`` and
``cfg.n_kv_heads`` may be one chip's share of each layer's heads (the leaves
then hold those heads' columns of ``wq``, ``wk``, ``wv``, ``Fb``, ``Gb``,
``Wb``, ``Wg``, of the convolutions, ``A_log`` and ``dt_bias``, and those
heads' rows of ``wo``; the gates' ``Fa`` and ``Ga``, the norms, the router and
the shared expert are whole): ``wo``'s output is then the held heads' part of
the layer's sum and goes on as it is, as an absent expert's does
(``parallel/moe.py``). The shares add up: ``tests/test_decoder_lm_solar.py``.

Head: final RMSNorm, logits over the untied head (``tie_word_embeddings``
false) [c]; mean next-token cross-entropy. No auxiliary loss [a]. Packed
documents carry no mask: state, convolution and attention cross document
boundaries inside a sequence, and never cross sequences [a]. AdamW decays
every parameter [a].
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from flink_ml_tpu.models.lm import reference as olmoe
from flink_ml_tpu.models.lm import reference_laguna as laguna
from flink_ml_tpu.models.lm.config import LMConfig

__all__ = ["forward", "loss", "loss_and_grads", "train_steps", "log_likelihood", "layer", "kda", "delta_rule",
           "attention", "UNIT_EPS"]

rms_norm = olmoe.rms_norm
swiglu, moe = laguna.swiglu, laguna.moe
UNIT_EPS = 1e-6


def _conv(z, w):
    """``silu`` of the causal depthwise convolution of ``z [T, C]`` with ``w [taps, C]``, zeros before position 0."""
    taps, t = w.shape[0], z.shape[0]
    earlier = jnp.concatenate([jnp.zeros((taps - 1, z.shape[1]), z.dtype), z])
    return jax.nn.silu(sum(w[j] * earlier[j: j + t] for j in range(taps)))


def delta_rule(q, k, v, g, beta):
    """``o [T, H, D]`` of the recurrence (5.) one position at a time from ``S = 0``: ``q``, ``k``, ``v``, ``g`` ``[T,
    H, D]``, ``beta [T, H]``."""
    def position(state, now):  # state [H, D_k, D_v]
        q_t, k_t, v_t, g_t, beta_t = now
        state = jnp.exp(g_t)[:, :, None] * state
        state = state - (beta_t[:, None] * k_t)[:, :, None] * jnp.einsum("hkv,hk->hv", state, k_t)[:, None, :]
        state = state + (beta_t[:, None] * k_t)[:, :, None] * v_t[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    heads, d = q.shape[1:]
    _, o = jax.lax.scan(position, jnp.zeros((heads, d, d), q.dtype), (q, k, v, g, beta))
    return o


def kda(u, w, cfg: LMConfig):
    """The delta-rule mixer on one sequence ``u [T, d]`` (already normed)."""
    t = u.shape[0]
    heads, d = cfg.kda_heads, cfg.kda_head_dim
    q, k, v = (_conv(u @ w[proj], w[taps]).reshape(t, heads, d)
               for proj, taps in (("wq", "conv_q"), ("wk", "conv_k"), ("wv", "conv_v")))
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + UNIT_EPS) / jnp.sqrt(float(d))
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + UNIT_EPS)
    dt = jax.nn.softplus((u @ w["Fa"]) @ w["Fb"] + w["dt_bias"]).reshape(t, heads, d)
    g = -jnp.exp(w["A_log"])[:, None] * dt
    beta = 2.0 * jax.nn.sigmoid(u @ w["Wb"])  # [T, H]
    o = delta_rule(q, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps) * w["o_norm"]
    gate = jax.nn.sigmoid((u @ w["Ga"]) @ w["Gb"])
    return (o.reshape(t, heads * d) * gate) @ w["wo"]


def attention(u, w, cfg: LMConfig):
    """Causal attention of one sequence ``u [T, d]`` on grouped queries, no position encoding, a gated output."""
    t = u.shape[0]
    heads, kv, d = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    q = (u @ w["wq"]).reshape(t, heads, d)
    k = jnp.repeat((u @ w["wk"]).reshape(t, kv, d), heads // kv, axis=1)
    v = jnp.repeat((u @ w["wv"]).reshape(t, kv, d), heads // kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * (d ** -0.5)
    keep = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1), v)
    return (o.reshape(t, heads * d) * jax.nn.sigmoid(u @ w["wg"])) @ w["wo"]


def layer(x, w, cfg: LMConfig, attends: bool):
    """One layer on one sequence ``x [T, d]``; returns it and the chosen experts ``[T, k]``."""
    u = rms_norm(x, w["attn_norm"], cfg.norm_eps)
    x = x + (attention(u, w, cfg) if attends else kda(u, w, cfg))
    u = rms_norm(x, w["ffn_norm"], cfg.norm_eps)
    y, chosen = moe(u, w, cfg)
    return x + y + swiglu(u, w["shared_gate"], w["shared_up"], w["shared_down"]), chosen


def forward(params, tok, cfg: LMConfig):
    """Logits ``[B, T, V]`` and, per layer, the chosen experts ``[B, T, k]``."""
    logits, chosen = [], []
    for row in tok:
        x, picks = params["embed"][row], []
        for i, w in enumerate(params["layers"]):
            x, e = layer(x, w, cfg, i in cfg.gqa_layers)
            picks.append(e)
        logits.append(rms_norm(x, params["final_norm"], cfg.norm_eps) @ params["lm_head"])
        chosen.append(jnp.stack(picks))
    return jnp.stack(logits), jnp.stack(chosen, axis=1)  # [B, T, V], [L, B, T, k]


def loss(params, tok, cfg: LMConfig):
    """Mean next-token cross-entropy over every sequence's ``T - 1`` targets."""
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(params, tok, cfg)
        return -jnp.mean(olmoe.token_log_probs(logits, tok))


def loss_and_grads(params, tok, cfg: LMConfig):
    return jax.value_and_grad(loss)(params, tok, cfg)


def log_likelihood(params, tok, cfg: LMConfig):
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(params, tok, cfg)
        return jnp.mean(olmoe.token_log_probs(logits, tok), axis=1)


def train_steps(params, batches, cfg: LMConfig, lr, **adamw):
    """``len(batches)`` AdamW steps (``reference.adamw_step``) from ``params``.
    Returns ``(params, losses, grad_norms)``."""
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, norms = [], []
    for i, tok in enumerate(batches):
        value, grads = loss_and_grads(params, tok, cfg)
        params, m, v, norm = olmoe.adamw_step(params, m, v, grads, i + 1, lr, **adamw)
        losses.append(float(value))
        norms.append(float(norm))
    return params, losses, norms
