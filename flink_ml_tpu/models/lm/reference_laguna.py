"""The plain reference of the ``laguna`` block kind (Laguna-XS.2's decoder
layers): forward, loss, gradients and AdamW steps in straightforward
``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``.

A Python loop over sequences and layers, positions as whole arrays: ``[H, T,
T]`` scores with the causal mask and, on a windowed layer, the window's mask;
every held expert applied to every token and masked; ``jax.grad`` for the
gradients; no kernel, no sort, no recomputation. It shares with the stage only
the parameter tree's layout (``config.py``); AdamW, the clip and the loss's
form are ``reference.py``'s, which are plain themselves.

Origin of each equation. [c]: a key of the model's ``config.json``
(https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json,
``model_type`` ``laguna``). [a]: assumed here, a detail the file does not fix;
the benchmark's configuration lists each under ``assumed`` with these words.

Layer ``i``, residual ``x [T, d]``, ``H_i = num_attention_heads_per_layer[i]``
query heads (48 on a full layer, 64 on a windowed one) on 8 key/value heads of
``D`` 128 channels, eps ``rms_norm_eps`` 1e-6 [c]:

- ``a = RMSNorm(x)``; ``q = a Wq [d, H_i D]``, ``k = a Wk [d, 8 D]``, ``v = a
  Wv [d, 8 D]``, no biases (``attention_bias`` false) [c]; no QK-norm [a: the
  file has no key for one].
- RoPE, rotate-half [c: ``rope_parameters``]. A windowed layer
  (``sliding_attention``) turns all ``D`` channels at theta 1e4, ``rope_type``
  default. A full layer (``full_attention``) turns the first
  ``partial_rotary_factor`` 0.5 of them at theta 5e5 under YaRN (factor 64,
  original length 4,096, ``beta_fast`` 64, ``beta_slow`` 1): pair ``j`` of the
  64 turned channels has frequency ``f_j = theta^(-2j/64)``; the correction
  dimension of ``r`` rotations is ``64 ln(4096 / (2 pi r)) / (2 ln theta)``,
  ``low = floor`` of it at ``beta_fast``, ``high = ceil`` of it at
  ``beta_slow`` (both clipped to the pairs there are); the ramp ``g_j =
  clip((j - low) / (high - low), 0, 1)``; the frequency used is ``g_j f_j / 64
  + (1 - g_j) f_j``; ``cos`` and ``sin`` are multiplied by
  ``attention_factor`` 1.4158883 (so the scores by its square) [a: the
  correction range is truncated to whole pairs, the family's default].
- Scores at ``D^-1/2``, causal; on a windowed layer key ``j`` is visible to
  query ``t`` iff ``t - sliding_window < j <= t`` [a: the 512 keys ending at
  the query]; query head ``h`` reads key/value head ``h // (H_i / 8)``.
- ``gating`` true [c] is read as a per-head output gate: ``g = sigmoid(a
  Wg)``, ``Wg [d, H_i]``, head ``h``'s output times ``g_h`` before ``Wo [H_i D,
  d]`` [a: the gate's shape follows from the published parameter count, an
  element-wise gate would make 34.07 B; the sigmoid is assumed]. ``x <- x +
  concat(o) Wo``.
- ``u = RMSNorm(x)``. A dense layer (``mlp_layer_types`` ``dense``: layer 0):
  ``x <- x + (silu(u Wg1) * u Wu1) Wd1``, width ``intermediate_size`` 8,192
  [c]. A sparse layer: ``s = sigmoid(u Wr)`` in float32 over all
  ``num_experts`` [a: no scoring key; ``moe_routed_scaling_factor`` 2.5 marks
  the sigmoid family]; the ``num_experts_per_tok`` 8 largest of ``s + b``
  (ties to the lower id), ``b`` the balancing bias: it starts at 0 and moves
  by a rule outside the gradient, which is left out, so it stays 0 [a];
  weights ``w = 2.5 * s_sel / sum(s_sel)`` [c: the factor; a: that the chosen
  are renormalised]; ``x <- x + sum_held w_e E_e(u) + S(u)``, ``E_e`` and the
  shared expert ``S`` SwiGLU of width 512 (``moe_intermediate_size``,
  ``shared_expert_intermediate_size``) [c]. Only experts ``first_held ..
  first_held + held`` are here: what the others would add is left out (the
  chip's share of an expert-parallel layer; the ``model-configs`` guide,
  section 4); ``S`` is computed whole, as on every chip of the group.

Head: final RMSNorm, logits over the untied head (``tie_word_embeddings``
false) [c]; mean next-token cross-entropy. No auxiliary loss [a]. Packed
documents attend across their boundaries [a]. AdamW decays every parameter.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from flink_ml_tpu.models.lm import reference as olmoe
from flink_ml_tpu.models.lm.config import LMConfig

__all__ = ["forward", "loss", "loss_and_grads", "train_steps", "log_likelihood", "layer", "attention", "moe",
           "yarn_inv_freq", "swiglu"]

rms_norm = olmoe.rms_norm


def yarn_inv_freq(rot: int, theta: float, factor, original, beta_fast, beta_slow) -> np.ndarray:
    """The ``rot / 2`` frequencies of a full layer's RoPE under YaRN, float64."""
    f = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)

    def correction_dim(rotations):
        return rot * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), rot - 1)
    g = np.clip((np.arange(rot // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return g * f / factor + (1.0 - g) * f


def turn(x, inv_freq, scale=1.0):
    """Rotate-half RoPE on the first ``2 * len(inv_freq)`` channels of each
    head of ``x [T, H, D]``, ``cos`` and ``sin`` times ``scale``."""
    rot = 2 * len(inv_freq)
    freqs = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    x1, x2, rest = x[..., : rot // 2], x[..., rot // 2: rot], x[..., rot:]
    turned = x[..., :rot] * (scale * jnp.cos(emb)) + jnp.concatenate([-x2, x1], axis=-1) * (scale * jnp.sin(emb))
    return jnp.concatenate([turned, rest], axis=-1)


def attention(a, w, cfg: LMConfig, window: int):
    """Gated attention of one sequence ``a [T, d]`` (already normed), full
    (``window`` 0) or over the ``window`` keys ending at each query."""
    t = a.shape[0]
    kv, d = cfg.kv_heads, cfg.head_dim
    heads = w["head_gate"].shape[1]
    q = (a @ w["wq"]).reshape(t, heads, d)
    k = (a @ w["wk"]).reshape(t, kv, d)
    v = (a @ w["wv"]).reshape(t, kv, d)
    if window:
        inv_freq = 1.0 / (cfg.window_rope_theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
        q, k = turn(q, inv_freq), turn(k, inv_freq)
    else:
        rot = int(d * cfg.rope_fraction)
        if cfg.yarn:
            inv_freq, scale = yarn_inv_freq(rot, cfg.rope_theta, *cfg.yarn[:4]), cfg.yarn[4]
        else:
            inv_freq, scale = 1.0 / (cfg.rope_theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)), 1.0
        q, k = turn(q, inv_freq, scale), turn(k, inv_freq, scale)
    k, v = jnp.repeat(k, heads // kv, axis=1), jnp.repeat(v, heads // kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * (d ** -0.5)
    pos = jnp.arange(t)
    keep = pos[:, None] >= pos[None, :]
    if window:
        keep &= pos[None, :] > pos[:, None] - window
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1), v)
    gate = jax.nn.sigmoid(a @ w["head_gate"])  # [T, H]
    return (o * gate[:, :, None]).reshape(t, heads * d) @ w["wo"]


def swiglu(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def moe(u, w, cfg: LMConfig):
    """The routed part of a sparse layer on ``u [T, d]``: every HELD expert on
    every token, the unchosen masked. Returns it and the chosen experts ``[T, k]``."""
    s = jax.nn.sigmoid(u @ w["router"])
    _, chosen = jax.lax.top_k(s + w["router_bias"], cfg.top_k)  # ties: the lower id
    picked = jnp.take_along_axis(s, chosen, axis=1)
    weight = cfg.routed_scale * picked / jnp.sum(picked, axis=1, keepdims=True)  # [T, k]
    y = jnp.zeros_like(u)
    for j in range(cfg.held):
        w_j = jnp.sum(jnp.where(chosen == cfg.first_held + j, weight, 0.0), axis=1)
        y = y + w_j[:, None] * swiglu(u, w["w_gate"][j], w["w_up"][j], w["w_down"][j])
    return y, chosen


def layer(x, w, cfg: LMConfig, window: int):
    """One layer on one sequence ``x [T, d]``; returns it and the chosen
    experts ``[T, k]`` (None for a dense layer)."""
    x = x + attention(rms_norm(x, w["attn_norm"], cfg.norm_eps), w, cfg, window)
    u = rms_norm(x, w["ffn_norm"], cfg.norm_eps)
    if "router" not in w:
        return x + swiglu(u, w["w_gate"], w["w_up"], w["w_down"]), None
    y, chosen = moe(u, w, cfg)
    return x + y + swiglu(u, w["shared_gate"], w["shared_up"], w["shared_down"]), chosen


def forward(params, tok, cfg: LMConfig):
    """Logits ``[B, T, V]`` and, per sparse layer, the chosen experts ``[B, T, k]``."""
    logits, chosen = [], []
    for row in tok:
        x, picks = params["embed"][row], []
        for w, window in zip(params["layers"], cfg.layer_windows):
            x, e = layer(x, w, cfg, window)
            if e is not None:
                picks.append(e)
        logits.append(rms_norm(x, params["final_norm"], cfg.norm_eps) @ params["lm_head"])
        chosen.append(jnp.stack(picks))
    return jnp.stack(logits), jnp.stack(chosen, axis=1)  # [B, T, V], [L_sparse, B, T, k]


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
