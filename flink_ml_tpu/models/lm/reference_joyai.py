"""The plain reference of the ``joyai`` block kind (JoyAI-LLM-Flash's decoder
layers and its multi-token-prediction module): forward, loss, gradients and
AdamW steps in straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``.

A Python loop over sequences and layers, positions as whole arrays: keys and
values rebuilt from the latent in the plain way, ``[H, T, T]`` scores with the
causal mask; every held expert applied to every token and masked; ``jax.grad``
for the gradients; no kernel, no sort, no recomputation. It shares with the
stage only the parameter tree's layout (``config.py``); the experts and the
SwiGLU are ``reference_laguna.py``'s, AdamW, the clip and the cross-entropy
``reference.py``'s, which are plain themselves.

Origin of each equation. [c]: a key of the model's ``config.json``
(https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json,
``model_type`` ``joyai_llm_flash``; the keys are the ``deepseek_v3`` family's).
[p]: DeepSeek-V3's report, arXiv:2412.19437, sections 2.1 (latent attention,
the experts) and 2.2 (multi-token prediction), whose keys the file uses. [a]:
assumed here, a detail neither fixes; the benchmark's configuration lists each
under ``assumed`` with these words.

Layer ``i``, residual ``x [T, d]``, ``num_attention_heads`` 32 heads, eps
``rms_norm_eps`` 1e-6, no biases (``attention_bias`` false) [c]:

- ``a = RMSNorm(x)``. Queries [c: ``q_lora_rank`` 1,536, ``qk_nope_head_dim``
  128, ``qk_rope_head_dim`` 64; p: eq. 6-9]: ``c_q = RMSNorm(a W_qa)``, ``q =
  c_q W_qb``, a head ``[q_nope 128 | q_rope 64]``.
- Keys and values [c: ``kv_lora_rank`` 512, ``v_head_dim`` 128; p: eq. 1-5]:
  ``[c_kv 512 | k_rope 64] = a W_kva``; ``c_kv = RMSNorm(c_kv)``; ``c_kv W_kvb``
  gives, a head, ``[k_nope 128 | v 128]``. ONE ``k_rope`` a token, under every
  head.
- RoPE [c: ``rope_theta`` 3.2e7, ``rope_scaling`` null, ``rope_interleave``
  true] turns ``q_rope`` of every head and the one ``k_rope`` on INTERLEAVED
  pairs: channels ``2 j``, ``2 j + 1`` by the angle ``pos theta^(-2 j / 64)``.
  ``k = [k_nope | k_rope]``.
- ``o = softmax(q k^T 192^-1/2 + causal mask) v``, 32 heads of 128; ``x <- x +
  concat(o) W_o`` (``[4096, d]``) [c: ``qk_head_dim`` 192; p: eq. 10-11]. No
  QK-norm beside the latents', no gate, no window [a: no key for any].
- ``u = RMSNorm(x)``. Layer ``i < first_k_dense_replace`` 1: ``x <- x +
  SwiGLU_7168(u)`` (``intermediate_size``) [c]. Else [c: ``scoring_func``
  ``sigmoid``, ``topk_method`` ``noaux_tc``, ``n_group`` 1 and ``topk_group`` 1
  (the group limit is trivial), ``norm_topk_prob`` true,
  ``routed_scaling_factor`` 2.5, ``n_shared_experts`` 1; p: eq. 12-16]: ``s =
  sigmoid(u W_r)`` over all ``n_routed_experts`` 256; the
  ``num_experts_per_tok`` 8 largest of ``s + b`` (ties to the lower id), ``b``
  the selection bias ``e_score_correction_bias``: no gradient reaches it, and
  the rule that moves it is left out, so it stays at its initial 0 [a]; gates
  ``g = 2.5 s_sel / (sum s_sel + 1e-20)``; ``x <- x + sum_held g_e E_e(u) +
  S(u)``, ``E_e`` and the shared expert ``S`` SwiGLU of width
  ``moe_intermediate_size`` 768 [c]. Only experts ``first_held .. first_held +
  held`` are here: what the others would add is left out (the chip's share of
  an expert-parallel layer; the ``model-configs`` guide, section 4).

Head: final RMSNorm, logits over the untied head (``tie_word_embeddings``
false) [c]; ``nll_main``: mean next-token cross-entropy over the ``T - 1``
positions that have a target.

The multi-token-prediction module [c: ``num_nextn_predict_layers`` 1; p:
section 2.2, eq. 21-25], with ``h`` the stack's output BEFORE the final norm
[a: the report's ``h^0`` is "the representation given by the main model"] and
``e_(i+1)`` the shared embedding of token ``i + 1``: ``h'_i = [RMSNorm(h_i;
hnorm) | RMSNorm(e_(i+1); enorm)] W_eh`` (``[2 d, d]``; hidden first [a: the
report's order]) for positions ``0 .. T - 2``; ``h'' = Layer(h')``, one expert
layer of its own as above on those ``T - 1`` positions from position 0; logits
``RMSNorm(h''; norm) W_head`` through the main head's matrix; position ``i``'s
target is token ``i + 2``: ``nll_mtp`` is the mean over the ``T - 2`` positions
that have one. ``loss = nll_main + lambda nll_mtp``, ``lambda`` 0.3 [a: the
report's first value; the file has none].

No auxiliary loss [a]. Packed documents attend across their boundaries [a].
AdamW decays every parameter.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from flink_ml_tpu.models.lm import reference as olmoe
from flink_ml_tpu.models.lm import reference_laguna as laguna
from flink_ml_tpu.models.lm.config import LMConfig

__all__ = ["forward", "losses", "loss", "loss_and_grads", "train_steps", "log_likelihood", "layer", "attention",
           "turn_pairs"]

rms_norm = olmoe.rms_norm


def turn_pairs(x, theta: float):
    """RoPE on interleaved pairs of ``x [T, ..., D]``, all ``D`` channels: ``2 j`` and ``2 j + 1`` by ``pos
    theta^(-2 j / D)``."""
    t, d = x.shape[0], x.shape[-1]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * (theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = angle.reshape(t, *(1,) * (x.ndim - 2), d // 2)
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * jnp.cos(angle) - x1 * jnp.sin(angle), x1 * jnp.cos(angle) + x0 * jnp.sin(angle)],
                     axis=-1).reshape(x.shape)


def attention(a, w, cfg: LMConfig):
    """Latent attention of one sequence ``a [T, d]`` (already normed)."""
    t, h = a.shape[0], cfg.n_heads
    nope, rope, dv = cfg.nope_dim, cfg.rope_dim, cfg.v_dim
    q = (rms_norm(a @ w["wq_a"], w["q_a_norm"], cfg.norm_eps) @ w["wq_b"]).reshape(t, h, nope + rope)
    down = a @ w["wkv_a"]
    c_kv, k_rope = down[:, :cfg.kv_rank], down[:, cfg.kv_rank:]
    up = (rms_norm(c_kv, w["kv_a_norm"], cfg.norm_eps) @ w["wkv_b"]).reshape(t, h, nope + dv)
    q = jnp.concatenate([q[..., :nope], turn_pairs(q[..., nope:], cfg.rope_theta)], axis=-1)
    k_rope = jnp.broadcast_to(turn_pairs(k_rope, cfg.rope_theta)[:, None, :], (t, h, rope))  # one key, every head
    k, v = jnp.concatenate([up[..., :nope], k_rope], axis=-1), up[..., nope:]
    s = jnp.einsum("qhd,khd->hqk", q, k) * ((nope + rope) ** -0.5)
    pos = jnp.arange(t)
    s = jnp.where((pos[:, None] >= pos[None, :])[None], s, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v).reshape(t, h * dv) @ w["wo"]


def layer(x, w, cfg: LMConfig):
    """One layer on one sequence ``x [T, d]``; returns it and the chosen
    experts ``[T, k]`` (None for a dense layer)."""
    x = x + attention(rms_norm(x, w["attn_norm"], cfg.norm_eps), w, cfg)
    u = rms_norm(x, w["ffn_norm"], cfg.norm_eps)
    if "router" not in w:
        return x + laguna.swiglu(u, w["w_gate"], w["w_up"], w["w_down"]), None
    y, chosen = laguna.moe(u, w, cfg)
    return x + y + laguna.swiglu(u, w["shared_gate"], w["shared_up"], w["shared_down"]), chosen


def forward(params, tok, cfg: LMConfig):
    """The main head's logits ``[B, T, V]``, the module's ``[B, T - 1, V]``
    (position ``i`` predicts token ``i + 2``; None without a module) and, per
    expert layer, the chosen experts: the stack's ``[L, B, T, k]`` and the
    module's ``[B, T - 1, k]``."""
    logits, ahead, chosen, chosen_ahead = [], [], [], []
    for row in tok:
        x, picks = params["embed"][row], []
        for w in params["layers"]:
            x, e = layer(x, w, cfg)
            if e is not None:
                picks.append(e)
        logits.append(rms_norm(x, params["final_norm"], cfg.norm_eps) @ params["lm_head"])
        chosen.append(jnp.stack(picks))
        if cfg.mtp_depth:
            m = params["mtp"]
            both = jnp.concatenate([rms_norm(x[:-1], m["hnorm"], cfg.norm_eps),
                                    rms_norm(params["embed"][row[1:]], m["enorm"], cfg.norm_eps)], axis=-1)
            y, e = layer(both @ m["eh_proj"], m["layer"], cfg)
            ahead.append(rms_norm(y, m["norm"], cfg.norm_eps) @ params["lm_head"])
            chosen_ahead.append(e)
    module = (jnp.stack(ahead), jnp.stack(chosen_ahead)) if cfg.mtp_depth else (None, None)
    return jnp.stack(logits), module[0], jnp.stack(chosen, axis=1), module[1]


def losses(params, tok, cfg: LMConfig):
    """``(nll_main, nll_mtp)``: the mean next-token cross-entropy over every
    sequence's ``T - 1`` targets, and the module's over its ``T - 2`` (0
    without a module)."""
    with jax.default_matmul_precision("highest"):
        logits, ahead, _, _ = forward(params, tok, cfg)
        main = -jnp.mean(olmoe.token_log_probs(logits, tok))
        if ahead is None:
            return main, jnp.float32(0.0)
        # the module's position i holds token i + 1's embedding and is scored on token i + 2: next-token
        # cross-entropy over the sequence from its second token on
        return main, -jnp.mean(olmoe.token_log_probs(ahead, tok[:, 1:]))


def loss(params, tok, cfg: LMConfig):
    main, ahead = losses(params, tok, cfg)
    return main + cfg.mtp_coef * ahead


def loss_and_grads(params, tok, cfg: LMConfig):
    return jax.value_and_grad(loss)(params, tok, cfg)


def log_likelihood(params, tok, cfg: LMConfig):
    """The main head's alone: the module is a training objective."""
    with jax.default_matmul_precision("highest"):
        logits, _, _, _ = forward(params, tok, cfg)
        return jnp.mean(olmoe.token_log_probs(logits, tok), axis=1)


def train_steps(params, batches, cfg: LMConfig, lr, **adamw):
    """``len(batches)`` AdamW steps (``reference.adamw_step``) from ``params``.
    Returns ``(params, losses, grad_norms)``."""
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    values, norms = [], []
    for i, tok in enumerate(batches):
        value, grads = loss_and_grads(params, tok, cfg)
        params, m, v, norm = olmoe.adamw_step(params, m, v, grads, i + 1, lr, **adamw)
        values.append(float(value))
        norms.append(float(norm))
    return params, values, norms
