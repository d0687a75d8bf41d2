"""The plain reference of the ``nemotron_h`` block kind (Nemotron-3-Nano's
hybrid decoder): forward, loss, gradients and AdamW steps in straightforward
``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``.

A Python loop over sequences and layers; the state-space layer as the
step-by-step recurrence, one position at a time (``lax.scan`` over positions,
nothing chunked); ``[H, T, T]`` scores with the causal mask; every held expert
applied to every token and masked; ``jax.grad`` for the gradients; no kernel,
no sort, no recomputation. It shares with the stage only the parameter tree's
layout (``config.py``); AdamW, the clip and the loss's form are
``reference.py``'s, which are plain themselves.

Origin of each equation. [c]: a key of the model's ``config.json``
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json,
``model_type`` ``nemotron_h``). [a]: assumed here, a detail the file does not
fix; the benchmark's configuration lists each under ``assumed`` with these
words.

Layer ``i`` is ``x <- x + mixer_i(RMSNorm(x; w_i))`` with eps ``norm_eps`` 1e-5
[c], its mixer the ``i``-th letter of ``hybrid_override_pattern`` [c]:

- ``M``, Mamba-2 (``mamba_num_heads`` H 64, ``mamba_head_dim`` P 64, so an
  inner width of 4,096; ``n_groups`` G 8; ``ssm_state_size`` N 128;
  ``conv_kernel`` 4) [c]. ``[z | xBC | dt] = u W_in`` (4,096 | 4,096 + 2 x 8 x
  128 | 64), no bias (``mamba_proj_bias`` false) [c]. ``xBC <- silu(conv(xBC))``
  (``mamba_hidden_act`` silu [c]): a causal depthwise convolution with bias
  (``use_conv_bias``) [c], ``out_t = b + sum_j w_j xBC_(t - 3 + j)``, zeros
  before the sequence's start [a: the tap order, the last tap reads the
  position itself]. Split ``x [T, H, P]``, ``B``, ``C`` ``[T, G, N]``; head
  ``h`` reads group ``h // (H / G)``. ``delta = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; per head ``S_t = exp(delta_t A) S_(t-1) + delta_t x_t B_t^T``
  (``S`` is ``P x N``, zero at the sequence's start), ``y_t = S_t C_t + D x_t``.
  Then the gated norm: ``y * silu(z)`` RMS-normed over groups of ``4,096 / G``
  channels, times one ``[4,096]`` weight [a: the gate before the norm, the
  family's ``norm_before_gate`` false]. ``out = y W_out``.
- ``*``, attention: ``q = u Wq`` on ``num_attention_heads`` 32 heads of
  ``head_dim`` 128, ``k``, ``v`` on ``num_key_value_heads`` 2, no biases [c];
  NO position encoding [a: the family's attention applies none; the file's
  ``rope_theta`` and ``partial_rotary_factor`` are read by no layer]; causal
  softmax at ``128^-1/2``; query head ``h`` reads key/value head ``h // 16``;
  ``out = o Wo``. No QK-norm, no gate [a: no key].
- ``E``, experts: ``s = sigmoid(u Wr)`` in float32 over ``n_routed_experts``
  128 [a: the scoring function; ``routed_scaling_factor`` marks the family];
  the ``num_experts_per_tok`` 6 largest of ``s + b`` chosen (ties to the lower
  id; ``n_group`` 1: no group limit [c]), ``b`` the correction bias: it enters
  the choice and nothing else, no gradient reaches it, and the rule that moves
  it is left out, so it stays 0 [a]; gates ``w = 2.5 s_sel / sum(s_sel)``
  (``norm_topk_prob``, ``routed_scaling_factor``) [c]; an expert is
  ``relu(u W_up)^2 W_down`` of width ``moe_intermediate_size`` 1,856
  (``mlp_hidden_act`` relu2, ``mlp_bias`` false) [c]; the shared expert the
  same at ``moe_shared_expert_intermediate_size`` 3,712, added ungated [c].
  Only experts ``first_held .. first_held + held`` are here: what the others
  would add is left out (the chip's share of an expert-parallel layer).

Head: final RMSNorm, logits over the untied head (``tie_word_embeddings``
false) [c]; mean next-token cross-entropy. No auxiliary loss [a]. Packed
documents carry no mask: state, convolution and attention cross document
boundaries inside a sequence, and never cross sequences [a]. AdamW decays
every parameter.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from flink_ml_tpu.models.lm import reference as olmoe
from flink_ml_tpu.models.lm.config import LMConfig

__all__ = ["forward", "loss", "loss_and_grads", "train_steps", "log_likelihood", "layer", "mamba", "attention",
           "experts", "relu2"]

rms_norm = olmoe.rms_norm


def mamba(u, w, cfg: LMConfig):
    """The Mamba-2 mixer on one sequence ``u [T, d]`` (already normed)."""
    t = u.shape[0]
    heads, p, groups, n, taps = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state, cfg.conv_kernel
    inner, bc = heads * p, groups * n
    proj = u @ w["in_proj"]
    z, xbc, dt = proj[:, :inner], proj[:, inner: 2 * inner + 2 * bc], proj[:, 2 * inner + 2 * bc:]
    earlier = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1])), xbc])
    xbc = jax.nn.silu(w["conv_b"] + sum(w["conv_w"][j] * earlier[j: j + t] for j in range(taps)))
    x = xbc[:, :inner].reshape(t, heads, p)
    b = jnp.repeat(xbc[:, inner: inner + bc].reshape(t, groups, n), heads // groups, axis=1)  # [T, H, N]
    c = jnp.repeat(xbc[:, inner + bc:].reshape(t, groups, n), heads // groups, axis=1)
    delta = jax.nn.softplus(dt + w["dt_bias"])  # [T, H]
    a = -jnp.exp(w["A_log"])

    def position(state, now):  # state [H, P, N]
        x_t, b_t, c_t, delta_t = now
        state = jnp.exp(delta_t * a)[:, None, None] * state + (delta_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    _, y = jax.lax.scan(position, jnp.zeros((heads, p, n)), (x, b, c, delta))
    y = ((y + w["D"][:, None] * x).reshape(t, inner) * jax.nn.silu(z)).reshape(t, groups, inner // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.norm_eps)
    return (y.reshape(t, inner) * w["gate_norm"]) @ w["out_proj"]


def attention(u, w, cfg: LMConfig):
    """Causal attention of one sequence ``u [T, d]`` on grouped queries, no position encoding."""
    t = u.shape[0]
    heads, kv, d = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    q = (u @ w["wq"]).reshape(t, heads, d)
    k = jnp.repeat((u @ w["wk"]).reshape(t, kv, d), heads // kv, axis=1)
    v = jnp.repeat((u @ w["wv"]).reshape(t, kv, d), heads // kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * (d ** -0.5)
    keep = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1), v)
    return o.reshape(t, heads * d) @ w["wo"]


def relu2(u, up, down):
    return jnp.square(jax.nn.relu(u @ up)) @ down


def experts(u, w, cfg: LMConfig):
    """The routed part of an expert layer on ``u [T, d]``: every HELD expert on
    every token, the unchosen masked. Returns it and the chosen experts ``[T, k]``."""
    s = jax.nn.sigmoid(u @ w["router"])
    _, chosen = jax.lax.top_k(s + w["router_bias"], cfg.top_k)  # ties: the lower id
    picked = jnp.take_along_axis(s, chosen, axis=1)
    weight = cfg.routed_scale * picked / jnp.sum(picked, axis=1, keepdims=True)  # [T, k]
    y = jnp.zeros_like(u)
    for j in range(cfg.held):
        w_j = jnp.sum(jnp.where(chosen == cfg.first_held + j, weight, 0.0), axis=1)
        y = y + w_j[:, None] * relu2(u, w["w_up"][j], w["w_down"][j])
    return y, chosen


def layer(x, w, cfg: LMConfig, kind: str):
    """One layer on one sequence ``x [T, d]``; returns it and the chosen
    experts ``[T, k]`` (None for a layer without experts)."""
    u = rms_norm(x, w["norm"], cfg.norm_eps)
    if kind == "M":
        return x + mamba(u, w, cfg), None
    if kind == "*":
        return x + attention(u, w, cfg), None
    y, chosen = experts(u, w, cfg)
    return x + y + relu2(u, w["shared_up"], w["shared_down"]), chosen


def forward(params, tok, cfg: LMConfig):
    """Logits ``[B, T, V]`` and, per expert layer, the chosen experts ``[B, T, k]``."""
    logits, chosen = [], []
    for row in tok:
        x, picks = params["embed"][row], []
        for w, kind in zip(params["layers"], cfg.layer_kinds):
            x, e = layer(x, w, cfg, kind)
            if e is not None:
                picks.append(e)
        logits.append(rms_norm(x, params["final_norm"], cfg.norm_eps) @ params["lm_head"])
        chosen.append(jnp.stack(picks) if picks else jnp.zeros((0, len(row), cfg.top_k), jnp.int32))
    return jnp.stack(logits), jnp.stack(chosen, axis=1)  # [B, T, V], [L_experts, B, T, k]


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
