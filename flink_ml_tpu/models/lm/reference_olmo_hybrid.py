"""The plain reference of the ``olmo_hybrid`` block kind (Olmo-Hybrid-7B's
hybrid decoder): forward, loss, gradients and AdamW steps in straightforward
``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``.

A Python loop over sequences and layers; the delta-rule layer as the
step-by-step recurrence, one position at a time (``lax.scan`` over positions,
nothing chunked, no triangular solve); ``[H, T, T]`` scores with the causal
mask; ``jax.grad`` for the gradients; no kernel, no recomputation. It shares
with the stage only the parameter tree's layout (``config.py``); AdamW, the
clip and the loss's form are ``reference.py``'s, which are plain themselves.

Origin of each equation. [c]: a key of the model's ``config.json``
(https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json,
``model_type`` ``olmo_hybrid``). [p]: the layer's paper, Gated Delta Networks,
arXiv:2412.06464 section 3, whose layer the file's ``linear_*`` keys name (the
key names of the Qwen3-Next family's implementation of it). [a]: assumed here,
a detail neither fixes; the benchmark's configuration lists each under
``assumed`` with these words.

Layer ``i`` is ``x <- x + RMSNorm(mixer_i(x))``, then ``x <- x +
RMSNorm(ffn(x))``, eps ``rms_norm_eps`` 1e-6, the stream in float32: NO norm
before a sublayer, each one's OUTPUT is normed before it joins [a: the Olmo
2/3 family's reordered norm; the file has no key for where the norms sit]. Its
mixer is ``layer_types[i]``: ``linear_attention`` three times, then
``full_attention`` [c] (``cfg.gqa_layers`` names the layers that attend).

- ``linear_attention``, the gated delta rule (``linear_num_key_heads`` =
  ``linear_num_value_heads`` H 30, ``linear_key_head_dim`` 96,
  ``linear_value_head_dim`` 192, ``linear_conv_kernel_dim`` 4) [c], per head:

  1. ``q, k, v = silu(conv(x Wq)), silu(conv(x Wk)), silu(conv(x Wv))``: a
     causal depthwise convolution of 4 taps a channel, ``out_t = sum_j w_j
     z_(t - 3 + j)``, zeros before the sequence's start [p]; no bias [a]; the
     last tap reads the position itself [a: the tap order].
  2. ``q_t <- q_t / |q_t| / sqrt(96)``, ``k_t <- k_t / |k_t|`` a head, in
     float32 [p: L2-normalised queries and keys, the scale ``d_k^-1/2`` on the
     query]; ``|z| = sqrt(sum z^2 + 1e-6)`` [a: the epsilon].
  3. ``g_t = -exp(A_log[h]) softplus(x_t Wa + dt_bias)[h]``: ONE log-decay a
     head and position, ``<= 0`` [p: the scalar gate ``alpha_t = exp(g_t)``];
     ``Wa [d, H]``, ``A_log [H]`` and ``dt_bias [H]`` parameters [a: the
     Mamba-2 family's parametrisation and initialiser, ``config.DT_BIAS`` and
     ``config.A_LOG``].
  4. ``beta_t = 2 sigmoid(x_t Wb)[h]`` in (0, 2): ``linear_allow_neg_eigval``
     true doubles the sigmoid, so that ``I - beta k k^T`` has an eigenvalue in
     (-1, 1) [c, p].
  5. ``S_t = exp(g_t) S_(t-1) + beta_t k_t (v_t - (exp(g_t) S_(t-1))^T k_t)^T``,
     ``S`` ``[96 x 192]`` float32, zero at a sequence's start; ``o_t = S_t^T
     q_t`` [p, equation 10]. ONE POSITION AT A TIME here.
  6. ``y_t = (RMSNorm_192(o_t) * o_norm * silu(x_t Wg)) Wo``: each head's 192
     channels normed (eps ``rms_norm_eps``) with one ``[192]`` weight for
     every head, gated element-wise by ``silu`` of a full projection ``Wg [d,
     H x 192]`` [p: the output gate and norm; a: the gate's activation is
     silu, the implementation family's gated norm].

- ``full_attention`` (``num_attention_heads`` 30 on ``num_key_value_heads`` 30
  of ``hidden_size / num_attention_heads`` = 128) [c]: ``q, k, v = x Wq, x Wk,
  x Wv``, no biases (``attention_bias`` false) [c]; ``q <- RMSNorm(q) *
  q_norm`` and ``k`` likewise over the WHOLE projection, all heads' channels
  together, before the heads are split [a: Olmo 2/3's QK-norm]; NO position
  encoding (``rope_parameters.rope_theta`` null) [a: read as no rotation; the
  delta-rule layers carry order]; causal softmax at ``128^-1/2``.
- Feed-forward: ``down(silu(gate(x)) * up(x))``, ``intermediate_size``
  11,008, ``hidden_act`` silu [c].

**A share of the heads.** ``cfg.kda_heads``, ``cfg.n_heads`` and
``cfg.n_kv_heads`` may be one chip's share of each layer's heads (the leaves
then hold those heads' columns of ``wq``, ``wk``, ``wv``, ``wg``, ``Wa``,
``Wb``, of the convolutions, ``A_log`` and ``dt_bias``, and those heads' rows
of ``wo``; ``o_norm``, the output norms and the dense SwiGLU are whole):
``wo``'s output is then the held heads' part of the layer's sum and is NORMED
AS IT IS - the norm sits behind the point where the deployment's all-reduce
would be, and nothing stands in for the other chips - and the attention
layer's QK-norm takes its mean square over the held channels (the deployment
would all-reduce one mean square a token). The shares add up at ``wo``'s
output, before the norm: ``tests/test_decoder_lm_olmo_hybrid.py``.

Head: final RMSNorm, logits over the untied head (``tie_word_embeddings``
false) [c]; mean next-token cross-entropy. Packed documents carry no mask:
state, convolution and attention cross document boundaries inside a sequence,
and never cross sequences [a]. AdamW decays every parameter [a].
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from flink_ml_tpu.models.lm import reference as olmoe
from flink_ml_tpu.models.lm.config import LMConfig

__all__ = ["forward", "loss", "loss_and_grads", "train_steps", "log_likelihood", "layer", "gated_delta", "delta_rule",
           "attention", "swiglu", "UNIT_EPS"]

rms_norm = olmoe.rms_norm
UNIT_EPS = 1e-6


def _conv(z, w):
    """``silu`` of the causal depthwise convolution of ``z [T, C]`` with ``w [taps, C]``, zeros before position 0."""
    taps, t = w.shape[0], z.shape[0]
    earlier = jnp.concatenate([jnp.zeros((taps - 1, z.shape[1]), z.dtype), z])
    return jax.nn.silu(sum(w[j] * earlier[j: j + t] for j in range(taps)))


def delta_rule(q, k, v, g, beta):
    """``o [T, H, D_v]`` of the recurrence (5.) one position at a time from ``S = 0``: ``q``, ``k`` ``[T, H, D_k]``,
    ``v [T, H, D_v]``, ``g`` and ``beta`` ``[T, H]``."""
    def position(state, now):  # state [H, D_k, D_v]
        q_t, k_t, v_t, g_t, beta_t = now
        state = jnp.exp(g_t)[:, None, None] * state
        seen = jnp.einsum("hkv,hk->hv", state, k_t)  # what the decayed state already says of k_t
        state = state + (beta_t[:, None] * k_t)[:, :, None] * (v_t - seen)[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(position, jnp.zeros(k.shape[1:] + v.shape[-1:], q.dtype), (q, k, v, g, beta))
    return o


def gated_delta_heads(x, w, cfg: LMConfig):
    """The delta-rule mixer's gated, normed heads ``[T, H x D_v]`` of one sequence ``x [T, d]``: what ``wo`` reads."""
    t = x.shape[0]
    heads, dk, dv = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_value_dim
    q = _conv(x @ w["wq"], w["conv_q"]).reshape(t, heads, dk)
    k = _conv(x @ w["wk"], w["conv_k"]).reshape(t, heads, dk)
    v = _conv(x @ w["wv"], w["conv_v"]).reshape(t, heads, dv)
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + UNIT_EPS) / jnp.sqrt(float(dk))
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + UNIT_EPS)
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(x @ w["Wa"] + w["dt_bias"])  # [T, H]
    beta = 2.0 * jax.nn.sigmoid(x @ w["Wb"])  # [T, H]
    o = delta_rule(q, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps) * w["o_norm"]
    return o.reshape(t, heads * dv) * jax.nn.silu(x @ w["wg"])


def gated_delta(x, w, cfg: LMConfig):
    """The delta-rule mixer on one sequence ``x [T, d]`` (the stream as it is), before the output's norm."""
    return gated_delta_heads(x, w, cfg) @ w["wo"]


def attention(x, w, cfg: LMConfig, mean_square=None):
    """Causal attention of one sequence ``x [T, d]`` without a position encoding under a QK-norm over the whole
    projection, before the output's norm. ``mean_square``: ``(of q, of k)`` ``[T, 1]`` each, handed in where the
    projections here are a share of the layer's heads and the statistic is the whole layer's (the shares test);
    left out, each is taken over the channels here."""
    t = x.shape[0]
    heads, kv, d = cfg.n_heads, cfg.kv_heads, cfg.head_dim

    def normed(z, weight, ms):
        ms = jnp.mean(z * z, axis=-1, keepdims=True) if ms is None else ms
        return z * jax.lax.rsqrt(ms + cfg.norm_eps) * weight

    given = mean_square or (None, None)
    q = normed(x @ w["wq"], w["q_norm"], given[0]).reshape(t, heads, d)
    k = jnp.repeat(normed(x @ w["wk"], w["k_norm"], given[1]).reshape(t, kv, d), heads // kv, axis=1)
    v = jnp.repeat((x @ w["wv"]).reshape(t, kv, d), heads // kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * (d ** -0.5)
    keep = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1), v)
    return o.reshape(t, heads * d) @ w["wo"]


def swiglu(x, w):
    return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


def layer(x, w, cfg: LMConfig, attends: bool):
    """One layer on one sequence ``x [T, d]``: each sublayer reads the stream as it is, its output is normed."""
    mixed = attention(x, w, cfg) if attends else gated_delta(x, w, cfg)
    x = x + rms_norm(mixed, w["attn_out_norm"], cfg.norm_eps)
    return x + rms_norm(swiglu(x, w), w["ffn_out_norm"], cfg.norm_eps)


def forward(params, tok, cfg: LMConfig):
    """Logits ``[B, T, V]``."""
    logits = []
    for row in tok:
        x = params["embed"][row]
        for i, w in enumerate(params["layers"]):
            x = layer(x, w, cfg, i in cfg.gqa_layers)
        logits.append(rms_norm(x, params["final_norm"], cfg.norm_eps) @ params["lm_head"])
    return jnp.stack(logits)


def loss(params, tok, cfg: LMConfig):
    """Mean next-token cross-entropy over every sequence's ``T - 1`` targets."""
    with jax.default_matmul_precision("highest"):
        return -jnp.mean(olmoe.token_log_probs(forward(params, tok, cfg), tok))


def loss_and_grads(params, tok, cfg: LMConfig):
    return jax.value_and_grad(loss)(params, tok, cfg)


def log_likelihood(params, tok, cfg: LMConfig):
    with jax.default_matmul_precision("highest"):
        return jnp.mean(olmoe.token_log_probs(forward(params, tok, cfg), tok), axis=1)


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
