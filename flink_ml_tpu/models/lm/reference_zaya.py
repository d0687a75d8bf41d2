"""The plain reference of the ``zaya`` block kind (ZAYA1-8B's decoder layer):
forward, loss, gradients and AdamW steps in straightforward ``jax.numpy``
float32 under ``jax.default_matmul_precision("highest")``.

A Python loop over layers and sequences' positions as whole arrays: ``[T, T]``
scores with a causal mask, the convolutions as shifted sums, every held expert
applied to every token and masked, ``jax.grad`` for the gradients; no kernel,
no sort, no recomputation. It shares with the stage only the parameter tree's
layout (``config.py``); AdamW, the clip and the loss's form are
``reference.py``'s, which are plain themselves.

Origin of each equation. [c]: a key of the model's ``config.json``
(https://huggingface.co/Zyphra/ZAYA1-8B, ``model_type`` ``zaya``). [p]: the
published descriptions, from memory - there is no network here: compressed
convolutional attention, arXiv:2510.04476; the ZAYA1 report, arXiv:2511.17127.
[a]: assumed here, a departure or a detail the documents do not fix; the
benchmark's configuration lists each under ``assumed`` with these words.

Layer ``l``, residual ``x [T, d]``: ``x <- S_attn(x, CCA(RMSNorm(x)))``, then
``x <- S_ffn(x, MoE(RMSNorm(x)))``, eps ``rms_norm_eps`` [c]. ``S(x, y) =
res_scale * x + res_bias + out_scale * y + out_bias``: the report's learned
residual scaling, per channel [p], initialised to scale 1, bias 0 [a].

CCA, ``h = RMSNorm(x)``:

- ``q0 = h Wq [T, H * D]``, ``k0 = h Wk [T, H_kv * D]`` [c]: the latent;
- mixing [p]: ``z = concat(q0, k0)``; ``z1_t = a0 * z_(t-1) + a1 * z_t + b``
  (depthwise causal convolution, kernel ``cca_time0`` = 2 [c], zero before
  position 0); ``z2_t[g] = z1_(t-1)[g] U0[g] + z1_t[g] U1[g] + c[g]`` for each
  of the ``H + H_kv`` heads ``g`` (grouped causal convolution over each head's
  channels, kernel ``cca_time1`` = 2 [c]; groups = heads [a]);
- the q-k mean [p]: ``mq = (q0 + rep(k0)) / 2`` (each query head with its
  key head), ``mk = (mean over its query heads of q0 + k0) / 2``; ``q =
  z2[:H] + mq``, ``k = z2[H:] + mk``;
- per head ``q <- sqrt(D) q / |q|_2``, ``k <- tau_g sqrt(D) k / |k|_2`` with a
  learned scalar per key head, initialised 1 [p, a]; the norm is taken as
  ``sqrt(sum + D * eps)``, RMSNorm's form without a weight [a];
- RoPE, rotate-half, on the first ``partial_rotary_factor * D`` channels of
  each head, theta ``rope_parameters.hybrid.rope_theta`` [c];
- values, value shift [p]: key/value head 0 is ``h_t Wv1``, head 1 is
  ``h_(t-1) Wv2`` (zero at ``t = 0``) - two key/value heads, as published;
- causal softmax attention at scale ``D^-1/2``, query head ``i`` on key/value
  head ``i // (H / H_kv)``, then ``Wo [H * D, d]``. No biases in the
  projections [c].

MoE, ``u = RMSNorm(x)``: ``r_l = u Wr [T, router_hidden_size]`` [c], ``r_l <-
r_l + gamma_l * r_(l-1)`` for ``l > 0`` (depth averaging, ``gamma`` initialised
0 [p, a]); ``s = gelu(gelu(RMSNorm(r_l) W1) W2) W3`` [p; depth, the exact
(erf) gelu and the norm's place a]; ``p = softmax(s)`` in float32; ``e =
argmax p`` (``num_experts_per_tok`` 1 [c]; ties to the lower id); ``y = p_e *
down_e(silu(gate_e u) * up_e u)`` [c]. Only experts ``first_held ..
first_held + held`` are here: a token whose ``e`` is elsewhere gets ``y = 0``
(the chip's share of an expert-parallel layer; the ``model-configs`` guide,
section 4).

Head: final RMSNorm, logits ``h E^T`` over the tied table [c]; next-token
cross-entropy. Departures, all [a]: the report's bias-based balancing moves
biases by a rule outside the gradient; they start at 0, so they and any
auxiliary loss are left out. The family's mixture-of-depths route
(``described_as``) has no key in this ``config.json`` and is not implemented.
Packed documents attend, and the convolutions and the value shift reach,
across their boundaries. AdamW decays every parameter.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from flink_ml_tpu.models.lm import reference as olmoe
from flink_ml_tpu.models.lm.config import LMConfig

__all__ = ["forward", "loss", "loss_and_grads", "train_steps", "log_likelihood", "moe", "cca"]

rms_norm = olmoe.rms_norm


def before(z):
    """``z [T, ...]`` one position earlier, zero at position 0."""
    return jnp.concatenate([jnp.zeros_like(z[:1]), z[:-1]], axis=0)


def rope_part(x, theta, rot):
    """Rotate-half RoPE on the first ``rot`` channels of each head of ``x [T, H, D]``."""
    t = x.shape[0]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    freqs = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    x1, x2, rest = x[..., : rot // 2], x[..., rot // 2: rot], x[..., rot:]
    turned = x[..., :rot] * jnp.cos(emb) + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(emb)
    return jnp.concatenate([turned, rest], axis=-1)


def cca(h, layer, cfg: LMConfig):
    """Compressed convolutional attention on one sequence ``h [T, d]``."""
    t = h.shape[0]
    n_q, n_kv, d = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    group = n_q // n_kv
    q0 = (h @ layer["wq"]).reshape(t, n_q, d)
    k0 = (h @ layer["wk"]).reshape(t, n_kv, d)
    z = jnp.concatenate([q0, k0], axis=1).reshape(t, -1)
    a, u = layer["conv0_w"], layer["conv1_w"]
    z1 = (a[0] * before(z) + a[1] * z + layer["conv0_b"]).reshape(t, n_q + n_kv, d)
    z2 = (jnp.einsum("tgi,gio->tgo", before(z1), u[0]) + jnp.einsum("tgi,gio->tgo", z1, u[1])
          + layer["conv1_b"])
    mq = (q0 + jnp.repeat(k0, group, axis=1)) / 2
    mk = (jnp.mean(q0.reshape(t, n_kv, group, d), axis=2) + k0) / 2
    q, k = z2[:, :n_q] + mq, z2[:, n_q:] + mk

    def unit(x):
        return jnp.sqrt(float(d)) * x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + d * cfg.norm_eps)

    rot = int(d * cfg.rope_fraction)
    q = rope_part(unit(q), cfg.rope_theta, rot)
    k = rope_part(unit(k) * layer["k_temp"][:, None], cfg.rope_theta, rot)
    v = jnp.stack([h @ layer["wv1"], before(h) @ layer["wv2"]], axis=1)  # [T, 2, D]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * (d ** -0.5)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(t, n_q * d) @ layer["wo"]


def router(u, layer, carry, cfg: LMConfig):
    """The router MLP on ``u [T, d]``: ``(p [T, E], r)`` with ``r`` the hidden
    state the next layer's router adds ``gamma`` times of."""
    r = u @ layer["router_in"]
    if carry is not None:
        r = r + layer["router_gamma"] * carry
    n = rms_norm(r, layer["router_norm"], cfg.norm_eps)
    n = jax.nn.gelu(n @ layer["router_w1"], approximate=False)
    n = jax.nn.gelu(n @ layer["router_w2"], approximate=False)
    return jax.nn.softmax(n @ layer["router_w3"], axis=-1), r


def moe(u, layer, carry, cfg: LMConfig):
    """Top-1 of ``n_experts``; every HELD expert runs on every token and the
    unchosen are masked. Returns the output, the router's state and the chosen
    expert of each token."""
    p, r = router(u, layer, carry, cfg)
    e = jnp.argmax(p, axis=-1)  # the first of equal maxima: the lower id
    gate = jnp.max(p, axis=-1)
    y = jnp.zeros_like(u)
    for j in range(cfg.held):
        hidden = jax.nn.silu(u @ layer["w_gate"][j]) * (u @ layer["w_up"][j])
        y = y + jnp.where(e == cfg.first_held + j, gate, 0.0)[:, None] * (hidden @ layer["w_down"][j])
    return y, r, e


def scaled(x, y, layer, sub):
    return (layer[f"{sub}_res_scale"] * x + layer[f"{sub}_res_bias"]
            + layer[f"{sub}_out_scale"] * y + layer[f"{sub}_out_bias"])


def forward(params, tok, cfg: LMConfig):
    """Logits ``[B, T, V]`` and, per layer, the chosen expert of each token ``[B, T]``."""
    logits, chosen = [], []
    for row in tok:
        x = params["embed"][row]
        carry, picks = None, []
        for layer in params["layers"]:
            x = scaled(x, cca(rms_norm(x, layer["attn_norm"], cfg.norm_eps), layer, cfg), layer, "attn")
            y, carry, e = moe(rms_norm(x, layer["ffn_norm"], cfg.norm_eps), layer, carry, cfg)
            x = scaled(x, y, layer, "ffn")
            picks.append(e)
        head = params["embed"].T if cfg.tied else params["lm_head"]
        logits.append(rms_norm(x, params["final_norm"], cfg.norm_eps) @ head)
        chosen.append(jnp.stack(picks))
    return jnp.stack(logits), jnp.stack(chosen, axis=1)  # [B, T, V], [L, B, T]


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
