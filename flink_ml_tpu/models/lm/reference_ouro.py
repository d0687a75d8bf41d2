"""The plain reference of the ``ouro`` block kind (Ouro-2.6B's looped language
model): forward, loss, gradients and AdamW steps in straightforward
``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``.

A Python loop over the passes and over the layers of each, ``[T, T]`` scores
with a causal mask, ``[B, T, V]`` logits for every pass, ``jax.grad`` for the
gradients; no kernel, no scan, no recomputation. It shares with the stage only
the parameter tree's layout (``config.py``); AdamW and the clip are
``reference.py``'s, which are plain themselves.

Origin of each equation. [c]: a key of the model's ``config.json``
(https://huggingface.co/ByteDance/Ouro-2.6B, ``model_type`` ``ouro``). [p]: the
published description, from memory - there is no network here: "Scaling Latent
Reasoning via Looped Language Models", arXiv:2510.25741. [a]: assumed here, a
detail the config does not settle; the benchmark's configuration lists each
under ``assumed`` with these words.

Layer, residual ``x [B, T, d]``, eps ``rms_norm_eps`` [c]:

- ``a = RMSNorm_1(x)``; ``q, k, v = a Wq, a Wk, a Wv`` with
  ``num_attention_heads`` = ``num_key_value_heads`` heads of ``head_dim`` [c],
  no biases [a], no QK-norm [a];
- rotate-half RoPE on the whole head, theta ``rope_theta`` [c];
- causal ``softmax(q k^T / sqrt(head_dim)) v``, then ``Wo``;
- ``x <- x + RMSNorm_2(attention)``; ``m = RMSNorm_3(x)``; ``x <- x +
  RMSNorm_4((silu(m Wg) * (m Wu)) Wd)`` (``hidden_act`` silu,
  ``intermediate_size`` [c]). Four norm weights a layer - the sandwich: a norm
  before and after each sublayer, the second inside the residual branch [p, a].

Stack [p]: ``h_0 = embed[tok]``; for ``r = 1 .. R`` (``total_ut_steps`` [c]):
``h_r = RMSNorm_final(layers(h_(r-1)))`` - the same layers and the same final
norm every pass, the normed state feeding the next pass [a]; ``lambda_r =
sigmoid(h_r w_e + b_e)`` per token, the exit gate a linear ``[d, 1]`` with a
bias [a].

Exit distribution per token [p]: ``p_r = lambda_r prod_(j<r) (1 - lambda_j)``
for ``r < R``; ``p_R = prod_(j<R) (1 - lambda_j)``, so that it sums to one (the
last pass's own gate is not asked). Loss (the paper's stage-one objective) [p]:
the mean over target positions of ``sum_r p_r nll_r - beta H(p)``, ``nll_r``
the next-token cross-entropy of ``h_r`` through the one shared, untied
``lm_head`` (``tie_word_embeddings`` false [c]), ``H`` the entropy of ``p``,
``beta`` 0.1 [a]. ``early_exit_threshold`` 1 [c]: no token leaves before the
last pass, so ``log_likelihood`` scores ``h_R``.

Departures, all [a]: packed documents attend across their boundaries; AdamW
decays every parameter; the gate's second training stage (the LM frozen, the
gate fitted to the passes' measured improvement) is not here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from flink_ml_tpu.models.lm import reference as olmoe
from flink_ml_tpu.models.lm.config import LMConfig

__all__ = ["layer", "forward", "exit_distribution", "loss_and_parts", "loss_and_grads", "log_likelihood",
           "train_steps"]

rms_norm = olmoe.rms_norm


def layer(x, w, cfg: LMConfig):
    """One sandwich-norm layer on ``x [B, T, d]``."""
    b, t, d = x.shape
    h, hd = cfg.n_heads, cfg.head_dim
    a = rms_norm(x, w["attn_norm"], cfg.norm_eps)
    q = olmoe.rope((a @ w["wq"]).reshape(b, t, h, hd), cfg.rope_theta)
    k = olmoe.rope((a @ w["wk"]).reshape(b, t, h, hd), cfg.rope_theta)
    v = (a @ w["wv"]).reshape(b, t, h, hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (hd ** -0.5)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v).reshape(b, t, h * hd)
    x = x + rms_norm(o @ w["wo"], w["attn_out_norm"], cfg.norm_eps)
    m = rms_norm(x, w["ffn_norm"], cfg.norm_eps)
    y = (jax.nn.silu(m @ w["w_gate"]) * (m @ w["w_up"])) @ w["w_down"]
    return x + rms_norm(y, w["ffn_out_norm"], cfg.norm_eps)


def forward(params, tok, cfg: LMConfig, passes=None):
    """Every pass's logits ``[R, B, T, V]`` and gate ``lambda [R, B, T]``.
    ``passes`` names the tree each pass reads its layers, final norm, head and
    gate from: the one shared tree ``cfg.loops`` times, unless a caller unties
    the loop by handing each pass a copy of its own."""
    h = params["embed"][tok]
    logits, gates = [], []
    for own in passes or [params] * cfg.loops:
        for w in own["layers"]:
            h = layer(h, w, cfg)
        h = rms_norm(h, own["final_norm"], cfg.norm_eps)
        logits.append(h @ own["lm_head"])
        gates.append(jax.nn.sigmoid(h @ own["exit_gate_w"] + own["exit_gate_b"])[..., 0])
    return jnp.stack(logits), jnp.stack(gates)


def exit_distribution(gates):
    """``p [R, ...]`` from ``lambda [R, ...]``: leave at pass ``r`` having
    stayed at every pass before it; the last pass takes what is left."""
    out, stayed = [], jnp.ones_like(gates[0])
    for lam in gates[:-1]:
        out.append(lam * stayed)
        stayed = stayed * (1.0 - lam)
    return jnp.stack(out + [stayed])


def loss_and_parts(params, tok, cfg: LMConfig, passes=None):
    """``(loss, (trip_nll [R], expected_nll, entropy))``: each pass's own mean
    cross-entropy, the exit-weighted one and the mean entropy of ``p``, over
    every sequence's ``T - 1`` targets; ``loss = expected_nll - beta * entropy``."""
    with jax.default_matmul_precision("highest"):
        logits, gates = forward(params, tok, cfg, passes)
        nll = jnp.stack([-olmoe.token_log_probs(lg, tok) for lg in logits])  # [R, B, T-1]
        p = exit_distribution(gates)[:, :, :-1]
        expected = jnp.mean(jnp.sum(p * nll, axis=0))
        entropy = jnp.mean(-jnp.sum(p * jnp.log(p), axis=0))
    return expected - cfg.exit_beta * entropy, (jnp.mean(nll, axis=(1, 2)), expected, entropy)


def loss_and_grads(params, tok, cfg: LMConfig):
    (loss, _), grads = jax.value_and_grad(loss_and_parts, has_aux=True)(params, tok, cfg)
    return loss, grads


def log_likelihood(params, tok, cfg: LMConfig):
    """Per row, the mean log-likelihood of its ``T - 1`` next tokens at the last pass."""
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(params, tok, cfg)
        return jnp.mean(olmoe.token_log_probs(logits[-1], tok), axis=1)


def train_steps(params, batches, cfg: LMConfig, lr, **adamw):
    """``len(batches)`` AdamW steps from ``params``, one ``[B, T]`` token batch
    each. Returns ``(params, losses, grad_norms, trip_losses)``."""
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, norms, trips = [], [], []
    for i, tok in enumerate(batches):
        (loss, (trip, _, _)), grads = jax.value_and_grad(loss_and_parts, has_aux=True)(params, tok, cfg)
        params, m, v, norm = olmoe.adamw_step(params, m, v, grads, i + 1, lr, **adamw)
        losses.append(float(loss))
        norms.append(float(norm))
        trips.append([float(x) for x in trip])
    return params, losses, norms, trips
