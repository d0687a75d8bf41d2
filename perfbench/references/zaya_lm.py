"""The benchmark's own plain reference of the ZAYA1-8B decoder LM as the
``zaya1_8b`` configuration cuts it: the head of a fit job - the first AdamW
step's loss, gradient norms and update, and the second step's loss - in
``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``. It
imports nothing of the program: the equations are written again here.

Origin of each equation: [c] the model's ``config.json``
(https://huggingface.co/Zyphra/ZAYA1-8B, ``model_type`` ``zaya``); [p] the
published descriptions from memory (compressed convolutional attention,
arXiv:2510.04476; the ZAYA1 report, arXiv:2511.17127); [a] assumed, and listed
under the configuration's ``assumed``. Matrices map ``x @ W``.

- Layer: ``x <- S(x, CCA(RMSNorm(x)))``, ``x <- S(x, MoE(RMSNorm(x)))`` [c];
  ``S(x, y) = res_scale * x + res_bias + out_scale * y + out_bias`` per channel
  [p], from scale 1 and bias 0 [a].
- CCA on ``h``: ``q0 = h Wq``, ``k0 = h Wk`` [c]; ``z = cat(q0, k0)``, ``z1_t =
  a0 z_(t-1) + a1 z_t + b`` (depthwise, kernel 2 [c]), ``z2_t[g] = z1_(t-1)[g]
  U0[g] + z1_t[g] U1[g] + c[g]`` per head (grouped, kernel 2 [c]; groups =
  heads [a]); ``q = z2_q + (q0 + rep(k0)) / 2``, ``k = z2_k + (mean of its
  query heads' q0 + k0) / 2`` [p]; each head of q to length ``sqrt(D)``, of k to
  ``tau_g sqrt(D)`` [p], the length taken as ``sqrt(sum + D eps)`` [a]; RoPE,
  rotate-half, on the first half of each head, theta 5e6 [c]; value head 0 is
  ``h_t Wv1``, head 1 is ``h_(t-1) Wv2`` [p]; causal softmax attention at
  ``D^-1/2``, query head ``i`` on key/value head ``i // 4``; ``Wo`` [c].
- MoE on ``u``: ``r_l = u Wr (+ gamma_l r_(l-1))`` [c, p]; ``s = gelu(gelu(
  RMSNorm(r_l) W1) W2) W3`` with the exact gelu [p, a]; ``p = softmax(s)``;
  top-1, ties to the lower id [c]; ``y = p_e down_e(silu(gate_e u) up_e u)``
  for the experts held here, 0 for a token routed elsewhere (the chip's share:
  the ``model-configs`` guide, section 4). No balancing bias, no auxiliary
  loss, no mixture-of-depths route [a].
- Head: final RMSNorm, logits over the tied table [c]; mean next-token
  cross-entropy.

Plain means ``[heads, q, T]`` scores with a causal mask, every held expert on
every token and masked, the convolutions as shifted sums, ``jax.grad``. What
is blocked, so that it fits beside 2.8 GB of weights and 2.8 GB of summed
gradients: one sequence at a time (nothing couples the sequences: the loss is
a sum over them); each layer, each expert's contribution, each block of 1,024
query positions and each block of 1,024 positions of the head rematerialised
in the backward; AdamW's first step from zero moments needs no moment storage.

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


def _scaling(sub, d):
    return [(f"{sub}_res_scale", (d,), 1.0), (f"{sub}_res_bias", (d,), 0.0),
            (f"{sub}_out_scale", (d,), 1.0), (f"{sub}_out_bias", (d,), 0.0)]


def leaf_table(dims: dict) -> list:
    """``(name, shape, start)`` of every parameter in the order the
    configuration's ``init`` numbers them; ``start`` is 1.0, 0.0, None
    (``init_std * normal``) or ``"small"`` (``out_proj_init_scale`` of that)."""
    d, hd, r = dims["hidden_size"], dims["head_dim"], dims["router_hidden_size"]
    n_q, n_kv = dims["num_attention_heads"], dims["num_key_value_heads"]
    a, c, g = n_q * hd, n_kv * hd, n_q + n_kv
    held, width, routed = dims["num_experts"], dims["moe_intermediate_size"], dims["num_experts_published"]
    out = [("embed", (dims["vocab_size"], d), None)]
    for i in range(dims["num_hidden_layers"]):
        layer = [("attn_norm", (d,), 1.0)] + _scaling("attn", d) + [
            ("wq", (d, a), None), ("wk", (d, c), None), ("wv1", (d, hd), None), ("wv2", (d, hd), None),
            ("conv0_w", (2, a + c), None), ("conv0_b", (a + c,), 0.0),
            ("conv1_w", (2, g, hd, hd), None), ("conv1_b", (g, hd), 0.0),
            ("k_temp", (n_kv,), 1.0), ("wo", (a, d), "small"), ("ffn_norm", (d,), 1.0),
        ] + _scaling("ffn", d) + [("router_in", (d, r), None)]
        if i:
            layer.append(("router_gamma", (r,), 0.0))
        layer += [("router_norm", (r,), 1.0), ("router_w1", (r, r), None), ("router_w2", (r, r), None),
                  ("router_w3", (r, routed), None),
                  ("w_gate", (held, d, width), None), ("w_up", (held, d, width), None),
                  ("w_down", (held, width, d), None)]
        out += [(f"layers.{i}.{name}", shape, start) for name, shape, start in layer]
    out.append(("final_norm", (d,), 1.0))
    return out


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _init_leaf(key, i, shape, std):
    return std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)


def init_params(dims: dict, seed: int, std: float, small: float) -> dict:
    key = jax.random.key(seed)
    out = {}
    for i, (name, shape, start) in enumerate(leaf_table(dims)):
        if start is None or start == "small":
            out[name] = _init_leaf(key, i, shape, std * (small if start == "small" else 1.0))
        else:
            out[name] = jnp.full(shape, start, jnp.float32)
    return out


# -- the equations ---------------------------------------------------------------


def _rms_norm(x, w, eps):
    return w * (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps))


def _earlier(z):
    """``z [T, ...]`` one position earlier, zero at position 0."""
    return jnp.concatenate([jnp.zeros_like(z[:1]), z[:-1]], axis=0)


def _rope_first(x, theta, rot):
    """Rotate-half RoPE on the first ``rot`` channels of each head of ``x [T, H, D]``."""
    t = x.shape[0]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    freqs = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    cos, sin = jnp.cos(emb).astype(x.dtype), jnp.sin(emb).astype(x.dtype)
    part = x[..., :rot]
    turned = part * cos + jnp.concatenate([-part[..., rot // 2:], part[..., : rot // 2]], axis=-1) * sin
    return jnp.concatenate([turned, x[..., rot:]], axis=-1)


def _cca(h, p, pre, dims):
    t = h.shape[0]
    n_q, n_kv, d = dims["num_attention_heads"], dims["num_key_value_heads"], dims["head_dim"]
    group = n_q // n_kv
    eps = dims["rms_norm_eps"]
    q0 = (h @ p[pre + "wq"]).reshape(t, n_q, d)
    k0 = (h @ p[pre + "wk"]).reshape(t, n_kv, d)
    z = jnp.concatenate([q0, k0], axis=1).reshape(t, -1)
    a, u = p[pre + "conv0_w"], p[pre + "conv1_w"]
    z1 = (a[0] * _earlier(z) + a[1] * z + p[pre + "conv0_b"]).reshape(t, n_q + n_kv, d)
    z2 = (jnp.einsum("tgi,gio->tgo", _earlier(z1), u[0]) + jnp.einsum("tgi,gio->tgo", z1, u[1])
          + p[pre + "conv1_b"])
    q = z2[:, :n_q] + (q0 + jnp.repeat(k0, group, axis=1)) / 2
    k = z2[:, n_q:] + (jnp.mean(q0.reshape(t, n_kv, group, d), axis=2) + k0) / 2

    def unit(x):
        return jnp.sqrt(float(d)).astype(x.dtype) * x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + d * eps)

    rot = int(d * dims["partial_rotary_factor"])
    q = _rope_first(unit(q), dims["rope_theta"], rot)
    k = _rope_first(unit(k) * p[pre + "k_temp"][:, None], dims["rope_theta"], rot)
    v = jnp.stack([h @ p[pre + "wv1"], _earlier(h) @ p[pre + "wv2"]], axis=1)
    qb = min(Q_BLOCK, t)

    @jax.checkpoint
    def block(args):  # the query positions of one block against every key
        q_blk, pos = args
        s = jnp.einsum("qjgd,kjd->jgqk", q_blk.reshape(qb, n_kv, group, d), k) * (d ** -0.5)
        keep = pos[:, None] >= jnp.arange(t)[None, :]
        s = jnp.where(keep[None, None], s, -jnp.inf)
        return jnp.einsum("jgqk,kjd->qjgd", jax.nn.softmax(s, axis=-1), v).reshape(qb, n_q * d)

    o = jax.lax.map(block, (q.reshape(t // qb, qb, n_q, d), jnp.arange(t).reshape(t // qb, qb)))
    return o.reshape(t, n_q * d) @ p[pre + "wo"]


def _moe(u, carry, p, pre, dims):
    """Every held expert on every token, the unchosen masked. Returns the
    output, the router's state and the chosen expert of each token."""
    r = u @ p[pre + "router_in"]
    if carry is not None:
        r = r + p[pre + "router_gamma"] * carry
    n = _rms_norm(r, p[pre + "router_norm"], dims["rms_norm_eps"])
    n = jax.nn.gelu(n @ p[pre + "router_w1"], approximate=False)
    n = jax.nn.gelu(n @ p[pre + "router_w2"], approximate=False)
    probs = jax.nn.softmax(n @ p[pre + "router_w3"], axis=-1)
    chosen = jnp.argmax(probs, axis=-1)
    gate = jnp.max(probs, axis=-1)
    held = dims["first_expert_held"] + jnp.arange(dims["num_experts"])
    weight = jnp.where(chosen[None, :] == held[:, None], gate[None, :], jnp.zeros((), gate.dtype))  # [held, T]

    @jax.checkpoint
    def contribution(w_e, wg, wu, wd):
        return w_e[:, None] * ((jax.nn.silu(u @ wg) * (u @ wu)) @ wd)

    def body(y, xs):
        return y + contribution(*xs), None

    y, _ = jax.lax.scan(body, jnp.zeros_like(u), (weight, p[pre + "w_gate"], p[pre + "w_up"], p[pre + "w_down"]))
    return y, r, chosen


def _scaled(x, y, p, pre, sub):
    return (p[f"{pre}{sub}_res_scale"] * x + p[f"{pre}{sub}_res_bias"]
            + p[f"{pre}{sub}_out_scale"] * y + p[f"{pre}{sub}_out_bias"])


def _sequence(p, tok, dims):
    """One sequence ``tok [T]``: its summed next-token cross-entropy and, per
    layer, how many tokens chose each of the router's experts."""
    eps = dims["rms_norm_eps"]
    x = p["embed"][tok]
    carry, counts = None, []

    def layer(x, carry, pre):
        x = _scaled(x, _cca(_rms_norm(x, p[pre + "attn_norm"], eps), p, pre, dims), p, pre, "attn")
        y, carry, chosen = _moe(_rms_norm(x, p[pre + "ffn_norm"], eps), carry, p, pre, dims)
        return _scaled(x, y, p, pre, "ffn"), carry, chosen

    for i in range(dims["num_hidden_layers"]):
        x, carry, chosen = jax.checkpoint(layer, static_argnums=(2,))(x, carry, f"layers.{i}.")
        counts.append(jnp.zeros((dims["num_experts_published"],), jnp.int32).at[chosen].add(1))
    hidden = _rms_norm(x, p["final_norm"], eps)
    t = tok.shape[0]
    qb = min(Q_BLOCK, t)
    targets = jnp.concatenate([tok[1:], tok[:1]])  # the last position has no target
    table = p["embed"]  # tied: the head is the table transposed

    @jax.checkpoint
    def block(args):
        h_blk, t_blk = args
        logp = jax.nn.log_softmax((h_blk @ table.T).astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, t_blk[:, None], axis=1)[:, 0]

    nll = jax.lax.map(block, (hidden.reshape(t // qb, qb, -1), targets.reshape(t // qb, qb)))
    return jnp.sum(nll.reshape(t)[:-1]), jnp.stack(counts)


def _cast(p, dtype):
    return {k: v.astype(dtype) for k, v in p.items()}


@functools.partial(jax.jit, static_argnums=(2, 3))
def _stats(p, tok, dims_items, dtype):
    with jax.default_matmul_precision("highest"):
        return _sequence(_cast(p, dtype), tok, dict(dims_items))


@functools.partial(jax.jit, static_argnums=(3, 4, 5), donate_argnums=(0,))
def _add_grads(acc, p, tok, dims_items, dtype, scale):
    """``acc + d/dp [ce_sum(tok) * scale]``."""
    def objective(p32):
        ce_sum, _ = _sequence(_cast(p32, dtype), tok, dict(dims_items))
        return ce_sum.astype(jnp.float32) * scale

    with jax.default_matmul_precision("highest"):
        grads = jax.grad(objective)(p)
    return {k: acc[k] + grads[k].astype(jnp.float32) for k in acc}


def _step_loss(p, batch, dims_items, dtype):
    b, t = batch.shape
    ce, counts = 0.0, 0
    for row in batch:
        ce_s, c_s = _stats(p, jnp.asarray(row), dims_items, dtype)
        ce = ce + ce_s.astype(jnp.float32)
        counts = counts + c_s
    return ce / (b * (t - 1)), counts


def head_of_job(dims: dict, hyper: dict, seed: int, batches, precision: str = "f32") -> dict:
    """The first two steps' losses, and the first step's gradient norms (global
    and per parameter) and expert loads ``[layers, published experts]``, for
    ``batches`` (two ``[B, T]`` int arrays) from the configuration's initial
    weights."""
    dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16}[precision]
    dims_items = tuple(sorted((k, v) for k, v in dims.items() if isinstance(v, (int, float))))
    p = init_params(dims, seed, hyper["init_std"], hyper["out_proj_init_scale"])
    b, t = batches[0].shape

    loss1, counts1 = _step_loss(p, batches[0], dims_items, dtype)
    grads = {k: jnp.zeros_like(v) for k, v in p.items()}
    for row in batches[0]:
        grads = _add_grads(grads, p, jnp.asarray(row), dims_items, dtype, 1.0 / (b * (t - 1)))
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
    loss2, _ = _step_loss(p, batches[1], dims_items, dtype)
    return {
        "losses": [float(loss1), float(loss2)],
        "grad_norms": [norm],
        "group_norms": group,
        "expert_rows": np.asarray(counts1),
    }
