"""The benchmark's own plain reference of the SDAR-30B-A3B-Chat decoder LM as
the ``sdar_30b_a3b`` configuration cuts it, trained by block diffusion: the
head of a fit job - the first AdamW step's objective, gradient norms and update,
and the second step's objective - in ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``. It imports nothing of the
program: the equations and the corruption's draws are written again here.

Origin of each equation: [c] the model's ``config.json``
(https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json,
``model_type`` ``sdar_moe``: the Qwen3-MoE layer); [p] block diffusion,
arXiv:2503.09573 section 3, as arXiv:2510.06303 adapts an autoregressive model
to it; [a] assumed, and listed under the configuration's ``assumed``.
Matrices map ``x @ W``, no biases (``attention_bias`` false); norms are RMS
norms with a weight, eps 1e-6 [c].

- ``a = RMSNorm(x)``; ``q = a Wq -> [P, 32, 128]``, ``k = a Wk``, ``v = a Wv ->
  [P, 4, 128]`` [c]; ``q <- RMSNorm(q; q_norm [128])``, ``k <- RMSNorm(k; k_norm
  [128])`` over each head's channels [c: the family's ``q_norm``/``k_norm``].
- RoPE at theta 1e6, unscaled [c], rotate-half on the whole head [a], at the
  position's id: ``[0 .. T-1 ; 0 .. T-1]`` over the ``P = 2 T`` positions [p].
- ``o = softmax(q k^T 128^-1/2 + M) v``, query head ``h`` on key/value head ``h
  // 8``; ``x <- x + concat(o) Wo`` [c]. ``M`` [p], with ``b(i) = i // L`` (``L``
  = ``block_length`` 4 [a]) of a position in its own half: clean -> clean keeps
  ``b(j) <= b(i)``; noised -> clean ``b(j) < b(i)``; noised -> noised ``b(j) =
  b(i)``; clean -> noised nothing.
- ``u = RMSNorm(x)``; ``s = softmax(u Wr)`` over all 128 [c]; the 8 largest
  chosen, ties to the lower id; ``g = s_sel / sum(s_sel)`` over all eight chosen
  (``norm_topk_prob`` true) [c]; ``x <- x + sum_held g_e E_e(u)`` with the
  experts held here (``first_expert_held .. + num_experts`` of the published
  128; what the others would add is left out: the chip's share, the
  ``model-configs`` guide, section 4), each a SwiGLU of width 768 [c]. No shared
  expert [c].
- The objective [p]: ``t ~ U[0, 1)`` a sequence, ``p = (1 - eps) t + eps``
  (``eps`` 1e-3 [a]), each token masked with probability ``p``; the stack runs
  once over ``[x ; x~]``; ``loss = 1 / (B T) sum over masked i of (1 / p) x
  -log softmax(RMSNorm(h~_i) Whead)[x_i]`` over the held slice of the untied
  head: the position's own token, no shift [a]. No auxiliary loss [a].
- The draws [a]: ``k = fold_in(fold_in(key(seed), 2^30), step)``; ``k_t, k_m =
  split(k)``; ``t = uniform(k_t, [B])``, ``u = uniform(k_m, [B, T])`` in
  float32; ``m = u < p[:, None]``.

Plain means a dense boolean mask from the four rules, every held expert on
every token and masked, ``jax.grad``. What is blocked, so that it fits beside
2.6 GB of weights and 2.6 GB of summed gradients: one sequence at a time
(nothing couples the sequences: the loss is a sum over them); each layer, each
expert's contribution, each block of 512 query positions (whose rows of the
mask are built there) and each block of 1,024 positions of the head
rematerialised in the backward; AdamW's first step from zero moments needs no
moment storage.

``precision="bf16"`` is the control, one precision below what the
configuration states: weights, activations, router, softmaxes and every
accumulator's result in bfloat16. It must fail the limits.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.95, 1e-8
Q_BLOCK, HEAD_BLOCK = 512, 1024
NOISE_STREAM = 2 ** 30


def leaf_table(dims: dict) -> list:
    """``(name, shape, start)`` of every parameter in the order the
    configuration's ``init`` numbers them; ``start`` is 1.0 or None
    (``init_std * normal``)."""
    d, heads, kv, hd = dims["hidden_size"], dims["num_attention_heads"], dims["num_key_value_heads"], dims["head_dim"]
    held, width, routed = dims["num_experts"], dims["moe_intermediate_size"], dims["num_experts_published"]
    out = [("embed", (dims["vocab_size"], d), None)]
    for i in range(dims["num_hidden_layers"]):
        out += [(f"layers.{i}.{name}", shape, start) for name, shape, start in (
            ("attn_norm", (d,), 1.0), ("wq", (d, heads * hd), None), ("wk", (d, kv * hd), None),
            ("wv", (d, kv * hd), None), ("wo", (heads * hd, d), None), ("q_norm", (hd,), 1.0), ("k_norm", (hd,), 1.0),
            ("ffn_norm", (d,), 1.0), ("router", (d, routed), None), ("w_gate", (held, d, width), None),
            ("w_up", (held, d, width), None), ("w_down", (held, width, d), None))]
    return out + [("final_norm", (d,), 1.0), ("lm_head", (d, dims["vocab_size"]), None)]


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _init_leaf(key, i, shape, std):
    return std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)


def init_params(dims: dict, seed: int, std: float) -> dict:
    key = jax.random.key(seed)
    return {name: _init_leaf(key, i, shape, std) if start is None else jnp.full(shape, start, jnp.float32)
            for i, (name, shape, start) in enumerate(leaf_table(dims))}


def corrupt(tok, seed: int, step: int, dims: dict):
    """``(x~ [B, T], m [B, T] bool, p [B])`` of step ``step`` of the job seeded ``seed``, by the stated draws."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(seed), NOISE_STREAM), step)
    k_t, k_m = jax.random.split(key)
    eps = jnp.float32(dims["noise_eps"])
    p = (1.0 - eps) * jax.random.uniform(k_t, (tok.shape[0],), jnp.float32) + eps
    m = jax.random.uniform(k_m, tok.shape, jnp.float32) < p[:, None]
    return jnp.where(m, dims["mask_token_id"], tok), m, p


# -- the equations ---------------------------------------------------------------


def _rms_norm(x, w, eps):
    return w * (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps))


def _turn(x, pos, theta):
    """Rotate-half RoPE of ``x [P, H, D]`` at the position ids ``pos [P]``."""
    d = x.shape[-1]
    inv_freq = jnp.asarray(float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d), jnp.float32)
    angle = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    cos, sin = jnp.cos(angle).astype(x.dtype), jnp.sin(angle).astype(x.dtype)
    return x * cos + jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1) * sin


def _kept(rows, t: int, block: int):
    """The mask's rows ``[len(rows), 2 T]`` for the doubled sequence's positions ``rows``, from the four rules."""
    keys = jnp.arange(2 * t)
    q_clean, k_clean = (rows < t)[:, None], (keys < t)[None, :]
    bq, bk = ((rows % t) // block)[:, None], ((keys % t) // block)[None, :]
    return ((q_clean & k_clean & (bk <= bq)) | (~q_clean & k_clean & (bk < bq)) | (~q_clean & ~k_clean & (bk == bq)))


def _attention(a, p, pre, dims):
    n, heads, kv, hd = a.shape[0], dims["num_attention_heads"], dims["num_key_value_heads"], dims["head_dim"]
    t, eps = n // 2, dims["rms_norm_eps"]
    pos = jnp.arange(n) % t  # [0 .. T-1 ; 0 .. T-1]
    q = _turn(_rms_norm((a @ p[pre + "wq"]).reshape(n, heads, hd), p[pre + "q_norm"], eps), pos, dims["rope_theta"])
    k = _turn(_rms_norm((a @ p[pre + "wk"]).reshape(n, kv, hd), p[pre + "k_norm"], eps), pos, dims["rope_theta"])
    v = (a @ p[pre + "wv"]).reshape(n, kv, hd)
    group = heads // kv
    qb = math.gcd(Q_BLOCK, n)  # whole blocks: the toy's 768 positions take 256

    @jax.checkpoint
    def block(args):  # the query positions of one block against every key, a key/value head's group together
        q_blk, rows = args
        s = jnp.einsum("qkgd,nkd->kgqn", q_blk.reshape(qb, kv, group, hd), k) * (hd ** -0.5)
        s = jnp.where(_kept(rows, t, dims["block_length"])[None, None], s, -jnp.inf)
        return jnp.einsum("kgqn,nkd->qkgd", jax.nn.softmax(s, axis=-1), v).reshape(qb, heads * hd)

    o = jax.lax.map(block, (q.reshape(n // qb, qb, heads, hd), jnp.arange(n).reshape(n // qb, qb)))
    return o.reshape(n, heads * hd) @ p[pre + "wo"]


def _swiglu(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def _moe(u, p, pre, dims):
    """Every held expert on every token, the unchosen masked. Returns the
    routed part and the chosen experts ``[P, k]``."""
    s = jax.nn.softmax(u @ p[pre + "router"], axis=-1)
    picked, chosen = jax.lax.top_k(s, dims["num_experts_per_tok"])
    gates = picked / jnp.sum(picked, axis=1, keepdims=True)  # over all the chosen, held here or not
    held = dims["first_expert_held"] + jnp.arange(dims["num_experts"])
    weight = jnp.sum(jnp.where(chosen[None, :, :] == held[:, None, None], gates[None], jnp.zeros((), gates.dtype)),
                     axis=2)  # [held, P]

    @jax.checkpoint
    def contribution(w_e, wg, wu, wd):
        return w_e[:, None] * _swiglu(u, wg, wu, wd)

    def body(y, xs):
        return y + contribution(*xs), None

    y, _ = jax.lax.scan(body, jnp.zeros_like(u), (weight, p[pre + "w_gate"], p[pre + "w_up"], p[pre + "w_down"]))
    return y, chosen


def _layer(x, p, pre, dims):
    eps = dims["rms_norm_eps"]
    x = x + _attention(_rms_norm(x, p[pre + "attn_norm"], eps), p, pre, dims)
    y, chosen = _moe(_rms_norm(x, p[pre + "ffn_norm"], eps), p, pre, dims)
    return x + y, chosen


def _weighted_nll_sum(hidden, head, targets, weight):
    """``sum_i weight_i x -log softmax(hidden_i head)[targets_i]``, blocks of the head's rows rematerialised."""
    n = hidden.shape[0]
    hb = math.gcd(HEAD_BLOCK, n)

    @jax.checkpoint
    def block(args):
        h_blk, t_blk = args
        logp = jax.nn.log_softmax((h_blk @ head).astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, t_blk[:, None], axis=1)[:, 0]

    nll = jax.lax.map(block, (hidden.reshape(n // hb, hb, -1), targets.reshape(n // hb, hb)))
    return jnp.sum(weight * nll.reshape(-1))


def _sequence(p, tok, noised, weight, dims):
    """One sequence ``tok [T]`` beside its noised copy: ``sum_i weight_i
    nll_i`` over the noised half's positions (``weight`` is ``m / p``, float32)
    and, per layer, how many (position, slot) choices fell on each of the
    router's experts."""
    eps, t = dims["rms_norm_eps"], tok.shape[0]
    x = p["embed"][jnp.concatenate([tok, noised])]
    counts = []
    run = jax.checkpoint(_layer, static_argnums=(2, 3))
    static = _Static(dims)
    for i in range(dims["num_hidden_layers"]):
        x, chosen = run(x, p, f"layers.{i}.", static)
        counts.append(jnp.zeros((dims["num_experts_published"],), jnp.int32).at[chosen.reshape(-1)].add(1))
    total = _weighted_nll_sum(_rms_norm(x[t:], p["final_norm"], eps), p["lm_head"], tok, weight)
    return total, jnp.stack(counts)


def _cast(p, dtype):
    return {k: v.astype(dtype) for k, v in p.items()}


class _Static(dict):
    """The configuration's numbers as one hashable jit argument."""

    def __init__(self, dims: dict):
        super().__init__(dims)
        self.key = repr(sorted(dims.items()))

    def __hash__(self):
        return hash(self.key)

    def __eq__(self, other):
        return self.key == other.key


@functools.partial(jax.jit, static_argnums=(4, 5))
def _stats(p, tok, noised, weight, static, dtype):
    with jax.default_matmul_precision("highest"):
        return _sequence(_cast(p, dtype), tok, noised, weight, static)


@functools.partial(jax.jit, static_argnums=(5, 6, 7), donate_argnums=(0,))
def _add_grads(acc, p, tok, noised, weight, static, dtype, scale):
    """``acc + d/dp [weighted_nll_sum(tok) * scale]``."""
    def objective(p32):
        return _sequence(_cast(p32, dtype), tok, noised, weight, static)[0].astype(jnp.float32) * scale

    with jax.default_matmul_precision("highest"):
        grads = jax.grad(objective)(p)
    return {k: acc[k] + grads[k].astype(jnp.float32) for k in acc}


def _rows(batch, seed, step, dims):
    """A batch's sequences with their noised copies and weights ``m / p``, and the positions scored."""
    tok = jnp.asarray(batch)
    noised, m, p = corrupt(tok, seed, step, dims)
    weight = m.astype(jnp.float32) / p[:, None]
    return list(zip(tok, noised, weight)), int(jnp.sum(m))


def _step_loss(p, rows, static, dtype):
    """``(loss, counts)`` of one batch's ``rows``."""
    total, counts = 0.0, 0
    for tok, noised, weight in rows:
        s, c = _stats(p, tok, noised, weight, static, dtype)
        total, counts = total + s.astype(jnp.float32), counts + c
    return total / (len(rows) * rows[0][0].shape[0]), counts


def head_of_job(dims: dict, hyper: dict, seed: int, batches, precision: str = "f32") -> dict:
    """The first two steps' objectives, the first step's gradient norms
    (global and per parameter) and expert loads ``[layers, published
    experts]``, both steps' scored positions, and the parameters after the
    first update (host arrays) with the norm of each one's change, for
    ``batches`` (two ``[B, T]`` int arrays) from the configuration's initial
    weights; batch ``i`` is corrupted as step ``i``."""
    dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16}[precision]
    static = _Static(dims)
    p = init_params(dims, seed, hyper["init_std"])
    b, t = batches[0].shape

    rows1, scored1 = _rows(batches[0], seed, 0, dims)
    loss1, counts1 = _step_loss(p, rows1, static, dtype)
    grads = {k: jnp.zeros_like(v) for k, v in p.items()}
    for tok, noised, weight in rows1:
        grads = _add_grads(grads, p, tok, noised, weight, static, dtype, 1.0 / (b * t))
    group = {k: float(jnp.sqrt(jnp.sum(g * g))) for k, g in grads.items()}
    norm = float(np.sqrt(sum(v * v for v in group.values())))

    # clip at the global norm, then AdamW's first step from zero moments
    scale = hyper["clip_norm"] / max(norm, hyper["clip_norm"])
    lr, wd = hyper["learning_rate"], hyper["weight_decay"]
    moved = {}
    for k in list(p):
        g = grads.pop(k) * scale
        m_hat = ((1.0 - ADAM_B1) * g) / (1.0 - ADAM_B1)
        v_hat = ((1.0 - ADAM_B2) * g * g) / (1.0 - ADAM_B2)
        step = lr * (m_hat / (jnp.sqrt(v_hat) + ADAM_EPS) + wd * p[k])
        moved[k] = float(jnp.sqrt(jnp.sum(step * step)))
        p[k] = p[k] - step
    rows2, scored2 = _rows(batches[1], seed, 1, dims)
    loss2, _ = _step_loss(p, rows2, static, dtype)
    return {
        "losses": [float(loss1), float(loss2)],
        "grad_norms": [norm],
        "group_norms": group,
        "expert_rows": np.asarray(counts1),
        "targets_masked": [scored1, scored2],
        "params_after": {k: np.asarray(v) for k, v in p.items()},  # on the host: the device keeps one tree at a time
        "update_norms": moved,
    }
