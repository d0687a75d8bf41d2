"""The benchmark's own plain reference of the JoyAI-LLM-Flash decoder LM as
the ``joyai_llm_flash`` configuration cuts it: the head of a fit job - the
first AdamW step's loss (both terms), gradient norms and update, and the second
step's loss - in ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``. It imports nothing of the
program: the equations are written again here.

Origin of each equation: [c] the model's ``config.json``
(https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json,
``model_type`` ``joyai_llm_flash``, the ``deepseek_v3`` family's keys); [p]
DeepSeek-V3's report, arXiv:2412.19437, sections 2.1 and 2.2, whose keys the
file uses; [a] assumed, and listed under the configuration's ``assumed``.
Matrices map ``x @ W``, no biases; norms are RMS norms with a weight, eps 1e-6
[c]. 32 heads [c].

- ``a = RMSNorm(x)``. ``c_q = RMSNorm(a Wqa [d, 1536])``; ``q = c_q Wqb``, a
  head ``[q_nope 128 | q_rope 64]`` [c: ``q_lora_rank``, ``qk_nope_head_dim``,
  ``qk_rope_head_dim``; p: eq. 6-9].
- ``[c_kv 512 | k_rope 64] = a Wkva``; ``c_kv = RMSNorm(c_kv)``; ``c_kv Wkvb``
  gives, a head, ``[k_nope 128 | v 128]`` [c: ``kv_lora_rank``, ``v_head_dim``;
  p: eq. 1-5]: keys and values rebuilt from the latent in the plain way, ONE
  ``k_rope`` a token under all 32 heads.
- RoPE at theta 3.2e7, unscaled, on INTERLEAVED pairs (``rope_interleave``
  true): channels ``2 j``, ``2 j + 1`` of ``q_rope`` (every head) and of
  ``k_rope`` turn by ``pos theta^(-2 j / 64)`` [c].
- ``o = softmax(q k^T 192^-1/2 + causal mask) v``, 32 heads of 128; ``x <- x +
  concat(o) Wo [4096, d]`` [c: ``qk_head_dim`` 192; p: eq. 10-11].
- ``u = RMSNorm(x)``. Layer 0 (``first_k_dense_replace`` 1): ``x <- x +
  (silu(u Wg) * u Wu) Wd``, width 7,168 [c]. Layers 1..: ``s = sigmoid(u Wr)``
  over all 256 (``scoring_func``); the 8 largest of ``s + b``, ties to the
  lower id (``topk_method`` ``noaux_tc``; ``n_group`` 1, ``topk_group`` 1: no
  group limit), ``b`` the selection bias at its initial 0 (its rule is outside
  the gradient and left out [a]); ``g = 2.5 s_sel / (sum s_sel + 1e-20)``
  (``norm_topk_prob``, ``routed_scaling_factor``); ``x <- x + sum_held g_e
  E_e(u) + S(u)`` with the experts held here (``first_expert_held .. +
  n_routed_experts`` of the published 256; what the others would add is left
  out: the chip's share, the ``model-configs`` guide, section 4) and the one
  shared expert whole, each a SwiGLU of width 768 [c; p: eq. 12-16].
- Head: final RMSNorm, logits over the held slice of the untied head [c];
  ``nll_main`` the mean next-token cross-entropy over ``T - 1`` targets.
- The multi-token-prediction module (``num_nextn_predict_layers`` 1 [c]; p:
  eq. 21-25): with ``h`` the stack's output before the final norm [a] and
  ``e_(i+1)`` the shared embedding of token ``i + 1``, ``h'_i = [RMSNorm(h_i;
  hnorm) | RMSNorm(e_(i+1); enorm)] Weh [2 d, d]`` (hidden first [a]) on
  positions ``0 .. T - 2``; ``h'' = Layer(h')``, one expert layer of its own on
  those ``T - 1`` positions; logits ``RMSNorm(h''; norm) Whead`` with the main
  head's matrix; position ``i``'s target is token ``i + 2``; ``nll_mtp`` the
  mean over the ``T - 2`` positions that have one. ``loss = nll_main + 0.3
  nll_mtp`` [a: lambda]. No auxiliary loss [a].

Plain means ``[heads, q, T]`` scores with the mask, every held expert on every
token and masked, ``jax.grad``. What is blocked, so that it fits beside 2.7 GB
of weights and 2.7 GB of summed gradients: one sequence at a time (nothing
couples the sequences: the loss is a sum over them); each layer, each expert's
contribution, each block of 512 query positions and each block of 1,024
positions of the head rematerialised in the backward; AdamW's first step from
zero moments needs no moment storage.

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
Q_BLOCK, HEAD_BLOCK = 512, 1024


def _layer_leaves(dims: dict, dense: bool) -> list:
    d, heads = dims["hidden_size"], dims["num_attention_heads"]
    nope, rope, dv = dims["qk_nope_head_dim"], dims["qk_rope_head_dim"], dims["v_head_dim"]
    q_rank, kv_rank = dims["q_lora_rank"], dims["kv_lora_rank"]
    held, width, routed = dims["n_routed_experts"], dims["moe_intermediate_size"], dims["n_routed_experts_published"]
    shared = width * dims["n_shared_experts"]
    out = [("attn_norm", (d,), 1.0), ("wq_a", (d, q_rank), None), ("q_a_norm", (q_rank,), 1.0),
           ("wq_b", (q_rank, heads * (nope + rope)), None), ("wkv_a", (d, kv_rank + rope), None),
           ("kv_a_norm", (kv_rank,), 1.0), ("wkv_b", (kv_rank, heads * (nope + dv)), None),
           ("wo", (heads * dv, d), None), ("ffn_norm", (d,), 1.0)]
    if dense:
        width = dims["intermediate_size"]
        return out + [("w_gate", (d, width), None), ("w_up", (d, width), None), ("w_down", (width, d), None)]
    return out + [("router", (d, routed), None), ("router_bias", (routed,), 0.0),
                  ("shared_gate", (d, shared), None), ("shared_up", (d, shared), None),
                  ("shared_down", (shared, d), None), ("w_gate", (held, d, width), None),
                  ("w_up", (held, d, width), None), ("w_down", (held, width, d), None)]


def leaf_table(dims: dict) -> list:
    """``(name, shape, start)`` of every parameter in the order the
    configuration's ``init`` numbers them; ``start`` is 1.0, 0.0 or None
    (``init_std * normal``)."""
    d = dims["hidden_size"]
    out = [("embed", (dims["vocab_size"], d), None)]
    for i in range(dims["num_hidden_layers"]):
        out += [(f"layers.{i}.{name}", shape, start)
                for name, shape, start in _layer_leaves(dims, i < dims["first_k_dense_replace"])]
    out += [("final_norm", (d,), 1.0), ("lm_head", (d, dims["vocab_size"]), None)]
    if dims["num_nextn_predict_layers"]:
        out += [("mtp.enorm", (d,), 1.0), ("mtp.hnorm", (d,), 1.0), ("mtp.eh_proj", (2 * d, d), None)]
        out += [(f"mtp.layer.{name}", shape, start) for name, shape, start in _layer_leaves(dims, False)]
        out += [("mtp.norm", (d,), 1.0)]
    return out


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _init_leaf(key, i, shape, std):
    return std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)


def init_params(dims: dict, seed: int, std: float) -> dict:
    key = jax.random.key(seed)
    return {name: _init_leaf(key, i, shape, std) if start is None else jnp.full(shape, start, jnp.float32)
            for i, (name, shape, start) in enumerate(leaf_table(dims))}


# -- the equations ---------------------------------------------------------------


def _rms_norm(x, w, eps):
    return w * (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps))


def _turn_pairs(x, theta):
    """RoPE on interleaved pairs of all channels of ``x [T, ..., D]``."""
    t, d = x.shape[0], x.shape[-1]
    inv_freq = jnp.asarray(float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d), jnp.float32)
    angle = (jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]).reshape(t, *(1,) * (x.ndim - 2), d // 2)
    cos, sin = jnp.cos(angle).astype(x.dtype), jnp.sin(angle).astype(x.dtype)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def _attention(a, p, pre, dims):
    t, heads, eps = a.shape[0], dims["num_attention_heads"], dims["rms_norm_eps"]
    nope, rope, dv, kv_rank = dims["qk_nope_head_dim"], dims["qk_rope_head_dim"], dims["v_head_dim"], \
        dims["kv_lora_rank"]
    q = (_rms_norm(a @ p[pre + "wq_a"], p[pre + "q_a_norm"], eps) @ p[pre + "wq_b"]).reshape(t, heads, nope + rope)
    down = a @ p[pre + "wkv_a"]
    up = (_rms_norm(down[:, :kv_rank], p[pre + "kv_a_norm"], eps) @ p[pre + "wkv_b"]).reshape(t, heads, nope + dv)
    q = jnp.concatenate([q[..., :nope], _turn_pairs(q[..., nope:], dims["rope_theta"])], axis=-1)
    k_rope = _turn_pairs(down[:, kv_rank:], dims["rope_theta"])  # [T, rope]: one key a token
    k = jnp.concatenate([up[..., :nope], jnp.broadcast_to(k_rope[:, None, :], (t, heads, rope))], axis=-1)
    v = up[..., nope:]
    qb = min(Q_BLOCK, t)
    n = -(-t // qb)  # the module's T - 1 positions are no whole number of blocks: the last is filled with rows
    q = jnp.pad(q, ((0, n * qb - t), (0, 0), (0, 0)))  # that are cut off again

    @jax.checkpoint
    def block(args):  # the query positions of one block against every key
        q_blk, pos = args
        s = jnp.einsum("qhd,khd->hqk", q_blk, k) * ((nope + rope) ** -0.5)
        s = jnp.where((pos[:, None] >= jnp.arange(t)[None, :])[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, (q.reshape(n, qb, heads, nope + rope), jnp.arange(n * qb).reshape(n, qb)))
    return o.reshape(n * qb, heads * dv)[:t] @ p[pre + "wo"]


def _swiglu(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def _moe(u, p, pre, dims):
    """Every held expert on every token, the unchosen masked. Returns the
    routed part and the chosen experts ``[T, k]``."""
    s = jax.nn.sigmoid(u @ p[pre + "router"])
    _, chosen = jax.lax.top_k(s + p[pre + "router_bias"], dims["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=1)
    gates = (jnp.asarray(dims["routed_scaling_factor"], s.dtype) * picked
             / (jnp.sum(picked, axis=1, keepdims=True) + jnp.asarray(1e-20, s.dtype)))
    held = dims["first_expert_held"] + jnp.arange(dims["n_routed_experts"])
    weight = jnp.sum(jnp.where(chosen[None, :, :] == held[:, None, None], gates[None], jnp.zeros((), gates.dtype)),
                     axis=2)  # [held, T]

    @jax.checkpoint
    def contribution(w_e, wg, wu, wd):
        return w_e[:, None] * _swiglu(u, wg, wu, wd)

    def body(y, xs):
        return y + contribution(*xs), None

    y, _ = jax.lax.scan(body, jnp.zeros_like(u), (weight, p[pre + "w_gate"], p[pre + "w_up"], p[pre + "w_down"]))
    return y, chosen


def _layer(x, p, pre, is_dense, dims):
    eps = dims["rms_norm_eps"]
    x = x + _attention(_rms_norm(x, p[pre + "attn_norm"], eps), p, pre, dims)
    u = _rms_norm(x, p[pre + "ffn_norm"], eps)
    if is_dense:
        return x + _swiglu(u, p[pre + "w_gate"], p[pre + "w_up"], p[pre + "w_down"]), None
    y, chosen = _moe(u, p, pre, dims)
    return x + y + _swiglu(u, p[pre + "shared_gate"], p[pre + "shared_up"], p[pre + "shared_down"]), chosen


def _nll_sum(hidden, head, targets):
    """Summed cross-entropy of ``hidden [n, d]`` on ``targets [n]``, blocks of the head's rows rematerialised (the
    last block filled with rows that are cut off again)."""
    n = hidden.shape[0]
    hb = min(HEAD_BLOCK, n)
    blocks = -(-n // hb)
    hidden, targets = jnp.pad(hidden, ((0, blocks * hb - n), (0, 0))), jnp.pad(targets, (0, blocks * hb - n))

    @jax.checkpoint
    def block(args):
        h_blk, t_blk = args
        logp = jax.nn.log_softmax((h_blk @ head).astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, t_blk[:, None], axis=1)[:, 0]

    nll = jax.lax.map(block, (hidden.reshape(blocks, hb, -1), targets.reshape(blocks, hb)))
    return jnp.sum(nll.reshape(-1)[:n])


def _count(chosen, dims):
    return jnp.zeros((dims["n_routed_experts_published"],), jnp.int32).at[chosen.reshape(-1)].add(1)


def _sequence(p, tok, dims):
    """One sequence ``tok [T]``: its summed next-token cross-entropy, the
    module's summed cross-entropy and, per expert layer (the module's last),
    how many (token, slot) choices fell on each of the router's experts."""
    eps, t = dims["rms_norm_eps"], tok.shape[0]
    x = p["embed"][tok]
    counts = []
    run = jax.checkpoint(_layer, static_argnums=(2, 3, 4))
    static = _Static(dims)
    for i in range(dims["num_hidden_layers"]):
        x, chosen = run(x, p, f"layers.{i}.", i < dims["first_k_dense_replace"], static)
        if chosen is not None:
            counts.append(_count(chosen, dims))
    main = _nll_sum(_rms_norm(x[:-1], p["final_norm"], eps), p["lm_head"], tok[1:])  # T - 1 targets
    ahead = jnp.zeros((), jnp.float32)
    if dims["num_nextn_predict_layers"]:
        both = jnp.concatenate([_rms_norm(x[:-1], p["mtp.hnorm"], eps),
                                _rms_norm(p["embed"][tok[1:]], p["mtp.enorm"], eps)], axis=-1)
        y, chosen = run(both @ p["mtp.eh_proj"], p, "mtp.layer.", False, static)  # T - 1 positions
        counts.append(_count(chosen, dims))
        ahead = _nll_sum(_rms_norm(y[:-1], p["mtp.norm"], eps), p["lm_head"], tok[2:])  # T - 2 targets
    return main, ahead, jnp.stack(counts)


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


@functools.partial(jax.jit, static_argnums=(2, 3))
def _stats(p, tok, static, dtype):
    with jax.default_matmul_precision("highest"):
        return _sequence(_cast(p, dtype), tok, static)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6), donate_argnums=(0,))
def _add_grads(acc, p, tok, static, dtype, scale, scale_ahead):
    """``acc + d/dp [nll_main_sum(tok) * scale + nll_mtp_sum(tok) * scale_ahead]``."""
    def objective(p32):
        main, ahead, _ = _sequence(_cast(p32, dtype), tok, static)
        return main.astype(jnp.float32) * scale + ahead.astype(jnp.float32) * scale_ahead

    with jax.default_matmul_precision("highest"):
        grads = jax.grad(objective)(p)
    return {k: acc[k] + grads[k].astype(jnp.float32) for k in acc}


def _step_loss(p, batch, static, dtype, coef):
    """``(loss, the module's own mean loss, counts)`` of one ``[B, T]`` batch."""
    b, t = batch.shape
    main, ahead, counts = 0.0, 0.0, 0
    for row in batch:
        m_s, a_s, c_s = _stats(p, jnp.asarray(row), static, dtype)
        main, ahead = main + m_s.astype(jnp.float32), ahead + a_s.astype(jnp.float32)
        counts = counts + c_s
    main, ahead = main / (b * (t - 1)), ahead / (b * (t - 2))
    return main + coef * ahead, ahead, counts


def head_of_job(dims: dict, hyper: dict, seed: int, batches, precision: str = "f32") -> dict:
    """The first two steps' losses (the whole objective), the first step's
    module loss, gradient norms (global and per parameter) and expert loads
    ``[expert layers with the module's last, published experts]``, for
    ``batches`` (two ``[B, T]`` int arrays) from the configuration's initial
    weights."""
    dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16}[precision]
    static = _Static(dims)
    coef = float(dims["mtp_loss_coef"]) if dims["num_nextn_predict_layers"] else 0.0
    p = init_params(dims, seed, hyper["init_std"])
    b, t = batches[0].shape

    loss1, ahead1, counts1 = _step_loss(p, batches[0], static, dtype, coef)
    grads = {k: jnp.zeros_like(v) for k, v in p.items()}
    for row in batches[0]:
        grads = _add_grads(grads, p, jnp.asarray(row), static, dtype, 1.0 / (b * (t - 1)), coef / (b * (t - 2)))
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
    loss2, _, _ = _step_loss(p, batches[1], static, dtype, coef)
    return {
        "losses": [float(loss1), float(loss2)],
        "mtp_losses": [float(ahead1)],
        "grad_norms": [norm],
        "group_norms": group,
        "expert_rows": np.asarray(counts1),
    }
