"""The selective state-space scan of a Mamba-2 layer (``models/lm``'s
``nemotron_h`` block kind) in its chunked, state-space-duality form, and the
plain step-by-step recurrence it has to agree with.

No analogue exists in the reference (its models are single coefficient
vectors). The recurrence, per sequence and head ``h`` (``P`` channels, a state
of ``P x N``), reading the ``B`` and ``C`` of its group ``h // (H / G)``::

    S_t = exp(delta_t A_h) S_(t-1) + delta_t x_t B_t^T        S_(-1) = 0
    y_t = S_t C_t

``delta > 0`` is the step size (after its softplus), ``A < 0`` the head's
decay rate; the skip ``D x`` and the gate are the caller's.

``reference_scan`` is that loop, one position at a time, in float32.

``ssd_scan`` computes the same ``y`` from chunks of ``chunk`` positions
(arXiv:2405.21060, section 6), so that its work is batched matmuls the MXU
takes. With ``cum_i`` the running sum of ``delta A`` inside a chunk:

- inside a chunk the state never exists: ``y_i += sum_(j<=i) (C_i . B_j)
  exp(cum_i - cum_j) delta_j x_j``, a ``[chunk, chunk]`` matrix per head
  (``C B^T`` once per GROUP, the decays and the mask per head) times the
  chunk's ``x``;
- each chunk's own contribution to the state at its end: ``sum_j exp(cum_last
  - cum_j) delta_j x_j B_j^T``;
- the recurrence over the ``T / chunk`` chunk states, ``S <- exp(cum_last) S
  + own``, in float32, in the same closed form as inside a chunk: the state a
  chunk starts from is ``sum_(c' < c) exp(sum of cum_last over the chunks
  between) own_c'``, ONE matmul of a ``[chunks, chunks]`` matrix of decays a
  head with the chunks' own contributions (no loop over the chunks: 64 of them
  at T 8,192 would be 64 trips of a few small operations, each direction);
- what the state a chunk starts from adds: ``y_i += exp(cum_i) C_i S_prev``.

Precision: ``delta``, ``A``, the cumulative log-decays, every ``exp`` and the
carried state are float32 whatever the compute type (the matmul over the chunk
states takes float32 inputs at the highest precision); the four matmuls over
positions take their inputs in the compute type (``bfloat16``: the MXU's path)
and accumulate in float32. The backward is AD through this form: every piece
is a matmul or an element-wise pass, and the caller's ``jax.checkpoint``
around the block keeps what it holds alive to the block's own backward.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["ssd_scan", "reference_scan"]

_HIGHEST = jax.lax.Precision.HIGHEST


def reference_scan(x, dt, a, b, c):
    """``y [B, T, H, P]`` float32 of the recurrence above, one position at a
    time: ``x [B, T, H, P]``, ``dt [B, T, H]``, ``a [H]``, ``b``, ``c`` ``[B,
    T, G, N]`` (head ``h`` reads group ``h // (H / G)``)."""
    heads, groups = x.shape[2], b.shape[2]
    f32 = jnp.float32
    x, dt, a = x.astype(f32), dt.astype(f32), a.astype(f32)
    b, c = (jnp.repeat(m.astype(f32), heads // groups, axis=2) for m in (b, c))  # [B, T, H, N]

    def sequence(x, dt, b, c):
        def position(state, now):
            x_t, dt_t, b_t, c_t = now
            state = jnp.exp(dt_t * a)[:, None, None] * state + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
            return state, jnp.sum(state * c_t[:, None, :], axis=-1)

        _, y = jax.lax.scan(position, jnp.zeros(x.shape[1:] + b.shape[-1:], f32), (x, dt, b, c))
        return y

    return jax.vmap(sequence)(x, dt, b, c)


def ssd_scan(x, dt, a, b, c, chunk: int, compute_dtype=jnp.float32):
    """``y [B, T, H, P]`` float32 of the recurrence above through chunks of
    ``chunk`` positions (``T`` a multiple of it); arguments as
    ``reference_scan``'s, ``compute_dtype`` the matmuls' input type."""
    batch, t, heads, p = x.shape
    groups, n = b.shape[2:]
    if t % chunk or heads % groups:
        raise ValueError(f"the scan takes whole chunks of {chunk} positions and whole groups of heads; got "
                         f"T {t}, {heads} heads in {groups} groups")
    cd, f32 = jnp.dtype(compute_dtype), jnp.float32
    precision = _HIGHEST if cd == f32 else None
    nc, r = t // chunk, heads // groups

    def dot(spec, lhs, rhs):
        return jnp.einsum(spec, lhs.astype(cd), rhs.astype(cd), preferred_element_type=f32, precision=precision)

    # heads as (group, head of the group); the per-head rows with the chunk's positions last
    xq = x.reshape(batch, nc, chunk, groups, r, p)
    bq, cq = b.reshape(batch, nc, chunk, groups, n), c.reshape(batch, nc, chunk, groups, n)
    dtq = jnp.transpose(dt.astype(f32).reshape(batch, nc, chunk, groups, r), (0, 1, 3, 4, 2))  # [B, c, G, r, Q]
    cum = jnp.cumsum(dtq * a.astype(f32).reshape(groups, r, 1), axis=-1)
    last = cum[..., -1:]

    # inside a chunk: (C B^T * L) (delta x), L the decays from j to i under the causal mask
    seen = jnp.tril(jnp.ones((chunk, chunk), bool))
    decays = jnp.exp(jnp.where(seen, cum[..., :, None] - cum[..., None, :], -jnp.inf))  # [B, c, G, r, Q, Q]
    cb = dot("bcign,bcjgn->bcgij", cq, bq)
    y = dot("bcgrij,bcjgrp->bcigrp", cb[:, :, :, None] * decays * dtq[..., None, :], xq)

    # each chunk's own contribution to the state at its end, then the recurrence over the chunks: the state
    # chunk z starts from is every earlier chunk's own, decayed over the chunks between them
    to_end = jnp.transpose(jnp.exp(last - cum) * dtq, (0, 1, 4, 2, 3))  # [B, c, Q, G, r]
    own = dot("bcjgrp,bcjgn->bcgrpn", xq * to_end[..., None], bq)
    through = jnp.cumsum(last[..., 0], axis=1)  # the log-decay from the sequence's start through chunk c
    # from chunk c's end to chunk z's start; the shifted sum itself (``through - last`` would round once more)
    since = jnp.concatenate([jnp.zeros_like(through[:, :1]), through[:, :-1]], axis=1)[:, :, None] - through[:, None]
    earlier = jnp.tril(jnp.ones((nc, nc), bool), -1)[None, :, :, None, None]
    between = jnp.exp(jnp.where(earlier, since, -jnp.inf))  # [B, z, c, G, r]
    before = jnp.einsum("bzcgr,bcgrpn->bzgrpn", between, own, preferred_element_type=f32, precision=_HIGHEST)
    from_start = jnp.transpose(jnp.exp(cum), (0, 1, 4, 2, 3))  # [B, c, Q, G, r]
    y = y + dot("bcign,bcgrpn->bcigrp", cq, before) * from_start[..., None]
    return y.reshape(batch, t, heads, p)
