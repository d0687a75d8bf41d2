"""Fused (flash-style) local attention block for ring attention.

The ring schedule's hot op is the per-step fold: this shard's queries
against the currently resident KV block, folded into the streaming-softmax
accumulator (``parallel/ring.py``). The jnp form materializes the
``[B, H, Tq, Tk]`` score and probability tensors through HBM every step —
at long local sequence lengths that traffic, not the matmuls, bounds the
step.

This module fuses one fold into a Pallas kernel: per ``(batch·head,
Q-tile)`` grid cell, the scores for the whole resident KV block live only
in VMEM — matmul, mask, streaming-softmax rescale and the ``p @ v``
accumulation happen in one pass, and only the ``O(T·D)`` accumulator
state touches HBM. The numerics replicate the jnp fold exactly: running
max with ``-inf`` hygiene (rows with nothing attendable yet must not
produce NaNs), masked positions dropped before the exponential, and the
same correction factors.

Gradients: ``fused_fold`` carries a ``jax.custom_vjp`` whose backward is
fused too — a hand-derived fold VJP (``reference_fold_bwd``, pinned
against jax AD including the ``-inf`` first-fold, masked-row and max-tie
edges) run as two Pallas kernels: a dq-kernel owning full score rows
(which also emits the row-level max/tie quantities) and a dkv-kernel
owning score columns with Q-axis grid accumulation. ``jax.grad`` through
ring attention is therefore exact and never materializes scores in HBM.

Grouped queries: ``q`` is ``[B, H, T, D]``; ``kb``/``vb`` are ``[B, H_kv, T,
D]`` with ``H`` a multiple of ``H_kv`` (equal for multi-head attention). Query
head ``h`` reads key/value block ``h // (H / H_kv)`` through the kernels'
index maps, so K and V are never repeated in HBM, consecutive grid cells of
one group reuse the block they staged, and the dkv-kernel sums ``dk``, ``dv``
over the group's query heads on its accumulation axis: they come out ``[B,
H_kv, Tk, D]``. With ``H_kv = H`` the index maps and the grid are what they
were. ``reference_fold`` keeps equal head counts: a grouped caller is tested
against it on K and V repeated.

Availability: TPU compiled, or any backend under ``interpret=True``. The
caller (``ring.py``) falls back to the jnp fold when the local length does
not tile or the devices have no Mosaic backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = [
    "fused_fold",
    "flash_available",
    "flash_train_available",
    "reference_fold",
    "TQ_TILE",
]

TQ_TILE = 256  # Q rows per grid cell


_KV_VMEM_BUDGET = 1 << 20  # Tk*D f32 elements the kernel may stage per head
# T=8192 (with D=128, so T*D == _KV_VMEM_BUDGET) is the largest shape whose
# Mosaic compilation is verified on hardware: the forward there since the ring
# tests, all three kernels in a TRAINING graph since models/lm trained ZAYA1-8B's
# block on the chip at 2 x 8 query heads on 2 key/value heads x 8,192 x 128 in
# bfloat16 (PERF.md, PR 30). Every admitted (T, D) then has
# score-buffer and KV footprints <= that shape's in all three kernels. 16384
# admitted shapes (e.g. T=16384, D=64) stage [TQ_TILE, 16384] f32 scores plus
# full KV — past the scoped-VMEM limit on paper and never compile-checked on
# chip, so they are rejected until verified.
_TK_MAX = 8192


def flash_available(T: int, D: int, devices=None) -> bool:
    """Whether the fused fold applies: Q tiles must divide the local length,
    one head's KV block AND the [TQ_TILE, Tk] score/probability buffers must
    fit the kernel's VMEM staging (the fold brings the whole resident block
    on-chip; past either budget the jnp fold's streamed HBM form is the
    right tool), and the devices must be TPUs (Mosaic target)."""
    from flink_ml_tpu.parallel.mesh import is_tpu_backend

    if T % TQ_TILE or T * D > _KV_VMEM_BUDGET or T > _TK_MAX:
        return False
    return is_tpu_backend(devices if devices is not None else jax.devices())


# Scoped VMEM the three kernels may use. A grid cell stages one head's whole
# resident K and V (double-buffered) beside its [tile, Tk] f32 score buffers:
# at T=4096, D=128 that is 16.5 MB in the forward and 20.4 MB in the dkv
# kernel, past Mosaic's default scoped limit of 16 MB whatever batch*heads is
# (the compile fails "allocating on stack" for the kernel's custom call). At
# T=8192, D=128 in bfloat16 the training graph's three kernels compile for the
# chip with the limit at 24 MB and not at 16 (K and V are 2 MB each, twice for
# the double buffer; the forward's [256, 8192] f32 scores are 8 MB a buffer, the
# dq kernel's [64, 8192] 2 MB, the dkv kernel's [2048, 256] 2 MB whatever T is).
# A v5e core has 128 MiB of VMEM; 96 MiB covers every shape ``flash_available``
# admits and leaves XLA its own share.
_VMEM_LIMIT_BYTES = 96 << 20


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT_BYTES)


# The attention classifier's envelope for a TRAINING (fwd+bwd) graph, as
# measured on a v5e chip before the kernels stated a VMEM limit: compile
# failures at B*H*T*(D+2)*4 = 16.8-17.2 MB (B=1, T=8192, H=4, D=128), success
# at 8.4 MB (B=1, T=4096). That note blamed XLA for placing the backward's
# [B*H, T, D] outputs in VMEM; compiled for the chip at 4 x 16 x 4096 x 128
# the failure is the kernels' own scoped allocation above, which depends on
# (T, D) alone, and ``_VMEM_LIMIT_BYTES`` cures it (models/lm trains there on
# the fused fold and does not ask this gate, at T=4096 and since PR 30 at
# T=8192). The classifier keeps its tested envelope until its own larger
# shapes are run on a chip: ROADMAP S5.
_TRAIN_OUT_VMEM_BUDGET = 9 << 20


def flash_train_available(T: int, D: int, batch: int, n_heads: int, devices=None) -> bool:
    """Whether the attention classifier's TRAINING step (fwd + the fused
    backward) takes the fused fold: ``flash_available`` and the envelope
    above. Past it the jnp fold trains the same numbers through HBM."""
    if not flash_available(T, D, devices):
        return False
    return batch * n_heads * T * (D + 2) * 4 <= _TRAIN_OUT_VMEM_BUDGET


def _kv_block_of(n_heads: int, n_kv_heads: int):
    """Grouped queries: which row of the flattened ``[B * H_kv, Tk, D]`` keys a
    row ``i`` of the flattened ``[B * H, ...]`` queries reads. Query head ``h``
    reads key/value head ``h // group``, and ``B * H`` is whole groups, so it is
    ``i // group``; one head a group is the identity, left out of the index map."""
    if n_heads % n_kv_heads:
        raise ValueError(f"{n_heads} query heads do not divide over {n_kv_heads} key/value heads")
    group = n_heads // n_kv_heads
    return (lambda i: i) if group == 1 else (lambda i: i // group)


def reference_fold(q, kb, vb, m, l, acc, q_pos0, k_pos0, causal, n_valid, scale):
    """The jnp fold in [B, H, ...] layout (ring.py numerics) — the source of
    truth the kernel is tested against and the backward recomputes through.

    ``q`` [B, H, Tq, D]; ``kb``/``vb`` [B, H, Tk, D] (equal head counts here;
    the kernels also take ``[B, H_kv, Tk, D]``); ``m``/``l`` [B, H, Tq];
    ``acc`` [B, H, Tq, D]. ``q_pos0``/``k_pos0`` are the global positions of
    query/key 0 (traced scalars); ``n_valid`` masks keys at global positions
    >= it (None = unmasked).
    """
    Tq, Tk = q.shape[2], kb.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kb) * scale
    if causal or n_valid is not None:
        q_pos = q_pos0 + jnp.arange(Tq)
        k_pos = k_pos0 + jnp.arange(Tk)
        mask = jnp.ones((Tq, Tk), bool)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if n_valid is not None:
            mask &= (k_pos < jnp.asarray(n_valid))[None, :]
        s = jnp.where(mask[None, None, :, :], s, -jnp.inf)
    block_max = jnp.max(s, axis=-1)
    new_m = jnp.maximum(m, block_max)
    safe_m = jnp.where(jnp.isneginf(new_m), 0.0, new_m)
    p = jnp.exp(s - safe_m[..., None])
    p = jnp.where(jnp.isneginf(s), 0.0, p)
    correction = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - safe_m))
    new_l = l * correction + jnp.sum(p, axis=-1)
    new_acc = acc * correction[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vb)
    return new_m, new_l, new_acc


def _fold_pallas(q, kb, vb, m, l, acc, q_pos0, k_pos0, causal, n_valid, scale,
                 interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Tq, D = q.shape
    Hkv, Tk = kb.shape[1], kb.shape[2]
    BH = B * H
    kv_of = _kv_block_of(H, Hkv)
    masked = n_valid is not None

    def kernel(scalars_ref, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
               mo_ref, lo_ref, ao_ref):
        j = pl.program_id(1)
        qt = q_ref[0]  # [TQ, D]
        s = jax.lax.dot_general(
            qt, k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [TQ, Tk]
        if causal or masked:
            q_pos = (
                scalars_ref[0] + j * TQ_TILE
                + jax.lax.broadcasted_iota(jnp.int32, (TQ_TILE, Tk), 0)
            )
            k_pos = scalars_ref[1] + jax.lax.broadcasted_iota(
                jnp.int32, (TQ_TILE, Tk), 1
            )
            keep = jnp.ones((TQ_TILE, Tk), bool)
            if causal:
                keep &= q_pos >= k_pos
            if masked:
                keep &= k_pos < scalars_ref[2]
            s = jnp.where(keep, s, -jnp.inf)
        # m/l ride as [TQ, 1] columns (Mosaic wants >= 2-D tiles with an
        # aligned or full trailing dim); all the math stays 2-D.
        mcol = m_ref[0]  # [TQ, 1]
        new_m = jnp.maximum(mcol, jnp.max(s, axis=1, keepdims=True))
        safe_m = jnp.where(jnp.isneginf(new_m), 0.0, new_m)
        p = jnp.exp(s - safe_m)
        p = jnp.where(jnp.isneginf(s), 0.0, p)
        correction = jnp.where(jnp.isneginf(mcol), 0.0, jnp.exp(mcol - safe_m))
        mo_ref[0] = new_m
        lo_ref[0] = l_ref[0] * correction + jnp.sum(p, axis=1, keepdims=True)
        # bfloat16 q/k/v (models/lm) take the MXU's bf16 path in every dot:
        # the f32 operand is rounded to the staged operand's type, a no-op
        # for the f32 callers
        ao_ref[0] = acc_ref[0] * correction + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32
        )

    scalars = jnp.stack(
        [
            jnp.asarray(q_pos0, jnp.int32),
            jnp.asarray(k_pos0, jnp.int32),
            jnp.asarray(0 if n_valid is None else n_valid, jnp.int32),
        ]
    )
    tile2 = pl.BlockSpec(
        (1, TQ_TILE, 1), lambda i, j, *_: (i, j, 0), memory_space=pltpu.VMEM
    )
    tile3 = pl.BlockSpec(
        (1, TQ_TILE, D), lambda i, j, *_: (i, j, 0), memory_space=pltpu.VMEM
    )
    full3 = pl.BlockSpec((1, Tk, D), lambda i, j, *_: (kv_of(i), 0, 0), memory_space=pltpu.VMEM)
    from flink_ml_tpu.parallel.mesh import vma_of

    vma = vma_of(q)
    mo, lo, ao = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, Tq // TQ_TILE),
            in_specs=[tile3, full3, full3, tile2, tile2, tile3],
            out_specs=[tile2, tile2, tile3],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tq, 1), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((BH, Tq, 1), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((BH, Tq, D), jnp.float32, vma=vma),
        ],
        interpret=interpret,
        compiler_params=_compiler_params(),
        name="flash_fold_fwd",
    )(
        scalars,
        q.reshape(BH, Tq, D),
        kb.reshape(B * Hkv, Tk, D),
        vb.reshape(B * Hkv, Tk, D),
        m.reshape(BH, Tq, 1),
        l.reshape(BH, Tq, 1),
        acc.reshape(BH, Tq, D),
    )
    return mo.reshape(B, H, Tq), lo.reshape(B, H, Tq), ao.reshape(B, H, Tq, D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 11, 12))
def fused_fold(q, kb, vb, m, l, acc, q_pos0, k_pos0, causal, has_n_valid,
               n_valid, scale, interpret=False):
    """One ring-attention fold, fused. Same contract as ``reference_fold``,
    and ``kb``/``vb`` may carry fewer heads than ``q`` (grouped queries: the
    module docstring) (``n_valid`` is a traced scalar consumed only when ``has_n_valid``);
    the primal runs the Pallas forward kernel and gradients run the fused
    backward kernels (``_fold_bwd_pallas``, AD-exact).
    ``causal``/``has_n_valid``/``scale``/``interpret`` are static.
    """
    return _fold_pallas(
        q, kb, vb, m, l, acc, q_pos0, k_pos0, causal,
        n_valid if has_n_valid else None, scale, interpret=interpret,
    )


def _fused_fold_fwd(q, kb, vb, m, l, acc, q_pos0, k_pos0, causal, has_n_valid,
                    n_valid, scale, interpret=False):
    out = fused_fold(
        q, kb, vb, m, l, acc, q_pos0, k_pos0, causal, has_n_valid, n_valid,
        scale, interpret,
    )
    return out, (q, kb, vb, m, l, acc, q_pos0, k_pos0, n_valid)


def _fused_fold_bwd(causal, has_n_valid, scale, interpret, res, g):
    q, kb, vb, m, l, acc, q_pos0, k_pos0, n_valid = res
    dm, dl, dacc = g
    dq, dkb, dvb, dm_in, dl_in, dacc_in = _fold_bwd_pallas(
        q, kb, vb, m, l, acc, q_pos0, k_pos0, causal,
        n_valid if has_n_valid else None, scale, dm, dl, dacc,
        interpret=interpret,
    )
    # integer position/count args carry no cotangent
    return (dq.astype(q.dtype), dkb.astype(kb.dtype), dvb.astype(vb.dtype),
            dm_in, dl_in, dacc_in, None, None, None)


fused_fold.defvjp(_fused_fold_fwd, _fused_fold_bwd)


# ---------------------------------------------------------------------------
# Fused backward: the fold's hand-derived VJP (pinned against jax.vjp of
# reference_fold, including the -inf first-fold and masked-row edges) run as
# two Pallas kernels. The dq-kernel owns full score rows, so it computes the
# row-level quantities (safe max, block max, tie coefficient) once and hands
# them to the dkv-kernel, whose cells own score columns.
# ---------------------------------------------------------------------------

_TQ_BWD = 64  # Q rows per dq-kernel cell (3 [TQ, Tk] f32 buffers live at once)
_TK_BWD = 256  # K rows per dkv-kernel cell
_TQ_DKV = 2048  # Q rows per dkv accumulation step (third grid dim)


def reference_fold_bwd(q, kb, vb, m, l, acc, q_pos0, k_pos0, causal, n_valid,
                       scale, dm, dl, dacc):
    """Hand-derived VJP of ``reference_fold`` — AD-equivalent (max ties split
    0.5/0.5 like ``jnp.maximum``; reduce-max ties spread evenly). The jnp
    source of truth the Pallas backward kernels are tested against."""
    Tq, Tk = q.shape[2], kb.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kb) * scale
    if causal or n_valid is not None:
        q_pos = q_pos0 + jnp.arange(Tq)
        k_pos = k_pos0 + jnp.arange(Tk)
        keep = jnp.ones((Tq, Tk), bool)
        if causal:
            keep &= q_pos[:, None] >= k_pos[None, :]
        if n_valid is not None:
            keep &= (k_pos < jnp.asarray(n_valid))[None, :]
        s = jnp.where(keep[None, None], s, -jnp.inf)
    B = jnp.max(s, axis=-1)
    new_m = jnp.maximum(m, B)
    safe = jnp.where(jnp.isneginf(new_m), 0.0, new_m)
    P = jnp.exp(s - safe[..., None])
    P = jnp.where(jnp.isneginf(s), 0.0, P)
    corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - safe))

    dP = dl[..., None] + jnp.einsum("bhqd,bhkd->bhqk", dacc, vb)
    dv = jnp.einsum("bhqk,bhqd->bhkd", P, dacc)
    dcorr = dl * l + jnp.sum(dacc * acc, axis=-1)
    dl_in = dl * corr
    dacc_in = dacc * corr[..., None]
    ds = dP * P
    dsafe = -jnp.sum(dP * P, axis=-1) - dcorr * corr
    dm_in = jnp.where(jnp.isneginf(m), 0.0, dcorr * corr)
    dnew_m = dm + jnp.where(jnp.isneginf(new_m), 0.0, dsafe)
    take_m = jnp.where(m > B, 1.0, jnp.where(m == B, 0.5, 0.0))
    dm_in = dm_in + dnew_m * take_m
    dB = dnew_m * (1.0 - take_m)
    is_max = (s == B[..., None]) & ~jnp.isneginf(s)
    cnt = jnp.maximum(jnp.sum(is_max, axis=-1), 1)
    ds = ds + is_max * (dB / cnt)[..., None]
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, kb) * scale
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q) * scale
    return dq, dk, dv, dm_in, dl_in, dacc_in


def _fold_bwd_pallas(q, kb, vb, m, l, acc, q_pos0, k_pos0, causal, n_valid,
                     scale, dm, dl, dacc, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from flink_ml_tpu.parallel.mesh import vma_of

    B_, H, Tq, D = q.shape
    Hkv, Tk = kb.shape[1], kb.shape[2]
    BH, BHkv = B_ * H, B_ * Hkv
    group = H // Hkv
    kv_of = _kv_block_of(H, Hkv)
    masked = n_valid is not None
    # tiles clamp to the largest 256-aligned divisor of the actual dims
    # (flash_available guarantees T % 256 == 0, so these always divide)
    tq_bwd = min(_TQ_BWD, Tq)
    tk_bwd = min(_TK_BWD, Tk)
    tq_dkv = next(c for c in (_TQ_DKV, 1024, 512, 256, Tq) if Tq % c == 0)
    n_q_dkv = Tq // tq_dkv

    def mask_of(q_pos, k_pos):
        keep = jnp.ones(q_pos.shape, bool)
        if causal:
            keep &= q_pos >= k_pos
        return keep

    def dq_kernel(scalars_ref, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                  dm_ref, dl_ref, dacc_ref,
                  dqo_ref, dmo_ref, dlo_ref, dao_ref, safe_ref, b_ref, dbc_ref):
        j = pl.program_id(1)
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [TQ, Tk]
        if causal or masked:
            q_pos = (
                scalars_ref[0] + j * tq_bwd
                + jax.lax.broadcasted_iota(jnp.int32, (tq_bwd, Tk), 0)
            )
            k_pos = scalars_ref[1] + jax.lax.broadcasted_iota(
                jnp.int32, (tq_bwd, Tk), 1
            )
            keep = mask_of(q_pos, k_pos)
            if masked:
                keep &= k_pos < scalars_ref[2]
            s = jnp.where(keep, s, -jnp.inf)
        mcol = m_ref[0]  # [TQ, 1]
        Bcol = jnp.max(s, axis=1, keepdims=True)
        new_m = jnp.maximum(mcol, Bcol)
        safe = jnp.where(jnp.isneginf(new_m), 0.0, new_m)
        P = jnp.exp(s - safe)
        P = jnp.where(jnp.isneginf(s), 0.0, P)
        corr = jnp.where(jnp.isneginf(mcol), 0.0, jnp.exp(mcol - safe))

        dlc = dl_ref[0]  # [TQ, 1]
        dP = dlc + jax.lax.dot_general(
            dacc_ref[0].astype(v_ref.dtype), v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [TQ, Tk]
        dPP = dP * P
        dcorr = dlc * l_ref[0] + jnp.sum(
            dacc_ref[0] * acc_ref[0], axis=1, keepdims=True
        )
        dsafe = -jnp.sum(dPP, axis=1, keepdims=True) - dcorr * corr
        dnew_m = dm_ref[0] + jnp.where(jnp.isneginf(new_m), 0.0, dsafe)
        take_m = jnp.where(mcol > Bcol, 1.0, jnp.where(mcol == Bcol, 0.5, 0.0))
        dB = dnew_m * (1.0 - take_m)
        is_max = (s == Bcol) & ~jnp.isneginf(s)
        cnt = jnp.maximum(jnp.sum(is_max.astype(jnp.float32), axis=1, keepdims=True), 1.0)
        dbc = dB / cnt
        ds = dPP + is_max.astype(jnp.float32) * dbc
        dqo_ref[0] = jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        dmo_ref[0] = jnp.where(jnp.isneginf(mcol), 0.0, dcorr * corr) + dnew_m * take_m
        dlo_ref[0] = dlc * corr
        dao_ref[0] = dacc_ref[0] * corr
        safe_ref[0] = safe
        b_ref[0] = Bcol
        dbc_ref[0] = dbc

    def dkv_kernel(scalars_ref, k_ref, v_ref, q_ref, dacc_ref, dl_ref,
                   safe_ref, b_ref, dbc_ref, dko_ref, dvo_ref):
        # grid (B*H_kv, ktiles, group*qtiles): the innermost axis walks the q
        # tiles of each query head of the group in turn; it is the accumulation
        # dim — dk/dv blocks are revisited across it and accumulated in VMEM.
        jk = pl.program_id(1)
        jq = pl.program_id(2)
        first = jq == 0
        if group > 1:
            jq = jq % n_q_dkv
        s_col = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [TQ_DKV, TK]
        if causal or masked:
            q_pos = (
                scalars_ref[0] + jq * tq_dkv
                + jax.lax.broadcasted_iota(jnp.int32, (tq_dkv, tk_bwd), 0)
            )
            k_pos = (
                scalars_ref[1] + jk * tk_bwd
                + jax.lax.broadcasted_iota(jnp.int32, (tq_dkv, tk_bwd), 1)
            )
            keep = mask_of(q_pos, k_pos)
            if masked:
                keep &= k_pos < scalars_ref[2]
            s_col = jnp.where(keep, s_col, -jnp.inf)
        P_col = jnp.exp(s_col - safe_ref[0])
        P_col = jnp.where(jnp.isneginf(s_col), 0.0, P_col)
        dacc_t = dacc_ref[0].astype(v_ref.dtype)
        dP_col = dl_ref[0] + jax.lax.dot_general(
            dacc_t, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        is_max = (s_col == b_ref[0]) & ~jnp.isneginf(s_col)
        ds_col = dP_col * P_col + is_max.astype(jnp.float32) * dbc_ref[0]
        dk_part = jax.lax.dot_general(
            ds_col.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        dv_part = jax.lax.dot_general(
            P_col.astype(v_ref.dtype), dacc_t, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        @pl.when(first)
        def _():
            dko_ref[0] = jnp.zeros_like(dko_ref[0])
            dvo_ref[0] = jnp.zeros_like(dvo_ref[0])

        dko_ref[0] += dk_part
        dvo_ref[0] += dv_part

    scalars = jnp.stack(
        [
            jnp.asarray(q_pos0, jnp.int32),
            jnp.asarray(k_pos0, jnp.int32),
            jnp.asarray(0 if n_valid is None else n_valid, jnp.int32),
        ]
    )
    vma = vma_of(q)

    def col(tile):
        return pl.BlockSpec((1, tile, 1), lambda i, j, *_: (i, j, 0), memory_space=pltpu.VMEM)

    def mat(tile):
        return pl.BlockSpec((1, tile, D), lambda i, j, *_: (i, j, 0), memory_space=pltpu.VMEM)

    fullk_mat = pl.BlockSpec((1, Tk, D), lambda i, j, *_: (kv_of(i), 0, 0), memory_space=pltpu.VMEM)

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, vma=vma)

    q4 = q.reshape(BH, Tq, D)
    k4 = kb.reshape(BHkv, Tk, D)
    v4 = vb.reshape(BHkv, Tk, D)
    dacc4 = dacc.reshape(BH, Tq, D)
    dl4 = dl.reshape(BH, Tq, 1)
    dq_o, dm_o, dl_o, dacc_o, safe_r, b_r, dbc_r = pl.pallas_call(
        dq_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, Tq // tq_bwd),
            in_specs=[
                mat(tq_bwd), fullk_mat, fullk_mat,
                col(tq_bwd), col(tq_bwd), mat(tq_bwd),
                col(tq_bwd), col(tq_bwd), mat(tq_bwd),
            ],
            out_specs=[
                mat(tq_bwd), col(tq_bwd), col(tq_bwd), mat(tq_bwd),
                col(tq_bwd), col(tq_bwd), col(tq_bwd),
            ],
        ),
        out_shape=[
            sds((BH, Tq, D)), sds((BH, Tq, 1)), sds((BH, Tq, 1)),
            sds((BH, Tq, D)), sds((BH, Tq, 1)), sds((BH, Tq, 1)),
            sds((BH, Tq, 1)),
        ],
        interpret=interpret,
        compiler_params=_compiler_params(),
        name="flash_fold_bwd_dq",
    )(
        scalars, q4, k4, v4,
        m.reshape(BH, Tq, 1), l.reshape(BH, Tq, 1), acc.reshape(BH, Tq, D),
        dm.reshape(BH, Tq, 1), dl4, dacc4,
    )

    kmat = pl.BlockSpec(
        (1, tk_bwd, D), lambda i, jk, jq, *_: (i, jk, 0), memory_space=pltpu.VMEM
    )
    if group == 1:
        def q_block(i, jk, jq, *_):
            return (i, jq, 0)
    else:
        def q_block(i, jk, jq, *_):  # the group's query heads one after another
            return (i * group + jq // n_q_dkv, jq % n_q_dkv, 0)

    qmat = pl.BlockSpec((1, tq_dkv, D), q_block, memory_space=pltpu.VMEM)
    qcol = pl.BlockSpec((1, tq_dkv, 1), q_block, memory_space=pltpu.VMEM)
    dk_o, dv_o = pl.pallas_call(
        dkv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BHkv, Tk // tk_bwd, group * n_q_dkv),
            in_specs=[kmat, kmat, qmat, qmat, qcol, qcol, qcol, qcol],
            out_specs=[kmat, kmat],
        ),
        out_shape=[sds((BHkv, Tk, D)), sds((BHkv, Tk, D))],
        interpret=interpret,
        compiler_params=_compiler_params(),
        name="flash_fold_bwd_dkv",
    )(scalars, k4, v4, q4, dacc4, dl4, safe_r, b_r, dbc_r)

    return (
        dq_o.reshape(B_, H, Tq, D),
        dk_o.reshape(B_, Hkv, Tk, D),
        dv_o.reshape(B_, Hkv, Tk, D),
        dm_o.reshape(B_, H, Tq),
        dl_o.reshape(B_, H, Tq),
        dacc_o.reshape(B_, H, Tq, D),
    )
