"""Fused (flash-style) local attention block for ring attention.

The ring schedule's hot op is the per-step fold: this shard's queries
against the currently resident KV block, folded into the streaming-softmax
accumulator (``parallel/ring.py``). The jnp form materializes the
``[B, H, Tq, Tk]`` score and probability tensors through HBM every step —
at long local sequence lengths that traffic, not the matmuls, bounds the
step.

This module fuses one fold into a Pallas kernel: per ``(batch·head,
Q-tile)`` grid cell, one head's resident K and V are staged whole in VMEM and
the tile's scores live only there — matmul, mask, streaming-softmax rescale
and the ``p @ v`` accumulation happen in the cell, and only the ``O(T·D)``
accumulator state touches HBM. Without ``causal``, and for a block of a single
key chunk, a cell takes the whole block's scores in one piece and masks them
there. Under ``causal`` a longer block is walked in key chunks, and a cell
visits only those that hold an entry the mask keeps, from the two position
scalars it prefetches: a ring step behind the diagonal runs without any mask
work, the LM's fold (``q_pos0 = k_pos0``, ``Tq = Tk``) over a little more than
half the square, a step ahead of the diagonal over nothing ("the causal fold's
key chunks" below; ``fold_chunk_counts`` counts the pairs). The numerics
replicate the jnp fold: running max with ``-inf`` hygiene (rows with nothing
attendable yet must not produce NaNs), masked positions dropped before the
exponential, and the same correction factors; a skipped entry is one the mask
gives ``-inf``, so the walk changes the order of the sums and nothing else.

Gradients: ``fused_fold`` carries a ``jax.custom_vjp`` whose backward is
fused too — a hand-derived fold VJP (``reference_fold_bwd``, pinned
against jax AD including the ``-inf`` first-fold, masked-row and max-tie
edges) run as two Pallas kernels: a dq-kernel owning full score rows
(which also emits the row-level max/tie quantities) and a dkv-kernel
owning score columns with Q-axis grid accumulation. ``jax.grad`` through
ring attention is therefore exact and never materializes scores in HBM.
Under ``causal`` the dq-kernel walks the visited key chunks three times (row
max; ``dP * P`` with its row sum and the tie count; ``ds @ K``), scores and
``dP * P`` waiting in VMEM so no matmul runs twice, and the dkv-kernel's grid
skips the (key tile, query tile) pairs the mask hides.

Grouped queries: ``q`` is ``[B, H, T, D]``; ``kb``/``vb`` are ``[B, H_kv, T,
D]`` with ``H`` a multiple of ``H_kv`` (equal for multi-head attention). Query
head ``h`` reads key/value block ``h // (H / H_kv)`` through the kernels'
index maps, so K and V are never repeated in HBM, consecutive grid cells of
one group reuse the block they staged, and the dkv-kernel sums ``dk``, ``dv``
over the group's query heads on its accumulation axis: they come out ``[B,
H_kv, Tk, D]``. With ``H_kv = H`` the index maps and the grid are what they
were. ``reference_fold`` keeps equal head counts: a grouped caller is tested
against it on K and V repeated.

A value head of its own size: the scores contract ``q`` and ``kb`` over their
``D`` channels and the output is as wide as ``vb``'s heads, ``D_v``: ``vb``
``[B, H_kv, T, D_v]``, ``acc`` and its gradient ``[B, H, T, D_v]``, ``dk`` at
``D`` and ``dv`` at ``D_v`` (latent attention's heads: 192-wide queries and
keys, 128-wide values). Each operand is staged and tiled at its own width and
the kernels' bodies are the same; with ``D_v = D`` every block spec is what it
was.

A sliding window: ``window`` (static, with ``causal``) keeps of each query's
keys the ``window`` that end at the query, ``t - window < j <= t``. It is a
second edge on the same walk: a cell also skips the chunks whose last key is
too old for its first query, and masks the chunk the window's edge crosses as
it masks the diagonal's (``_window_chunks``); the dkv-kernel's grid skips the
pairs on either side. The kernels of a windowed fold are named
``flash_fold_win_*``; without a window every kernel is traced as it was before
the fold knew of one.

The block-diffusion mask: ``blocks`` (static: a ``BlockDiffusion`` of ``T``
tokens in blocks of ``L``; the one-block form alone takes it) is the third
mask form, in the causal one's place. The ``2 T`` positions are a sequence and
its noised copy behind it, ``[x ; x~]``, and with ``b(i) = i // L`` of a
position in its own half a clean query keeps the clean keys of ``b(j) <=
b(i)``, a noised query the clean keys of ``b(j) < b(i)`` and the noised keys of
``b(j) = b(i)``, and no clean query a noised key: block-causal on the clean
half, strictly block-causal from the noised half onto it, block-diagonal inside
the noised half. The clean half leads so that everything kept lies under the
(block) diagonal of the ``2 T x 2 T`` square, and a cell walks TWO key ranges
(``_bd_chunks``): the clean chunks that hold a key its rows keep - for a tile
of clean rows the causal walk's own chunks, for a tile of noised rows the
chunks before its first block - whole where every row keeps them and masked
where not, and then the band, the one or two chunks that hold the tile's own
noised positions, masked. At ``T`` 4,096 and the tiles below the forward
visits 37.5% of its (tile, chunk) pairs and the backward kernel, at chunks half
as long, 31.25% (``fold_chunk_counts``; the mask keeps ``T^2 + T L`` of the ``4
T^2`` entries, 25.02%) where a causal walk of the same 8,192 positions visits
56.25% and 53.1%. A crossed chunk's mask is two compares of block ids (``_bd_keep``: shifts, ``L``
a power of two) worked out on one column of rows and one row of keys. The
kernels are named ``flash_fold_bd_*``; the causal and the windowed forms trace
to what they traced to before the fold knew of it. The ring's entry
(``fused_fold``) does not take it; ``reference_fold`` does.

The one-block form: ``fused_attention`` is the same fold where the ring has
ONE step: a whole sequence on itself (``q_pos0 = k_pos0 = 0``, ``Tq = Tk``,
nothing carried in). Its forward hands back the normalised ``o = acc / l`` in
float32 and one log-sum-exp a row, ``lse = m + log l``; its residuals are ``q,
k, v, o, lse``; its backward is the softmax's own VJP, ``P = exp(s - lse)``,
``ds = P (dP - delta)`` with ``delta = rowsum(dO * o)``. There are no tie terms:
the gradient through the row maximum, ``dsafe = -(dl l + dacc . acc)``, is zero
once the output is normalised (``dacc = dO / l``, ``dl = -dO . o / l``), so
what the ring form computes there (``take_m``, ``is_max``, the tie count,
``dbc``) is rounding noise of a term that cancels. With ``lse`` known and
``delta`` formed in the cell before the walk, a chunk's ``P`` and ``ds`` are
final as they are formed, so the backward is ONE kernel and ONE walk: a query
tile visits its visible key chunks once (the forward's walk and helpers at
chunks of its own, 512 keys: it parks nothing, and a window's or a band's edge
wastes less of a shorter chunk) and each chunk's ``s``, ``P``, ``dP`` and
``ds`` feed all three gradients, five matmuls and one ``exp`` a visited pair
where a dq kernel and a dkv kernel, each forming the scores for itself, made
seven and two. A chunk's scores are formed transposed (``s^T = k q^T``,
``[keys, rows]``), so ``lse`` and ``delta`` broadcast down the sublanes as
``[1, rows]`` rows, ``dk += ds^T q`` and ``dv += P^T dO`` are plain matmuls
whose left operand stands as it was made, and ``dq += (ds^T)^T k`` alone has
its left operand transposed (one of the three has, whichever way the scores
lie), which Mosaic lowers itself. ``dq`` leaves a tile at the end of its walk.
``dk`` and ``dv`` of a key/value head wait whole in VMEM in float32 (``T x (D +
D_v) x 4`` B: 10.5 MB at 8,192 x (192 + 128)) from the first tile of the
head's first query head to the last tile of its last, added into at each
visited chunk's rows and written once, scaled and cast: the grid is ``(B x H,
T / rows)``, a group's query heads follow one another, and their cells name
the same ``dk`` and ``dv`` block, so grouped queries are summed with no second
pass.
The kernel keeps the dkv kernel's name (``flash_fold[_win|_bd]_bwd_dkv``:
what reads the fold's time by name goes on reading all of it). ONE float32
row statistic crosses HBM, twice (``lse`` out of the forward and into the
backward; ``delta`` never leaves a cell), where the ring form's kernels move
seventeen, and it lies along the lanes (``[B x H, 1, T]``; a ``[B x H, T, 1]``
column is padded 128 times by the device's tiling). The tiles, the walk's
helpers and the kernels' names are the ring form's. Who calls which:
``models/lm/decoder_lm._fold`` (training, ``transform``, ``log_likelihood``)
calls ``fused_attention``; ``ring.py`` (state carried across ring steps,
``n_valid``, steps without ``causal``) and ``SelfAttentionClassifier`` need
the general contract and keep ``fused_fold``. The two share no contract, and
the choice is made by who calls, never by an option.

Availability: TPU compiled, or any backend under ``interpret=True``. The
caller (``ring.py``) falls back to the jnp fold when the local length does
not tile or the devices have no Mosaic backend.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = [
    "fused_fold",
    "fused_attention",
    "BlockDiffusion",
    "flash_available",
    "flash_train_available",
    "reference_fold",
    "fold_chunk_counts",
    "fold_kernel_calls",
    "ONE_BLOCK_ROW_STATS",
    "TQ_TILE",
]

TQ_TILE = 256  # Q rows per grid cell


_KV_VMEM_BUDGET = 8192 * (192 + 128)  # Tk*(D + D_v) elements of K and V a cell may stage per head
# T=8192 with 192-wide keys and 128-wide values (so T*(D + D_v) ==
# _KV_VMEM_BUDGET) is the largest shape whose Mosaic compilation is verified
# on hardware: the forward at D = D_v = 128 there since the ring tests, all
# three kernels in a TRAINING graph since models/lm trained ZAYA1-8B's block on
# the chip at 2 x 8 query heads on 2 key/value heads x 8,192 x 128 in bfloat16
# (PERF.md, PR 30), and at 2 x 32 heads x 8,192 x 192 / 128 since it trained a
# latent-attention stack there (PERF.md, PR 44). Every admitted (T, D, D_v)
# then has score-buffer and KV footprints <= that shape's in all three
# kernels. 16384
# admitted shapes (e.g. T=16384, D=64) stage [tile, 16384] f32 scores plus
# full KV — past the scoped-VMEM limit on paper and never compile-checked on
# chip, so they are rejected until verified.
_TK_MAX = 8192


def flash_available(T: int, D: int, devices=None, Dv=None) -> bool:
    """Whether the fused fold applies: Q tiles must divide the local length,
    one head's KV block (K at ``D`` channels plus V at ``Dv``; ``None``: ``D``)
    AND a tile's ``[rows, Tk]`` score buffers (whole, or as the visited chunks
    under ``causal``) must fit the kernel's VMEM staging (the fold brings one
    head's whole resident K and V on-chip; past either budget the jnp fold's
    streamed HBM form is the right tool), and the devices must be TPUs (Mosaic
    target)."""
    from flink_ml_tpu.parallel.mesh import is_tpu_backend

    if T % TQ_TILE or T * (D + (D if Dv is None else Dv)) > _KV_VMEM_BUDGET or T > _TK_MAX:
        return False
    return is_tpu_backend(devices if devices is not None else jax.devices())


# Scoped VMEM the three kernels may use. A grid cell stages one head's whole
# resident K and V (double-buffered) beside its f32 score buffers. The
# whole-block kernels (``causal`` off) hold [tile, Tk] of them: at T=4096,
# D=128 that is 16.5 MB in the forward ([256, Tk]) and 20.4 MB in the dkv
# kernel, past Mosaic's default scoped limit of 16 MB whatever batch*heads is
# (the compile fails "allocating on stack" for the kernel's custom call); at
# T=8192 in bfloat16 they compile with the limit at 24 MB and not at 16. The
# walking kernels (``causal`` on more than one chunk) park the visited chunks'
# scores in a scratch the size of the tile's whole row block, [Tk / 1024, 512,
# 1024] f32: at T=8192, D=128 that is
# 16 MB in the forward and twice that in the dq kernel (scores and dP * P),
# beside K and V (2 MB each in bfloat16, twice for the double buffer; K at 192
# channels 3 MB, 4 as Mosaic pads it to 256 lanes) and a
# chunk's [512, 1024] temporaries; the dkv kernel's [1024, 1024] pair is 4 MB
# a temporary whatever T is. Compiled here for the chip, that training graph
# fits a limit of 48 MB and not 40 in bfloat16, 64 and not 48 in float32 (the
# dq kernel is the largest). A v5e core has 128 MiB of VMEM; 96 MiB covers
# every shape ``flash_available`` admits and leaves XLA its own share.
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


# -- the causal fold's key chunks ---------------------------------------------
#
# Under ``causal`` a grid cell of each kernel walks a resident block of more
# than one key chunk and sorts the chunks, from the two position scalars and
# its tile index,
# into *hidden* (the chunk's first key lies after the tile's last query:
# nothing of it is computed), *diagonal* (the mask crosses it: iota, compare and
# select as before) and *full* (its last key is at or before the tile's first
# query: computed without the mask). A masked entry contributes ``-inf`` to the
# row max and 0 to every sum, so skipping it changes the order of a summation
# and nothing else: ``reference_fold`` over the whole block stays the truth.
# Keys run left to right, so the full chunks come first, then the diagonal
# ones, then the hidden: two counts describe a tile.

_LANES = 128
# Tiles of the causal kernels, the best of those run on the chip at both LM
# cells' shapes (PERF.md, PR 31). A walk's cost is per chunk as well as per
# entry (a loop with a traced bound is neither unrolled nor overlapped with the
# next chunk's matmul), so chunks are long; the hidden share falls with the tile
# (44% of the pairs at 512 x 1024 on T 8,192, 47% at 256 x 512) and the time
# falls faster. The dkv pair is square: at 256 keys a pair the kernel ran at a
# third of this speed whatever it skipped. The one-block form's ONE backward
# kernel (PERF.md, PR 48) walks shorter chunks: it parks nothing, so a chunk
# costs it less, and 512 x 512 beat 512 x 1024, 256 x 512, 512 x 256 and 1024
# x 256 at all eight of the LM cells' folds (by 1-5% where the mask is causal,
# by 26% under a 512-key window and 13% under the block-diffusion mask, whose
# bands a 1,024-key chunk covers twice over).
_TQ_CAUSAL = 512  # Q rows per forward, per dq and per one-block backward cell
_KEY_CHUNK = 1024  # keys per chunk of the forward's and the dq kernel's walks
_DKV_CAUSAL = 1024  # Q rows and K rows of a dkv pair
_BWD_KEY_CHUNK = 512  # keys per chunk of the one-block backward's walk


def _tile(T: int, most: int) -> int:
    """The largest of ``most, most / 2, .. 256`` that divides ``T``
    (``flash_available`` guarantees ``T % 256 == 0``), else ``T`` whole."""
    c = most
    while c >= 256:
        if T % c == 0:
            return c
        c //= 2
    return T


def _fold_tiles(Tq: int, Tk: int, causal: bool):
    """``(forward Q rows, dq Q rows, dkv Q rows, dkv K rows, key chunk)``: what a
    cell of each kernel owns, from the lengths alone. The forward and the dq
    kernel walk only where a chunk can be skipped: without ``causal``, or on a
    block that is one chunk, they take the resident block in one piece (chunk
    ``Tk``) at the tiles they always had, and park no scores in a scratch.
    (That is also what keeps ``ring_attention(causal=True)`` running under the
    TPU interpreter on the eight-device CPU mesh, at the 256 keys a shard of
    its tests: there a scratch goes through a host callback on every device,
    and eight of them at their barrier leave the CPU client no thread.)"""
    kc = _tile(Tk, _KEY_CHUNK) if causal else Tk
    rows = (_tile(Tq, _TQ_CAUSAL),) * 2 if kc < Tk else (TQ_TILE, min(_TQ_BWD, Tq))
    pair = (_DKV_CAUSAL, _DKV_CAUSAL) if causal else (_TQ_DKV, _TK_BWD)
    return (*rows, _tile(Tq, pair[0]), _tile(Tk, pair[1]), kc)


def _chunks_upto(x, chunk: int, n_chunks: int):
    """How many of ``n_chunks`` chunks of ``chunk`` keys START below offset
    ``x + chunk`` (``x`` a traced int32 or an int, negative allowed), which is
    also how many END at or below ``x``: ``clip(x, 0, all) // chunk``."""
    if isinstance(x, int):
        return min(max(x, 0), chunk * n_chunks) // chunk
    return jax.lax.div(jnp.clip(x, 0, chunk * n_chunks), jnp.int32(chunk))


def _least(a, b):
    return min(a, b) if isinstance(a, int) and isinstance(b, int) else jnp.minimum(a, b)


def _visible_chunks(q_first, n_rows: int, k_pos0, chunk: int, n_chunks: int, n_valid=None):
    """``(n_full, n_vis)`` for query rows ``q_first .. q_first + n_rows - 1``
    against ``n_chunks`` key chunks from global key ``k_pos0`` under the causal
    mask (and ``n_valid`` when given): chunks ``[0, n_full)`` need no mask,
    ``[n_full, n_vis)`` are crossed by it, the rest hold nothing kept. Works on
    ints (``fold_chunk_counts``) and on the kernels' prefetched scalars."""
    off = q_first - k_pos0
    n_vis = _chunks_upto(off + n_rows - 1 + chunk, chunk, n_chunks)  # first key <= last query
    n_full = _chunks_upto(off + 1, chunk, n_chunks)  # last key <= first query
    if n_valid is not None:
        n_vis = _least(n_vis, _chunks_upto(n_valid - k_pos0 + chunk - 1, chunk, n_chunks))
        n_full = _least(n_full, _chunks_upto(n_valid - k_pos0, chunk, n_chunks))
    return n_full, n_vis  # a chunk that needs no mask is visible: n_full <= n_vis


def _most(a, b):
    return max(a, b) if isinstance(a, int) and isinstance(b, int) else jnp.maximum(a, b)


def _window_chunks(q_first, n_rows: int, k_pos0, chunk: int, n_chunks: int, window: int, n_full, n_vis):
    """The lower edge a sliding ``window`` adds to ``_visible_chunks``' two
    counts (query ``t`` keeps keys ``t - window < j <= t``): ``(n_lo, lo_end,
    n_full)`` with chunks ``[0, n_lo)`` hidden below the window (their last key
    is too old for the tile's first query), ``[n_lo, lo_end)`` crossed by the
    window's edge, ``[lo_end, n_full)`` kept whole and ``[n_full, n_vis)``
    crossed by the diagonal as before (a chunk both edges cross is masked once,
    by both). ``n_lo <= lo_end <= n_full <= n_vis``."""
    off = q_first - k_pos0
    n_lo = _least(_chunks_upto(off - window + 1, chunk, n_chunks), n_vis)  # last key <= first query - window
    # chunks that start at or below the last query's oldest kept key less one: the edge crosses them
    crossed = _chunks_upto(off + n_rows - 1 - window + chunk, chunk, n_chunks)
    lo_end = _least(_most(crossed, n_lo), n_vis)
    return n_lo, lo_end, _least(_most(n_full, lo_end), n_vis)


class BlockDiffusion(NamedTuple):
    """The block-diffusion mask of ``fused_attention`` ("The block-diffusion
    mask" in the module docstring) over ``2 x tokens`` positions: the clean
    sequence first, its noised copy behind it, both in blocks of ``block``
    positions (a power of two that divides ``tokens`` and every query tile)."""
    tokens: int
    block: int


def _bd_keep(q_pos, k_pos, blocks):
    """Which (query, key) entries the block-diffusion mask keeps, from their
    positions in the doubled sequence (int32 arrays that broadcast against each
    other): with ``b`` a position's block in its own half, a clean key is kept
    by a clean query of ``b(key) <= b(query)`` and by a noised query of
    ``b(key) < b(query)``; a noised key by the noised queries of its own block
    and by no clean query (whose block id lies under every noised key's)."""
    tokens, block = blocks
    shift = block.bit_length() - 1
    half = tokens >> shift  # the first block of the noised half
    qb, kb = jnp.right_shift(q_pos, shift), jnp.right_shift(k_pos, shift)
    newest_clean = jnp.where(qb >= half, qb - (half + 1), qb)  # the last clean block a query keeps: under ``half``
    return (kb <= newest_clean) | (kb == qb)  # a clean key up to it, or (a noised key) the query's own block


def _pick(cond, a, b):
    return (a if cond else b) if isinstance(cond, bool) else jnp.where(cond, a, b)


def _bd_chunks(q_first, n_rows: int, chunk: int, n_chunks: int, blocks):
    """``(n_full, n_vis, b_lo, b_hi)`` for query rows ``q_first .. q_first +
    n_rows - 1`` of the doubled sequence (``q_first`` and ``n_rows`` whole
    blocks) against ``n_chunks`` key chunks from key 0 under the
    block-diffusion mask. Chunks ``[0, n_full)`` lie among the clean keys every
    row keeps and need no mask; ``[n_full, n_vis)`` hold a clean key some row
    keeps and not all; ``[b_lo, b_hi)``, past them, hold the rows' own noised
    positions (the band: each noised row keeps its own block there); the rest
    hold nothing kept. A tile of clean rows walks what the causal mask would
    give it; a tile of noised rows the clean chunks before its first block and
    the one or two chunks of its band. Works on ints (``fold_chunk_counts``)
    and on the kernels' scalars."""
    tokens, block = blocks
    q_end = q_first + n_rows
    clean, noised = q_first < tokens, q_end > tokens  # which halves the rows lie in: one, or both
    # the clean keys EVERY row keeps end where the first row's do (a clean row keeps its own block, a noised row
    # the blocks before its own), and those SOME row keeps where the last row's do
    full_end = _pick(clean, _pick(noised, 0, q_first + block), q_first - tokens)
    vis_end = _most(_pick(clean, _least(q_end, tokens), 0), _pick(noised, q_end - tokens - block, 0))
    n_full = _chunks_upto(full_end, chunk, n_chunks)  # last key < full_end
    n_vis = _chunks_upto(vis_end + chunk - 1, chunk, n_chunks)  # first key < vis_end
    b_lo = _most(_chunks_upto(_most(q_first, tokens), chunk, n_chunks), n_vis)
    return n_full, n_vis, b_lo, _most(_chunks_upto(q_end + chunk - 1, chunk, n_chunks), b_lo)


def _mask_chunk(s, q_first, k_first, causal: bool, n_valid=None, window=None, q_axis: int = 0, blocks=None):
    """``s [rows, keys]`` with ``-inf`` where the causal mask (when ``causal``),
    the sliding ``window`` under it (when given: a query keeps the ``window``
    keys ending at itself) or ``n_valid`` (when given; ``causal`` or it is)
    drops the entry; ``q_first``/``k_first`` are the global positions of row 0
    and key 0. ``q_axis=1``: ``s`` is ``[keys, rows]``, the scores transposed.
    ``blocks``: the block-diffusion mask instead (``_bd_keep``), its block ids
    worked out on one column of rows and one row of keys."""
    if blocks is not None:
        q_shape = tuple(n if axis == q_axis else 1 for axis, n in enumerate(s.shape))
        k_shape = tuple(n if axis != q_axis else 1 for axis, n in enumerate(s.shape))
        keep = _bd_keep(q_first + jax.lax.broadcasted_iota(jnp.int32, q_shape, q_axis),
                        k_first + jax.lax.broadcasted_iota(jnp.int32, k_shape, 1 - q_axis), blocks)
        return jnp.where(keep, s, -jnp.inf)
    q_pos = q_first + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis) if causal else None
    k_pos = k_first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    keep = q_pos >= k_pos if causal else k_pos < n_valid
    if causal and n_valid is not None:
        keep &= k_pos < n_valid
    if window is not None:
        keep &= k_pos > q_pos - window
    return jnp.where(keep, s, -jnp.inf)


def _fold_lanes(x, op):
    """``x [rows, n * 128] -> [rows, 128]``: ``op`` over the lane tiles, which
    leaves the one cross-lane reduction of a row to the end of the walk."""
    out = x[:, :_LANES]
    for i in range(_LANES, x.shape[1], _LANES):
        out = op(out, x[:, i:i + _LANES])
    return out


def _key_rows(ref, c, kc: int):
    """Chunk ``c`` of the ``kc``-row chunks of a staged ``[1, Tk, D]`` K or V block."""
    from jax.experimental import pallas as pl

    return ref[0, pl.ds(pl.multiple_of(c * kc, kc), kc), :]


def _walk_chunks(walk, carry, n_full, n_vis, window=None, n_lo=0, lo_end=0, band=None):
    """One walk of a causal cell over its visible key chunks: ``walk(masked)``
    is a ``fori_loop`` body ``(chunk, carry) -> carry``, taken with the mask on
    the chunks an edge crosses and without it on those kept whole. Under the
    block-diffusion mask a second range follows, ``band``: the chunks that hold
    the cell's own noised rows (``_bd_chunks``), masked."""
    if window is not None:  # the window's edge first; "diagonal" there means masked, by both edges
        carry = jax.lax.fori_loop(n_lo, lo_end, walk(True), carry)
        n_lo = lo_end
    carry = jax.lax.fori_loop(n_lo, n_full, walk(False), carry)
    carry = jax.lax.fori_loop(n_full, n_vis, walk(True), carry)
    return carry if band is None else jax.lax.fori_loop(*band, walk(True), carry)


def _park_scores(qt, k_ref, s_scr, q_first, k_pos0, n_valid, scale, kc: int, n_full, n_vis, window=None,
                 n_lo=0, lo_end=0, blocks=None, band=None):
    """The first walk of a causal forward or dq cell: the scores of ``qt
    [rows, D]`` on key chunks ``[0, n_vis)``, masked on ``[n_full, n_vis)``,
    parked in ``s_scr [chunks, rows, kc]``. Under a ``window`` the walk starts
    at chunk ``n_lo`` and ``[n_lo, lo_end)`` are masked too
    (``_window_chunks``); under the block-diffusion mask (``blocks``) the chunks
    of ``band`` follow, masked. Returns their running row max, still 128 lanes
    wide."""

    def walk(on_diagonal):
        def body(c, mx):
            s = jax.lax.dot_general(
                qt, _key_rows(k_ref, c, kc), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [rows, kc]
            if on_diagonal:
                s = _mask_chunk(s, q_first, k_pos0 + c * kc, True, n_valid, window, blocks=blocks)
            s_scr[c] = s
            return jnp.maximum(mx, _fold_lanes(s, jnp.maximum))

        return body

    return _walk_chunks(walk, jnp.full((qt.shape[0], _LANES), -jnp.inf, jnp.float32), n_full, n_vis, window,
                        n_lo, lo_end, band)


def fold_chunk_counts(Tq: int, Tk: int, q_off: int, causal: bool, window=None, blocks=None, one_block: bool = False):
    """``(visited, total)`` chunk pairs of ONE fold of ``Tq`` queries on ``Tk``
    keys, one head, its kernels together at the tiles they use. The ring form
    (``fused_fold``) has three: the forward's and the dq kernel's (query tile,
    key chunk) pairs and the dkv kernel's (key tile, query tile) pairs.
    ``one_block`` (``fused_attention``; ``blocks`` implies it) has two, the
    forward and the ONE backward kernel, whose cells walk (query tile, key
    chunk) pairs as the forward's do, at shorter chunks (``_attention_tiles``):
    no third term. ``q_off = q_pos0 - k_pos0``. A kernel that takes the block
    in one piece visits every pair: all three without ``causal``, every
    kernel but the ring's dkv on a block of one chunk of its own. Under a
    sliding ``window`` (``causal`` with it) a walk also skips the chunks below
    the window's edge; under the block-diffusion mask (``blocks``, of a doubled
    sequence on itself: ``Tq = Tk = 2 x blocks.tokens``, ``q_off`` 0) a tile
    visits what ``_bd_chunks`` gives it."""
    if one_block or blocks is not None:
        tq, kc, tq_bwd, kc_bwd = _attention_tiles(Tk)
        kernels = ((tq, kc, kc < Tk), (tq_bwd, kc_bwd, kc_bwd < Tk))
    else:
        tq_fwd, tq_dq, tq_dkv, tk_dkv, kc = _fold_tiles(Tq, Tk, causal)
        kernels = ((tq_fwd, kc, kc < Tk), (tq_dq, kc, kc < Tk), (tq_dkv, tk_dkv, causal))
    visited = total = 0

    def seen(q_first, rows, keys, n_keys):
        if blocks is not None:
            _, n_vis, b_lo, b_hi = _bd_chunks(q_first, rows, keys, n_keys, blocks)
            return n_vis + b_hi - b_lo
        n_full, n_vis = _visible_chunks(q_first, rows, 0, keys, n_keys)
        if window is None:
            return n_vis
        return n_vis - _window_chunks(q_first, rows, 0, keys, n_keys, window, n_full, n_vis)[0]

    for rows, keys, skips in kernels:
        n_keys = Tk // keys
        total += (Tq // rows) * n_keys
        visited += sum(seen(q_off + j * rows, rows, keys, n_keys) if skips else n_keys for j in range(Tq // rows))
    return visited, total


def _kept(Tq: int, Tk: int, q_pos0, k_pos0, causal, n_valid, window, blocks=None):
    """The references' mask ``[Tq, Tk]``: which (query, key) entries are kept."""
    q_pos = q_pos0 + jnp.arange(Tq)
    k_pos = k_pos0 + jnp.arange(Tk)
    if blocks is not None:  # the block-diffusion mask in the causal one's place: it keeps a query's whole block
        return _bd_keep(q_pos[:, None], k_pos[None, :], blocks)
    keep = jnp.ones((Tq, Tk), bool)
    if causal:
        keep &= q_pos[:, None] >= k_pos[None, :]
    if n_valid is not None:
        keep &= (k_pos < jnp.asarray(n_valid))[None, :]
    if window is not None:
        keep &= k_pos[None, :] > q_pos[:, None] - window
    return keep


def reference_fold(q, kb, vb, m, l, acc, q_pos0, k_pos0, causal, n_valid, scale, window=None, blocks=None):
    """The jnp fold in [B, H, ...] layout (ring.py numerics) — the source of
    truth the kernel is tested against and the backward recomputes through.

    ``q`` [B, H, Tq, D]; ``kb`` [B, H, Tk, D], ``vb`` [B, H, Tk, D_v] (equal
    head counts here; the kernels also take ``[B, H_kv, Tk, .]``); ``m``/``l``
    [B, H, Tq]; ``acc`` [B, H, Tq, D_v]. ``q_pos0``/``k_pos0`` are the global positions of
    query/key 0 (traced scalars); ``n_valid`` masks keys at global positions
    >= it (None = unmasked); ``window`` (with ``causal``) keeps of each
    query's keys the ``window`` that end at it; ``blocks`` (a
    ``BlockDiffusion``, with ``causal``, of a doubled sequence on itself from
    position 0) puts the block-diffusion mask in the causal one's place.
    """
    Tq, Tk = q.shape[2], kb.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kb) * scale
    if causal or n_valid is not None:
        mask = _kept(Tq, Tk, q_pos0, k_pos0, causal, n_valid, window, blocks)
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


def _kernel_name(part: str, window, blocks=None) -> str:
    """A windowed fold's kernels, and those of a fold under the block-diffusion
    mask, carry names of their own, so that a device trace tells a stack's
    windowed layers from its full ones and either from a doubled sequence's."""
    mask = "bd_" if blocks is not None else "win_" if window is not None else ""
    return f"flash_fold_{mask}{part}"


#: The float32 row statistics (``[B x H, T, 1]`` columns or ``[B x H, 1, T]`` rows) among a kernel's operands and
#: results in the one-block form, by the kernel's part of its name: ``lse`` out of the forward and into the one
#: backward kernel (``delta`` is formed in its cells and crosses nothing). The ring form's three kernels carry 4, 9
#: and 4: ``m``, ``l``, their cotangents and the tie terms.
ONE_BLOCK_ROW_STATS = {"fwd": 1, "bwd_dkv": 1}


def fold_kernel_calls(jaxpr) -> list:
    """``[(part, row statistics)]``, one entry a call of a fold kernel
    (``part`` one of ``fwd``, ``bwd_dq``, ``bwd_dkv``; whatever its mask) among
    ``jaxpr``'s equations and those of every jaxpr inside them (a loop's body,
    what a ``checkpoint`` recomputes), with the float32 row statistics among
    the call's operands and results: what a step as traced hands its fold."""
    found = []
    for eqn in jaxpr.eqns:
        name = eqn.params.get("name") if eqn.primitive.name == "pallas_call" else None
        if name is not None and name.startswith("flash_fold_"):
            avals = [v.aval for v in (*eqn.invars, *eqn.outvars)]
            found.append((name.removeprefix("flash_fold_").removeprefix("win_").removeprefix("bd_"),
                          sum(a.dtype == jnp.float32 and a.ndim == 3 and 1 in a.shape[1:] for a in avals)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += fold_kernel_calls(sub)
    return found


def _fold_pallas(q, kb, vb, m, l, acc, q_pos0, k_pos0, causal, n_valid, scale,
                 interpret=False, window=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if window is not None and not (causal and window > 0):
        raise ValueError(f"a sliding window ({window}) lies under the causal mask and holds at least the query")
    B, H, Tq, D = q.shape
    Hkv, Tk, Dv = kb.shape[1], kb.shape[2], vb.shape[3]
    BH = B * H
    kv_of = _kv_block_of(H, Hkv)
    masked = n_valid is not None
    tq, _, _, _, kc = _fold_tiles(Tq, Tk, causal)
    n_chunks = Tk // kc  # 1: the block in one piece

    def kernel(scalars_ref, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
               mo_ref, lo_ref, ao_ref):
        qt = q_ref[0]  # [TQ, D]
        s = jax.lax.dot_general(
            qt, k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [TQ, Tk]
        if causal or masked:
            s = _mask_chunk(s, scalars_ref[0] + pl.program_id(1) * tq, scalars_ref[1], causal,
                            scalars_ref[2] if masked else None, window)
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

    def walking_kernel(scalars_ref, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                       mo_ref, lo_ref, ao_ref, s_scr):
        # the same fold over the key chunks this tile's rows can see: scores
        # of the visited chunks wait in ``s_scr`` for the row max, then feed
        # the exponential and ``p @ v``; a hidden chunk is never touched
        q_first = scalars_ref[0] + pl.program_id(1) * tq
        nv = scalars_ref[2] if masked else None
        n_full, n_vis = _visible_chunks(q_first, tq, scalars_ref[1], kc, n_chunks, nv)
        n_lo = lo_end = 0
        if window is not None:
            n_lo, lo_end, n_full = _window_chunks(q_first, tq, scalars_ref[1], kc, n_chunks, window, n_full, n_vis)
        mx = _park_scores(q_ref[0], k_ref, s_scr, q_first, scalars_ref[1], nv, scale, kc, n_full, n_vis,
                          window, n_lo, lo_end)
        mcol = m_ref[0]  # [TQ, 1]
        new_m = jnp.maximum(mcol, jnp.max(mx, axis=1, keepdims=True))
        safe_m = jnp.where(jnp.isneginf(new_m), 0.0, new_m)
        correction = jnp.where(jnp.isneginf(mcol), 0.0, jnp.exp(mcol - safe_m))
        mo_ref[0] = new_m
        ao_ref[0] = acc_ref[0] * correction

        def accumulate(c, l_lanes):
            # safe_m is finite, so a masked score's exp(-inf) is the 0 the
            # reference selects
            p = jnp.exp(s_scr[c] - safe_m)
            ao_ref[0] += jnp.dot(
                p.astype(v_ref.dtype), _key_rows(v_ref, c, kc), preferred_element_type=jnp.float32
            )
            return l_lanes + _fold_lanes(p, jnp.add)

        l_lanes = jax.lax.fori_loop(
            n_lo, n_vis, accumulate, jnp.zeros((tq, _LANES), jnp.float32)
        )
        lo_ref[0] = l_ref[0] * correction + jnp.sum(l_lanes, axis=1, keepdims=True)

    scalars = jnp.stack(
        [
            jnp.asarray(q_pos0, jnp.int32),
            jnp.asarray(k_pos0, jnp.int32),
            jnp.asarray(0 if n_valid is None else n_valid, jnp.int32),
        ]
    )
    tile2 = pl.BlockSpec(
        (1, tq, 1), lambda i, j, *_: (i, j, 0), memory_space=pltpu.VMEM
    )
    def tile3(width):
        return pl.BlockSpec((1, tq, width), lambda i, j, *_: (i, j, 0), memory_space=pltpu.VMEM)

    def full3(width):
        return pl.BlockSpec((1, Tk, width), lambda i, j, *_: (kv_of(i), 0, 0), memory_space=pltpu.VMEM)

    from flink_ml_tpu.parallel.mesh import vma_of

    vma = vma_of(q)
    mo, lo, ao = pl.pallas_call(
        walking_kernel if n_chunks > 1 else kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, Tq // tq),
            in_specs=[tile3(D), full3(D), full3(Dv), tile2, tile2, tile3(Dv)],
            out_specs=[tile2, tile2, tile3(Dv)],
            scratch_shapes=(
                [pltpu.VMEM((n_chunks, tq, kc), jnp.float32)] if n_chunks > 1 else []
            ),
        ),
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tq, 1), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((BH, Tq, 1), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((BH, Tq, Dv), jnp.float32, vma=vma),
        ],
        interpret=interpret,
        compiler_params=_compiler_params(),
        name=_kernel_name("fwd", window),
    )(
        scalars,
        q.reshape(BH, Tq, D),
        kb.reshape(B * Hkv, Tk, D),
        vb.reshape(B * Hkv, Tk, Dv),
        m.reshape(BH, Tq, 1),
        l.reshape(BH, Tq, 1),
        acc.reshape(BH, Tq, Dv),
    )
    return mo.reshape(B, H, Tq), lo.reshape(B, H, Tq), ao.reshape(B, H, Tq, Dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 11, 12, 13))
def fused_fold(q, kb, vb, m, l, acc, q_pos0, k_pos0, causal, has_n_valid,
               n_valid, scale, interpret=False, window=None):
    """One ring-attention fold, fused. Same contract as ``reference_fold``,
    and ``kb``/``vb`` may carry fewer heads than ``q`` (grouped queries: the
    module docstring) and ``vb`` heads of another width than ``kb``'s (``acc``
    is then as wide as ``vb``'s) (``n_valid`` is a traced scalar consumed only when ``has_n_valid``);
    the primal runs the Pallas forward kernel and gradients run the fused
    backward kernels (``_fold_bwd_pallas``, AD-exact).
    ``causal``/``has_n_valid``/``scale``/``interpret``/``window`` are static;
    ``window`` (an int, with ``causal``) keeps of each query's keys the
    ``window`` that end at the query ("a sliding window" in the module
    docstring), ``None`` traces the kernels as they were without one.
    """
    return _fold_pallas(
        q, kb, vb, m, l, acc, q_pos0, k_pos0, causal,
        n_valid if has_n_valid else None, scale, interpret=interpret, window=window,
    )


def _fused_fold_fwd(q, kb, vb, m, l, acc, q_pos0, k_pos0, causal, has_n_valid,
                    n_valid, scale, interpret=False, window=None):
    out = fused_fold(
        q, kb, vb, m, l, acc, q_pos0, k_pos0, causal, has_n_valid, n_valid,
        scale, interpret, window,
    )
    return out, (q, kb, vb, m, l, acc, q_pos0, k_pos0, n_valid)


def _fused_fold_bwd(causal, has_n_valid, scale, interpret, window, res, g):
    q, kb, vb, m, l, acc, q_pos0, k_pos0, n_valid = res
    dm, dl, dacc = g
    dq, dkb, dvb, dm_in, dl_in, dacc_in = _fold_bwd_pallas(
        q, kb, vb, m, l, acc, q_pos0, k_pos0, causal,
        n_valid if has_n_valid else None, scale, dm, dl, dacc,
        interpret=interpret, window=window,
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

# the whole-block kernels' tiles (``causal`` off, or a block of one chunk); the walk's: _fold_tiles
_TQ_BWD = 64  # Q rows per dq-kernel cell (3 [TQ, Tk] f32 buffers live at once)
_TK_BWD = 256  # K rows per dkv-kernel cell
_TQ_DKV = 2048  # Q rows per dkv accumulation step (third grid dim)


def reference_fold_bwd(q, kb, vb, m, l, acc, q_pos0, k_pos0, causal, n_valid,
                       scale, dm, dl, dacc, window=None):
    """Hand-derived VJP of ``reference_fold`` — AD-equivalent (max ties split
    0.5/0.5 like ``jnp.maximum``; reduce-max ties spread evenly). The jnp
    source of truth the Pallas backward kernels are tested against."""
    Tq, Tk = q.shape[2], kb.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kb) * scale
    if causal or n_valid is not None:
        keep = _kept(Tq, Tk, q_pos0, k_pos0, causal, n_valid, window)
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
                     scale, dm, dl, dacc, interpret=False, window=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from flink_ml_tpu.parallel.mesh import vma_of

    B_, H, Tq, D = q.shape
    Hkv, Tk, Dv = kb.shape[1], kb.shape[2], vb.shape[3]
    BH, BHkv = B_ * H, B_ * Hkv
    group = H // Hkv
    kv_of = _kv_block_of(H, Hkv)
    masked = n_valid is not None
    _, tq_bwd, tq_dkv, tk_bwd, kc = _fold_tiles(Tq, Tk, causal)
    n_q_dkv, n_k_dkv = Tq // tq_dkv, Tk // tk_bwd
    n_chunks = Tk // kc

    def dq_kernel(scalars_ref, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                  dm_ref, dl_ref, dacc_ref,
                  dqo_ref, dmo_ref, dlo_ref, dao_ref, safe_ref, b_ref, dbc_ref):
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [TQ, Tk]
        if causal or masked:
            s = _mask_chunk(s, scalars_ref[0] + pl.program_id(1) * tq_bwd, scalars_ref[1], causal,
                            scalars_ref[2] if masked else None, window)
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

    def dq_walking_kernel(scalars_ref, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                          dm_ref, dl_ref, dacc_ref,
                          dqo_ref, dmo_ref, dlo_ref, dao_ref, safe_ref, b_ref, dbc_ref,
                          s_scr, dpp_scr):
        # ``dq_kernel`` over the key chunks this tile's rows can see, in three
        # walks of the same chunks, because each needs a whole-row quantity of
        # the one before: the scores and their row max; ``dP * P`` with its row
        # sum and the tie count; ``ds`` and ``ds @ K``. Scores and ``dP * P``
        # wait in VMEM between the walks, so no matmul runs twice.
        q_first = scalars_ref[0] + pl.program_id(1) * tq_bwd
        nv = scalars_ref[2] if masked else None
        n_full, n_vis = _visible_chunks(q_first, tq_bwd, scalars_ref[1], kc, n_chunks, nv)
        n_lo = lo_end = 0
        if window is not None:
            n_lo, lo_end, n_full = _window_chunks(q_first, tq_bwd, scalars_ref[1], kc, n_chunks, window, n_full, n_vis)
        mx = _park_scores(q_ref[0], k_ref, s_scr, q_first, scalars_ref[1], nv, scale, kc, n_full, n_vis,
                          window, n_lo, lo_end)
        mcol = m_ref[0]  # [TQ, 1]
        Bcol = jnp.max(mx, axis=1, keepdims=True)
        new_m = jnp.maximum(mcol, Bcol)
        safe = jnp.where(jnp.isneginf(new_m), 0.0, new_m)
        corr = jnp.where(jnp.isneginf(mcol), 0.0, jnp.exp(mcol - safe))
        # a row with nothing kept has no max entry: +inf equals no score
        hit = jnp.where(jnp.isneginf(Bcol), jnp.inf, Bcol)

        dlc = dl_ref[0]  # [TQ, 1]
        dacc_t = dacc_ref[0].astype(v_ref.dtype)

        def products(c, sums):
            sum_dpp, cnt = sums
            s = s_scr[c]
            P = jnp.exp(s - safe)  # safe is finite: exp(-inf) is the 0 the reference selects
            dP = dlc + jax.lax.dot_general(
                dacc_t, _key_rows(v_ref, c, kc), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [TQ, KC]
            dPP = dP * P
            dpp_scr[c] = dPP
            return (sum_dpp + _fold_lanes(dPP, jnp.add),
                    cnt + _fold_lanes((s == hit).astype(jnp.float32), jnp.add))

        zeros = jnp.zeros((tq_bwd, _LANES), jnp.float32)
        sum_dpp, cnt = jax.lax.fori_loop(n_lo, n_vis, products, (zeros, zeros))
        dcorr = dlc * l_ref[0] + jnp.sum(
            dacc_ref[0] * acc_ref[0], axis=1, keepdims=True
        )
        dsafe = -jnp.sum(sum_dpp, axis=1, keepdims=True) - dcorr * corr
        dnew_m = dm_ref[0] + jnp.where(jnp.isneginf(new_m), 0.0, dsafe)
        take_m = jnp.where(mcol > Bcol, 1.0, jnp.where(mcol == Bcol, 0.5, 0.0))
        dB = dnew_m * (1.0 - take_m)
        dbc = dB / jnp.maximum(jnp.sum(cnt, axis=1, keepdims=True), 1.0)

        dqo_ref[0] = jnp.zeros_like(dqo_ref[0])

        def dq_of(c, carry):
            ds = dpp_scr[c] + jnp.where(s_scr[c] == hit, dbc, 0.0)
            dqo_ref[0] += jax.lax.dot_general(
                ds.astype(k_ref.dtype), _key_rows(k_ref, c, kc), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return carry

        jax.lax.fori_loop(n_lo, n_vis, dq_of, 0)
        dqo_ref[0] *= scale
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
        q_first = scalars_ref[0] + jq * tq_dkv
        k_first = scalars_ref[1] + jk * tk_bwd
        nv = scalars_ref[2] if masked else None

        def accumulate(mask):
            """One (k tile, q tile) pair into dk, dv; ``mask`` is whether an
            entry of it can be dropped (else every score is finite)."""
            s_col = jax.lax.dot_general(
                q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [TQ_DKV, TK]
            if mask:
                s_col = _mask_chunk(s_col, q_first, k_first, causal, nv, window)
            P_col = jnp.exp(s_col - safe_ref[0])
            is_max = s_col == b_ref[0]
            if mask or not causal:  # a full pair's scores are finite; the whole-block kernel stays as it was
                P_col = jnp.where(jnp.isneginf(s_col), 0.0, P_col)
                is_max &= ~jnp.isneginf(s_col)
            dacc_t = dacc_ref[0].astype(v_ref.dtype)
            dP_col = dl_ref[0] + jax.lax.dot_general(
                dacc_t, v_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds_col = dP_col * P_col + is_max.astype(jnp.float32) * dbc_ref[0]
            dko_ref[0] += jax.lax.dot_general(
                ds_col.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            dvo_ref[0] += jax.lax.dot_general(
                P_col.astype(v_ref.dtype), dacc_t, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        @pl.when(first)
        def _():
            dko_ref[0] = jnp.zeros_like(dko_ref[0])
            dvo_ref[0] = jnp.zeros_like(dvo_ref[0])

        if causal:
            # of this q tile's key tiles [0, n_full) need no mask, [n_full,
            # n_vis) are crossed by it; a hidden pair adds nothing
            n_full, n_vis = _visible_chunks(q_first, tq_dkv, scalars_ref[1], tk_bwd, n_k_dkv, nv)
            if window is None:
                pl.when(jk < n_full)(lambda: accumulate(False))
                pl.when((jk >= n_full) & (jk < n_vis))(lambda: accumulate(True))
            else:  # below the window nothing; the pairs either edge crosses masked, by both
                n_lo, lo_end, n_full = _window_chunks(q_first, tq_dkv, scalars_ref[1], tk_bwd, n_k_dkv, window,
                                                      n_full, n_vis)
                pl.when((jk >= lo_end) & (jk < n_full))(lambda: accumulate(False))
                pl.when(((jk >= n_lo) & (jk < lo_end)) | ((jk >= n_full) & (jk < n_vis)))(lambda: accumulate(True))
        else:
            accumulate(masked)

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

    def mat(tile, width=D):
        return pl.BlockSpec((1, tile, width), lambda i, j, *_: (i, j, 0), memory_space=pltpu.VMEM)

    def fullk_mat(width):
        return pl.BlockSpec((1, Tk, width), lambda i, j, *_: (kv_of(i), 0, 0), memory_space=pltpu.VMEM)

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, vma=vma)

    q4 = q.reshape(BH, Tq, D)
    k4 = kb.reshape(BHkv, Tk, D)
    v4 = vb.reshape(BHkv, Tk, Dv)
    dacc4 = dacc.reshape(BH, Tq, Dv)
    dl4 = dl.reshape(BH, Tq, 1)
    dq_o, dm_o, dl_o, dacc_o, safe_r, b_r, dbc_r = pl.pallas_call(
        dq_walking_kernel if n_chunks > 1 else dq_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, Tq // tq_bwd),
            in_specs=[
                mat(tq_bwd), fullk_mat(D), fullk_mat(Dv),
                col(tq_bwd), col(tq_bwd), mat(tq_bwd, Dv),
                col(tq_bwd), col(tq_bwd), mat(tq_bwd, Dv),
            ],
            out_specs=[
                mat(tq_bwd), col(tq_bwd), col(tq_bwd), mat(tq_bwd, Dv),
                col(tq_bwd), col(tq_bwd), col(tq_bwd),
            ],
            # the visited chunks' scores and dP * P between the walks
            scratch_shapes=(
                [pltpu.VMEM((n_chunks, tq_bwd, kc), jnp.float32)] * 2 if n_chunks > 1 else []
            ),
        ),
        out_shape=[
            sds((BH, Tq, D)), sds((BH, Tq, 1)), sds((BH, Tq, 1)),
            sds((BH, Tq, Dv)), sds((BH, Tq, 1)), sds((BH, Tq, 1)),
            sds((BH, Tq, 1)),
        ],
        interpret=interpret,
        compiler_params=_compiler_params(),
        name=_kernel_name("bwd_dq", window),
    )(
        scalars, q4, k4, v4,
        m.reshape(BH, Tq, 1), l.reshape(BH, Tq, 1), acc.reshape(BH, Tq, Dv),
        dm.reshape(BH, Tq, 1), dl4, dacc4,
    )

    def kmat(width):
        return pl.BlockSpec((1, tk_bwd, width), lambda i, jk, jq, *_: (i, jk, 0), memory_space=pltpu.VMEM)

    def q_block(i, jk, jq, scalars_ref):
        head = i
        if group > 1:  # the group's query heads one after another
            head, jq = i * group + jq // n_q_dkv, jq % n_q_dkv
        if causal:
            # q tiles before the first that sees this k tile are hidden: they
            # name that first tile's block, so nothing is fetched for them
            first_seen = _chunks_upto(scalars_ref[1] + jk * tk_bwd - scalars_ref[0], tq_dkv, n_q_dkv - 1)
            jq = jnp.maximum(jq, first_seen)
            if window is not None:
                # and those past the last that sees it (its last key's last query) name that last tile's
                last_seen = _chunks_upto(scalars_ref[1] + (jk + 1) * tk_bwd + window - 2 - scalars_ref[0],
                                         tq_dkv, n_q_dkv - 1)
                jq = jnp.minimum(jq, last_seen)
        return (head, jq, 0)

    def qmat(width):
        return pl.BlockSpec((1, tq_dkv, width), q_block, memory_space=pltpu.VMEM)

    qcol = pl.BlockSpec((1, tq_dkv, 1), q_block, memory_space=pltpu.VMEM)
    dk_o, dv_o = pl.pallas_call(
        dkv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BHkv, Tk // tk_bwd, group * n_q_dkv),
            in_specs=[kmat(D), kmat(Dv), qmat(D), qmat(Dv), qcol, qcol, qcol, qcol],
            out_specs=[kmat(D), kmat(Dv)],
        ),
        out_shape=[sds((BHkv, Tk, D)), sds((BHkv, Tk, Dv))],
        interpret=interpret,
        compiler_params=_compiler_params(),
        name=_kernel_name("bwd_dkv", window),
    )(scalars, k4, v4, q4, dacc4, dl4, safe_r, b_r, dbc_r)

    return (
        dq_o.reshape(B_, H, Tq, D),
        dk_o.reshape(B_, Hkv, Tk, D),
        dv_o.reshape(B_, Hkv, Tk, Dv),
        dm_o.reshape(B_, H, Tq),
        dl_o.reshape(B_, H, Tq),
        dacc_o.reshape(B_, H, Tq, Dv),
    )


# ---------------------------------------------------------------------------
# The one-block form ("The one-block form" in the module docstring): the LM's
# fold, a ring of one. The walk's helpers, tiles and kernel names are the ring
# form's above; the contract, the kernels' bodies and the VJP are its own.
# ---------------------------------------------------------------------------


def _col_to_row(col):
    """``[rows, 1] -> [1, rows]``: a row statistic leaves a cell along the
    lanes (``[B x H, 1, T]`` in HBM is dense; a ``[B x H, T, 1]`` column is
    padded 128 times). Mosaic takes the move as a 32-bit tile transpose."""
    return jnp.transpose(jnp.broadcast_to(col, (col.shape[0], _LANES)))[:1]


def _nt_dot(a, b):
    """``a [n, d] , b [m, d] -> a b^T [n, m]`` in float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _attention_tiles(T: int):
    """``(forward rows, forward key chunk, backward rows, backward key chunk)``
    of the LM's fold: the forward's are ``_fold_tiles``' walk (a block of one
    chunk, T up to 1,024, in one piece at 256 rows); the backward walks chunks
    of its own (``_BWD_KEY_CHUNK``) and takes a block of one of them in one
    piece."""
    tq, _, _, _, kc = _fold_tiles(T, T, True)
    return tq, kc, _tile(T, _TQ_CAUSAL), _tile(T, _BWD_KEY_CHUNK)


def _cell_chunks(q_first, n_rows: int, chunk: int, n_chunks: int, window, blocks=None):
    """``(n_lo, lo_end, n_full, n_vis, band)`` of a cell whose rows start at
    ``q_first`` on keys from 0: chunks ``[n_lo, lo_end)`` and ``[n_full,
    n_vis)`` are crossed by the window's edge and the diagonal, ``[lo_end,
    n_full)`` kept whole, the rest hidden (``_visible_chunks``,
    ``_window_chunks``); without a ``window`` nothing lies below it. ``band``
    is None but under the block-diffusion mask, whose counts are its own
    (``_bd_chunks``): the chunks ``(b_lo, b_hi)`` of the rows' own noised
    positions, crossed too."""
    if blocks is not None:
        n_full, n_vis, b_lo, b_hi = _bd_chunks(q_first, n_rows, chunk, n_chunks, blocks)
        return 0, 0, n_full, n_vis, (b_lo, b_hi)
    n_full, n_vis = _visible_chunks(q_first, n_rows, 0, chunk, n_chunks)
    if window is None:
        return 0, 0, n_full, n_vis, None
    return (*_window_chunks(q_first, n_rows, 0, chunk, n_chunks, window, n_full, n_vis), n_vis, None)


def _attention_specs(T: int, tq: int, kv_of):
    """The forward's and the backward's block specs on a ``(B x H, T / tq)``
    grid: a query tile ``rows(width)``, a head's whole K or V (or ``dk``, ``dv``)
    ``keys(width)``, a row statistic's ``[1, tq]`` piece."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def rows(width):
        return pl.BlockSpec((1, tq, width), lambda i, j: (i, j, 0), memory_space=pltpu.VMEM)

    def keys(width):
        return pl.BlockSpec((1, T, width), lambda i, j: (kv_of(i), 0, 0), memory_space=pltpu.VMEM)

    return rows, keys, pl.BlockSpec((1, 1, tq), lambda i, j: (i, 0, j), memory_space=pltpu.VMEM)


def _check_blocks(T: int, blocks, window, tiles) -> None:
    """Refuse a block-diffusion mask the kernels' counts are not written for."""
    if blocks is None:
        return
    tokens, block = blocks
    if window is not None:
        raise ValueError("the block-diffusion mask takes no sliding window")
    if block <= 0 or block & (block - 1) or tokens % block or T != 2 * tokens:
        raise ValueError(f"the block-diffusion mask lies over twice {tokens} tokens in blocks of a power of two that "
                         f"divides them; got {T} positions in blocks of {block}")
    if any(tile % block for tile in tiles):
        raise ValueError(f"blocks of {block} do not divide the fold's query tiles {tiles}")


def _attention_pallas(q, k, v, scale, window, interpret, blocks=None):
    """``(o [B, H, T, D_v] float32, lse [B x H, 1, T])`` of the one-block form."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from flink_ml_tpu.parallel.mesh import vma_of

    if window is not None and window <= 0:
        raise ValueError(f"a sliding window ({window}) holds at least the query")
    B, H, T, D = q.shape
    Hkv, Dv = k.shape[1], v.shape[3]
    BH = B * H
    kv_of = _kv_block_of(H, Hkv)
    tq, kc, tq_bwd, _ = _attention_tiles(T)
    _check_blocks(T, blocks, window, (tq, tq_bwd))
    n_chunks = T // kc

    def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *s_scr):
        q_first = pl.program_id(1) * tq
        if n_chunks == 1:  # the block in one piece
            s = _mask_chunk(_nt_dot(q_ref[0], k_ref[0]) * scale, q_first, 0, True, None, window, blocks=blocks)
            m = jnp.max(s, axis=1, keepdims=True)
            p = jnp.exp(s - m)  # every row keeps itself: m is finite and a masked score's exp(-inf) is 0
            l = jnp.sum(p, axis=1, keepdims=True)
            o_ref[0] = jnp.dot(p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32) / l
        else:  # ``walking_kernel`` with no carried state to read or correct
            n_lo, lo_end, n_full, n_vis, band = _cell_chunks(q_first, tq, kc, n_chunks, window, blocks)
            mx = _park_scores(q_ref[0], k_ref, s_scr[0], q_first, 0, None, scale, kc, n_full, n_vis,
                              window, n_lo, lo_end, blocks, band)
            m = jnp.max(mx, axis=1, keepdims=True)
            o_ref[0] = jnp.zeros_like(o_ref[0])

            def accumulate(c, l_lanes):
                p = jnp.exp(s_scr[0][c] - m)
                o_ref[0] += jnp.dot(p.astype(v_ref.dtype), _key_rows(v_ref, c, kc),
                                    preferred_element_type=jnp.float32)
                return l_lanes + _fold_lanes(p, jnp.add)

            l_lanes = jax.lax.fori_loop(n_lo, n_vis, accumulate, jnp.zeros((tq, _LANES), jnp.float32))
            if band is not None:
                l_lanes = jax.lax.fori_loop(*band, accumulate, l_lanes)
            l = jnp.sum(l_lanes, axis=1, keepdims=True)
            o_ref[0] = o_ref[0] / l
        lse_ref[0] = _col_to_row(m + jnp.log(l))

    rows, keys, stat = _attention_specs(T, tq, kv_of)
    vma = vma_of(q)
    o, lse = pl.pallas_call(
        kernel,
        grid=(BH, T // tq),
        in_specs=[rows(D), keys(D), keys(Dv)],
        out_specs=[rows(Dv), stat],
        scratch_shapes=[pltpu.VMEM((n_chunks, tq, kc), jnp.float32)] if n_chunks > 1 else [],
        out_shape=[jax.ShapeDtypeStruct((BH, T, Dv), jnp.float32, vma=vma),
                   jax.ShapeDtypeStruct((BH, 1, T), jnp.float32, vma=vma)],
        interpret=interpret,
        compiler_params=_compiler_params(),
        name=_kernel_name("fwd", window, blocks),
    )(q.reshape(BH, T, D), k.reshape(B * Hkv, T, D), v.reshape(B * Hkv, T, Dv))
    return o.reshape(B, H, T, Dv), lse


def _tn_dot(a, b):
    """``a [n, d] , b [n, m] -> a^T b [d, m]`` in float32: the left operand transposed."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _attention_bwd_pallas(q, k, v, o, lse, do, scale, window, interpret, blocks=None):
    """``(dq, dk, dv)`` of the one-block form, each in its operand's type, from
    ONE kernel: a query tile walks its visible key chunks once and every chunk's
    ``s``, ``P``, ``dP`` and ``ds`` feed all three gradients."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from flink_ml_tpu.parallel.mesh import vma_of

    B, H, T, D = q.shape
    Hkv, Dv = k.shape[1], v.shape[3]
    BH, BHkv = B * H, B * Hkv
    group = H // Hkv
    kv_of = _kv_block_of(H, Hkv)
    _, _, tq, kc = _attention_tiles(T)
    n_chunks, n_tiles = T // kc, T // tq

    def kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr):
        # grid (B*H, tiles): the cells of one key/value head's query heads follow one another, and the head's dk and
        # dv wait in VMEM, whole and in float32, from the first tile of its first query head to the last of its last.
        # With the row's log-sum-exp and delta in hand a chunk's P and ds are final as they are formed: no score
        # waits in VMEM, and nothing is formed twice. The scores are formed TRANSPOSED, [keys, rows]: the two
        # statistics broadcast down the sublanes (lse as it arrives), P^T and ds^T are the left operands of dv's and
        # dk's matmuls as they stand, and dq's alone has its left operand transposed
        head, tile = pl.program_id(0), pl.program_id(1)
        q_first = tile * tq
        qt, do = q_ref[0], do_ref[0]
        delta = _col_to_row(jnp.sum(do * o_ref[0], axis=1, keepdims=True))  # float32, from the unrounded dO and o
        lse = lse_ref[0]  # [1, rows], as delta
        do_t = do.astype(v_ref.dtype)
        dq_scr[...] = jnp.zeros_like(dq_scr)

        @pl.when((head % group == 0) & (tile == 0))
        def _():
            dk_scr[...] = jnp.zeros_like(dk_scr)
            dv_scr[...] = jnp.zeros_like(dv_scr)

        def chunk(kt, vt, k_first, keys, masked):
            s = _nt_dot(kt, qt) * scale  # [kc, rows]
            if masked:
                s = _mask_chunk(s, q_first, k_first, True, None, window, q_axis=1, blocks=blocks)
            p = jnp.exp(s - lse)  # lse is finite: a masked score's exp(-inf) is exactly 0
            ds = (p * (_nt_dot(vt, do_t) - delta)).astype(kt.dtype)
            dk_scr[keys, :] += jnp.dot(ds, qt, preferred_element_type=jnp.float32)
            dv_scr[keys, :] += jnp.dot(p.astype(vt.dtype), do_t, preferred_element_type=jnp.float32)
            dq_scr[...] += _tn_dot(ds, kt)

        def walk(masked):
            def body(c, carry):
                chunk(_key_rows(k_ref, c, kc), _key_rows(v_ref, c, kc), c * kc,
                      pl.ds(pl.multiple_of(c * kc, kc), kc), masked)
                return carry

            return body

        if n_chunks == 1:  # the block in one piece
            chunk(k_ref[0], v_ref[0], 0, slice(None), True)
        else:
            n_lo, lo_end, n_full, n_vis, band = _cell_chunks(q_first, tq, kc, n_chunks, window, blocks)
            _walk_chunks(walk, 0, n_full, n_vis, window, n_lo, lo_end, band)
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)

        @pl.when((head % group == group - 1) & (tile == n_tiles - 1))
        def _():
            dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
            dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    vma = vma_of(q)
    rows, keys, stat = _attention_specs(T, tq, kv_of)
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(BH, n_tiles),
        in_specs=[rows(D), keys(D), keys(Dv), rows(Dv), rows(Dv), stat],
        out_specs=[rows(D), keys(D), keys(Dv)],
        scratch_shapes=[pltpu.VMEM((tq, D), jnp.float32), pltpu.VMEM((T, D), jnp.float32),
                        pltpu.VMEM((T, Dv), jnp.float32)],
        out_shape=[jax.ShapeDtypeStruct((BH, T, D), q.dtype, vma=vma),
                   jax.ShapeDtypeStruct((BHkv, T, D), k.dtype, vma=vma),
                   jax.ShapeDtypeStruct((BHkv, T, Dv), v.dtype, vma=vma)],
        interpret=interpret,
        compiler_params=_compiler_params(),
        name=_kernel_name("bwd_dkv", window, blocks),
    )(q.reshape(BH, T, D), k.reshape(BHkv, T, D), v.reshape(BHkv, T, Dv), o.reshape(BH, T, Dv),
      do.reshape(BH, T, Dv), lse)
    return dq.reshape(B, H, T, D), dk.reshape(B, Hkv, T, D), dv.reshape(B, Hkv, T, Dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def fused_attention(q, k, v, scale, window=None, interpret=False, blocks=None):
    """Causal softmax attention of a whole sequence on itself, fused: ``q [B,
    H, T, D]`` on ``k [B, H_kv, T, D]`` and ``v [B, H_kv, T, D_v]`` ``-> o [B,
    H, T, D_v]`` in float32 (grouped queries, a value head of its own size and
    a static sliding ``window`` as ``fused_fold`` takes them). The fold of a
    ring of one: no ``m``, ``l``, ``acc``, positions or ``n_valid``; a caller
    that carries state across blocks is the ring and keeps ``fused_fold``.
    ``blocks`` (a ``BlockDiffusion``; no ``window`` with it) puts the
    block-diffusion mask in the causal one's place: the ``T`` positions are a
    clean sequence and its noised copy behind it ("The block-diffusion mask" in
    the module docstring). ``scale``, ``window``, ``interpret`` and ``blocks``
    are static."""
    return _attention_pallas(q, k, v, scale, window, interpret, blocks)[0]


def _fused_attention_fwd(q, k, v, scale, window, interpret, blocks):
    o, lse = _attention_pallas(q, k, v, scale, window, interpret, blocks)
    return o, (q, k, v, o, lse)


def _fused_attention_bwd(scale, window, interpret, blocks, res, do):
    return _attention_bwd_pallas(*res, do, scale, window, interpret, blocks)


fused_attention.defvjp(_fused_attention_fwd, _fused_attention_bwd)
