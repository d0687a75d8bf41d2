"""One-hot matmulized sparse training — the TPU answer to scatter/gather.

Reference: the sparse branches of ``BLAS.java:30-179`` accumulate gradients
with per-nonzero ``axpy`` and read features with per-nonzero indexing. The
literal TPU translations — ``grad.at[idx].add(v)`` and ``coef[idx]`` — both
lower to *serialized* per-element HBM operations inside a training loop
(~7-10 ns/element measured on chip, whether or not the table is small, the
indices are sorted, or hints are given), which caps Criteo-shape sparse SGD
at ~1.5M rows/s on a chip that does 340M rows/s on the dense shape.

TPU-first redesign: SGD re-reads the same cached rows every epoch, so the
sparsity *pattern* is static. That lets every per-element memory operation
be replaced by dense one-hot algebra the MXU/VPU execute at full width:

- **Feature side (gather + scatter → blocked one-hot VPU sums).** The
  coefficient lives *permuted* during training as ``coef_perm [nblk, 128]``
  (128-wide feature blocks, ordered by occupancy class: a power-of-two
  width for a block with few entries, and one class of 64-slot chunks for
  the heavy blocks; blocks of one class sit contiguously, so each per-class
  round slices its coefficient rows, and the chunked class copies one whole
  row per chunk — entries are never gathered). A batch entry with local
  lane ``l`` reads its coefficient as ``sum(onehot(l) * coef_block)`` and
  writes its gradient through the transposed sum — both as f32 VPU
  broadcast-reduces (~0.4-1 ns/entry measured; the equivalent einsum lowers
  to tiny batched matvecs that run ~6x slower). Padding entries carry
  value 0.
- **Row side (the crossing).** The forward dot needs per-entry values
  summed *by row*, and the backward pass needs the per-row loss multiplier
  broadcast *to entries* — an irreducible reindex between feature-grouped
  and row-grouped orders. Both run as two-level one-hot MXU contractions
  over the row id split as ``(hi, lo) = (r // 128, r % 128)``, with the
  value side carried as split-bf16 pairs (``x = hi + lo``, each half its
  own matmul — f32-grade precision, ~2^-16 relative error).
- **Sub-batch gradient accumulation.** Because the crossing cost scales
  with the row-space width, each minibatch is processed as sequential
  sub-batches of ``SUB_ROWS`` rows *with the same coefficient*, summing
  sub-gradients before the single update — bit-for-bit the same SGD step,
  with the crossing width (and its one-hot bytes) shrunk by
  ``batch / SUB_ROWS``. The sub size balances per-entry crossing cost
  (~sqrt of the sub's row space) against padding (fewer rows per sub means
  sparser blocks and more padding up to a class width); 16384 measured best of
  {8192, 16384, 32768} at the Criteo shape.
- **What a window's ids alone decide is made once.** The resident route
  materializes the row one-hots before the training scan
  (``premat_row_onehots``), and the lane ids are unpacked outside the scan
  like them: ``unpack_lane_ids`` (int8 -> int32, one array a class as the
  rounds read it) runs once a step program over all its windows wherever
  the program visits a window more than once and the ids fit the route's
  share of HBM beside the stacks and the one-hots
  (``ops/optimizer.py::SGD._hoists_lane_ids``).

The crossings run two ways: a pure-XLA form (works on any backend;
one-hots are materialized through HBM) and Pallas kernels (TPU only;
one-hots are built tile-by-tile in VMEM and never touch HBM), selected by
``use_pallas``. The design-time timings quoted in this module (ns/entry,
"measured best of", speedup factors) were taken on a retired setup and
another JAX and are not re-measured on the current toolchain; they explain
why the code has this shape, they are not records (ROADMAP S1/S3). What IS
re-observed on jax 0.9.0 / TPU v5e: every kernel here compiles under Mosaic
as written and the fit matches the numpy step on the chip (``chip_smoke.py``),
and the VMEM notes below reproduce to the digit (docs/kernels.md).
"""
from __future__ import annotations

import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor, wait
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from flink_ml_tpu.parallel.mesh import vma_of
from flink_ml_tpu.trace import CAT_INGEST, tracer
from flink_ml_tpu.utils.arrays import next_pow2

__all__ = [
    "OneHotSparseLayout", "OneHotSparsePlan", "onehot_batch_step",
    "block_counts", "validate_indices", "SUB_ROWS", "BLOCK",
    "premat_row_onehots", "premat_bytes", "unpack_lane_ids", "lane_ids_bytes",
]

BLOCK = 128  # feature-block width: the VPU lane count
_BLOCK_SHIFT = 7  # idx >> 7 is the block, idx & 127 the lane, in the indices' own dtype
assert 1 << _BLOCK_SHIFT == BLOCK
SUB_ROWS = 16384  # sub-batch rows per crossing (gradient accumulation grain)
# Slots of one chunk of a heavy feature block, and the entry count from which
# a block is heavy (OneHotSparsePlan). A smaller chunk pads less and makes
# more rows for the rounds: at the Criteo layout the whole step measured
# 18.7 ms at 64 against 19.3 at 32 and 19.6 at 128, from 22.3 with no chunks
# (TPU v5e; PERF.md, PR 29).
CHUNK = 64
# Host threads one layout build spreads its units over, at most (_over_units).
# The fill of 32 Criteo units of 16,384 x 40 entries on a four-chip TPU v5e
# host's 30 cores, median of twenty builds: 0.457 s on 1 worker, 0.291 on 2,
# 0.188 on 4, 0.131 on 8, 0.113 on 16, 0.115 on 30 (PERF.md, PR 52). A unit
# takes 14 ms alone, 31 among 8 and 53 among 16: past 8 the units slow each
# other as fast as they are added, and twice the threads buy 2% of a fit.
UNIT_WORKERS = 8
_ROW_LO = 128  # row-id split minor width

_POOL: Optional[ThreadPoolExecutor] = None
_POOL_LOCK = threading.Lock()


def _unit_pool() -> ThreadPoolExecutor:
    """Process-wide pool for the layout build's units (lazy: a build of one
    unit, or on one core, never makes it). Its threads idle between builds."""
    global _POOL
    with _POOL_LOCK:
        if _POOL is None:
            _POOL = ThreadPoolExecutor(
                max_workers=UNIT_WORKERS, thread_name_prefix="onehot-layout"
            )
        return _POOL


def _over_units(stripe_fn: Callable[[range], object], n_units: int) -> list:
    """``stripe_fn`` over the units ``0 .. n_units - 1`` cut into stripes
    (worker ``w`` takes units ``w, w + workers, ...``), one stripe a worker;
    returns the stripes' results in stripe order. The workers are
    ``min(units, cores the process may run on, UNIT_WORKERS)``: one runs
    in-line on the calling thread, more on the ``onehot-layout`` pool.

    What makes this safe without a lock: a unit reads its own row range of
    the indices and values and the plan's tables, which nothing writes after
    the plan is made, and writes its own ``[n_model, n_flat]`` slices of the
    preallocated stacks (or, in the counting pass, an array of its stripe's
    own), so no two workers touch the same bytes. Every stripe has ended
    when this returns or raises; of several errors the first in stripe order
    is raised."""
    workers = min(n_units, len(os.sched_getaffinity(0)), UNIT_WORKERS)
    stripes = [range(w, n_units, workers) for w in range(workers)]
    if workers <= 1:
        return [stripe_fn(stripe) for stripe in stripes]
    pool = _unit_pool()
    futures = [pool.submit(stripe_fn, stripe) for stripe in stripes]
    wait(futures)
    return [f.result() for f in futures]


def validate_indices(indices: np.ndarray, dim: int) -> None:
    if indices.size and (np.any(indices < 0) or np.any(indices >= dim)):
        bad_lo, bad_hi = indices.min(), indices.max()
        raise ValueError(f"feature index out of range [0, {dim}): [{bad_lo}, {bad_hi}]")


def block_counts(indices: np.ndarray, values: np.ndarray, nblk: int) -> np.ndarray:
    """Per-feature-block nonzero-entry counts for one sub-batch unit
    (``[rows, K]`` padded-CSR slices; value 0 = padding)."""
    blocks = (np.asarray(indices) >> _BLOCK_SHIFT).ravel()
    nz = np.asarray(values).ravel() != 0.0
    if not nz.all():
        blocks[~nz] = nblk  # counted one past the last block, and dropped
    return np.bincount(blocks, minlength=nblk + 1)[:nblk]


def _stable_order(keys: np.ndarray) -> np.ndarray:
    """Stable argsort of unsigned keys by numpy's radix sort, which it has
    for 16-bit keys only (a wider stable sort is a merge sort, ten times
    slower at a unit's size): 32-bit keys take two 16-bit passes, low half
    then high half."""
    if keys.dtype == np.uint16:
        return np.argsort(keys, kind="stable")
    by_low = np.argsort(keys.astype(np.uint16), kind="stable")
    high = (keys >> 16).astype(np.uint16)
    return by_low.take(np.argsort(high.take(by_low), kind="stable"))


class OneHotSparsePlan:
    """The global static class structure one compiled program is keyed on.

    Built from *per-block maximum entry counts over every sub-batch unit the
    plan will ever serve* — the resident path's units, or every
    (shard, window, minibatch, sub) unit of a streamed run. Because the
    class metadata depends only on those maxima, any unit whose counts fit
    the plan can be transposed into stacks later (``fill_unit``) and
    executed by the same program: this is the window-stable layout contract
    that lets the streamed (larger-than-HBM) path run the one-hot kernel
    with ONE compilation serving every window.

    **Widths.** A block whose largest count stays under ``CHUNK`` takes the
    next power of two, and the blocks of one width form one occupancy class
    (widths 1 to ``CHUNK``). A block that reaches ``CHUNK`` entries in some
    unit is *heavy*: it takes ``ceil(count / CHUNK)`` chunks of ``CHUNK``
    slots, one behind the other, and the chunks of all heavy blocks form ONE
    class, the last, in the order of their blocks' chunk counts. Click-log
    ids make such blocks (the popular values of small fields): a power of
    two for each of them padded the Criteo layout by a seventh and split its
    rounds into 15 classes (PERF.md, PR 29). Which side a block falls on is
    read from the counting pass: data with no heavy block has no chunked
    class, and its plan and compiled step are what they were before chunks
    existed. Either way the plan is a function of the multiset of the
    blocks' counts, not of which block holds which, so data sets that differ
    by a renaming of ids share one compiled program.

    **Tensor parallelism** (``n_model > 1``): each occupancy class's block
    count is padded to a multiple of ``n_model`` and its blocks dealt
    round-robin to model shards, so every shard carries the SAME local
    ``class_meta`` (shard_map traces one program for all shards) and owns a
    contiguous local slice per class. ``class_meta``/``n_flat`` then
    describe ONE shard's local layout; the coefficient lives shard-major
    (``[n_model, nblk_local * BLOCK]`` flattened) and the row-crossing dot
    assembles with a psum over the model axis (the gradient stays
    block-local by construction). The shards' heavy blocks hold different
    numbers of chunks: the chunked class is padded to the shard with the
    most, and the rounds pick their shard's chunk-to-block map by its index
    on the model axis.

    ``class_meta``: per power-of-two class ``(n_blocks_local, width,
    flat_offset, block_offset)``; for the chunked class, where there is one,
    ``(n_chunks_local, CHUNK, flat_offset, block_offset, chunks_of_block)``
    with ``chunks_of_block[shard][i]`` the chunk count of the shard's
    ``i``-th heavy block (0 for one that pads the deal). ``perm``/
    ``inv_perm`` map block ids between original and class-major order.
    """

    _FIELDS = (
        "dim", "nblk", "nblk_local", "n_model", "sub_batch", "n_flat",
        "class_meta", "perm", "inv_perm", "width_of_pos",
        "owner_of_pos", "base_of_pos", "local_block_of_pos",
    )
    # fill_unit's tables, derived from the fields above: the sort key of a
    # block, and per key its class width and first shard-local flat slot
    __slots__ = _FIELDS + (
        "key_of_block", "width_of_key", "base_of_key", "shard_first_key",
    )

    def __init__(self, **kw):
        for k in self._FIELDS:
            setattr(self, k, kw[k])
        # A block's sort key is its class-major position, shard-major: the
        # position itself when n_model == 1. Sorted by it, a unit's entries
        # lie shard after shard, each shard's in the order of its flat slots.
        by_shard = np.argsort(self.owner_of_pos, kind="stable")  # key -> position
        key_of_pos = np.empty(self.nblk, np.intp)
        key_of_pos[by_shard] = np.arange(self.nblk)
        # the key range holds one value past the positions: ``nblk`` itself,
        # which fill_unit gives the zero-valued entries
        self.key_of_block = key_of_pos[self.inv_perm].astype(
            np.uint16 if self.nblk < 1 << 16 else np.uint32
        )
        self.width_of_key = self.width_of_pos[by_shard]
        self.base_of_key = self.base_of_pos[by_shard].astype(np.intp)
        self.shard_first_key = np.searchsorted(
            self.owner_of_pos[by_shard], np.arange(self.n_model + 1)
        )

    @property
    def key_bits(self) -> int:
        """Width of fill_unit's sort keys: the narrower of 16 and 32 bits
        that holds ``nblk`` (16 up to dim 2^23 - 128)."""
        return 8 * self.key_of_block.dtype.itemsize

    @property
    def chunks_of_block(self) -> tuple:
        """Per model shard, the chunk counts of its heavy blocks; ``()`` for
        a plan with no chunked class."""
        last = self.class_meta[-1]
        return last[4] if len(last) > 4 else ()

    @property
    def n_chunks(self) -> int:
        """Chunks of heavy blocks, over all model shards (padding left out)."""
        return sum(map(sum, self.chunks_of_block))

    @classmethod
    def from_max_counts(
        cls, max_count: np.ndarray, dim: int, sub_batch: int, n_model: int = 1
    ) -> "OneHotSparsePlan":
        if sub_batch > np.iinfo(np.int16).max:
            # the packed int16 rowid would wrap and silently drop entries
            raise ValueError(
                f"sub_batch {sub_batch} exceeds the packed rowid range "
                f"({np.iinfo(np.int16).max}); use sub_rows <= 32767"
            )
        nblk = -(-dim // BLOCK)
        max_count = np.maximum(np.asarray(max_count, np.int64), 0)
        heavy = max_count >= CHUNK
        # a light block takes the next power of two, an empty one no slot,
        # a heavy one whole chunks
        width = np.where(heavy, -(-max_count // CHUNK) * CHUNK, next_pow2(max_count))
        width[max_count == 0] = 0
        # class-major: the light classes by width (the empty blocks first;
        # they own no range), then the heavy blocks by theirs, so that the
        # plan is a function of the widths' multiset, whichever block holds
        # which: data sets that differ by a renaming of ids share a program
        order = np.argsort(width + heavy, kind="stable")
        perm = order.astype(np.int32)  # class position -> original block id
        inv_perm = np.empty(nblk, np.int32)
        inv_perm[order] = np.arange(nblk, dtype=np.int32)
        width_sorted = width[order]
        n_light = nblk - int(heavy.sum())

        class_meta: List[tuple] = []
        # Per class-major position p: which model shard owns the block, the
        # shard-local flat slot of its first entry, and its shard-local
        # block index. Round-robin within the class keeps every shard's
        # local class slice contiguous AND identically sized (after pad).
        owner_of_pos = np.zeros(nblk, np.int32)
        base_of_pos = np.zeros(nblk, np.int64)
        local_block_of_pos = np.zeros(nblk, np.int64)
        flat_off = 0  # shard-LOCAL flat offset
        block_off = 0  # shard-LOCAL block offset
        widths, first = np.unique(width_sorted[:n_light], return_index=True)
        classes = list(zip(widths, first, np.append(first[1:], n_light)))
        if n_light < nblk:
            classes.append((None, n_light, nblk))  # the chunked class
        for wdt, p0, p1 in classes:
            f_c = int(p1 - p0)
            local_f = -(-f_c // n_model)  # padded: same local count per shard
            rel = np.arange(f_c, dtype=np.int64)
            owner_of_pos[p0:p1] = (rel % n_model).astype(np.int32)
            local_block_of_pos[p0:p1] = block_off + rel // n_model
            if wdt is None:
                # The chunked class: a shard's heavy blocks lie one behind
                # the other, each over its own chunks, and the shards are
                # padded to the one with the most.
                chunks = width_sorted[p0:p1] // CHUNK
                chunks_of_block = []
                for o in range(n_model):
                    mine = chunks[o::n_model]
                    base_of_pos[p0 + o : p1 : n_model] = (
                        flat_off + (np.cumsum(mine) - mine) * CHUNK
                    )
                    chunks_of_block.append(
                        tuple(mine.tolist()) + (0,) * (local_f - len(mine))
                    )
                n_chunks = max(map(sum, chunks_of_block))
                class_meta.append(
                    (n_chunks, CHUNK, flat_off, block_off, tuple(chunks_of_block))
                )
                flat_off += n_chunks * CHUNK
            elif wdt > 0:
                # Empty (zero-width) classes own coefficient blocks but no
                # flat slots and no class_meta round: their coefficients
                # still live on the mesh (round-trip + regularization apply
                # to never-observed features exactly like the scatter path)
                # while gather/scatter rounds never touch them.
                base_of_pos[p0:p1] = flat_off + (rel // n_model) * int(wdt)
                class_meta.append((local_f, int(wdt), flat_off, block_off))
                flat_off += local_f * int(wdt)
            block_off += local_f
        if flat_off == 0:
            raise ValueError("no nonzero entries; nothing to train on")
        return cls(
            dim=int(dim), nblk=nblk, nblk_local=block_off, n_model=int(n_model),
            sub_batch=int(sub_batch), n_flat=flat_off,
            class_meta=tuple(class_meta), perm=perm, inv_perm=inv_perm,
            width_of_pos=width_sorted,
            owner_of_pos=owner_of_pos, base_of_pos=base_of_pos,
            local_block_of_pos=local_block_of_pos,
        )

    @property
    def row_hi(self) -> int:
        """Row-space major width of one sub-batch (minor is ``_ROW_LO``)."""
        return -(-self.sub_batch // _ROW_LO)

    def stack_bytes(self, n_units: int) -> int:
        """Host/HBM bytes of ``n_units`` sub-batch units' stacks across all
        model shards (int8 lane + int16 rowid + f32 value per flat slot)."""
        return 7 * n_units * self.n_model * self.n_flat

    def fill_unit(self, idx_u, val_u, out_lidx, out_rowid, out_lvals) -> bool:
        """Transpose one sub-batch unit ([rows <= sub_batch, K] padded-CSR)
        into its per-model-shard class-major stack slices (preallocated,
        zeroed, shape [n_model, n_flat]). Raises, before it writes anything,
        if any block's entry count exceeds its planned class width — a unit
        outside the plan's counting pass must fail loudly, never corrupt a
        neighbouring block's slots. Returns whether the unit held a zero
        value (padding or an explicit 0.0) and so took the mask.

        A counting placement: the entries' blocks become narrow sort keys
        (``key_of_block``; ``key_bits`` wide), one ``np.bincount`` of the
        keys gives every block's count — hence the overflow check on
        ``nblk`` numbers and each group's start in the sorted order — and a
        stable radix argsort of the keys gives the order, row-major within
        a block. An entry's slot is its block's base plus its rank in the
        group, which is an ``arange`` plus a ``np.repeat`` of per-group
        offsets; lanes, row ids and values follow the order in their stack
        dtypes and are written through one flat index per model shard. The
        mask, taken only where a value is zero, is one more key: zero-valued
        entries get ``nblk``, sort behind every block and are cut off the
        order. No int64 copy of the indices, no compression, no second
        search.

        Stacks are packed for transfer/HBM (the streamed path ships them
        every window): ``lidx`` int8 (lane < 128), ``rowid`` int16 (the
        sub-batch-relative row, < SUB_ROWS = 16384); the program unpacks to
        int32 (hi, lo) = (rowid // 128, rowid % 128) on device. 7 B/slot
        vs the unpacked 16 — below even the padded-CSR 8 B/nnz."""
        idx_u = np.asarray(idx_u)
        vals = np.asarray(val_u).ravel()
        keys = self.key_of_block.take((idx_u >> _BLOCK_SHIFT).ravel())
        nz = vals != 0.0
        masked = not nz.all()
        if masked:
            keys[~nz] = self.nblk  # behind every block in the sorted order
        counts = np.bincount(keys, minlength=self.nblk + 1)
        kept = keys.size - counts[self.nblk]
        counts = counts[: self.nblk]
        if (counts > self.width_of_key).any():
            raise ValueError(
                "sub-batch unit exceeds the plan's per-block occupancy — the "
                "plan was built from a counting pass that did not cover this data"
            )
        order = _stable_order(keys)[:kept]  # the unit's row-major entry numbers
        start = np.zeros(self.nblk + 1, np.intp)  # of each group, sorted order
        np.cumsum(counts, out=start[1:])
        # slot of sorted entry i of group g: base[g] + (i - start[g])
        slot = np.repeat(self.base_of_key - start[:-1], counts)
        slot += np.arange(kept)
        lanes = (idx_u & (BLOCK - 1)).astype(np.int8).ravel().take(order)
        rows_rel = (order // idx_u.shape[1]).astype(np.int16)
        vals = vals.take(order)
        shard_start = start[self.shard_first_key]
        for o in range(self.n_model):
            a, b = shard_start[o], shard_start[o + 1]
            out_lidx[o][slot[a:b]] = lanes[a:b]
            out_rowid[o][slot[a:b]] = rows_rel[a:b]
            out_lvals[o][slot[a:b]] = vals[a:b]
        return masked

    def permute_coef(self, coef: np.ndarray) -> np.ndarray:
        """Original [dim] coefficient -> shard-major class-major padded
        ``[n_model * nblk_local * BLOCK]`` (for n_model == 1 this is the
        plain class-major permutation)."""
        coef = np.asarray(coef)
        c = np.zeros((self.nblk, BLOCK), coef.dtype)
        c.reshape(-1)[: self.dim] = coef
        out = np.zeros((self.n_model, self.nblk_local, BLOCK), coef.dtype)
        pos = np.arange(self.nblk)
        out[self.owner_of_pos[pos], self.local_block_of_pos[pos]] = c[self.perm]
        return out.reshape(-1)

    def unpermute_coef(self, coef_perm: np.ndarray) -> np.ndarray:
        """Shard-major padded coefficient -> original [dim]."""
        c = np.asarray(coef_perm).reshape(self.n_model, self.nblk_local, BLOCK)
        pos = np.arange(self.nblk)
        orig = np.zeros((self.nblk, BLOCK), c.dtype)
        orig[self.perm] = c[self.owner_of_pos[pos], self.local_block_of_pos[pos]]
        return orig.reshape(-1)[: self.dim]

    def program_key(self) -> tuple:
        """The plan identity a compiled program depends on. ``nblk_local``
        is NOT derivable from the other members (zero-width classes add
        coefficient blocks but no class_meta entry), so it must ride along —
        it sets the coef/grad array lengths."""
        return (
            self.dim, self.nblk, self.nblk_local, self.n_model,
            self.sub_batch, self.n_flat, self.class_meta,
        )

    def __repr__(self) -> str:
        return (
            f"OneHotSparsePlan(dim={self.dim}, sub={self.sub_batch}, "
            f"flat={self.n_flat}, n_model={self.n_model}, "
            f"classes={[m[:2] for m in self.class_meta]})"
        )


class OneHotSparseLayout:
    """Static host-built layout for one resident dataset + minibatch schedule:
    an ``OneHotSparsePlan`` plus the filled ``[n_shards, n_windows, n_sub,
    n_flat]`` stacks. Windows are the distinct minibatch slice starts of
    ``offset_schedule`` (contiguous ``local_batch`` rows, tail clamped)."""

    __slots__ = (
        "plan", "dim", "n_shards", "n_windows", "n_sub", "n_flat", "nblk",
        "n_model", "class_meta", "perm", "inv_perm", "lidx", "rowid",
        "lvals", "window_starts", "local_batch", "sub_batch",
    )

    def __init__(self, **kw):
        for k in self.__slots__:
            setattr(self, k, kw[k])

    @classmethod
    def build(
        cls,
        indices: np.ndarray,
        values: np.ndarray,
        dim: int,
        n_shards: int,
        local_batch: int,
        sub_rows: int = SUB_ROWS,
        max_stack_bytes: Optional[int] = None,
        n_model: int = 1,
    ) -> Optional["OneHotSparseLayout"]:
        """Transpose a padded-CSR batch ([n, K] indices/values, value 0 =
        padding) into per-(data shard, model shard, window, sub-batch)
        class-major block layouts (stacks [n_shards, n_model, n_windows,
        n_sub, n_flat]). With ``max_stack_bytes``, returns None instead of
        materializing stacks that would exceed it (the size is known after
        the counting pass, before any stack allocation).

        Float values pack into an f32 stack — float64 inputs are downcast
        (the MXU crossing path carries values as split-bf16 pairs, which
        reconstruct f32-grade precision, not f64; the SGD gate admits only
        f32 fits, but direct callers lose f64 precision here)."""
        from flink_ml_tpu.ops.schedule import offset_schedule

        # Five phases (docs/observability.md, "The fit span tree"); every
        # statement of the build lies in one of them.
        with tracer.phase("train.layout.prepare", CAT_INGEST):
            indices = np.asarray(indices)
            values = np.asarray(values)
            n = indices.shape[0]
            m = -(-n // n_shards)  # local rows per shard (cache pads to this)
            local_batch = min(local_batch, m)
            sub = min(sub_rows, local_batch)
            n_sub = -(-local_batch // sub)

            # Distinct windows, in first-visit order, from the canonical schedule.
            starts, _ = offset_schedule(m, local_batch, max(1, -(-m // local_batch)))
            window_starts = list(dict.fromkeys(int(s) for s in starts))
            n_windows = len(window_starts)

            nblk = -(-dim // BLOCK)
            validate_indices(indices, dim)
            n_units = n_shards * n_windows * n_sub

        # Pass 1 (counting): per-block max entry count over every unit, a
        # stripe of units folded by each worker, the stripes folded here
        # (np.maximum is exact and order-free: the plan cannot change).
        with tracer.phase("train.layout.count", CAT_INGEST, units=n_units):
            bounds = []  # unit -> (r0, r1) row range, (shard, window, sub) order
            for s in range(n_shards):
                lo_s = s * m
                for w0 in window_starts:
                    for b0 in range(0, local_batch, sub):
                        r0 = lo_s + w0 + b0
                        r1 = min(r0 + sub, lo_s + min(w0 + local_batch, m), n)
                        bounds.append((r0, r1))

            def count_stripe(units: range) -> np.ndarray:
                stripe_max = np.zeros(nblk, np.int64)
                for u in units:
                    r0, r1 = bounds[u]
                    np.maximum(
                        stripe_max,
                        block_counts(indices[r0:r1], values[r0:r1], nblk),
                        out=stripe_max,
                    )
                return stripe_max

            max_count = np.maximum.reduce(_over_units(count_stripe, n_units))

        with tracer.phase("train.layout.plan", CAT_INGEST) as phase:
            plan = OneHotSparsePlan.from_max_counts(max_count, dim, sub, n_model)
            # max_sum is the floor of any width rule: n_flat over it is padding
            phase.set_metadata(
                classes=len(plan.class_meta),
                chunked_blocks=sum(map(np.count_nonzero, plan.chunks_of_block)),
                chunks=plan.n_chunks,
                n_flat=plan.n_flat, max_sum=int(max_count.sum()),
            )
            if max_stack_bytes is not None and plan.stack_bytes(n_units) > max_stack_bytes:
                return None

        with tracer.phase("train.layout.alloc", CAT_INGEST, bytes=plan.stack_bytes(n_units)):
            shape = (n_shards, n_model, n_windows, n_sub, plan.n_flat)
            lidx = np.zeros(shape, np.int8)
            rowid = np.zeros(shape, np.int16)
            lvals = np.zeros(shape, np.float32 if values.dtype.kind == "f" else values.dtype)
        with tracer.phase(
            "train.layout.fill", CAT_INGEST, units=n_units, key_bits=plan.key_bits
        ) as phase:
            def fill_stripe(units: range) -> tuple:
                masked = unit_ns = 0
                for u in units:
                    t0 = time.perf_counter_ns()
                    s, wb = divmod(u, n_windows * n_sub)
                    wi, bi = divmod(wb, n_sub)
                    r0, r1 = bounds[u]
                    masked += plan.fill_unit(
                        indices[r0:r1], values[r0:r1],
                        lidx[s, :, wi, bi], rowid[s, :, wi, bi],
                        lvals[s, :, wi, bi],
                    )
                    unit_ns += time.perf_counter_ns() - t0
                return masked, unit_ns

            t0 = time.perf_counter_ns()
            stripes = _over_units(fill_stripe, n_units)
            wall_ns = time.perf_counter_ns() - t0
            # masked: units that held a zero value and took the mask; unit_us
            # over wall_us is the units in flight on average (1.0 in-line)
            phase.set_metadata(
                masked=sum(took for took, _ in stripes), workers=len(stripes),
                unit_us=sum(ns for _, ns in stripes) // 1000, wall_us=wall_ns // 1000,
            )

        return cls(
            plan=plan, dim=int(dim), n_shards=n_shards, n_windows=n_windows,
            n_sub=n_sub, n_flat=plan.n_flat, nblk=nblk, n_model=n_model,
            class_meta=plan.class_meta, perm=plan.perm, inv_perm=plan.inv_perm,
            lidx=lidx, rowid=rowid, lvals=lvals,
            window_starts=window_starts, local_batch=local_batch, sub_batch=sub,
        )

    @property
    def row_hi(self) -> int:
        """Row-space major width of one sub-batch (minor is ``_ROW_LO``)."""
        return -(-self.sub_batch // _ROW_LO)

    @property
    def nblk_local(self) -> int:
        """One model shard's block count (== nblk padded when n_model == 1)."""
        return self.plan.nblk_local

    def padding_ratio(self) -> float:
        nnz = float(np.count_nonzero(self.lvals))
        return float(self.lvals.size) / max(nnz, 1.0)

    def permute_coef(self, coef: np.ndarray) -> np.ndarray:
        return self.plan.permute_coef(coef)

    def unpermute_coef(self, coef_perm: np.ndarray) -> np.ndarray:
        return self.plan.unpermute_coef(coef_perm)

    def __repr__(self) -> str:
        return (
            f"OneHotSparseLayout(dim={self.dim}, shards={self.n_shards}, "
            f"windows={self.n_windows}, sub={self.n_sub}x{self.sub_batch}, "
            f"flat={self.n_flat}, classes={[m[:2] for m in self.class_meta]})"
        )


def _split_bf16(x):
    """f32 -> (hi, lo) bf16 pair with hi + lo == x to ~2^-16 relative."""
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _lane_onehot(ids, width, dtype=jnp.bfloat16):
    """[..., w] int32 -> [..., w, width] one-hot (exact in any dtype)."""
    iota = jax.lax.broadcasted_iota(jnp.int32, ids.shape + (width,), ids.ndim)
    return (ids[..., None] == iota).astype(dtype)


def _block_of_chunk(chunks_of_block, n_chunks: int, model_axis):
    """``[n_chunks]`` int32: the heavy block, counted within the chunked
    class, that each chunk of this model shard belongs to. Static and sorted;
    a chunk that only pads the shard up to the fullest one points at the last
    block, and holds nothing but zero-valued slots. The rounds use it on
    whole 128-lane coefficient rows, a few thousand of them — never on
    entries, which is the per-element memory operation this module exists to
    avoid."""
    ids = np.empty((len(chunks_of_block), n_chunks), np.int32)
    for mine, counts in zip(ids, chunks_of_block):
        mine[:] = len(counts) - 1
        mine[: sum(counts)] = np.repeat(np.arange(len(counts)), counts)
    if model_axis is None:
        return jnp.asarray(ids[0])
    # one traced program for all shards: each looks its own row up
    return jax.lax.dynamic_index_in_dim(
        jnp.asarray(ids), jax.lax.axis_index(model_axis), keepdims=False
    )


def _class_scope(wdt: int, chunked) -> str:
    """The ``jax.named_scope`` of one occupancy class inside a round,
    RELATIVE like ``parallel/moe.py``'s: it nests under whatever the caller
    opened (``lin.gather/light/w4``, ``lin.scatter/chunks``;
    docs/observability.md, "The linear step's scopes")."""
    return "chunks" if chunked else f"light/w{wdt}"


def lane_ids_bytes(n_units: int, class_meta) -> int:
    """HBM bytes of ``unpack_lane_ids``' arrays for ``n_units`` sub-batch
    units in the layout the rounds read, at most: ``wdt`` minor, padded to
    the 128 lanes, a class's rows to the 8 sublanes (a width-2 class takes 64
    times its 4 B a slot; the compiler holds the narrow classes more
    compactly between steps: the whole step program of the one-chip Criteo
    cell compiles to 127 MB of temporaries where this reads 245). An upper
    bound on purpose: it is what ``SGD._hoists_lane_ids`` sets against the
    HBM budget before a program may hold every window's unpacked ids, and
    over-counting costs the hoist in a narrow band, never the fit."""
    tiles = sum(-(-f_c // 8) * 8 * -(-wdt // BLOCK) * BLOCK for f_c, wdt, *_ in class_meta)
    return 4 * n_units * tiles


def unpack_lane_ids(lidx, class_meta):
    """Packed lane ids ``[..., n_sub, n_flat]`` int8 -> one int32 array a
    class of ``class_meta``, ``[..., n_sub, f_c, wdt]``: the class's cut at
    its ``flat_offset``, shaped as ``gather_round`` and ``scatter_round``
    read it. A function of the ids alone, so a step program that visits a
    window more than once calls it before its scan, once over all its
    windows (``ops/optimizer.py::_fused_onehot_program``). The caller opens
    ``lin.unpack``; a class's cut sits under it by the rounds' relative class
    scope (``lin.unpack/light/w4``, ``lin.unpack/chunks``)."""
    ids = lidx.astype(jnp.int32)
    lead = ids.shape[:-1]
    parts = []
    for f_c, wdt, off, _b0, *chunked in class_meta:
        with jax.named_scope(_class_scope(wdt, chunked)):
            parts.append(
                jax.lax.slice_in_dim(ids, off, off + f_c * wdt, axis=ids.ndim - 1)
                .reshape(lead + (f_c, wdt))
            )
    return parts


def gather_round(coef_perm, lane_ids, class_meta, model_axis=None):
    """Per-entry coefficient read, g[e] = coef_perm[block(e)*BLOCK + lane(e)],
    for every sub-batch at once (``lane_ids``: ``unpack_lane_ids``' list, a
    class ``[n_sub, f_c, wdt]`` -> [n_sub, n_flat]).

    Per occupancy class: a 128-lane one-hot times the class's contiguous
    coefficient rows (a static slice — the class-major permutation exists
    precisely so this is never a gather), reduced on the VPU in f32. The
    VPU broadcast-sum form matters: the same contraction as an einsum
    lowers to width-``wdt`` batched matvecs that run ~6x slower (measured),
    and the VPU form is exact f32 — no bf16 split needed. The chunked class
    is one more such class whose rows are chunks: each reads its block's
    coefficient row, a static sorted gather of whole rows.
    """
    parts = []
    c2 = coef_perm.reshape(-1, BLOCK)
    for (f_c, wdt, _off, b0, *chunked), ids in zip(class_meta, lane_ids):
        with jax.named_scope(_class_scope(wdt, chunked)):
            if chunked:  # [heavy blocks, BLOCK] -> [f_c chunks, BLOCK]
                rows = jnp.take(
                    jax.lax.slice_in_dim(c2, b0, b0 + len(chunked[0][0])),
                    _block_of_chunk(chunked[0], f_c, model_axis),
                    axis=0, indices_are_sorted=True, mode="clip",
                )
            else:
                rows = jax.lax.slice_in_dim(c2, b0, b0 + f_c)  # [f_c, BLOCK]
            oh = _lane_onehot(ids, BLOCK, jnp.float32)  # [n_sub, f_c, wdt, BLOCK]
            parts.append(
                jnp.sum(oh * rows[None, :, None, :], axis=3).reshape(ids.shape[0], -1)
            )
    return jnp.concatenate(parts, axis=1)


def scatter_round(u, lane_ids, class_meta, nblk, model_axis=None):
    """Transposed gather_round: per-entry values summed into the permuted
    gradient across every sub-batch (``u`` [n_sub, n_flat], ``lane_ids`` a
    class ``[n_sub, f_c, wdt]`` -> [nblk * BLOCK]) — the same exact-f32 VPU
    broadcast-sum form, reduced
    over the sub and width dims (the gradient accumulation). The chunked
    class's per-chunk row sums are added block by block, a sorted segment
    sum of whole rows."""
    c2 = jnp.zeros((nblk, BLOCK), jnp.float32)
    n_sub = u.shape[0]
    for (f_c, wdt, off, b0, *chunked), ids in zip(class_meta, lane_ids):
        with jax.named_scope(_class_scope(wdt, chunked)):
            vals = jax.lax.slice_in_dim(u, off, off + f_c * wdt, axis=1).reshape(
                n_sub, f_c, wdt
            )
            oh = _lane_onehot(ids, BLOCK, jnp.float32)
            sums = jnp.sum(oh * vals[..., None], axis=(0, 2))  # [f_c, BLOCK]
            if chunked:  # [f_c chunks, BLOCK] -> [heavy blocks, BLOCK]
                sums = jax.ops.segment_sum(
                    sums, _block_of_chunk(chunked[0], f_c, model_axis),
                    num_segments=len(chunked[0][0]),
                    indices_are_sorted=True, mode="promise_in_bounds",
                )
            c2 = jax.lax.dynamic_update_slice(c2, sums, (b0, 0))
    return c2.reshape(-1)


def _row_onehots(rhi, rlo, row_hi, dtype=jnp.bfloat16):
    oh_hi = _lane_onehot(rhi, row_hi, dtype)  # [N, row_hi]
    oh_lo = _lane_onehot(rlo, _ROW_LO, dtype)  # [N, 128]
    return oh_hi, oh_lo


def dot_crossing_xla(q, rhi, rlo, row_hi):
    """Row sums per sub-batch: dot3[s, h, l] = sum of q[s] over entries with
    row (h, l). ``q/rhi/rlo`` [n_sub, n] -> [n_sub, row_hi, 128]."""
    oh_hi, oh_lo = _row_onehots(rhi, rlo, row_hi)
    q_hi, q_lo = _split_bf16(q)
    dims = (((1,), (1,)), ((0,), (0,)))  # contract entries, batch subs
    # The halves MUST ride separate matmuls: summing bf16 rhs terms first
    # would round the low half away and forfeit the split's precision.
    return jax.lax.dot_general(
        oh_hi, oh_lo * q_hi[..., None], dims, preferred_element_type=jnp.float32
    ) + jax.lax.dot_general(
        oh_hi, oh_lo * q_lo[..., None], dims, preferred_element_type=jnp.float32
    )  # [n_sub, row_hi, 128]


def mult_crossing_xla(mult3, rhi, rlo, row_hi):
    """Per-entry row broadcast per sub-batch: u[s, e] = mult3[s, rhi, rlo].
    ``mult3`` [n_sub, row_hi, 128]; ``rhi/rlo`` [n_sub, n] -> [n_sub, n]."""
    oh_hi, oh_lo = _row_onehots(rhi, rlo, row_hi)
    m_hi, m_lo = _split_bf16(mult3)
    dims = (((2,), (1,)), ((0,), (0,)))  # contract row_hi, batch subs
    rowvecs = jax.lax.dot_general(
        oh_hi, m_hi, dims, preferred_element_type=jnp.float32
    ) + jax.lax.dot_general(
        oh_hi, m_lo, dims, preferred_element_type=jnp.float32
    )  # [n_sub, n, 128]
    return jnp.sum(rowvecs * oh_lo.astype(jnp.float32), axis=2)


# ---------------------------------------------------------------------------
# Pallas crossings: identical contraction, one-hots built in VMEM per tile.
# ---------------------------------------------------------------------------

_CROSS_TILE = 8192


def dot_crossing_pallas(q, rhi, rlo, row_hi, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_sub, n = q.shape
    # Below row_hi=64 the cell does NOT get cheaper — Mosaic pads the
    # one-hot's minor dim to the 128-lane tile — and the full-size tile
    # overruns the 16 MB scoped-VMEM limit by ~0.5 MB (measured on chip at
    # row_hi 16/32: 16.4-16.6 MB). Halving the tile restores headroom;
    # row_hi >= 64 compiles at full tile.
    tile = min(_CROSS_TILE if row_hi >= 64 else _CROSS_TILE // 2, n)
    if n % tile:  # pad to a whole number of tiles (q=0 contributes nothing)
        pad = tile - n % tile
        q = jnp.pad(q, ((0, 0), (0, pad)))
        rhi = jnp.pad(rhi, ((0, 0), (0, pad)))
        rlo = jnp.pad(rlo, ((0, 0), (0, pad)))
        n += pad

    def kernel(hi_ref, lo_ref, q_ref, o_ref):
        oh_hi = (
            hi_ref[:][:, None]
            == jax.lax.broadcasted_iota(jnp.int32, (tile, row_hi), 1)
        ).astype(jnp.bfloat16)
        oh_lo = (
            lo_ref[:][:, None]
            == jax.lax.broadcasted_iota(jnp.int32, (tile, _ROW_LO), 1)
        ).astype(jnp.bfloat16)
        # split in-kernel AFTER the [T, 1] reshape: Mosaic only inserts minor
        # dims on 32-bit types, so the reshape must happen in f32
        q2 = q_ref[:][:, None]
        q_hi = q2.astype(jnp.bfloat16)
        q_lo = (q2 - q_hi.astype(jnp.float32)).astype(jnp.bfloat16)
        dims = (((0,), (0,)), ((), ()))
        # separate matmuls per split half (summing bf16 rhs first would
        # round the low half away)
        o_ref[0, 0] = jax.lax.dot_general(
            oh_hi, oh_lo * q_hi, dims, preferred_element_type=jnp.float32
        ) + jax.lax.dot_general(
            oh_hi, oh_lo * q_lo, dims, preferred_element_type=jnp.float32
        )

    # Inputs ride flat 1-D (Mosaic's tiling rules reject (1, tile) blocks);
    # the 2-D grid recovers the sub index through the index map arithmetic.
    ntiles = n // tile
    row = pl.BlockSpec(
        (tile,), lambda i, k: (i * ntiles + k,), memory_space=pltpu.VMEM
    )
    parts = pl.pallas_call(
        kernel,
        grid=(n_sub, ntiles),
        in_specs=[row, row, row],
        out_specs=pl.BlockSpec(
            (1, 1, row_hi, _ROW_LO), lambda i, k: (i, k, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct(
            (n_sub, ntiles, row_hi, _ROW_LO), jnp.float32, vma=vma_of(q)
        ),
        interpret=interpret,
        name="onehot_dot_crossing",
    )(rhi.reshape(-1), rlo.reshape(-1), q.reshape(-1))
    return jnp.sum(parts, axis=1)


def mult_crossing_pallas(mult3, rhi, rlo, row_hi, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_sub, n = rhi.shape
    # Unlike dot_crossing, the full tile fits at every row_hi here (probed
    # on chip at row_hi 16/32/64/128): this cell carries one bf16 one-hot +
    # two f32 [tile, 128] buffers vs the dot cell's three bf16 [tile, 128]
    # products plus the matmul staging that overruns at small row_hi.
    tile = min(_CROSS_TILE, n)
    pad = (tile - n % tile) % tile
    if pad:
        rhi = jnp.pad(rhi, ((0, 0), (0, pad)))
        rlo = jnp.pad(rlo, ((0, 0), (0, pad)))

    def kernel(m_ref, hi_ref, lo_ref, o_ref):
        oh_hi = (
            hi_ref[:][:, None]
            == jax.lax.broadcasted_iota(jnp.int32, (tile, row_hi), 1)
        ).astype(jnp.bfloat16)
        m2 = m_ref[0]
        m_hi = m2.astype(jnp.bfloat16)
        m_lo = (m2 - m_hi.astype(jnp.float32)).astype(jnp.bfloat16)
        rowvecs = jnp.dot(
            oh_hi, m_hi, preferred_element_type=jnp.float32
        ) + jnp.dot(oh_hi, m_lo, preferred_element_type=jnp.float32)
        oh_lo = (
            lo_ref[:][:, None]
            == jax.lax.broadcasted_iota(jnp.int32, (tile, _ROW_LO), 1)
        ).astype(jnp.float32)
        o_ref[:] = jnp.sum(rowvecs * oh_lo, axis=1)

    # flat 1-D entry arrays + 2-D grid (see dot_crossing_pallas)
    ntiles = (n + pad) // tile
    row = pl.BlockSpec(
        (tile,), lambda i, k: (i * ntiles + k,), memory_space=pltpu.VMEM
    )
    out = pl.pallas_call(
        kernel,
        grid=(n_sub, ntiles),
        in_specs=[
            pl.BlockSpec(
                (1, row_hi, _ROW_LO), lambda i, k: (i, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            row,
            row,
        ],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct(
            (n_sub * (n + pad),), jnp.float32, vma=vma_of(rhi)
        ),
        interpret=interpret,
        name="onehot_mult_crossing",
    )(mult3, rhi.reshape(-1), rlo.reshape(-1))
    return out.reshape(n_sub, n + pad)[:, :n]


# ---------------------------------------------------------------------------
# Precomputed one-hots: the same two crossings with the row one-hots
# materialized ONCE (bf16, HBM) instead of rebuilt every minibatch step.
#
# The one-hots depend only on the rowid stacks, which are static across
# epochs — the on-chip stripped-kernel decomposition (docs/benchmarks.md)
# measured the in-kernel one-hot build at ~65% of the dot-crossing's time,
# and streaming prebuilt one-hots into product+matmul-only kernels ran the
# crossings 1.86x faster at the headline unit shape (bit-identical output).
# The catch is storage: (row_hi + 128) * 2 B per entry ~= 73x the 7 B/slot
# packed stacks — so the path is HBM-gated (ops/optimizer.py). The resident
# route materializes the whole run's one-hots once; the streamed route
# never SHIPS one-hots (73x the ingest) — instead each window's one-hots
# are materialized ON DEVICE from the just-landed rowid stacks in the
# prefetch gap, bounding storage at the two prefetch-live windows.
#
# Orientation: the ENTRIES lie on the lanes — ``[..., row_hi, n_pad]`` and
# ``[..., 128, n_pad]`` — like every array around the kernels (q, u, the
# packed stacks: ``[n_sub, n_flat]``). With the entries on the sublanes
# (``[..., n_pad, row_hi]``, the build-form kernels' in-VMEM shape) the mult
# crossing's per-entry result came out one value a sublane and had to be
# laid out again as the lane-major output block: at the Criteo cell's shape
# (4 x 956,753 entries, row_hi 128; chip runs, PR 34) that kernel took 6.36
# ms whole, 5.98 without the lane reduction, and 2.64 with neither reduction
# nor relayout — the relayout held 3.3 ms, the reduction 0.4. Here the
# reduction runs over the 128 row-lows on the SUBLANES (vreg adds and one
# fold) and its result is already the ``[1, tile]`` block the output takes;
# the dot crossing's q broadcasts down the sublanes and its contraction over
# entries is over the last axis of both operands (the ``q k^T`` form). Both
# then stream their 1.96 GB in 2.68 ms (89% of the HBM rate), at any tile
# from 4,096 to 16,384 and whether or not the cell is cut into chunks.
#
# Tile: at row_hi < 64 the old ``[tile, row_hi]`` block padded row_hi to 128
# lanes and the full tile overran scoped VMEM, hence the halving. The
# ``[row_hi, tile]`` block pads row_hi to the 16-sublane bf16 tile only and
# the full tile compiles at every row_hi (4, 16, 64, 128 compiled for v5e).
# The halving stays all the same: ``premat_bytes``' padding — what the
# optimizer's HBM gate budgets — follows this tile. What it costs: at row_hi
# 16 and the cell's entry count the mult crossing took 1.75 ms at the half
# tile and 1.57 at the full one (chip runs, PR 34); at row_hi 128, where the
# tile is full, 4,096 and 8,192 measured alike.
# ---------------------------------------------------------------------------


def _premat_tile(n: int, row_hi: int) -> int:
    """One tile policy for BOTH premat kernels (the storage pad must divide
    evenly for each)."""
    return min(_CROSS_TILE if row_hi >= 64 else _CROSS_TILE // 2, max(n, 1))


def _premat_pad(n: int, row_hi: int) -> int:
    t = _premat_tile(n, row_hi)
    return -(-n // t) * t


def premat_bytes(n_units: int, n_flat: int, row_hi: int) -> int:
    """HBM bytes of the materialized bf16 row one-hots for ``n_units``
    sub-batch units of ``n_flat`` entries (the ~73x-the-stacks figure the
    optimizer's premat gate budgets against)."""
    return 2 * n_units * _premat_pad(n_flat, row_hi) * (row_hi + _ROW_LO)


def _sublane_onehot(ids, width, dtype=jnp.bfloat16):
    """[..., n] int32 -> [..., width, n] one-hot: ``_lane_onehot`` with the
    ids' axis left minor."""
    shape = ids.shape[:-1] + (width, ids.shape[-1])
    iota = jax.lax.broadcasted_iota(jnp.int32, shape, ids.ndim - 1)
    return (ids[..., None, :] == iota).astype(dtype)


def premat_row_onehots(rowid, row_hi: int):
    """Packed rowid stacks ``[..., n_flat]`` int16 -> materialized bf16 row
    one-hots ``(oh_hi [..., row_hi, n_pad], oh_lo [..., 128, n_pad])``, the
    entry axis (minor: entries on the lanes) padded to the premat crossing
    tile with all-zero oh columns (padding contributes nothing to the dot
    crossing even if the caller's padded q slots are garbage; the mult
    crossing's padded outputs are sliced off). Built once per layout,
    outside the training scan."""
    n = rowid.shape[-1]
    pad = _premat_pad(n, row_hi) - n
    rid = rowid.astype(jnp.int32)
    oh_hi = _sublane_onehot(rid // _ROW_LO, row_hi)
    oh_lo = _sublane_onehot(rid % _ROW_LO, _ROW_LO)
    if pad:
        width = [(0, 0)] * rowid.ndim + [(0, pad)]
        oh_hi = jnp.pad(oh_hi, width)
        oh_lo = jnp.pad(oh_lo, width)
    return oh_hi, oh_lo


def _premat_window(oh_hi, oh_lo, wi):
    """Select window ``wi`` from (possibly windowed) one-hot stacks. XLA
    form only — this materializes the window slice, which is fine on the
    CPU/test backends the XLA form serves; the Pallas form indexes the
    window inside the BlockSpec instead (no copy)."""
    if oh_hi.ndim == 4:
        oh_hi = jax.lax.dynamic_index_in_dim(oh_hi, wi, 0, keepdims=False)
        oh_lo = jax.lax.dynamic_index_in_dim(oh_lo, wi, 0, keepdims=False)
    return oh_hi, oh_lo


def _pad_entries(q, n_pad):
    """Zero q on the one-hots' padded slots: contributes nothing."""
    if q.shape[1] < n_pad:
        q = jnp.pad(q, ((0, 0), (0, n_pad - q.shape[1])))
    return q


def dot_crossing_premat_xla(q, oh_hi, oh_lo, wi=0):
    """``dot_crossing_xla`` with the one-hots supplied instead of built.
    ``q`` [n_sub, n] (n <= the one-hots' padded entry axis)."""
    oh_hi, oh_lo = _premat_window(oh_hi, oh_lo, wi)
    q_hi, q_lo = _split_bf16(_pad_entries(q, oh_hi.shape[2]))
    dims = (((2,), (2,)), ((0,), (0,)))  # contract entries, batch subs
    return jax.lax.dot_general(
        oh_hi, oh_lo * q_hi[:, None], dims, preferred_element_type=jnp.float32
    ) + jax.lax.dot_general(
        oh_hi, oh_lo * q_lo[:, None], dims, preferred_element_type=jnp.float32
    )


def mult_crossing_premat_xla(mult3, oh_hi, oh_lo, wi=0):
    """``mult_crossing_xla`` with the one-hots supplied (returns the padded
    entry axis; the caller slices to its n)."""
    oh_hi, oh_lo = _premat_window(oh_hi, oh_lo, wi)
    m_hi, m_lo = _split_bf16(mult3)
    dims = (((1,), (1,)), ((0,), (0,)))  # contract row_hi, batch subs
    rowvecs = jax.lax.dot_general(
        m_hi, oh_hi, dims, preferred_element_type=jnp.float32
    ) + jax.lax.dot_general(
        m_lo, oh_hi, dims, preferred_element_type=jnp.float32
    )  # [n_sub, 128, n_pad]
    return jnp.sum(rowvecs * oh_lo.astype(jnp.float32), axis=1)


def _premat_grid(oh_hi, oh_lo):
    """The premat kernels' common frame: windowed stacks
    ``[n_windows, n_sub, w, n_pad]``, the grid ``(n_sub, ntiles)`` and the
    BlockSpecs of a ``[w, tile]`` one-hot tile (window ``wi_ref[0]``, chosen
    in the index map) and of a ``[1, tile]`` tile of a per-entry
    ``[n_sub, 1, n_pad]`` array."""
    from jax.experimental import pallas as pl

    if oh_hi.ndim == 3:
        oh_hi, oh_lo = oh_hi[None], oh_lo[None]
    _, n_sub, row_hi, n_pad = oh_hi.shape
    tile = _premat_tile(n_pad, row_hi)
    oh_spec = lambda w: pl.BlockSpec(
        (1, 1, w, tile), lambda i, k, wi_ref: (wi_ref[0], i, 0, k)
    )
    entry_spec = pl.BlockSpec((1, 1, tile), lambda i, k, wi_ref: (i, 0, k))
    return oh_hi, oh_lo, (n_sub, n_pad // tile), oh_spec, entry_spec


def dot_crossing_premat_pallas(q, oh_hi, oh_lo, wi=0, interpret: bool = False):
    """``dot_crossing_pallas`` minus the in-kernel one-hot build: tiles of
    the materialized one-hots stream from HBM into product+matmul-only
    cells. Same contraction, same split-bf16 halves; ``q`` enters as a
    ``[1, tile]`` row and broadcasts down the 128 row-lows on the sublanes.

    ``oh_hi/oh_lo`` may carry a leading window axis
    (``[n_windows, n_sub, w, n_pad]``); ``wi`` (traced scalar ok) selects
    the window *inside the BlockSpec index map* via scalar prefetch, so the
    kernel DMAs tiles straight out of the full stack — a
    ``dynamic_index_in_dim`` outside would materialize a multi-GB window
    copy every minibatch step (measured: it costs more than the build-form
    kernels save)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    oh_hi, oh_lo, grid, oh_spec, entry_spec = _premat_grid(oh_hi, oh_lo)
    n_sub, row_hi, n_pad = oh_hi.shape[1:]

    def kernel(wi_ref, hi_ref, lo_ref, q_ref, o_ref):
        del wi_ref
        oh_hi_t = hi_ref[0, 0]  # [row_hi, tile] bf16
        oh_lo_t = lo_ref[0, 0].astype(jnp.float32)  # [128, tile]
        q2 = q_ref[0]  # [1, tile] f32
        q_hi = q2.astype(jnp.bfloat16).astype(jnp.float32)
        # a 0/1 one-hot times a bf16 value: exact in f32, exact back in bf16
        p_hi = (oh_lo_t * q_hi).astype(jnp.bfloat16)
        p_lo = (oh_lo_t * (q2 - q_hi)).astype(jnp.bfloat16)
        dims = (((1,), (1,)), ((), ()))  # contract entries: the q k^T form
        # separate matmuls per split half (summing bf16 rhs first would
        # round the low half away)
        o_ref[0, 0] = jax.lax.dot_general(
            oh_hi_t, p_hi, dims, preferred_element_type=jnp.float32
        ) + jax.lax.dot_general(
            oh_hi_t, p_lo, dims, preferred_element_type=jnp.float32
        )

    parts = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[oh_spec(row_hi), oh_spec(_ROW_LO), entry_spec],
            out_specs=pl.BlockSpec(
                (1, 1, row_hi, _ROW_LO), lambda i, k, wi_ref: (i, k, 0, 0)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct(
            grid + (row_hi, _ROW_LO), jnp.float32, vma=vma_of(q)
        ),
        interpret=interpret,
        name="onehot_dot_crossing_premat",
    )(
        jnp.asarray(wi, jnp.int32).reshape(1), oh_hi, oh_lo,
        _pad_entries(q, n_pad).reshape(n_sub, 1, n_pad),
    )
    return jnp.sum(parts, axis=1)


def mult_crossing_premat_pallas(mult3, oh_hi, oh_lo, wi=0, interpret: bool = False):
    """``mult_crossing_pallas`` minus the in-kernel build (returns the padded
    entry axis; the caller slices to its n). Window selection as in
    ``dot_crossing_premat_pallas``. The multiplier enters transposed
    (``[128, row_hi]``, 64 KB a sub-batch), so a cell is
    ``m^T @ oh_hi -> [128, tile]``, masked by ``oh_lo`` and summed over the
    sublanes into the ``[1, tile]`` output row."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    oh_hi, oh_lo, grid, oh_spec, entry_spec = _premat_grid(oh_hi, oh_lo)
    n_sub, row_hi, n_pad = oh_hi.shape[1:]

    def kernel(wi_ref, m_ref, hi_ref, lo_ref, o_ref):
        del wi_ref
        oh_hi_t = hi_ref[0, 0]  # [row_hi, tile] bf16
        m2 = m_ref[0]  # [128, row_hi] f32
        m_hi = m2.astype(jnp.bfloat16)
        m_lo = (m2 - m_hi.astype(jnp.float32)).astype(jnp.bfloat16)
        rowvecs = jnp.dot(
            m_hi, oh_hi_t, preferred_element_type=jnp.float32
        ) + jnp.dot(m_lo, oh_hi_t, preferred_element_type=jnp.float32)
        o_ref[0] = jnp.sum(
            rowvecs * lo_ref[0, 0].astype(jnp.float32), axis=0, keepdims=True
        )

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (1, _ROW_LO, row_hi), lambda i, k, wi_ref: (i, 0, 0)
                ),
                oh_spec(row_hi),
                oh_spec(_ROW_LO),
            ],
            out_specs=entry_spec,
        ),
        out_shape=jax.ShapeDtypeStruct(
            (n_sub, 1, n_pad), jnp.float32, vma=vma_of(mult3)
        ),
        interpret=interpret,
        name="onehot_mult_crossing_premat",
    )(jnp.asarray(wi, jnp.int32).reshape(1), jnp.swapaxes(mult3, 1, 2), oh_hi, oh_lo)
    return out.reshape(n_sub, n_pad)


def onehot_batch_step(
    coef_perm,
    lane_ids_w,
    rowid_w,
    lvals_w,
    yb,
    wb,
    loss_func,
    class_meta,
    nblk: int,
    sub_batch: int,
    row_hi: int,
    use_pallas: bool,
    model_axis=None,
    premat=None,
):
    """One full minibatch: per-sub-batch forward + crossing + backward,
    gradients accumulated, returning ``(grad_perm, loss_sum, weight_sum)``
    with exactly the scatter path's batch semantics.

    ``lane_ids_w``: this window's lane ids as ``unpack_lane_ids`` makes them
    from the packed int8 ``lidx`` stack, one int32 ``[n_sub, f_c, wdt]`` a
    class (the caller unpacks: once a step program where it can, see there).
    ``rowid_w/lvals_w``: this window's ``[n_sub, n_flat]`` packed stack
    slices (this model shard's, under TP; the int16 rowid is unpacked to
    int32 here, and only without the premat one-hots; the 7 B/slot packed
    form is what rides the host->device link). ``yb/wb``:
    the window's label/weight rows ``[local_batch]`` (wb already carries
    the mask and tail gating — padded rows weigh 0, so their entries
    contribute nothing, and padded entries carry value 0 on top). ``nblk``
    is the model shard's LOCAL block count; ``model_axis`` names the mesh
    axis the partial row dots assemble over (each shard's entries cover
    only its feature blocks — one psum completes the margin, after which
    the loss multiplier is replicated across the axis and the gradient is
    block-local).

    ``premat``: the run's materialized row one-hots plus this minibatch's
    window index, ``(oh_hi, oh_lo, wi)`` (``premat_row_onehots``; stacks
    may be windowed ``[n_windows, n_sub, ., n_pad]``) — when given, the
    crossings run the product+matmul-only premat kernels, selecting the
    window via scalar-prefetch (Pallas) or a dynamic slice (XLA/test
    form), and ``rowid_w`` is never unpacked (the resident fast path; see
    the premat section above)."""
    n_sub, n_flat = lvals_w.shape
    # The step names its parts (``lin.*``; docs/observability.md, "The linear
    # step's scopes"): trace-time metadata on each instruction's op_name.
    if premat is None:
        dot_cross = dot_crossing_pallas if use_pallas else dot_crossing_xla
        mult_cross = mult_crossing_pallas if use_pallas else mult_crossing_xla
        with jax.named_scope("lin.unpack"):
            rid = rowid_w.astype(jnp.int32)
            rhi_w = rid // _ROW_LO
            rlo_w = rid % _ROW_LO
    # Every stage processes ALL sub-batches in one invocation (the sub axis
    # is just a leading batch dim) — per-invocation floors, not per-entry
    # work, dominated the per-sub form (measured).
    with jax.named_scope("lin.gather"):
        g = gather_round(coef_perm, lane_ids_w, class_meta, model_axis)  # [n_sub, n_flat]
        q = lvals_w * g
    with jax.named_scope("lin.cross_dot"):
        if premat is not None:
            oh_hi_w, oh_lo_w, wi = premat
            dot3 = (
                dot_crossing_premat_pallas(q, oh_hi_w, oh_lo_w, wi)
                if use_pallas
                else dot_crossing_premat_xla(q, oh_hi_w, oh_lo_w, wi)
            )
        else:
            dot3 = dot_cross(q, rhi_w, rlo_w, row_hi)  # [n_sub, row_hi, 128]
        if model_axis is not None:
            dot3 = jax.lax.psum(dot3, model_axis)
        dot = dot3.reshape(n_sub, row_hi * _ROW_LO)[:, :sub_batch].reshape(-1)
    with jax.named_scope("lin.loss"):
        loss_sum, mult = loss_func.loss_and_mult(dot, yb, wb)
        mult3 = jnp.pad(
            mult.reshape(n_sub, sub_batch),
            ((0, 0), (0, row_hi * _ROW_LO - sub_batch)),
        ).reshape(n_sub, row_hi, _ROW_LO)
    with jax.named_scope("lin.cross_mult"):
        if premat is not None:
            back = (
                mult_crossing_premat_pallas(mult3, oh_hi_w, oh_lo_w, wi)
                if use_pallas
                else mult_crossing_premat_xla(mult3, oh_hi_w, oh_lo_w, wi)
            )[:, :n_flat]
        else:
            back = mult_cross(mult3, rhi_w, rlo_w, row_hi)
    with jax.named_scope("lin.scatter"):
        u = lvals_w * back
        grad = scatter_round(u, lane_ids_w, class_meta, nblk, model_axis)
    with jax.named_scope("lin.loss"):  # the weight sum the mean loss divides by
        weight_sum = jnp.sum(wb)
    return grad, loss_sum, weight_sum
