"""Per-partition training-data caches.

Reference: ``flink-ml-iteration/.../datacache/nonkeyed/`` — ``DataCacheWriter.java:37``
(MemorySegment pool spilling to file segments), ``DataCacheReader``,
``DataCacheSnapshot.java:52`` and ``ListStateWithCache.java:43``, the drop-in ListState
used by SGD/KMeans to cache each subtask's slice of the training data across epochs.

TPU-native: two tiers.

``DeviceDataCache`` — the hot tier. The dataset is placed **once** on the mesh, sharded
over the ``data`` axis, and lives in HBM across all epochs. The reference re-reads its
cache every epoch through a serializer; here epoch N+1 reuses the same device buffers —
zero host↔device traffic after load. Per-step minibatch selection happens *inside* the
jit'd step (wraparound gather on the local shard), mirroring the reference's per-subtask
batch-offset cycling (SGD.java:246-285).

``HostDataCache`` — the capacity tier for datasets larger than HBM: appended columnar
chunks in host RAM with optional disk spill (npy memmap), iterated as device-sized
minibatches with one-batch prefetch (jax async dispatch gives the overlap).
Snapshot/restore mirror ``DataCacheSnapshot.writeTo:95/recover:164``.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Iterator, List, Optional

import jax
import numpy as np

from flink_ml_tpu.faults import faults
from flink_ml_tpu.metrics import MLMetrics, metrics
from flink_ml_tpu.parallel.mesh import MeshContext, get_mesh_context
from flink_ml_tpu.trace import CAT_INGEST, tracer

__all__ = ["DeviceDataCache", "HostDataCache", "create_capacity_cache"]


def create_capacity_cache(memory_budget_bytes=None, spill_dir=None):
    """Capacity-tier cache factory honoring the runtime config tier.

    Returns the C++-backed ``NativeDataCache`` when
    ``native.datacache.enabled`` is set and the toolchain builds, else the
    pure-Python ``HostDataCache`` (identical contract; snapshots are
    interchangeable on disk).
    """
    from flink_ml_tpu.config import Options, config

    if config.get(Options.NATIVE_DATACACHE_ENABLED):
        from flink_ml_tpu.native import native_available

        if native_available():
            from flink_ml_tpu.native.cache import NativeDataCache

            return NativeDataCache(memory_budget_bytes, spill_dir)
    return HostDataCache(memory_budget_bytes, spill_dir)


def _gather_rows(chunk_rows, chunk_at, start: int, stop: int) -> Dict[str, np.ndarray]:
    """Concatenate rows [start, stop) out of an append-ordered chunk log.

    Shared by the Python and native cache tiers; ``chunk_at(i)`` materializes
    (or memory-maps) chunk ``i``'s columns.
    """
    total = sum(chunk_rows)
    if not 0 <= start <= stop <= total:
        raise IndexError(f"rows [{start}, {stop}) out of range [0, {total})")
    parts: List[Dict[str, np.ndarray]] = []
    pos = 0
    for i, n in enumerate(chunk_rows):
        if pos >= stop:
            break
        end = pos + n
        if end > start:
            a, b = max(start - pos, 0), min(stop - pos, n)
            chunk = chunk_at(i)
            parts.append({k: np.asarray(v[a:b]) for k, v in chunk.items()})
        pos = end
    if not parts:  # empty range: zero-row arrays with the right dtypes/shapes
        if not chunk_rows:
            return {}
        proto = chunk_at(0)
        return {k: np.asarray(v[:0]) for k, v in proto.items()}
    if len(parts) == 1:
        # Copy so the caller never holds a live (or read-only) view into cache
        # internals — multi-chunk ranges copy via concatenate anyway.
        return {k: np.array(v) for k, v in parts[0].items()}
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


class DeviceDataCache:
    """Columnar dataset resident in HBM, sharded over the mesh's data axis.

    ``columns`` maps name → host array of shape [n, ...]. All columns are padded to a
    common multiple of the data-axis size; ``n_valid`` is the true row count and
    ``padding_mask`` (float, 1.0 valid / 0.0 pad) lets weighted computations ignore
    padding — the analogue of the reference's per-subtask record counts.
    """

    def __init__(
        self,
        columns: Dict[str, np.ndarray],
        ctx: Optional[MeshContext] = None,
        column_specs: Optional[Dict[str, tuple]] = None,
    ):
        """``column_specs`` optionally maps a column name to a PartitionSpec
        tuple (e.g. ``("data", "model")``) so wide columns land on the mesh in
        their training layout at ingest — dense tensor parallelism shards the
        feature matrix over both axes this way and never holds a row-only
        duplicate in HBM. Trailing dims named by a mesh axis are zero-padded
        to that axis size."""
        self.ctx = ctx or get_mesh_context()
        with tracer.phase("train.cache_put", CAT_INGEST, columns=len(columns)) as phase:
            self._put(columns, column_specs or {})
            # what the host handed to device_put, padding and mask included; the
            # phase times the host's part of the upload, not the transfer's end
            nbytes = sum(a.nbytes for a in self.arrays.values())
            phase.set_metadata(bytes=nbytes)
        metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_H2D_BYTES, nbytes)

    def _put(self, columns: Dict[str, np.ndarray], column_specs: Dict[str, tuple]) -> None:
        lengths = {np.asarray(c).shape[0] for c in columns.values()}
        if len(lengths) != 1:
            raise ValueError(f"inconsistent column lengths {lengths}")
        (n,) = lengths
        self.n_valid = n
        self.arrays: Dict[str, jax.Array] = {}
        # Host references are kept for the sparse columns only — zero-copy
        # for ndarray inputs (the caller's arrays would stay alive anyway):
        # host-side sparse layout construction (bucketing the static sparsity
        # pattern once per dataset, rebuilt per batch size in sweeps) reads
        # them back without a device->host round trip. Dense columns are not
        # retained — nothing reads them back, and pinning e.g. a 250k x 256
        # feature matrix would waste a quarter GB of host RAM.
        self.host_columns: Dict[str, np.ndarray] = {}
        from flink_ml_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

        axis_sizes = {DATA_AXIS: self.ctx.n_data, MODEL_AXIS: self.ctx.n_model}
        for name, col in columns.items():
            col = np.asarray(col)
            if name in ("indices", "values"):
                self.host_columns[name] = col
            spec = column_specs.get(name)
            if spec is None:
                arr, _ = self.ctx.shard_batch(col)
            else:
                pads = [(0, self.ctx.pad_batch(col.shape[0]))]
                for d, axis in enumerate(spec[1:], start=1):
                    size = axis_sizes.get(axis, 1) if axis else 1
                    pads.append((0, (-col.shape[d]) % size))
                pads += [(0, 0)] * (col.ndim - len(pads))
                if any(p for _, p in pads):
                    col = np.pad(col, pads)
                arr = jax.device_put(col, self.ctx.sharding(*spec))
            self.arrays[name] = arr
        mask = np.ones(n, np.float32)
        self.arrays["__mask__"], _ = self.ctx.shard_batch(mask)
        self.n_padded = self.arrays["__mask__"].shape[0]

    @property
    def local_rows(self) -> int:
        """Rows per device shard (padded)."""
        return self.n_padded // self.ctx.n_data

    def __getitem__(self, name: str) -> jax.Array:
        return self.arrays[name]

    @property
    def mask(self) -> jax.Array:
        return self.arrays["__mask__"]


class HostDataCache:
    """Append-only columnar cache in host RAM with disk spill.

    ``append`` adds a chunk (dict of equally-long arrays); once ``memory_budget_bytes``
    is exceeded, subsequent chunks are written as .npy files under ``spill_dir`` and
    memory-mapped on read. ``iter_minibatches`` yields device-ready batches of
    ``batch_size`` rows (trailing partial batch emitted unless ``drop_last``),
    cycling epoch after epoch like the reference's DataCacheReader replay.
    """

    def __init__(
        self,
        memory_budget_bytes: Optional[int] = None,
        spill_dir: Optional[str] = None,
    ):
        # Constructor args win; otherwise the runtime config tier decides
        # (ref iteration.data-cache.path — deployments set spill locations
        # without code changes).
        from flink_ml_tpu.config import resolve_cache_config

        self.memory_budget, self.spill_dir = resolve_cache_config(
            memory_budget_bytes, spill_dir
        )
        # Append-ordered log; each entry is either {"mem": chunk} or {"files": paths}.
        self._log: List[Dict[str, object]] = []
        self._chunk_rows: List[int] = []
        self._mem_bytes = 0
        self._n_rows = 0
        self._spill_count = 0
        self._finished = False

    # --- write side (DataCacheWriter.addRecord/finish) -----------------------
    def append(self, chunk: Dict[str, np.ndarray]) -> None:
        if self._finished:
            raise RuntimeError("cache already finished")
        chunk = {k: np.asarray(v) for k, v in chunk.items()}
        lengths = {v.shape[0] for v in chunk.values()}
        if len(lengths) != 1:
            raise ValueError(f"inconsistent column lengths {lengths}")
        (n,) = lengths
        nbytes = sum(v.nbytes for v in chunk.values())
        if self._mem_bytes + nbytes > self.memory_budget and self.spill_dir:
            faults.trip("datacache.spill.write", chunk=self._spill_count)
            os.makedirs(self.spill_dir, exist_ok=True)
            files = {}
            for k, v in chunk.items():
                path = os.path.join(self.spill_dir, f"chunk{self._spill_count}_{k}.npy")
                np.save(path, v)
                files[k] = path
            self._log.append({"files": files})
            self._spill_count += 1
        else:
            self._log.append({"mem": chunk})
            self._mem_bytes += nbytes
        self._chunk_rows.append(n)
        self._n_rows += n

    def finish(self) -> None:
        self._finished = True

    @property
    def num_rows(self) -> int:
        return self._n_rows

    # --- read side (DataCacheReader) -----------------------------------------
    def _chunks(self) -> Iterator[Dict[str, np.ndarray]]:
        """Chunks in append order (memory and spilled tiers interleaved as written)."""
        for i in range(len(self._log)):
            yield self._chunk_at(i)

    def iter_rows(self) -> Iterator[Dict[str, np.ndarray]]:
        yield from self._chunks()

    def _chunk_at(self, idx: int) -> Dict[str, np.ndarray]:
        entry = self._log[idx]
        if "mem" in entry:
            return entry["mem"]  # type: ignore[return-value]
        faults.trip("datacache.spill.read", chunk=idx)
        return {
            k: np.load(path, mmap_mode="r")
            for k, path in entry["files"].items()  # type: ignore[union-attr]
        }

    def rows(self, start: int, stop: int) -> Dict[str, np.ndarray]:
        """Random-access gather of rows ``[start, stop)`` across the chunk log.

        Spilled chunks are memory-mapped and sliced, so only the requested rows
        materialize — this is what lets training stream HBM-sized windows out of
        a larger-than-memory cache (the ``DataCacheReader`` random-access role).
        Requires ``0 <= start <= stop <= num_rows``.
        """
        return _gather_rows(self._chunk_rows, self._chunk_at, start, stop)

    def iter_minibatches(
        self, batch_size: int, drop_last: bool = False
    ) -> Iterator[Dict[str, np.ndarray]]:
        """One pass over the cache in fixed-size batches (re-chunking across chunk
        boundaries; a trailing partial batch is emitted unless ``drop_last``)."""
        from flink_ml_tpu.iteration.stream import rebatch

        yield from rebatch(
            ({k: np.asarray(v) for k, v in c.items()} for c in self._chunks()),
            batch_size,
            drop_last=drop_last,
        )

    # --- snapshot (DataCacheSnapshot.writeTo/recover) ------------------------
    def snapshot(self, path: str) -> None:
        os.makedirs(path, exist_ok=True)
        count = 0
        for i, chunk in enumerate(self._chunks()):
            np.savez(os.path.join(path, f"chunk{i}.npz"), **chunk)
            count = i + 1
        # Manifest guards against stale chunk files from an earlier, larger snapshot
        # in the same directory.
        with open(os.path.join(path, "MANIFEST.json"), "w") as f:
            json.dump({"num_chunks": count, "num_rows": self._n_rows}, f)

    @classmethod
    def recover(cls, path: str, **kwargs) -> "HostDataCache":
        cache = cls(**kwargs)
        with open(os.path.join(path, "MANIFEST.json")) as f:
            manifest = json.load(f)
        for i in range(manifest["num_chunks"]):
            with np.load(os.path.join(path, f"chunk{i}.npz")) as z:
                cache.append({k: z[k] for k in z.files})
        cache.finish()
        return cache
