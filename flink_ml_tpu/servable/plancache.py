"""PlanCache — the persistent compiled-executable cache (docs/plancache.md).

Every process start, supervisor restart, hot swap, and rollback pays full XLA
compilation per (version, bucket, shard, fusion-tier) program — the dominant
term in publish→serve latency and the entire ``compile``/``recovery`` goodput
categories. The Gemma-on-TPU serving comparison (PAPERS.md) credits much of
TPU serving's edge to AOT/cache discipline, and ML Productivity Goodput
counts recompile-after-preemption as pure goodput loss. This module makes the
chain executor's ``lower().compile()`` a **load-or-compile**:

- **Tier 1 — serialized AOT executables.** A compiled chain program is
  serialized (``jax.experimental.serialize_executable`` — the
  ``compiled.serialize`` surface of this jaxlib) into one ``<digest>.plan``
  entry per program, written atomically (tmp + fsync + rename) with a
  per-entry CRC32. The next incarnation's ``run_segment`` deserializes the
  executable instead of compiling it — measured ~15-50× faster than the XLA
  compile on this backend, bit-identical by construction (the loaded
  executable IS the compiled artifact).
- **Tier 2 — JAX's persistent compilation cache.** Not this module's: the
  process-wide cache the entry points place once
  (``utils/compile_cache.py`` — ``JAX_COMPILATION_CACHE_DIR``, else
  ``<checkout>/.jax_cache``). Programs tier 1 cannot carry (fallback stages'
  own jit kernels, executables whose serialization the backend rejects)
  skip the XLA backend work through it on a warm disk. Activating a plan
  cache never moves it.

**Key schema** (docs/plancache.md): the digest is a content fingerprint of
the program's *lowered StableHLO text* — which bakes in the spec-chain
params (traced constants: thresholds, column bindings), the model-array
shapes/dtypes (executable inputs — weight *values* are arguments, so a new
published version with the same architecture HITS the old version's
entries), and the input signature/bucket — plus the mesh shape + TP split
(``PlanSharding.key``), the fusion tier (``FusionTier.key`` + program kind),
and the jax/jaxlib/backend/device-topology versions. Fingerprinting happens
only on the compile path (a chain already built never hashes anything), and
lowering is paid in both the hit and miss cases — the cache removes the XLA
*compile*, the expensive term.

**Corruption / fallback contract** (the checkpoint-corrupt semantics): a
truncated, checksum-failing, or version-mismatched entry — or one whose
deserialization dies mid-flight (fault point ``plancache.load``) — is
quarantined as ``<entry>.corrupt`` (kept for forensics, never reloaded) and
the chain falls back to a live compile. Fail-open, never wrong: no cache
state can ever surface as a serving error or a wrong bit. Stores are equally
fail-open (``plancache.write``): a torn write leaves only a ``.tmp`` orphan
(swept at the next cache init), never a visible entry.

Entries are bounded by ``plancache.max.bytes`` LRU (hits ``os.utime`` the
entry; eviction removes the stalest). Hits/misses/bytes/load-ms land in
``ml.plancache.*``; every load/store decision lands in the flight recorder
(``plancache.load`` / ``plancache.store`` records).

Trust model: entries deserialize via pickle (the jax serialize_executable
format), so the cache directory must be writable only by the serving
deployment itself — same trust class as the model publish directory.
"""
from __future__ import annotations

import json
import os
import pickle
import struct
import threading
import time
import zlib
from hashlib import sha256
from typing import Any, Dict, Optional, Tuple

import jax

import flink_ml_tpu.telemetry as telemetry
from flink_ml_tpu.config import Options, config
from flink_ml_tpu.faults import faults
from flink_ml_tpu.metrics import MLMetrics, metrics

__all__ = ["PlanCache", "program_digest", "resolve_plan_cache"]

SCOPE = MLMetrics.PLANCACHE_GROUP

_MAGIC = b"FMLPLAN1"
_FORMAT = 1
_ENTRY_SUFFIX = ".plan"
_QUARANTINE_SUFFIX = ".corrupt"
_TMP_MARKER = ".plan.tmp."


class _EntryInvalid(Exception):
    """An entry failed verification (corrupt bytes or a header whose
    format/digest/toolchain does not match this process) — quarantine it."""


# -- fingerprinting -----------------------------------------------------------

_ENV_LOCK = threading.Lock()
_ENV: Optional[Dict[str, Any]] = None


def _env_fingerprint() -> Dict[str, Any]:
    """The toolchain/topology part of every digest: an executable compiled by
    one jaxlib for one device topology must never load into another."""
    global _ENV
    with _ENV_LOCK:
        if _ENV is None:
            import jaxlib

            devices = jax.devices()
            _ENV = {
                "jax": jax.__version__,
                "jaxlib": jaxlib.__version__,
                "backend": jax.default_backend(),
                "device_kind": devices[0].device_kind,
                "devices": len(devices),
            }
        return _ENV


def program_digest(
    lowered: Any,
    *,
    kind: str,
    sharding_key: Optional[Tuple] = None,
    fusion_key: Optional[Tuple] = None,
    replicated: bool = False,
    sparse_key: Optional[int] = None,
    precision_key: Optional[str] = None,
) -> str:
    """Content fingerprint of one chain program: the lowered StableHLO text
    (spec-chain params as traced constants, model-array shapes/dtypes as
    executable inputs, the input signature/bucket as argument shapes) plus
    the mesh shape + TP split, the fusion tier + program kind, the sparse
    nnz-cap ladder key (the ELL cap already shapes the lowered text — the
    explicit component keeps two caps distinct even for a program whose
    lowering happens not to read the padding), the precision tier
    (``PrecisionTier.cache_key`` — the bf16-rounded lowering already differs
    textually, but the explicit component is the rebuild-key contract the
    plan-key-completeness rule enforces; ``None`` ≡ f32 keeps every
    pre-precision digest valid), and the jax/jaxlib/backend versions.
    Deterministic across processes — the cross-incarnation cache identity
    (docs/plancache.md)."""
    h = sha256()
    h.update(json.dumps(_env_fingerprint(), sort_keys=True).encode())
    parts = (kind, sharding_key, fusion_key, bool(replicated), sparse_key)
    if precision_key is not None:
        # Appended only when a low-precision tier is in play, so every digest
        # minted before the precision axis existed stays byte-identical.
        parts = parts + (precision_key,)
    h.update(repr(parts).encode())
    h.update(lowered.as_text().encode())
    return h.hexdigest()


# -- the cache ----------------------------------------------------------------


class PlanCache:
    """One on-disk entry tier. Immutable after construction (directory,
    bound, scope); all mutable state is the filesystem itself plus the
    process-global metrics registry, so warmup on the poller thread and a
    programmatic swap on the caller's thread may share one instance freely —
    tmp names are unique per (pid, thread), ``os.replace`` is atomic, and a
    concurrent eviction surfaces to a loader as an ordinary miss."""

    def __init__(self, directory: str, max_bytes: int, scope: str = SCOPE):
        self.directory = os.path.abspath(directory)
        self.max_bytes = int(max_bytes)
        self.scope = scope
        os.makedirs(self.directory, exist_ok=True)
        self._sweep_orphans()
        self._update_bytes_gauge()

    # -- load ------------------------------------------------------------------
    def load(self, digest: str, *, context: Optional[Dict[str, Any]] = None):  # graftcheck: cold
        """The serialized executable stored under ``digest``, loaded back as
        a callable ``jax.stages.Compiled`` — or None on a miss. A corrupt,
        mismatched, or mid-deserialize-dying entry is quarantined and
        reported as a miss: the caller live-compiles (fail-open, never
        wrong). Hits refresh the entry's LRU recency."""
        path = self._entry_path(digest)
        t0 = time.perf_counter()
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except (FileNotFoundError, NotADirectoryError):
            metrics.counter(self.scope, MLMetrics.PLANCACHE_MISSES)
            self._record("plancache.load", digest, "miss", context)
            return None
        except OSError:
            metrics.counter(self.scope, MLMetrics.PLANCACHE_MISSES)
            self._record("plancache.load", digest, "miss", context)
            return None
        try:
            faults.trip("plancache.load", digest=digest[:16])
            compiled = self._decode(raw, digest)
        except Exception as e:  # noqa: BLE001 — fail-open by contract
            self._quarantine(path, type(e).__name__)
            metrics.counter(self.scope, MLMetrics.PLANCACHE_MISSES)
            self._record(
                "plancache.load", digest, "quarantined", context,
                error=type(e).__name__,
            )
            return None
        ms = (time.perf_counter() - t0) * 1000.0
        try:
            os.utime(path, None)  # LRU recency
        except OSError:
            pass
        metrics.counter(self.scope, MLMetrics.PLANCACHE_HITS)
        metrics.observe(self.scope, MLMetrics.PLANCACHE_LOAD_MS, ms)
        self._record("plancache.load", digest, "hit", context, ms=round(ms, 3))
        return compiled

    def _decode(self, raw: bytes, digest: str):
        """Verify and deserialize one entry's bytes. Raises
        :class:`_EntryInvalid` on any structural/checksum/toolchain mismatch
        (quarantined by the caller); the jax deserializer's own failures
        propagate to the same fate."""
        if len(raw) < len(_MAGIC) + 4 or raw[: len(_MAGIC)] != _MAGIC:
            raise _EntryInvalid("bad magic")
        (header_len,) = struct.unpack(
            ">I", raw[len(_MAGIC): len(_MAGIC) + 4]
        )
        header_end = len(_MAGIC) + 4 + header_len
        if header_end > len(raw):
            raise _EntryInvalid("truncated header")
        try:
            header = json.loads(raw[len(_MAGIC) + 4: header_end])
        except ValueError as e:
            raise _EntryInvalid("unparsable header") from e
        if header.get("format") != _FORMAT:
            raise _EntryInvalid(f"format {header.get('format')!r}")
        if header.get("digest") != digest:
            raise _EntryInvalid("digest mismatch")
        env = _env_fingerprint()
        if header.get("env") != env:
            # Defense in depth: the digest already encodes the toolchain, so
            # reaching here means a collision or a tampered header — exactly
            # what the quarantine forensics trail exists for.
            raise _EntryInvalid("toolchain mismatch")
        payload = raw[header_end:]
        if len(payload) != header.get("payload_bytes"):
            raise _EntryInvalid("truncated payload")
        if zlib.crc32(payload) != header.get("crc32"):
            raise _EntryInvalid("checksum mismatch")
        from jax.experimental import serialize_executable

        blob, in_tree, out_tree = pickle.loads(payload)
        return serialize_executable.deserialize_and_load(blob, in_tree, out_tree)

    # -- store -----------------------------------------------------------------
    def store(  # graftcheck: cold
        self, digest: str, compiled: Any, *, meta: Optional[Dict[str, Any]] = None
    ) -> bool:
        """Serialize ``compiled`` under ``digest``, atomically (tmp + fsync +
        rename, per-entry CRC32). Fail-open: a backend that cannot serialize
        this executable (``ml.plancache.store.errors``) or a write that dies
        mid-flight (fault point ``plancache.write`` — a torn ``.tmp`` orphan,
        never a visible entry) leaves serving untouched."""
        path = self._entry_path(digest)
        if os.path.exists(path):
            return True
        tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
        try:
            from jax.experimental import serialize_executable

            blob, in_tree, out_tree = serialize_executable.serialize(compiled)
            payload = pickle.dumps((blob, in_tree, out_tree))
            header = {
                "format": _FORMAT,
                "digest": digest,
                "env": _env_fingerprint(),
                "payload_bytes": len(payload),
                "crc32": zlib.crc32(payload),
                "meta": dict(meta or {}),
            }
            header_bytes = json.dumps(header, sort_keys=True).encode()
            with open(tmp, "wb") as f:
                f.write(_MAGIC)
                f.write(struct.pack(">I", len(header_bytes)))
                f.write(header_bytes)
                # The torn-tail discipline (telemetry.journal): flush half,
                # then the injection seam — a killed store leaves a REAL
                # torn tmp file for the orphan sweep, never a visible entry.
                f.write(payload[: len(payload) // 2])
                f.flush()
                faults.trip("plancache.write", digest=digest[:16])
                f.write(payload[len(payload) // 2:])
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except Exception as e:  # noqa: BLE001 — fail-open by contract
            metrics.counter(self.scope, MLMetrics.PLANCACHE_STORE_ERRORS)
            self._record(
                "plancache.store", digest, "error", meta, error=type(e).__name__
            )
            return False
        metrics.counter(self.scope, MLMetrics.PLANCACHE_STORES)
        self._record(
            "plancache.store", digest, "stored", meta,
            bytes=len(_MAGIC) + 4 + len(header_bytes) + len(payload),
        )
        self._enforce_budget()
        return True

    # -- maintenance -----------------------------------------------------------
    def _entry_path(self, digest: str) -> str:
        return os.path.join(self.directory, digest + _ENTRY_SUFFIX)

    def _quarantine(self, path: str, reason: str) -> None:
        """Set a bad entry aside as ``<entry>.corrupt`` — the checkpoint
        tier's corrupt-snapshot semantics: kept for forensics, invisible to
        every future load (the suffixed name is never a cache path)."""
        dst = path + _QUARANTINE_SUFFIX
        n = 0
        while os.path.exists(dst):
            n += 1
            dst = f"{path}{_QUARANTINE_SUFFIX}.{n}"
        try:
            os.rename(path, dst)
        except OSError:
            return
        metrics.counter(self.scope, MLMetrics.PLANCACHE_QUARANTINED)
        telemetry.emit(
            "plancache.quarantine",
            self.scope,
            {"entry": os.path.basename(path), "reason": reason},
        )

    def _sweep_orphans(self) -> None:
        """Remove ``.tmp`` orphans a killed store left behind (the
        checkpoint tier's orphan sweep): they never became entries, so
        deleting them can lose nothing."""
        try:
            names = os.listdir(self.directory)
        except OSError:
            return
        swept = 0
        for name in names:
            if _TMP_MARKER in name:
                try:
                    os.remove(os.path.join(self.directory, name))
                    swept += 1
                except OSError:
                    pass
        if swept:
            metrics.counter(self.scope, MLMetrics.PLANCACHE_TMP_SWEPT, swept)

    def _entries(self):
        """(path, mtime, size) per live entry, oldest-recency first."""
        out = []
        try:
            names = os.listdir(self.directory)
        except OSError:
            return out
        for name in names:
            if not name.endswith(_ENTRY_SUFFIX):
                continue
            path = os.path.join(self.directory, name)
            try:
                st = os.stat(path)
            except OSError:
                continue
            out.append((path, st.st_mtime, st.st_size))
        out.sort(key=lambda e: e[1])
        return out

    def bytes_used(self) -> int:
        return sum(size for _path, _mtime, size in self._entries())

    def _update_bytes_gauge(self) -> int:
        total = self.bytes_used()
        metrics.gauge(self.scope, MLMetrics.PLANCACHE_BYTES, total)
        return total

    def _enforce_budget(self) -> None:
        """LRU eviction: drop the least-recently-loaded entries until the
        tier fits ``plancache.max.bytes`` (hits refresh mtime via utime)."""
        entries = self._entries()
        total = sum(size for _p, _m, size in entries)
        evicted = 0
        for path, _mtime, size in entries:
            if total <= self.max_bytes:
                break
            try:
                os.remove(path)
            except OSError:
                continue
            total -= size
            evicted += 1
        if evicted:
            metrics.counter(self.scope, MLMetrics.PLANCACHE_EVICTED, evicted)
        metrics.gauge(self.scope, MLMetrics.PLANCACHE_BYTES, max(0, total))

    def _record(
        self,
        kind: str,
        digest: str,
        outcome: str,
        context: Optional[Dict[str, Any]],
        **extra: Any,
    ) -> None:
        """One flight-recorder decision record per load/store outcome —
        compile/warmup-path only (a chain already built never reaches the
        cache), so the volume is bounded by the executable set."""
        data: Dict[str, Any] = {"digest": digest[:16], "outcome": outcome}
        if context:
            data.update(context)
        data.update(extra)
        telemetry.emit(kind, self.scope, data)


# -- resolution ---------------------------------------------------------------

_CACHES_LOCK = threading.Lock()
_CACHES: Dict[Tuple[str, int], PlanCache] = {}


def resolve_plan_cache() -> Optional[PlanCache]:
    """The process's plan cache per the config tier (``plancache.enabled`` /
    ``plancache.dir`` / ``plancache.max.bytes``), or None when inactive —
    the default: with no directory configured every plan compiles live,
    exactly the pre-cache behavior."""
    if not config.get(Options.PLANCACHE_ENABLED):
        return None
    directory = config.get(Options.PLANCACHE_DIR)
    if not directory:
        return None
    key = (os.path.abspath(str(directory)), int(config.get(Options.PLANCACHE_MAX_BYTES)))
    with _CACHES_LOCK:
        cache = _CACHES.get(key)
    if cache is not None:
        return cache
    # Construction scans/creates the directory — blocking I/O that must not
    # run under the registry lock (a slow disk would stall every serving
    # thread resolving the cache). Build outside, publish inside: a racing
    # thread may build a second candidate, but exactly one wins the dict and
    # the loser's object is garbage (its mkdir/scan side effects idempotent).
    candidate = PlanCache(key[0], key[1])
    with _CACHES_LOCK:
        cache = _CACHES.get(key)
        if cache is None:
            cache = candidate
            _CACHES[key] = cache
        return cache

