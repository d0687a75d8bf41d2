"""Native (C++) runtime components and their ctypes bindings.

The reference keeps its runtime in managed Java (zero native code — SURVEY.md
§2.1); this framework's compute path is XLA (itself a native runtime), and the
host-side pieces that want native performance live here. First component: the
spillable chunk store behind the capacity-tier data cache (datacache.cpp — the
MemorySegment datacache analogue).

The shared library is compiled on first use with the system toolchain and cached
next to the source under a name that carries the source's hash, so a binary
built from any other source — a stale one copied along with the tree — is
never loaded. ``native_available()`` reports whether the toolchain/binary is
usable so callers can fall back to the pure-Python tier; a failed build is
logged once, with the compiler's stderr.
"""
from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

__all__ = ["load_datacache_lib", "native_available", "NativeChunkStore"]

_DIR = os.path.dirname(__file__)
_SRC = os.path.join(_DIR, "datacache.cpp")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_error: Optional[str] = None
_log = logging.getLogger(__name__)


def lib_path() -> str:
    """``_datacache-<source sha256, 12 hex>.so`` next to the source."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    return os.path.join(_DIR, f"_datacache-{digest}.so")


def _build(lib: str) -> None:
    tmp = f"{lib}.tmp.{os.getpid()}"
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp]
    result = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
    if result.returncode != 0:
        raise RuntimeError(f"native build failed: {result.stderr[-1000:]}")
    os.replace(tmp, lib)  # atomic: a concurrent loader never sees a torn file


def load_datacache_lib() -> ctypes.CDLL:
    """Compile (once) and load the datacache shared library."""
    global _lib, _build_error
    with _lock:
        if _lib is not None:
            return _lib
        if _build_error is not None:
            raise RuntimeError(_build_error)
        try:
            path = lib_path()
            if not os.path.exists(path):
                _build(path)
            lib = ctypes.CDLL(path)
        except Exception as e:  # remember the failure; don't retry every call
            _build_error = f"{type(e).__name__}: {e}"
            _log.warning(
                "native chunk store unavailable, the pure-Python store runs "
                "instead: %s", _build_error,
            )
            raise RuntimeError(_build_error) from e
        lib.dc_create.restype = ctypes.c_void_p
        lib.dc_create.argtypes = [ctypes.c_size_t, ctypes.c_char_p]
        lib.dc_append.restype = ctypes.c_long
        lib.dc_append.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t]
        lib.dc_num_chunks.restype = ctypes.c_long
        lib.dc_num_chunks.argtypes = [ctypes.c_void_p]
        lib.dc_chunk_size.restype = ctypes.c_long
        lib.dc_chunk_size.argtypes = [ctypes.c_void_p, ctypes.c_long]
        lib.dc_read.restype = ctypes.c_int
        lib.dc_read.argtypes = [ctypes.c_void_p, ctypes.c_long, ctypes.c_void_p]
        lib.dc_memory_bytes.restype = ctypes.c_size_t
        lib.dc_memory_bytes.argtypes = [ctypes.c_void_p]
        lib.dc_spilled_chunks.restype = ctypes.c_long
        lib.dc_spilled_chunks.argtypes = [ctypes.c_void_p]
        lib.dc_destroy.restype = None
        lib.dc_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
        return lib


def native_available() -> bool:
    try:
        load_datacache_lib()
        return True
    except Exception:
        return False


class NativeChunkStore:
    """Thin RAII wrapper over the C chunk store."""

    def __init__(self, memory_budget_bytes: int, spill_dir: Optional[str] = None):
        self._lib = load_datacache_lib()
        if spill_dir:
            os.makedirs(spill_dir, exist_ok=True)
        self._handle = self._lib.dc_create(
            memory_budget_bytes, spill_dir.encode() if spill_dir else None
        )
        if not self._handle:
            raise MemoryError("dc_create failed")

    def append(self, data: bytes) -> int:
        idx = self._lib.dc_append(self._handle, data, len(data))
        if idx < 0:
            raise IOError("dc_append failed (spill write error?)")
        return idx

    def __len__(self) -> int:
        return self._lib.dc_num_chunks(self._handle)

    def read(self, idx: int) -> bytes:
        size = self._lib.dc_chunk_size(self._handle, idx)
        if size < 0:
            raise IndexError(f"chunk {idx} out of range")
        buf = ctypes.create_string_buffer(size)
        if self._lib.dc_read(self._handle, idx, buf) != 0:
            raise IOError(f"dc_read failed for chunk {idx}")
        return buf.raw

    @property
    def memory_bytes(self) -> int:
        return self._lib.dc_memory_bytes(self._handle)

    @property
    def spilled_chunks(self) -> int:
        return self._lib.dc_spilled_chunks(self._handle)

    def close(self) -> None:
        if getattr(self, "_handle", None):
            self._lib.dc_destroy(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
