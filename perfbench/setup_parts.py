"""The set-up taken apart, and the compilations JAX reports.

``setup_s`` is process start to the start of the measured window. Every run
prints its parts so that a part that moves between runs can be named:
``interp_s`` (process start to the harness's first line), ``import_s``,
``backend_init_s``, ``data_s``, ``build_s``, ``warm_s`` (with the compile
seconds and the persistent-cache requests, hits and misses inside it),
``settle_s``, and their sum beside ``setup_s``.
"""
from __future__ import annotations

import contextlib
import os
import time


def process_age_s() -> float:
    """Seconds since this process was started (/proc; 0.0 where unreadable)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        return time.clock_gettime(time.CLOCK_BOOTTIME) - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, AttributeError):
        return 0.0


class SetupParts:
    def __init__(self, interp_s: float, t_first: float):
        self.parts = {"interp_s": interp_s}
        self.t_first = t_first
        self.interp_s = interp_s

    @contextlib.contextmanager
    def part(self, name: str):
        t = time.perf_counter()
        try:
            yield
        finally:
            self.parts[name] = self.parts.get(name, 0.0) + time.perf_counter() - t

    def close(self, window_start: float) -> dict:
        """The printed record, once the window's start is known."""
        setup_s = self.interp_s + window_start - self.t_first
        out = dict(self.parts)
        out["sum_s"] = sum(self.parts.values())
        out["setup_s"] = setup_s
        return out


class CompileWatch:
    """Backend compilations and persistent-cache traffic, as ``jax.monitoring``
    reports them, each with the host time it was seen at."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.compiles = []  # (seen at, seconds)
        self.requests = []
        self.hits = []
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == self.COMPILE:
            self.compiles.append((time.perf_counter(), duration))

    def _on_event(self, event: str, **_) -> None:
        if event == self.REQUEST:
            self.requests.append(time.perf_counter())
        elif event == self.HIT:
            self.hits.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> dict:
        """``programs`` counts every program JAX built or fetched (the event wraps
        the persistent-cache lookup too); ``compiled`` leaves out the cache hits."""
        inside = [d for t, d in self.compiles if t0 <= t < t1]
        requests = sum(1 for t in self.requests if t0 <= t < t1)
        hits = sum(1 for t in self.hits if t0 <= t < t1)
        return {
            "programs": len(inside),
            "compiled": len(inside) - hits,
            "compile_s": sum(inside),
            "cache_requests": requests,
            "cache_hits": hits,
            "cache_misses": requests - hits,
        }
