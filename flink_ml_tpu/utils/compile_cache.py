"""Where a process keeps JAX's persistent compilation cache.

A fresh machine compiles every program it runs, and on the chip compilation
is a large part of a cold start. JAX's persistent cache removes it on the
second run — but the directory is part of how entries are found, so a path
that moves (a ``tempfile`` name) never hits. The placement therefore comes
from OUTSIDE the program: ``JAX_COMPILATION_CACHE_DIR`` when the deployment
sets it (JAX reads that variable itself), else one fixed directory inside the
checkout. No library code sets the cache anywhere else; every entry point
(``chip_smoke.py``, the benchmark CLI, the fleet worker) calls
:func:`configure_compile_cache` once, before it compiles anything
(``perfbench/run.py`` applies the same rule with a fixed directory of its own).
"""
from __future__ import annotations

import os

import jax

__all__ = ["CACHE_DIR_ENV", "DEFAULT_CACHE_DIR", "configure_compile_cache"]

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` — beside the package, listed in ``.gitignore``.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure_compile_cache() -> str:
    """Place the process-wide compilation cache; returns the directory in use.

    With ``JAX_COMPILATION_CACHE_DIR`` set this touches nothing — the
    directory and every cache threshold stay whatever the deployment chose.
    Unset, the cache goes to :data:`DEFAULT_CACHE_DIR` and keeps every
    program, however quick to compile: a serving warmup is dozens of
    sub-second compiles that JAX's default threshold would drop."""
    from_env = os.environ.get(CACHE_DIR_ENV)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return DEFAULT_CACHE_DIR
