"""The benchmark's own spans, around the calls into each layer.

A span is timed on the host clock, kept in memory by name, and mirrored into
the profiler's trace (``jax.profiler.TraceAnnotation``) so that device idle
gaps can be laid against it. Spans inside the program are the program's.
"""
from __future__ import annotations

import contextlib
import functools
import importlib
import time

_wrapped = []  # (owner, attribute, original)


@contextlib.contextmanager
def span(name: str, sink: dict):
    import jax

    t = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        yield
    sink.setdefault(name, []).append(time.perf_counter() - t)


def wrap_layers(table, names: set, on_result=None) -> None:
    """For the traced run: put a trace annotation around each host function of
    the program that ``table`` lists as ``(module, class, attribute, span)``.
    ``on_result(span name, result)`` sees what each call returned."""
    import jax

    for module, cls, attr, name in table:
        owner = getattr(importlib.import_module(module), cls)
        original = owner.__dict__[attr]
        inner = original.__func__ if isinstance(original, (classmethod, staticmethod)) else original

        def make(inner=inner, name=name):
            @functools.wraps(inner)
            def wrapper(*a, **k):
                with jax.profiler.TraceAnnotation(name):
                    out = inner(*a, **k)
                if on_result is not None:
                    on_result(name, out)
                return out

            return wrapper

        wrapper = make()
        if isinstance(original, classmethod):
            wrapper = classmethod(wrapper)
        elif isinstance(original, staticmethod):
            wrapper = staticmethod(wrapper)
        setattr(owner, attr, wrapper)
        _wrapped.append((owner, attr, original))
        names.add(name)


def unwrap_layers() -> None:
    while _wrapped:
        owner, attr, original = _wrapped.pop()
        setattr(owner, attr, original)
