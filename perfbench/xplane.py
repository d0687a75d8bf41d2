"""From a profiler trace to intervals, and from intervals to numbers.

Two stages, so that the arithmetic is testable without a chip:

1. :func:`read_trace` opens the ``.xplane.pb`` the JAX profiler wrote
   (``jax.profiler.ProfileData``) and keeps three plain tables: per device the
   executed operations (line ``XLA Ops``) and the executed programs (line
   ``XLA Modules``), and the host's annotation spans by name. A table is a
   list of ``[name, start_ns, duration_ns]``; ``perfbench/tests/data`` keeps a
   small recorded one.
2. The functions below reduce tables: busy time as the union of intervals,
   totals by operation name, idle gaps by the host span open at the time, the
   exposed part of collectives.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: The op line names an event by its whole HLO instruction
#: (``%fusion.3 = f32[...] fusion(...)``); the instruction's own name is kept.
OP_NAME = re.compile(r"^%?([^\s=(]+)")
#: Control flow that holds other operations of the line inside its interval:
#: busy while it runs, but never summed with what it holds.
CONTAINER = re.compile(r"^(while|conditional|call)(\.|$)")
#: Collective operations as XLA names them on the device's op line (an
#: all-reduce that came from ``lax.psum`` is called ``psum_invariant.N`` there).
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|psum|ppermute)", re.I
)


class Trace:
    """``ops``/``modules``: device id -> rows ``[name, start_ns, dur_ns]``;
    ``host``: rows of the annotation spans asked for; all on one clock."""

    def __init__(self, ops: Dict[int, list], modules: Dict[int, list], host: list):
        self.ops = {int(k): sorted(v, key=lambda r: r[1]) for k, v in ops.items()}
        self.modules = {int(k): sorted(v, key=lambda r: r[1]) for k, v in modules.items()}
        self.host = sorted(host, key=lambda r: r[1])


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_trace(trace_dir: str, host_names: Iterable[str]) -> Trace:
    """Open the newest trace under ``trace_dir``; keep the device lines and the
    host events whose name is in ``host_names`` (the spans the benchmark and
    the program's tracer wrote)."""
    from jax.profiler import ProfileData

    wanted = set(host_names)
    data = ProfileData.from_file(find_xplane(trace_dir))
    ops: Dict[int, list] = {}
    modules: Dict[int, list] = {}
    host: list = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    target = ops.setdefault(dev, [])
                elif line.name == MODULES_LINE:
                    target = modules.setdefault(dev, [])
                else:
                    continue
                for ev in line.events:
                    name = OP_NAME.match(ev.name)
                    target.append(
                        [name.group(1) if name else ev.name, float(ev.start_ns), float(ev.duration_ns)]
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        host.append([ev.name, float(ev.start_ns), float(ev.duration_ns)])
    return Trace(ops, modules, host)


# -- interval arithmetic ------------------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], t0: float, t1: float) -> List[Interval]:
    return [(max(a, t0), min(b, t1)) for a, b in intervals if b > t0 and a < t1]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(merged: Sequence[Interval], t0: float, t1: float) -> List[Interval]:
    """The complement of merged intervals inside ``[t0, t1]``."""
    out = []
    at = t0
    for a, b in clip(merged, t0, t1):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if t1 > at:
        out.append((at, t1))
    return out


def rows_to_intervals(rows: Iterable[Sequence]) -> List[Interval]:
    return [(r[1], r[1] + r[2]) for r in rows]


def busy_seconds(trace: Trace, t0: float, t1: float) -> Dict[int, float]:
    """Per device: seconds inside the window in which an operation ran."""
    return {
        dev: total(clip(union(rows_to_intervals(rows)), t0, t1)) / 1e9
        for dev, rows in trace.ops.items()
    }


def seconds_by_name(rows: Iterable[Sequence], t0: float, t1: float) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for name, start, dur in rows:
        if t0 <= start < t1 and not CONTAINER.match(name):
            out[name] = out.get(name, 0.0) + dur / 1e9
    return out


def matching_seconds(rows: Iterable[Sequence], pattern: str, t0: float, t1: float) -> float:
    rx = re.compile(pattern)
    return sum(
        dur for name, start, dur in rows
        if t0 <= start < t1 and rx.search(name) and not CONTAINER.match(name)
    ) / 1e9


def idle_by_host_span(
    trace: Trace, dev: int, t0: float, t1: float, none_name: str = "_no_annotation_"
) -> Dict[str, float]:
    """Seconds the device sat idle inside the window, by the innermost host
    span open at the time (the latest-started span that covers the moment)."""
    spans = [(s, s + d, name) for name, s, d in trace.host if s + d > t0 and s < t1]
    merged = union(rows_to_intervals(trace.ops.get(dev, [])))
    out: Dict[str, float] = {}
    for a, b in gaps(merged, t0, t1):
        cuts = sorted({a, b, *[p for s, e, _ in spans for p in (s, e) if a < p < b]})
        for lo, hi in zip(cuts, cuts[1:]):
            mid = (lo + hi) / 2
            open_now = [(s, name) for s, e, name in spans if s <= mid < e]
            name = max(open_now)[1] if open_now else none_name
            out[name] = out.get(name, 0.0) + (hi - lo) / 1e9
    return out


def exposed_seconds(rows: Sequence[Sequence], t0: float, t1: float) -> float:
    """Seconds of collective operations on one device during which no other
    operation ran there."""
    coll = [(s, s + d) for name, s, d in rows if COLLECTIVE.match(name)]
    rest = union(
        (s, s + d) for name, s, d in rows
        if not COLLECTIVE.match(name) and not CONTAINER.match(name)
    )
    exposed = 0.0
    for a, b in clip(union(coll), t0, t1):
        exposed += (b - a) - total(clip(rest, a, b))
    return exposed / 1e9


def host_spans(trace: Trace, name: str, t0: Optional[float] = None, t1: Optional[float] = None):
    return [
        (s, s + d) for n, s, d in trace.host
        if n == name and (t0 is None or s >= t0) and (t1 is None or s < t1)
    ]

