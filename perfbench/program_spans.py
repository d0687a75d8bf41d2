"""The program's own phases, read from the profiler's trace.

The program opens its fit phases (``train.fit``, ``train.pack``,
``train.layout.fill``, ...; ``flink_ml_tpu/trace.py::Tracer.phase``) as
``jax.profiler.TraceAnnotation``s, whoever started the profiler. A traced run
therefore holds them on its host planes, on the clock of the ``XLA Ops`` line,
with their counts (``rows``, ``bytes``, ``reused``, ...) as the event's stats.
Nobody switches them on, and a program that has none (the parent of the PR
that brought them) gives an empty table: every reducer over it then returns
None and its metric is left out of the line.

A span's children are the spans of its own thread line that lie inside it;
its self time is its duration minus the part its children cover.
"""
from __future__ import annotations

from typing import Iterable, List, Optional

from perfbench import xplane

PREFIX = "train."


class Span:
    __slots__ = ("name", "start", "dur", "line", "stats", "parent", "children")

    def __init__(self, name: str, start: float, dur: float, line=0, stats: Optional[dict] = None):
        self.name, self.start, self.dur, self.line = name, float(start), float(dur), line
        self.stats = stats or {}
        self.parent: Optional["Span"] = None
        self.children: List["Span"] = []

    @property
    def end(self) -> float:
        return self.start + self.dur

    def inside(self, t0: float, t1: float) -> float:
        """Nanoseconds of this span that lie inside ``[t0, t1]``."""
        return max(0.0, min(self.end, t1) - max(self.start, t0))

    def self_inside(self, t0: float, t1: float) -> float:
        return self.inside(t0, t1) - sum(c.inside(t0, t1) for c in self.children)


class Table:
    """Spans nested by containment on their thread line."""

    def __init__(self, spans: Iterable[Span]):
        self.spans = sorted(spans, key=lambda s: (s.start, -s.dur))
        open_on = {}  # thread line -> stack of the spans open at this point
        for s in self.spans:
            stack = open_on.setdefault(s.line, [])
            while stack and s.start >= stack[-1].end:
                stack.pop()
            if stack:
                s.parent = stack[-1]
                stack[-1].children.append(s)
            stack.append(s)

    def named(self, names) -> List[Span]:
        names = {names} if isinstance(names, str) else set(names)
        return [s for s in self.spans if s.name in names]

    def seconds(self, names, t0: float, t1: float, self_time: bool = False) -> Optional[float]:
        """Seconds of the named spans inside the window (a span the window's
        edge cuts counts with the part inside); None where there is none."""
        found = [s for s in self.named(names) if s.inside(t0, t1) > 0]
        if not found:
            return None
        part = Span.self_inside if self_time else Span.inside
        return sum(part(s, t0, t1) for s in found) / 1e9

    def started(self, names, t0: float, t1: float) -> List[Span]:
        return [s for s in self.named(names) if t0 <= s.start < t1]


def read(trace_dir: str) -> Table:
    """Every ``train.*`` event on the host planes of the newest trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane.find_xplane(trace_dir))
    spans = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    spans.append(Span(ev.name, ev.start_ns, ev.duration_ns,
                                      (plane.name, i), dict(ev.stats)))
    return Table(spans)


def of_run(run) -> Table:
    """The run's table, read once."""
    table = getattr(run, "program_spans", None)
    if table is None:
        table = run.program_spans = read(run.trace_dir)
    return table
