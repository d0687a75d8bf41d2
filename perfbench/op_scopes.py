"""The step program's own names for its parts, read from the profiler's trace.

The program opens ``jax.named_scope`` blocks where it defines its work
(``lm.embed``, ``lm.block/norm``, ``lm.head``, ``lm.opt``, ...;
docs/observability.md, "The step's scopes"). A scope is trace-time metadata:
it reaches every HLO instruction's ``metadata.op_name`` beside what JAX's
transformations write there (``jvp``, ``transpose``, ``checkpoint/
rematted_computation``, ``while/body``), and the profiler hands it back with
each executed operation, whoever started it. Nobody switches it on, and a
program that has no scopes (the parent of the PR that brought them, a step
served from a compile cache that an unscoped build filled: the cache's key
leaves metadata out) gives a table in which nothing is scoped: every reducer
over it then returns None and its metric is left out of the line.

Where the name is (confirmed on the chip, TPU v5 lite, jax 0.9.0): HLO
``metadata.op_name`` is the stat ``tf_op`` (``<op_name>:<op_type>``, the type
empty), and it is a stat of the operation's EVENT METADATA (one entry per
HLO instruction of a program, beside ``hlo_category``, ``flops``,
``bytes_accessed``, ``source``), not of the event: an ``XLA Ops`` event's own
stats are ``device_offset_ps``, ``device_duration_ps`` and a time scale, and
``jax.profiler.ProfileData`` hands out only those. So the plane's
``event_metadata`` is read from the file itself (:func:`op_names`, a walk over
the protobuf's wire format: nothing but the standard library) and joined to
the events by the event's name, which is the metadata's: the whole HLO
instruction as text. Two programs can hold an instruction of the same text;
where their ``tf_op`` differ the name says nothing and reads None.

One classification (:func:`classify`), a pure function of the string:
``jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/
rematted_computation/lm.block/norm/mul`` is scope ``lm.block/norm``, direction
``remat``. A fusion is ONE device operation and is billed whole to the scope
of the instruction XLA took the fusion's metadata from (its root): a norm
fused into the matmul that reads it counts under the matmul's scope.

Kernels the compiler renames: XLA's TPU expansion of ``ragged_dot_general``
writes its own ``op_name`` (``ragged-dot-none``) over the ``dot_general``'s,
so the grouped matmuls arrive without the scope they were traced under. A
metric names them (``renamed``: instruction-name pattern -> scope), and such
an operation takes the direction of the last operation before it on the
device's line that lies under the same outermost scope: the producer of its
rows (the permutation's gather, the guard) is such an operation and runs
before it in the same direction.
"""
from __future__ import annotations

import bisect
import functools
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from perfbench import xplane

#: The stat of an operation's event metadata that carries HLO ``metadata.op_name``.
OP_NAME_STAT = "tf_op"
#: What JAX's transformations write into an ``op_name`` beside the program's
#: scopes, as the three LM step programs show it on the chip.
TRANSFORMS = frozenset({"jvp", "transpose", "checkpoint", "rematted_computation", "closed_call", "while", "body", "cond"})
#: A nested ``jit(f)`` is a library function's inside (``jit(take_along_axis)``,
#: ``jit(cumsum)/...``): the scope ends where it begins.
NESTED = frozenset({"jit", "pjit"})
FWD, REMAT, BWD = "fwd", "remat", "bwd"
DIRECTIONS = (FWD, REMAT, BWD)

Scope = Tuple[str, ...]


@functools.lru_cache(maxsize=None)  # a window repeats one step's few hundred names
def classify(op_name: Optional[str], root: str) -> Tuple[Optional[Scope], Optional[str]]:
    """``(scope, direction)`` of one ``op_name``; ``(None, None)`` where no
    segment starts with ``root``. The scope runs from the first such segment
    up to, not including, the last segment (the primitive's name) or the
    first nested ``jit``, without the transformations' own segments."""
    if not op_name:
        return None, None
    # a pass that merges two instructions joins their names with ";": the first is the root's
    segments = [s for s in re.split(r"[/()]", op_name.split(";")[0]) if s]
    around = segments[:-1]  # the last is the primitive, and one of them is called transpose
    first = next((i for i, s in enumerate(around) if s.startswith(root)), None)
    if first is None:
        return None, None
    scope = []
    for s in around[first:]:
        if s in NESTED:
            break
        if s not in TRANSFORMS:
            scope.append(s)
    if "rematted_computation" in around:  # first: a recomputed op sits under transpose(jvp()) too
        direction = REMAT
    elif "transpose" in around:
        direction = BWD
    else:
        direction = FWD
    return tuple(scope), direction


def matches(scope: Optional[Scope], scopes: Sequence[str]) -> bool:
    """Whether a scope is one of ``scopes``: an entry with a ``/`` or a ``.``
    is a path and matches as a prefix (``lm.head``, ``lm.block/fold``); any
    other is a segment's name and matches wherever it stands (``permute``).
    An unscoped operation matches nothing."""
    if scope is None:
        return False
    for entry in scopes:
        if "/" in entry or "." in entry:
            want = tuple(entry.split("/"))
            if scope[: len(want)] == want:
                return True
        elif entry in scope:
            return True
    return False


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a varint,
    a slice of ``buf`` for anything with a length or a fixed width."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"wire type {wire} at byte {i}")
            value, i = buf[i: i + size], i + size
        yield key >> 3, value


def _map_entries(plane, field: int):
    """The values of one of an ``XPlane``'s maps (``event_metadata`` is its
    field 4, ``stat_metadata`` its field 5): each entry a message of key 1 and
    value 2."""
    for number, entry in _fields(plane):
        if number == field:
            yield from (v for k, v in _fields(entry) if k == 2)


def op_names(path: str) -> Dict[str, Optional[str]]:
    """Event name -> ``op_name`` over the first device plane of an
    ``.xplane.pb``: ``XSpace.planes`` (1) -> ``XPlane.name`` (2); per
    ``XEventMetadata`` its ``name`` (2) and, among its ``stats`` (5), the
    ``XStat`` whose ``metadata_id`` (1) is ``tf_op``'s, a ``str_value`` (5) or
    a ``ref_value`` (7) into ``stat_metadata``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = {}
    for number, plane in _fields(space):
        if number == 1:
            name = next((bytes(v).decode() for k, v in _fields(plane) if k == 2), "")
            m = xplane.DEVICE_PLANE.match(name)
            if m:
                planes[int(m.group(1))] = plane
    if not planes:
        return {}
    plane = planes[min(planes)]
    stat_names = {}
    for meta in _map_entries(plane, 5):
        fields = dict(_fields(meta))
        stat_names[fields.get(1, 0)] = bytes(fields.get(2, b"")).decode()
    wanted = {i for i, name in stat_names.items() if name == OP_NAME_STAT}
    names: Dict[str, Optional[str]] = {}
    for meta in _map_entries(plane, 4):
        name, op_name = "", None
        for number, value in _fields(meta):
            if number == 2:
                name = bytes(value).decode()
            elif number == 5:
                stat = dict(_fields(value))
                if stat.get(1) in wanted:
                    op_name = bytes(stat[5]).decode() if 5 in stat else stat_names.get(stat.get(7))
        if op_name is not None:
            op_name = op_name.rpartition(":")[0] or op_name  # "<op_name>:<op_type>"
        if name in names and names[name] != op_name:
            op_name = None  # two programs' instructions of one text under two names
        names[name] = op_name
    return names


def read(trace_dir: str) -> List[list]:
    """``[instruction name, start_ns, dur_ns, op_name]`` for every event of the
    first device's ``XLA Ops`` line in the newest trace; ``op_name`` is None
    where the operation's metadata carries none."""
    from jax.profiler import ProfileData

    path = xplane.find_xplane(trace_dir)
    names = op_names(path)
    data = ProfileData.from_file(path)
    planes = sorted((int(m.group(1)), plane) for plane in data.planes
                    for m in [xplane.DEVICE_PLANE.match(plane.name)] if m)
    rows: List[list] = []
    for _, plane in planes[:1]:
        for line in plane.lines:
            if line.name != xplane.OPS_LINE:
                continue
            for ev in line.events:
                name = xplane.OP_NAME.match(ev.name)
                rows.append([name.group(1) if name else ev.name, float(ev.start_ns),
                             float(ev.duration_ns), names.get(ev.name)])
    rows.sort(key=lambda r: r[1])
    return rows


def of_run(run) -> List[list]:
    """The run's table, read once."""
    rows = getattr(run, "op_scopes", None)
    if rows is None:
        rows = run.op_scopes = read(run.trace_dir)
    return rows


def step_programs(rows: Sequence[Sequence], modules: Iterable[Sequence], holds: str,
                  t0: float, t1: float) -> List[Tuple[float, float]]:
    """The ``XLA Modules`` intervals inside which an operation matching
    ``holds`` started inside the window (``module_ms_per_unit``'s rule)."""
    rx = re.compile(holds)
    marks = sorted(r[1] for r in rows if t0 <= r[1] < t1 and rx.search(r[0]))
    found = []
    for _, start, dur in modules:
        i = bisect.bisect_left(marks, start)
        if i < len(marks) and marks[i] < start + dur:
            found.append((start, start + dur))
    return sorted(found)


class Op:
    __slots__ = ("name", "dur", "scope", "direction")

    def __init__(self, name: str, dur: float, scope: Optional[Scope], direction: Optional[str]):
        self.name, self.dur, self.scope, self.direction = name, dur, scope, direction


def step_ops(rows: Sequence[Sequence], programs: Sequence[Tuple[float, float]], root: str,
             renamed: Optional[Dict[str, str]] = None) -> List[Op]:
    """The operations that started inside one of ``programs``, classified;
    containers (``while``, ``conditional``, ``call``) hold other operations of
    the line and are left out, as in ``xplane.seconds_by_name``. ``renamed``
    names nothing in a program that wrote no scope of its own."""
    starts = [a for a, _ in programs]
    patterns = [(re.compile(p), tuple(s.split("/"))) for p, s in (renamed or {}).items()]
    ops: List[Op] = []
    last = {}  # outermost scope -> direction of the last operation under it
    for name, start, dur, op_name in rows:
        i = bisect.bisect_right(starts, start) - 1
        if i < 0 or start >= programs[i][1] or xplane.CONTAINER.match(name):
            continue
        scope, direction = classify(op_name, root)
        if scope is None:
            scope = next((s for rx, s in patterns if rx.search(name)), None)
            direction = None if scope is None else last.get(scope[0])
        else:
            last[scope[0]] = direction
        ops.append(Op(name, dur, scope, direction))
    if not last:  # the program's own names are missing: the kernels' alone would read as a low coverage
        for op in ops:
            op.scope = op.direction = None
    return ops


def of_context(ctx, holds: str, root: str, renamed: Optional[Dict[str, str]] = None) -> List[Op]:
    """What a reducer sums over: the classified operations of the window's
    step programs on the context's device."""
    rows = of_run(ctx.run)
    programs = step_programs(rows, ctx.trace.modules.get(ctx.dev, []), holds, ctx.w0, ctx.w1)
    return step_ops(rows, programs, root, renamed)
