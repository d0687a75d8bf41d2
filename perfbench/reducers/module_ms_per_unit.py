"""Device milliseconds per unit of the programs (``XLA Modules`` rows) inside
which an operation matching ``holds`` ran: the time of the step program."""
import bisect
import re


def reduce(ctx, holds, per):
    units = ctx.per(per)
    rx = re.compile(holds)
    marks = sorted(s for name, s, _ in ctx.ops() if rx.search(name) and ctx.w0 <= s < ctx.w1)
    modules = ctx.trace.modules.get(ctx.dev, [])
    if not units or not marks or not modules:
        return None
    total_ns = 0.0
    for _, start, dur in modules:
        i = bisect.bisect_left(marks, start)
        if i < len(marks) and marks[i] < start + dur:
            total_ns += dur
    return total_ns / 1e6 / units if total_ns else None
