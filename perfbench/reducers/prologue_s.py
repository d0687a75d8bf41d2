"""Per call of ``span``: host seconds from its start to the first device
operation matching ``first_op`` that starts inside it; the mean over calls."""
import re

from perfbench import xplane


def reduce(ctx, span, first_op):
    rx = re.compile(first_op)
    starts = [s for name, s, _ in ctx.ops() if rx.search(name)]
    waits = []
    for a, b in xplane.host_spans(ctx.trace, span, ctx.w0, ctx.w1):
        inside = [s for s in starts if a <= s < b]
        if inside:
            waits.append((min(inside) - a) / 1e9)
    return sum(waits) / len(waits) if waits else None
