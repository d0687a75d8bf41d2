"""The share of the device's idle time in the window that no phase of the
program explains: idle while the innermost of the program's spans open on the
host is ``root`` itself (its self time) or none at all (between fits, where
the harness collects). Percent of the window's idle seconds."""
from perfbench import program_spans, xplane

NONE = "_outside_"


def reduce(ctx, root):
    table = program_spans.of_run(ctx.run)
    # the root spans and their children decide it: a deeper span lies in a child
    rows = [[s.name, s.start, s.dur] for s in table.spans
            if s.name == root or (s.parent is not None and s.parent.name == root)]
    if not rows:
        return None
    trace = xplane.Trace(ctx.trace.ops, ctx.trace.modules, rows)
    idle = xplane.idle_by_host_span(trace, ctx.dev, ctx.w0, ctx.w1, none_name=NONE)
    total = sum(idle.values())
    if total <= 0:
        return None
    return 100.0 * (idle.get(root, 0.0) + idle.get(NONE, 0.0)) / total
