"""``100 x sum(part) / sum(part + rest)`` of two counts the program wrote on
one of its phases (``train.*`` spans), over the events that start inside the
window; None where no event carries both."""
from perfbench import program_spans


def reduce(ctx, span, part, rest):
    events = program_spans.of_run(ctx.run).started(span, ctx.w0, ctx.w1)
    pairs = [(e.stats[part], e.stats[rest]) for e in events if part in e.stats and rest in e.stats]
    whole = sum(a + b for a, b in pairs)
    return 100.0 * sum(a for a, _ in pairs) / whole if whole else None
