"""``sum(stat) / sum(over)`` of two counts the program wrote on one of its
phases (``train.*`` spans), over the events that start inside the window; None
where no event carries both."""
from perfbench import program_spans


def reduce(ctx, span, stat, over):
    events = program_spans.of_run(ctx.run).started(span, ctx.w0, ctx.w1)
    pairs = [(e.stats[stat], e.stats[over]) for e in events if stat in e.stats and e.stats.get(over)]
    return sum(a for a, _ in pairs) / sum(b for _, b in pairs) if pairs else None
