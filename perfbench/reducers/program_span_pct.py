"""``100 x sum(stat) / sum(over)`` of two counts the program wrote on one of
its phases (``train.*`` spans), ``stat`` a part of ``over``, over the events
that start inside the window; None where no event carries both."""
from perfbench.reducers import program_span_ratio


def reduce(ctx, span, stat, over):
    ratio = program_span_ratio.reduce(ctx, span, stat, over)
    return None if ratio is None else 100.0 * ratio
