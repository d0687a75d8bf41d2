"""A count the program wrote on its own phases (``train.*`` spans), over the
events that start inside the window: ``mode`` ``sum`` adds ``stat`` up (per
unit if ``per`` is given), ``share`` is the percentage of the events whose
``stat`` is 1."""
from perfbench import program_spans


def reduce(ctx, span, stat, mode="sum", per=None, scale=1.0):
    events = program_spans.of_run(ctx.run).started(span, ctx.w0, ctx.w1)
    values = [e.stats[stat] for e in events if stat in e.stats]
    if not values:
        return None
    if mode == "share":
        return 100.0 * sum(1 for v in values if v == 1) / len(values)
    if mode != "sum":
        raise ValueError(f"unknown mode {mode!r}")
    units = ctx.per(per) if per else 1
    return sum(values) / units * scale if units else None
