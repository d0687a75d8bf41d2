"""Host seconds per unit (a fit) inside one of the program's own phases, or
several summed: ``span`` is a name or a list of names of ``train.*`` spans
(``perfbench/program_spans.py``); ``self_time`` takes the span's duration minus
the part its child spans cover."""
from perfbench import program_spans


def reduce(ctx, span, self_time=False, per="fits", scale=1.0):
    units = ctx.per(per)
    seconds = program_spans.of_run(ctx.run).seconds(span, ctx.w0, ctx.w1, self_time)
    if seconds is None or not units:
        return None
    return seconds / units * scale
