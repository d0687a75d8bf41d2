"""Summed device durations of the operations matching ``pattern`` inside the
window, in milliseconds per unit (``steps``, ``batches``, ...)."""
from perfbench import xplane


def reduce(ctx, pattern, per):
    units = ctx.per(per)
    seconds = xplane.matching_seconds(ctx.ops(), pattern, ctx.w0, ctx.w1)
    if not units or seconds <= 0:
        return None
    return seconds * 1e3 / units
