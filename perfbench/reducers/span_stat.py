"""A statistic of one span's durations: ``mean``, ``median`` or ``sum``."""
import statistics


def reduce(ctx, span, stat="mean", scale=1.0):
    values = sorted(ctx.spans.get(span, []))
    if not values:
        return None
    if stat == "mean":
        out = statistics.fmean(values)
    elif stat == "median":
        out = statistics.median(values)
    elif stat == "sum":
        out = sum(values)
    else:
        raise ValueError(f"unknown stat {stat!r}")
    return out * scale
