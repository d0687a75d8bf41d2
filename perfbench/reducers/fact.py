"""A number the run counted itself, or a ratio of two: ``facts[name] /
facts[over]``, times ``scale``."""


def reduce(ctx, name, over=None, scale=1.0):
    value = ctx.facts.get(name)
    if value is None:
        return None
    if over is not None:
        den = ctx.facts.get(over)
        if not den:
            return None
        value = value / den
    return value * scale
