"""Device idle share of the traced window: 1 - busy over window, in percent."""


def reduce(ctx):
    return 100.0 * (1.0 - ctx.facts["busy_s"] / ctx.facts["window_s"])
