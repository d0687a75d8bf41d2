"""Milliseconds per unit of collective operations during which no other
operation ran on that device (the worst device)."""
from perfbench import xplane


def reduce(ctx, per):
    units = ctx.per(per)
    if not units:
        return None
    rows = [xplane.exposed_seconds(ctx.ops(d), ctx.w0, ctx.w1) for d in ctx.devices]
    has = any(xplane.COLLECTIVE.match(r[0]) for d in ctx.devices for r in ctx.ops(d))
    return max(rows) * 1e3 / units if has else None
