"""A part of the ``ouro_2_6b`` step's share of its roofline: the least time the
chip could take for the operations and bytes ``perfbench/ouro_costs.py``
computes from the configuration's shapes (over ``layers x loops`` block
applications), over the device time of the operations matching ``pattern`` per
step. Prints which bound it is. A run whose layout names no ``loops`` gives
nothing to read."""
from perfbench import ouro_costs, xplane


def reduce(ctx, pattern, cost, per="steps"):
    units = ctx.per(per)
    seconds = xplane.matching_seconds(ctx.ops(), pattern, ctx.w0, ctx.w1)
    shapes = ctx.facts.get("layout")
    if not units or seconds <= 0 or not shapes or not ctx.peaks or "loops" not in shapes:
        return None
    flops, nbytes = getattr(ouro_costs, cost)(**shapes)
    t_flops = flops / ctx.peaks["bf16_flops"]
    t_bytes = nbytes / ctx.peaks["hbm_bytes_per_s"]
    bound = "hbm" if t_bytes >= t_flops else "mxu"
    print(f"roofline {cost}: {shapes['layers'] * shapes['loops']} block applications a step, {flops:.4g} flop "
          f"({t_flops * 1e3:.3f} ms at peak), {nbytes:.4g} B ({t_bytes * 1e3:.3f} ms at peak), bound by {bound}, "
          f"measured {seconds / units * 1e3:.3f} ms", flush=True)
    return 100.0 * max(t_flops, t_bytes) / (seconds / units)
