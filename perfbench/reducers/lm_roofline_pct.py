"""A part of the LM step's share of its roofline: the least time the chip could
take for the operations and bytes ``perfbench/lm_costs.py`` computes from the
configuration's shapes, over the device time of the operations matching
``pattern`` per step. Prints which bound it is."""
from perfbench import lm_costs, xplane


def reduce(ctx, pattern, cost, per="steps"):
    units = ctx.per(per)
    seconds = xplane.matching_seconds(ctx.ops(), pattern, ctx.w0, ctx.w1)
    shapes = ctx.facts.get("layout")
    if not units or seconds <= 0 or not shapes or not ctx.peaks:
        return None
    flops, nbytes = getattr(lm_costs, cost)(**shapes)
    t_flops = flops / ctx.peaks["bf16_flops"]
    t_bytes = nbytes / ctx.peaks["hbm_bytes_per_s"]
    bound = "hbm" if t_bytes >= t_flops else "mxu"
    print(f"roofline {cost}: {flops:.4g} flop ({t_flops * 1e3:.3f} ms at peak), "
          f"{nbytes:.4g} B ({t_bytes * 1e3:.3f} ms at peak), bound by {bound}, "
          f"measured {seconds / units * 1e3:.3f} ms", flush=True)
    return 100.0 * max(t_flops, t_bytes) / (seconds / units)
