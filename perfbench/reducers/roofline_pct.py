"""A kernel's share of its roofline: the least time the chip could take for
the operations and bytes ``perfbench/kernel_costs.py`` computes from the
shapes, over the kernel's measured device time. Prints which bound it is."""
from perfbench import kernel_costs, xplane


def reduce(ctx, pattern, cost, per):
    units = ctx.per(per)
    seconds = xplane.matching_seconds(ctx.ops(), pattern, ctx.w0, ctx.w1)
    shapes = ctx.facts.get("layout")
    if not units or seconds <= 0 or not shapes or not ctx.peaks:
        return None
    flops, nbytes = getattr(kernel_costs, cost)(**shapes)
    t_flops = flops / ctx.peaks["bf16_flops"]
    t_bytes = nbytes / ctx.peaks["hbm_bytes_per_s"]
    bound = "hbm" if t_bytes >= t_flops else "mxu"
    share = 100.0 * max(t_flops, t_bytes) / (seconds / units)
    print(f"roofline {cost}: {flops:.4g} flop ({t_flops * 1e3:.3f} ms at peak), "
          f"{nbytes:.4g} B ({t_bytes * 1e3:.3f} ms at peak), bound by {bound}, "
          f"measured {seconds / units * 1e3:.3f} ms", flush=True)
    return share
