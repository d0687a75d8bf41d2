"""A part of the ``sdar_30b_a3b`` step's share of its roofline: the least time
the chip could take for the operations and bytes ``perfbench/sdar_costs.py``
computes from the configuration's shapes and from the rows the held experts
ran (``rows_held`` on the program's ``train.drain`` spans, per step), over the
device time of the operations matching ``pattern`` per step (kernels found by
name). Prints which bound it is. A run whose layout names no diffusion block,
whose trace holds no such kernel, or whose fits write no held-row count gives
nothing to read."""
from perfbench import sdar_costs, xplane
from perfbench.reducers.zaya_roofline_pct import rows_held_per_step


def reduce(ctx, pattern, cost, per="steps"):
    units = ctx.per(per)
    seconds = xplane.matching_seconds(ctx.ops(), pattern, ctx.w0, ctx.w1)
    shapes = ctx.facts.get("layout")
    rows = rows_held_per_step(ctx)
    if not units or seconds <= 0 or not shapes or not ctx.peaks or rows is None or "block" not in shapes:
        return None
    flops, nbytes = getattr(sdar_costs, cost)(rows_held=rows, **shapes)
    t_flops = flops / ctx.peaks["bf16_flops"]
    t_bytes = nbytes / ctx.peaks["hbm_bytes_per_s"]
    bound = "hbm" if t_bytes >= t_flops else "mxu"
    print(f"roofline {cost}: {rows:.0f} held rows a step, {flops:.4g} flop ({t_flops * 1e3:.3f} ms at peak), "
          f"{nbytes:.4g} B ({t_bytes * 1e3:.3f} ms at peak), bound by {bound}, "
          f"measured {seconds / units * 1e3:.3f} ms", flush=True)
    return 100.0 * max(t_flops, t_bytes) / (seconds / units)
