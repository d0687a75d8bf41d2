"""A part of the ``solar_open2_250b`` step's share of its roofline: the least
time the chip could take for the operations and bytes
``perfbench/solar_costs.py`` computes from the configuration's shapes and from
the rows the held experts ran (``rows_held`` on the program's ``train.drain``
spans, per step), over that part's device time per step: of the operations
matching ``pattern`` (kernels found by name), or of ALL the step programs'
operations under the program's own ``scopes`` (``perfbench/op_scopes.py``),
forward, recomputed and backward together, so a part that recomputes its
forward pays for it here. Prints which bound it is. A run whose layout names
no delta-rule heads, or whose fits write no held-row count, or whose step has
no such scope or kernel, gives nothing to read."""
from perfbench import op_scopes, solar_costs, xplane
from perfbench.reducers.zaya_roofline_pct import rows_held_per_step


def reduce(ctx, cost, pattern=None, scopes=None, holds=None, per="steps", root="lm.", renamed=None):
    units = ctx.per(per)
    if scopes is None:
        seconds = xplane.matching_seconds(ctx.ops(), pattern, ctx.w0, ctx.w1)
    else:
        ops = op_scopes.of_context(ctx, holds, root, renamed)
        seconds = sum(op.dur for op in ops if op_scopes.matches(op.scope, scopes)) / 1e9
    shapes = ctx.facts.get("layout")
    rows = rows_held_per_step(ctx)
    if not units or seconds <= 0 or not shapes or not ctx.peaks or rows is None or "kda_heads" not in shapes:
        return None
    flops, nbytes = getattr(solar_costs, cost)(rows_held=rows, **shapes)
    t_flops = flops / ctx.peaks["bf16_flops"]
    t_bytes = nbytes / ctx.peaks["hbm_bytes_per_s"]
    bound = "hbm" if t_bytes >= t_flops else "mxu"
    print(f"roofline {cost}: {rows:.0f} held rows a step, {flops:.4g} flop ({t_flops * 1e3:.3f} ms at peak), "
          f"{nbytes:.4g} B ({t_bytes * 1e3:.3f} ms at peak), bound by {bound}, "
          f"measured {seconds / units * 1e3:.3f} ms", flush=True)
    return 100.0 * max(t_flops, t_bytes) / (seconds / units)
