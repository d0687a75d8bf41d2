"""Model FLOP/s utilization of the ``solar_open2_250b`` step: the operations a
step needs (``solar_costs.model``: every matmul, the delta rule at its
recurrence's count, attention's causal half, the held experts on the rows they
ran; recomputation not counted) over the device time of the step program per
step (``module_ms_per_unit``) at the chip's bfloat16 peak. A run whose layout
names no delta-rule heads, or whose fits write no held-row count, gives
nothing to read."""
from perfbench import solar_costs
from perfbench.reducers import module_ms_per_unit
from perfbench.reducers.zaya_roofline_pct import rows_held_per_step


def reduce(ctx, holds, per="steps"):
    step_ms = module_ms_per_unit.reduce(ctx, holds, per)
    shapes = ctx.facts.get("layout")
    rows = rows_held_per_step(ctx)
    if not step_ms or not shapes or not ctx.peaks or rows is None or "kda_heads" not in shapes:
        return None
    flops, _ = solar_costs.model(rows_held=rows, **shapes)
    print(f"mfu: {flops:.4g} model flop a step ({flops / ctx.peaks['bf16_flops'] * 1e3:.2f} ms "
          f"at peak), step program {step_ms:.2f} ms", flush=True)
    return 100.0 * flops / ctx.peaks["bf16_flops"] / (step_ms / 1e3)
