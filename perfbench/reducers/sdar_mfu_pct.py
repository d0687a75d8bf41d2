"""Model FLOP/s utilization of the ``sdar_30b_a3b`` step: the operations a
step needs (``sdar_costs.model``: every matmul at the doubled positions, the
fold at the pairs the block-diffusion mask keeps, the held experts on the rows
they ran, the head over the noised half's rows; recomputation not counted)
over the device time of the step program per step (``module_ms_per_unit``) at
the chip's bfloat16 peak. A run whose layout names no diffusion block, or
whose fits write no held-row count, gives nothing to read."""
from perfbench import sdar_costs
from perfbench.reducers import module_ms_per_unit
from perfbench.reducers.zaya_roofline_pct import rows_held_per_step


def reduce(ctx, holds, per="steps"):
    step_ms = module_ms_per_unit.reduce(ctx, holds, per)
    shapes = ctx.facts.get("layout")
    rows = rows_held_per_step(ctx)
    if not step_ms or not shapes or not ctx.peaks or rows is None or "block" not in shapes:
        return None
    flops, _ = sdar_costs.model(rows_held=rows, **shapes)
    print(f"mfu: {flops:.4g} model flop a step ({flops / ctx.peaks['bf16_flops'] * 1e3:.2f} ms "
          f"at peak), step program {step_ms:.2f} ms", flush=True)
    return 100.0 * flops / ctx.peaks["bf16_flops"] / (step_ms / 1e3)
