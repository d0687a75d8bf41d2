"""Model FLOP/s utilization of the ``ouro_2_6b`` step: the operations every
matmul of a step needs (``ouro_costs.model``: every block application of every
pass, the head once a pass; recomputation not counted) over the device time of
the step program per step (``module_ms_per_unit``) at the chip's bfloat16
peak. A run whose layout names no ``loops`` gives nothing to read."""
from perfbench import ouro_costs
from perfbench.reducers import module_ms_per_unit


def reduce(ctx, holds, per="steps"):
    step_ms = module_ms_per_unit.reduce(ctx, holds, per)
    shapes = ctx.facts.get("layout")
    if not step_ms or not shapes or not ctx.peaks or "loops" not in shapes:
        return None
    flops, _ = ouro_costs.model(**shapes)
    print(f"mfu: {flops:.4g} model flop a step ({flops / ctx.peaks['bf16_flops'] * 1e3:.2f} ms "
          f"at peak), step program {step_ms:.2f} ms", flush=True)
    return 100.0 * flops / ctx.peaks["bf16_flops"] / (step_ms / 1e3)
