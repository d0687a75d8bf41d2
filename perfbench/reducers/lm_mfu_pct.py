"""Model FLOP/s utilization of an LM step: the operations a step needs
(``model`` of the configuration's cost module, ``perf.costs``: every matmul,
attention at the pairs its mask keeps, a recurrence at its own count, held
experts on the rows they ran; recomputation not counted) over the device time
of the step program per step (``module_ms_per_unit``) at the chip's bfloat16
peak. Reads nothing where ``lm_roofline_pct.counted`` finds nothing. The linear
step reads the same way (``step_mfu_pct``: ``kernel_costs.model``)."""
from perfbench.reducers import module_ms_per_unit
from perfbench.reducers.lm_roofline_pct import counted


def reduce(ctx, holds, per="steps"):
    step_ms = module_ms_per_unit.reduce(ctx, holds, per)
    if not step_ms or not ctx.peaks:
        return None
    found = counted(ctx, "model")
    if found is None:
        return None
    (flops, _), on = found
    print(f"mfu {on}, {flops:.4g} model flop a step ({flops / ctx.peaks['bf16_flops'] * 1e3:.2f} ms "
          f"at peak), step program {step_ms:.2f} ms", flush=True)
    return 100.0 * flops / ctx.peaks["bf16_flops"] / (step_ms / 1e3)
