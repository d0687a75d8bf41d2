"""A part of an LM step's share of its roofline: the least time the chip could
take for the operations and bytes the configuration's cost module computes
(``perfbench/<costs>.py::<cost>``; ``costs`` is the configuration's
``perf.costs``) from the run's shapes and, where the function takes
``rows_held``, from the rows the held experts ran (on the program's
``train.drain`` spans, per step), over that part's device time per step: of
the operations matching ``pattern`` (kernels found by name), or of ALL the
step programs' operations under the program's own ``scopes``
(``perfbench/op_scopes.py``), forward, recomputed and backward together, so a
part that recomputes its forward pays for it here. Prints which bound it is.
A configuration that names no cost module, a run whose layout lacks a shape
the function names, whose fits write no held-row count where one is taken, or
whose step has no such kernel or scope, gives nothing to read."""
import inspect

from perfbench import op_scopes, program_spans, xplane
from perfbench.manifest import load_module


def rows_held_per_step(ctx):
    """Rows the held experts ran per optimizer step, all layers together, over
    the fits that drained inside the window; None where no fit says."""
    events = program_spans.of_run(ctx.run).started("train.drain", ctx.w0, ctx.w1)
    pairs = [(e.stats["rows_held"], e.stats["steps"]) for e in events
             if "rows_held" in e.stats and e.stats.get("steps")]
    if not pairs:
        return None
    return sum(r for r, _ in pairs) / sum(s for _, s in pairs)


def counted(ctx, cost):
    """``(flops, bytes)`` of the configuration's ``cost`` function at the
    run's shapes, and what it was counted on, as text; None where the
    configuration, the shapes or the held-row count do not give it."""
    costs = ctx.config.get("perf", {}).get("costs")
    shapes = ctx.facts.get("layout")
    if not costs or not shapes:
        return None
    function = getattr(load_module("", costs), cost)
    signature = inspect.signature(function)
    given, on = dict(shapes), f"{costs}.{cost}"
    if "rows_held" in signature.parameters:
        given["rows_held"] = rows_held_per_step(ctx)
        if given["rows_held"] is None:
            return None
        on += f": {given['rows_held']:.0f} held rows a step"
    try:
        signature.bind(**given)
    except TypeError:  # another configuration's layout: a shape the function names is missing
        return None
    return function(**given), on


def reduce(ctx, cost, pattern=None, scopes=None, holds=None, per="steps", root="lm.", renamed=None):
    units = ctx.per(per)
    if scopes is None:
        seconds = xplane.matching_seconds(ctx.ops(), pattern, ctx.w0, ctx.w1)
    else:
        ops = op_scopes.of_context(ctx, holds, root, renamed)
        seconds = sum(op.dur for op in ops if op_scopes.matches(op.scope, scopes)) / 1e9
    if not units or seconds <= 0 or not ctx.peaks:
        return None
    found = counted(ctx, cost)
    if found is None:
        return None
    (flops, nbytes), on = found
    t_flops = flops / ctx.peaks["bf16_flops"]
    t_bytes = nbytes / ctx.peaks["hbm_bytes_per_s"]
    bound = "hbm" if t_bytes >= t_flops else "mxu"
    print(f"roofline {on}, {flops:.4g} flop ({t_flops * 1e3:.3f} ms at peak), "
          f"{nbytes:.4g} B ({t_bytes * 1e3:.3f} ms at peak), bound by {bound}, "
          f"measured {seconds / units * 1e3:.3f} ms", flush=True)
    return 100.0 * max(t_flops, t_bytes) / (seconds / units)
