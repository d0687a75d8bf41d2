"""The share of the step programs' device time that runs under one of the
program's own scopes (``perfbench/op_scopes.py``): percent of the summed
durations of their operations, containers left out. A traced run that reads
low here served a step compiled without scopes (the compile cache's key
leaves metadata out) or lost them to the compiler: no scope metric of that
run is evidence. None where nothing is scoped at all."""
from perfbench import op_scopes


def reduce(ctx, holds, root="lm.", renamed=None):
    ops = op_scopes.of_context(ctx, holds, root, renamed)
    scoped = sum(op.dur for op in ops if op.scope is not None)
    if not scoped:
        return None
    return 100.0 * scoped / sum(op.dur for op in ops)
