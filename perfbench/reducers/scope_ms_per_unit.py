"""Device milliseconds per unit (a step) of the step programs' operations
under the program's own scopes (``perfbench/op_scopes.py``): ``scopes`` lists
paths (``lm.head``: a prefix) or segment names (``permute``: wherever it
stands), ``direction`` keeps ``fwd``, ``remat`` or ``bwd`` alone. ``holds``
marks the step programs, ``root`` the scopes, ``renamed`` the kernels whose
name the compiler rewrote. None where nothing is scoped (a program without
scopes, the CPU rehearsal) or nothing matches."""
from perfbench import op_scopes


def reduce(ctx, holds, per, scopes, direction=None, root="lm.", renamed=None):
    units = ctx.per(per)
    ops = op_scopes.of_context(ctx, holds, root, renamed)
    total_ns = sum(op.dur for op in ops if op_scopes.matches(op.scope, scopes)
                   and (direction is None or op.direction == direction))
    if not units or not total_ns:
        return None
    return total_ns / 1e6 / units
