"""Print the step's device time by the program's own scopes, from a traced run.

    python3 -m perfbench.tools.scopes --workload olmoe_1b_7b.fit_packed4k

reads the trace the last ``--trace 1`` run of that cell left under
``perfbench/.trace/<cell>/`` (or ``--trace-dir``) and prints, for the step
programs inside the window, one ``scope`` line per scope
(``perfbench/op_scopes.py``): milliseconds a step forward, recomputed,
backward and in all, operations a step, the share of the step, and the
instruction kinds that took most of it (``flash_fold_bwd_dkv``, ``fusion``,
...: a name without its number); then the ``unscoped`` operations by
instruction name and, summed, by kind, and a ``step`` line with the sums beside
the step programs' own time. The marker of a step program, the scopes' root and the renamed
kernels are those of one metric's file (``--metric``), read as a traced run
reads them: what the file leaves to the configuration comes from the
``perf`` block of ``--workload``'s. The result line of a traced run gives a
few sums; this gives every row.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys

from perfbench import op_scopes, xplane
from perfbench.manifest import HERE, Manifest
from perfbench.reduce import WINDOW_SPAN, resolved

KIND = re.compile(r"[.\d]+$")


def table(ops, steps: int) -> list:
    """Rows ``{"scope", "fwd", "remat", "bwd", "ms", "ops", "kinds"}`` per step,
    largest first; the unscoped operations are the row whose scope is None."""
    by = {}
    for op in ops:
        row = by.setdefault(op.scope, {"ms": 0.0, "ops": 0, "kinds": {}, **dict.fromkeys(op_scopes.DIRECTIONS, 0.0)})
        ms = op.dur / 1e6 / steps
        row["ms"] += ms
        row["ops"] += 1 / steps
        if op.direction:
            row[op.direction] += ms
        # an unscoped operation keeps its number: it is looked up by it
        kind = KIND.sub("", op.name) if op.scope is not None else op.name
        row["kinds"][kind] = row["kinds"].get(kind, 0.0) + ms
    return sorted(({"scope": scope, **row} for scope, row in by.items()), key=lambda r: -r["ms"])


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--trace-dir", help="another trace of that cell than the last traced run's")
    parser.add_argument("--metric", default="lm_scope_coverage_pct",
                        help="the layer metric whose file names the step programs' marker, the root and the renamed kernels")
    parser.add_argument("--top", type=int, default=4, help="instruction kinds printed per scope")
    parser.add_argument("--unscoped", type=int, default=25, help="unscoped operations printed")
    args = parser.parse_args(argv)
    trace_dir = args.trace_dir or os.path.join(HERE, ".trace", args.workload)
    manifest = Manifest()
    config = manifest.config(manifest.cell(args.workload)["config"])
    params = resolved(manifest.layer_metric(args.metric)["params"], config)
    if params is None:
        print(f"scopes: {args.workload}'s configuration lacks a 'perf' key that {args.metric} reads", file=sys.stderr)
        return 1
    root = params.get("root", "lm.")

    trace = xplane.read_trace(trace_dir, {WINDOW_SPAN})
    window = xplane.host_spans(trace, WINDOW_SPAN)
    if not window or not trace.modules:
        print(f"scopes: no '{WINDOW_SPAN}' span or no device in {trace_dir}", file=sys.stderr)
        return 1
    w0, w1 = window[-1]
    rows = op_scopes.read(trace_dir)
    programs = op_scopes.step_programs(rows, trace.modules[min(trace.modules)], params["holds"], w0, w1)
    ops = op_scopes.step_ops(rows, programs, root, params.get("renamed"))
    if not programs or not ops:
        print(f"scopes: no step program (an operation matching {params['holds']!r}) inside the window", file=sys.stderr)
        return 1
    steps = len(programs)
    program_ms = sum(b - a for a, b in programs) / 1e6 / steps
    found = table(ops, steps)
    total = sum(r["ms"] for r in found)
    for r in found:
        if r["scope"] is None:
            continue
        kinds = sorted(r["kinds"].items(), key=lambda kv: -kv[1])[: args.top]
        print("scope " + json.dumps({
            "scope": "/".join(r["scope"]), **{k: round(r[k], 3) for k in (*op_scopes.DIRECTIONS, "ms")},
            "ops": round(r["ops"], 1), "pct": round(100 * r["ms"] / total, 2),
            "kinds": {k: round(v, 3) for k, v in kinds}}), flush=True)
    rest = next((r for r in found if r["scope"] is None), {"ms": 0.0, "ops": 0, "kinds": {}})
    for name, ms in sorted(rest["kinds"].items(), key=lambda kv: -kv[1])[: args.unscoped]:
        print("unscoped " + json.dumps({"op": name, "ms": round(ms, 3)}), flush=True)
    kinds = {}
    for name, ms in rest["kinds"].items():
        kinds[KIND.sub("", name)] = kinds.get(KIND.sub("", name), 0.0) + ms
    print("unscoped_kinds " + json.dumps({k: round(v, 3) for k, v in sorted(kinds.items(), key=lambda kv: -kv[1])[:8]}),
          flush=True)
    print("step " + json.dumps({
        "steps": steps, "scoped_ms": round(total - rest["ms"], 3), "unscoped_ms": round(rest["ms"], 3),
        "ops_ms": round(total, 3), "program_ms": round(program_ms, 3),
        "coverage_pct": round(100 * (total - rest["ms"]) / total, 2)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
