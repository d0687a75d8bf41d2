"""Print the program's fit phases of a traced run, fit by fit.

    python3 -m perfbench.tools.phases --workload criteo_lr.fit_resident

reads the trace the last ``--trace 1`` run of that cell left under
``perfbench/.trace/<cell>/`` (or ``--trace-dir``) and prints one ``fit`` line
per ``train.fit`` span: its seconds, its self time, and the seconds of every
phase inside it by name (``perfbench/program_spans.py``), then their ``mean``.
The result line of a traced run gives the means only; this gives the fits.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from perfbench import program_spans
from perfbench.manifest import HERE

ROOT_SPAN = "train.fit"


def fits_of(table: program_spans.Table) -> list:
    rows = []
    for fit in table.named(ROOT_SPAN):
        row = {ROOT_SPAN: fit.dur / 1e9, "self": fit.self_inside(fit.start, fit.end) / 1e9}
        todo = list(fit.children)
        while todo:
            s = todo.pop()
            row[s.name] = row.get(s.name, 0.0) + s.dur / 1e9
            todo += s.children
        rows.append(row)
    return rows


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload")
    parser.add_argument("--trace-dir")
    args = parser.parse_args(argv)
    if not (args.workload or args.trace_dir):
        parser.error("give --workload or --trace-dir")
    trace_dir = args.trace_dir or os.path.join(HERE, ".trace", args.workload)
    rows = fits_of(program_spans.read(trace_dir))
    if not rows:
        print(f"phases: no {ROOT_SPAN} span in {trace_dir}", file=sys.stderr)
        return 1
    for i, row in enumerate(rows):
        print("fit " + json.dumps({"i": i, **{k: round(v, 6) for k, v in row.items()}}), flush=True)
    names = sorted({k for row in rows for k in row})
    print("mean " + json.dumps({k: round(sum(r.get(k, 0.0) for r in rows) / len(rows), 6)
                                for k in names}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
