"""One benchmark run with the program's tracer recording, and its goodput.

    FLINK_ML_TPU_OBSERVABILITY_TRACE=1 python3 -m perfbench.tools.goodput \\
        --workload criteo_lr.fit_resident --seed 11 --trace 0

runs ``perfbench.run`` as it is (same arguments, same output) and then prints
one ``goodput`` line: the category seconds and the productive fraction of
scope ``ml.train`` over the fits after the warm-up fit, from the spans the
program's own tracer recorded (no profiler session is needed for them). The
environment variable is the program's switch, ``observability.trace``; without
it the tracer is off and the line says so.
"""
from __future__ import annotations

import json
import sys

SCOPE = "ml.train"
ROOT_SPAN = "train.fit"


def main(argv=None) -> int:
    from perfbench import run

    rc = run.main(argv)
    from flink_ml_tpu.trace import GoodputReport, tracer

    spans = tracer.recorder.snapshot()
    fits = [s for s in spans if s.name == ROOT_SPAN and s.scope == SCOPE]
    if not tracer.enabled or len(fits) < 2:
        print("goodput " + json.dumps({"tracer_enabled": tracer.enabled, "fits": len(fits)}), flush=True)
        return rc
    warm_end = fits[0].end
    report = GoodputReport.from_spans(s for s in spans if s.start >= warm_end)
    print("goodput " + json.dumps({
        "scope": SCOPE, "fits": len(fits) - 1, "spans": len(spans),
        "dropped": tracer.recorder.dropped,
        "fraction": report.fraction(SCOPE), "wall_s": report.wall_s(SCOPE),
        "seconds": report.totals.get(SCOPE, {}),
    }), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
