"""Traffic kind ``fit_loop``: back-to-back whole fit jobs.

Set-up makes the data from the seed, builds the DataFrame, and runs ONE fit of
the cell's own job, so the window reuses its compiled program. The window runs
whole fits until ``--seconds`` have passed and ends at the end of the last fit
that completed. ``fit_rows_per_s`` is the rows those fits consumed (the sum of
their minibatch sizes, never steps x batch) over the whole window, host work
included. The output check runs after the window, on the last completed fit's
coefficient and losses.
"""
from __future__ import annotations

import gc
import time

from perfbench import spans


def run(run) -> None:
    system = run.system
    parts = run.parts
    with parts.part("data_s"):
        system.make_data()
    with parts.part("build_s"):
        system.build()
    t = time.perf_counter()
    with parts.part("warm_s"):
        system.fit()
    run.facts["warm_compiles"] = run.watch.between(t, time.perf_counter())
    with parts.part("settle_s"):
        if run.traced:
            spans.wrap_layers(system.LAYER_SPANS, run.span_names, system.note_layout)
            run.start_trace()
        gc.collect()  # the warm-up fit's device cache is gone before the window

    rows_per_job = system.rows_per_job()
    fits = 0
    last = None
    run.span_names.add("fit.call")
    with spans.span("window", run.spans):
        start = time.perf_counter()
        end = start
        while end - start < run.seconds:
            with spans.span("fit.call", run.spans):
                last = system.fit()
            gc.collect()  # or two multi-GB caches meet on the chip; the user pays this too
            end = time.perf_counter()
            fits += 1
    if run.traced:
        run.stop_trace()
        spans.unwrap_layers()
    run.window = (start, end)
    run.attempted, run.failed = fits, 0
    run.end_to_end["fit_rows_per_s"] = fits * rows_per_job / (end - start)
    run.facts.update(fits=fits, steps=fits * system.steps, rows=fits * rows_per_job,
                     layout=system.layout_dims)

    print("fit_seconds " + " ".join(f"{d:.3f}" for d in run.spans["fit.call"]), flush=True)

    # the output check: not set-up, not window
    t = time.perf_counter()
    want = system.reference()
    limits = run.config["check_limits"]
    for name, value in system.compare(last, want).items():
        run.check(name, value, limits[name])
    if run.control:
        for name, value in system.compare(system.reference("bf16"), want).items():
            print(f"control {name} {value!r} limit {limits[name]!r}", flush=True)
    print(f"check_seconds {time.perf_counter() - t:.3f}", flush=True)
