"""From a traced run to the cell's per-layer metrics and its breakdown.

The window is the host span ``window`` that every traffic kind writes around
what it measures. Each per-layer metric is read by the reducer its own file
names (``layer_metrics/<name>.json`` -> ``reducers/<reducer>.py``); a reducer
that finds nothing to read returns None and the metric is left out.

A metric is one quantity, whatever configuration runs it. What differs between
configurations (the operation that marks the step program, the kernels the
compiler renames, the name of a part's cost function) stands in the
configuration's ``perf`` block, and a metric's file points at it: a parameter
``{"perf": "step_holds"}`` is ``config["perf"]["step_holds"]``, ``{"perf":
"cost_of.attn"}`` descends. A configuration that lacks the key has nothing to
read there, and the metric is left out of its line.
"""
from __future__ import annotations

from perfbench import xplane
from perfbench.manifest import load_module

WINDOW_SPAN = "window"


class Context:
    """What a reducer reads."""

    def __init__(self, run, trace, w0, w1):
        self.run = run
        self.facts = run.facts
        self.peaks = run.peaks
        self.config = run.config
        self.trace = trace
        self.w0, self.w1 = w0, w1
        self.devices = sorted(trace.ops)
        self.dev = self.devices[0] if self.devices else None
        # spans by name, in seconds: the benchmark's and the program's host-clock
        # spans, and the annotation spans found in the profiler's trace
        self.spans = {k: list(v) for k, v in run.spans.items()}
        for name, start, dur in trace.host:
            if w0 <= start < w1 and name not in run.spans:
                self.spans.setdefault(name, []).append(dur / 1e9)

    def ops(self, dev=None):
        return self.trace.ops.get(self.dev if dev is None else dev, [])

    def per(self, unit: str):
        n = self.facts.get(unit)
        return n if n else None


def resolved(params: dict, config: dict):
    """``params`` with every ``{"perf": "<key>"}`` replaced by what the
    configuration's ``perf`` block holds under that key; None where it holds
    nothing."""
    out = {}
    for name, value in params.items():
        if isinstance(value, dict) and set(value) == {"perf"}:
            key, value = value["perf"], config.get("perf", {})
            for part in key.split("."):
                value = value.get(part) if isinstance(value, dict) else None
            if value is None:
                return None
        out[name] = value
    return out


def metric_value(ctx, spec: dict):
    """What the reducer a metric's file names reads of this run, or None."""
    params = resolved(spec.get("params", {}), ctx.config)
    if params is None:
        return None
    return load_module("reducers", spec["reducer"]).reduce(ctx, **params)


def _top(seconds_by: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(seconds_by.items(), key=lambda kv: -kv[1])[:n]]


def per_layer(run):
    trace = xplane.read_trace(run.trace_dir, run.span_names | {WINDOW_SPAN})
    window = xplane.host_spans(trace, WINDOW_SPAN)
    if not window:
        raise RuntimeError("the trace holds no 'window' span")
    w0, w1 = window[-1]
    ctx = Context(run, trace, w0, w1)
    busy = xplane.busy_seconds(trace, w0, w1)
    if not busy or max(busy.values()) <= 0:
        if not run.toy:
            raise RuntimeError("no operation ran on a device inside the traced window")
        busy = {0: 0.0}  # the CPU rehearsal's trace has no device plane
    busy_s = sum(busy.values()) / len(busy)
    window_s = (w1 - w0) / 1e9
    run.facts.update(busy_s=busy_s, window_s=window_s)

    metrics = {}
    for name in run.manifest.cell_metrics("per_layer", run.cell["name"]):
        spec = run.manifest.layer_metric(name)
        value = metric_value(ctx, spec)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": spec["unit"]}

    by_name = xplane.seconds_by_name(ctx.ops(), w0, w1)
    idle = xplane.idle_by_host_span(trace, ctx.dev, w0, w1) if ctx.dev is not None else {}
    breakdown = {"device_ops": _top(by_name), "idle_gaps": _top(idle)}
    return metrics, breakdown, busy_s, window_s
