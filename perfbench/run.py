"""Run one cell of the benchmark once.

    python3 -m perfbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell asks
for. Loads, warms every shape the cell's traffic uses (set-up), measures for
``--seconds``, checks what the window produced against the plain reference,
and prints as the LAST line of stdout one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device`` and, traced, ``breakdown``.
Before it: one ``check`` line per number compared, ``setup_parts`` and
``compiles_in_window``.

Without a TPU of a known ``device_kind``, or with another number of chips than
the cell asks for, it exits 2 and prints no result line.
``--rehearse-on-cpu`` drives the same code at toy sizes on the CPU backend to
debug the harness; it never prints a result line.
"""
from __future__ import annotations

import time

_T_FIRST = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

from perfbench import setup_parts  # noqa: E402

_INTERP_S = setup_parts.process_age_s()

from perfbench import allocator  # noqa: E402
from perfbench.manifest import HERE, Manifest, load_module  # noqa: E402

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
#: One cache, never moved: the deployment's, else this fixed path (the path is
#: part of the cache's key).
DEFAULT_CACHE_DIR = os.path.join(HERE, ".jax_cache")
TRACE_DIR = os.path.join(HERE, ".trace")


class Run:
    """What a traffic kind is handed, and what it hands back."""

    def __init__(self, args, manifest, cell, parts, watch, devices, peaks):
        self.args = args
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.traced = bool(args.trace)
        self.toy = bool(args.rehearse_on_cpu)
        self.control = bool(args.control)
        self.manifest = manifest
        self.cell = cell
        self.config = manifest.config(cell["config"])
        if self.toy:
            self.config = {**self.config, **self.config.get("toy", {})}
        self.traffic = manifest.traffic(cell["traffic"])
        if self.toy:
            self.traffic = {**self.traffic, **self.traffic.get("toy", {})}
        self.parts = parts
        self.watch = watch
        self.devices = devices
        self.peaks = peaks
        self.trace_dir = os.path.join(TRACE_DIR, cell["name"])
        # filled by the kind
        self.window = None  # (start, end) on time.perf_counter()
        self.end_to_end = {}  # metric name -> value
        self.attempted = 0
        self.failed = 0
        self.checks = []  # (name, value, limit)
        self.facts = {}  # counts and sizes the reducers read
        self.spans = {}  # span name -> [seconds]
        self.span_names = set()  # host spans to find in the profiler's trace

    def start_trace(self) -> None:
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)

    def stop_trace(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks.append((name, float(value), float(limit)))


def _die(message: str) -> int:
    print(f"perfbench: {message}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse-on-cpu", action="store_true",
                        help="toy sizes on the CPU backend, to debug the harness; never a result")
    parser.add_argument("--control", type=int, choices=(0, 1), default=0,
                        help="also print the check's numbers for the lower-precision control")
    args = parser.parse_args(argv)

    manifest = Manifest()
    bad = manifest.problems()
    if bad:
        return _die("BENCHMARK.json: " + "; ".join(bad))
    cell = manifest.cell(args.workload)
    if args.seconds is None:
        args.seconds = manifest.data["run_seconds"]
    parts = setup_parts.SetupParts(_INTERP_S, _T_FIRST)
    try:  # the deployment's allocator setting, before numpy allocates anything
        malloc = allocator.apply(manifest.config(cell["config"]).get("launch", {}).get("glibc_malloc"))
    except RuntimeError as e:
        return _die(str(e))

    with parts.part("import_s"):
        import jax
        import numpy  # noqa: F401

        traffic_kind = manifest.traffic(cell["traffic"])["kind"]
        kind = load_module("kinds", traffic_kind)
        system = load_module("systems", manifest.config(cell["config"])["system"])
        system.import_program()
        if not os.environ.get(CACHE_DIR_ENV):
            jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
        # keep every program, however quick to compile: JAX's default threshold
        # would drop the sub-second ones and a warm run would build them again
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        watch = setup_parts.CompileWatch()

    with parts.part("backend_init_s"):
        backend = jax.default_backend()
        devices = jax.devices()
        device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
                  "count": len(devices)}
    with open(os.path.join(HERE, "peaks.json"), encoding="utf-8") as f:
        peaks_table = json.load(f)["device_kinds"]
    if args.rehearse_on_cpu:
        if backend != "cpu":
            return _die(f"--rehearse-on-cpu needs the CPU backend, found {backend!r}")
        peaks = None
    else:
        if backend != "tpu":
            return _die(f"no TPU: jax.default_backend() is {backend!r}; the benchmark does not fall back")
        if device["kind"] not in peaks_table:
            return _die(f"unknown device_kind {device['kind']!r}: no published peaks in peaks.json")
        if device["count"] != cell["chips"]:
            return _die(f"cell {cell['name']} asks for {cell['chips']} chip(s), JAX finds {device['count']}")
        peaks = peaks_table[device["kind"]]

    run = Run(args, manifest, cell, parts, watch, devices, peaks)
    run.system = system.create(run.config, run.seed, len(devices))
    kind.run(run)  # set-up, window, check

    t0, t1 = run.window
    record = parts.close(t0)
    record["warm"] = run.facts.get("warm_compiles")
    record["malloc"] = malloc
    in_window = watch.between(t0, t1)
    for name, value, limit in run.checks:
        print("check " + json.dumps({"name": name, "value": value, "limit": limit,
                                     "ok": bool(value <= limit)}), flush=True)
    print("setup_parts " + json.dumps(record), flush=True)
    print("compiles_in_window " + json.dumps(in_window), flush=True)
    correct = all(value <= limit for _, value, limit in run.checks) and bool(run.checks)

    stats = [d.memory_stats() or {} for d in devices]
    device["memory_peak_bytes"] = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    run.facts["memory_peak_bytes"] = device["memory_peak_bytes"]
    run.end_to_end["setup_s"] = record["setup_s"]

    out = {"correct": correct, "attempted": run.attempted, "failed": run.failed}
    if run.traced:
        from perfbench import reduce

        metrics, breakdown, busy_s, window_s = reduce.per_layer(run)
        out["metrics"] = metrics
        device["busy_s"], device["window_s"] = busy_s, window_s
        out["breakdown"] = breakdown
    else:
        out["metrics"] = {
            name: {"value": run.end_to_end[name], "unit": manifest.end_to_end[name]["unit"]}
            for name in manifest.cell_metrics("end_to_end", cell["name"])
        }
    out["device"] = device
    if args.rehearse_on_cpu:
        print("rehearsal " + json.dumps(out), flush=True)
        print("perfbench: cpu rehearsal finished - this is not a result", flush=True)
        return 0
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
