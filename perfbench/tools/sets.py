"""Run a list of benchmark runs one after another, each in a process of its
own (this parent never touches JAX, so each child gets the chip), keep every
run's output under ``chiprun_out/<tag>/`` and print one summary line per run.

    python3 -m perfbench.tools.sets --tag study --seconds 20 \\
        criteo_lr.fit_resident,11,0 criteo_lr.fit_resident,12,0,control ...

A run is ``workload,seed,trace[,control][,s=<seconds>]``. The summary holds ``setup_parts``,
the result line's metrics, the checks and the control's numbers: what the
set-up study and the bounds are read from. ``--spread`` prints, per workload
and metric, the quartile spread of the untraced runs as the contract defines
it (``statistics.quantiles(values, n=4)``, over the median).
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time


def summarize(text: str) -> dict:
    out = {"checks": {}, "control": {}}
    lines = text.strip().splitlines()
    for line in lines:
        if line.startswith("setup_parts "):
            out["setup_parts"] = json.loads(line.split(" ", 1)[1])
        elif line.startswith("compiles_in_window "):
            out["compiles_in_window"] = json.loads(line.split(" ", 1)[1])["programs"]
        elif line.startswith("check "):
            c = json.loads(line.split(" ", 1)[1])
            out["checks"][c["name"]] = c["value"]
        elif line.startswith("control "):
            _, name, value = line.split()[:3]
            out["control"][name] = float(value)
        elif line.startswith("check_seconds "):
            out["check_seconds"] = float(line.split()[1])
        elif line.startswith("fit_seconds "):
            out["fit_seconds"] = [float(x) for x in line.split()[1:]]
        elif line.startswith("facts "):
            out["facts"] = json.loads(line.split(" ", 1)[1])
    if lines and lines[-1].startswith("{"):
        result = json.loads(lines[-1])
        out["correct"] = result["correct"]
        out["attempted"], out["failed"] = result["attempted"], result["failed"]
        out["metrics"] = {k: v["value"] for k, v in result["metrics"].items()}
        out["memory_peak_bytes"] = result["device"].get("memory_peak_bytes")
        if "busy_s" in result["device"]:
            out["idle_share"] = 1 - result["device"]["busy_s"] / result["device"]["window_s"]
    return out


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--tag", default="sets")
    parser.add_argument("--seconds", default=None)
    parser.add_argument("--spread", action="store_true")
    parser.add_argument("--run-timeout", type=float, default=600.0,
                        help="seconds one run may take before it is killed")
    parser.add_argument("runs", nargs="+")
    args = parser.parse_args(argv)
    out_dir = os.path.join("chiprun_out", args.tag)
    os.makedirs(out_dir, exist_ok=True)
    rows = []
    for i, spec in enumerate(args.runs):
        workload, seed, trace, *flags = spec.split(",")
        cmd = [sys.executable, "-m", "perfbench.run", "--workload", workload,
               "--seed", seed, "--trace", trace]
        seconds = next((f[2:] for f in flags if f.startswith("s=")), args.seconds)
        if seconds:
            cmd += ["--seconds", seconds]
        if "control" in flags:
            cmd += ["--control", "1"]
        t = time.perf_counter()
        try:
            proc = subprocess.run(cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                                  text=True, timeout=args.run_timeout)
        except subprocess.TimeoutExpired as e:  # the child is killed; the list goes on
            out = e.stdout.decode() if isinstance(e.stdout, bytes) else (e.stdout or "")
            proc = subprocess.CompletedProcess(cmd, 124, out + "\nsets: run timed out\n")
        wall = time.perf_counter() - t
        with open(os.path.join(out_dir, f"{i:02d}_{workload}_{seed}_{trace}.txt"), "w") as f:
            f.write(proc.stdout)
        row = {"i": i, "workload": workload, "seed": int(seed), "trace": int(trace), "flags": flags,
               "rc": proc.returncode, "wall_s": round(wall, 2)}
        try:
            row.update(summarize(proc.stdout))
        except (ValueError, KeyError) as e:
            row["unreadable"] = repr(e)
        if proc.returncode != 0 or "metrics" not in row:
            row["tail"] = proc.stdout[-1500:]
        rows.append(row)
        print("run " + json.dumps(row), flush=True)
    if args.spread:
        by = {}
        for row in rows:
            if row["trace"] == 0 and "metrics" in row:
                for k, v in row["metrics"].items():
                    by.setdefault((row["workload"], k), []).append(v)
        for (workload, metric), values in sorted(by.items()):
            if len(values) >= 2:
                print("spread " + json.dumps({
                    "workload": workload, "metric": metric, "n": len(values),
                    "median": statistics.median(values), "spread": spread(values),
                    "min": min(values), "max": max(values)}), flush=True)
    with open(os.path.join(out_dir, "summary.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0 if all(r["rc"] == 0 for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
