"""Benchmark runner + CLI.

Reference: ``Benchmark.java:41`` (``main:129`` parses ``--output-file``, runs each
named config entry :99) and ``BenchmarkUtils.runBenchmark:75`` (instantiate stage
and generators from className/paramMap, execute, measure netRuntime →
``totalTimeMs`` / ``inputThroughput`` / ``outputThroughput``,
BenchmarkUtils.java:132-143). Config schema (benchmark-demo.json):

    {"version": 1,
     "<name>": {"stage": {"className", "paramMap"},
                 "inputData": {"className", "paramMap"},
                 "modelData": {"className", "paramMap"}?}}

Java class names from the reference configs are accepted — they resolve by
simple name through the stage/generator registries.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import time
from typing import Any, Dict, List

from flink_ml_tpu.api.core import Estimator, Model
from flink_ml_tpu.benchmark.datagenerator import GENERATOR_REGISTRY
from flink_ml_tpu.models import STAGE_REGISTRY, get_stage_class

__all__ = ["run_benchmark", "run_config", "main"]


def _resolve_stage_class(class_name: str):
    simple = class_name.rsplit(".", 1)[-1]
    if simple in STAGE_REGISTRY:
        return get_stage_class(simple)
    # fall back to a full dotted python path
    import importlib

    module, _, cls = class_name.rpartition(".")
    return getattr(importlib.import_module(module), cls)


def _resolve_generator_class(class_name: str):
    simple = class_name.rsplit(".", 1)[-1]
    if simple in GENERATOR_REGISTRY:
        return GENERATOR_REGISTRY[simple]
    raise ValueError(f"Unknown data generator {class_name}")


def _instantiate(cls, param_map: Dict[str, Any]):
    obj = cls()
    known = {p.name: p for p in obj.get_param_map()}
    for name, value in (param_map or {}).items():
        if name in known:
            # values arrive as raw JSON — route through the param's decoder
            # (vector params in reference configs are {"values": [...]} dicts)
            obj.set(known[name], known[name].json_decode(value))
        else:
            raise ValueError(
                f"Unknown parameter {name} for {cls.__name__}"
            )
    return obj


def run_benchmark(
    name: str, config: Dict[str, Any], profile_dir: str = None
) -> Dict[str, Any]:
    """Ref BenchmarkUtils.runBenchmark:75.

    With ``profile_dir`` set, the run executes under ``jax.profiler.trace``
    (one subdirectory per benchmark, loadable in TensorBoard/XProf/Perfetto —
    SURVEY §5.1's tracing role) and the result carries the trace path.
    """
    import contextlib

    stage = _instantiate(
        _resolve_stage_class(config["stage"]["className"]),
        config["stage"].get("paramMap", {}),
    )
    input_df = _instantiate(
        _resolve_generator_class(config["inputData"]["className"]),
        config["inputData"].get("paramMap", {}),
    ).generate()
    model_df = None
    if "modelData" in config:
        model_df = _instantiate(
            _resolve_generator_class(config["modelData"]["className"]),
            config["modelData"].get("paramMap", {}),
        ).generate()

    trace = contextlib.nullcontext()
    trace_path = None
    if profile_dir:
        import os

        import jax

        trace_path = os.path.join(profile_dir, name)
        trace = jax.profiler.trace(trace_path)

    fit_ms = 0.0
    with trace:
        start = time.perf_counter()
        if isinstance(stage, Estimator):
            model = stage.fit(input_df)
            fit_ms = (time.perf_counter() - start) * 1000.0
            out = model.transform(input_df)
        else:
            if model_df is not None and isinstance(stage, Model):
                stage.set_model_data(model_df)
            out = stage.transform(input_df)
        if isinstance(out, (list, tuple)):
            out = out[0]
        output_num = len(out)
        elapsed_ms = (time.perf_counter() - start) * 1000.0

    input_num = len(input_df)
    result = {
        "name": name,
        "totalTimeMs": round(elapsed_ms, 3),
        "fitTimeMs": round(fit_ms, 3),
        "transformTimeMs": round(elapsed_ms - fit_ms, 3),
        "inputRecordNum": input_num,
        "inputThroughput": round(input_num * 1000.0 / elapsed_ms, 3),
        "outputRecordNum": output_num,
        "outputThroughput": round(output_num * 1000.0 / elapsed_ms, 3),
    }
    # Per-epoch observability: stages that train through the shared loss
    # machinery expose their per-epoch loss curve.
    history = getattr(stage, "loss_history", None)
    if history:
        result["numEpochs"] = len(history)
        result["finalLoss"] = round(float(history[-1]), 6)
    if trace_path:
        result["profileTrace"] = trace_path
    return result


def _load_config(path: str) -> Dict[str, Any]:
    with open(path) as f:
        text = f.read()
    # the reference configs carry // license comments; strip them like its
    # comment-tolerant jackson parser
    text = re.sub(r"^\s*//.*$", "", text, flags=re.M)
    return json.loads(text)


def run_config(path: str, profile_dir: str = None) -> List[Dict[str, Any]]:
    config = _load_config(path)
    results = []
    for name, entry in config.items():
        if name == "version":
            continue
        try:
            results.append(run_benchmark(name, entry, profile_dir=profile_dir))
        except Exception as e:  # mirror the reference's per-benchmark failure logs
            results.append({"name": name, "error": f"{type(e).__name__}: {e}"})
    return results


def main(argv=None) -> int:
    """Ref Benchmark.main:129."""
    parser = argparse.ArgumentParser(description="flink-ml-tpu benchmark runner")
    parser.add_argument("config", help="benchmark config JSON file")
    parser.add_argument("--output-file", help="write results JSON here")
    parser.add_argument(
        "--profile",
        metavar="DIR",
        help="emit a jax.profiler trace per benchmark under DIR "
        "(view with TensorBoard/XProf or Perfetto)",
    )
    parser.add_argument(
        "--trace",
        metavar="FILE",
        help="record graftscope spans across the run and export Chrome "
        "trace-event JSON to FILE (analyze with tools/traceview.py or "
        "Perfetto; combine with --profile to nest spans in the XLA dump "
        "via observability.trace.xprof)",
    )
    args = parser.parse_args(argv)
    from flink_ml_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    if args.trace:
        from flink_ml_tpu import trace

        with trace.capture() as recorder:
            results = run_config(args.config, profile_dir=args.profile)
        n = recorder.export_chrome_trace(args.trace)
        print(f"graftscope: {n} spans written to {args.trace}", file=sys.stderr)
    else:
        results = run_config(args.config, profile_dir=args.profile)
    payload = json.dumps(results, indent=2)
    if args.output_file:
        with open(args.output_file, "w") as f:
            f.write(payload)
    print(payload)
    failed = [r["name"] for r in results if "error" in r]
    if failed:  # a smoke/CI caller must see benchmark breakage as a failure
        print(f"benchmarks failed: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
