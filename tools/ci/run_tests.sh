#!/usr/bin/env bash
# CI test controller (ref tools/ci/java_test_controller.sh): runs the whole
# verification surface on the 8-device virtual CPU mesh.
set -euo pipefail

ci_path="$(cd -- "$(dirname "$0")" >/dev/null 2>&1; pwd -P)"
root_path="$(cd "${ci_path}/../.."; pwd -P)"
cd "$root_path"

export JAX_PLATFORMS=cpu
# Collective-rendezvous abort bound (see tests/conftest.py): transient
# starvation on this few-core box survives, a true stall fails fast.
export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8 --xla_cpu_collective_call_warn_stuck_timeout_seconds=30 --xla_cpu_collective_call_terminate_timeout_seconds=120"
export PYTHONPATH="${root_path}${PYTHONPATH:+:$PYTHONPATH}"

# Static analysis first: an import-layer leak or lock-order cycle should fail
# the build in seconds, not after the full suite has run.
"${ci_path}/run_static_analysis.sh"

echo "=== unit + integration tests (8-device virtual mesh) ==="
python -m pytest tests/ -q

echo "=== multi-chip dryrun compile check ==="
python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

echo "=== benchmark smoke (demo config) ==="
python -m flink_ml_tpu.benchmark.benchmark \
    flink_ml_tpu/benchmark/benchmark-demo.json \
    --output-file /tmp/ci-bench-results.json
python bin/benchmark-results-visualize.py /tmp/ci-bench-results.json \
    --output /tmp/ci-bench-results.png

# Trace smoke: serve a burst with tracing on, export a Chrome trace, run the
# offline analyzer on it. TRACE_ARTIFACT overrides the export path (the CI
# annotation artifact, mirroring GRAFTCHECK_SARIF).
echo "=== trace smoke (graftscope burst + traceview) ==="
trace_artifact="${TRACE_ARTIFACT:-/tmp/ci-trace.json}"
python tools/ci/trace_smoke.py "${trace_artifact}"
python tools/traceview.py "${trace_artifact}"

# Sharded smoke: publish → warm → serve burst → hot swap on a mesh=4 grid,
# bit-exact vs the per-stage reference with zero serving-path compiles, and
# traceview showing the per-shard attribution section on the exported trace.
echo "=== sharded smoke (mesh=4 fan-out + per-shard traceview) ==="
sharded_artifact="${SHARDED_TRACE_ARTIFACT:-/tmp/ci-sharded-trace.json}"
python tools/ci/sharded_smoke.py "${sharded_artifact}"
python tools/traceview.py "${sharded_artifact}" --scope ml.serving | grep -A 3 "shards:"

# Fusion smoke: build and serve BOTH fusion tiers (exact + fast with
# megakernels forced hot), assert zero fast-path compiles after warmup in
# each, exact bit-identical to the per-stage reference, fast inside the
# documented ulp envelope (docs/fusion.md).
echo "=== fusion smoke (exact + fast tiers, zero post-warmup compiles) ==="
python tools/ci/fusion_smoke.py

# Precision smoke: publish f32 + int8 artifacts, serve a burst through every
# precision tier with zero post-warmup compiles, f32 bit-identical to the
# per-stage reference, bf16 inside the documented cross-tier deviation
# envelope — then inject a drift regression mid-burst and prove the
# automatic fallback to the warm f32 plan of the same version with every
# request resolved exactly once (docs/precision.md).
echo "=== precision smoke (f32/bf16/int8 tiers + drift fallback mid-burst) ==="
python tools/ci/precision_smoke.py

# Chaos smoke: a seeded open-loop ramp to ~2.2x saturation with
# serving.dispatch + serving.swap armed against a live server — no deadlock,
# typed-error-only failures with retry context, priority sheds before any
# high-priority deadline miss, at least one adaptive-controller action from
# the live goodput ledger, and recovery to within 10% of the pre-fault
# goodput fraction (docs/serving.md "Load shedding & adaptive control").
# Runs with the flight recorder pointed at a scratch journal: every
# controller action, swap and fault trip must land in the journal exactly
# once, and the armed-swap episode must yield one incident bundle that
# `traceview incident` renders (docs/observability.md).
echo "=== chaos smoke (open-loop ramp past saturation, faults armed) ==="
python tools/ci/chaos_smoke.py

# Restart smoke: serve → hard-kill (os._exit) → a new incarnation over the
# same plan-cache directory resumes with the XLA compile seam POISONED and
# answers every bucket bit-identically from the serialized executables,
# inside the smoke deadline — the zero-compile-resume contract
# (docs/plancache.md).
echo "=== restart smoke (hard-kill -> zero-compile resume from plan cache) ==="
python tools/ci/restart_smoke.py

# Fleet smoke: 3 process-isolated replicas behind the retrying router with
# a running supervisor; one replica hard-killed mid-ramp — every arrival
# resolved exactly once with typed errors only and bounded goodput/p999
# movement, the killed slot respawned and re-admitted with ZERO serving-path
# compiles (plan-cache-warmed — O(load) not O(XLA)), a deliberately
# regressed canary held inside its hard traffic slice and quarantined by the
# live drift score, and the full eject/respawn/readmit/canary decision
# timeline reconstructed from the merged journals by tools/fleetview.py
# (docs/fleet.md).
echo "=== fleet smoke (replica kill -> respawn -> canary quarantine) ==="
python tools/ci/fleet_smoke.py

# Retrieval smoke: a registry-published CandidateIndex served as a fused
# top-K head — concurrent mixed-K burst, hot swap to v-2 mid-burst, every
# request resolved exactly once and bit-exact (ids + scores) against the
# numpy reference for whichever index version served it, per-request K
# honored, and zero fast-path compiles outside the boot/swap warmup windows
# (docs/retrieval.md).
echo "=== retrieval smoke (index hot swap mid-burst, zero-compile top-K) ==="
python tools/ci/retrieval_smoke.py

# Train smoke: sharded-training kill → resume across a real process
# boundary — a sharded KMeans fit_stream at train.mesh=2 hard-killed
# (os._exit) mid-epoch by an armed fault, then resumed at train.mesh=4 from
# the per-shard snapshots and required to land BIT-identical to a clean run
# — the width-invariant resume contract (docs/distributed_training.md).
echo "=== train smoke (sharded fit hard-kill -> cross-width resume) ==="
python tools/ci/train_smoke.py

echo "CI OK"
