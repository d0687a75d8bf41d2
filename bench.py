"""Benchmark entry point — prints ONE JSON line (headline) and writes
``BENCH_DETAIL.json`` with the full suite.

Headline: the BASELINE.json north-star — LogisticRegression steady-state
training throughput (rows consumed by the fused SGD loop per second once the
dataset is HBM-resident) vs a same-semantics single-host CPU numpy baseline
measured in-process (the stand-in for the reference's CPU-TaskManager
cluster; the reference publishes no absolute LR numbers, BASELINE.md).

Suite (all on the real chip, reference harness semantics — wall-clock
throughput like ``BenchmarkUtils.java:132-143``):

- ``logreg``: a Criteo-class dense shape (250k x 256 f32) resident in HBM
  (DeviceDataCache), SGD driven directly. Steady-state rows/s comes from
  differencing two iteration counts — (t(I2) - t(I1)) / (I2 - I1) isolates
  the per-step cost, exactly how per-row cost amortizes over a 1B-row
  stream. One end-to-end Estimator.fit (including ingest) is also recorded.
  The CPU baseline is measured the same marginal way (data already in RAM).
- ``kmeans``: the reference demo config at 10x shape (100k x 10, k=2;
  ``benchmark-demo.json`` KMeans-1 is 10k). Per-iteration time via the same
  differencing; ``vs_reference_cpu`` anchors end-to-end rows/s against the
  reference's illustrative 1,399 rows/s CPU output for the 10k config
  (flink-ml-benchmark/README.md:86-113) — the only reference-anchored number
  that exists.
- ``mlp``: MXU-bound MLP forward inference at serving shapes (batch 4096,
  256-512-512-8, bf16), timed with pipelined dispatch (issue all steps, block
  once) so per-call completion latency is amortized as it would be in a
  serving loop.

Methodology: every workload warms up once so XLA compilation (the analogue
of the reference's one-time JVM/job-graph startup) never lands in a
steady-state metric; timed numbers are medians of 3 runs.
"""
import json
import sys
import time

import numpy as np

_PEAK_FLOPS = {
    # bf16 dense peak per chip; used for MFU. f32 workloads are reported
    # against the same number (conservative).
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v4": 275e12,
    "TPU v6 lite": 918e12,
}

_PEAK_HBM_GBPS = {
    # HBM bandwidth per chip — the roofline denominator for the
    # bandwidth-bound workloads (dense LR, KMeans).
    "TPU v5 lite": 819.0,
    "TPU v5e": 819.0,
    "TPU v4": 1228.0,
    "TPU v6 lite": 1640.0,
}


def _marginal_time(total, r1=5, r2=45, samples=3):
    """Median-of-``samples`` marginal cost via rep differencing: ``total(r)``
    runs r reps and returns its wall time (with a scalar fetch as the
    completion barrier). The rep counts and the differencing were calibrated
    on a retired setup whose fixed overhead per measurement was large and
    variable, not re-measured; ROADMAP S1 replaces the protocol.
    Shared by every kernel-grade timing in this file — the protocol must not
    drift between entries."""
    total(2)  # warm-up: compile
    times = [max((total(r2) - total(r1)) / (r2 - r1), 1e-9) for _ in range(samples)]
    return sorted(times)[len(times) // 2]


def _median_time(fn, repeats=5):
    # median-of-5: calibrated on a retired, time-shared setup where single
    # measurements swung 2-4x under contention; not re-measured.
    fn()  # warm-up: XLA compile
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def _median_time_spread(fn, repeats=5):
    """Same protocol as :func:`_median_time`, but also returns the min/max
    window so readers of the JSON see the box's noise next to the headline."""
    fn()  # warm-up: XLA compile
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    times.sort()
    median = times[len(times) // 2]
    spread = {
        "min_s": round(times[0], 4),
        "median_s": round(median, 4),
        "max_s": round(times[-1], 4),
        "repeats": repeats,
    }
    return median, spread


def cpu_env() -> dict:
    """The baseline environment record: which CPU, how many cores, how loaded.
    The reference fixes its measurement procedure (BenchmarkUtils.java:132-143);
    this pins the other half — what the baseline actually ran on."""
    model = "unknown"
    try:
        for line in open("/proc/cpuinfo"):
            if line.startswith("model name"):
                model = line.split(":", 1)[1].strip()
                break
    except OSError:
        pass
    try:
        load1 = float(open("/proc/loadavg").read().split()[0])
    except (OSError, ValueError):
        load1 = None
    import os

    return {"cpu_model": model, "cpu_cores": os.cpu_count(), "loadavg_1m": load1}


def pinned_baseline(step_fn, rows_per_call: int, n_runs: int = 5, calls_per_run: int = 3):
    """Best-of-N CPU-baseline protocol: ``n_runs`` independent measurements
    of ``calls_per_run`` steps each on a shared, noisy box; the HEADLINE
    divides by the STRONGEST run (the most conservative ratio for us), and
    the spread is recorded so readers see the noise instead of guessing.
    Returns (best_rows_per_sec, spread_dict)."""
    step_fn()  # warm caches
    rates = []
    for _ in range(n_runs):
        t0 = time.perf_counter()
        for _ in range(calls_per_run):
            step_fn()
        rates.append(calls_per_run * rows_per_call / (time.perf_counter() - t0))
    best = max(rates)
    spread = {
        "best_rows_per_sec": round(best, 1),
        "min_rows_per_sec": round(min(rates), 1),
        "median_rows_per_sec": round(sorted(rates)[len(rates) // 2], 1),
        "n_runs": n_runs,
        "env": cpu_env(),
    }
    return best, spread


def bench_logreg(peak_flops, peak_gbps):
    from flink_ml_tpu.api.dataframe import DataFrame
    from flink_ml_tpu.iteration import DeviceDataCache
    from flink_ml_tpu.models.classification.logistic_regression import LogisticRegression
    from flink_ml_tpu.ops import SGD, BinaryLogisticLoss
    from flink_ml_tpu.parallel.mesh import get_mesh_context

    n, d = 250_000, 256
    batch = 65_536
    i1, i2 = 100, 2100
    rng = np.random.default_rng(0)
    X = rng.standard_normal(size=(n, d), dtype=np.float32)
    w_true = rng.standard_normal(size=d, dtype=np.float32)
    y = (X @ w_true + 0.5 * rng.standard_normal(size=n, dtype=np.float32) > 0).astype(
        np.float32
    )

    # Steady state: dataset resident in HBM (DeviceDataCache), optimizer driven
    # directly; differencing two iteration counts isolates the per-step cost.
    ctx = get_mesh_context()
    cache = DeviceDataCache(
        {"features": X, "labels": y, "weights": np.ones(n, np.float32)}, ctx=ctx
    )

    def steps(iters):
        SGD(max_iter=iters, global_batch_size=batch, tol=0.0).optimize(
            np.zeros(d, np.float32), cache, BinaryLogisticLoss.INSTANCE
        )

    t1 = _median_time(lambda: steps(i1))
    t2 = _median_time(lambda: steps(i2))
    step_s = max((t2 - t1) / (i2 - i1), 1e-9)
    flops_per_step = 4.0 * batch * d  # fwd X@coef (2BD) + grad X.T@mult (2BD)

    # End-to-end: one Estimator.fit including host->device ingest; recorded
    # next to the steady-state number, not used as the headline.
    df = DataFrame.from_dict({"features": X, "label": y.astype(np.float64)})
    t0 = time.perf_counter()
    LogisticRegression().set_max_iter(i1).set_global_batch_size(batch).set_tol(0.0).fit(df)
    e2e = time.perf_counter() - t0

    # Roofline: this step is HBM-bound, not FLOP-bound — X is read twice
    # (forward X@coef, gradient X.T@mult; everything else is O(d) or O(B)).
    bytes_per_step = 2.0 * batch * d * 4
    out = {
        "name": "logreg_fit_250k_d256_b65536",
        "steady_rows_per_sec": round(batch / step_s, 1),
        "step_time_us": round(step_s * 1e6, 1),
        "achieved_gflops": round(flops_per_step / step_s / 1e9, 1),
        "achieved_gbps": round(bytes_per_step / step_s / 1e9, 1),
        "peak_hbm_gbps": peak_gbps,
        "e2e_fit_time_s_100_iters": round(e2e, 3),
        "e2e_note": "includes host->device ingest",
    }
    if peak_gbps:
        out["hbm_utilization"] = round(bytes_per_step / step_s / 1e9 / peak_gbps, 3)
    if peak_flops:
        out["mfu"] = round(flops_per_step / step_s / peak_flops, 6)
    return out, (X, y)


def bench_logreg_cpu_baseline(X, y, batch=65_536):
    """Same minibatch-SGD semantics in numpy on the host CPU (the stand-in for
    the reference's CPU TaskManager), measured with the pinned best-of-N
    protocol (the same dataset, already resident in RAM)."""
    n, d = X.shape
    coef = np.zeros(d, np.float32)
    offset = 0

    def step():
        nonlocal coef, offset
        Xb, yb = X[offset : offset + batch], y[offset : offset + batch]
        ys = 2.0 * yb - 1.0
        z = (Xb @ coef) * ys
        mult = -ys / (1.0 + np.exp(z))
        grad = Xb.T @ mult
        coef = coef - 0.1 / len(Xb) * grad
        offset = 0 if offset + batch >= n else offset + batch

    return pinned_baseline(step, batch, n_runs=5, calls_per_run=10)


def bench_logreg_sparse(peak_flops, peak_gbps=None):
    """The actual Criteo shape: wide sparse features in padded-CSR layout.

    2^22-dim coefficient, 39 nnz/row (Criteo has 39 feature fields) — a batch
    that would be 1 TB/step densified streams as [B, 40] index/value pairs.
    Steady-state rows/s via the same two-point differencing as the dense
    benchmark.
    """
    from flink_ml_tpu.iteration import DeviceDataCache
    from flink_ml_tpu.ops import SGD, BinaryLogisticLoss
    from flink_ml_tpu.parallel.mesh import get_mesh_context

    n, d, nnz = 250_000, 1 << 22, 39
    K = 40  # lane-padded row width
    batch = 65_536
    i1, i2 = 50, 550
    rng = np.random.default_rng(1)
    idx = rng.integers(0, d, size=(n, K), dtype=np.int32)  # hash-style indices
    vals = np.ones((n, K), np.float32)
    vals[:, nnz:] = 0.0  # padding slots
    w_true = (rng.random(d) < 0.001) * rng.standard_normal(d).astype(np.float32)
    y = (np.sum(vals * w_true[idx], axis=1) > 0).astype(np.float32)

    ctx = get_mesh_context()
    cache = DeviceDataCache(
        {"indices": idx, "values": vals, "labels": y, "weights": np.ones(n, np.float32)},
        ctx=ctx,
    )

    def steps(iters, premat="auto"):
        sgd = SGD(
            max_iter=iters, global_batch_size=batch, tol=0.0,
            learning_rate=0.5, onehot_premat=premat,
        )
        sgd.optimize(np.zeros(d, np.float32), cache, BinaryLogisticLoss.INSTANCE)
        return sgd

    premat_active = steps(2).onehot_premat_active  # compile + gate decision
    t1 = _median_time(lambda: steps(i1))
    t2 = _median_time(lambda: steps(i2))
    step_s = max((t2 - t1) / (i2 - i1), 1e-9)
    # The build-form (rebuild-one-hots-every-step) time, for the record:
    # what the same fit costs when the premat one-hots don't fit HBM
    # (many-window/streamed regime) — and the continuity column against
    # rounds 3-4, which measured this form as the headline.
    if premat_active:
        steps(2, premat="off")
        b1 = _median_time(lambda: steps(i1, premat="off"))
        b2 = _median_time(lambda: steps(i2, premat="off"))
        build_step_s = max((b2 - b1) / (i2 - i1), 1e-9)
    else:
        build_step_s = step_s
    # fwd gather-dot (2*B*K) + grad scatter (2*B*K), counting madds like dense
    flops_per_step = 4.0 * batch * K

    # Same-semantics CPU step (gather-dot, np.add.at scatter, full coefficient
    # update, batch-offset cycling), measured with the pinned best-of-N
    # protocol. The TPU side auto-selects the one-hot matmul path
    # (linalg/onehot_sparse.py, Pallas crossings) — the step is
    # crossing-bound; docs/benchmarks.md has the roofline and the multi-chip
    # scaling artifact.
    coef = np.zeros(d, np.float32)
    offset = 0

    def cpu_step():
        nonlocal coef, offset
        Xb_i, Xb_v, yb = (
            idx[offset : offset + batch],
            vals[offset : offset + batch],
            y[offset : offset + batch],
        )
        ys = 2.0 * yb - 1.0
        z = np.sum(Xb_v * coef[Xb_i], axis=1) * ys
        mult = -ys / (1.0 + np.exp(z))
        grad = np.zeros(d, np.float32)
        np.add.at(grad, Xb_i.ravel(), (Xb_v * mult[:, None]).ravel())
        coef = coef - (0.5 / len(yb)) * grad
        offset = 0 if offset + batch >= n else offset + batch

    cpu_best, cpu_spread = pinned_baseline(cpu_step, batch, n_runs=5, calls_per_run=3)

    out = {
        "name": "logreg_sparse_fit_250k_d4M_nnz39_b65536",
        "steady_rows_per_sec": round(batch / step_s, 1),
        "step_time_us": round(step_s * 1e6, 1),
        "achieved_gflops": round(flops_per_step / step_s / 1e9, 2),
        "onehot_premat_active": premat_active,
        "build_form_step_time_us": round(build_step_s * 1e6, 1),
        "vs_build_form": round(build_step_s / step_s, 2),
        "cpu_baseline_rows_per_sec": round(cpu_best, 1),
        "cpu_baseline_spread": cpu_spread,
        "vs_cpu_baseline": round((batch / step_s) / cpu_best, 2),
        "note": "padded-CSR; densified this batch would be ~1 TB/step; "
        "ratio divides by the STRONGEST of 5 baseline runs; the headline "
        "step runs the premat (precomputed-one-hot) kernels when "
        "onehot_premat_active, with build_form_step_time_us the "
        "rebuild-every-step form rounds 3-4 measured",
    }
    if peak_flops:
        out["mfu"] = round(flops_per_step / step_s / peak_flops, 8)
    # The crossing roofline: what the "remaining cost is crossing-bound"
    # claim actually means, in numbers (skipped when auto picked scatter).
    memo = getattr(cache, "_onehot_memo", None)
    if memo is not None and memo[1] is not None:
        from flink_ml_tpu.parallel.mesh import is_tpu_backend

        out.update(
            _crossing_roofline(
                memo[1], out["step_time_us"], peak_flops, peak_gbps,
                use_pallas=is_tpu_backend(ctx.mesh.devices.flat),
                premat=premat_active,
            )
        )
    return out


def _crossing_roofline(lay, step_us, peak_flops, peak_gbps, use_pallas=True, premat=False):
    """Quantified crossing roofline (VERDICT r4 next #3): measure the two
    crossing kernels ALONE at the step's exact unit shapes, and bound them
    by spec — MXU FLOPs at bf16 peak and HBM stream bytes at peak
    bandwidth. Returns fields for the sparse bench entry; derivation in
    docs/benchmarks.md (sparse roofline section).

    The bound is for the crossing *as contracted* (the one-hot matmul's own
    FLOPs/bytes), so crossing_bound_share says how close those kernels run
    to hardware limits, and step_share_crossing says how much of the whole
    step they explain — together they either close the "what remains is
    crossing-bound" claim or size the remaining gap.
    """
    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.linalg.onehot_sparse import (
        dot_crossing_pallas,
        dot_crossing_premat_pallas,
        dot_crossing_premat_xla,
        dot_crossing_xla,
        mult_crossing_pallas,
        mult_crossing_premat_pallas,
        mult_crossing_premat_xla,
        mult_crossing_xla,
        premat_row_onehots,
    )

    n_sub, n_flat, sub = lay.n_sub, lay.n_flat, lay.sub_batch
    row_hi = lay.row_hi
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal((n_sub, n_flat)).astype(np.float32))
    rhi = jnp.asarray(rng.integers(0, row_hi, (n_sub, n_flat)).astype(np.int32))
    rlo = jnp.asarray(rng.integers(0, 128, (n_sub, n_flat)).astype(np.int32))
    mult3 = jnp.asarray(
        rng.standard_normal((n_sub, row_hi, 128)).astype(np.float32)
    )
    dot_fn = dot_crossing_pallas if use_pallas else dot_crossing_xla
    mult_fn = mult_crossing_pallas if use_pallas else mult_crossing_xla

    @jax.jit
    def both(q, rhi, rlo, mult3):
        d3 = dot_fn(q, rhi, rlo, row_hi)
        u = mult_fn(mult3, rhi, rlo, row_hi)
        return d3, u

    def _time_form(f, *args):
        def total(reps):
            t0 = time.perf_counter()
            for _ in range(reps):
                d3, u = f(*args)
            float(d3[0, 0, 0]) + float(u.reshape(-1)[0])  # fetch barrier
            return time.perf_counter() - t0

        return _marginal_time(total)

    build_s = _time_form(both, q, rhi, rlo, mult3)

    # The premat form at the same unit shape (one window's one-hots,
    # materialized once outside the timed region) — ONLY when the step's
    # gate admitted the path: if it was rejected for not fitting HBM, the
    # roofline must not allocate the very stacks the gate refused.
    if premat:
        rowid = (rhi * 128 + rlo).astype(jnp.int16)
        oh_hi, oh_lo = jax.jit(premat_row_onehots, static_argnums=1)(rowid, row_hi)
        pdot = dot_crossing_premat_pallas if use_pallas else dot_crossing_premat_xla
        pmult = mult_crossing_premat_pallas if use_pallas else mult_crossing_premat_xla

        @jax.jit
        def both_premat(q, mult3, oh_hi, oh_lo):
            return pdot(q, oh_hi, oh_lo), pmult(mult3, oh_hi, oh_lo)

        premat_s = _time_form(both_premat, q, mult3, oh_hi, oh_lo)
        crossing_s = premat_s
    else:
        premat_s = None
        crossing_s = build_s

    # Each crossing: 2 split-bf16 halves x 2 flops/MAC over the
    # [n_flat x (row_hi*128=sub)] one-hot contraction, per sub-batch.
    crossing_flops = 8.0 * n_sub * n_flat * sub
    if premat:
        # Premat form HBM traffic: each crossing re-streams the window's
        # materialized one-hots ((row_hi + 128) bf16 per entry) plus
        # q in / u out; dot3/mult3 are [row_hi, 128] f32 = sub*4 B, small.
        n_pad = oh_hi.shape[-2]
        crossing_bytes = n_sub * (
            2.0 * n_pad * (row_hi + 128) * 2 + 2.0 * n_flat * 4 + 2.0 * sub * 4
        )
    else:
        # Build-form HBM traffic: q/rhi/rlo in, u out (4 B x n_flat each);
        # one-hots are built in VMEM and never touch HBM.
        crossing_bytes = n_sub * (4.0 * n_flat * 4 + 2.0 * sub * 4)
    out = {
        "crossing_only_ms": round(crossing_s * 1e3, 2),
        "crossing_build_form_ms": round(build_s * 1e3, 2),
        "crossing_premat_ms": (
            round(premat_s * 1e3, 2) if premat_s is not None else None
        ),
        "crossing_mxu_bound_ms": (
            round(crossing_flops / peak_flops * 1e3, 2) if peak_flops else None
        ),
        "crossing_hbm_bound_ms": (
            round(crossing_bytes / (peak_gbps * 1e9) * 1e3, 3) if peak_gbps else None
        ),
        "step_share_crossing": round(crossing_s * 1e6 / step_us, 3),
    }
    if peak_flops and peak_gbps:
        bound_s = max(crossing_flops / peak_flops, crossing_bytes / (peak_gbps * 1e9))
        out["crossing_bound_share"] = round(bound_s / crossing_s, 3)
    return out


def bench_onehot_per_chip_sweep(peak_flops):
    """The north-star per-chip shapes, timed on the real chip (VERDICT r4
    next #1): run the fused one-hot program single-chip at the LOCAL shard
    shape of p in {1, 2, 4, 8, 16} data-parallel chips (local batch 65536
    down to 4096, sub tracking the 16384 cap) and record measured step time
    next to the predicted compiled-FLOP falloff — wall-clock evidence for
    (or against) the 1/p^2 crossing-scaling projection that
    tools/crossing_scaling.py derives from cost analysis.

    A p-way DP step is the per-shard program plus one psum; timing the
    per-shard shape on one chip measures everything except the collective,
    which at 16 MB/coef over ICI is sub-ms — the projection's error bar.
    """
    d, nnz, K = 1 << 22, 39, 40
    global_batch = 65_536
    rows = []
    for p in (1, 2, 4, 8, 16):
        try:
            rows.append(_sweep_row(p, global_batch, d, nnz, K))
        except Exception as e:  # a failing shape must not sink the sweep
            rows.append({"p": p, "error": f"{type(e).__name__}: {str(e)[:300]}"})
    ok = [r for r in rows if "error" not in r]
    # Falloff columns are anchored at p=1 by definition; if that row failed,
    # rebasing silently would make every falloff read ~p_base x too small.
    base = ok[0] if ok and ok[0]["p"] == 1 else None
    if base is None and ok:
        for r in ok:
            r["falloff_note"] = "p=1 row missing: falloff columns omitted"
    if base is not None:
        for r in ok:
            r["predicted_flop_falloff"] = round(
                base["predicted_flops_per_chip"] / r["predicted_flops_per_chip"], 2
            )
            r["measured_time_falloff"] = round(
                base["measured_step_ms"] / r["measured_step_ms"], 2
            )
            if peak_flops:
                r["mfu"] = round(
                    r["predicted_flops_per_chip"]
                    / (r["measured_step_ms"] / 1e3)
                    / peak_flops,
                    4,
                )
    return {
        "name": "onehot_per_chip_shape_sweep",
        "global_batch": global_batch,
        "dim": d,
        "nnz": nnz,
        "rows": rows,
        "note": "single-chip wall-clock at each p's per-shard shape; "
        "measured_time_falloff is the hardware-evidence column for the "
        "crossing-scaling projection (predicted_flop_falloff); excludes "
        "the per-step psum (sub-ms at 16 MB over ICI). Deltas have a "
        "400-iteration floor (a contention-shrunk pilot once produced an "
        "unusable flat sweep); the time-shared chip still swings single "
        "rows 2-4x, so cross-run BANDS (BASELINE.md) are the quotable "
        "numbers, not any one run's row",
    }


def _sweep_row(p, global_batch, d, nnz, K):
    """One p's per-shard measurement (see bench_onehot_per_chip_sweep)."""
    from flink_ml_tpu.iteration import DeviceDataCache
    from flink_ml_tpu.linalg.onehot_sparse import BLOCK
    from flink_ml_tpu.ops import SGD, BinaryLogisticLoss

    lb = global_batch // p
    rng = np.random.default_rng(100 + p)
    idx = rng.integers(0, d, size=(lb, K), dtype=np.int32)
    vals = np.ones((lb, K), np.float32)
    vals[:, nnz:] = 0.0
    y = (rng.random(lb) > 0.5).astype(np.float32)
    cache = DeviceDataCache(
        {
            "indices": idx,
            "values": vals,
            "labels": y,
            "weights": np.ones(lb, np.float32),
        }
    )

    def steps(iters):
        sgd = SGD(
            max_iter=iters, global_batch_size=lb, tol=0.0,
            learning_rate=0.5, sparse_kernel="onehot",
        )
        sgd.optimize(np.zeros(d, np.float32), cache, BinaryLogisticLoss.INSTANCE)
        return sgd

    # Pilot differencing to size the real delta: the marginal estimate
    # must itself be a difference (a single-point pilot is mostly fixed
    # dispatch overhead at small shards). The final delta is
    # sized to ~5 s of pure step time, a multiple of that overhead, with
    # a 400-iteration floor — a contention spike during the pilot must
    # not shrink the real delta into the noise (observed: a sweep row
    # reading 7 ms where the headline's pinned protocol reads 12-17).
    premat_active = steps(2).onehot_premat_active  # compile + gate decision
    p1 = _median_time(lambda: steps(5), repeats=3)
    p2 = _median_time(lambda: steps(55), repeats=3)
    est_step = max((p2 - p1) / 50, 2e-4)
    extra = int(min(max(400, 5.0 / est_step), 5000))
    i1, i2 = 10, 10 + extra
    t1 = _median_time(lambda: steps(i1))
    t2 = _median_time(lambda: steps(i2))
    step_ms = max((t2 - t1) / (i2 - i1), 1e-9) * 1e3

    lay = cache._onehot_memo[1]
    flops = 4.0 * lay.n_sub * lay.n_flat * (lay.sub_batch + 2 * BLOCK)
    return {
        "p": p,
        "local_batch": lb,
        "sub_batch": lay.sub_batch,
        "n_sub": lay.n_sub,
        "n_flat": lay.n_flat,
        "onehot_premat_active": premat_active,
        "predicted_flops_per_chip": flops,
        "measured_step_ms": round(step_ms, 2),
    }


def bench_logreg_sparse_streamed():
    """The north-star rehearsal: every Criteo ingredient run TOGETHER —
    streamed (larger-than-HBM windows out of a spilling host cache) + sparse
    (padded-CSR) + fused — now on the ONE-HOT matmul kernel (the streamed
    path auto-selects it since round 4; windows share one compiled program
    through the global OneHotSparsePlan).

    The machinery is what's under test; per-row cost is shape-invariant. Three
    numbers matter: the streamed one-hot step time (must be comparable to
    the resident path's), the scatter step it replaced, and the overlap
    efficiency — the fraction of compute the prefetch actually hides behind
    ingest (wall ≈ ingest when overlap is perfect and ingest dominates).
    """
    import tempfile

    from flink_ml_tpu.iteration import DeviceDataCache, HostDataCache
    from flink_ml_tpu.ops import SGD, BinaryLogisticLoss

    n, d, nnz = 250_000, 1 << 22, 39
    K = 40
    batch = 65_536
    epochs = 8
    window = 125_000
    rng = np.random.default_rng(7)
    with tempfile.TemporaryDirectory() as spill:
        cache = HostDataCache(memory_budget_bytes=64 << 20, spill_dir=spill)
        for lo in range(0, n, 25_000):  # the synthetic Criteo-shaped stream
            m = min(25_000, n - lo)
            idx = rng.integers(0, d, size=(m, K), dtype=np.int32)
            vals = np.ones((m, K), np.float32)
            vals[:, nnz:] = 0.0
            cache.append(
                {
                    "indices": idx,
                    "values": vals,
                    "labels": (rng.random(m) > 0.5).astype(np.float32),
                    "weights": np.ones(m, np.float32),
                }
            )
        cache.finish()

        last_fit = {}

        def streamed_fit(kernel):
            sgd = SGD(
                max_iter=epochs,
                global_batch_size=batch,
                tol=0.0,
                learning_rate=0.5,
                stream_window_rows=window,
                sparse_kernel=kernel,
            )
            t0 = time.perf_counter()
            sgd.optimize(np.zeros(d, np.float32), cache, BinaryLogisticLoss.INSTANCE)
            last_fit["premat"] = sgd.onehot_premat_active
            return time.perf_counter() - t0

        streamed_fit("scatter")  # warm-up: program compile
        wall_scatter = streamed_fit("scatter")
        streamed_fit("onehot")  # warm-up: plan + program compile

        # Pure-ingest time: load the windows the run actually loads (dedup
        # consecutive same-window runs — run_windows keeps those resident),
        # no compute. Measured IMMEDIATELY BEFORE the timed fit, so probe and
        # fit are adjacent — and the counting pass the fit repeats is timed
        # separately and removed from wall for the overlap accounting — it
        # is neither ingest nor compute, and runs before any window exists.
        from flink_ml_tpu.iteration.streaming import WindowSchedule
        from flink_ml_tpu.linalg.onehot_sparse import BLOCK, SUB_ROWS
        from flink_ml_tpu.ops.optimizer import _OneHotWindowStream, streamed_onehot_plan
        from flink_ml_tpu.parallel.mesh import get_mesh_context

        ctx = get_mesh_context()
        m_shard = -(-n // ctx.n_data)
        b_local = -(-batch // ctx.n_data)
        sub = min(SUB_ROWS, b_local)
        W = WindowSchedule(m_shard, b_local, window, epochs).window
        t0 = time.perf_counter()
        plan = streamed_onehot_plan(cache, n, ctx.n_data, W, b_local, d)
        plan_s = time.perf_counter() - t0
        n_sub = -(-b_local // sub)
        flops = 4.0 * n_sub * plan.n_flat * (sub + 2 * BLOCK)
        sched = WindowSchedule(
            m_shard, b_local, window, epochs, flops_per_epoch=flops
        )
        # The probe must exercise the SAME load() path the fit uses — with
        # premat engaged, load() also materializes the window's one-hots on
        # device, and that cost belongs to the probe's ingest_s, not to the
        # overlap formula's residual.
        stream = _OneHotWindowStream(
            cache, ctx, plan, sched.window, b_local, n_sub, m_shard, n,
            premat=last_fit.get("premat", False),
        )
        visited = [j for j, _ in sched.runs]
        loads = [j for i, j in enumerate(visited) if i == 0 or j != visited[i - 1]]
        t0 = time.perf_counter()
        for j in loads:
            import jax

            buf = stream.load(j)
            jax.block_until_ready(buf.get("oh", buf["labels"]))
        ingest_s = time.perf_counter() - t0
        del buf

        wall = streamed_fit("onehot")

    # The compute half, measured directly: the one-hot program on a
    # window-sized resident cache — the VERDICT's "comparable to the
    # resident path" criterion, plus the scatter step it replaced.
    rng2 = np.random.default_rng(8)
    widx = rng2.integers(0, d, size=(window, K), dtype=np.int32)
    wvals = np.ones((window, K), np.float32)
    wvals[:, nnz:] = 0.0
    wcache = DeviceDataCache(
        {
            "indices": widx,
            "values": wvals,
            "labels": (rng2.random(window) > 0.5).astype(np.float32),
            "weights": np.ones(window, np.float32),
        }
    )

    def wsteps(kernel, iters):
        SGD(
            max_iter=iters, global_batch_size=batch, tol=0.0, learning_rate=0.5,
            sparse_kernel=kernel,
        ).optimize(np.zeros(d, np.float32), wcache, BinaryLogisticLoss.INSTANCE)

    step_us = {}
    for kernel in ("onehot", "scatter"):
        # 100-step differencing: the step-time signal must be a multiple of
        # the fixed dispatch+fetch overhead (delta calibrated on a retired
        # setup, not re-measured).
        t1 = _median_time(lambda: wsteps(kernel, 10))
        t2 = _median_time(lambda: wsteps(kernel, 110))
        step_us[kernel] = max((t2 - t1) / 100, 1e-9) * 1e6

    compute_s = epochs * step_us["onehot"] / 1e6
    wall_train = max(wall - plan_s, 1e-9)  # windows-phase wall: counting pass excluded
    # The probe and the fit are separate measurements, so the estimated
    # shares are clamped into [0, 1].
    ingest_clamped = min(ingest_s, wall_train)
    # Report overlap unmeasured (null) rather than fabricated when either
    # input is outside the measurement's validity: compute a negligible
    # share of the wall, or the probe's ingest exceeding the fit's whole
    # wall (drift between the two runs — clamping it into the formula would
    # emit a deterministic fake 1.0).
    if compute_s < 0.05 * wall_train or ingest_s > wall_train:
        overlap = None
    else:
        overlap = (compute_s + ingest_clamped - wall_train) / max(
            min(compute_s, ingest_clamped), 1e-9
        )
        overlap = round(min(max(overlap, 0.0), 1.0), 3)
    rows_consumed = epochs * batch
    return {
        "name": "logreg_sparse_streamed_250k_d4M_w125k",
        "wall_time_s": round(wall, 2),
        "wall_time_s_scatter": round(wall_scatter, 2),
        "plan_pass_s": round(plan_s, 2),
        "epochs": epochs,
        "window_rows": window,
        "e2e_rows_per_sec": round(rows_consumed / wall, 1),
        "onehot_premat_active": last_fit.get("premat", False),
        "onehot_step_us": round(step_us["onehot"], 1),
        "scatter_step_us": round(step_us["scatter"], 1),
        "onehot_vs_scatter_step": round(step_us["scatter"] / step_us["onehot"], 2),
        "ingest_s": round(ingest_s, 2),
        "compute_s": round(compute_s, 2),
        "compute_share": round(min(compute_s / wall_train, 1.0), 4),
        "ingest_share": round(ingest_clamped / wall_train, 4),
        "overlap_efficiency": overlap,
        "note": "streamed+sparse+fused on the one-hot kernel; "
        "overlap_efficiency (fraction of compute hidden behind ingest) is null "
        "when compute is under 5% of the wall or the ingest probe exceeds it",
    }


def bench_streamed_overlap_cpu_mesh():
    """Run tools/bench_streamed_overlap.py in a subprocess on the 8-device
    virtual CPU mesh. This process has touched JAX and holds the chip, which
    belongs to one process at a time, so the child is forced onto the CPU
    backend — it counts and checks, its times are not device times."""
    import os
    import subprocess

    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",  # the parent holds the chip
            "XLA_FLAGS": (
                env.get("XLA_FLAGS", "")
                + " --xla_cpu_collective_call_warn_stuck_timeout_seconds=30"
                + " --xla_cpu_collective_call_terminate_timeout_seconds=120"
                + " --xla_force_host_platform_device_count=8"
            ).strip(),
            "PYTHONPATH": os.path.dirname(os.path.abspath(__file__)),
        }
    )
    try:
        proc = subprocess.run(
            [sys.executable, "tools/bench_streamed_overlap.py"],
            capture_output=True, text=True, timeout=1200, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        return json.loads(proc.stdout.strip().splitlines()[-1])
    except Exception as e:  # never sink the whole bench for the side artifact
        return {
            "name": "streamed_overlap_cpu_mesh_196k_d256k",
            "error": f"{type(e).__name__}: {e}",
        }


def bench_mlp_train(peak_flops):
    """Compute-bound training: can the framework feed the MXU?

    The MLPClassifier fused training path (adam, psum, minibatch windows — the
    exact ``fit`` program) at MXU-saturating shapes: batch 32768, layers
    2048-4096-4096-1024, bf16 matmuls (``computeType`` mixed precision). Data
    is generated on device, so ingest never touches the measurement; the
    timed unit is one fused multi-epoch dispatch, like a real training run.
    """
    import jax
    import jax.numpy as jnp
    import optax

    from flink_ml_tpu.models.classification.mlp_classifier import (
        MLPClassifier,
        _init_params,
    )
    from flink_ml_tpu.ops.optimizer import offset_schedule
    from flink_ml_tpu.parallel.mesh import get_mesh_context

    n = batch = 32_768
    dims = [2048, 4096, 4096, 1024]
    ctx = get_mesh_context()

    key = jax.random.PRNGKey(0)
    kx, ky = jax.random.split(key)
    X = jax.device_put(jax.random.normal(kx, (n, dims[0]), jnp.float32), ctx.batch)
    y = jax.device_put(
        jax.random.randint(ky, (n,), 0, dims[-1]).astype(jnp.float32), ctx.batch
    )
    w = jax.device_put(jnp.ones(n, jnp.float32), ctx.batch)

    clf = (
        MLPClassifier()
        .set_hidden_layers(*dims[1:-1])
        .set_learning_rate(1e-3)
        .set_global_batch_size(batch)
        .set_tol(0.0)
        .set_compute_type("bfloat16")
    )
    local_batch = max(1, batch // ctx.n_data)
    optimizer = optax.adam(1e-3)
    params = [tuple(jnp.asarray(a) for a in layer) for layer in _init_params(np.random.default_rng(0), dims)]
    opt_state = optimizer.init(params)
    done = ctx.replicate(np.asarray(False))

    epochs = 20
    fused = clf._build_fused(ctx, optimizer, local_batch, epochs, None)
    starts, offsets = offset_schedule(n // ctx.n_data, local_batch, epochs)
    active = np.ones(epochs, bool)

    def run():
        nonlocal params, opt_state, done
        params, opt_state, done, n_exec = fused(
            params, opt_state, done, starts, offsets, active, X, y, w
        )
        jax.block_until_ready(n_exec)

    step_s = _median_time(run) / epochs
    # fwd 2 + bwd 4 madd-flops per weight per row
    flops_per_step = 6.0 * batch * sum(a * b for a, b in zip(dims[:-1], dims[1:]))
    achieved = flops_per_step / step_s
    out = {
        "name": "mlp_train_bf16_b32768_2048_4096_4096_1024",
        "rows_per_sec": round(batch / step_s, 1),
        "step_time_us": round(step_s * 1e6, 1),
        "achieved_tflops": round(achieved / 1e12, 2),
        "note": "full training step: fwd+bwd+psum+adam, the MLPClassifier.fit program",
    }
    if peak_flops:
        out["mfu"] = round(achieved / peak_flops, 4)
    return out


def bench_attention(peak_flops):
    """Long-context attention: the ring fold at a single-chip shape.

    T=8192 causal self-attention (H=4, D=128) through the ring program —
    on one chip that is one fold, which runs as the fused Pallas flash
    kernel (parallel/flash.py): scores never touch HBM. The jnp fold is
    timed alongside so the artifact records the kernel's margin.
    """
    import jax

    from flink_ml_tpu.parallel.mesh import get_mesh_context
    from flink_ml_tpu.parallel.ring import _sharded_program

    from flink_ml_tpu.parallel.flash import flash_available

    rng = np.random.default_rng(3)
    ctx = get_mesh_context()
    B, T, H, D = 1, 8192, 4, 128
    if not flash_available(T // ctx.n_data, D, list(ctx.mesh.devices.flat)):
        return {
            "name": "ring_attention_causal_T8192_h4_d128",
            "note": "flash fold unavailable on this backend/shape; skipped",
        }
    q = jax.device_put(rng.standard_normal((B, T, H, D)).astype(np.float32))
    k = jax.device_put(rng.standard_normal((B, T, H, D)).astype(np.float32))
    v = jax.device_put(rng.standard_normal((B, T, H, D)).astype(np.float32))

    def timed(flash):
        prog = _sharded_program(ctx.mesh, True, False, flash)

        def total(reps):
            t0 = time.perf_counter()
            for _ in range(reps):
                out = prog(q, k, v)
            float(out[0, 0, 0, 0])  # scalar fetch = completion barrier
            return time.perf_counter() - t0

        return _marginal_time(total)

    t_flash, t_jnp = timed(True), timed(False)
    flops = 4.0 * B * H * T * T * D  # qk^T + pv matmuls (f32, causal-masked)
    out = {
        "name": "ring_attention_causal_T8192_h4_d128",
        "flash_step_ms": round(t_flash * 1e3, 2),
        "jnp_step_ms": round(t_jnp * 1e3, 2),
        "flash_speedup": round(t_jnp / t_flash, 2),
        "achieved_tflops": round(flops / t_flash / 1e12, 2),
        "note": "fused Pallas fold (scores stay in VMEM) vs the jnp fold",
    }
    if peak_flops:
        out["mfu"] = round(flops / t_flash / peak_flops, 4)
    return out


def _attention_train_step_ms(B, T, flash):
    """Time one SelfAttentionClassifier training step (fwd+bwd+psum+adam) —
    the exact ``_train_step`` program ``fit`` compiles — chaining
    params/opt_state through reps (buffer donation) with a scalar fetch as
    the completion barrier and rep differencing (``_marginal_time``)."""
    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.models.classification.attention_classifier import (
        _init_params,
        _train_step,
    )
    from flink_ml_tpu.parallel.mesh import DATA_AXIS, get_mesh_context

    ctx = get_mesh_context()
    H, E, vocab, C = 4, 512, 1024, 8  # head dim 128
    rng = np.random.default_rng(5)
    tok = rng.integers(0, vocab, size=(B, T)).astype(np.int32)
    y = rng.integers(0, C, size=(B,)).astype(np.int32)
    params = jax.tree_util.tree_map(jnp.asarray, _init_params(rng, vocab, E, C))
    optimizer, step = _train_step(ctx.mesh, H, 1e-3, flash)
    opt_state = optimizer.init(params)
    tok_dev = jax.device_put(tok, ctx.sharding(None, DATA_AXIS))
    y_dev = ctx.replicate(y)
    w_dev = ctx.replicate(np.ones(B, np.float32))
    nv = jnp.asarray(T, jnp.int32)
    state = {"params": params, "opt": opt_state}

    def total(reps):
        t0 = time.perf_counter()
        for _ in range(reps):
            state["params"], state["opt"], loss = step(
                state["params"], state["opt"], tok_dev, y_dev, w_dev, nv
            )
        float(loss)  # scalar fetch = completion barrier
        return time.perf_counter() - t0

    return _marginal_time(total) * 1e3


def _attention_train_flops(B, T, H=4, E=512, C=8):
    # fwd attention 4BHT^2D (qk^T + pv), bwd ~2x more; projections
    # (q/k/v/o/cls) 2 madd-flops fwd + 4 bwd per weight per row.
    return 12.0 * B * H * T * T * (E // H) + 6.0 * B * T * (4 * E * E + E * C)


def bench_attention_train(peak_flops):
    """The SelfAttentionClassifier *fit step* — fwd + bwd + psum + adam —
    the number a user of the SP stage actually gets (VERDICT r4 missing #4
    pinned the fused-fold forward but not the training step).

    Two rows: (a) T=8192 single-chip with the kernel the product gate
    actually picks there — the fused backward's pallas outputs exceed the
    scoped-VMEM training envelope at B*H*T*(D+2)*4 ≈ 17 MB, so fit trains
    on the jnp fold; and (b) the fused training step at B=1, T=4096 — the
    per-shard shape of T=8192 on a 2-chip SP mesh, i.e. the per-chip
    evidence for multi-chip fused training (flash_train_available admits it
    once the sequence axis is sharded).
    """
    from flink_ml_tpu.parallel.flash import flash_available, flash_train_available
    from flink_ml_tpu.parallel.mesh import get_mesh_context

    ctx = get_mesh_context()
    H, E = 4, 512
    if not flash_available(8192 // ctx.n_data, E // H, list(ctx.mesh.devices.flat)):
        return {
            "name": "attention_train_T8192_h4_d128",
            "note": "flash fold unavailable on this backend; skipped",
        }

    out = {"name": "attention_train_T8192_h4_d128", "rows": []}
    for label, B, T in (("fit_T8192_single_chip", 1, 8192), ("fused_per_shard_T4096", 1, 4096)):
        flash = flash_train_available(
            T // ctx.n_data, E // H, B, H, list(ctx.mesh.devices.flat)
        )
        step_ms = _attention_train_step_ms(B, T, flash)
        flops = _attention_train_flops(B, T)
        achieved = flops / (step_ms / 1e3)
        row = {
            "config": label,
            "batch": B,
            "T": T,
            "kernel": "fused" if flash else "jnp_fold",
            "step_time_ms": round(step_ms, 2),
            "tokens_per_sec": round(B * T / (step_ms / 1e3), 1),
            "achieved_tflops": round(achieved / 1e12, 2),
        }
        if peak_flops:
            row["mfu"] = round(achieved / peak_flops, 4)
        out["rows"].append(row)
    out["note"] = (
        "full fit step (fwd+bwd+psum+adam). Single-chip T=8192 trains on the "
        "jnp fold (the fused backward's outputs exceed the scoped-VMEM "
        "training envelope, flash.flash_train_available); the T=4096 row is "
        "the fused per-shard program a 2-chip SP mesh runs for T=8192"
    )
    return out


def bench_kmeans(peak_gbps):
    from flink_ml_tpu.api.dataframe import DataFrame
    from flink_ml_tpu.models.clustering.kmeans import KMeans

    rng = np.random.default_rng(2)
    num_rows, dim = 100_000, 10
    # wide spread: the per-iteration delta must clear the dispatch jitter
    # (epochs are ~20 us each once fused)
    i1, i2 = 20, 10_020
    df = DataFrame.from_dict({"features": rng.random((num_rows, dim))})

    def fit(iters):
        KMeans().set_seed(2).set_max_iter(iters).fit(df)

    t1 = _median_time(lambda: fit(i1))
    t2 = _median_time(lambda: fit(i2))
    # A non-positive delta means jitter swamped the measurement — report null
    # rather than a fabricated clamp value.
    iter_s = (t2 - t1) / (i2 - i1) if t2 > t1 else None

    # The reference's own config (10k rows) for the apples-to-apples anchor —
    # rows/s is not shape-invariant, so the 1,399 rows/s comparison uses the
    # exact shape it was measured on.
    df10k = DataFrame.from_dict({"features": rng.random((10_000, dim))})
    t10k = _median_time(lambda: KMeans().set_seed(2).set_max_iter(i1).fit(df10k))
    # Roofline: the fused iteration reads X for distances and again for the
    # centroid update. An achieved number above HBM peak means the 4 MB
    # dataset went VMEM-resident across the scan — report it as-is with the
    # denominator so the comparison stays honest.
    bytes_per_iter = 2.0 * num_rows * dim * 4  # f32 features (KMeans casts)
    out = {
        "name": "kmeans_fit_d10_k2",
        "iter_time_us_100k": None if iter_s is None else round(iter_s * 1e6, 1),
        "e2e_rows_per_sec_100k_20_iters": round(num_rows / t1, 1),
        "fit_time_s_100k_20_iters": round(t1, 3),
        "e2e_rows_per_sec_10k_20_iters": round(10_000 / t10k, 1),
        # reference illustrative CPU output for this exact 10k config (rows/s)
        "reference_cpu_rows_per_sec": 1399.0,
        "vs_reference_cpu_10k": round(10_000 / t10k / 1399.0, 2),
        "peak_hbm_gbps": peak_gbps,
    }
    if iter_s is not None:
        gbps = round(bytes_per_iter / iter_s / 1e9, 1)
        if peak_gbps and gbps > peak_gbps:
            # The 4 MB dataset went VMEM-resident across the fused scan, so
            # HBM peak is the wrong denominator for this entry — report the
            # number under its own key so no table row exceeds 100% of a
            # stated peak (the bytes are HBM-equivalent traffic the scan
            # never actually paid).
            out["vmem_resident_hbm_equiv_gbps"] = gbps
            out["roofline_note"] = (
                "dataset VMEM-resident across the fused scan: the iteration "
                "re-reads X from VMEM, so HBM bandwidth is not the ceiling "
                "and no HBM utilization is claimed; vmem_resident_hbm_equiv_"
                "gbps is the HBM traffic an un-fused iteration would have paid"
            )
        else:
            out["achieved_gbps"] = gbps
            if peak_gbps:
                out["hbm_utilization"] = round(gbps / peak_gbps, 3)
    return out


def bench_training_weak_scaling():
    """Weak-scaling sweep of the sharded training tier
    (docs/distributed_training.md): per-shard work held FIXED while
    ``train.mesh`` sweeps 1/2/4/8, so ideal scaling is flat epoch time and
    linearly growing rows/s. Two legs: the sharded KMeans epoch (mapreduce
    centroid update) and the deterministic-tier SGD step.

    Honest-1-core-box note: on the CI host the 8 "devices" are XLA virtual
    CPU devices time-sharing one core, so epoch time grows ~linearly with
    width instead of holding flat — the sweep here is an overhead/regression
    gate (deal + collective cost at each width, bit-identity priced in),
    not a scaling demonstration; the flat-epoch claim needs >= width cores
    or real chips.
    """
    import jax

    from flink_ml_tpu.api.dataframe import DataFrame
    from flink_ml_tpu.config import Options, config
    from flink_ml_tpu.models.clustering.kmeans import KMeans
    from flink_ml_tpu.ops import SGD, BinaryLogisticLoss
    from flink_ml_tpu.parallel import TrainSharding

    widths = [w for w in (1, 2, 4, 8) if w <= len(jax.devices())]
    rng = np.random.default_rng(5)
    rows_per_shard, dim = 8_192, 8
    i1, i2 = 3, 23

    out = {
        "name": "training_weak_scaling",
        "rows_per_shard": rows_per_shard,
        "dim": dim,
        "note": (
            "weak scaling: per-shard rows fixed, total rows = width x "
            "per-shard; measured on XLA virtual CPU devices time-sharing "
            "one core, so per-epoch time is an overhead gate, not a "
            "scaling demo (see docstring)"
        ),
        "kmeans_epoch": {},
        "sgd_step": {},
    }
    for w in widths:
        n = rows_per_shard * w
        df = DataFrame.from_dict({"features": rng.random((n, dim))})
        config.set(Options.TRAIN_MESH, w)
        try:
            def fit(iters):
                KMeans().set_seed(2).set_k(4).set_max_iter(iters).fit(df)

            t1 = _median_time(lambda: fit(i1), repeats=3)
            t2 = _median_time(lambda: fit(i2), repeats=3)
            epoch_s = (t2 - t1) / (i2 - i1) if t2 > t1 else None
        finally:
            config.unset(Options.TRAIN_MESH)
        out["kmeans_epoch"][f"mesh_{w}"] = {
            "total_rows": n,
            "epoch_p50_ms": None if epoch_s is None else round(epoch_s * 1e3, 3),
            "rows_per_sec": None if epoch_s is None else round(n / epoch_s, 1),
        }

    sgd_batch = 64 * 8  # one quantum multiple at every width
    for w in widths:
        n = rows_per_shard * w
        X = rng.normal(size=(n, dim)).astype(np.float32)
        y = (X.sum(axis=1) > 0).astype(np.float32)
        data = {"features": X, "labels": y}
        ts = TrainSharding(w)

        def opt(iters):
            SGD(
                max_iter=iters,
                learning_rate=0.1,
                global_batch_size=sgd_batch,
                tol=0.0,
                sharding=ts,
            ).optimize(np.zeros(dim), data, BinaryLogisticLoss.INSTANCE)

        t1 = _median_time(lambda: opt(i1), repeats=3)
        t2 = _median_time(lambda: opt(i2), repeats=3)
        step_s = (t2 - t1) / (i2 - i1) if t2 > t1 else None
        out["sgd_step"][f"mesh_{w}"] = {
            "total_rows": n,
            "global_batch": sgd_batch,
            "step_p50_ms": None if step_s is None else round(step_s * 1e3, 3),
            "rows_per_sec": None if step_s is None else round(sgd_batch / step_s, 1),
        }
    return out


def bench_serving():
    """Offered-load sweep over the online serving runtime (docs/serving.md).

    Request sizes 1/8/64 rows — the bucket shapes the micro-batcher pads to —
    each driven from 4 client threads at saturation against a d=256 logistic
    servable (the BASELINE.json CTR shape). Reports throughput (rows/s
    through the full queue→batch→pad→transform→slice path) and p50/p99
    request latency scraped from the server's own ``ml.serving.*`` histogram,
    so BENCH rounds track the serving pillar with the same metrics a
    deployment would alert on. Warmup happens once per bucket at server
    construction (the hot-swap warm path), so compiles never land in the
    timed window — the same discipline as every other workload here.
    """
    import threading

    from flink_ml_tpu.api.dataframe import DataFrame
    from flink_ml_tpu.metrics import MLMetrics, metrics
    from flink_ml_tpu.servable import PipelineModelServable
    from flink_ml_tpu.servable.lib import (
        LogisticRegressionModelServable,
        StandardScalerModelServable,
    )
    from flink_ml_tpu.serving import InferenceServer, ServingConfig

    rng = np.random.default_rng(5)
    dim = 256
    X = rng.standard_normal((4096, dim)).astype(np.float32)

    def make_lr(features_col="features"):
        servable = LogisticRegressionModelServable().set_features_col(features_col)
        servable.coefficient = rng.standard_normal(dim).astype(np.float32)
        return servable

    def make_pipeline():
        """Depth-2 pipeline: scaler -> logistic, the fusion benchmark shape."""
        scaler = (
            StandardScalerModelServable()
            .set_input_col("features")
            .set_output_col("scaled")
            .set_with_mean(True)
        )
        scaler.mean = rng.standard_normal(dim).astype(np.float32)
        scaler.std = (np.abs(rng.standard_normal(dim)) + 0.5).astype(np.float32)
        return PipelineModelServable([scaler, make_lr("scaled")])

    n_threads = 4
    requests_per_thread = 150

    def run_load(servable, name, req_rows, *, fastpath=None, pipeline_depth=None):
        """Drive the server at saturation from n_threads clients; report
        throughput + p50/p99 from the server's own ml.serving histogram."""
        server = InferenceServer(
            servable,
            name=name,
            serving_config=ServingConfig(
                max_batch_size=64,
                max_delay_ms=1.0,
                queue_capacity_rows=8192,
                default_timeout_ms=120_000,
                fastpath=fastpath,
                pipeline_depth=pipeline_depth,
            ),
            warmup_template=DataFrame.from_dict({"features": X[:1]}),
        )
        try:
            barrier = threading.Barrier(n_threads + 1)

            def client(tid):
                barrier.wait()
                for i in range(requests_per_thread):
                    j = (tid * 997 + i * 61) % (X.shape[0] - req_rows)
                    server.predict(
                        DataFrame.from_dict({"features": X[j : j + req_rows]})
                    )

            threads = [
                threading.Thread(target=client, args=(t,)) for t in range(n_threads)
            ]
            for t in threads:
                t.start()
            barrier.wait()
            t0 = time.perf_counter()
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - t0
            scraped = metrics.scope(server.scope)
            lat = scraped[MLMetrics.SERVING_LATENCY_MS]
            total_rows = n_threads * requests_per_thread * req_rows
            batches = scraped[MLMetrics.SERVING_BATCHES]
            return {
                "request_rows": req_rows,
                "rows_per_sec": round(total_rows / elapsed, 1),
                "requests_per_sec": round(
                    n_threads * requests_per_thread / elapsed, 1
                ),
                "latency_p50_ms": round(lat.quantile(0.5), 3),
                "latency_p99_ms": round(lat.quantile(0.99), 3),
                "mean_batch_rows": round(total_rows / batches, 1),
                "batches": batches,
                "fused_batches": scraped.get(MLMetrics.SERVING_FUSED_BATCHES, 0),
                "warmup_compile_ms": round(
                    scraped.get(MLMetrics.SERVING_WARMUP_COMPILE_MS, 0.0), 1
                ),
            }
        finally:
            server.close()

    sweep = [
        run_load(make_lr(), f"bench-load-{req_rows}", req_rows)
        for req_rows in (1, 8, 64)
    ]

    # Fused-vs-unfused + pipeline-depth sweep on the depth-2 pipeline: the
    # fast-path acceptance contract is a p50 win for fastpath on at depth>=2
    # (fused executable + device-resident weights + pipelined dispatch) over
    # the per-stage transform path on the same pipeline.
    fused_sweep = []
    for fastpath, depth in ((False, 1), (True, 1), (True, 2), (True, 3)):
        leg = run_load(
            make_pipeline(),
            f"bench-fused-{int(fastpath)}-d{depth}",
            8,
            fastpath=fastpath,
            pipeline_depth=depth,
        )
        leg.update({"fastpath": fastpath, "pipeline_depth": depth})
        fused_sweep.append(leg)

    return {
        "name": "serving_microbatch_lr_d256",
        "threads": n_threads,
        "requests_per_thread": requests_per_thread,
        "max_batch_size": 64,
        "sweep": sweep,
        "fused_sweep": fused_sweep,
        "note": "end-to-end serving path (queue + micro-batch + pad + jit'd "
        "transform + slice); latency is enqueue->response per request from "
        "the ml.serving latency histogram. fused_sweep: depth-2 "
        "scaler->logistic pipeline, per-stage transform path (fastpath "
        "false) vs ONE fused AOT executable per bucket with device-resident "
        "weights, at dispatch windows 1-3",
    }


def bench_serving_open_loop():
    """Open-loop offered-load ramp x priority mix (docs/serving.md "Load
    shedding & adaptive control") — the serving number that closed-loop
    sweeps structurally cannot show.

    Every other serving row here is closed-loop: each client thread waits
    for its response before sending again, so the offered rate silently
    adapts to capacity and queueing collapse is invisible. This row drives
    the d=256 logistic servable with flink_ml_tpu.loadgen: seeded Poisson
    arrivals with a heavy-tailed (Zipf) size mix and a 70/30
    guaranteed/best-effort priority split, stepped to ~0.5x / 1x / 2x of a
    measured saturation estimate. Per step: achieved rows/s, p50/p99/p999
    latency, sheds, hard rejects, deadline misses and time-to-first-shed —
    the numbers a capacity plan is actually made of.
    """
    from flink_ml_tpu.api.dataframe import DataFrame
    from flink_ml_tpu.loadgen import OpenLoopLoadGenerator, ZipfSizes, ramp_schedule
    from flink_ml_tpu.servable.lib import LogisticRegressionModelServable
    from flink_ml_tpu.serving import InferenceServer, ServingConfig

    rng = np.random.default_rng(29)
    dim = 256
    X = rng.standard_normal((4096, dim)).astype(np.float32)

    def make_server(name):
        servable = LogisticRegressionModelServable().set_features_col("features")
        servable.coefficient = rng.standard_normal(dim).astype(np.float32)
        return InferenceServer(
            servable,
            name=name,
            serving_config=ServingConfig(
                max_batch_size=64,
                max_delay_ms=1.0,
                queue_capacity_rows=1024,
                default_timeout_ms=30_000,
                shed_sustain_ms=10.0,
            ),
            warmup_template=DataFrame.from_dict({"features": X[:1]}),
        )

    def request(rows):
        j = int(rng.integers(0, X.shape[0] - rows))
        return DataFrame.from_dict({"features": X[j : j + rows]})

    sizes = ZipfSizes((1, 2, 4, 8, 16, 32), alpha=1.5)

    # Calibration: a short deliberately-overloaded burst; the achieved
    # (completed) rows/s under it is the saturation estimate the ramp is
    # expressed against.
    cal_server = make_server("bench-ol-cal")
    try:
        cal_sched = ramp_schedule(
            [(4000.0, 1.0)], sizes=sizes, seed=1, priority_mix={0: 1.0}
        )
        cal_gen = OpenLoopLoadGenerator(cal_sched, request, timeout_ms=30_000.0)
        cal_report = cal_gen.run(cal_server)
        completed_rows = sum(
            s.offered_rows * (s.completed / max(s.arrivals, 1)) for s in cal_report.steps
        )
        saturation_rows_per_s = max(completed_rows / cal_report.wall_s, 1.0)
    finally:
        cal_server.close()
    sat_rps = saturation_rows_per_s / sizes.mean_rows

    server = make_server("bench-ol")
    try:
        steps = [(0.5 * sat_rps, 1.5), (1.0 * sat_rps, 1.5), (2.0 * sat_rps, 1.5)]
        sched = ramp_schedule(
            steps, sizes=sizes, priority_mix={0: 0.7, 1: 0.3}, seed=2
        )
        gen = OpenLoopLoadGenerator(
            sched, request, timeout_ms={0: 30_000.0, 1: 250.0}
        )
        report = gen.run(server)
        controller = server.controller
        sweep = []
        for s in report.steps:
            d = s.as_dict()
            d["offered_x_saturation"] = round(
                s.offered_rps * sizes.mean_rows / saturation_rows_per_s, 2
            )
            # achieved rows/s: the completed fraction of the step's offered rows
            d["achieved_rows_per_sec"] = round(
                s.offered_rows * (s.completed / max(s.arrivals, 1)) / max(s.duration_s, 1e-9),
                1,
            )
            sweep.append(d)
        actions = [
            {"kind": a.kind, "value": a.value, "reason": a.reason}
            for a in controller.actions
            if a.kind in ("depth", "bucket", "mesh.recommend", "shed")
        ][:16]
    finally:
        server.close()

    return {
        "name": "serving_open_loop_lr_d256",
        "saturation_rows_per_sec": round(saturation_rows_per_s, 1),
        "mean_request_rows": round(sizes.mean_rows, 3),
        "priority_mix": {"0": 0.7, "1": 0.3},
        "timeout_ms": {"0": 30000, "1": 250},
        "sweep": sweep,
        "controller_actions": actions,
        "fully_resolved": report.fully_resolved(),
        "note": "open-loop seeded Poisson ramp (flink_ml_tpu.loadgen) against "
        "the d=256 logistic fast path on a 1-core CPU host: absolute rows/s "
        "measures this box's XLA-CPU dispatch, not TPU serving capacity — "
        "the row exists for the SHAPE of the curve (p99/p999 blow-up past "
        "saturation, time-to-first-shed, shed-before-reject ordering, "
        "priority discipline under 2x overload), which is hardware-relative.",
    }


def bench_mlp_serving_throughput():
    """Throughput-mode MLP serving (VERDICT r6 item 8): the batched,
    weight-resident counterpart of ``mlp_forward``'s 0.0135-MFU latency shape.

    Same 256->512->512->8 network, served end-to-end through the
    InferenceServer at batched request sizes (64 rows, coalescing onto a
    256-row max bucket) from 4 client threads at saturation — so the number
    includes queueing, micro-batching, padding and readback, not just the
    matmuls. The fastpath leg keeps every layer's weights device-resident
    (one upload at swap) and serves one fused AOT program per bucket; the
    per-stage leg re-uploads weights per call — the throughput delta IS the
    weight-residency + AOT win. The same network architecture reproduces from
    the CLI alone via the JSON suite
    (``python -m flink_ml_tpu.benchmark flink_ml_tpu/benchmark/configs/
    mlpclassifier-benchmark.json``).
    """
    import threading

    from flink_ml_tpu.api.dataframe import DataFrame
    from flink_ml_tpu.metrics import MLMetrics, metrics
    from flink_ml_tpu.servable.lib import MLPClassifierModelServable
    from flink_ml_tpu.serving import InferenceServer, ServingConfig

    rng = np.random.default_rng(17)
    dims = (256, 512, 512, 8)
    servable = MLPClassifierModelServable()
    arrays = {"labels": np.arange(dims[-1], dtype=np.float64)}
    for i, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
        arrays[f"W{i}"] = (
            rng.normal(size=(d_in, d_out)) * np.sqrt(2.0 / d_in)
        ).astype(np.float32)
        arrays[f"b{i}"] = np.zeros(d_out, np.float32)
    X = rng.standard_normal((8192, dims[0])).astype(np.float32)

    n_threads = 4
    requests_per_thread = 60
    req_rows = 64

    def run_leg(fastpath):
        leg_servable = MLPClassifierModelServable()._apply_model_arrays(arrays)
        server = InferenceServer(
            leg_servable,
            name=f"bench-mlp-throughput-{int(fastpath)}",
            serving_config=ServingConfig(
                max_batch_size=256,
                max_delay_ms=1.0,
                queue_capacity_rows=16384,
                default_timeout_ms=120_000,
                fastpath=fastpath,
                pipeline_depth=2,
            ),
            warmup_template=DataFrame.from_dict({"features": X[:1]}),
        )
        try:
            barrier = threading.Barrier(n_threads + 1)

            def client(tid):
                barrier.wait()
                for i in range(requests_per_thread):
                    j = (tid * 997 + i * 193) % (X.shape[0] - req_rows)
                    server.predict(
                        DataFrame.from_dict({"features": X[j : j + req_rows]})
                    )

            threads = [
                threading.Thread(target=client, args=(t,)) for t in range(n_threads)
            ]
            for t in threads:
                t.start()
            barrier.wait()
            t0 = time.perf_counter()
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - t0
            scraped = metrics.scope(server.scope)
            lat = scraped[MLMetrics.SERVING_LATENCY_MS]
            total_rows = n_threads * requests_per_thread * req_rows
            return {
                "fastpath": fastpath,
                "rows_per_sec": round(total_rows / elapsed, 1),
                "latency_p50_ms": round(lat.quantile(0.5), 3),
                "latency_p99_ms": round(lat.quantile(0.99), 3),
                "mean_batch_rows": round(
                    total_rows / scraped[MLMetrics.SERVING_BATCHES], 1
                ),
                "fused_batches": scraped.get(MLMetrics.SERVING_FUSED_BATCHES, 0),
                "fastpath_compiles_post_warmup": scraped.get(
                    MLMetrics.SERVING_FASTPATH_COMPILES, 0
                ),
            }
        finally:
            server.close()

    legs = [run_leg(False), run_leg(True)]
    fused, per_stage = legs[1]["rows_per_sec"], legs[0]["rows_per_sec"]
    return {
        "name": "mlp_serving_throughput_b64_256_512_512_8",
        "threads": n_threads,
        "requests_per_thread": requests_per_thread,
        "request_rows": req_rows,
        "max_batch_size": 256,
        "legs": legs,
        "fused_vs_per_stage": round(fused / per_stage, 2) if per_stage else None,
        "note": "throughput counterpart of mlp_forward's latency shape: "
        "batched 64-row requests through the full serving path; fastpath leg "
        "= device-resident weights + one fused AOT program per bucket, "
        "per-stage leg re-uploads weights per call. Config-suite twin: "
        "mlpclassifier-benchmark.json trains/transforms the same network "
        "from the CLI.",
    }


def bench_continuous_loop():
    """Continuous learning loop (docs/continuous.md): the closed train →
    publish → AOT-warm → flip cycle at the Criteo-ish d=256 online-LR shape.

    What the row quantifies is the loop's *model logistics* cost: the
    publish→serve latency per version (save + poll + plan build + per-bucket
    AOT warm + atomic flip — the window in which the fleet serves the
    previous version), the pre-flip warm time itself, and the goodput
    fraction (productive train/serve time over total, the ML Productivity
    Goodput accounting). Serving-path compiles must be zero: every flip is
    warmed before activation.
    """
    import tempfile

    from flink_ml_tpu.api.dataframe import DataFrame
    from flink_ml_tpu.linalg.vectors import DenseVector
    from flink_ml_tpu.loop import ContinuousLearningLoop, ContinuousTrainer, DriftMonitor
    from flink_ml_tpu.metrics import MLMetrics, metrics
    from flink_ml_tpu.models.classification.online_logistic_regression import (
        OnlineLogisticRegression,
    )
    from flink_ml_tpu.models.online import QueueBatchStream
    from flink_ml_tpu.serving import InferenceServer, ServingConfig

    dim = 256
    rng = np.random.default_rng(23)
    true_w = rng.normal(size=dim) / np.sqrt(dim)

    def batch(n=4096, seed=0):
        r = np.random.default_rng(seed)
        X = r.normal(size=(n, dim))
        y = (X @ true_w > 0).astype(np.float64)
        return {"features": X.astype(np.float64), "label": y}

    n_versions = 6
    with tempfile.TemporaryDirectory() as tmp:
        scope = f"{MLMetrics.LOOP_GROUP}[bench]"
        stream = QueueBatchStream()
        for i in range(n_versions):
            stream.add(batch(seed=i))
        trainer = ContinuousTrainer(
            OnlineLogisticRegression()
            .set_initial_model_data(
                DataFrame(["coefficient"], None, [[DenseVector(np.zeros(dim))]])
            )
            .set_alpha(0.5)
            .set_global_batch_size(4096),
            stream,
            tmp + "/pub",
            publish_every_versions=1,
            scope=scope,
        )
        server = InferenceServer(
            name="bench-loop",
            serving_config=ServingConfig(max_batch_size=64, max_delay_ms=0.5),
            warmup_template=DataFrame.from_dict(
                {"features": batch(1, seed=99)["features"]}
            ),
        )
        loop = ContinuousLearningLoop(
            trainer,
            server,
            eval_source=lambda: DataFrame.from_dict(batch(64, seed=77)),
            name="bench",
            monitor=DriftMonitor(window=4, scope=scope),
        )
        t0 = time.perf_counter()
        loop.run(publish_target=n_versions, max_steps=n_versions + 2)
        elapsed = time.perf_counter() - t0
        scraped = metrics.scope(scope)
        hist = scraped[MLMetrics.LOOP_PUBLISH_TO_SERVE_MS]
        result = {
            "name": f"continuous_loop_lr_d{dim}",
            "versions_published": scraped[MLMetrics.LOOP_PUBLISHED],
            "versions_swapped": scraped[MLMetrics.LOOP_SWAPPED],
            "publish_to_serve_p50_ms": round(hist.quantile(0.5), 2),
            "publish_to_serve_p99_ms": round(hist.quantile(0.99), 2),
            "warm_ms_last": round(scraped[MLMetrics.LOOP_WARM_MS], 2),
            "goodput_fraction": round(scraped[MLMetrics.LOOP_GOODPUT_FRACTION], 4),
            "versions_per_sec": round(n_versions / elapsed, 2),
            "serving_path_compiles": metrics.get(
                server.scope, MLMetrics.SERVING_FASTPATH_COMPILES, 0
            ),
            "note": "closed train->publish->warm->flip loop; "
            "publish_to_serve is the stale-model window per version (save + "
            "poll + plan build + per-bucket AOT warm + atomic flip), "
            "goodput_fraction = productive/(productive+overhead) per the ML "
            "Productivity Goodput accounting; serving_path_compiles must be 0",
        }
        server.close()
        return result


def _make_feature6_stages(rng, d, n_docs=400_000):
    """The benched 6-stage feature chain (scaler → normalizer → weighting
    product → idf → rescale → binarizer) — shared by the fusion sweep and
    the cold-start bench so both rows name the same chain."""
    from flink_ml_tpu.models.feature.binarizer import Binarizer
    from flink_ml_tpu.models.feature.elementwise_product import ElementwiseProduct
    from flink_ml_tpu.models.feature.idf import IDFModel
    from flink_ml_tpu.models.feature.normalizer import Normalizer
    from flink_ml_tpu.models.feature.standard_scaler import StandardScalerModel

    scaler = StandardScalerModel().set_input_col("input").set_output_col("scaled")
    scaler.set_with_mean(True)
    scaler.mean = rng.standard_normal(d)
    scaler.std = np.abs(rng.standard_normal(d)) + 0.5
    idf = IDFModel().set_input_col("weighted").set_output_col("tfidf")
    idf.idf = np.abs(rng.standard_normal(d)) + 0.2
    idf.doc_freq = np.ones(d)
    idf.num_docs = np.asarray(float(n_docs))
    rescale = StandardScalerModel().set_input_col("tfidf").set_output_col("rescaled")
    rescale.set_with_mean(False)
    rescale.mean = np.zeros(d)
    rescale.std = np.abs(rng.standard_normal(d)) + 0.5
    return [
        scaler,
        Normalizer().set_input_col("scaled").set_output_col("norm"),
        ElementwiseProduct()
        .set_scaling_vec(np.abs(rng.standard_normal(d)) + 0.1)
        .set_input_col("norm")
        .set_output_col("weighted"),
        idf,
        rescale,
        Binarizer()
        .set_input_cols("rescaled")
        .set_output_cols("bin")
        .set_thresholds(0.05),
    ]


def bench_cold_start():
    """Persistent compiled-plan cache (docs/plancache.md): publish→first-
    response wall on the 6-stage feature chain + logistic head, three legs
    per fusion tier:

    - **cold cache** — a fresh plan-cache directory: every (program, bucket)
      pays trace + XLA compile + serialize/store. The pre-PR-14 restart cost
      plus the one-time store tax.
    - **warm cache** — a new "incarnation" (fresh servable/plan/server
      objects — fresh jit closures, so nothing rides the in-process jit
      cache) over the populated directory: every program loads its
      serialized executable; compiles drop to zero
      (``ml.plancache.misses`` asserted unchanged).
    - **in-process warm** — the same server again: the steady-state request
      path, for scale.

    Honest 1-core-box note: on this CPU backend the warm leg still pays
    tracing/lowering per program (the digest is the lowered StableHLO — see
    docs/plancache.md), so the win is the compile term only; on real TPUs
    the compile term is 10-100× larger and the ratio grows with it. The
    fast+mega tier reports whether interpret-mode megakernel executables
    serialized or fell back to live compiles (store_errors).
    """
    import os
    import shutil
    import tempfile

    from flink_ml_tpu.api.dataframe import DataFrame
    from flink_ml_tpu.config import Options, config
    from flink_ml_tpu.metrics import MLMetrics, metrics
    from flink_ml_tpu.servable.builder import PipelineModelServable
    from flink_ml_tpu.servable.fusion import FusionTier
    from flink_ml_tpu.servable.lib import LogisticRegressionModelServable
    from flink_ml_tpu.serving import InferenceServer, ServingConfig
    from flink_ml_tpu.serving.plan import CompiledServingPlan

    d = 32
    max_batch = 16
    rng = np.random.default_rng(31)
    template = DataFrame.from_dict({"input": rng.standard_normal((1, d))})
    request = DataFrame.from_dict({"input": rng.standard_normal((max_batch, d))})

    def make_servable():
        stage_rng = np.random.default_rng(77)
        lr = LogisticRegressionModelServable().set_features_col("bin")
        lr.coefficient = stage_rng.standard_normal(d)
        return PipelineModelServable(_make_feature6_stages(stage_rng, d) + [lr])

    def leg(name, fusion, repeats=3):
        """One (cold, warm, steady) measurement set for a fusion tier."""
        colds, warms = [], []
        pc_scope = MLMetrics.PLANCACHE_GROUP
        cold_stores = warm_miss = store_errors = 0
        base_dir = tempfile.mkdtemp(prefix=f"bench-plancache-{name}-")
        steady_ms = []
        try:
            for r in range(repeats):
                # A fresh, never-seen directory per repeat: the cold leg
                # must start from an empty cache every time.
                config.set(Options.PLANCACHE_DIR, os.path.join(base_dir, f"r{r}"))

                def first_response(tag):
                    t0 = time.perf_counter()
                    server = InferenceServer(
                        make_servable(),
                        name=f"bench-cold-{name}-{tag}",
                        serving_config=ServingConfig(
                            max_batch_size=max_batch,
                            max_delay_ms=0.1,
                            fusion_mode=fusion.mode if fusion else None,
                        ),
                        warmup_template=template,
                    )
                    server.predict(request)
                    wall = time.perf_counter() - t0
                    return server, wall

                if fusion is not None:
                    config.set(Options.FUSION_MEGAKERNEL_MIN_SCORE, 1.0)
                e0 = metrics.get(pc_scope, MLMetrics.PLANCACHE_STORE_ERRORS, 0)
                s0 = metrics.get(pc_scope, MLMetrics.PLANCACHE_STORES, 0)
                server, cold_s = first_response(f"c{r}")
                colds.append(cold_s)
                cold_stores = metrics.get(pc_scope, MLMetrics.PLANCACHE_STORES, 0) - s0
                store_errors = metrics.get(pc_scope, MLMetrics.PLANCACHE_STORE_ERRORS, 0) - e0
                server.close()
                m0 = metrics.get(pc_scope, MLMetrics.PLANCACHE_MISSES, 0)
                server, warm_s = first_response(f"w{r}")
                warms.append(warm_s)
                warm_miss = metrics.get(pc_scope, MLMetrics.PLANCACHE_MISSES, 0) - m0
                if r == repeats - 1:
                    for _ in range(20):
                        t0 = time.perf_counter()
                        server.predict(request)
                        steady_ms.append((time.perf_counter() - t0) * 1000.0)
                server.close()
        finally:
            config.unset(Options.PLANCACHE_DIR)
            config.unset(Options.FUSION_MEGAKERNEL_MIN_SCORE)
            shutil.rmtree(base_dir, ignore_errors=True)
        cold = sorted(colds)[len(colds) // 2]
        warm = sorted(warms)[len(warms) // 2]
        return {
            "cold_publish_to_first_response_s": round(cold, 3),
            "warm_publish_to_first_response_s": round(warm, 3),
            "in_process_warm_p50_ms": round(sorted(steady_ms)[len(steady_ms) // 2], 3),
            "speedup_warm_vs_cold": round(cold / warm, 2),
            "cold_stores": cold_stores,
            "warm_live_compiles": warm_miss,
            "store_errors": store_errors,
        }

    exact = leg("exact", None)
    mega = leg("mega", FusionTier("fast", megakernel=True, min_score=1.0))
    mega["note"] = (
        "interpret-mode Pallas megakernel executables "
        + (
            "serialized and resumed from cache"
            if mega["store_errors"] == 0 and mega["warm_live_compiles"] == 0
            else f"fell back to live compiles for {max(mega['store_errors'], mega['warm_live_compiles'])} program(s)"
        )
    )
    return {
        "name": "cold_start_feature6_logistic",
        "chain": "6-stage feature chain + logistic head, d=32, buckets 1..16",
        "exact": exact,
        "fast_mega": mega,
        "note": "publish->first-response wall per leg (server build + plan "
        "build + per-bucket AOT warm + first request). warm = fresh "
        "servable/plan/server objects over a populated plancache.dir (fresh "
        "jit closures — nothing rides the in-process jit cache); "
        "warm_live_compiles must be 0. 1-core-box note: the warm leg still "
        "pays per-program trace/lowering (the digest is the lowered "
        "StableHLO), so the ratio here prices the XLA-compile term only — "
        "it grows with compile cost on real accelerators.",
    }


def bench_pipeline_batch_transform():
    """Batch transform fast path (docs/batch_transform.md): fused chunked
    CompiledBatchPlan vs the per-stage transform path on a 6-stage feature
    chain (scaler → normalizer → weighting product → idf → rescale →
    binarizer), 400k x 32 (columns several times last-level cache, so both legs run at DRAM bandwidth and the fused plan's ~2x traffic advantage is what the ratio measures).

    The per-stage path pays, per stage: a host gather + f64 astype of its
    input column, a jit dispatch, a blocking ``np.asarray`` readback and a
    full host DataFrame materialization. The fused plan pays one ingest + one
    readback per chunk with columns staying device-resident across all six
    stages (the five elementwise stages merge into reduction-free XLA
    programs; the normalizer's row-norm reduction keeps its own), and
    overlaps chunk j+1's host ingest with chunk j's execution
    (``batch.prefetch.depth``). Reports rows/s for both legs plus a
    chunk-rows × prefetch-depth sweep with p50 per-chunk latency from the
    plan's own ``ml.batch.fastpath`` histogram.

    On a single-core host the whole bench runs with synchronous CPU dispatch
    (restored on exit): the async dispatch thread buys no overlap with one
    core — both legs block on every readback anyway — and its context
    switches tax the fused path's many short program calls 30-40%.
    """
    import os

    import jax

    if (os.cpu_count() or 1) == 1:
        jax.config.update("jax_cpu_enable_async_dispatch", False)
        try:
            return _bench_pipeline_batch_transform_body()
        finally:
            jax.config.update("jax_cpu_enable_async_dispatch", True)
    return _bench_pipeline_batch_transform_body()


def _bench_pipeline_batch_transform_body():
    from flink_ml_tpu.api.dataframe import DataFrame
    from flink_ml_tpu.builder.batch_plan import CompiledBatchPlan
    from flink_ml_tpu.config import Options, config
    from flink_ml_tpu.metrics import MLMetrics, metrics

    rng = np.random.default_rng(9)
    n, d = 400_000, 32
    df = DataFrame.from_dict({"input": rng.standard_normal((n, d))})

    # Same rng draw order as the old inline construction — identical params.
    stages = _make_feature6_stages(rng, d, n_docs=n)

    def run_per_stage():
        out = df
        for stage in stages:
            out = stage.transform(out)
        return out

    def fused_leg(chunk_rows, depth, scope):
        config.set(Options.BATCH_CHUNK_ROWS, chunk_rows)
        config.set(Options.BATCH_PREFETCH_DEPTH, depth)
        try:
            plan = CompiledBatchPlan.build(stages, scope=scope)
            plan.transform(df)  # warm: compiles both chunk signatures
            t, spread = _median_time_spread(lambda: plan.transform(df), repeats=3)
            hist = metrics.get(scope, MLMetrics.BATCH_CHUNK_MS)
            return {
                "chunk_rows": chunk_rows,
                "prefetch_depth": depth,
                "rows_per_sec": round(n / t, 1),
                "spread": spread,
                "chunk_p50_ms": round(hist.quantile(0.5), 3) if hist else None,
                "compiles": metrics.get(scope, MLMetrics.BATCH_COMPILES, 0),
            }
        finally:
            config.unset(Options.BATCH_CHUNK_ROWS)
            config.unset(Options.BATCH_PREFETCH_DEPTH)

    # Headline: per-stage vs fused at the config DEFAULTS. The box is
    # time-shared and ambient load swings wall time 3x on a ~100 ms sample,
    # so the protocol is interleaved best-of-N: alternate the legs (so load
    # bursts hit both) and take each leg's MINIMUM — the run with the least
    # interference, the best estimate of true cost on a noisy host (the
    # pyperf min protocol). Medians are reported alongside for honesty.
    plan = CompiledBatchPlan.build(stages, scope="ml.batch[bench-main]")
    for _ in range(2):  # warm twice: jit caches + chunk signatures on the
        run_per_stage()  # first pass, allocator/arena steady state on the
        plan.transform(df)  # second (first-call-after-compile runs ~20% cold)
    ps_times, fu_times = [], []
    for _ in range(9):
        t0 = time.perf_counter()
        run_per_stage()
        ps_times.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        plan.transform(df)
        fu_times.append(time.perf_counter() - t0)
    ps_times.sort()
    fu_times.sort()
    t_ps, t_fu = ps_times[0], fu_times[0]
    hist = metrics.get("ml.batch[bench-main]", MLMetrics.BATCH_CHUNK_MS)
    per_stage = {
        "rows_per_sec": round(n / t_ps, 1),
        "spread": {
            "min_s": round(ps_times[0], 4),
            "median_s": round(ps_times[len(ps_times) // 2], 4),
            "max_s": round(ps_times[-1], 4),
            "repeats": len(ps_times),
        },
    }
    fused = {
        "rows_per_sec": round(n / t_fu, 1),
        "spread": {
            "min_s": round(fu_times[0], 4),
            "median_s": round(fu_times[len(fu_times) // 2], 4),
            "max_s": round(fu_times[-1], 4),
            "repeats": len(fu_times),
        },
        "chunk_p50_ms": round(hist.quantile(0.5), 3) if hist else None,
    }
    sweep = [
        fused_leg(chunk_rows, depth, f"ml.batch[bench-{chunk_rows}-{depth}]")
        for chunk_rows in (8_192, 32_768, 131_072)
        for depth in (1, 2)
    ]
    return {
        "name": "pipeline_batch_transform_6stage_d32",
        "rows": n,
        "dim": d,
        "stages": 6,
        "per_stage_rows_per_sec": per_stage["rows_per_sec"],
        "per_stage_spread": per_stage["spread"],
        "fused_rows_per_sec": fused["rows_per_sec"],
        "fused_spread": fused["spread"],
        "fused_chunk_p50_ms": fused["chunk_p50_ms"],
        "fused_vs_per_stage": round(
            fused["rows_per_sec"] / per_stage["rows_per_sec"], 2
        ),
        "sweep": sweep,
        "note": "per-stage = today's PipelineModel.transform loop (jit + "
        "readback + DataFrame per stage); fused = CompiledBatchPlan, one "
        "ingest/readback per chunk, columns device-resident across stages, "
        "double-buffered chunk prefetch. Bit-exactness of the two paths is "
        "pinned by tests/test_batch_fastpath.py.",
    }


def bench_sparse_pipelines():
    """Sparse/ragged fast path (docs/sparse.md): the two acceptance
    workloads, fused (sparse calling convention: ELL triples on the nnz-cap
    ladder, segment-reduce kernels, chains device-resident end to end) vs
    the per-stage fallback path, batch tier.

    - ``sparse_text_pipeline``: tokenize → hashingTF → IDF → logistic over
      ragged documents. Both legs pay the same host tokenize+hash featurize;
      the fused leg's win is everything downstream — no SparseVector
      materialization between stages, the counts/idf/margin chain as three
      AOT programs over the packed triple. An nnz-cap sweep sizes the
      ladder-padding cost.
    - ``sparse_ctr_pipeline``: one-hot → interaction → logistic (the CTR
      shape, nnz 1 per one-hot, cross dim = cats_a × cats_b never
      densified in the fused leg).

    Single-core hosts run with synchronous CPU dispatch like the other batch
    benches (restored on exit).
    """
    import os

    import jax

    if (os.cpu_count() or 1) == 1:
        jax.config.update("jax_cpu_enable_async_dispatch", False)
        try:
            return _bench_sparse_pipelines_body()
        finally:
            jax.config.update("jax_cpu_enable_async_dispatch", True)
    return _bench_sparse_pipelines_body()


def _bench_sparse_pipelines_body():
    from flink_ml_tpu.api.dataframe import DataFrame
    from flink_ml_tpu.builder.pipeline import Pipeline
    from flink_ml_tpu.config import Options, config
    from flink_ml_tpu.models.classification.logistic_regression import LogisticRegression
    from flink_ml_tpu.models.feature.hashing_tf import HashingTF
    from flink_ml_tpu.models.feature.idf import IDF
    from flink_ml_tpu.models.feature.interaction import Interaction
    from flink_ml_tpu.models.feature.one_hot_encoder import OneHotEncoder
    from flink_ml_tpu.models.feature.tokenizer import Tokenizer

    rng = np.random.default_rng(29)
    words = [f"w{i:03d}" for i in range(64)]

    def text_df(n, tokens_per_doc):
        docs = [
            " ".join(rng.choice(words, size=tokens_per_doc)) for _ in range(n)
        ]
        return DataFrame.from_dict(
            {"text": docs, "label": rng.integers(0, 2, n).astype(np.float64)}
        )

    def both_legs(model, df, repeats=3):
        n = len(df)
        config.set(Options.BATCH_FASTPATH, False)
        model.transform(df)  # warm per-stage jit caches
        t_slow, slow_spread = _median_time_spread(
            lambda: model.transform(df), repeats=repeats
        )
        config.set(Options.BATCH_FASTPATH, True)
        model.invalidate_batch_plan()
        model.transform(df)  # warm: compiles the chunk signatures
        t_fast, fast_spread = _median_time_spread(
            lambda: model.transform(df), repeats=repeats
        )
        config.unset(Options.BATCH_FASTPATH)
        return {
            "per_stage_rows_per_sec": round(n / t_slow, 1),
            "fused_rows_per_sec": round(n / t_fast, 1),
            "fused_vs_per_stage": round(t_slow / t_fast, 3),
            "per_stage_spread": slow_spread,
            "fused_spread": fast_spread,
        }

    # -- text ----------------------------------------------------------------
    n_text, dim = 50_000, 4096
    fit_df = text_df(2_000, 8)
    text_model = Pipeline(
        [
            Tokenizer().set_input_col("text").set_output_col("tokens"),
            HashingTF().set_input_col("tokens").set_output_col("tf").set_num_features(dim),
            IDF().set_input_col("tf").set_output_col("feat"),
            LogisticRegression()
            .set_features_col("feat")
            .set_label_col("label")
            .set_prediction_col("pred")
            .set_raw_prediction_col("raw")
            .set_max_iter(2),
        ]
    ).fit(fit_df)
    headline = both_legs(text_model, text_df(n_text, 8))
    cap_sweep = []
    for tokens in (4, 16, 64):
        config.set(Options.SPARSE_NNZ_CAP_MAX, 64)
        legs = both_legs(text_model, text_df(n_text // 5, tokens), repeats=3)
        config.unset(Options.SPARSE_NNZ_CAP_MAX)
        legs["tokens_per_doc"] = tokens
        from flink_ml_tpu.linalg.sparse_batch import ladder_cap

        legs["nnz_cap"] = ladder_cap(tokens)
        cap_sweep.append(legs)
    text = {
        "name": "sparse_text_pipeline",
        "chain": f"tokenize->hashingTF(d={dim})->idf->logistic, {n_text} docs x 8 tokens",
        **headline,
        "nnz_cap_sweep": cap_sweep,
        "note": (
            "both legs pay the same host tokenize+hash featurize; the fused "
            "leg chains counts/idf/margin on device over the packed ELL "
            "triple with zero SparseVector materialization between stages. "
            "1-core box: ratios are directional; the host featurize share "
            "shrinks (and the fused win grows) with vocabulary/doc size."
        ),
    }

    # -- CTR -----------------------------------------------------------------
    n_ctr, cats = 200_000, (1000, 500)
    fit = DataFrame.from_dict(
        {
            "ad": rng.integers(0, cats[0], 4_000).astype(np.float64),
            "user": rng.integers(0, cats[1], 4_000).astype(np.float64),
            "label": rng.integers(0, 2, 4_000).astype(np.float64),
        }
    )
    ctr_model = Pipeline(
        [
            OneHotEncoder()
            .set_input_cols("ad", "user")
            .set_output_cols("ad_v", "user_v")
            .set_handle_invalid("keep")
            .set_drop_last(False),
            Interaction().set_input_cols("ad_v", "user_v").set_output_col("cross"),
            LogisticRegression()
            .set_features_col("cross")
            .set_label_col("label")
            .set_prediction_col("pred")
            .set_raw_prediction_col("raw")
            .set_max_iter(2),
        ]
    ).fit(fit)
    ctr_df = DataFrame.from_dict(
        {
            "ad": rng.integers(0, cats[0], n_ctr).astype(np.float64),
            "user": rng.integers(0, cats[1], n_ctr).astype(np.float64),
        }
    )
    ctr = {
        "name": "sparse_ctr_pipeline",
        "chain": (
            f"one-hot({cats[0]},{cats[1]})->interaction(cross dim "
            f"{cats[0] * cats[1]})->logistic, {n_ctr} rows"
        ),
        **both_legs(ctr_model, ctr_df),
        "note": (
            "nnz 1 per one-hot; the fused leg never densifies the "
            f"{cats[0] * cats[1]}-dim cross — margins ride the "
            "gather-scale-segment-sum head at cap 1. 1-core box note as above."
        ),
    }
    out = {"name": "sparse_pipelines", "workloads": [text, ctr]}
    print(json.dumps(out, indent=1))
    return out


def bench_fusion_sweep():
    """Fusion tiers (docs/fusion.md): ``fusion.mode=exact`` vs ``fast`` vs
    ``fast`` with Pallas megakernels forced hot, on the two benched chains —
    the 6-stage feature chain (400k × 32, chunked batch transform) and the
    serving heads (scaler → logistic d=32 and scaler → MLP 256→512→512→8 at
    bucket 64, p50/p99 per batch).

    What each leg measures on this box: the exact tier compiles one program
    per reduction-bearing stage (3 programs for the 6-stage chain, 2 for each
    serving head); the fast tier merges each chain into ONE XLA program —
    the win here is per-program dispatch + XLA fusing elementwise math into
    the neighbouring reduction. The megakernel leg runs under
    ``pallas.interpret`` on CPU (the tier-1 fallback): it proves the code
    path and prices the interpreter, NOT the VMEM-residency win — on real
    TPUs the megakernel is where the BENCH_r05 flash-attention-style 4.7×
    lives. Ulp envelopes of every fast leg are pinned by
    tests/test_fusion.py.
    """
    import os

    import jax

    if (os.cpu_count() or 1) == 1:
        jax.config.update("jax_cpu_enable_async_dispatch", False)
        try:
            return _bench_fusion_sweep_body()
        finally:
            jax.config.update("jax_cpu_enable_async_dispatch", True)
    return _bench_fusion_sweep_body()


def _bench_fusion_sweep_body():
    from flink_ml_tpu.api.dataframe import DataFrame
    from flink_ml_tpu.builder.batch_plan import CompiledBatchPlan
    from flink_ml_tpu.metrics import MLMetrics, metrics
    from flink_ml_tpu.servable.builder import PipelineModelServable
    from flink_ml_tpu.servable.fusion import FusionTier, ULP_ENVELOPE
    from flink_ml_tpu.servable.lib import (
        LogisticRegressionModelServable,
        MLPClassifierModelServable,
        StandardScalerModelServable,
    )
    from flink_ml_tpu.serving.plan import CompiledServingPlan

    rng = np.random.default_rng(9)
    n, d = 400_000, 32
    df = DataFrame.from_dict({"input": rng.standard_normal((n, d))})

    # Same rng draw order as the old inline construction — identical params.
    stages = _make_feature6_stages(rng, d, n_docs=n)

    tiers = {
        "exact": None,
        "fast": FusionTier("fast", megakernel=False),
        "megakernel": FusionTier("fast", megakernel=True, min_score=1.0),
    }

    # Batch chain: interleaved best-of-N (the pyperf min protocol of
    # pipeline_batch_transform — this box's ambient load swings 3x).
    plans = {
        name: CompiledBatchPlan.build(stages, scope=f"ml.batch[fusion-{name}]", fusion=tier)
        for name, tier in tiers.items()
    }
    for plan in plans.values():  # warm both chunk signatures, twice
        plan.transform(df)
        plan.transform(df)
    times = {name: [] for name in plans}
    for _ in range(7):
        for name, plan in plans.items():
            t0 = time.perf_counter()
            plan.transform(df)
            times[name].append(time.perf_counter() - t0)
    batch_rows = {}
    for name, ts in times.items():
        ts.sort()
        batch_rows[name] = {
            "rows_per_sec": round(n / ts[0], 1),
            "spread": {
                "min_s": round(ts[0], 4),
                "median_s": round(ts[len(ts) // 2], 4),
                "max_s": round(ts[-1], 4),
                "repeats": len(ts),
            },
            "programs_per_chunk": (
                len(plans[name].segments[0].programs)
            ),
            "megakernel_compiles": metrics.get(
                f"ml.batch[fusion-{name}]", MLMetrics.FUSION_PROGRAMS_MEGAKERNEL, 0
            ),
        }

    # Serving heads: closed-loop p50/p99 per 64-row batch through the
    # compiled plan (the micro-batcher's exec step, isolated).
    def serving_chain(servable, dim, reps=400):
        r = np.random.default_rng(1)
        batch = DataFrame.from_dict({"features": r.standard_normal((64, dim))})
        out = {}
        for name, tier in tiers.items():
            plan = CompiledServingPlan.build(
                servable, scope=f"ml.serving[fusion-{name}]", fusion=tier
            )
            plan.execute(batch)
            plan.execute(batch)
            lat = []
            for _ in range(reps):
                t0 = time.perf_counter()
                plan.execute(batch)
                lat.append((time.perf_counter() - t0) * 1e3)
            lat.sort()
            p50 = lat[len(lat) // 2]
            out[name] = {
                "latency_p50_ms": round(p50, 4),
                "latency_p99_ms": round(lat[int(len(lat) * 0.99)], 4),
                "rows_per_sec": round(64 / (p50 / 1e3), 1),
            }
        return out

    sc = StandardScalerModelServable().set_input_col("features").set_output_col("scaled")
    sc.set_with_mean(True)
    sc.mean = rng.standard_normal(d)
    sc.std = np.abs(rng.standard_normal(d)) + 0.5
    lr = LogisticRegressionModelServable().set_features_col("scaled")
    lr.coefficient = rng.standard_normal(d)
    lr_rows = serving_chain(PipelineModelServable([sc, lr]), d)

    sc2 = StandardScalerModelServable().set_input_col("features").set_output_col("scaled")
    sc2.set_with_mean(True)
    sc2.mean = rng.standard_normal(256)
    sc2.std = np.abs(rng.standard_normal(256)) + 0.5
    mlp = MLPClassifierModelServable().set_features_col("scaled")
    dims = [256, 512, 512, 8]
    arrays = {"labels": np.arange(8.0)}
    for i in range(3):
        arrays[f"W{i}"] = (
            rng.standard_normal((dims[i], dims[i + 1])) / np.sqrt(dims[i])
        ).astype(np.float32)
        arrays[f"b{i}"] = rng.standard_normal(dims[i + 1]).astype(np.float32)
    mlp._apply_model_arrays(arrays)
    mlp_rows = serving_chain(PipelineModelServable([sc2, mlp]), 256)

    return {
        "name": "fusion_sweep",
        "batch_6stage_400k_d32": batch_rows,
        "batch_fast_vs_exact": round(
            batch_rows["fast"]["rows_per_sec"] / batch_rows["exact"]["rows_per_sec"], 3
        ),
        "serving_scale_logistic_d32_b64": lr_rows,
        "serving_logistic_fast_vs_exact": round(
            lr_rows["fast"]["rows_per_sec"] / lr_rows["exact"]["rows_per_sec"], 3
        ),
        "serving_scale_mlp_256_512_512_8_b64": mlp_rows,
        "serving_mlp_fast_vs_exact": round(
            mlp_rows["fast"]["rows_per_sec"] / mlp_rows["exact"]["rows_per_sec"], 3
        ),
        "ulp_envelopes": dict(ULP_ENVELOPE),
        "note": "exact = per-stage programs (bit-identical to the per-stage "
        "path); fast = ONE cross-reduction XLA program per fusable chain "
        "(ulp-envelope numerics, tests/test_fusion.py); megakernel = the "
        "same chain as ONE Pallas kernel — on this CPU box it runs "
        "interpret-mode (code-path proof + interpreter price; the batch leg "
        "is expected SLOWER than fast), on TPU it is the VMEM-residency "
        "tier. The fast-vs-exact ratios are the honest CPU win: mostly "
        "saved per-program dispatch.",
    }


def bench_precision_sweep():
    """Precision tiers (docs/precision.md): ``precision.mode=f32`` vs
    ``bf16`` vs ``int8`` on the four benched chains — the serving heads
    (scaler → logistic d=32 and scaler → MLP 256→512→512→8 at bucket 64,
    p50/p99 per batch), the 6-stage feature chain (400k × 32, chunked batch
    transform), and the fused sparse CTR chain (one-hot → interaction →
    logistic, config-resolved tier through the Pipeline fast path).

    What each leg measures on this box: bf16 rounds activations to the bf16
    grid at ingest and every unfused stage boundary with f32 accumulation
    inside each program; int8 is the same transport over publish-time
    dequantized int8 weights (the serving path never quantizes — the int8
    serving legs here run weights through ``quantize_array_int8`` /
    ``quantize_model_arrays`` exactly as ``publish_servable(...,
    precision="int8")`` would). Ulp envelopes of every lowp leg are pinned
    by tests/test_precision.py.
    """
    import os

    import jax

    if (os.cpu_count() or 1) == 1:
        jax.config.update("jax_cpu_enable_async_dispatch", False)
        try:
            return _bench_precision_sweep_body()
        finally:
            jax.config.update("jax_cpu_enable_async_dispatch", True)
    return _bench_precision_sweep_body()


def _bench_precision_sweep_body():
    from flink_ml_tpu.api.dataframe import DataFrame
    from flink_ml_tpu.builder.batch_plan import CompiledBatchPlan
    from flink_ml_tpu.builder.pipeline import Pipeline
    from flink_ml_tpu.config import Options, config
    from flink_ml_tpu.models.classification.logistic_regression import LogisticRegression
    from flink_ml_tpu.models.feature.interaction import Interaction
    from flink_ml_tpu.models.feature.one_hot_encoder import OneHotEncoder
    from flink_ml_tpu.servable.builder import PipelineModelServable
    from flink_ml_tpu.servable.lib import (
        LogisticRegressionModelServable,
        MLPClassifierModelServable,
        StandardScalerModelServable,
    )
    from flink_ml_tpu.servable.precision import (
        PRECISION_TIER_DEVIATION,
        PrecisionTier,
        quantize_array_int8,
        quantize_model_arrays,
    )
    from flink_ml_tpu.serving.plan import CompiledServingPlan

    rng = np.random.default_rng(31)
    n, d = 400_000, 32
    tiers = {
        "f32": PrecisionTier("f32"),
        "bf16": PrecisionTier("bf16"),
        "int8": PrecisionTier("int8"),
    }

    # Serving heads: closed-loop p50/p99 per 64-row batch through the
    # compiled plan (the micro-batcher's exec step, isolated). One servable
    # per tier because the int8 leg serves different (publish-quantized)
    # weights — same params across the f32/bf16 pair.
    def serving_chain(servables, dim, reps=400):
        r = np.random.default_rng(1)
        batch = DataFrame.from_dict({"features": r.standard_normal((64, dim))})
        out = {}
        for name, tier in tiers.items():
            plan = CompiledServingPlan.build(
                servables[name], scope=f"ml.serving[precision-{name}]", precision=tier
            )
            plan.execute(batch)
            plan.execute(batch)
            lat = []
            for _ in range(reps):
                t0 = time.perf_counter()
                plan.execute(batch)
                lat.append((time.perf_counter() - t0) * 1e3)
            lat.sort()
            p50 = lat[len(lat) // 2]
            out[name] = {
                "latency_p50_ms": round(p50, 4),
                "latency_p99_ms": round(lat[int(len(lat) * 0.99)], 4),
                "rows_per_sec": round(64 / (p50 / 1e3), 1),
            }
        return out

    mean = rng.standard_normal(d)
    std = np.abs(rng.standard_normal(d)) + 0.5
    coef = rng.standard_normal(d)
    coef_q, _ = quantize_array_int8(coef)

    def scale_logistic(coefficient):
        sc = StandardScalerModelServable().set_input_col("features").set_output_col("scaled")
        sc.set_with_mean(True)
        sc.mean = mean
        sc.std = std
        lr = LogisticRegressionModelServable().set_features_col("scaled")
        lr.coefficient = coefficient
        return PipelineModelServable([sc, lr])

    lr_rows = serving_chain(
        {"f32": scale_logistic(coef), "bf16": scale_logistic(coef), "int8": scale_logistic(coef_q)},
        d,
    )

    mean2 = rng.standard_normal(256)
    std2 = np.abs(rng.standard_normal(256)) + 0.5
    dims = [256, 512, 512, 8]
    arrays = {"labels": np.arange(8.0)}
    for i in range(3):
        arrays[f"W{i}"] = (
            rng.standard_normal((dims[i], dims[i + 1])) / np.sqrt(dims[i])
        ).astype(np.float32)
        arrays[f"b{i}"] = rng.standard_normal(dims[i + 1]).astype(np.float32)
    arrays_q, _ = quantize_model_arrays(arrays)

    def scale_mlp(model_arrays):
        sc = StandardScalerModelServable().set_input_col("features").set_output_col("scaled")
        sc.set_with_mean(True)
        sc.mean = mean2
        sc.std = std2
        mlp = MLPClassifierModelServable().set_features_col("scaled")
        mlp._apply_model_arrays(model_arrays)
        return PipelineModelServable([sc, mlp])

    mlp_rows = serving_chain(
        {"f32": scale_mlp(arrays), "bf16": scale_mlp(arrays), "int8": scale_mlp(arrays_q)},
        256,
    )

    # Batch chain: interleaved best-of-N over the 6-stage feature chain (the
    # pyperf min protocol — this box's ambient load swings 3x). The chain
    # has no int8-eligible weights, so the int8 leg prices the same bf16
    # transport (the ≡-bf16 row in PRECISION_TIER_DEVIATION).
    df = DataFrame.from_dict({"input": rng.standard_normal((n, d))})
    stages = _make_feature6_stages(rng, d, n_docs=n)
    plans = {
        name: CompiledBatchPlan.build(
            stages, scope=f"ml.batch[precision-{name}]", precision=tier
        )
        for name, tier in tiers.items()
    }
    for plan in plans.values():  # warm both chunk signatures, twice
        plan.transform(df)
        plan.transform(df)
    times = {name: [] for name in plans}
    for _ in range(7):
        for name, plan in plans.items():
            t0 = time.perf_counter()
            plan.transform(df)
            times[name].append(time.perf_counter() - t0)
    batch_rows = {}
    for name, ts in times.items():
        ts.sort()
        batch_rows[name] = {
            "rows_per_sec": round(n / ts[0], 1),
            "spread": {
                "min_s": round(ts[0], 4),
                "median_s": round(ts[len(ts) // 2], 4),
                "max_s": round(ts[-1], 4),
                "repeats": len(ts),
            },
        }

    # Sparse CTR chain through the Pipeline fused path, tier resolved from
    # precision.mode config — the deployment route (docs/precision.md:
    # weights quantize at publish only, so this leg's int8 measures the
    # bf16 transport over the packed ELL triple).
    n_ctr, cats = 200_000, (1000, 500)
    fit = DataFrame.from_dict(
        {
            "ad": rng.integers(0, cats[0], 4_000).astype(np.float64),
            "user": rng.integers(0, cats[1], 4_000).astype(np.float64),
            "label": rng.integers(0, 2, 4_000).astype(np.float64),
        }
    )
    ctr_model = Pipeline(
        [
            OneHotEncoder()
            .set_input_cols("ad", "user")
            .set_output_cols("ad_v", "user_v")
            .set_handle_invalid("keep")
            .set_drop_last(False),
            Interaction().set_input_cols("ad_v", "user_v").set_output_col("cross"),
            LogisticRegression()
            .set_features_col("cross")
            .set_label_col("label")
            .set_prediction_col("pred")
            .set_raw_prediction_col("raw")
            .set_max_iter(2),
        ]
    ).fit(fit)
    ctr_df = DataFrame.from_dict(
        {
            "ad": rng.integers(0, cats[0], n_ctr).astype(np.float64),
            "user": rng.integers(0, cats[1], n_ctr).astype(np.float64),
        }
    )
    ctr_rows = {}
    config.set(Options.BATCH_FASTPATH, True)
    try:
        for name in tiers:
            if name == "f32":
                config.unset(Options.PRECISION_MODE)
            else:
                config.set(Options.PRECISION_MODE, name)
            ctr_model.invalidate_batch_plan()
            ctr_model.transform(ctr_df)  # warm: compiles the chunk signatures
            t, spread = _median_time_spread(
                lambda: ctr_model.transform(ctr_df), repeats=3
            )
            ctr_rows[name] = {
                "fused_rows_per_sec": round(n_ctr / t, 1),
                "spread": spread,
            }
    finally:
        config.unset(Options.PRECISION_MODE)
        config.unset(Options.BATCH_FASTPATH)

    return {
        "name": "precision_sweep",
        "serving_scale_logistic_d32_b64": lr_rows,
        "serving_logistic_bf16_vs_f32": round(
            lr_rows["bf16"]["rows_per_sec"] / lr_rows["f32"]["rows_per_sec"], 3
        ),
        "serving_logistic_int8_vs_f32": round(
            lr_rows["int8"]["rows_per_sec"] / lr_rows["f32"]["rows_per_sec"], 3
        ),
        "serving_scale_mlp_256_512_512_8_b64": mlp_rows,
        "serving_mlp_bf16_vs_f32": round(
            mlp_rows["bf16"]["rows_per_sec"] / mlp_rows["f32"]["rows_per_sec"], 3
        ),
        "serving_mlp_int8_vs_f32": round(
            mlp_rows["int8"]["rows_per_sec"] / mlp_rows["f32"]["rows_per_sec"], 3
        ),
        "batch_6stage_400k_d32": batch_rows,
        "batch_bf16_vs_f32": round(
            batch_rows["bf16"]["rows_per_sec"] / batch_rows["f32"]["rows_per_sec"], 3
        ),
        "sparse_ctr_fused_200k": ctr_rows,
        "sparse_ctr_bf16_vs_f32": round(
            ctr_rows["bf16"]["fused_rows_per_sec"]
            / ctr_rows["f32"]["fused_rows_per_sec"],
            3,
        ),
        "tier_deviation_envelopes_ulps": {
            f"{chain}/{mode}": ulps
            for (chain, mode), ulps in sorted(PRECISION_TIER_DEVIATION.items())
        },
        "note": "HONEST 1-CORE NOTE: on XLA CPU there is no bf16 ALU and no "
        "bandwidth-bound transport, so the bf16/int8 legs PAY for the "
        "rounding casts at every stage boundary and win nothing back — "
        "expect parity-to-slower vs f32 here. The tier is an accelerator "
        "play: activations cross fused-segment boundaries at half width and "
        "the published int8 artifact halves the weight payload again (the "
        "cost model prices exactly those bytes). These rows pin the code "
        "path and price the cast overhead honestly; the numerics envelopes "
        "are the contract (tests/test_precision.py), and int8 quantization "
        "happens at publish only — in-flight legs never quantize.",
    }


_SHARDED_NOTE = (
    "HONEST NOTE: measured on a 1-core dev box with "
    "--xla_force_host_platform_device_count=8 — the 8 'devices' time-share "
    "one core, so these rows measure SPMD DISPATCH OVERHEAD (partitioning, "
    "per-shard buffers, collective plumbing), not speedup. On real chips the "
    "same programs split N-ways in wall time; here mesh>1 legs are expected "
    "to run SLOWER than mesh=1. Bit-exactness vs mesh=1 is pinned by "
    "tests/test_sharded_plans.py."
)


def _bench_serving_sharded_body():
    """Mesh sweep over the sharded serving fast path (child process only —
    requires the forced 8-device grid; see bench_sharded_fanout)."""
    import threading

    from flink_ml_tpu.api.dataframe import DataFrame
    from flink_ml_tpu.metrics import MLMetrics, metrics
    from flink_ml_tpu.servable import PipelineModelServable
    from flink_ml_tpu.servable.lib import (
        LogisticRegressionModelServable,
        StandardScalerModelServable,
    )
    from flink_ml_tpu.serving import InferenceServer, ServingConfig

    rng = np.random.default_rng(5)
    dim = 256
    X = rng.standard_normal((4096, dim)).astype(np.float32)

    def make_pipeline():
        scaler = (
            StandardScalerModelServable()
            .set_input_col("features")
            .set_output_col("scaled")
            .set_with_mean(True)
        )
        scaler.mean = rng.standard_normal(dim).astype(np.float32)
        scaler.std = (np.abs(rng.standard_normal(dim)) + 0.5).astype(np.float32)
        lr = LogisticRegressionModelServable().set_features_col("scaled")
        lr.coefficient = rng.standard_normal(dim).astype(np.float32)
        return PipelineModelServable([scaler, lr])

    n_threads, requests_per_thread, req_rows = 2, 60, 8
    sweep = []
    for mesh in (1, 2, 4, 8):
        server = InferenceServer(
            make_pipeline(),
            name=f"bench-shard-{mesh}",
            serving_config=ServingConfig(
                max_batch_size=64,
                max_delay_ms=1.0,
                queue_capacity_rows=8192,
                default_timeout_ms=120_000,
                mesh=mesh,
            ),
            warmup_template=DataFrame.from_dict({"features": X[:1]}),
        )
        try:
            barrier = threading.Barrier(n_threads + 1)

            def client(tid):
                barrier.wait()
                for i in range(requests_per_thread):
                    j = (tid * 997 + i * 61) % (X.shape[0] - req_rows)
                    server.predict(
                        DataFrame.from_dict({"features": X[j : j + req_rows]})
                    )

            threads = [
                threading.Thread(target=client, args=(t,)) for t in range(n_threads)
            ]
            for t in threads:
                t.start()
            barrier.wait()
            t0 = time.perf_counter()
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - t0
            scraped = metrics.scope(server.scope)
            lat = scraped[MLMetrics.SERVING_LATENCY_MS]
            total_rows = n_threads * requests_per_thread * req_rows
            sweep.append(
                {
                    "mesh": mesh,
                    "buckets": list(server._batcher.buckets),
                    "rows_per_sec": round(total_rows / elapsed, 1),
                    "latency_p50_ms": round(lat.quantile(0.5), 3),
                    "latency_p99_ms": round(lat.quantile(0.99), 3),
                    "fastpath_compiles": scraped.get(
                        MLMetrics.SERVING_FASTPATH_COMPILES, 0
                    ),
                    "shard_rows": scraped.get(MLMetrics.SERVING_SHARD_ROWS, 0),
                    "warmup_compile_ms": round(
                        scraped.get(MLMetrics.SERVING_WARMUP_COMPILE_MS, 0.0), 1
                    ),
                }
            )
        finally:
            server.close()
    return {
        "name": "serving_sharded_scaler_lr_d256",
        "threads": n_threads,
        "requests_per_thread": requests_per_thread,
        "request_rows": req_rows,
        "sweep": sweep,
        "note": _SHARDED_NOTE,
    }


def _bench_batch_sharded_body():
    """Mesh sweep over the sharded batch-transform fast path (child process
    only — see bench_sharded_fanout)."""
    from flink_ml_tpu.api.dataframe import DataFrame
    from flink_ml_tpu.builder.batch_plan import CompiledBatchPlan
    from flink_ml_tpu.config import Options, config
    from flink_ml_tpu.metrics import MLMetrics, metrics
    from flink_ml_tpu.servable.lib import (
        LogisticRegressionModelServable,
        StandardScalerModelServable,
    )
    from flink_ml_tpu.servable.sharding import resolve_plan_sharding

    rng = np.random.default_rng(9)
    n, d = 200_000, 32
    df = DataFrame.from_dict({"features": rng.standard_normal((n, d))})
    scaler = (
        StandardScalerModelServable()
        .set_input_col("features")
        .set_output_col("scaled")
        .set_with_mean(True)
    )
    scaler.mean = rng.standard_normal(d)
    scaler.std = np.abs(rng.standard_normal(d)) + 0.5
    lr = LogisticRegressionModelServable().set_features_col("scaled")
    lr.coefficient = rng.standard_normal(d)
    stages = [scaler, lr]

    config.set(Options.BATCH_CHUNK_ROWS, 32_768)
    sweep = []
    try:
        for mesh in (1, 2, 4, 8):
            scope = f"ml.batch[bench-shard-{mesh}]"
            sharding = resolve_plan_sharding(mesh)
            plan = CompiledBatchPlan.build(stages, scope=scope, sharding=sharding)
            plan.transform(df)  # warm: compiles the chunk signatures
            t, spread = _median_time_spread(lambda: plan.transform(df), repeats=3)
            sweep.append(
                {
                    "mesh": mesh,
                    "rows_per_sec": round(n / t, 1),
                    "spread": spread,
                    "shard_rows": metrics.get(scope, MLMetrics.BATCH_SHARD_ROWS, 0),
                    "shard_pad_rows": metrics.get(
                        scope, MLMetrics.BATCH_SHARD_PAD_ROWS, 0
                    ),
                    "replicated_chunks": metrics.get(
                        scope, MLMetrics.BATCH_SHARD_REPLICATED_CHUNKS, 0
                    ),
                }
            )
    finally:
        config.unset(Options.BATCH_CHUNK_ROWS)
    return {
        "name": "batch_sharded_scaler_lr_200k_d32",
        "rows": n,
        "dim": d,
        "chunk_rows": 32_768,
        "sweep": sweep,
        "note": _SHARDED_NOTE,
    }


def _bench_sharded_trace_attrs():
    """One traced mesh=4 burst: the per-shard span attrs BENCH rounds record
    so traceview's shard section is reproducible from the artifact."""
    from flink_ml_tpu import trace
    from flink_ml_tpu.api.dataframe import DataFrame
    from flink_ml_tpu.servable.lib import LogisticRegressionModelServable
    from flink_ml_tpu.serving import InferenceServer, ServingConfig

    rng = np.random.default_rng(3)
    dim = 64
    servable = LogisticRegressionModelServable().set_features_col("features")
    servable.coefficient = rng.standard_normal(dim).astype(np.float32)
    X = rng.standard_normal((256, dim)).astype(np.float32)
    with trace.capture() as recorder:
        with InferenceServer(
            servable,
            name="bench-shard-trace",
            serving_config=ServingConfig(
                max_batch_size=64, max_delay_ms=0.0, default_timeout_ms=60_000,
                mesh=4,
            ),
            warmup_template=DataFrame.from_dict({"features": X[:1]}),
        ) as server:
            for i in range(16):
                j = (i * 31) % (X.shape[0] - 4)
                server.predict(DataFrame.from_dict({"features": X[j : j + 4]}))
    spans = recorder.snapshot()
    sharded = [
        s for s in spans
        if s.attrs and s.attrs.get("shards") == 4
        and s.name in ("serving.dispatch", "serving.exec", "serving.batch")
    ]
    by_name = {}
    for s in sharded:
        entry = by_name.setdefault(
            s.name, {"count": 0, "total_ms": 0.0, "shards": 4, "shard_rows": None}
        )
        entry["count"] += 1
        entry["total_ms"] = round(entry["total_ms"] + s.duration * 1000.0, 3)
        if isinstance(s.attrs.get("shard_rows"), int):
            entry["shard_rows"] = s.attrs["shard_rows"]
    return {
        "mesh": 4,
        "sharded_spans": len(sharded),
        "per_span": by_name,
        "note": "spans carrying shards/shard_rows attrs; traceview divides "
        "their device time per shard (tools/traceview.py shard section)",
    }


def _sharded_child() -> None:
    """Entry point of the forced-8-device child (bench_sharded_fanout)."""
    print(
        json.dumps(
            {
                "serving_sharded": _bench_serving_sharded_body(),
                "batch_sharded": _bench_batch_sharded_body(),
                "trace_shard_attrs": _bench_sharded_trace_attrs(),
            }
        )
    )


def bench_sharded_fanout():
    """Pod-scale fan-out sweep (serving.mesh / batch.mesh 1-8) in a
    subprocess on the 8-device virtual CPU grid — the same re-exec pattern
    as bench_streamed_overlap_cpu_mesh: the sharded paths need the forced
    device count before jax initializes, and the parent holds the chip."""
    import os
    import subprocess

    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",  # the parent holds the chip
            "XLA_FLAGS": (
                env.get("XLA_FLAGS", "")
                + " --xla_cpu_collective_call_warn_stuck_timeout_seconds=30"
                + " --xla_cpu_collective_call_terminate_timeout_seconds=120"
                + " --xla_force_host_platform_device_count=8"
            ).strip(),
            "PYTHONPATH": os.path.dirname(os.path.abspath(__file__)),
        }
    )
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--sharded-child"],
            capture_output=True, text=True, timeout=1800, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        payload = json.loads(proc.stdout.strip().splitlines()[-1])
        payload["name"] = "sharded_fanout_mesh_sweep"
        return payload
    except Exception as e:  # never sink the whole bench for the side artifact
        return {"name": "sharded_fanout_mesh_sweep", "error": f"{type(e).__name__}: {e}"}


def bench_tracing_overhead():
    """graftscope acceptance row (docs/observability.md): the same
    single-client serving loop with tracing off vs on.

    Off is the default production state — the contract is that the disabled
    tracer is one attribute check per instrumented site, so the off leg must
    match the untraced PR 7 baseline path (tier-1 asserts the structural
    half: zero spans, shared no-op span, no per-request span allocation; this
    row quantifies the residual). The on leg prices full span recording —
    ~7 spans per request — for capacity planning.
    """
    from flink_ml_tpu import trace
    from flink_ml_tpu.api.dataframe import DataFrame
    from flink_ml_tpu.metrics import MLMetrics, metrics
    from flink_ml_tpu.servable.lib import LogisticRegressionModelServable
    from flink_ml_tpu.serving import InferenceServer, ServingConfig

    rng = np.random.default_rng(17)
    dim = 256
    X = rng.standard_normal((2048, dim)).astype(np.float32)
    requests = 400
    req_rows = 8

    def run_leg(name):
        servable = LogisticRegressionModelServable().set_features_col("features")
        servable.coefficient = rng.standard_normal(dim).astype(np.float32)
        server = InferenceServer(
            servable,
            name=name,
            serving_config=ServingConfig(
                max_batch_size=64,
                max_delay_ms=0.0,  # single client: coalescing buys nothing
                default_timeout_ms=120_000,
            ),
            warmup_template=DataFrame.from_dict({"features": X[:1]}),
        )
        try:
            t0 = time.perf_counter()
            for i in range(requests):
                j = (i * 61) % (X.shape[0] - req_rows)
                server.predict(DataFrame.from_dict({"features": X[j : j + req_rows]}))
            elapsed = time.perf_counter() - t0
            hist = metrics.histogram(server.scope, MLMetrics.SERVING_LATENCY_MS)
            p50, p99 = hist.quantiles((0.5, 0.99))
            return {
                "requests": requests,
                "request_rows": req_rows,
                "rows_per_sec": round(requests * req_rows / elapsed, 1),
                "latency_p50_ms": round(p50, 3),
                "latency_p99_ms": round(p99, 3),
            }
        finally:
            server.close()

    off = run_leg("bench-trace-off")
    assert not trace.tracer.enabled
    with trace.capture() as recorder:
        on = run_leg("bench-trace-on")
        on["spans"] = recorder.recorded
        report = recorder.goodput_report()
        on["goodput_fraction"] = round(
            report.fraction("ml.serving[bench-trace-on]") or 0.0, 4
        )
    overhead = (
        round(100.0 * (on["latency_p50_ms"] / off["latency_p50_ms"] - 1.0), 1)
        if off["latency_p50_ms"]
        else None
    )
    return {
        "name": "tracing_overhead_serving_microbatch",
        "off": off,
        "on": on,
        "p50_overhead_pct": overhead,
        "note": "single-client closed loop, d=256 logistic servable; off = "
        "default disabled tracer (one attribute check per site), on = full "
        "span recording incl. queue/pad/dispatch/readback tree per request",
    }


def bench_journal_overhead():
    """Flight-recorder acceptance row (docs/observability.md): the d=256
    logistic fast path with the always-on journal disabled vs enabled (the
    shipped default), as a paired median-of-ratios measurement.

    Protocol: 15 alternating off/on leg pairs (400 closed-loop requests
    each), per-leg p50 + mean latency, and the reported overhead is the
    MEDIAN of the 15 pairwise on/off ratios. One leg on this 1-core box
    carries heavy-tailed scheduler noise (individual legs swing >15% in
    both directions — the per-pair ratios are recorded); pairing adjacent
    legs cancels slow drift and the median rejects the outlier legs, which
    best-of-N and single-pair protocols measurably do not here.

    The journal records *decisions*, not requests — the steady fast path
    reaches zero emit() sites, so the expected delta is zero by
    construction; this row prices the residual (the writer thread existing,
    the disabled-vs-armed branch) and the separate overload leg prices the
    emit sites that DO fire under load (sheds/deadline misses at 2x
    saturation: one bounded-queue enqueue each, writes on the
    flight-recorder thread — tests/test_telemetry.py asserts the thread
    discipline and the dispatch path's zero-write contract).
    """
    import statistics
    import tempfile

    import flink_ml_tpu.telemetry as telemetry
    from flink_ml_tpu.api.dataframe import DataFrame
    from flink_ml_tpu.loadgen import OpenLoopLoadGenerator, ZipfSizes, ramp_schedule
    from flink_ml_tpu.metrics import MLMetrics, metrics
    from flink_ml_tpu.servable.lib import LogisticRegressionModelServable
    from flink_ml_tpu.serving import InferenceServer, ServingConfig

    rng = np.random.default_rng(29)
    dim = 256
    X = rng.standard_normal((4096, dim)).astype(np.float32)
    requests = 400
    req_rows = 8

    def make_server(name, queue_capacity=1024):
        servable = LogisticRegressionModelServable().set_features_col("features")
        servable.coefficient = rng.standard_normal(dim).astype(np.float32)
        return InferenceServer(
            servable,
            name=name,
            serving_config=ServingConfig(
                max_batch_size=64,
                max_delay_ms=0.0,  # single client: coalescing buys nothing
                queue_capacity_rows=queue_capacity,
                default_timeout_ms=30_000,
                shed_sustain_ms=10.0,
            ),
            warmup_template=DataFrame.from_dict({"features": X[:1]}),
        )

    def leg(name):
        """(p50 ms, mean ms/request) of one closed-loop leg."""
        server = make_server(name)
        try:
            t0 = time.perf_counter()
            for i in range(requests):
                j = (i * 61) % (X.shape[0] - req_rows)
                server.predict(DataFrame.from_dict({"features": X[j : j + req_rows]}))
            mean_ms = (time.perf_counter() - t0) / requests * 1000.0
            hist = metrics.histogram(server.scope, MLMetrics.SERVING_LATENCY_MS)
            return hist.quantile(0.5), mean_ms
        finally:
            server.close()

    pairs = 15
    off_p50s, on_p50s, p50_ratios, mean_ratios = [], [], [], []
    try:
        telemetry.configure(enabled=False)
        leg("bench-journal-warm")  # discarded: pays the process-wide compiles
        for r in range(pairs):
            order = ("off", "on") if r % 2 == 0 else ("on", "off")
            results = {}
            for mode in order:
                if mode == "off":
                    telemetry.configure(enabled=False)
                else:
                    telemetry.configure(tempfile.mkdtemp(prefix="bench-journal-"))
                results[mode] = leg(f"bench-journal-{mode}-{r}")
            off_p50s.append(results["off"][0])
            on_p50s.append(results["on"][0])
            p50_ratios.append(results["on"][0] / results["off"][0])
            mean_ratios.append(results["on"][1] / results["off"][1])
        # Overload leg (journal on): ~2x a measured saturation, where the
        # shed/deadline decision sites actually emit.
        recorder = telemetry.configure(tempfile.mkdtemp(prefix="bench-journal-"))
        sizes = ZipfSizes((1, 2, 4, 8, 16, 32), alpha=1.5)
        server = make_server("bench-journal-overload", queue_capacity=256)

        def request(rows):
            j = int(rng.integers(0, X.shape[0] - rows))
            return DataFrame.from_dict({"features": X[j : j + rows]})

        overload_rps = 8000.0  # ~2x this head's measured ~4k rps saturation
        try:
            sched = ramp_schedule(
                [(overload_rps, 1.0)], sizes=sizes, priority_mix={0: 0.7, 1: 0.3}, seed=9
            )
            report = OpenLoopLoadGenerator(
                sched, request, timeout_ms={0: 30_000.0, 1: 250.0}
            ).run(server)
            step = report.steps[0]
        finally:
            server.close()
        recorder.flush(10.0)
        overload = {
            "offered_rps": overload_rps,
            "latency_p50_ms": round(step.latency_ms(0.5), 3),
            "shed": step.shed,
            "deadline_misses": step.deadline_misses,
            "journal_events": recorder.seq,
            "journal_dropped": recorder.dropped,
        }
    finally:
        telemetry.configure(None)
    p50_med = statistics.median(p50_ratios)
    mean_med = statistics.median(mean_ratios)
    return {
        "name": "journal_overhead_serving_microbatch",
        "pairs": pairs,
        "requests_per_leg": requests,
        "request_rows": req_rows,
        "off": {"median_latency_p50_ms": round(statistics.median(off_p50s), 3)},
        "on": {"median_latency_p50_ms": round(statistics.median(on_p50s), 3)},
        "p50_pairwise_ratios": [round(x, 3) for x in p50_ratios],
        "p50_overhead_pct": round(100.0 * (p50_med - 1.0), 2),
        "mean_latency_overhead_pct": round(100.0 * (mean_med - 1.0), 2),
        "overload_on": overload,
        "note": "d=256 logistic fast path, single-client closed loop; off = "
        "observability.journal disabled, on = the shipped always-on "
        "default. Overhead = median of 15 pairwise on/off ratios (paired "
        "legs cancel drift, the median rejects this box's heavy-tailed "
        "scheduler outliers — individual legs swing >15% both directions, "
        "see the recorded ratios). The steady path reaches zero emit() "
        "sites by design; overload_on exercises the shed/deadline emit "
        "sites (one bounded-queue enqueue each, journal_dropped must stay "
        "0, writes only on the flight-recorder thread per "
        "tests/test_telemetry.py).",
    }


def bench_mlp_forward(peak_flops):
    import jax
    import jax.numpy as jnp

    import __graft_entry__

    fn, (params, X) = __graft_entry__.entry()
    params = [(jnp.asarray(W, jnp.bfloat16), jnp.asarray(b, jnp.bfloat16)) for W, b in params]
    X = jnp.asarray(X, jnp.bfloat16)
    step = jax.jit(fn)

    jax.block_until_ready(step(params, X))
    reps = 100
    t0 = time.perf_counter()
    outs = [step(params, X) for _ in range(reps)]  # pipelined async dispatch
    np.asarray(outs[-1][0])  # forces the whole dependency chain to finish
    elapsed = (time.perf_counter() - t0) / reps
    batch = X.shape[0]
    flops = 2.0 * batch * sum(int(W.shape[0]) * int(W.shape[1]) for W, _ in params)
    achieved = flops / elapsed
    return {
        "name": "mlp_forward_bf16_b4096_256_512_512_8",
        "rows_per_sec": round(batch / elapsed, 1),
        "step_time_us": round(elapsed * 1e6, 1),
        "achieved_gflops": round(achieved / 1e9, 1),
        "mfu": round(achieved / peak_flops, 4) if peak_flops else None,
        "latency_target_us": 5000,
        "note": "serving shape: bandwidth-bound by design (weights re-read per "
        "call), so low MFU is expected — the quantified contract is the "
        "latency target, met with ~4x headroom; for throughput, batch up "
        "(mlp_train shows the same network at 78% MFU at batch 32k)",
        "latency_target_source": "half the ~10 ms model-inference slice of "
        "the classic 100 ms real-time-bidding budget (the Criteo CTR "
        "setting BASELINE.json's north star lives in): scoring must leave "
        "room for feature transforms in the same window, the role the "
        "reference's servable path plays downstream of its online models",
    }


def bench_retrieval_topk():
    """Retrieval tier (docs/retrieval.md): top-K serving latency at catalog
    scale under OPEN-LOOP load — the p99 a capacity plan is made of, at the
    candidate counts the recsys family actually carries (10^5 and 10^6).

    Per (candidates, K) cell: a swing ``CandidateIndex`` is synthesized at
    scale (ELL neighbor table, 16 slots/row), served through
    ``InferenceServer`` with the sparse nnz ladder x K rung warmed up front,
    then driven with seeded Poisson single-row arrivals (every request: an
    8-item history + its own ``k``) at ~0.6x of a measured saturation burst.
    Recorded: achieved qps, p50/p99 latency, zero post-warmup compiles.
    1-core CPU box: absolute numbers are directional (XLA-CPU top_k over
    [batch, C]); the contract under test is the SHAPE of the path — fused,
    compile-free, p99 bounded while C grows 10x.
    """
    from flink_ml_tpu.api.dataframe import DataFrame
    from flink_ml_tpu.config import Options, config
    from flink_ml_tpu.linalg.vectors import SparseVector
    from flink_ml_tpu.loadgen import FixedSizes, OpenLoopLoadGenerator, ramp_schedule
    from flink_ml_tpu.metrics import MLMetrics, metrics
    from flink_ml_tpu.retrieval import CandidateIndex

    NNZ = 8  # history items per request — one warmed nnz cap
    NBRS = 16  # ELL similarity slots per candidate row

    def make_index(C, seed):
        rng = np.random.default_rng(seed)
        sim_ids = rng.integers(0, C, (C, NBRS)).astype(np.int32)
        sim_ids.sort(axis=1)  # the sorted-per-row scatter invariant
        sim_values = rng.random((C, NBRS), np.float32) + np.float32(0.01)
        idx = CandidateIndex(
            {
                "item_ids": np.arange(C, dtype=np.int64),
                "sim_values": sim_values,
                "sim_ids": sim_ids,
            }
        )
        idx.set_output_col("rec")
        return idx

    rows = []
    for C in (100_000, 1_000_000):
        idx = make_index(C, seed=C)
        rng = np.random.default_rng(17)
        # pre-drawn request pool: arrival threads must not pay rng/pack cost
        pool = [
            DataFrame(
                ["history", "k"],
                None,
                [
                    [
                        SparseVector(
                            C,
                            np.sort(
                                rng.choice(C, size=NNZ, replace=False)
                            ).astype(np.int64),
                            np.ones(NNZ),
                        )
                    ],
                    np.asarray([0], np.int64),  # k patched per cell below
                ],
            )
            for _ in range(64)
        ]
        for K in (10, 100):
            from flink_ml_tpu.serving import InferenceServer, ServingConfig

            config.set(Options.SPARSE_WARMUP_CAPS, str(NNZ))
            config.set(Options.SPARSE_NNZ_CAP_MAX, NNZ)
            config.set(Options.RETRIEVAL_WARMUP_KS, str(K))
            config.set(Options.RETRIEVAL_K_CAP_MAX, 128)
            reqs = [
                DataFrame(
                    df.column_names, None, [df.column("history"), np.asarray([K], np.int64)]
                )
                for df in pool
            ]
            req_i = [0]

            def request(_rows):
                req_i[0] = (req_i[0] + 1) % len(reqs)
                return reqs[req_i[0]]

            name = f"bench-ret-{C}-{K}"
            scope = f"ml.serving[{name}]"
            template = reqs[0]
            server = InferenceServer(
                idx.servable(),
                name=name,
                serving_config=ServingConfig(
                    max_batch_size=8,
                    max_delay_ms=1.0,
                    queue_capacity_rows=256,
                    default_timeout_ms=60_000,
                ),
                warmup_template=template,
            )
            try:
                compiles0 = metrics.get(
                    scope, MLMetrics.SERVING_FASTPATH_COMPILES, 0
                )
                # saturation estimate: a short deliberately-overloaded burst
                cal = OpenLoopLoadGenerator(
                    ramp_schedule([(400.0, 1.0)], sizes=FixedSizes(1), seed=1),
                    request,
                    timeout_ms=60_000.0,
                ).run(server)
                sat_qps = max(cal.total_resolved / cal.wall_s, 1.0)
                rate = 0.6 * sat_qps
                report = OpenLoopLoadGenerator(
                    ramp_schedule([(rate, 4.0)], sizes=FixedSizes(1), seed=2),
                    request,
                    timeout_ms=60_000.0,
                ).run(server)
                step = report.steps[0]
                compiles = (
                    metrics.get(scope, MLMetrics.SERVING_FASTPATH_COMPILES, 0)
                    - compiles0
                )
                rows.append(
                    {
                        "candidates": C,
                        "k": K,
                        "k_rung": 16 if K == 10 else 128,
                        "saturation_qps": round(sat_qps, 1),
                        "offered_qps": round(rate, 1),
                        "achieved_qps": round(
                            step.completed / max(step.duration_s, 1e-9), 1
                        ),
                        "p50_ms": round(step.latency_ms(0.5) or 0.0, 2),
                        "p99_ms": round(step.latency_ms(0.99) or 0.0, 2),
                        "fully_resolved": report.fully_resolved(),
                        "post_warmup_compiles": compiles,
                    }
                )
            finally:
                server.close()
                for opt in (
                    Options.SPARSE_WARMUP_CAPS,
                    Options.SPARSE_NNZ_CAP_MAX,
                    Options.RETRIEVAL_WARMUP_KS,
                    Options.RETRIEVAL_K_CAP_MAX,
                ):
                    config.unset(opt)
    return {
        "name": "retrieval_topk_open_loop",
        "chain": "8-item history -> fused segment-reduce swing scores -> "
        "lax.top_k, served single-row open-loop @ 0.6x saturation",
        "sweep": rows,
        "note": "device-resident swing index (16 ELL slots/row); every cell "
        "fused with zero post-warmup compiles. 1-core XLA-CPU box: "
        "absolute qps/latency directional only — the recorded contract "
        "is p99 boundedness as C grows 10x and K 10x on the rung "
        "ladder, and the compile-free fast path holding under "
        "open-loop arrivals.",
    }


def _failed_phases(workloads) -> list:
    """Names of the workloads (or sweep rows) that recorded an ``error``
    instead of a result — a side artifact may not sink the suite mid-run,
    but the run as a whole must not pass for complete."""
    failed = []
    for w in workloads:
        if "error" in w:
            failed.append(w.get("name", "?"))
        failed += [
            f"{w.get('name', '?')}[p={r.get('p')}]"
            for r in w.get("rows", ())
            if isinstance(r, dict) and "error" in r
        ]
    return failed


def main() -> int:
    import jax

    from flink_ml_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    kind = jax.devices()[0].device_kind
    if kind not in _PEAK_FLOPS:
        raise SystemExit(
            f"bench.py: unknown device_kind {kind!r} (known: "
            f"{sorted(_PEAK_FLOPS)}) — a device without published peaks is an "
            "error, not a default; this suite measures the chip"
        )
    peak = _PEAK_FLOPS[kind]
    peak_bw = _PEAK_HBM_GBPS[kind]

    logreg, (X, y) = bench_logreg(peak, peak_bw)
    cpu_rows, cpu_spread = bench_logreg_cpu_baseline(X, y)
    logreg["cpu_baseline_rows_per_sec"] = round(cpu_rows, 1)
    logreg["cpu_baseline_spread"] = cpu_spread
    logreg["vs_cpu_baseline"] = round(logreg["steady_rows_per_sec"] / cpu_rows, 2)
    del X, y
    sparse = bench_logreg_sparse(peak, peak_bw)
    sweep = bench_onehot_per_chip_sweep(peak)
    sparse_streamed = bench_logreg_sparse_streamed()
    overlap = bench_streamed_overlap_cpu_mesh()
    kmeans = bench_kmeans(peak_bw)
    mlp = bench_mlp_forward(peak)
    mlp_train = bench_mlp_train(peak)
    attention = bench_attention(peak)
    attention_train = bench_attention_train(peak)
    serving = bench_serving()
    open_loop = bench_serving_open_loop()
    tracing = bench_tracing_overhead()
    journal = bench_journal_overhead()
    mlp_serving = bench_mlp_serving_throughput()
    continuous_loop = bench_continuous_loop()
    batch_transform = bench_pipeline_batch_transform()
    fusion = bench_fusion_sweep()
    sharded = bench_sharded_fanout()
    cold_start = bench_cold_start()
    sparse_pipelines = bench_sparse_pipelines()
    precision = bench_precision_sweep()

    detail = {
        "device_kind": kind,
        "peak_bf16_flops": peak,
        "peak_hbm_gbps": peak_bw,
        "workloads": [
            logreg, sparse, sweep, sparse_streamed, overlap, kmeans, mlp,
            mlp_train, attention, attention_train, serving, open_loop,
            tracing, journal, mlp_serving, continuous_loop, batch_transform,
            fusion, sharded, cold_start, sparse_pipelines, precision,
        ],
    }
    with open("BENCH_DETAIL.json", "w") as f:
        json.dump(detail, f, indent=2)

    print(
        json.dumps(
            {
                "metric": "logreg_steady_train_rows_per_sec_d256",
                "value": logreg["steady_rows_per_sec"],
                "unit": "rows/s",
                "vs_baseline": logreg["vs_cpu_baseline"],
                "detail": detail,
            }
        )
    )
    failed = _failed_phases(detail["workloads"])
    if failed:
        print(f"bench.py: phases failed: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    if "--sharded-child" in sys.argv[1:]:
        sys.exit(_sharded_child())
    if "retrieval_topk" in sys.argv[1:]:
        print(json.dumps(bench_retrieval_topk(), indent=2))
        sys.exit(0)
    if "precision_sweep" in sys.argv[1:]:
        print(json.dumps(bench_precision_sweep(), indent=2))
        sys.exit(0)
    if "training_weak_scaling" in sys.argv[1:]:
        print(json.dumps(bench_training_weak_scaling(), indent=2))
        sys.exit(0)
    sys.exit(main())
