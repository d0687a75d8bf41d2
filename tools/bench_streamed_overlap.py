"""Streamed-training overlap rehearsal on the CPU backend (run as a subprocess
by bench.py on an 8-device virtual CPU mesh).

A CPU run: it checks the streaming machinery (window prefetch in
``run_windows``, spill reads, per-window one-hot layout fill) and splits the
host's wall clock between ingest and everything else. None of its times is a
device time — the on-chip split of the streamed fit is ROADMAP S2's to
measure. The streamed regime is enforced by a spilling host cache (RAM
budget << dataset, windows read back off disk) — the CPU mesh has no HBM to
overflow, so the window:dataset ratio stands in for the HBM:dataset ratio.

Also exercises checkpoint+resume mid-run on the streamed one-hot path (the
fit checkpoints every other window run; a resume from the second-to-last
snapshot must land on the identical coefficient).

Prints one JSON object on stdout.
"""
import json
import shutil
import sys
import tempfile
import time

import numpy as np


def main():
    import jax

    from flink_ml_tpu.checkpoint import CheckpointManager
    from flink_ml_tpu.iteration import HostDataCache
    from flink_ml_tpu.iteration.streaming import WindowSchedule
    from flink_ml_tpu.linalg.onehot_sparse import SUB_ROWS
    from flink_ml_tpu.ops import SGD, BinaryLogisticLoss
    from flink_ml_tpu.ops.optimizer import _OneHotWindowStream, streamed_onehot_plan
    from flink_ml_tpu.parallel.mesh import get_mesh_context

    n, d, K = 196_608, 1 << 18, 16
    batch = 32_768
    epochs = 6
    # window << per-shard rows: multiple window runs per fit, so the
    # checkpoint-at-run-boundary machinery and the prefetch both engage
    window = 8_192
    rng = np.random.default_rng(11)

    with tempfile.TemporaryDirectory() as tmp:
        # RAM budget 4 MB vs a ~25 MB dataset: most chunks spill to disk and
        # every window read comes back off the spill files.
        cache = HostDataCache(memory_budget_bytes=4 << 20, spill_dir=tmp)
        for lo in range(0, n, 32_768):
            m = min(32_768, n - lo)
            idx = rng.integers(0, d, size=(m, K), dtype=np.int32)
            vals = np.ones((m, K), np.float32)
            cache.append(
                {
                    "indices": idx,
                    "values": vals,
                    "labels": (rng.random(m) > 0.5).astype(np.float32),
                    "weights": np.ones(m, np.float32),
                }
            )
        cache.finish()
        spilled = sum(1 for e in cache._log if "files" in e)

        last_fit = {}

        def fit(mgr=None, interval=0):
            sgd = SGD(
                max_iter=epochs, global_batch_size=batch, tol=0.0,
                learning_rate=0.5, stream_window_rows=window,
                sparse_kernel="onehot", checkpoint_manager=mgr,
                checkpoint_interval=interval,
            )
            coef = sgd.optimize(
                np.zeros(d, np.float32), cache, BinaryLogisticLoss.INSTANCE
            )
            last_fit["premat"] = sgd.onehot_premat_active
            return coef

        fit()  # warm-up: plan + program compile
        t0 = time.perf_counter()
        want = fit()
        wall = time.perf_counter() - t0

        # Pure ingest: load the windows the run actually loads (dedup
        # consecutive same-window runs — run_windows keeps those resident),
        # no compute; the fit's in-band counting pass is timed apart and
        # excluded from the windows-phase wall.
        from flink_ml_tpu.linalg.onehot_sparse import BLOCK

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
        # The probe must exercise the SAME load() path the fit used (with
        # premat, load() also materializes the window's one-hots on device).
        stream = _OneHotWindowStream(
            cache, ctx, plan, sched.window, b_local, n_sub, m_shard, n,
            premat=last_fit.get("premat", False),
        )
        visited = [j for j, _ in sched.runs]
        loads = [j for i, j in enumerate(visited) if i == 0 or j != visited[i - 1]]
        t0 = time.perf_counter()
        for j in loads:
            buf = stream.load(j)
            jax.block_until_ready(buf.get("oh", buf["labels"]))
        ingest_s = time.perf_counter() - t0

        # Checkpoint + resume mid-run: identical coefficient required.
        ckdir = f"{tmp}/ck"
        got_ck = fit(CheckpointManager(ckdir), interval=2)
        steps = CheckpointManager(ckdir).all_steps()
        resume_ok = False
        if len(steps) >= 2:
            shutil.rmtree(f"{ckdir}/ckpt-{steps[-1]}")
            resumed = fit(CheckpointManager(ckdir), interval=2)
            resume_ok = bool(
                np.array_equal(got_ck, want) and np.array_equal(resumed, want)
            )

    # windows-phase wall: the fit repeats the counting pass in-band; it is
    # neither window ingest nor device compute, so take it out of the split
    wall_train = max(wall - plan_s, 1e-9)
    compute_s = max(wall_train - ingest_s, 0.0)  # whatever ingest can't explain
    out = {
        "name": "streamed_overlap_cpu_mesh_196k_d256k",
        "backend": "cpu x 8 (virtual mesh)",
        "rows": n,
        "window_rows": window,
        "epochs": epochs,
        "spilled_chunks": spilled,
        "onehot_premat_active": last_fit.get("premat", False),
        "wall_time_s": round(wall, 2),
        "plan_pass_s": round(plan_s, 2),
        "ingest_s": round(ingest_s, 2),
        "compute_share": round(compute_s / wall_train, 4),
        "ingest_share": round(ingest_s / wall_train, 4),
        "e2e_rows_per_sec": round(epochs * batch / wall, 1),
        "checkpoint_resume_identical": resume_ok,
        "note": "CPU run: ingest (spill read + layout fill + transfer) vs "
        "the fused one-hot compute; compute_share = fraction of wall not "
        "explained by pure ingest (prefetch hides ingest behind compute when "
        "compute dominates)",
    }
    print(json.dumps(out))


if __name__ == "__main__":
    sys.exit(main())
