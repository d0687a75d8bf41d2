"""``chip_smoke.py`` proves the chip path and nothing else: without a TPU it
exits non-zero before any phase and prints no result — it never falls back
to the CPU backend the tests run on."""
import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_without_a_tpu_exits_nonzero_and_runs_no_phase():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert proc.stdout == ""  # no phase line, no device line, no pass marker
    assert "no TPU" in proc.stderr


def test_reference_step_matches_the_library_schedule_on_a_ragged_tail():
    """The smoke's numpy reference is independent of the code under test, so
    pin it against the scatter-path SGD on the shape the smoke relies on:
    several data shards, a batch that does not divide the shard."""
    import numpy as np

    sys.path.insert(0, REPO_ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO_ROOT)
    from flink_ml_tpu.ops import SGD, BinaryLogisticLoss
    from flink_ml_tpu.parallel.mesh import get_mesh_context

    rng = np.random.default_rng(3)
    n, dim, k = 250, 512, 4
    idx = rng.integers(0, dim, size=(n, k)).astype(np.int32)
    vals = np.ones((n, k), np.float32)
    y = (rng.random(n) > 0.5).astype(np.float32)
    n_data = get_mesh_context().n_data
    got = SGD(
        max_iter=7, global_batch_size=96, tol=0.0, learning_rate=4.0,
        sparse_kernel="scatter",
    ).optimize(
        np.zeros(dim, np.float32),
        {"indices": idx, "values": vals, "labels": y},
        BinaryLogisticLoss.INSTANCE,
    )
    want = chip_smoke.reference_sgd(idx, vals, y, dim, n_data, 96, 7, 4.0)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
