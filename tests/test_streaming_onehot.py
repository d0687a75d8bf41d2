"""Streamed (larger-than-HBM) sparse SGD on the one-hot matmul kernel.

The north-star combination (BASELINE.json): Criteo-shape sparse LR streamed
from a host-tier cache, running the fast one-hot kernel instead of serialized
scatter/gather. The contract: a global ``OneHotSparsePlan`` built from one
counting pass serves every window with ONE compiled program, and the result
matches both the resident one-hot path and the streamed scatter path.
"""
import numpy as np
import pytest

from flink_ml_tpu.iteration import DeviceDataCache, HostDataCache
from flink_ml_tpu.ops import SGD, BinaryLogisticLoss
from flink_ml_tpu.parallel.mesh import MeshContext, mesh_context


def _sparse_data(n, d, K, seed=0):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, d, size=(n, K)).astype(np.int32)
    val = rng.normal(size=(n, K)).astype(np.float32)
    val[rng.random((n, K)) < 0.15] = 0.0  # padding slots
    y = (rng.random(n) > 0.5).astype(np.float32)
    return {"indices": idx, "values": val, "labels": y}


def _heavy_sparse_data(n, d, K, seed=0):
    """``_sparse_data`` with nine entries in ten sent to the block of ids
    256..383: a unit of 192 entries, 15% of them padding, then holds about
    147 of them, so the block is heavy (OneHotSparsePlan) and takes chunks."""
    cols = _sparse_data(n, d, K, seed)
    rng = np.random.default_rng(seed + 1000)
    crowded = rng.random((n, K)) < 0.9
    cols["indices"][crowded] = rng.integers(256, 384, size=int(crowded.sum()))
    return cols


def _fill(cache, cols, chunk=40):
    n = len(cols["labels"])
    for a in range(0, n, chunk):
        cache.append({k: v[a : a + chunk] for k, v in cols.items()})
    cache.finish()
    return cache


KW = dict(max_iter=12, global_batch_size=128, tol=0.0, learning_rate=0.3)


def test_streamed_onehot_matches_streamed_scatter(tmp_path):
    cols = _sparse_data(512, 2000, 6, seed=1)
    cache = _fill(
        HostDataCache(memory_budget_bytes=2000, spill_dir=str(tmp_path)), cols
    )
    assert any("files" in e for e in cache._log), "budget should force spill"
    coefs, hists = {}, {}
    for kernel in ("onehot", "scatter"):
        sgd = SGD(stream_window_rows=32, sparse_kernel=kernel, **KW)
        coefs[kernel] = sgd.optimize(
            np.zeros(2000, np.float32), cache, BinaryLogisticLoss.INSTANCE
        )
        hists[kernel] = sgd.loss_history
    np.testing.assert_allclose(coefs["onehot"], coefs["scatter"], rtol=1e-3, atol=1e-5)
    assert len(hists["onehot"]) == len(hists["scatter"]) == KW["max_iter"]
    np.testing.assert_allclose(hists["onehot"], hists["scatter"], rtol=1e-3)


@pytest.mark.parametrize("make_cols,K", [(_sparse_data, 6), (_heavy_sparse_data, 16)])
def test_streamed_onehot_matches_resident_onehot(make_cols, K):
    # 512 rows / 8 devices -> m=64; local batch 16 divides m evenly, so the
    # streamed epochs consume exactly the resident rows and weights: two
    # windows of 32 rows a shard. The heavy rows put a chunked class into
    # the plan both routes build.
    cols = make_cols(512, 2000, K, seed=2)
    resident = SGD(sparse_kernel="onehot", **KW)
    want = resident.optimize(
        np.zeros(2000, np.float32), dict(cols), BinaryLogisticLoss.INSTANCE
    )
    cache = _fill(HostDataCache(), cols)
    streamed = SGD(stream_window_rows=32, sparse_kernel="onehot", **KW)
    got = streamed.optimize(
        np.zeros(2000, np.float32), cache, BinaryLogisticLoss.INSTANCE
    )
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(
        streamed.loss_history, resident.loss_history, rtol=1e-4
    )
    if make_cols is _heavy_sparse_data:
        from flink_ml_tpu.ops.optimizer import streamed_onehot_plan

        assert streamed_onehot_plan(cache, 512, 8, 32, 16, 2000).chunks_of_block


def test_streamed_onehot_ragged_tail_matches_scatter(tmp_path):
    # 400 rows -> m=50 per shard with global padding; batch 16 does not
    # divide evenly, exercising the masked short-tail epochs.
    cols = _sparse_data(400, 1500, 5, seed=3)
    cache = _fill(
        HostDataCache(memory_budget_bytes=1500, spill_dir=str(tmp_path)), cols
    )
    coefs = {}
    for kernel in ("onehot", "scatter"):
        coefs[kernel] = SGD(
            stream_window_rows=20, sparse_kernel=kernel, **KW
        ).optimize(np.zeros(1500, np.float32), cache, BinaryLogisticLoss.INSTANCE)
    np.testing.assert_allclose(coefs["onehot"], coefs["scatter"], rtol=1e-3, atol=1e-5)


def test_streamed_onehot_tol_stops_like_scatter():
    cols = _sparse_data(512, 2000, 6, seed=4)
    cache = _fill(HostDataCache(), cols)
    hists = {}
    for kernel in ("onehot", "scatter"):
        sgd = SGD(
            stream_window_rows=32, sparse_kernel=kernel,
            max_iter=300, global_batch_size=512, tol=0.5, learning_rate=0.5,
        )
        sgd.optimize(np.zeros(2000, np.float32), cache, BinaryLogisticLoss.INSTANCE)
        hists[kernel] = sgd.loss_history
    assert len(hists["onehot"]) < 300, "tol should stop early"
    assert len(hists["onehot"]) == len(hists["scatter"])
    np.testing.assert_allclose(hists["onehot"], hists["scatter"], rtol=1e-3)


def test_streamed_onehot_checkpoint_resume(tmp_path):
    from flink_ml_tpu.checkpoint import CheckpointManager

    cols = _sparse_data(512, 2000, 6, seed=5)
    cache = _fill(HostDataCache(), cols)
    want = SGD(stream_window_rows=32, sparse_kernel="onehot", **KW).optimize(
        np.zeros(2000, np.float32), cache, BinaryLogisticLoss.INSTANCE
    )

    ckdir = str(tmp_path / "ck")
    got = SGD(
        stream_window_rows=32, sparse_kernel="onehot",
        checkpoint_manager=CheckpointManager(ckdir), checkpoint_interval=2, **KW
    ).optimize(np.zeros(2000, np.float32), cache, BinaryLogisticLoss.INSTANCE)
    np.testing.assert_array_equal(got, want)

    mgr = CheckpointManager(ckdir)
    steps = mgr.all_steps()
    assert len(steps) >= 2, "expected multiple checkpoints"
    import shutil

    shutil.rmtree(f"{ckdir}/ckpt-{steps[-1]}")
    resumed = SGD(
        stream_window_rows=32, sparse_kernel="onehot",
        checkpoint_manager=CheckpointManager(ckdir), checkpoint_interval=2, **KW
    ).optimize(np.zeros(2000, np.float32), cache, BinaryLogisticLoss.INSTANCE)
    np.testing.assert_array_equal(resumed, want)


def test_streamed_auto_picks_onehot_for_wide_models(monkeypatch):
    import flink_ml_tpu.ops.optimizer as om

    calls = []
    orig = om.SGD._optimize_streaming_onehot

    def spy(self, *a, **k):
        calls.append(1)
        return orig(self, *a, **k)

    monkeypatch.setattr(om.SGD, "_optimize_streaming_onehot", spy)
    n, d, K = 2048, 1 << 15, 32  # n*K = 2^16, d >= 2^14
    cols = _sparse_data(n, d, K, seed=6)
    cache = _fill(HostDataCache(), cols, chunk=256)
    coef = SGD(stream_window_rows=256, max_iter=3, global_batch_size=512, tol=0.0).optimize(
        np.zeros(d, np.float32), cache, BinaryLogisticLoss.INSTANCE
    )
    assert calls, "auto should engage the one-hot kernel on the streamed path"
    assert np.all(np.isfinite(coef))


def test_streamed_auto_narrow_stays_on_scatter(monkeypatch):
    import flink_ml_tpu.ops.optimizer as om

    calls = []
    monkeypatch.setattr(
        om.SGD, "_optimize_streaming_onehot",
        lambda self, *a, **k: calls.append(1) or None,
    )
    cols = _sparse_data(256, 500, 4, seed=7)  # narrow: scatter territory
    cache = _fill(HostDataCache(), cols)
    SGD(stream_window_rows=16, max_iter=2, global_batch_size=64, tol=0.0).optimize(
        np.zeros(500, np.float32), cache, BinaryLogisticLoss.INSTANCE
    )
    assert not calls


def test_streamed_auto_falls_back_when_stacks_exceed_hbm(monkeypatch):
    import flink_ml_tpu.ops.optimizer as om

    monkeypatch.setattr(om, "_hbm_bytes_limit", lambda ctx=None: 1 << 16)
    n, d, K = 2048, 1 << 15, 32
    cols = _sparse_data(n, d, K, seed=8)
    cache = _fill(HostDataCache(), cols, chunk=256)
    coef = SGD(stream_window_rows=256, max_iter=2, global_batch_size=512, tol=0.0).optimize(
        np.zeros(d, np.float32), cache, BinaryLogisticLoss.INSTANCE
    )
    assert np.all(np.isfinite(coef))  # scatter fallback trained


def test_forced_streamed_onehot_infeasible_raises():
    cols = _sparse_data(256, 500, 4, seed=9)
    cache = _fill(HostDataCache(), cols)
    # f64 fit: the MXU split-bf16 crossings reconstruct f32, not f64
    with pytest.raises(ValueError, match="f32"):
        SGD(
            stream_window_rows=16, sparse_kernel="onehot", dtype=np.float64, **KW
        ).optimize(np.zeros(500, np.float64), cache, BinaryLogisticLoss.INSTANCE)


def test_streamed_onehot_tp_matches_streamed_scatter_tp():
    # The full composition: streamed + one-hot + tensor parallelism on a
    # (4 data x 2 model) mesh, vs the streamed scatter-TP path.
    cols = _sparse_data(512, 2000, 6, seed=10)
    cache = _fill(HostDataCache(), cols)
    with mesh_context(MeshContext(n_data=4, n_model=2)) as ctx:
        coefs = {}
        for kernel in ("onehot", "scatter"):
            coefs[kernel] = SGD(
                stream_window_rows=32, sparse_kernel=kernel, ctx=ctx, **KW
            ).optimize(np.zeros(2000, np.float32), cache, BinaryLogisticLoss.INSTANCE)
        np.testing.assert_allclose(
            coefs["onehot"], coefs["scatter"], rtol=1e-3, atol=1e-5
        )


def test_streamed_onehot_multislice_matches_streamed_scatter():
    # Round-5 composition (VERDICT r4 missing #3), streamed flavor: the
    # streamed one-hot kernel on a (2 slices x 4 chips) mesh vs the streamed
    # scatter path on the same mesh — the window stacks stay intra-slice and
    # only the gradient psum crosses DCN.
    import jax

    cols = _sparse_data(512, 2000, 6, seed=11)
    cache = _fill(HostDataCache(), cols)
    with mesh_context(
        MeshContext(devices=jax.devices()[:8], n_data=4, n_model=1, n_slices=2)
    ) as ctx:
        coefs = {}
        for kernel in ("onehot", "scatter"):
            coefs[kernel] = SGD(
                stream_window_rows=32, sparse_kernel=kernel, ctx=ctx, **KW
            ).optimize(np.zeros(2000, np.float32), cache, BinaryLogisticLoss.INSTANCE)
        np.testing.assert_allclose(
            coefs["onehot"], coefs["scatter"], rtol=1e-3, atol=1e-5
        )


@pytest.mark.parametrize("make_cols", [_sparse_data, _heavy_sparse_data])
@pytest.mark.parametrize("n_data,n_model", [(1, 1), (2, 1), (2, 2)])
def test_streamed_window_stacks_equal_the_resident_builds(n_data, n_model, make_cols):
    # The same rows through both routes: each shard's 128 rows are four
    # minibatches of 32, streamed as two windows of two minibatches. The
    # units are the same, so the global plan is the same, and window j's
    # stacks are the resident build's windows 2j and 2j + 1.
    import jax

    from flink_ml_tpu.linalg.onehot_sparse import OneHotSparseLayout
    from flink_ml_tpu.ops.optimizer import _OneHotWindowStream, streamed_onehot_plan

    n, dim, b, W = 128 * n_data, 3000, 32, 64
    cols = make_cols(n, dim, 6, seed=20 + n_data)
    cache = _fill(HostDataCache(), cols)
    resident = OneHotSparseLayout.build(
        cols["indices"], cols["values"], dim, n_data, b, n_model=n_model
    )
    assert bool(resident.plan.chunks_of_block) == (make_cols is _heavy_sparse_data)
    assert resident.window_starts == [0, 32, 64, 96] and resident.n_sub == 1
    plan = streamed_onehot_plan(cache, n, n_data, W, b, dim, n_model)
    assert plan.program_key() == resident.plan.program_key()
    devices = jax.devices()[: n_data * n_model]
    with mesh_context(MeshContext(devices=devices, n_data=n_data, n_model=n_model)) as ctx:
        stream = _OneHotWindowStream(cache, ctx, plan, W, b, 1, 128, n)
        for j in range(2):
            got = stream.load(j)["stacks"]
            for have, want in zip(got, (resident.lidx, resident.rowid, resident.lvals)):
                np.testing.assert_array_equal(
                    np.asarray(have), want[:, :, 2 * j : 2 * j + 2]
                )
