#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py [--seed N]

One process on a TPU host drives the repo's main path — ``Estimator.fit`` →
``publish_servable`` → ``InferenceServer.predict`` — through the public entry
points, at the full width of the north-star model (Criteo-shape sparse
LogisticRegression: 2^22 features, 39 nnz/row, global batch 65,536), with
data and weights made from the seed. Four phases, each printing one JSON line
(``phase``, ``first_s`` = seconds to the first result with compilation
inside, ``compile_s`` = the XLA/Mosaic compile seconds JAX reported within
it, ``steady_s``, ``correct``):

- ``sparse_lr``           resident fit vs the plain numpy SGD step, then
                          publish → fresh server → 32 sparse requests
- ``sparse_lr_streamed``  the same data through the capacity-tier cache in
                          125,000-row windows, plus a short premat-off fit
- ``attention``           SelfAttentionClassifier, T=4096, fused fold vs jnp
- ``fusion_fast``         scaler → MLP served as a Pallas megakernel

Every Pallas kernel on the path must have been lowered for Mosaic (never the
interpreter): the smoke reads the modules JAX lowered during each phase and
requires the kernels there by name as ``tpu_custom_call``s.

The run needs a TPU: its first act is to require ``jax.default_backend() ==
"tpu"`` and a ``device_kind`` it knows, else it exits 2 before any phase and
prints no result. Exit 1 = a phase failed. On success the LAST line of stdout
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.

``--rehearse-on-cpu`` runs the same code at toy sizes on the CPU backend to
debug the script itself; it never prints the pass line.
"""
from __future__ import annotations

import argparse
import gc
import glob
import json
import os
import re
import sys
import tempfile
import time

#: The chips this program has been brought up on — the kernels' VMEM budgets
#: are calibrated for them. Any other ``device_kind`` is an error.
KNOWN_DEVICE_KINDS = ("TPU v5 lite", "TPU v5e")

FULL = dict(
    dim=1 << 22, nnz=39, rows=250_000, batch=65_536, steps=8, window=125_000,
    chunk=25_000, lr=2048.0, requests=32, max_request_rows=64,
    attn_t=4096, attn_emb=512, attn_heads=4, attn_vocab=1024,
    mlp=(256, 512, 512, 8),
)
TOY = dict(
    dim=1 << 15, nnz=7, rows=15_000, batch=4_096, steps=8, window=8_192,
    chunk=4_096, lr=128.0, requests=8, max_request_rows=16,
    attn_t=64, attn_emb=32, attn_heads=4, attn_vocab=32,
    mlp=(32, 64, 64, 8),
)


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def check(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


# ---------------------------------------------------------------------------
# what the run records: compile seconds, lowered kernels, device memory
# ---------------------------------------------------------------------------


class Recorder:
    """Compile seconds (``jax.monitoring``) and the Mosaic kernels in the
    modules JAX lowered (``jax_dump_ir_to``), readable per phase."""

    def __init__(self, ir_dir: str):
        import jax

        self.ir_dir = ir_dir
        self.compile_s = 0.0
        self._seen = set()
        jax.config.update("jax_dump_ir_to", ir_dir)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def take_compile_s(self) -> float:
        out, self.compile_s = self.compile_s, 0.0
        return round(out, 3)

    def take_kernels(self) -> dict:
        """``{kernel name: count}`` of the ``tpu_custom_call``s in the modules
        lowered since the last call."""
        found = {}
        for path in sorted(glob.glob(os.path.join(self.ir_dir, "*.mlir"))):
            if path in self._seen:
                continue
            self._seen.add(path)
            with open(path) as f:
                text = f.read()
            for call in re.findall(r"@tpu_custom_call\(.*", text):
                name = re.search(r'kernel_name = "([^"]+)"', call)
                key = name.group(1) if name else "?"
                found[key] = found.get(key, 0) + 1
        return found


def device_memory() -> list:
    import jax

    out = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out.append(
            {
                "id": d.id,
                "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
                "bytes_limit": int(stats.get("bytes_limit", 0)),
            }
        )
    return out


# ---------------------------------------------------------------------------
# data and the plain reference
# ---------------------------------------------------------------------------


def make_criteo_shape(rng, n: int, dim: int, nnz: int):
    """Hash-style sparse rows: ``nnz`` distinct sorted feature ids per row,
    value 1.0 (Criteo's categorical fields), labels from a planted dense
    coefficient so the classes are balanced."""
    import numpy as np

    idx = np.sort(rng.integers(0, dim, size=(n, nnz), dtype=np.int64), axis=1)
    for r in np.flatnonzero((np.diff(idx, axis=1) == 0).any(axis=1)):
        idx[r] = np.sort(rng.choice(dim, size=nnz, replace=False))
    planted = rng.standard_normal(dim).astype(np.float32)
    y = (planted[idx].sum(axis=1) > 0).astype(np.float64)
    return idx, y


def reference_sgd(idx, vals, y, dim, n_shards, global_batch, steps, lr):
    """The plain numpy minibatch-SGD step (gather-dot, ``np.add.at``
    scatter, full coefficient update), in float64,
    with the library's batch schedule: every data shard cycles through ITS
    rows by ``ceil(batch / shards)`` (SGD.java:246-285), short tail batch
    included. Independent of the code under test."""
    import numpy as np

    n = len(y)
    m = -(-n // n_shards)
    lb = min(-(-global_batch // n_shards), m)
    coef = np.zeros(dim, np.float64)
    off = 0
    for _ in range(steps):
        rows = np.concatenate(
            [
                np.arange(k * m + off, min(k * m + min(off + lb, m), n))
                for k in range(n_shards)
            ]
        )
        xi, xv, yb = idx[rows], vals[rows].astype(np.float64), y[rows]
        ys = 2.0 * yb - 1.0
        z = np.sum(xv * coef[xi], axis=1) * ys
        mult = -ys / (1.0 + np.exp(z))
        grad = np.zeros(dim, np.float64)
        np.add.at(grad, xi.ravel(), (xv * mult[:, None]).ravel())
        coef = coef - (lr / len(yb)) * grad
        off = 0 if off + lb >= m else off + lb
    return coef


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_sparse_lr(cfg, rng, rec, on_tpu, state):
    import jax
    import numpy as np

    from flink_ml_tpu.api.dataframe import DataFrame
    from flink_ml_tpu.iteration import DeviceDataCache
    from flink_ml_tpu.linalg.vectors import SparseVector
    from flink_ml_tpu.metrics import MLMetrics, metrics
    from flink_ml_tpu.models.classification.logistic_regression import (
        LogisticRegression,
    )
    from flink_ml_tpu.serving import InferenceServer, ServingConfig, publish_servable

    n, dim, nnz = cfg["rows"], cfg["dim"], cfg["nnz"]
    out = {}

    t0 = time.perf_counter()
    idx, y = make_criteo_shape(rng, n, dim, nnz)
    ones = np.ones(nnz)
    vectors = [SparseVector(dim, row, ones) for row in idx]  # per-row loop
    df = DataFrame.from_dict({"features": vectors, "label": y})
    sb = df.sparse_batch("features")  # the padded-CSR pack fit() performs
    out["host_build_pack_s"] = round(time.perf_counter() - t0, 2)
    check(sb.indices.shape == (n, -(-nnz // 8) * 8), f"packed K: {sb.indices.shape}")
    state.update(indices=sb.indices, values=sb.values, labels=y.astype(np.float32))

    def fit():
        est = (
            LogisticRegression()
            .set_max_iter(cfg["steps"])
            .set_global_batch_size(cfg["batch"])
            .set_learning_rate(cfg["lr"])
            .set_tol(0.0)
        )
        gc.collect()  # the previous fit's multi-GB device cache is gone first
        t = time.perf_counter()
        model = est.fit(df)
        return est, model, time.perf_counter() - t

    est, model, first_s = fit()
    out["first_s"] = round(first_s, 2)
    out["compile_s"] = rec.take_compile_s()
    kernels = rec.take_kernels()
    _, _, steady_s = fit()
    out["steady_s"] = round(steady_s, 2)
    out["steps"] = len(est.loss_history)
    out["onehot_premat_active"] = bool(est.optimizer.onehot_premat_active)
    out["kernels"] = kernels

    n_dev = len(jax.devices())
    want = reference_sgd(
        sb.indices, sb.values, y, dim, n_dev, cfg["batch"], cfg["steps"], cfg["lr"]
    )
    got = np.asarray(model.coefficient, np.float64)
    out["coef_absmax"] = float(np.max(np.abs(want)))
    out["coef_max_abs_err"] = float(np.max(np.abs(got - want)))
    check(len(est.loss_history) == cfg["steps"], f"ran {len(est.loss_history)} steps")
    check(np.all(np.isfinite(got)) and got.shape == (dim,), "coefficient not finite [dim]")
    # the tolerance __graft_entry__ holds the one-hot kernel to; coef_absmax
    # shows it is not vacuous (the values sit far above atol)
    check(out["coef_absmax"] > 1e-2, f"reference coefficient too small: {out['coef_absmax']}")
    check(
        np.allclose(got, want, rtol=1e-4, atol=1e-5),
        f"coefficient != numpy reference: max abs err {out['coef_max_abs_err']:.3e}",
    )
    check(est.optimizer.onehot_premat_active, "one-hot premat route did not run")
    if on_tpu:
        for k in ("onehot_dot_crossing_premat", "onehot_mult_crossing_premat"):
            check(k in kernels, f"Mosaic kernel {k} not in the lowered step: {kernels}")
    state["coef"] = got

    # every device holds a shard of the training cache, nothing piled on one:
    # the cache fit() builds (same constructor, same default mesh), then the
    # peaks the fits above left on each chip
    cache = DeviceDataCache(
        {"indices": sb.indices, "values": sb.values, "labels": state["labels"]}
    )
    all_devices = set(jax.devices())
    for name, arr in cache.arrays.items():
        check(
            arr.sharding.device_set == all_devices,
            f"cache column {name} on {len(arr.sharding.device_set)}/{n_dev} devices",
        )
    del cache
    out["device_memory"] = device_memory()
    if on_tpu:
        peaks = [m["peak_bytes_in_use"] for m in out["device_memory"]]
        check(min(peaks) > 0, f"a chip reports no memory in use: {peaks}")
        check(min(peaks) >= 0.5 * max(peaks), f"memory piled on one chip: {peaks}")

    # publish → a fresh server → requests
    with tempfile.TemporaryDirectory(prefix="chip_smoke_models_") as model_dir:
        publish_servable(model, model_dir)
        template = DataFrame.from_dict({"features": [vectors[0]]})
        with InferenceServer(
            name="chip-smoke-lr",
            serving_config=ServingConfig(default_timeout_ms=120_000),
            warmup_template=template,
        ) as server:
            poller = server.attach_poller(model_dir, start=False)
            t0 = time.perf_counter()
            version = poller.poll_once()
            out["serve_warmup_s"] = round(time.perf_counter() - t0, 2)
            out["serve_compile_s"] = rec.take_compile_s()
            check(version == 1, f"published model did not load/warm: {poller.failed}")
            scope = server.scope
            compiles0 = metrics.get(scope, MLMetrics.SERVING_FASTPATH_COMPILES, 0)
            worst = 0.0
            t0 = time.perf_counter()
            for _ in range(cfg["requests"]):
                rows = rng.integers(0, n, size=int(rng.integers(1, cfg["max_request_rows"] + 1)))
                req = DataFrame.from_dict({"features": [vectors[int(r)] for r in rows]})
                resp = server.predict(req)
                p = np.asarray(resp.dataframe.column("rawPrediction"), np.float64)[:, 1]
                margin = (sb.values[rows].astype(np.float64) * got[sb.indices[rows]]).sum(axis=1)
                worst = max(worst, float(np.max(np.abs(p - 1.0 / (1.0 + np.exp(-margin))))))
                check(resp.model_version == 1, f"served by version {resp.model_version}")
            out["serve_steady_s"] = round(time.perf_counter() - t0, 3)
            out["serve_max_abs_err"] = worst
            moved = metrics.get(scope, MLMetrics.SERVING_FASTPATH_COMPILES, 0) - compiles0
            fused = metrics.get(scope, MLMetrics.SERVING_FUSED_BATCHES, 0)
            check(worst <= 1e-6, f"probabilities != sigmoid(margin): {worst:.3e}")
            check(moved == 0, f"{moved} fast-path compiles after warmup")
            check(fused >= cfg["requests"], f"only {fused} fused batches — requests fell back")
    return out


def phase_sparse_lr_streamed(cfg, rng, rec, on_tpu, state):
    import jax
    import numpy as np

    from flink_ml_tpu.iteration import create_capacity_cache
    from flink_ml_tpu.ops import SGD, BinaryLogisticLoss

    n, dim = cfg["rows"], cfg["dim"]
    idx, vals, labels = state["indices"], state["values"], state["labels"]
    out = {}
    with tempfile.TemporaryDirectory(prefix="chip_smoke_spill_") as spill:
        cache = create_capacity_cache(memory_budget_bytes=64 << 20, spill_dir=spill)
        out["chunk_store"] = (
            "native" if type(cache).__name__ == "NativeDataCache" else "python"
        )
        for lo in range(0, n, cfg["chunk"]):
            hi = min(lo + cfg["chunk"], n)
            cache.append(
                {
                    "indices": idx[lo:hi],
                    "values": vals[lo:hi],
                    "labels": labels[lo:hi],
                    "weights": np.ones(hi - lo, np.float32),
                }
            )
        cache.finish()

        def fit(steps, premat):
            sgd = SGD(
                max_iter=steps, global_batch_size=cfg["batch"], tol=0.0,
                learning_rate=cfg["lr"], stream_window_rows=cfg["window"],
                onehot_premat=premat,
            )
            gc.collect()
            t = time.perf_counter()
            coef = sgd.optimize(np.zeros(dim, np.float32), cache, BinaryLogisticLoss.INSTANCE)
            return sgd, np.asarray(coef, np.float64), time.perf_counter() - t

        sgd, coef, first_s = fit(cfg["steps"], "auto")
        out["first_s"] = round(first_s, 2)
        out["compile_s"] = rec.take_compile_s()
        kernels = rec.take_kernels()
        _, _, steady_s = fit(cfg["steps"], "auto")
        out["steady_s"] = round(steady_s, 2)
        out["onehot_premat_active"] = bool(sgd.onehot_premat_active)
        out["vs_resident_max_abs_err"] = float(np.max(np.abs(coef - state["coef"])))
        # the tolerance tests/test_streaming_onehot.py holds streamed vs resident to
        check(
            np.allclose(coef, state["coef"], rtol=1e-4, atol=1e-6),
            f"streamed != resident coefficient: {out['vs_resident_max_abs_err']:.3e}",
        )

        # the build-form kernels, compiled by Mosaic too: a short premat-off fit
        short = 2
        sgd_off, coef_off, off_s = fit(short, "off")
        out["premat_off_first_s"] = round(off_s, 2)
        out["premat_off_compile_s"] = rec.take_compile_s()
        kernels_off = rec.take_kernels()
        want = reference_sgd(
            idx, vals, labels, dim, len(jax.devices()), cfg["batch"], short, cfg["lr"]
        )
        out["premat_off_max_abs_err"] = float(np.max(np.abs(coef_off - want)))
        check(not sgd_off.onehot_premat_active, "premat ran although forced off")
        check(
            np.allclose(coef_off, want, rtol=1e-4, atol=1e-5),
            f"build-form coefficient != numpy reference: {out['premat_off_max_abs_err']:.3e}",
        )
        out["kernels"] = {**kernels, **kernels_off}
        if on_tpu:
            form = "_premat" if sgd.onehot_premat_active else ""
            for k in (f"onehot_dot_crossing{form}", f"onehot_mult_crossing{form}"):
                check(k in kernels, f"Mosaic kernel {k} not in the streamed step: {kernels}")
            for k in ("onehot_dot_crossing", "onehot_mult_crossing"):
                check(k in kernels_off, f"Mosaic kernel {k} not in the build-form step: {kernels_off}")
        if hasattr(cache, "close"):
            cache.close()
    return out


def phase_attention(cfg, rng, rec, on_tpu, state):
    import numpy as np

    from flink_ml_tpu.api.dataframe import DataFrame
    from flink_ml_tpu.models.classification import attention_classifier as ac

    T, vocab = cfg["attn_t"], cfg["attn_vocab"]
    tok = rng.integers(0, vocab, size=(2, T)).astype(np.float64)
    df = DataFrame.from_dict({"features": tok, "label": np.asarray([0.0, 1.0])})
    out = {}

    def fit():
        est = (
            ac.SelfAttentionClassifier()
            .set_embedding_dim(cfg["attn_emb"])
            .set_num_heads(cfg["attn_heads"])
            .set_vocab_size(vocab)
            .set_global_batch_size(1)
            .set_max_iter(2)
            .set_learning_rate(1e-3)  # adam: keep the second step comparable
            .set_seed(int(state["seed"]))
        )
        t = time.perf_counter()
        model = est.fit(df)
        return est, model, time.perf_counter() - t

    est, model, first_s = fit()
    out["first_s"] = round(first_s, 2)
    out["compile_s"] = rec.take_compile_s()
    kernels = rec.take_kernels()
    _, _, steady_s = fit()
    out["steady_s"] = round(steady_s, 3)
    out["kernels"] = kernels
    out["loss"] = est.loss_history

    # the same two steps on the jnp fold (flash off), same seed
    gate = ac._use_flash_train
    ac._use_flash_train = lambda *a, **k: False
    try:
        ref, _, _ = fit()
    finally:
        ac._use_flash_train = gate
    rec.take_compile_s()
    rec.take_kernels()
    out["loss_jnp_fold"] = ref.loss_history
    rel = max(
        abs(a - b) / max(abs(b), 1e-30) for a, b in zip(est.loss_history, ref.loss_history)
    )
    out["loss_max_rel_err"] = rel
    check(len(est.loss_history) == 2 and all(np.isfinite(est.loss_history)), "loss not finite")
    check(rel <= 1e-3, f"fused-fold loss != jnp-fold loss: rel {rel:.3e}")
    probs = np.asarray(model.transform(df).column("rawPrediction"))
    check(probs.shape == (2, 2) and np.all(np.isfinite(probs)), "transform not finite [2, 2]")
    if on_tpu:
        for k in ("flash_fold_fwd", "flash_fold_bwd_dq", "flash_fold_bwd_dkv"):
            check(k in kernels, f"Mosaic kernel {k} not in the lowered step: {kernels}")
    return out


def phase_fusion_fast(cfg, rng, rec, on_tpu, state):
    import numpy as np

    from flink_ml_tpu.api.dataframe import DataFrame
    from flink_ml_tpu.config import Options, config
    from flink_ml_tpu.metrics import MLMetrics, metrics
    from flink_ml_tpu.servable import (
        MLPClassifierModelServable,
        PipelineModelServable,
        StandardScalerModelServable,
    )
    from flink_ml_tpu.servable.fusion import ULP_ENVELOPE, ulp_diff
    from flink_ml_tpu.serving import InferenceServer, ServingConfig

    dims = cfg["mlp"]

    def servable():
        r = np.random.default_rng(int(state["seed"]) + 1)
        sc = StandardScalerModelServable().set_input_col("features").set_output_col("scaled")
        sc.set_with_mean(True)
        sc.mean = r.standard_normal(dims[0])
        sc.std = np.abs(r.standard_normal(dims[0])) + 0.5
        mlp = MLPClassifierModelServable().set_features_col("scaled")
        mlp.layers = [
            (
                (r.standard_normal((a, b)) * np.sqrt(2.0 / a)).astype(np.float32),
                (0.1 * r.standard_normal(b)).astype(np.float32),
            )
            for a, b in zip(dims[:-1], dims[1:])
        ]
        mlp.labels = np.arange(float(dims[-1]))
        return PipelineModelServable([sc, mlp])

    template = DataFrame.from_dict({"features": rng.standard_normal((1, dims[0]))})
    requests = [
        DataFrame.from_dict(
            {"features": rng.standard_normal((int(rng.integers(1, 65)), dims[0]))}
        )
        for _ in range(16)
    ]
    out = {}
    results = {}
    config.set(Options.FUSION_MEGAKERNEL_MIN_SCORE, 1.0)  # force the chain hot
    try:
        for mode in ("exact", "fast"):
            model = servable()  # fresh per tier: each carries its own compiled plan
            t0 = time.perf_counter()
            with InferenceServer(
                model,
                name=f"chip-smoke-fusion-{mode}",
                serving_config=ServingConfig(fusion_mode=mode, default_timeout_ms=120_000),
                warmup_template=template,
            ) as server:
                warm_s = time.perf_counter() - t0
                scope = server.scope
                compiles0 = metrics.get(scope, MLMetrics.SERVING_FASTPATH_COMPILES, 0)
                t0 = time.perf_counter()
                results[mode] = [server.predict(req).dataframe for req in requests]
                steady_s = time.perf_counter() - t0
                moved = metrics.get(scope, MLMetrics.SERVING_FASTPATH_COMPILES, 0) - compiles0
                check(moved == 0, f"{moved} fast-path compiles after warmup in fusion.mode={mode}")
            if mode == "fast":
                out["first_s"] = round(warm_s, 2)
                out["steady_s"] = round(steady_s, 3)
                (segment,) = model._fastpath_plan.segments
                out["plan_kinds"] = sorted({k for kinds in segment.plan_kinds.values() for k in kinds})
                out["megakernel_fallbacks"] = metrics.get(
                    scope, MLMetrics.FUSION_MEGAKERNEL_FALLBACKS, 0
                )
        out["compile_s"] = rec.take_compile_s()
        out["kernels"] = rec.take_kernels()
    finally:
        config.unset(Options.FUSION_MEGAKERNEL_MIN_SCORE)

    envelope = ULP_ENVELOPE["scale_mlp"]
    moved = max(
        ulp_diff(f.column("rawPrediction"), e.column("rawPrediction"))
        for f, e in zip(results["fast"], results["exact"])
    )
    out["ulp_vs_exact"] = moved
    out["ulp_envelope"] = envelope
    flips = sum(
        int(np.sum(np.asarray(f.column("prediction")) != np.asarray(e.column("prediction"))))
        for f, e in zip(results["fast"], results["exact"])
    )
    out["prediction_flips"] = flips
    check(out["plan_kinds"] == ["megakernel"], f"plan kinds {out['plan_kinds']}")
    check(out["megakernel_fallbacks"] == 0, f"{out['megakernel_fallbacks']} megakernel fallbacks")
    check(moved <= envelope, f"fast tier moved {moved} ulps (envelope {envelope})")
    if on_tpu:
        check(
            out["kernels"].get("serving_megakernel", 0) > 0,
            f"Mosaic megakernel not in the lowered plans: {out['kernels']}",
        )
    return out


PHASES = {
    "sparse_lr": phase_sparse_lr,
    "sparse_lr_streamed": phase_sparse_lr_streamed,
    "attention": phase_attention,
    "fusion_fast": phase_fusion_fast,
}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--rehearse-on-cpu", action="store_true",
        help="toy sizes on the CPU backend, to debug this script; never a pass",
    )
    args = parser.parse_args(argv)

    import jax

    backend = jax.default_backend()
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    on_tpu = backend == "tpu"
    if args.rehearse_on_cpu:
        if backend != "cpu":
            print(f"chip_smoke: --rehearse-on-cpu needs the CPU backend, found {backend!r}", file=sys.stderr)
            return 2
    elif not on_tpu:
        print(
            f"chip_smoke: no TPU — jax.default_backend() is {backend!r} "
            f"({device['kind']} x{device['count']}); this smoke proves the chip path "
            "and does not fall back",
            file=sys.stderr,
        )
        return 2
    elif device["kind"] not in KNOWN_DEVICE_KINDS:
        print(
            f"chip_smoke: unknown device_kind {device['kind']!r} (known: "
            f"{', '.join(KNOWN_DEVICE_KINDS)}) — not a chip this program was brought up on",
            file=sys.stderr,
        )
        return 2

    import importlib.metadata as md

    import jaxlib
    import numpy as np

    from flink_ml_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    cache_entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    print(
        json.dumps(
            {
                "device": device,
                "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__, "libtpu": libtpu},
                "compile_cache": {"dir": cache_dir, "entries_at_start": cache_entries},
                "seed": args.seed,
                "size": "toy (cpu rehearsal)" if args.rehearse_on_cpu else "full",
            }
        ),
        flush=True,
    )

    cfg = TOY if args.rehearse_on_cpu else FULL
    rng = np.random.default_rng(args.seed)
    state = {"seed": args.seed}
    failed = []
    t_run = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ir_") as ir_dir:
        rec = Recorder(ir_dir)
        for name, phase in PHASES.items():
            t0 = time.perf_counter()
            line = {"phase": name}
            try:
                line.update(phase(cfg, rng, rec, on_tpu, state))
                line["correct"] = True
            except Exception as e:  # noqa: BLE001 — a failed phase is the result
                import traceback

                traceback.print_exc()
                line.update(correct=False, error=f"{type(e).__name__}: {e}"[:2000])
                failed.append(name)
            line["wall_s"] = round(time.perf_counter() - t0, 2)
            print(json.dumps(line), flush=True)
            gc.collect()
    print(json.dumps({"total_s": round(time.perf_counter() - t_run, 2), "failed": failed}), flush=True)
    if failed:
        return 1
    if args.rehearse_on_cpu:
        print("chip_smoke: cpu rehearsal finished — this is not a pass", flush=True)
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
