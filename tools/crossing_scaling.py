"""Measured per-chip cost of the one-hot sparse program vs data parallelism.

The sparse roofline's scaling claim (docs/benchmarks.md): the crossing term —
the two-level one-hot contractions reindexing entries between feature-grouped
and row-grouped orders — costs ~``local_batch * sub_batch * nnz_pad`` MACs
per chip, so p-way DP (which divides both the per-shard entry count and,
once below the 16384 cap, the sub-batch row space) drives it down ~1/p².

This module turns that argument into a *measured artifact*: it compiles the
actual ``_fused_onehot_program`` over a p-way mesh for each p and reads the
per-chip FLOP/byte counts from XLA's compiled-cost analysis
(``jit(...).lower(...).compile().cost_analysis()`` — under SPMD partitioning
the compiled executable IS the per-device program, so these are per-chip
numbers). The XLA (non-Pallas) crossings are measured: same contraction
structure, and Mosaic kernels are opaque to XLA cost analysis anyway.

Run on the 8-device virtual CPU mesh:

    env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/crossing_scaling.py

``tests/test_crossing_scaling.py`` asserts the superlinear falloff on a
smaller shape every CI run.
"""
from __future__ import annotations

import numpy as np

__all__ = ["measure_scaling", "markdown_table"]


def measure_scaling(p_list, global_batch, dim, nnz, K, seed=0):
    """Compile the fused one-hot SGD program at each DP width and return
    ``[{p, local_batch, sub_batch, n_flat, flops_per_chip, bytes_per_chip}]``.

    One window, one epoch per chunk (chunk_len=1): the numbers are one
    minibatch step's per-chip cost, the unit the scaling claim is about.
    """
    import jax

    from flink_ml_tpu.iteration import DeviceDataCache
    from flink_ml_tpu.linalg.onehot_sparse import OneHotSparseLayout
    from flink_ml_tpu.ops import BinaryLogisticLoss
    from flink_ml_tpu.ops.optimizer import _fused_onehot_program
    from flink_ml_tpu.parallel.mesh import (
        DATA_AXIS,
        MODEL_AXIS,
        MeshContext,
        mesh_context,
    )

    rng = np.random.default_rng(seed)
    n = global_batch  # one window: the dataset IS one global minibatch
    idx = rng.integers(0, dim, size=(n, K), dtype=np.int32)
    vals = np.ones((n, K), np.float32)
    vals[:, nnz:] = 0.0
    y = (rng.random(n) > 0.5).astype(np.float32)
    w = np.ones(n, np.float32)

    rows = []
    for p in p_list:
        with mesh_context(MeshContext(n_data=p, n_model=1)) as ctx:
            local_batch = global_batch // p
            lay = OneHotSparseLayout.build(idx, vals, dim, p, local_batch)
            cache = DeviceDataCache(
                {"indices": idx, "values": vals, "labels": y, "weights": w},
                ctx=ctx,
            )
            program = _fused_onehot_program(
                ctx, BinaryLogisticLoss.INSTANCE, lay, 1, 0.1, 0.0, 0.0, None,
                use_pallas=False,
            )
            sh = ctx.sharding(DATA_AXIS, MODEL_AXIS)
            stacks = (
                jax.device_put(lay.lidx, sh),
                jax.device_put(lay.rowid, sh),
                jax.device_put(np.asarray(lay.lvals, np.float32), sh),
            )
            args = (
                ctx.replicate(lay.permute_coef(np.zeros(dim, np.float32))),
                ctx.replicate(np.asarray(False)),
                np.zeros(1, np.int32),
                np.zeros(1, np.int32),
                np.ones(1, bool),
                *stacks,
                cache["labels"],
                cache["weights"],
                cache.mask.astype(np.float32),
            )
            cost = program.lower(*args).compile().cost_analysis()
            if isinstance(cost, (list, tuple)):  # some backends wrap in a list
                cost = cost[0]
            rows.append({
                "p": p,
                "local_batch": local_batch,
                "sub_batch": lay.sub_batch,
                "n_sub": lay.n_sub,
                "n_flat": lay.n_flat,
                "flops_per_chip": float(cost.get("flops", float("nan"))),
                "bytes_per_chip": float(
                    cost.get("bytes accessed", float("nan"))
                ),
            })
    return rows


def markdown_table(rows) -> str:
    head = (
        "| p (DP chips) | local batch | sub batch | n_flat/unit | "
        "per-chip GFLOP/step | x fall vs p=1 | p x fall (superlinear > 1/p) |"
        "\n|---|---|---|---|---|---|---|\n"
    )
    base = rows[0]["flops_per_chip"]
    lines = []
    for r in rows:
        fall = base / r["flops_per_chip"] if r["flops_per_chip"] else float("nan")
        lines.append(
            f"| {r['p']} | {r['local_batch']} | {r['sub_batch']} | {r['n_flat']} "
            f"| {r['flops_per_chip'] / 1e9:.2f} | {fall:.1f}x "
            f"| {fall / r['p']:.2f} |"
        )
    return head + "\n".join(lines)


if __name__ == "__main__":
    rows = measure_scaling(
        [1, 2, 4, 8], global_batch=65_536, dim=1 << 20, nnz=39, K=40,
    )
    print(markdown_table(rows))
