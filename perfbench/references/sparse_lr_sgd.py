"""Plain reference for the sparse logistic-regression fit: minibatch SGD in
float64 numpy, independent of the code under test.

Copied from ``chip_smoke.py::reference_sgd`` (the library's batch schedule:
every data shard cycles through ITS rows by ``ceil(batch / shards)``, short
tail batch included — SGD.java:246-285), with ``np.add.at`` replaced by
``np.bincount`` and the per-step mean loss returned beside the coefficient.

``precision="bf16"`` is the control of the output check: the same steps with
the two crossings of the step (the gather-dot of the coefficient and the
scatter of the multiplier) each computed in ONE bfloat16 pass — the operand a
TPU would feed its MXU without the split-bf16 pair the program carries. It
must come out as not correct.
"""
from __future__ import annotations

import numpy as np


def round_bf16(x: np.ndarray) -> np.ndarray:
    """float -> nearest-even bfloat16, returned as float64."""
    bits = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32).astype(np.float64)


def batch_schedule(n: int, n_shards: int, global_batch: int, steps: int):
    """Yield, per step, the row numbers the step consumes."""
    m = -(-n // n_shards)
    lb = min(-(-global_batch // n_shards), m)
    off = 0
    for _ in range(steps):
        yield np.concatenate(
            [
                np.arange(k * m + off, min(k * m + min(off + lb, m), n))
                for k in range(n_shards)
            ]
        )
        off = 0 if off + lb >= m else off + lb


def rows_consumed(n: int, n_shards: int, global_batch: int, steps: int) -> int:
    """Rows one fit job consumes: the sum of its minibatch sizes."""
    return sum(len(r) for r in batch_schedule(n, n_shards, global_batch, steps))


def reference_fit(idx, vals, y, dim, n_shards, global_batch, steps, lr, precision="f64"):
    """``(coefficient [dim] float64, per-step mean loss [steps])``."""
    if precision not in ("f64", "bf16"):
        raise ValueError(f"unknown precision {precision!r}")
    low = precision == "bf16"
    coef = np.zeros(dim, np.float64)
    losses = []
    for rows in batch_schedule(len(y), n_shards, global_batch, steps):
        xi, xv = idx[rows], vals[rows].astype(np.float64)
        ys = 2.0 * y[rows].astype(np.float64) - 1.0
        gathered = round_bf16(coef)[xi] if low else coef[xi]
        z = np.sum(xv * gathered, axis=1) * ys
        losses.append(float(np.mean(np.logaddexp(0.0, -z))))
        mult = -ys / (1.0 + np.exp(z))
        if low:
            mult = round_bf16(mult)
        grad = np.bincount(
            xi.ravel(), weights=(xv * mult[:, None]).ravel(), minlength=dim
        )
        coef = coef - (lr / len(rows)) * grad
    return coef, np.asarray(losses)
