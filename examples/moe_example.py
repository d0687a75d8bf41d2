"""Dropless top-k mixture-of-experts feed-forward (no reference analogue - the
routed layer of ``models/lm``'s decoder; see docs/distributed.md).
"""
import numpy as np

from flink_ml_tpu.parallel import moe_dropless


def main():
    rng = np.random.default_rng(0)
    T, d, h, E, k = 512, 16, 32, 8, 2
    x = rng.standard_normal((T, d)).astype(np.float32)
    router = rng.standard_normal((d, E)).astype(np.float32)
    w_gate = (rng.standard_normal((E, d, h)) * 0.2).astype(np.float32)
    w_up = (rng.standard_normal((E, d, h)) * 0.2).astype(np.float32)
    w_down = (rng.standard_normal((E, h, d)) * 0.2).astype(np.float32)

    out, stats = moe_dropless(x, router, w_gate, w_up, w_down, k)
    rows = np.asarray(stats["rows"])
    print(f"{T} tokens x top-{k} routed across {E} experts, none dropped")
    print("rows per expert:", rows.tolist(), "sum:", int(rows.sum()), "=", T * k)
    print("output shape:", out.shape, "finite:", bool(np.isfinite(np.asarray(out)).all()))


if __name__ == "__main__":
    main()
