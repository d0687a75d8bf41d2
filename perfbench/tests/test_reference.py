"""The copied reference agrees with the program at a toy size on the CPU, and
the check's control - the same reference in one bfloat16 pass - does not."""
import numpy as np
import pytest

from perfbench.manifest import Manifest
from perfbench.references import sparse_lr_sgd
from perfbench.systems import sparse_lr_fit

TOY_FIELDS = [3, 16, 64, 1000, 50000, 2000000, 40000000]


def toy(name):
    cfg = Manifest().config(name)
    return {**cfg, **cfg["toy"]}


def test_round_bf16():
    x = np.array([1.0, 1.00390625, 1.001, -3.14159, 0.0], np.float64)
    got = sparse_lr_sgd.round_bf16(x)
    assert got[0] == 1.0 and got[4] == 0.0
    assert got[1] in (1.0, 1.0078125)  # a tie: nearest even
    assert abs(got[3] + 3.14159) < 2 ** -6
    assert np.all(np.abs(got - x) <= np.abs(x) * 2 ** -8)


def test_schedule_counts_rows_not_steps_times_batch():
    # 250,000 rows, batch 65,536: three full minibatches and a 53,392-row tail per pass
    sizes = [len(r) for r in sparse_lr_sgd.batch_schedule(250_000, 1, 65_536, 8)]
    assert sizes == [65_536, 65_536, 65_536, 53_392] * 2
    assert sparse_lr_sgd.rows_consumed(250_000, 1, 65_536, 64) == 4_000_000
    assert sparse_lr_sgd.rows_consumed(196_608, 1, 65_536, 64) == 64 * 65_536
    assert sparse_lr_sgd.rows_consumed(524_288, 4, 65_536, 64) == 64 * 65_536


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_sparse_lr_reference_agrees_with_a_toy_fit_and_the_control_does_not(seed):
    system = sparse_lr_fit.create(toy("criteo_lr"), seed, 1)
    system.make_data()
    system.build()
    got = system.fit()
    want = system.reference()
    sound = system.compare(got, want)
    control = system.compare(system.reference("bf16"), want)
    assert sound["steps_missing"] == 0
    assert sound["coef_rel_err"] < 1e-5, sound
    assert control["coef_rel_err"] > 30 * sound["coef_rel_err"], (sound, control)


def test_every_seed_runs_the_same_layout_on_other_data():
    a = sparse_lr_fit.make_rows(1, 4000, 1 << 15, TOY_FIELDS, 1.05)
    b = sparse_lr_fit.make_rows(2**31 + 2, 4000, 1 << 15, TOY_FIELDS, 1.05)
    assert not np.array_equal(a[0], b[0])
    # entries per block of 128 ids: the same multiset, in another order
    count = lambda idx: np.sort(np.bincount((idx // 128).ravel(), minlength=256))  # noqa: E731
    assert np.array_equal(count(a[0]), count(b[0]))
    assert np.all(np.diff(a[0], axis=1) > 0) and np.all(np.diff(b[0], axis=1) > 0)
    again = sparse_lr_fit.make_rows(1, 4000, 1 << 15, TOY_FIELDS, 1.05)
    assert np.array_equal(a[0], again[0]) and np.array_equal(a[1], again[1])
    assert a[1].mean() == 0.5  # classes balanced


def test_field_values_are_heavy_tailed():
    """A few ids (a small field's commonest values) sit in a large share of the
    rows and most ids are rare: the shape of a click log, not uniform ids."""
    idx, _ = sparse_lr_fit.make_rows(1, 20000, 1 << 22, Manifest().config("criteo_lr")["field_cardinalities"], 1.05)
    assert idx.shape == (20000, 39)
    counts = np.bincount(idx.ravel())
    counts = np.sort(counts[counts > 0])[::-1]
    assert counts[0] > 0.4 * 20000 and counts[9] > 0.1 * 20000
    assert np.median(counts) <= 2
    # two cardinalities, checked against the law: P(rank 1) = (2^e - 1) / ((C + 1)^e - 1), e = 1 - alpha
    rng = np.random.default_rng(0)
    ids = sparse_lr_fit._hashed_field_ids(rng, 200000, [3], 1.05, 1 << 22)
    top = np.bincount(ids.ravel()).max() / 200000
    assert abs(top - (2 ** -0.05 - 1) / (4 ** -0.05 - 1)) < 0.01


def test_the_column_is_what_the_public_constructor_builds():
    """``build()`` fills ``SparseVector`` objects directly (the constructor's
    per-row sort and checks are about 5 s for a window, in every run's set-up);
    the objects are the ones the constructor would have made."""
    from flink_ml_tpu.linalg.vectors import SparseVector

    system = sparse_lr_fit.create(toy("criteo_lr"), 4, 1)
    system.make_data()
    system.build()
    rows = system.df.column("features")
    assert set(SparseVector.__slots__) == {"n", "indices", "values"}
    for i in (0, 1, len(system.idx) - 1):
        want = SparseVector(system.dim, system.idx[i], np.ones(system.nnz))
        got = rows[i]
        assert got.n == want.n and np.array_equal(got.indices, want.indices)
        assert np.array_equal(got.values, want.values) and got.values.dtype == want.values.dtype
        assert got.indices.dtype == want.indices.dtype
