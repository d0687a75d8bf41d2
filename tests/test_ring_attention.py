"""Ring attention (parallel/ring.py): sequence-parallel blockwise attention
must match dense single-device attention exactly (up to float tolerance),
causal and not, on the 8-device mesh."""
import numpy as np
import pytest

from flink_ml_tpu.parallel.ring import ring_attention_sharded


def _dense_attention(q, k, v, causal):
    B, T, H, D = q.shape
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    if causal:
        mask = np.tril(np.ones((T, T), bool))
        s = np.where(mask[None, None], s, -np.inf)
    s = s - s.max(axis=-1, keepdims=True)
    p = np.exp(s)
    p = p / p.sum(axis=-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_matches_dense_attention(causal):
    rng = np.random.default_rng(0)
    B, T, H, D = 2, 64, 2, 8  # T sharded 8 ways -> 8 ring steps
    q = rng.standard_normal((B, T, H, D)).astype(np.float32)
    k = rng.standard_normal((B, T, H, D)).astype(np.float32)
    v = rng.standard_normal((B, T, H, D)).astype(np.float32)
    got = np.asarray(ring_attention_sharded(q, k, v, causal=causal))
    want = _dense_attention(q, k, v, causal)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_long_sequence_runs():
    # 16k tokens on the virtual mesh: the [T, T] score matrix (256M floats)
    # never materializes; per-shard peak is O(T_local^2) per ring step.
    rng = np.random.default_rng(1)
    B, T, H, D = 1, 16_384, 1, 16
    q = rng.standard_normal((B, T, H, D)).astype(np.float32)
    out = np.asarray(ring_attention_sharded(q, q, q, causal=True))
    assert out.shape == (B, T, H, D)
    assert np.all(np.isfinite(out))
    # position 0 attends only to itself under causal masking
    np.testing.assert_allclose(out[0, 0, 0], q[0, 0, 0], rtol=1e-5)


def test_uneven_sequence_rejected():
    q = np.zeros((1, 10, 1, 4), np.float32)
    with pytest.raises(ValueError, match="divisible"):
        ring_attention_sharded(q, q, q)


def test_flash_gate_rejects_unverified_boundary_shapes():
    # Shapes past T_local=8192 stage VMEM the scoped limit does not cover
    # (e.g. T=16384, D=64: a [256, 16384] f32 score buffer plus full KV) and
    # were never compile-verified on chip — the gate must refuse them so the
    # caller falls back to the jnp fold rather than fail Mosaic compilation.
    from flink_ml_tpu.parallel.flash import TQ_TILE, flash_available

    class FakeTpu:
        device_kind = "TPU v5 lite"

    devs = [FakeTpu()]
    assert flash_available(8192, 128, devs)  # the hardware-measured shape
    assert not flash_available(16384, 64, devs)  # boundary: rejected
    assert not flash_available(8192, 256, devs)  # KV budget still enforced
    assert not flash_available(TQ_TILE - 1, 64, devs)  # tiling still enforced


def test_flash_gate_budgets_keys_and_values_at_their_own_widths():
    """A value head of its own size: the gate budgets what a cell stages, K at
    ``D`` plus V at ``Dv``. Latent attention's 192 / 128 at T 8,192 is the
    largest shape compiled in a training graph on the chip (PERF.md, PR 44) and
    is the budget; the same keys with values as wide as them are past it."""
    from flink_ml_tpu.parallel.flash import flash_available

    class FakeTpu:
        device_kind = "TPU v5 lite"

    devs = [FakeTpu()]
    assert flash_available(8192, 192, devs, Dv=128)
    assert not flash_available(8192, 192, devs) and not flash_available(8192, 192, devs, Dv=192)
    assert not flash_available(8192, 192, devs, Dv=256) and not flash_available(16384, 96, devs, Dv=64)
    assert flash_available(4096, 256, devs) == flash_available(4096, 256, devs, Dv=256) is True  # None: as wide as K
    assert not flash_available(8192, 192, [object()], Dv=128)  # and the devices are still asked


def test_padded_sequence_with_n_valid_matches_dense():
    rng = np.random.default_rng(2)
    B, T_real, H, D = 1, 50, 2, 8
    T_pad = 56  # next multiple of the 8-way mesh
    q = rng.standard_normal((B, T_real, H, D)).astype(np.float32)
    k = rng.standard_normal((B, T_real, H, D)).astype(np.float32)
    v = rng.standard_normal((B, T_real, H, D)).astype(np.float32)
    pad = ((0, 0), (0, T_pad - T_real), (0, 0), (0, 0))
    got = np.asarray(
        ring_attention_sharded(
            np.pad(q, pad), np.pad(k, pad), np.pad(v, pad), n_valid=T_real
        )
    )[:, :T_real]
    want = _dense_attention(q, k, v, causal=False)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_gradients_match_dense_attention():
    """jax.grad flows through the ring schedule (scan + ppermute are
    differentiable), matching dense-attention gradients — the property a
    sequence-model trainer would rely on."""
    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.parallel.mesh import get_mesh_context
    from flink_ml_tpu.parallel.ring import _sharded_program

    rng = np.random.default_rng(3)
    B, T, H, D = 1, 32, 2, 4
    q = jnp.asarray(rng.standard_normal((B, T, H, D)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, T, H, D)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, T, H, D)).astype(np.float32))
    ctx = get_mesh_context()
    program = _sharded_program(ctx.mesh, True, False, False)

    def ring_loss(q, k, v):
        return jnp.sum(program(q, k, v) ** 2)

    def dense_loss(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        return jnp.sum(out ** 2)

    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for gr, gd in zip(g_ring, g_dense):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gd), rtol=2e-4, atol=2e-5)


class TestFlashFold:
    """The fused Pallas fold (parallel/flash.py) must reproduce the jnp fold
    and, through the ring, dense attention — in interpret mode on any
    backend (compiled on TPU)."""

    @pytest.mark.parametrize("n_shards", [8, 4])
    def test_flash_ring_matches_dense(self, n_shards):
        import jax.numpy as jnp
        from jax.experimental.pallas import tpu as pltpu

        from flink_ml_tpu.parallel.mesh import MeshContext, get_mesh_context
        from flink_ml_tpu.parallel.ring import _sharded_program

        rng = np.random.default_rng(4)
        ctx = get_mesh_context() if n_shards == 8 else MeshContext(n_data=n_shards)
        assert ctx.n_data == n_shards
        T = 256 * ctx.n_data  # T_local = one Q tile per shard
        B, H, D = 1, 2, 8
        q = rng.standard_normal((B, T, H, D)).astype(np.float32)
        k = rng.standard_normal((B, T, H, D)).astype(np.float32)
        v = rng.standard_normal((B, T, H, D)).astype(np.float32)
        with pltpu.force_tpu_interpret_mode():
            got = np.asarray(
                _sharded_program(ctx.mesh, True, False, True)(q, k, v)
            )
        want = _dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    def test_flash_ring_padded_n_valid(self):
        import jax.numpy as jnp
        from jax.experimental.pallas import tpu as pltpu

        from flink_ml_tpu.parallel.mesh import get_mesh_context
        from flink_ml_tpu.parallel.ring import _sharded_program

        rng = np.random.default_rng(5)
        ctx = get_mesh_context()
        T = 256 * ctx.n_data
        n_real = T - 100
        B, H, D = 1, 1, 8
        q = rng.standard_normal((B, T, H, D)).astype(np.float32)
        k = rng.standard_normal((B, T, H, D)).astype(np.float32)
        v = rng.standard_normal((B, T, H, D)).astype(np.float32)
        with pltpu.force_tpu_interpret_mode():
            got = np.asarray(
                _sharded_program(ctx.mesh, False, True, True)(
                    q, k, v, jnp.asarray(n_real, jnp.int32)
                )
            )
        want = _dense_attention(
            q[:, :n_real], k[:, :n_real], v[:, :n_real], causal=False
        )
        np.testing.assert_allclose(got[:, :n_real], want, rtol=2e-4, atol=2e-5)

    def test_fused_fold_grads_match_reference(self):
        import jax
        import jax.numpy as jnp

        from flink_ml_tpu.parallel.flash import fused_fold, reference_fold

        rng = np.random.default_rng(6)
        B, H, Tq, Tk, D = 1, 2, 256, 256, 8
        q = jnp.asarray(rng.standard_normal((B, H, Tq, D)).astype(np.float32))
        kb = jnp.asarray(rng.standard_normal((B, H, Tk, D)).astype(np.float32))
        vb = jnp.asarray(rng.standard_normal((B, H, Tk, D)).astype(np.float32))
        m0 = jnp.full((B, H, Tq), -jnp.inf)
        l0 = jnp.zeros((B, H, Tq))
        a0 = jnp.zeros((B, H, Tq, D))
        scale = 1.0 / np.sqrt(D)

        def loss_fused(q, kb, vb):
            m, l, a = fused_fold(
                q, kb, vb, m0, l0, a0, jnp.int32(0), jnp.int32(0), True,
                False, jnp.int32(0), scale, True,
            )
            return jnp.sum(a / jnp.maximum(l, 1e-30)[..., None] * 0.1)

        def loss_ref(q, kb, vb):
            m, l, a = reference_fold(
                q, kb, vb, m0, l0, a0, 0, 0, True, None, scale
            )
            return jnp.sum(a / jnp.maximum(l, 1e-30)[..., None] * 0.1)

        gf = jax.grad(loss_fused, argnums=(0, 1, 2))(q, kb, vb)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, kb, vb)
        for a_, b_ in zip(gf, gr):
            np.testing.assert_allclose(
                np.asarray(a_), np.asarray(b_), rtol=1e-5, atol=1e-5
            )

    @pytest.mark.parametrize(
        "case", ["first-fold", "mid-fold", "masked", "fully-masked", "plain"]
    )
    def test_hand_derived_fold_bwd_matches_ad(self, case):
        import jax
        import jax.numpy as jnp

        from flink_ml_tpu.parallel.flash import (
            _fold_bwd_pallas,
            reference_fold,
            reference_fold_bwd,
        )

        rng = np.random.default_rng(7)
        B, H, Tq, Tk, D = 1, 2, 256, 256, 8
        scale = 1.0 / np.sqrt(D)
        r = lambda *sh: jnp.asarray(rng.normal(size=sh).astype(np.float32))
        causal, nv, qp, kp = {
            "first-fold": (True, None, 0, 0),
            "mid-fold": (True, None, 512, 256),
            "masked": (False, 300, 0, 256),  # keys 256-299 valid, rest masked
            "fully-masked": (False, 10, 0, 128),  # rows with nothing attendable
            "plain": (False, None, 0, 0),
        }[case]
        q, kb, vb = r(B, H, Tq, D), r(B, H, Tk, D), r(B, H, Tk, D)
        if case in ("first-fold", "fully-masked"):
            m = jnp.full((B, H, Tq), -jnp.inf)
            l = jnp.zeros((B, H, Tq))
            acc = jnp.zeros((B, H, Tq, D))
        else:
            m, l, acc = r(B, H, Tq) * 0.5, jnp.abs(r(B, H, Tq)) + 0.5, r(B, H, Tq, D)
        dm, dl, dacc = r(B, H, Tq), r(B, H, Tq), r(B, H, Tq, D)

        _, vjp = jax.vjp(
            lambda q_, k_, v_, m_, l_, a_: reference_fold(
                q_, k_, v_, m_, l_, a_, qp, kp, causal, nv, scale
            ),
            q, kb, vb, m, l, acc,
        )
        want = vjp((dm, dl, dacc))
        got_ref = reference_fold_bwd(
            q, kb, vb, m, l, acc, qp, kp, causal, nv, scale, dm, dl, dacc
        )
        got_pl = _fold_bwd_pallas(
            q, kb, vb, m, l, acc, qp, kp, causal, nv, scale, dm, dl, dacc,
            interpret=True,
        )
        for w, gr, gp, name in zip(want, got_ref, got_pl, ["dq", "dk", "dv", "dm", "dl", "dacc"]):
            np.testing.assert_allclose(
                np.asarray(gr), np.asarray(w), rtol=2e-5, atol=2e-5,
                err_msg=f"{case}/{name} reference_fold_bwd",
            )
            np.testing.assert_allclose(
                np.asarray(gp), np.asarray(w), rtol=2e-5, atol=2e-5,
                err_msg=f"{case}/{name} pallas bwd",
            )


def _digest(arrays):
    import hashlib

    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a, np.float32)).tobytes())
    return h.hexdigest()[:16]


#: The outputs that sum over the keys of one row or column entry by entry:
#: ``acc`` (tens at 4,096 keys), ``dq`` and ``dk`` (hundreds to thousands). One
#: float32 rounding of a row's largest entry is past ``atol`` there (3.8e-6 at
#: 35, against 2e-6), so a sum taken chunk by chunk lands an entry near zero
#: further than ``atol`` from the same sum taken whole, both correct. Measured
#: on these cases against plain ``assert_allclose`` (PERF.md, PR 31): the
#: whole-block kernels of PR 30 miss it on ``dk`` by up to 7.7e-5, the walk on
#: ``dk`` by the same, on ``dq`` by 1.9e-5 and on ``acc`` by 1.5e-5; every other
#: output of both is inside it, and is held to it below.
AT_ROW_SCALE = ("acc", "dq", "dk")


def _assert_close(name, got, want, rtol, atol, err_msg=""):
    """Plain ``assert_allclose`` at the tolerances the whole-block kernels were
    held to, but for ``AT_ROW_SCALE``, whose absolute term counts in units of
    the row's largest entry (never under 1; at the 256 keys of the older cases
    rows are of size 1 and the two are the same test)."""
    if name not in AT_ROW_SCALE:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=rtol, atol=atol, err_msg=err_msg)
        return
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    finite = np.isfinite(want)
    np.testing.assert_array_equal(got[~finite], want[~finite], err_msg=err_msg)
    size = np.abs(np.where(finite, want, 0.0))
    row = np.maximum(1.0, size.max(axis=-1, keepdims=True))
    with np.errstate(invalid="ignore"):  # inf - inf where both are -inf: compared above
        excess = np.where(finite, np.abs(got - want) - (atol * row + rtol * size), 0.0)
    assert excess.max() <= 0, f"{err_msg}: {int((excess > 0).sum())} entries past rtol={rtol}, atol={atol} x row size, worst by {excess.max():.3g}"


class TestCausalChunks:
    """Under ``causal`` the three kernels walk the resident block in key chunks
    and skip those the mask hides whole (parallel/flash.py). What they compute
    is still ``reference_fold`` / ``reference_fold_bwd`` over the whole block,
    at the tolerances the whole-block kernels were held to."""

    TQ, TK, D = 1024, 4096, 8  # two forward and dq tiles of 512 on four key chunks of 1,024

    #: name -> (q_pos0, k_pos0, n_valid, H, H_kv, incoming m)
    CASES = {
        "all-full": (4096 + 300, 300, None, 2, 2, "finite"),  # q_pos0 >= k_pos0 + Tk: no mask at all
        "all-hidden": (0, 1024, None, 2, 2, "finite"),  # k_pos0 > q_pos0 + Tq - 1: nothing visited
        "all-hidden-first-fold": (0, 1024, None, 2, 2, "-inf"),
        "diagonal": (1024, 0, None, 2, 2, "-inf"),  # a full, a crossed and two hidden chunks a tile
        "diagonal-off-the-grid": (1637, 100, None, 2, 2, "finite"),  # 1,537 apart: no tile's multiple
        "grouped-queries": (1024, 0, None, 4, 1, "-inf"),
        "n-valid-inside-a-chunk": (2048, 0, 1300, 2, 2, "-inf"),  # chunk 1 is full under causal, cut by n_valid
        "tie-in-two-chunks": (3072, 0, None, 1, 1, "-inf"),
        # a value head of its own size (HEADS): the names sort last, so the cases above keep their seeds
        "value-head-192-128": (1024, 0, None, 2, 2, "-inf"),  # latent attention's head
        "value-head-narrower": (1637, 100, None, 2, 2, "finite"),
        "value-head-narrower-grouped": (1024, 0, None, 4, 1, "-inf"),
        "value-head-wider-n-valid": (2048, 0, 1300, 2, 2, "finite"),
    }
    #: case -> (channels of q and k, channels of v and acc) where they differ from ``D``, ``D``
    HEADS = {"value-head-192-128": (192, 128), "value-head-narrower": (24, 16),
             "value-head-narrower-grouped": (24, 16), "value-head-wider-n-valid": (8, 40)}

    def _scale(self, case):
        return 1.0 / np.sqrt(self.HEADS.get(case, (self.D,))[0])

    def _inputs(self, case):
        import jax.numpy as jnp

        qp, kp, nv, H, Hkv, m_kind = self.CASES[case]
        rng = np.random.default_rng(sorted(self.CASES).index(case))
        r = lambda *sh: rng.normal(size=sh).astype(np.float32)
        d, dv = self.HEADS.get(case, (self.D, self.D))
        q, kb, vb = r(1, H, self.TQ, d), r(1, Hkv, self.TK, d), r(1, Hkv, self.TK, dv)
        if case == "tie-in-two-chunks":
            # keys 100 (chunk 0) and 1500 (chunk 1) are one vector, and the max of rows 5 and 700
            kb[0, 0, 100] = kb[0, 0, 1500] = 3.0
            q[0, 0, 5] = q[0, 0, 700] = 3.0
        if m_kind == "-inf":
            m, l, acc = np.full((1, H, self.TQ), -np.inf, np.float32), np.zeros((1, H, self.TQ), np.float32), \
                np.zeros((1, H, self.TQ, dv), np.float32)
        else:
            m, l, acc = r(1, H, self.TQ) * 0.5, np.abs(r(1, H, self.TQ)) + 0.5, r(1, H, self.TQ, dv)
        cot = r(1, H, self.TQ), r(1, H, self.TQ), r(1, H, self.TQ, dv)
        as_j = lambda *xs: tuple(jnp.asarray(x) for x in xs)
        return as_j(q, kb, vb), as_j(m, l, acc), as_j(*cot), (qp, kp, nv, H // Hkv)

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_forward_matches_reference_fold(self, case):
        import jax.numpy as jnp

        from flink_ml_tpu.parallel.flash import _fold_pallas, reference_fold

        (q, kb, vb), state, _, (qp, kp, nv, group) = self._inputs(case)
        scale = self._scale(case)
        got = _fold_pallas(q, kb, vb, *state, qp, kp, True, nv, scale, interpret=True)
        want = reference_fold(
            q, jnp.repeat(kb, group, axis=1), jnp.repeat(vb, group, axis=1), *state, qp, kp, True, nv, scale
        )
        if case.startswith("all-hidden"):
            # nothing attendable: m, l * 1 (or 0 from -inf), acc * 1 pass through
            np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(state[0]))
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))  # a max has no order
        for g, w, name in zip(got, want, ["m", "l", "acc"]):
            _assert_close(name, g, w, 2e-5, 2e-6, f"{case}/{name}")

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_backward_matches_reference_and_ad(self, case):
        import jax
        import jax.numpy as jnp

        from flink_ml_tpu.parallel.flash import _fold_bwd_pallas, reference_fold, reference_fold_bwd

        (q, kb, vb), state, cot, (qp, kp, nv, group) = self._inputs(case)
        scale = self._scale(case)
        k_rep, v_rep = jnp.repeat(kb, group, axis=1), jnp.repeat(vb, group, axis=1)
        _, vjp = jax.vjp(
            lambda q_, k_, v_, m_, l_, a_: reference_fold(q_, k_, v_, m_, l_, a_, qp, kp, True, nv, scale),
            q, k_rep, v_rep, *state,
        )
        by_ad = vjp(cot)
        by_hand = reference_fold_bwd(q, k_rep, v_rep, *state, qp, kp, True, nv, scale, *cot)
        got = _fold_bwd_pallas(q, kb, vb, *state, qp, kp, True, nv, scale, *cot, interpret=True)
        if case == "tie-in-two-chunks":
            # the tied rows' max cotangent went half to each of the two chunks
            s = jnp.einsum("qd,kd->qk", q[0, 0], kb[0, 0]) * scale
            assert float(s[5, 100]) == float(s[5, 1500]) == float(jnp.max(s[5]))

        def per_kv_head(x):  # the group's query heads summed, as the dkv kernel hands dk and dv back
            return x.reshape(1, x.shape[1] // group, group, *x.shape[2:]).sum(axis=2)

        for i, name in enumerate(["dq", "dk", "dv", "dm", "dl", "dacc"]):
            for want, whose in ((by_ad[i], "jax AD"), (by_hand[i], "reference_fold_bwd")):
                want = per_kv_head(want) if name in ("dk", "dv") else want
                assert got[i].shape == want.shape, f"{case}/{name}"  # dk at q's channels, dv and dacc at v's
                _assert_close(name, got[i], want, 2e-5, 2e-5, f"{case}/{name} against {whose}")

    def test_hidden_chunks_are_never_read(self):
        """Rows 1,024..2,047 on keys 0..4,095: keys from 2,048 on are hidden from
        every tile. NaNs there reach no output (the reference would spread them
        through ``0 * NaN``), and their dk, dv are exact zeros."""
        import jax.numpy as jnp

        from flink_ml_tpu.parallel.flash import _fold_bwd_pallas, _fold_pallas, reference_fold, reference_fold_bwd

        (q, kb, vb), state, cot, (qp, kp, nv, _) = self._inputs("diagonal")
        scale = 1.0 / np.sqrt(self.D)
        poisoned = [x.at[:, :, 2048:].set(jnp.nan) for x in (kb, vb)]
        zeroed = [x.at[:, :, 2048:].set(0.0) for x in (kb, vb)]
        got = _fold_pallas(q, *poisoned, *state, qp, kp, True, nv, scale, interpret=True)
        want = reference_fold(q, *zeroed, *state, qp, kp, True, nv, scale)
        for g, w, name in zip(got, want, ["m", "l", "acc"]):
            _assert_close(name, g, w, 2e-5, 2e-6)
        got = _fold_bwd_pallas(q, *poisoned, *state, qp, kp, True, nv, scale, *cot, interpret=True)
        want = reference_fold_bwd(q, *zeroed, *state, qp, kp, True, nv, scale, *cot)
        for g, w, name in zip(got, want, ["dq", "dk", "dv", "dm", "dl", "dacc"]):
            _assert_close(name, g, w, 2e-5, 2e-5)
        assert not np.asarray(got[1])[:, :, 2048:].any() and not np.asarray(got[2])[:, :, 2048:].any()

    #: the whole-block kernels' outputs at commit 0269dca (PR 30), interpreted on
    #: the CPU, for ``_inputs_whole_block``: (forward, backward) digests
    PARENT = {
        "plain": ("236200f784f2b695", "a0a4eaa319c65b43"),
        "n-valid": ("b2de3ef904ce5165", "fed9439d3aa00e02"),
        "grouped-queries": ("d2b27648e04927aa", "6d3c1c0b1e3946a8"),
    }

    @pytest.mark.parametrize("case", sorted(PARENT))
    def test_without_causal_the_kernels_are_the_parents(self, case):
        """``causal=False`` walks nothing: the whole resident block in one piece,
        bit for bit what the kernels gave before the causal ones were split off
        (jax and jaxlib are pinned; on other bits the tolerance still holds)."""
        import jax.numpy as jnp

        from flink_ml_tpu.parallel.flash import _fold_bwd_pallas, _fold_pallas, reference_fold, reference_fold_bwd

        nv, qp, kp, hkv = {"plain": (None, 0, 0, 2), "n-valid": (700, 0, 256, 2), "grouped-queries": (None, 3, 5, 1)}[case]
        rng = np.random.default_rng(11)
        B, H, Tq, Tk, D = 1, 2, 512, 768, 8
        r = lambda *sh: jnp.asarray(rng.normal(size=sh).astype(np.float32))
        q, kb, vb = r(B, H, Tq, D), r(B, hkv, Tk, D), r(B, hkv, Tk, D)
        m, l, acc = r(B, H, Tq) * 0.5, jnp.abs(r(B, H, Tq)) + 0.5, r(B, H, Tq, D)
        dm, dl, dacc = r(B, H, Tq), r(B, H, Tq), r(B, H, Tq, D)
        scale = 1.0 / np.sqrt(D)
        fwd = _fold_pallas(q, kb, vb, m, l, acc, qp, kp, False, nv, scale, interpret=True)
        bwd = _fold_bwd_pallas(q, kb, vb, m, l, acc, qp, kp, False, nv, scale, dm, dl, dacc, interpret=True)
        k_rep, v_rep = jnp.repeat(kb, H // hkv, axis=1), jnp.repeat(vb, H // hkv, axis=1)
        for g, w in zip(fwd, reference_fold(q, k_rep, v_rep, m, l, acc, qp, kp, False, nv, scale)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5, atol=2e-6)
        want = reference_fold_bwd(q, k_rep, v_rep, m, l, acc, qp, kp, False, nv, scale, dm, dl, dacc)
        for i, (g, w) in enumerate(zip(bwd, want)):
            if i in (1, 2):
                w = w.reshape(B, hkv, H // hkv, Tk, D).sum(axis=2)
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5, atol=2e-5)
        assert (_digest(fwd), _digest(bwd)) == self.PARENT[case]


#: (Tq, Tk, q_pos0 - k_pos0): the two LM cells' folds (OLMoE: 16 heads at 4,096;
#: ZAYA: 8 query heads on 2 key/value heads at 8,192; the counts are per query
#: head), then offsets on and off the tiles, and a block wholly ahead
@pytest.mark.parametrize(
    "Tq,Tk,q_off", [(4096, 4096, 0), (8192, 8192, 0), (2048, 4096, 1024), (2048, 4096, 1537), (1024, 2048, -700),
                    (2048, 2048, -2048)],
)
def test_fold_chunk_counts_is_a_count_of_the_mask(Tq, Tk, q_off):
    """``fold_chunk_counts`` against the mask itself: a (query tile, key chunk)
    pair is visited iff the mask keeps an entry of it, at each kernel's tiles."""
    from flink_ml_tpu.parallel.flash import _fold_tiles, fold_chunk_counts

    keep = (q_off + np.arange(Tq))[:, None] >= np.arange(Tk)[None, :]
    tq_fwd, tq_dq, tq_dkv, tk_dkv, kc = _fold_tiles(Tq, Tk, True)
    visited = total = 0
    for rows, keys in ((tq_fwd, kc), (tq_dq, kc), (tq_dkv, tk_dkv)):
        pairs = keep.reshape(Tq // rows, rows, Tk // keys, keys).any(axis=(1, 3))
        visited, total = visited + int(pairs.sum()), total + pairs.size
    assert fold_chunk_counts(Tq, Tk, q_off, True) == (visited, total)
    assert fold_chunk_counts(Tq, Tk, q_off, False)[0] == fold_chunk_counts(Tq, Tk, q_off, False)[1]
    if q_off == 0:  # the LM's fold: a little over half, more at the shorter length
        assert {4096: 0.625, 8192: 0.5625}[Tq] == visited / total
    if q_off == -Tq:
        assert visited == 0


def test_a_block_of_one_chunk_is_taken_whole():
    """Up to 1,024 keys there is one chunk and nothing to walk: the forward and
    the dq kernel take the block in one piece at the tiles they had before (and
    park no scores: the ring's tests run there, interpreted on eight devices),
    so they count every pair; the dkv kernel still skips a hidden one."""
    from flink_ml_tpu.parallel.flash import _fold_tiles, fold_chunk_counts

    assert _fold_tiles(1024, 1024, True) == (256, 64, 1024, 1024, 1024)
    assert _fold_tiles(1024, 1024, True)[:2] == _fold_tiles(1024, 1024, False)[:2]
    assert fold_chunk_counts(1024, 1024, 0, True) == (4 + 16 + 1, 4 + 16 + 1)
    assert fold_chunk_counts(1024, 1024, -1024, True) == (4 + 16 + 0, 4 + 16 + 1)
    assert _fold_tiles(1024, 2048, True)[:2] == (512, 512)  # two chunks: walked


#: window -> sha256 of ``str(make_jaxpr(value_and_grad(fold)))`` with addresses blanked, at 3738dc6 (PR 43), before
#: the fold took a value head of its own size: 4 query heads on 2 key/value heads x 2,048 x 16, two key chunks
FOLD_JAXPRS = {None: "672b7326f961a89fb465717d93b2d8e62b7921680905d388b94ecd3ae7b1e261",
               300: "0a92f0254a896f585ff298a1d753122f08d63f19b7332091ef5b09fc23cc7639"}


@pytest.mark.parametrize("window", sorted(FOLD_JAXPRS, key=str))
def test_with_equal_head_sizes_the_fold_traces_to_what_it_was(window):
    """A call whose values are as wide as its keys lowers to the kernels it lowered to before ``vb`` could be
    narrower: the three kernels' block specs, grids and bodies, character for character."""
    import hashlib
    import re

    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.parallel.flash import fused_fold

    def fold(q, k, v):
        b, h, t, d = q.shape
        m0, l0 = jnp.full((b, h, t), -jnp.inf, jnp.float32), jnp.zeros((b, h, t), jnp.float32)
        acc0 = jnp.zeros((b, h, t, v.shape[-1]), jnp.float32)
        _, l, acc = fused_fold(q, k, v, m0, l0, acc0, jnp.int32(0), jnp.int32(0), True, False, jnp.int32(0),
                               d ** -0.5, True, window)
        return jnp.sum(acc / l[..., None])

    shape = lambda heads: jax.ShapeDtypeStruct((1, heads, 2048, 16), jnp.float32)  # noqa: E731
    text = str(jax.make_jaxpr(jax.value_and_grad(fold, argnums=(0, 1, 2)))(shape(4), shape(2), shape(2)))
    assert hashlib.sha256(re.sub(r"0x[0-9a-f]+", "0x", text).encode()).hexdigest() == FOLD_JAXPRS[window]
