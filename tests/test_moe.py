"""Dropless top-k MoE (parallel/moe.py): sorting the (token, expert) rows and
running the experts as grouped matmuls over the ragged groups must reproduce
the masked-dense form - every expert on every token, the unchosen masked - in
the output AND in every gradient, and must never drop a token, whatever the
router does.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu.parallel.moe import moe_dropless, route_top_k


def _dense(x, router, w_gate, w_up, w_down, k):
    """Masked-dense top-k SwiGLU experts: float32 router, softmax over all
    experts, the k largest kept as they are (not renormalised)."""
    with jax.default_matmul_precision("highest"):
        p = jax.nn.softmax(x @ router, axis=-1)
        _, top_e = jax.lax.top_k(p, k)
        weight = p * jnp.sum(jax.nn.one_hot(top_e, router.shape[1], dtype=p.dtype), axis=1)
        y = jnp.zeros_like(x)
        for e in range(router.shape[1]):
            hidden = jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e])
            y = y + weight[:, e: e + 1] * (hidden @ w_down[e])
        return y


def _setup(T=96, d=16, h=24, E=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((T, d)).astype(np.float32)
    router = rng.standard_normal((d, E)).astype(np.float32)
    w_gate = (rng.standard_normal((E, d, h)) * 0.3).astype(np.float32)
    w_up = (rng.standard_normal((E, d, h)) * 0.3).astype(np.float32)
    w_down = (rng.standard_normal((E, h, d)) * 0.3).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (x, router, w_gate, w_up, w_down))


# (experts, k): top-1, the toy model's top-2 of 8, OLMoE's top-8 of 64
ROUTINGS = [(8, 1), (8, 2), (64, 8)]


@pytest.mark.parametrize("E,k", ROUTINGS)
def test_matches_masked_dense(E, k):
    args = _setup(E=E, seed=E + k)
    got, stats = moe_dropless(*args, k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_dense(*args, k)), rtol=2e-5, atol=2e-6)
    rows = np.asarray(stats["rows"])
    assert rows.sum() == args[0].shape[0] * k, "a token was dropped"
    np.testing.assert_allclose(np.asarray(stats["f"]).sum(), k, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(stats["P"]).sum(), 1.0, rtol=1e-5)


@pytest.mark.parametrize("E,k", ROUTINGS)
def test_vjp_matches_masked_dense(E, k):
    """Every cotangent - tokens, router, the three expert matrices - against
    jax.grad of the masked-dense form."""
    args = _setup(E=E, seed=10 + E + k)
    probe = jnp.asarray(np.random.default_rng(1).standard_normal(args[0].shape).astype(np.float32))

    def scalar(f):
        return lambda *a: jnp.sum(f(*a) * probe)

    got = jax.grad(scalar(lambda *a: moe_dropless(*a, k)[0]), argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(scalar(lambda *a: _dense(*a, k)), argnums=(0, 1, 2, 3, 4))(*args)
    for name, g, w in zip(("x", "router", "w_gate", "w_up", "w_down"), got, want):
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4, atol=2e-6 * scale,
                                   err_msg=name)


@pytest.mark.parametrize("E,k", ROUTINGS)
def test_dropless_under_a_forced_router(E, k):
    """A router that sends every token to the same experts: one group holds
    all the tokens and most are empty; nothing is dropped, nothing changes."""
    x, router, w_gate, w_up, w_down = _setup(E=E, seed=3)
    T = x.shape[0]
    x = jnp.abs(x) + 0.1  # positive features, so the forced columns win everywhere
    forced = jnp.zeros_like(router).at[:, :k].set(5.0 + jnp.arange(k)[None, :])
    got, stats = moe_dropless(x, forced, w_gate, w_up, w_down, k)
    rows = np.asarray(stats["rows"])
    assert rows[:k].tolist() == [T] * k and rows[k:].sum() == 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(_dense(x, forced, w_gate, w_up, w_down, k)),
                               rtol=2e-5, atol=2e-6)


def test_router_is_float32_and_not_renormalised():
    x, router, *_ = _setup()
    p, top_p, top_e = route_top_k(x.astype(jnp.bfloat16), router.astype(jnp.bfloat16), 2)
    assert p.dtype == jnp.float32 and top_p.dtype == jnp.float32
    assert np.all(np.asarray(top_p).sum(axis=1) < 1.0)  # the two largest of eight, as they are
    np.testing.assert_array_equal(np.asarray(jnp.take_along_axis(p, top_e, axis=1)), np.asarray(top_p))


def test_bfloat16_compute_keeps_the_router_exact():
    """bfloat16 grouped matmuls move the output by bfloat16's rounding; the
    routing (float32) does not move at all."""
    args = _setup(seed=4)
    exact, stats32 = moe_dropless(*args, 2)
    rough, stats16 = moe_dropless(*args, 2, compute_dtype=jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(stats32["rows"]), np.asarray(stats16["rows"]))
    err = float(jnp.max(jnp.abs(rough - exact)) / jnp.max(jnp.abs(exact)))
    assert 1e-4 < err < 3e-2
