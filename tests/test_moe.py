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


# -- the held range in windows ---------------------------------------------------

from flink_ml_tpu.parallel import moe  # noqa: E402

#: ``(experts, held, k, compute type, the router's lean towards the held
#: experts)``: an eighth held under top-2 and under top-8 (Laguna's 32 of 256
#: in small) at one window and, the router leaning, at three; a sixteenth;
#: three eighths, whose second window is part padding
HELD_RANGES = [(16, 2, 2, "float32", 0.0), (16, 2, 2, "bfloat16", 0.0), (16, 2, 2, "float32", 6.0),
               (16, 2, 2, "bfloat16", 6.0), (64, 8, 8, "float32", 0.0), (64, 8, 8, "bfloat16", 0.0),
               (64, 8, 8, "float32", 3.0), (32, 2, 4, "float32", 4.0), (8, 3, 2, "float32", 0.0),
               (8, 3, 2, "float32", 8.0), (8, 3, 2, "bfloat16", 8.0)]
FIRST_HELD = 3


def _held_setup(E, H, T=2048, d=16, h=24, seed=0):
    x, router, w_gate, w_up, w_down = _setup(T=T, d=d, h=h, E=E, seed=seed)
    return x, router, w_gate[:H], w_up[:H], w_down[:H]


def _layer_and_cotangents(args, k, dtype, lean):
    x, router, *experts = args
    held = slice(FIRST_HELD, FIRST_HELD + experts[0].shape[0])
    probe = jnp.asarray(np.random.default_rng(1).standard_normal(x.shape).astype(np.float32))

    def scalar(x, router, *experts):
        def logits(x):
            return jnp.dot(x, router, precision="highest").at[:, held].add(lean)

        y, stats = moe_dropless(x, logits, *experts, k, jnp.dtype(dtype), FIRST_HELD)
        return jnp.sum(y * probe), (y, stats)

    (_, (y, stats)), grads = jax.value_and_grad(scalar, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
    return y, stats, grads


@pytest.mark.parametrize("E,H,k,dtype,lean", HELD_RANGES)
def test_the_windows_are_the_whole_range(E, H, k, dtype, lean, monkeypatch):
    """The layer a window of sorted rows at a time against the layer through
    all of them at once, same inputs: the result, the rows counted and every
    cotangent (tokens, router, the three expert leaves)."""
    args = _held_setup(E, H, seed=E + k)
    rows = args[0].shape[0] * k
    window = moe._window_rows(rows, H, E)
    y, stats, got = _layer_and_cotangents(args, k, dtype, lean)
    monkeypatch.setattr(moe, "_window_rows", lambda *_: 0)
    want_y, want_stats, want = _layer_and_cotangents(args, k, dtype, lean)
    assert "carried" not in want_stats
    held = int(np.asarray(stats["rows"])[FIRST_HELD: FIRST_HELD + H].sum())
    assert int(stats["carried"]) == min(-(-held // window) * window, rows)
    assert (held > window) == bool(lean), "the lean is there to fill more than one window"
    np.testing.assert_array_equal(np.asarray(stats["rows"]), np.asarray(want_stats["rows"]))
    assert int(stats["rows"].sum()) == rows, "a row was dropped"
    # float32: another order of the same sums. bfloat16: the same rounded rows through the same forward
    # matmuls; backward, the weight is applied after the matmul through W_down^T where the whole range
    # rounds the weighted cotangent before it, and a token's cotangent rows are summed in f32
    tol = 2e-5 if dtype == "float32" else 2e-2
    scale = float(jnp.max(jnp.abs(want_y)))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), rtol=0, atol=2e-6 * scale)
    for name, g, w in zip(("x", "router", "w_gate", "w_up", "w_down"), got, want):
        assert float(jnp.max(jnp.abs(w))) > 0, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=0, atol=tol * float(jnp.max(jnp.abs(w))),
                                   err_msg=name)


def _forced_logits(T, E, held):
    """Logits that put exactly ``held`` of the ``T x 2`` rows on the held
    experts ``FIRST_HELD, FIRST_HELD + 1``: tokens choose both, then one of
    them beside expert 0, then experts 0 and 1."""
    both, one = divmod(held, 2)
    logits = np.full((T, E), -10.0, np.float32)
    logits[:both, FIRST_HELD], logits[:both, FIRST_HELD + 1] = 10.0, 9.0
    logits[both: both + one, FIRST_HELD + 1], logits[both: both + one, 0] = 10.0, 9.0
    logits[both + one:, 0], logits[both + one:, 1] = 10.0, 9.0
    return jnp.asarray(logits)


@pytest.mark.parametrize("held,windows", [(0, 0), (1, 1), (1024, 1), (1025, 2), (2048, 2), (2049, 3), (3073, 4),
                                          (4096, 4)])
def test_the_windows_follow_the_held_rows(held, windows, monkeypatch):
    """A router forced to put exactly so many rows on held experts (a window,
    a window and a row, two, two and a row, ... every row) takes so many
    windows of 1,024 of its 4,096 sorted rows; nothing is dropped on any
    count, and the result is the whole range's."""
    E, H, k, T = 16, 2, 2, 2048
    x, _, w_gate, w_up, w_down = _held_setup(E, H, T=T)
    assert moe._window_rows(T * k, H, E) == 1024
    logits = _forced_logits(T, E, held)
    y, stats = moe_dropless(x, lambda _: logits, w_gate, w_up, w_down, k, first_held=FIRST_HELD)
    rows = np.asarray(stats["rows"])
    assert rows[FIRST_HELD: FIRST_HELD + H].sum() == held and rows.sum() == T * k
    assert int(stats["carried"]) == 1024 * windows
    monkeypatch.setattr(moe, "_window_rows", lambda *_: 0)
    want, _ = moe_dropless(x, lambda _: logits, w_gate, w_up, w_down, k, first_held=FIRST_HELD)
    assert (float(jnp.max(jnp.abs(want))) > 0) == (held > 0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=0, atol=2e-6 * float(jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("E,H,window", [(8, 8, 0), (64, 64, 0), (16, 8, 0), (8, 5, 0), (8, 3, 3072),
                                        (16, 2, 1024), (256, 32, 1024)])
def test_the_windows_exist_only_under_half_of_the_experts(E, H, window):
    """With every expert held, or a half or more of them, the layer traces no
    loop (and is then the program it was); under a half it traces one a
    direction."""
    k, T = 2, 2048
    assert moe._window_rows(T * k, H, E) == window
    args = _held_setup(E, H)
    first = min(FIRST_HELD, E - H)
    text = str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(moe_dropless(*a, k, first_held=first)[0])))(*args))
    assert ("while[" in text) == bool(window) and "cond[" not in text
    _, stats = moe_dropless(*args, k, first_held=first)
    assert ("carried" in stats) == bool(window)


def test_the_cells_windows():
    """Laguna's 32 of 256 under top-8 at 8,192 tokens: 16,384 of 65,536 rows
    at a time; ZAYA's 8 of 16 and OLMoE's 64 of 64: all of them at once."""
    assert moe._window_rows(8192 * 8, 32, 256) == 16384
    assert moe._window_rows(16384 * 1, 8, 16) == 0 == moe._window_rows(16384 * 8, 64, 64)
    assert moe._window_rows(10000, 1, 16) == 1536  # whole row tiles: 1,250 rounded up
    assert moe._window_rows(1024, 2, 16) == 512


# -- the expert function the layer is told: relu² on two matrices ----------------


def _dense_relu2(x, router, w_up, w_down, k):
    """Masked-dense top-k experts ``down(relu(up(x))^2)``, routed as ``_dense``."""
    with jax.default_matmul_precision("highest"):
        p = jax.nn.softmax(x @ router, axis=-1)
        _, top_e = jax.lax.top_k(p, k)
        weight = p * jnp.sum(jax.nn.one_hot(top_e, router.shape[1], dtype=p.dtype), axis=1)
        y = jnp.zeros_like(x)
        for e in range(router.shape[1]):
            y = y + weight[:, e: e + 1] * (jnp.square(jax.nn.relu(x @ w_up[e])) @ w_down[e])
        return y


@pytest.mark.parametrize("E,k", ROUTINGS)
def test_relu2_experts_match_masked_dense_forward_and_backward(E, k):
    """Told no gate matrix the layer computes ``down(relu(up(x))^2)``: the
    result and every cotangent against the masked-dense form, and
    ``dense_swiglu`` told the same is one such expert."""
    x, router, _, w_up, w_down = _setup(E=E, seed=20 + E + k)
    probe = jnp.asarray(np.random.default_rng(1).standard_normal(x.shape).astype(np.float32))
    got = moe_dropless(x, router, None, w_up, w_down, k)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(_dense_relu2(x, router, w_up, w_down, k)),
                               rtol=2e-5, atol=2e-6)
    grads = jax.grad(lambda x, r, u, dn: jnp.sum(moe_dropless(x, r, None, u, dn, k)[0] * probe), argnums=(0, 1, 2, 3))
    want = jax.grad(lambda *a: jnp.sum(_dense_relu2(*a, k) * probe), argnums=(0, 1, 2, 3))(x, router, w_up, w_down)
    for name, g, w in zip(("x", "router", "w_up", "w_down"), grads(x, router, w_up, w_down), want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4, atol=2e-6 * float(jnp.max(jnp.abs(w))),
                                   err_msg=name)
    with jax.default_matmul_precision("highest"):
        one = jnp.square(jax.nn.relu(x @ w_up[0])) @ w_down[0]
    np.testing.assert_allclose(np.asarray(moe.dense_swiglu(x, None, w_up[0], w_down[0])), np.asarray(one),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("dtype,lean", [("float32", 0.0), ("float32", 6.0), ("bfloat16", 6.0)])
def test_the_relu2_windows_are_the_whole_range(dtype, lean, monkeypatch):
    """Experts without a gate matrix through the windows (one hidden gradient
    parked, two ``dW`` matmuls), at one window and, the router leaning, at
    three: the result and every cotangent against the whole range at once."""
    E, H, k = 16, 2, 2
    x, router, _, w_up, w_down = _held_setup(E, H, seed=5)
    probe = jnp.asarray(np.random.default_rng(1).standard_normal(x.shape).astype(np.float32))
    held = slice(FIRST_HELD, FIRST_HELD + H)

    def scalar(x, router, w_up, w_down):
        logits = lambda x: jnp.dot(x, router, precision="highest").at[:, held].add(lean)  # noqa: E731
        y, stats = moe_dropless(x, logits, None, w_up, w_down, k, jnp.dtype(dtype), FIRST_HELD)
        return jnp.sum(y * probe), stats

    run = jax.value_and_grad(scalar, argnums=(0, 1, 2, 3), has_aux=True)
    (value, stats), got = run(x, router, w_up, w_down)
    assert int(stats["carried"]) == (3 if lean else 1) * moe._window_rows(x.shape[0] * k, H, E)
    monkeypatch.setattr(moe, "_window_rows", lambda *_: 0)
    (want_value, _), want = run(x, router, w_up, w_down)
    tol = 2e-5 if dtype == "float32" else 2e-2
    assert abs(float(value - want_value)) <= tol * abs(float(want_value)) + 1e-4
    for name, g, w in zip(("x", "router", "w_up", "w_down"), got, want):
        assert float(jnp.max(jnp.abs(w))) > 0, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=0, atol=tol * float(jnp.max(jnp.abs(w))),
                                   err_msg=name)
