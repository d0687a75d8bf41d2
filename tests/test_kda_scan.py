"""The chunked gated delta rule (``parallel/kda.py::kda_scan``: a Pallas kernel
pair under a ``custom_vjp``, interpreted here on the CPU) against the plain
recurrence it has to agree with (``reference_delta``: one position at a time),
forward and every gradient, at toy sizes: a sequence of one chunk (no state is
ever carried, ``dS`` stays zero), of several (the forward walk carries the
state first to last, the backward walk ``dS`` last to first, across every
edge), two sequences a batch (nothing couples them), chunks of one sub-chunk
and of four, decays as strong as the initialiser's strongest (``A_log = log
16`` at ``dt = 0.1``: 1.6 a position, ``exp(-102)`` over a 64-position chunk,
past float32's range if it were ever inverted) over 256 positions, and a
correction strength near 2, where ``I - beta k k^T`` has an eigenvalue near -1.

Both forms of the rule (a log-decay a key channel, ``g [B, T, H, D_k]``: the
sub-chunked pair matrices on token-major arrays; ONE log-decay a head, ``g [B,
T, H]``: a ``[chunk, chunk]`` decay factor on head-major arrays, several heads
a cell) at heads that are square and not, up to Olmo-Hybrid-7B's 96 key and 192
value channels: against the recurrence forward and in all five gradients,
whatever the chunk, and against each other under a broadcast decay.

Tolerances. float32: the two compute one sum in different orders (a chunk's
triangular solve and pair matrices against a running state), read here at 6e-7
of the largest entry forward and 2e-6 backward; the limits are 2e-5 and 1e-4.
bfloat16 matmul inputs (the decays, their sums, the solve and the carried state
stay float32): 3e-2 of the largest entry.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu.parallel import kda
from flink_ml_tpu.parallel.kda import kda_kernel_chunks, kda_scan, reference_delta

NAMES = ("q", "k", "v", "g", "beta")
#: ``(sequences, T, chunk, heads, channels a head, decay rate, beta near 2)``
SHAPES = {"one_chunk": (1, 32, 32, 2, 16, 1.0, False), "four_chunks": (1, 128, 32, 2, 16, 4.0, False),
          "two_sequences": (2, 128, 64, 2, 16, 4.0, False), "one_sub_chunk": (1, 64, 16, 2, 8, 4.0, False),
          "strong_decay_256": (1, 256, 64, 2, 16, 16.0, False), "beta_near_2": (1, 128, 64, 2, 16, 1.0, True),
          "strong_decay_beta_near_2": (2, 256, 64, 1, 32, 16.0, True),
          "eight_position_chunks": (1, 64, 8, 3, 8, 2.0, False)}


def _inputs(shape, seed=0):
    """Unit keys, queries at ``D^-1/2`` (as the layer hands them), log-decays
    ``-rate x dt`` with ``dt`` in 0.09 .. 0.1 a channel, ``beta = 2
    sigmoid(.)`` around 1 or, ``near 2``, around 1.96."""
    batch, t, _, heads, d, rate, high = SHAPES[shape]
    ks = jax.random.split(jax.random.key(seed), 5)
    q, k, v = (jax.random.normal(key, (batch, t, heads, d)) for key in ks[:3])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(d)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -rate * jax.random.uniform(ks[3], (batch, t, heads, d), minval=0.09, maxval=0.1)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (batch, t, heads)) + (4.0 if high else 0.0))
    return q, k, v, g, beta


def _worst(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_chunks_are_the_recurrence_forward(shape):
    args = _inputs(shape)
    want = reference_delta(*args)
    got = kda_scan(*args, SHAPES[shape][2])
    assert got.shape == want.shape == args[0].shape and got.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(got)))
    assert _worst(got, want) < 2e-5


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_chunks_are_the_recurrence_backward(shape):
    """Every argument's gradient - ``q``, ``k``, ``v``, the log-decays, the
    correction's strength - of a random projection of ``o``."""
    args = _inputs(shape, seed=1)
    probe = jax.random.normal(jax.random.key(9), args[0].shape)
    want = jax.grad(lambda *a: jnp.sum(reference_delta(*a) * probe), argnums=range(5))(*args)
    got = jax.grad(lambda *a: jnp.sum(kda_scan(*a, SHAPES[shape][2]) * probe), argnums=range(5))(*args)
    for name, g, w in zip(NAMES, got, want):
        assert float(jnp.max(jnp.abs(w))) > 0, name
        assert bool(jnp.all(jnp.isfinite(g))), name
        assert _worst(g, w) < 1e-4, name


@pytest.mark.parametrize("chunk", [8, 16, 32, 64, 128])
def test_the_chunk_size_changes_nothing(chunk):
    """One sequence of 128 positions through chunks of every size that divides it."""
    args = _inputs("four_chunks", seed=2)
    assert _worst(kda_scan(*args, chunk), reference_delta(*args)) < 2e-5


def test_the_strongest_decay_would_overflow_if_inverted():
    """What the sub-chunks are for: at the test's strongest decay the running
    sum of a 64-position chunk passes the float32 exponent's range, so
    ``exp(-G)`` is infinite where the kernels' factors (half a sub-chunk's
    span, either way) stay under ``exp(13)``."""
    _, _, _, g, _ = _inputs("strong_decay_256")
    sums = np.cumsum(np.asarray(g[0, :64, 0]), axis=0)
    assert sums.min() < -88.0 and np.isinf(np.exp(-sums.astype(np.float32))).any()
    span = np.abs(sums[kda._SUB // 2] - sums[0]).max()
    assert span < 13.0


def test_a_sequences_state_is_its_own():
    """Two sequences a batch give what each gives alone: the state starts at zero in each."""
    args = _inputs("two_sequences", seed=3)
    both = kda_scan(*args, 64)
    for i in range(2):
        alone = kda_scan(*(a[i: i + 1] for a in args), 64)
        np.testing.assert_allclose(np.asarray(both[i: i + 1]), np.asarray(alone), rtol=1e-6, atol=1e-7)


def test_the_state_crosses_every_chunk_edge():
    """A change of the first position's value reaches the last chunk's output
    (weak decay), and a kernel that forgot the state at a chunk's edge is told
    apart: its output from the second chunk on is that of a sequence starting
    there."""
    q, k, v, g, beta = _inputs("four_chunks", seed=4)
    g = g * 0.01
    base = kda_scan(q, k, v, g, beta, 32)
    moved = kda_scan(q, k, v.at[0, 0].add(1.0), g, beta, 32)
    assert float(jnp.max(jnp.abs(moved[0, 96:] - base[0, 96:]))) > 1e-4
    forgotten = jnp.concatenate([kda_scan(*(a[:, lo: lo + 32] for a in (q, k, v, g, beta)), 32)
                                 for lo in range(0, 128, 32)], axis=1)
    np.testing.assert_allclose(np.asarray(forgotten[:, :32]), np.asarray(base[:, :32]), rtol=1e-6, atol=1e-7)
    assert _worst(forgotten, reference_delta(q, k, v, g, beta)) > 1e-2


def test_beta_past_one_turns_the_state():
    """One unit key and one value written again and again, no decay: along the
    key the state follows ``S_t = (1 - beta) S_(t-1) + beta k v^T``. At ``beta =
    1`` the first write is the fixed point and every read-out is ``v``; at
    ``beta = 2`` the distance from it changes sign a position - the eigenvalue
    -1 that the configuration's ``kda_allow_neg_eigval`` allows - and the
    read-outs are ``2 v, 0, 2 v, 0, ..``."""
    d = 8
    k = jnp.zeros((1, 8, 1, d)).at[..., 0].set(1.0)
    v = jnp.broadcast_to(jnp.arange(1.0, d + 1), (1, 8, 1, d))
    g = jnp.zeros((1, 8, 1, d))
    out = {b: np.asarray(kda_scan(k, k, v, g, jnp.full((1, 8, 1), b), 8)[0, :, 0]) for b in (1.0, 2.0)}
    np.testing.assert_allclose(out[1.0], np.asarray(v[0, :, 0]), atol=1e-6)
    np.testing.assert_allclose(out[2.0], np.asarray(v[0, :, 0]) * (1 + (-1.0) ** np.arange(8))[:, None], atol=1e-5)


@pytest.mark.parametrize("shape", ["two_sequences", "strong_decay_beta_near_2"])
def test_bfloat16_matmul_inputs_stay_within_their_band(shape):
    args = _inputs(shape, seed=5)
    want = reference_delta(*args)
    got = kda_scan(*args, SHAPES[shape][2], jnp.bfloat16)
    assert got.dtype == jnp.float32 and _worst(got, want) < 3e-2
    probe = jax.random.normal(jax.random.key(9), want.shape)
    gw = jax.grad(lambda *a: jnp.sum(reference_delta(*a) * probe), argnums=range(5))(*args)
    gg = jax.grad(lambda *a: jnp.sum(kda_scan(*a, SHAPES[shape][2], jnp.bfloat16) * probe), argnums=range(5))(*args)
    for name, g, w in zip(NAMES, gg, gw):
        assert _worst(g, w) < 6e-2, name


def test_no_chunk_matrix_is_saved_for_the_backward():
    """The residuals are the kernel's inputs and the state each chunk starts
    from: nothing ``[chunk, chunk]`` a chunk."""
    args = _inputs("four_chunks")
    batch, t, chunk, heads, d = SHAPES["four_chunks"][:5]
    _, residuals = jax.vjp(lambda *a: kda_scan(*a, chunk), *args)
    shapes = {tuple(x.shape) for x in jax.tree_util.tree_leaves(residuals)}
    assert (batch, t // chunk, heads, d, d) in shapes
    assert not any(len(s) > 2 and s[-2:] == (chunk, chunk) for s in shapes)  # (the triangle of ones is one matrix)


def test_the_kernels_cover_every_chunk():
    assert kda_kernel_chunks(2, 4096, 8, 64) == 2 * 8 * 64
    args = _inputs("two_sequences")
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(kda_scan(*a, 64)), argnums=range(5)))(*args)
    names = [e.params["name"] for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert names == [kda.FWD_NAME, kda.BWD_NAME]


#: a head's ``(key channels, value channels)``: square; neither a multiple of the other's tile; Olmo-Hybrid-7B's
WIDTHS = ((16, 16), (24, 48), (96, 192))
DECAYS = ("channel", "head")


def _wide_inputs(decay: str, widths, seed=0, t=128, heads=2, rate=4.0):
    """As ``_inputs`` at ``widths = (D_k, D_v)``; ``decay`` ``head``: one log-decay a head and position."""
    dk, dv = widths
    ks = jax.random.split(jax.random.key(seed), 5)
    q, k = (jax.random.normal(key, (1, t, heads, dk)) for key in ks[:2])
    v = jax.random.normal(ks[2], (1, t, heads, dv))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -rate * jax.random.uniform(ks[3], (1, t, heads) + ((dk,) if decay == "channel" else ()), minval=0.09,
                                   maxval=0.1)
    return q, k, v, g, 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (1, t, heads)))


def _recurrence(q, k, v, g, beta):
    """``reference_delta``, a head's one decay laid over its key channels: the ONE step-by-step loop."""
    return reference_delta(q, k, v, g if g.ndim == 4 else jnp.broadcast_to(g[..., None], q.shape), beta)


@pytest.mark.parametrize("widths", WIDTHS, ids=lambda w: f"{w[0]}x{w[1]}")
@pytest.mark.parametrize("decay", DECAYS)
def test_either_decay_at_either_width_is_the_recurrence(decay, widths):
    """Forward and every gradient, four chunks of 32: the state ``[D_k, D_v]`` is carried across three edges."""
    args = _wide_inputs(decay, widths)
    want = _recurrence(*args)
    got = kda_scan(*args, 32)
    assert got.shape == want.shape == args[2].shape and got.dtype == jnp.float32
    assert _worst(got, want) < 2e-5
    probe = jax.random.normal(jax.random.key(9), want.shape)
    gw = jax.grad(lambda *a: jnp.sum(_recurrence(*a) * probe), argnums=range(5))(*args)
    gg = jax.grad(lambda *a: jnp.sum(kda_scan(*a, 32) * probe), argnums=range(5))(*args)
    for name, g, w in zip(NAMES, gg, gw):
        assert g.shape == w.shape and float(jnp.max(jnp.abs(w))) > 0, name
        assert _worst(g, w) < 1e-4, name


@pytest.mark.parametrize("chunk", [8, 16, 64, 128])
@pytest.mark.parametrize("decay", DECAYS)
def test_the_chunk_size_changes_nothing_at_heads_that_are_not_square(decay, chunk):
    args = _wide_inputs(decay, (24, 48), seed=2)
    assert _worst(kda_scan(*args, chunk), _recurrence(*args)) < 2e-5


@pytest.mark.parametrize("widths", WIDTHS, ids=lambda w: f"{w[0]}x{w[1]}")
def test_the_one_decay_form_is_the_channel_form_under_a_broadcast_decay(widths):
    """The same numbers through both forms - a ``[chunk, chunk]`` factor on the
    products here, sub-chunk references inside the contraction there - forward
    and in the decay's gradient, summed over a head's channels."""
    q, k, v, g, beta = _wide_inputs("head", widths, seed=3, rate=16.0)  # the initialiser's strongest decay
    wide = jnp.broadcast_to(g[..., None], q.shape)
    one, channel = kda_scan(q, k, v, g, beta, 64), kda_scan(q, k, v, wide, beta, 64)
    assert _worst(one, channel) < 2e-5
    probe = jax.random.normal(jax.random.key(9), one.shape)
    d_one = jax.grad(lambda g: jnp.sum(kda_scan(q, k, v, g, beta, 64) * probe))(g)
    d_channel = jax.grad(lambda g: jnp.sum(kda_scan(q, k, v, g, beta, 64) * probe))(wide)
    assert _worst(d_one, jnp.sum(d_channel, axis=-1)) < 1e-4


def test_a_cells_heads_are_independent(monkeypatch):
    """Several heads a cell of the one-decay form give what one a cell gives: six heads in cells of three."""
    monkeypatch.setattr(kda, "_HEADS_A_CELL", 3)
    assert kda._heads_a_cell(6) == 3 and kda._heads_a_cell(15) == 3 and kda._heads_a_cell(7) == 1
    args = _wide_inputs("head", (24, 48), seed=4, heads=6)
    assert _worst(kda_scan(*args, 32), _recurrence(*args)) < 2e-5
    grads = jax.grad(lambda *a: jnp.sum(kda_scan(*a, 32) ** 2), argnums=range(5))(*args)
    wants = jax.grad(lambda *a: jnp.sum(_recurrence(*a) ** 2), argnums=range(5))(*args)
    for name, g, w in zip(NAMES, grads, wants):
        assert _worst(g, w) < 1e-4, name


def test_one_decay_a_head_takes_no_exponential_of_a_channel():
    """The one-decay form's kernels hold the decays as ``[1, chunk]`` rows a
    head: the chunk sums they are handed are ``[B, H, T / chunk, 1, chunk]``,
    and nothing ``[.., D_k]`` wide carries a decay."""
    args = _wide_inputs("head", (24, 48))
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(kda_scan(*a, 32)), argnums=range(5)))(*args)
    call, _ = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]  # the forward walk, the backward
    shapes = [tuple(v.aval.shape) for v in call.invars]
    assert shapes.count((1, 2, 128, 24)) == 3 and (1, 2, 128, 48) in shapes  # q, k, beta k; beta v: head-major
    assert (1, 2, 4, 1, 32) in shapes and len(shapes) == 5


@pytest.mark.parametrize("bad", ["ragged", "chunk_not_a_power_of_two", "values_of_other_heads"])
def test_shapes_the_kernels_cannot_take_are_refused(bad):
    q, k, v, g, beta = _inputs("one_chunk")
    with pytest.raises(ValueError, match="whole chunks"):
        if bad == "ragged":
            kda_scan(q, k, v, g, beta, 64)
        elif bad == "chunk_not_a_power_of_two":
            kda_scan(q[:, :24], k[:, :24], v[:, :24], g[:, :24], beta[:, :24], 24)
        else:
            kda_scan(q, k, v[:, :, :1], g, beta, 32)


def test_on_the_tpu_narrow_heads_are_refused(monkeypatch):
    """Under a decay a key channel, whose blocks cut a head out of the lanes; one decay a head takes heads of any
    width (``tests/test_lm_chip_compile.py`` compiles it at 96 and 192)."""
    monkeypatch.setattr(kda, "_interpreted", lambda: False)
    with pytest.raises(ValueError, match="128"):
        kda_scan(*_inputs("one_chunk"), 32)
    q, k, v, g, beta = _inputs("one_chunk")
    with pytest.raises(ValueError, match="chunks of a multiple of 16"):
        kda_scan(q, k, v, g[..., 0], beta, 8)
