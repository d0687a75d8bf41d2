"""The chunked state-space scan (``parallel/ssd.py::ssd_scan``) against the
plain recurrence it has to agree with (``reference_scan``: one position at a
time), forward and every gradient, at toy sizes on the CPU: a sequence of one
chunk (no state is ever carried), of several (the chunk states' recurrence is
real), two sequences a batch (nothing couples them), heads that share a
group's ``B`` and ``C`` and heads with their own.

Tolerances. float32: the two compute one sum in different orders (a chunk's
``[chunk, chunk]`` decays against a running state), read here at 2e-6 of the
largest entry forward and 1e-5 backward; the limits are 2e-5 and 1e-4.
bfloat16 matmul inputs (the decays, the step sizes and the carried state stay
float32): 2e-2 of the largest entry.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu.parallel.ssd import reference_scan, ssd_scan

NAMES = ("x", "dt", "a", "b", "c")
#: ``(sequences, T, chunk, heads, groups)``
SHAPES = {"one_chunk": (1, 32, 32, 4, 2), "four_chunks": (1, 128, 32, 4, 2), "two_sequences": (2, 96, 32, 4, 2),
          "a_group_a_head": (1, 64, 16, 4, 4), "one_group": (2, 64, 32, 6, 1)}
P, N = 8, 16


def _inputs(shape, seed=0):
    """Step sizes and decay rates in the published ranges' order (``dt`` in
    0.001 .. 0.1 through a softplus, ``a`` in -16 .. -1), so that a state
    decays by ``exp(-0.001)`` .. ``exp(-1.6)`` a position: both a memory of
    hundreds of positions and one of a few are among the heads."""
    batch, t, _, heads, groups = SHAPES[shape]
    k = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(k[0], (batch, t, heads, P))
    dt = jax.nn.softplus(jax.random.uniform(k[1], (batch, t, heads), minval=-7.0, maxval=-2.0) + 0.5)
    a = -jnp.exp(jax.random.uniform(k[2], (heads,), minval=0.0, maxval=np.log(16.0)))
    b = jax.random.normal(k[3], (batch, t, groups, N))
    c = jax.random.normal(k[4], (batch, t, groups, N))
    return x, dt, a, b, c


def _worst(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_chunks_are_the_recurrence_forward(shape):
    args = _inputs(shape)
    want = reference_scan(*args)
    got = ssd_scan(*args, SHAPES[shape][2])
    assert got.shape == want.shape == args[0].shape and got.dtype == jnp.float32
    assert _worst(got, want) < 2e-5


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_chunks_are_the_recurrence_backward(shape):
    """Every argument's gradient - ``x``, the step sizes, the decay rates,
    ``B``, ``C`` - of a random projection of ``y``."""
    args = _inputs(shape, seed=1)
    probe = jax.random.normal(jax.random.key(9), args[0].shape)
    want = jax.grad(lambda *a: jnp.sum(reference_scan(*a) * probe), argnums=range(5))(*args)
    got = jax.grad(lambda *a: jnp.sum(ssd_scan(*a, SHAPES[shape][2]) * probe), argnums=range(5))(*args)
    for name, g, w in zip(NAMES, got, want):
        assert float(jnp.max(jnp.abs(w))) > 0, name
        assert _worst(g, w) < 1e-4, name


@pytest.mark.parametrize("chunk", [16, 32, 64, 128])
def test_the_chunk_size_changes_nothing(chunk):
    """One sequence of 128 positions through chunks of every size that divides it."""
    args = _inputs("four_chunks", seed=2)
    assert _worst(ssd_scan(*args, chunk), reference_scan(*args)) < 2e-5


def test_a_sequences_state_is_its_own():
    """The second sequence of a batch reads nothing of the first: alone it gives the same rows."""
    x, dt, a, b, c = _inputs("two_sequences", seed=3)
    both = ssd_scan(x, dt, a, b, c, 32)
    alone = ssd_scan(x[1:], dt[1:], a, b[1:], c[1:], 32)
    np.testing.assert_allclose(np.asarray(both[1:]), np.asarray(alone), rtol=1e-6, atol=1e-6)


def test_the_state_crosses_the_chunks_edge():
    """A sequence whose ``x`` is zero after its first chunk still reads that
    chunk's state later on: the carried state's part is there, and decays."""
    x, dt, a, b, c = _inputs("four_chunks", seed=4)
    x = x.at[:, 32:].set(0.0)
    y = ssd_scan(x, dt, a, b, c, 32)
    later = jnp.max(jnp.abs(y[:, 32:64]), axis=(0, 1, 3))  # per head, just past the edge
    assert float(jnp.min(later)) > 0
    assert _worst(y, reference_scan(x, dt, a, b, c)) < 2e-5


@pytest.mark.parametrize("shape", ["four_chunks", "two_sequences"])
def test_bfloat16_matmul_inputs_keep_the_decays_in_float32(shape):
    """``compute_dtype`` bfloat16 rounds the four matmuls' inputs alone: within
    2e-2 of the largest entry, forward and backward, over heads whose state
    lives hundreds of positions (a decay rounded to bfloat16 would not be)."""
    args = _inputs(shape, seed=5)
    probe = jax.random.normal(jax.random.key(9), args[0].shape)
    chunk = SHAPES[shape][2]
    assert _worst(ssd_scan(*args, chunk, jnp.bfloat16), reference_scan(*args)) < 2e-2
    want = jax.grad(lambda *a: jnp.sum(reference_scan(*a) * probe), argnums=range(5))(*args)
    got = jax.grad(lambda *a: jnp.sum(ssd_scan(*a, chunk, jnp.bfloat16) * probe), argnums=range(5))(*args)
    for name, g, w in zip(NAMES, got, want):
        assert g.dtype == w.dtype == jnp.float32 and _worst(g, w) < 2e-2, name


def test_sizes_that_do_not_divide_are_refused():
    x, dt, a, b, c = _inputs("four_chunks")
    with pytest.raises(ValueError, match="whole chunks of 48"):
        ssd_scan(x, dt, a, b, c, 48)
    with pytest.raises(ValueError, match="whole groups"):
        ssd_scan(x, dt, a, b[:, :, :1].repeat(3, axis=2), c[:, :, :1].repeat(3, axis=2), 32)
