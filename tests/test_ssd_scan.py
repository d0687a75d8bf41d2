"""The chunked state-space scan (``parallel/ssd.py::ssd_scan``: a Pallas kernel
pair under a ``custom_vjp``, interpreted here on the CPU) against the plain
recurrence it has to agree with (``reference_scan``: one position at a time),
forward and every gradient, at toy sizes: a sequence of one chunk (no state is
ever carried, ``dS`` stays zero), of several and of many (the forward walk
carries the state first to last, the backward walk ``dS`` last to first, across
every edge), two sequences a batch (nothing couples them), heads that share a
group's ``B`` and ``C`` - two, three or all of them - and heads with their own.

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

from flink_ml_tpu.parallel import ssd
from flink_ml_tpu.parallel.ssd import reference_scan, scan_kernel_chunks, ssd_scan

NAMES = ("x", "dt", "a", "b", "c")
#: ``(sequences, T, chunk, heads, groups)``
SHAPES = {"one_chunk": (1, 32, 32, 4, 2), "four_chunks": (1, 128, 32, 4, 2), "two_sequences": (2, 96, 32, 4, 2),
          "a_group_a_head": (1, 64, 16, 4, 4), "one_group": (2, 64, 32, 6, 1), "sixteen_chunks": (1, 256, 16, 4, 2),
          "three_heads_a_group": (2, 64, 32, 6, 2)}
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


@pytest.mark.parametrize("shape", ["one_chunk", "four_chunks", "sixteen_chunks"])
def test_the_backward_walk_carries_the_last_chunks_pull_to_the_first(shape):
    """A loss that reads the LAST chunk's ``y`` alone: what it asks of the
    first chunk's ``x``, ``B`` and step sizes reaches them only through ``dS``,
    carried back over every edge between (none when the sequence is one chunk:
    then the first chunk is the last, and nothing comes from past it)."""
    args = _inputs(shape, seed=6)
    _, t, chunk, _, _ = SHAPES[shape]
    probe = jax.random.normal(jax.random.key(9), args[0].shape).at[:, : t - chunk].set(0.0)
    want = jax.grad(lambda *a: jnp.sum(reference_scan(*a) * probe), argnums=range(5))(*args)
    got = jax.grad(lambda *a: jnp.sum(ssd_scan(*a, chunk) * probe), argnums=range(5))(*args)
    for name, g, w in zip(NAMES, got, want):
        assert _worst(g, w) < 1e-4, name
    for g in (got[0], got[1], got[3]):  # x, the step sizes, B: each position's own
        assert float(jnp.max(jnp.abs(g[:, :chunk]))) > 0
    if t > chunk:
        assert float(jnp.max(jnp.abs(got[4][:, : t - chunk]))) == 0.0  # C is read where y is, nowhere earlier


def test_no_chunk_by_chunk_block_leaves_the_kernels():
    """Forward and backward are the two kernels by name, and nothing the
    program holds outside them has a ``[chunk, chunk]`` face: the decays and
    masked scores exist inside a grid cell alone. The forward on its own does
    not write the chunk states; under differentiation it does, once."""
    args = _inputs("four_chunks")
    chunk, nc = 32, 4

    def shapes(jaxpr):
        return [tuple(v.aval.shape) for eqn in jaxpr.eqns for v in eqn.outvars]

    forward = jax.make_jaxpr(lambda *a: ssd_scan(*a, chunk))(*args)
    both = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(ssd_scan(*a, chunk)), argnums=range(5)))(*args)
    assert "ssd_scan_fwd" in str(forward) and "ssd_scan_bwd" not in str(forward)
    assert "ssd_scan_fwd" in str(both) and "ssd_scan_bwd" in str(both)
    starts = (1, nc, 2, N, 2 * P)  # [B, chunks, G, N, r P]
    for jaxpr, saved in ((forward, 0), (both, 1)):
        # the custom_vjp's call holds the kernels: look inside it too
        inner = [sub for eqn in jaxpr.jaxpr.eqns for sub in jax.core.jaxprs_in_params(eqn.params)]
        found = shapes(jaxpr.jaxpr) + [s for sub in inner for s in shapes(sub)]
        assert not [s for s in found if len(s) >= 2 and s[-2:] == (chunk, chunk) and s != (chunk, chunk)], found
        assert found.count(starts) == saved


def test_the_kernels_grid_covers_every_chunk_of_every_head():
    for shape, (batch, t, chunk, heads, groups) in SHAPES.items():
        assert scan_kernel_chunks(batch, t, heads, groups, chunk) == batch * heads * (t // chunk), shape


def test_a_tpu_takes_only_shapes_that_tile(monkeypatch):
    """Compiled for the chip the lane dimensions have to tile; the toy shapes
    of this file run interpreted alone."""
    monkeypatch.setattr(ssd, "_interpreted", lambda: False)
    with pytest.raises(ValueError, match="on the TPU the scan's kernels take"):
        ssd_scan(*_inputs("four_chunks"), 32)


def test_sizes_that_do_not_divide_are_refused():
    x, dt, a, b, c = _inputs("four_chunks")
    with pytest.raises(ValueError, match="whole chunks of 48"):
        ssd_scan(x, dt, a, b, c, 48)
    with pytest.raises(ValueError, match="whole groups"):
        ssd_scan(x, dt, a, b[:, :, :1].repeat(3, axis=2), c[:, :, :1].repeat(3, axis=2), 32)
