"""The causal depthwise convolution with its bias and SiLU
(``parallel/causal_conv.py::causal_conv``: a Pallas kernel pair under a
``custom_vjp``, interpreted here on the CPU) against the plain form it has to
agree with (``reference_conv``: a padded copy and ``taps`` shifted slices),
forward and every gradient, at toy sizes: 4 taps and 2; a sequence of one
position block (no halo is read: zeros before it, nothing after it) and of
several (a block reads the rows before it out of its neighbour, and the
backward the rows after it: the halo crossed both ways); two sequences a batch
(the second's first positions read zeros, not the first's tail); channels of
one block and of several; the convolved channels read in place between others,
and written as three arrays of uneven widths.

Tolerances. float32 on both sides, the same sums in the same order but for the
sigmoid's form: read here at 3e-7 of the largest entry forward and backward;
the limit is 1e-5.
"""
import jax
import jax.numpy as jnp
import pytest

from flink_ml_tpu.parallel import causal_conv as module
from flink_ml_tpu.parallel.causal_conv import causal_conv, forward_positions, reference_conv

#: ``(sequences, T, taps, channels before, widths, channels after)``; a position block is 512 rows at the most, a
#: channel block the largest divisor of 512 that every part starts and ends at
CASES = {
    "one_block": (1, 64, 4, 0, (8,), 0),
    "three_position_blocks": (1, 1536, 4, 0, (8,), 0),
    "two_taps": (1, 1024, 2, 0, (8,), 0),
    "two_sequences": (2, 1024, 4, 0, (8,), 0),
    "two_channel_blocks": (1, 64, 4, 0, (1024,), 0),
    "uneven_split_read_in_place": (2, 1024, 4, 16, (24, 8, 16), 8),
}


def _inputs(case, seed=0):
    batch, t, taps, before, widths, after = CASES[case]
    k = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(k[0], (batch, t, before + sum(widths) + after)),
            jax.random.normal(k[1], (taps, sum(widths))), jax.random.normal(k[2], (sum(widths),)), widths, before)


def _worst(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernels_are_the_plain_convolution(case):
    """Forward, and the gradient of a random projection of every output in
    ``u`` (the channels the convolution passes by among them), ``w`` and ``b``."""
    u, w, b, widths, first = _inputs(case)
    parts = [first, *widths, u.shape[2] - first - sum(widths)]
    probes = [jax.random.normal(jax.random.key(9 + i), u.shape[:2] + (k,)) for i, k in enumerate(parts)]

    def both(conv):  # one program a side: the outputs and the gradients of their projection on the probes
        def run(u, w, b):
            outs, pull = jax.vjp(lambda *args: jax.tree_util.tree_leaves(conv(*args, widths, first)), u, w, b)
            return outs, pull(probes)

        return jax.jit(run)(u, w, b)

    want, want_grads = both(reference_conv)
    got, got_grads = both(causal_conv)
    assert [g.shape[2] for g in got] == parts and all(g.dtype == jnp.float32 for g in got)
    for g, x in zip(got, want):
        assert g.shape == x.shape and (g.size == 0 or _worst(g, x) < 1e-5)
    for name, g, x in zip(("u", "w", "b"), got_grads, want_grads):
        assert float(jnp.max(jnp.abs(x))) > 0 and g.shape == x.shape, name
        assert _worst(g, x) < 1e-5, name


def test_a_sequence_reads_nothing_of_the_one_before_it():
    """The second sequence of a batch alone gives the same rows, forward and
    backward: its first positions read zeros, its last hand nothing on."""
    u, w, b, widths, first = _inputs("two_sequences", seed=1)

    @jax.jit
    def both(u):
        out, pull = jax.vjp(lambda u: causal_conv(u, w, b, widths, first)[1][0], u)
        return out, pull(2.0 * out)[0]

    (out, du), (out_alone, du_alone) = both(u), both(u[1:])
    assert float(jnp.max(jnp.abs(out[1:] - out_alone))) == 0.0 and float(jnp.max(jnp.abs(du[1:] - du_alone))) == 0.0


def test_both_directions_are_the_kernels_by_name():
    """One call a direction whatever the parts, and no padded copy of the channels beside them."""
    u, w, b, widths, first = _inputs("uneven_split_read_in_place")
    batch, t, _ = u.shape
    forward = str(jax.make_jaxpr(lambda u: causal_conv(u, w, b, widths, first))(u))
    both = jax.make_jaxpr(jax.grad(lambda u: sum(jnp.sum(o) for o in causal_conv(u, w, b, widths, first)[1])))(u)
    assert forward.count("name=causal_conv_fwd") == 1 and "causal_conv_bwd" not in forward
    assert str(both).count("name=causal_conv_bwd") == 1
    assert f"f32[{batch},{t + w.shape[0] - 1}," not in forward + str(both)
    # what the forward's calls cover, grid cells x block: every convolved position of every channel, once
    assert forward_positions(both.jaxpr) == batch * t * sum(widths)


def test_a_shape_the_chip_cannot_tile_is_refused_there(monkeypatch):
    """On a TPU backend the parts start and end at multiples of the 128 lanes,
    or the call is refused: there is no other path. Anywhere, a sequence is a
    multiple of 8 positions and the taps fit one halo."""
    u, w, b, widths, first = _inputs("uneven_split_read_in_place")
    monkeypatch.setattr(module, "_interpreted", lambda: False)
    with pytest.raises(ValueError, match="on the TPU, parts that start and end at multiples of 128 channels"):
        causal_conv(u, w, b, widths, first)
    monkeypatch.undo()
    with pytest.raises(ValueError, match="a multiple of 8 positions"):
        causal_conv(u[:, :60], w, b, widths, first)
    with pytest.raises(ValueError, match="1 to 9 taps"):
        causal_conv(u, jnp.zeros((10, sum(widths))), b, widths, first)
    with pytest.raises(ValueError, match="1 to 9 taps"):
        causal_conv(u, w, b, widths, first + 16)  # the parts end past u's channels
