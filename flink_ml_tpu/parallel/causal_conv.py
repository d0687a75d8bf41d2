"""The short causal depthwise convolution of a Mamba-2 layer (``models/lm``'s
``nemotron_h`` block kind) with its bias and SiLU as a pair of Pallas kernels,
one pass over the channels a direction, and the plain ``jax.numpy`` form it
has to agree with.

No analogue exists in the reference (its models are single coefficient
vectors). Per sequence and channel ``c``, with ``taps`` weights ``w [taps,
channels]`` and a bias ``b``::

    pre_t = b + sum_j w_j u_(t - (taps - 1) + j)        u_t = 0 for t < 0
    out_t = silu(pre_t)

zeros before the sequence's first position and nowhere else (packed documents
carry no mask). ``reference_conv`` is that sum over a padded copy and ``taps``
shifted slices of it: what ``_mamba_block`` ran until PR 42, 5.2 ms a layer
forward and 10.7 backward on the chip where the bytes are 1.0 and 1.5 (ledger,
PR 41: ``ssm_conv_ms`` 90.66 of a 707.5 ms step). A slice shifted by a
POSITION is shifted along the sublanes, so each of them was a relayout, and
AD's backward ran through the pad, the slices and a concatenation.

``causal_conv`` reads the convolved channels IN PLACE out of a wider array
(``u [B, T, C]``, a Mamba-2 layer's whole in-projection; channels ``first ..
first + sum(widths)``) and writes each of the ``widths`` as an array of its
own (the scan's ``x``, ``B`` and ``C``: ``ssd_scan`` takes them apart, and
cutting them out of one activated array was a copy each, PR 41). One call a
direction whatever the parts: three calls a direction measured the same on
the chip and cost the fit's set-up 1.4 s of tracing and lowering (PR 42).

**The grid** is ``(channel block, sequence, position block)`` over all the
convolved channels. A cell holds a ``[positions, channels]`` block (512 x 512
float32 at the Nemotron cell) and, through a second block spec on the same
array, the 8 rows that end where the block starts: the ``taps - 1`` positions
a tap reads before the block are its last rows (zeroed at the sequence's
first block). The backward also holds the 8 rows after the block, of ``u``
and of the incoming gradient: ``d pre`` of the positions that read this block
from beyond it. ``w`` and ``b`` ride as ``[., channels]`` ROWS, broadcast
down the sublanes (a ``[positions, 1]`` column costs a whole tile's pass,
``parallel/ssd.py``). An array that holds ONE part (an output of the forward,
an incoming gradient of the backward) moves its block only while the grid is
at that part's channels and stands still before and after (``_part_spec``):
a block is fetched, and written back, when its index moves, so the forward
writes each part's blocks once and the backward reads each once; the cell
stores to, or selects from, the part it is at.

**Inside a cell** the block is walked in strips of rows that fit the vector
registers; a tap's shifted read is the strip behind its 8 earlier rows rolled
down the sublanes (``_shifted``), in VMEM: nothing shifted reaches HBM.

**The backward** (``causal_conv_bwd``) saves nothing but the kernel's inputs:
it recomputes ``pre`` from ``u``, forms ``d pre = d out silu'(pre)``, emits
``d u_t = sum_j w_j d pre_(t + (taps - 1) - j)`` (the anti-causal reads: the
strip ahead of its 8 later rows rolled up) walking a block's strips last to
first, and sums ``d w`` and ``d b`` in float32 in an output block whose index
does not move along the sequence and position axes, the grid's sequential
ones (no scratch: ``flash.py::_fold_tiles`` says why), as 8 partial sums a
tap down the sublanes, added up outside. The channels of ``u`` the
convolution does not read leave ``causal_conv`` beside its parts, so the
backward is handed their gradients and ``d u`` is ONE concatenation (XLA
writes it in the compute type of the matmuls that read it).

Precision, the configuration's: float32 in, float32 arithmetic, float32 out.

Compiled by Mosaic on a TPU backend, interpreted elsewhere (the CPU mesh of
the tests), decided here from the backend. On the TPU the channel blocks have
to tile the 128 lanes: ``first`` and every width a multiple of 128; a shape
that does not is refused, there is no other path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flink_ml_tpu.parallel.mesh import is_tpu_backend

__all__ = ["causal_conv", "reference_conv", "forward_positions"]

_LANES = 128
#: Rows of a halo block: one sublane tile, which bounds ``taps - 1``.
_HALO = 8
#: Positions and channels of a grid cell's block at the most, rows of a strip inside it.
_POSITIONS, _CHANNELS, _STRIP = 512, 512, 32


def reference_conv(u, w, b, widths, first: int = 0):
    """``(before, parts, after)``: ``silu`` of the causal depthwise convolution
    of channels ``first .. first + sum(widths)`` of ``u [B, T, C]`` with ``w
    [taps, sum(widths)]`` and ``b [sum(widths)]``, cut into float32 arrays of
    ``widths``, between the channels before and after them as they are."""
    taps, t, end = w.shape[0], u.shape[1], first + sum(widths)
    f32 = jnp.float32
    earlier = jnp.pad(u[..., first: end].astype(f32), ((0, 0), (taps - 1, 0), (0, 0)))
    # tap j reads position t - (taps - 1) + j
    pre = sum(w[j].astype(f32) * earlier[:, j: j + t] for j in range(taps)) + b.astype(f32)
    lo = [sum(widths[:i]) for i in range(len(widths))]
    return u[..., :first], tuple(jax.nn.silu(pre[..., at: at + width]) for at, width in zip(lo, widths)), u[..., end:]


def _interpreted() -> bool:
    """Off the TPU the kernels run under the Pallas interpreter."""
    return not is_tpu_backend(jax.devices())


# -- the kernels --


def _shifted(behind, strip, s: int):
    """``strip [rows, c]`` read ``s`` rows earlier, ``behind [8, c]`` being the
    rows before it (``s`` negative: later, ``behind`` the rows AFTER it)."""
    if not s:
        return strip
    rows = strip.shape[0]
    if s > 0:
        return pltpu.roll(jnp.concatenate([behind, strip], axis=0), s, 0)[_HALO:]
    return pltpu.roll(jnp.concatenate([strip, behind], axis=0), rows + _HALO + s, 0)[:rows]


def _pre(behind, strip, w, b):
    """The pre-activation of ``strip``'s rows and the ``taps`` shifted reads it
    sums (tap ``j`` reads ``taps - 1 - j`` rows earlier)."""
    taps = w.shape[0]
    reads = [_shifted(behind, strip, taps - 1 - j) for j in range(taps)]
    pre = b
    for j in range(taps):
        pre = pre + w[j: j + 1] * reads[j]
    return pre, reads


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _fold8(m):
    """``m [rows, c]`` summed into 8 partial sums down the sublanes."""
    return functools.reduce(jnp.add, (m[i: i + _HALO] for i in range(0, m.shape[0], _HALO)))


def _mine(c, span):
    """Whether channel block ``c`` of the grid lies in ``span``, a part's first block and the one past its last."""
    return (c >= span[0]) & (c < span[1])


def _fwd_kernel(rows: int, spans, before_ref, u_ref, w_ref, b_ref, *out_refs):
    w, b, c = w_ref[...], b_ref[...], pl.program_id(0)
    # zeros before the sequence's first position
    before = jnp.where(pl.program_id(2) == 0, 0.0, before_ref[0])

    def strip(r, behind):
        at = pl.ds(pl.multiple_of(r * rows, rows), rows)
        now = u_ref[0, at, :]
        pre, _ = _pre(behind, now, w, b)
        out = pre * _sigmoid(pre)
        for span, out_ref in zip(spans, out_refs):  # the part this channel block belongs to takes it
            @pl.when(_mine(c, span))
            def _(out_ref=out_ref):
                out_ref[0, at, :] = out

        return now[rows - _HALO:]

    jax.lax.fori_loop(0, u_ref.shape[1] // rows, strip, before)


def _bwd_kernel(rows: int, spans, before_ref, u_ref, after_ref, *refs):
    n = len(spans)
    dout_refs, dout_after_refs, (w_ref, b_ref, du_ref, dwb_ref) = refs[:n], refs[n: 2 * n], refs[2 * n:]

    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _():
        dwb_ref[...] = jnp.zeros_like(dwb_ref)

    w, b, c = w_ref[...], b_ref[...], pl.program_id(0)
    taps, positions = w.shape[0], u_ref.shape[1]
    strips = positions // rows
    before = jnp.where(pl.program_id(2) == 0, 0.0, before_ref[0])

    def incoming(part_refs, at):
        """The rows ``at`` of the part this channel block belongs to (the others' blocks stand still: ``_part_spec``)."""
        rows_at = part_refs[-1][0, at, :]
        for span, ref in list(zip(spans, part_refs))[-2::-1]:
            rows_at = jnp.where(_mine(c, span), ref[0, at, :], rows_at)
        return rows_at

    def dpre_of(behind, now, dout):
        pre, reads = _pre(behind, now, w, b)
        sig = _sigmoid(pre)
        return dout * (sig * (1.0 + pre * (1.0 - sig))), reads

    # d pre of the 8 positions after the block, which read its last rows: zero past the sequence's end
    ahead, _ = dpre_of(u_ref[0, positions - _HALO:, :], after_ref[0], incoming(dout_after_refs, slice(None)))
    ahead = jnp.where(pl.program_id(2) == pl.num_programs(2) - 1, 0.0, ahead)

    def strip(k, ahead):  # last to first: a strip hands the one before it its first rows' d pre
        r = strips - 1 - k
        at = pl.ds(pl.multiple_of(r * rows, rows), rows)
        now = u_ref[0, at, :]
        behind = jnp.where(r == 0, before, u_ref[0, pl.ds(pl.multiple_of(jnp.maximum(r * rows - _HALO, 0), _HALO),
                                                          _HALO), :])
        dpre, reads = dpre_of(behind, now, incoming(dout_refs, at))
        du = w[taps - 1: taps] * dpre
        for j in range(taps - 1):
            du = du + w[j: j + 1] * _shifted(ahead, dpre, j - (taps - 1))
        du_ref[0, at, :] = du
        for j in range(taps):
            dwb_ref[_HALO * j: _HALO * (j + 1), :] += _fold8(dpre * reads[j])
        dwb_ref[_HALO * taps:, :] += _fold8(dpre)
        return dpre[:_HALO]

    jax.lax.fori_loop(0, strips, strip, ahead)


_FWD_NAME = "causal_conv_fwd"
#: every axis in order: a part's blocks stand still while the grid is at another part's channels (``_part_spec``),
#: and the backward sums d w and d b over the sequence and position axes
_PARAMS = pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary", "arbitrary"))


def _blocks(t: int, first: int, widths, interpret: bool):
    """``(positions, channels, strip rows)`` of a cell for sequences of ``t``
    positions and parts of ``widths`` channels from channel ``first`` on."""
    channels = [first + sum(widths[:i]) for i in range(len(widths) + 1)]
    cb = math.gcd(*channels, _CHANNELS)
    if t % _HALO or (not interpret and cb % _LANES):
        raise ValueError(f"the convolution's kernels take sequences of a multiple of {_HALO} positions and, on the "
                         f"TPU, parts that start and end at multiples of {_LANES} channels; got T {t}, parts at "
                         f"{sorted(set(channels))}")
    tq = math.gcd(t, _POSITIONS)
    return tq, cb, min(tq, _STRIP)


def _spans(widths, cb: int):
    """``(first channel block, the one past the last)`` a part, of the grid's channel blocks."""
    edges = [sum(widths[:i]) // cb for i in range(len(widths) + 1)]
    return list(zip(edges[:-1], edges[1:]))


def _specs(t: int, tq: int, cb: int, at: int = 0):
    """The block specs of an array ``[B, T, .]`` read from channel ``at`` on,
    a channel block a grid step: a cell's block, the 8 rows before it and the
    8 rows after it."""
    lo, per, last = at // cb, tq // _HALO, t // _HALO - 1
    return (pl.BlockSpec((1, tq, cb), lambda c, i, z: (i, z, lo + c), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _HALO, cb), lambda c, i, z: (i, jnp.maximum(z * per - 1, 0), lo + c),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _HALO, cb), lambda c, i, z: (i, jnp.minimum((z + 1) * per, last), lo + c),
                         memory_space=pltpu.VMEM))


def _part_spec(spec, span, grid):
    """``spec`` (one of ``_specs``' three) for an array that holds one part's
    channels alone, under a grid that walks every part's: inside the part's
    span the block ``spec`` names, counted from the part's first channel
    block; before it the array's first block and after it its last, standing
    still, so that nothing is fetched or written back for them (a block moves
    when its index does)."""
    (lo, hi), (_, batch, nz) = span, grid

    def index(c, i, z):
        inside = spec.index_map(jnp.clip(c, lo, hi - 1) - lo, i, z)
        # the block the part's first, or last, grid step names
        still = spec.index_map(jnp.where(c < lo, 0, hi - 1 - lo), jnp.where(c < lo, 0, batch - 1),
                               jnp.where(c < lo, 0, nz - 1))
        return tuple(jnp.where(_mine(c, span), a, b) for a, b in zip(inside, still))

    return pl.BlockSpec(spec.block_shape, index, memory_space=pltpu.VMEM)


def _rows_spec(rows: int, cb: int):
    """``[rows, channels]`` beside the grid's channel blocks: the same block whatever the sequence and position."""
    return pl.BlockSpec((rows, cb), lambda c, i, z: (0, c), memory_space=pltpu.VMEM)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _conv(u, w, b, first, widths, blocks, interpret):
    return _conv_fwd(u, w, b, first, widths, blocks, interpret)[0]


def _conv_fwd(u, w, b, first, widths, blocks, interpret):
    (batch, t, _), taps, (tq, cb, rows) = u.shape, w.shape[0], blocks
    spans, grid = _spans(widths, cb), (sum(widths) // cb, batch, t // tq)
    block, before, _ = _specs(t, tq, cb, first)
    outs = pl.pallas_call(
        functools.partial(_fwd_kernel, rows, spans),
        grid=grid,
        in_specs=[before, block, _rows_spec(taps, cb), _rows_spec(1, cb)],
        out_specs=[_part_spec(_specs(t, tq, cb)[0], span, grid) for span in spans],
        out_shape=[jax.ShapeDtypeStruct((batch, t, width), jnp.float32) for width in widths],
        interpret=interpret,
        compiler_params=_PARAMS,
        name=_FWD_NAME,
    )(u, u, w, b[None])
    # the channels the convolution does not read leave beside its parts, so that the backward is handed their
    # gradients and lays all of d u down once
    return (u[..., :first], tuple(outs), u[..., first + sum(widths):]), (u, w, b)


def _conv_bwd(first, widths, blocks, interpret, res, cotangents):
    u, w, b = res
    d_before, douts, d_after = cotangents
    (batch, t, _), taps, (tq, cb, rows) = u.shape, w.shape[0], blocks
    spans, grid, sums = _spans(widths, cb), (sum(widths) // cb, batch, t // tq), _HALO * (taps + 1)
    block, before, after = _specs(t, tq, cb, first)
    mine, _, mine_after = _specs(t, tq, cb)
    du, dwb = pl.pallas_call(
        functools.partial(_bwd_kernel, rows, spans),
        grid=grid,
        in_specs=[before, block, after, *(_part_spec(mine, span, grid) for span in spans),
                  *(_part_spec(mine_after, span, grid) for span in spans), _rows_spec(taps, cb), _rows_spec(1, cb)],
        out_specs=[mine, _rows_spec(sums, cb)],
        out_shape=[jax.ShapeDtypeStruct((batch, t, sum(widths)), jnp.float32),
                   jax.ShapeDtypeStruct((sums, sum(widths)), jnp.float32)],
        interpret=interpret,
        compiler_params=_PARAMS,
        name="causal_conv_bwd",
    )(u, u, u, *douts, *douts, w, b[None])
    dwb = jnp.sum(dwb.reshape(taps + 1, _HALO, -1), axis=1)
    return jnp.concatenate([d_before, du, d_after], axis=-1), dwb[:taps].astype(w.dtype), dwb[taps].astype(b.dtype)


_conv.defvjp(_conv_fwd, _conv_bwd)


def forward_positions(jaxpr) -> int:
    """The positions x channels that the forward kernel's calls among
    ``jaxpr``'s own equations cover: each call's grid cells times its output
    block. Of a training step's jaxpr these are the forward's calls (what the
    backward rematerialises sits inside its ``checkpoint`` equations), however
    many layers share one traced block: a count made where ``causal_conv`` is
    called would miss the layers whose trace JAX found in its cache."""
    covered = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" and eqn.params["name"] == _FWD_NAME:
            mapping = eqn.params["grid_mapping"]
            covered += math.prod(mapping.grid) * math.prod(d.block_size for d in mapping.block_mappings[-1].block_shape)
    return covered


def causal_conv(u, w, b, widths, first: int = 0):
    """``reference_conv`` through the kernels: ``u [B, T, C]`` float32 read in
    place from channel ``first`` on, ``w [taps, sum(widths)]``, ``b
    [sum(widths)]``; ``(before, parts, after)``, ``parts`` a tuple of float32
    arrays ``[B, T, width]``, one a width, ``before`` and ``after`` the
    channels of ``u`` on either side of them. Differentiable in ``u``, ``w``
    and ``b`` (the backward is the second kernel)."""
    widths = tuple(int(k) for k in widths)
    taps = w.shape[0]
    if not 1 <= taps <= _HALO + 1 or w.shape[1] != sum(widths) or first + sum(widths) > u.shape[2]:
        raise ValueError(f"the convolution takes 1 to {_HALO + 1} taps over the channels it is given; got w "
                         f"{w.shape} for widths {widths} from channel {first} of {u.shape[2]}")
    interpret = _interpreted()
    f32 = jnp.float32
    return _conv(u.astype(f32), w.astype(f32), b.astype(f32), int(first), widths,
                 _blocks(u.shape[1], int(first), widths, interpret), interpret)
