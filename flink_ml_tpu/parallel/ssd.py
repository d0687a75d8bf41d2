"""The selective state-space scan of a Mamba-2 layer (``models/lm``'s
``nemotron_h`` block kind) in its chunked, state-space-duality form as a pair
of Pallas kernels, and the plain step-by-step recurrence it has to agree with.

No analogue exists in the reference (its models are single coefficient
vectors). The recurrence, per sequence and head ``h`` (``P`` channels, a state
of ``P x N``), reading the ``B`` and ``C`` of its group ``h // (H / G)``::

    S_t = exp(delta_t A_h) S_(t-1) + delta_t x_t B_t^T        S_(-1) = 0
    y_t = S_t C_t

``delta > 0`` is the step size (after its softplus), ``A < 0`` the head's
decay rate; the skip ``D x`` and the gate are the caller's.

``reference_scan`` is that loop, one position at a time, in float32.

``ssd_scan`` computes the same ``y`` from chunks of ``chunk`` positions
(arXiv:2405.21060, section 6), so that its work is matmuls the MXU takes. With
``cum_i`` the running sum of ``delta A`` inside a chunk:

- inside a chunk the state never exists: ``y_i += sum_(j<=i) (C_i . B_j)
  exp(cum_i - cum_j) delta_j x_j``, a ``[chunk, chunk]`` matrix per head
  (``C B^T`` once per GROUP, the decays and the mask per head) times the
  chunk's ``x``;
- what the state a chunk starts from adds: ``y_i += exp(cum_i) C_i S_prev``;
- the state the next chunk starts from: ``S <- exp(cum_last) S_prev + sum_j
  exp(cum_last - cum_j) delta_j x_j B_j^T``.

**What lives in VMEM.** A grid cell is one chunk of one group of one sequence:
it reads the chunk's ``x`` (``[chunk, r P]``, the group's ``r = H / G`` heads
side by side as the caller holds them), ``B`` and ``C`` (``[chunk, N]``, once
for the group), and the group's step sizes and cumulative log-decays (``[2 r,
chunk]``, a few KB); it builds ``C B^T``, every head's ``[chunk, chunk]``
decays and masked scores and their products there, and writes the chunk's
``y``. None of those blocks reaches HBM, in either direction. The group's state
(``[N, r P]`` float32) rides the chunk axis, the grid's last and sequential
one, in an output block whose index does not move with the chunk (no scratch:
``flash.py::_fold_tiles`` says why).

**The two walks.** ``ssd_scan_fwd`` walks a sequence's chunks first to last
carrying ``S``; under differentiation it also writes the state every chunk
STARTS from (``[B, T / chunk, G, N, r P]`` float32: 268 MB a layer at 2 x 8,192
tokens, 64 heads x 64, state 128, written once and read once), which nothing
else could hand the backward. ``ssd_scan_bwd`` walks them last to first
carrying ``dS``, the gradient of the state at the chunk's end (zero past the
last chunk). It recomputes the chunk's ``C B^T``, decays and scores from the
same inputs (no ``[chunk, chunk]`` residual is saved), in the TRANSPOSED
orientation (``j`` down the sublanes), in which ``dx = M^T dy`` is a plain
matmul; it emits ``dx``, ``dB`` and ``dC`` (summed over the group's heads
inside the cell) and, per position and head, the gradients of ``delta`` and of
the cumulative log-decay (``[B, G, 2 r, T]``, as they came in). What is left is
``jax.numpy`` around the kernels on ``[B, T, H]`` arrays: the cumulative sum
inside a chunk and its transpose (one matmul with a triangle of ones each,
float32 at the highest precision; XLA's own ``cumsum`` is a slow
reduce-window on this chip), ``delta A`` and the sum that is ``dA``.

Rows and columns: a head's decays are ``exp(cum_i - cum_j)``, an outer
difference, which wants ``cum`` once along the sublanes and once along the
lanes. The per-head numbers come in with the positions along the lanes
(rows); a cell transposes the few KB once (``_Cell.columns``) and does every
head's column arithmetic - the ``exp`` of the log-decays, the decay to the
chunk's end - on ``[chunk, r]`` at once: a ``[chunk, 1]`` column costs a whole
``[chunk, 128]`` tile's pass, so per-head columns were 40% of the forward
kernel's time and half the backward's (chip runs, PR 41). The backward's
per-position sums fall out the way they fall - sums over ``i`` as columns,
over ``j`` as rows - and are gathered beside the other heads', finished on
``[chunk, r]`` and transposed back once. Heads narrower than the 128 lanes
share a tile: ``128 // P`` of them are loaded, multiplied and stored together,
each masked to its own lanes.

Precision, the configuration's: ``delta``, ``A``, the cumulative log-decays,
every ``exp``, the carried state and ``dS``, every per-position sum and ``y``
are float32 whatever the compute type; the matmuls over positions and over the
state take their inputs in the compute type (``bfloat16``: the MXU's path;
float32 at the highest precision otherwise) and accumulate in float32.

Compiled by Mosaic on a TPU backend, interpreted elsewhere (the CPU mesh of
the tests), decided here from the backend. On the TPU the lane dimensions have
to tile: ``r P`` and ``N`` multiples of 128 (or the whole of their arrays),
``chunk`` a multiple of 128; a shape that does not is refused, there is no
other path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flink_ml_tpu.parallel.mesh import is_tpu_backend

__all__ = ["ssd_scan", "reference_scan", "scan_kernel_chunks"]

_HIGHEST = jax.lax.Precision.HIGHEST
_LANES = 128


def reference_scan(x, dt, a, b, c):
    """``y [B, T, H, P]`` float32 of the recurrence above, one position at a
    time: ``x [B, T, H, P]``, ``dt [B, T, H]``, ``a [H]``, ``b``, ``c`` ``[B,
    T, G, N]`` (head ``h`` reads group ``h // (H / G)``)."""
    heads, groups = x.shape[2], b.shape[2]
    f32 = jnp.float32
    x, dt, a = x.astype(f32), dt.astype(f32), a.astype(f32)
    b, c = (jnp.repeat(m.astype(f32), heads // groups, axis=2) for m in (b, c))  # [B, T, H, N]

    def sequence(x, dt, b, c):
        def position(state, now):
            x_t, dt_t, b_t, c_t = now
            state = jnp.exp(dt_t * a)[:, None, None] * state + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
            return state, jnp.sum(state * c_t[:, None, :], axis=-1)

        _, y = jax.lax.scan(position, jnp.zeros(x.shape[1:] + b.shape[-1:], f32), (x, dt, b, c))
        return y

    return jax.vmap(sequence)(x, dt, b, c)


def _interpreted() -> bool:
    """Off the TPU the kernels run under the Pallas interpreter."""
    return not is_tpu_backend(jax.devices())


def _heads_a_tile(r: int, p: int) -> int:
    """How many of a group's ``r`` heads of ``p`` channels share a lane tile."""
    k = max(1, min(r, _LANES // p))
    while r % k:
        k -= 1
    return k


class _Cell:
    """What both kernels compute alike on one (sequence, group, chunk) cell."""

    def __init__(self, q: int, r: int, p: int, cd):
        self.q, self.r, self.p, self.cd = q, r, p, jnp.dtype(cd)
        self.k = _heads_a_tile(r, p)
        self.w = self.k * p
        self.precision = _HIGHEST if self.cd == jnp.float32 else None

    def dot(self, lhs, rhs, contract=((1,), (0,))):
        """``lhs @ rhs`` (or the contraction named) on compute-type inputs into float32."""
        return jax.lax.dot_general(lhs.astype(self.cd), rhs.astype(self.cd), (contract, ((), ())),
                                   preferred_element_type=jnp.float32, precision=self.precision)

    def dot_nt(self, lhs, rhs):  # lhs @ rhs^T
        return self.dot(lhs, rhs, ((1,), (1,)))

    def dot_tn(self, lhs, rhs):  # lhs^T @ rhs
        return self.dot(lhs, rhs, ((0,), (0,)))

    def tiles(self):
        """``(lane slice, [(head of the group, its lanes' mask [1, w])])`` a tile of heads."""
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, self.w), 1)
        for tile in range(self.r // self.k):
            yield slice(tile * self.w, (tile + 1) * self.w), [
                (tile * self.k + j, (lane >= j * self.p) & (lane < (j + 1) * self.p)) for j in range(self.k)]

    def columns(self, rows):
        """From ``rows [2 r, q]`` (the step sizes above the cumulative
        log-decays), every head's columns at once, ``[q, r]`` each: the step
        sizes, the log-decays, ``exp(cum)`` (what a position reads of the state
        the chunk starts from), ``exp(last - cum)`` and that times the step size
        (what it leaves in the state the chunk ends with), and ``exp(last) [1,
        r]``. One pass over a few vregs here, where a head's own ``[q, 1]``
        column would cost a ``[q, 128]`` tile's pass each."""
        cols = rows.T
        dt, cum = cols[:, : self.r], cols[:, self.r:]
        last = cum[self.q - 1:]
        decay_to_end = jnp.exp(last - cum)
        return dt, cum, jnp.exp(cum), decay_to_end, decay_to_end * dt, jnp.exp(last)

    def spread(self, per_head, heads):
        """``per_head [., r]`` over a tile's lanes: each head's column on its own channels."""
        out = jnp.zeros((per_head.shape[0], self.w), jnp.float32)
        for h, mine in heads:
            out = jnp.where(mine, per_head[:, h: h + 1], out)
        return out


def _fwd_kernel(cell: _Cell, save: bool, x_ref, b_ref, c_ref, rows_ref, y_ref, state_ref, *starts_ref):
    f32, q, r = jnp.float32, cell.q, cell.r

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    state = state_ref[0, 0]  # [N, r P]: what this chunk starts from
    if save:
        starts_ref[0][0, 0, 0] = state
    b, c = b_ref[0], c_ref[0]
    rows = rows_ref[0, 0]
    _, cum, grow, _, to_end, through = cell.columns(rows)
    cb = cell.dot_nt(c, b)  # [i, j]
    from_state = cell.dot(c, state)  # [q, r P]
    seen = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    for lanes, heads in cell.tiles():
        x = x_ref[0, :, lanes].astype(f32)
        y = cell.spread(grow, heads) * from_state[:, lanes]
        for h, mine in heads:
            decays = jnp.exp(jnp.where(seen, cum[:, h: h + 1] - rows[r + h: r + h + 1], -jnp.inf))  # exp(cum_i - cum_j)
            y = y + cell.dot(cb * decays * rows[h: h + 1], jnp.where(mine, x, 0.0))
        y_ref[0, :, lanes] = y
        state_ref[0, 0, :, lanes] = (cell.spread(through, heads) * state[:, lanes]
                                     + cell.dot_tn(b, x * cell.spread(to_end, heads)))


def _bwd_kernel(cell: _Cell, x_ref, b_ref, c_ref, rows_ref, starts_ref, dy_ref,
                dx_ref, db_ref, dc_ref, drows_ref, dstate_ref):
    f32, q, r = jnp.float32, cell.q, cell.r

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    dstate = dstate_ref[0, 0]  # [N, r P]: the gradient of the state this chunk ENDS with
    state = starts_ref[0, 0, 0]  # the state it started from
    b, c = b_ref[0], c_ref[0]
    rows = rows_ref[0, 0]
    dt, cum, grow, decay_to_end, to_end, through = cell.columns(rows)
    cb_t = cell.dot_nt(b, c)  # [j, i]
    to_state = cell.dot(b, dstate)  # [q, r P]: what dS asks of each position's delta x
    from_state = cell.dot(c, state)
    seen = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1) >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)  # i >= j
    # a head's sums over i fall out as columns, its sums over j as rows: each is gathered beside the other heads'
    # and the arithmetic on them is done once, on [q, r]
    row_of = jax.lax.broadcasted_iota(jnp.int32, (2 * r, 1), 0)
    col_of = jax.lax.broadcasted_iota(jnp.int32, (1, r), 1)
    pulled_rows = jnp.zeros((2 * r, q), f32)  # d cum_i of the scores' decays, under the step sizes' (empty) rows
    ddt = asked = read = jnp.zeros((q, r), f32)
    kept_state = jnp.zeros((1, r), f32)
    dcb_t = jnp.zeros((q, q), f32)
    db = dc = jnp.zeros(b.shape, f32)
    for lanes, heads in cell.tiles():
        x, dy = x_ref[0, :, lanes].astype(f32), dy_ref[0, :, lanes].astype(f32)
        leaves = cell.spread(to_end, heads)  # what each position's delta x leaves in the state at the chunk's end
        dx = leaves * to_state[:, lanes]
        for h, mine in heads:
            dt_col = dt[:, h: h + 1]
            x_h, dy_h = jnp.where(mine, x, 0.0), jnp.where(mine, dy, 0.0)
            decays = jnp.exp(jnp.where(seen, rows[r + h: r + h + 1] - cum[:, h: h + 1], -jnp.inf))  # [j, i]: exp(cum_i - cum_j)
            kept = cb_t * decays  # the scores without delta_j
            dscores = cell.dot_nt(x_h, dy)  # [j, i] = x_j . dy_i over the head's channels
            dx = dx + cell.dot(kept * dt_col, dy_h)
            dcb_t = dcb_t + dscores * decays * dt_col
            pull = dscores * kept  # times delta_j: the gradient of (cum_i - cum_j)
            ddt = jnp.where(col_of == h, jnp.sum(pull, axis=1, keepdims=True), ddt)
            pulled_rows = jnp.where(row_of == r + h, jnp.sum(pull * dt_col, axis=0, keepdims=True), pulled_rows)
            # the state's terms: y_i += exp(cum_i) C_i S and S' = exp(last) S + sum_j exp(last - cum_j) delta_j x_j B_j^T
            asked = jnp.where(col_of == h, jnp.sum(x_h * to_state[:, lanes], axis=1, keepdims=True), asked)
            read = jnp.where(col_of == h, jnp.sum(dy_h * from_state[:, lanes], axis=1, keepdims=True), read)
            kept_state = jnp.where(col_of == h, jnp.sum(jnp.sum(jnp.where(mine, dstate[:, lanes] * state[:, lanes], 0.0),
                                                                axis=0, keepdims=True), axis=1, keepdims=True), kept_state)
        dx_ref[0, :, lanes] = dx.astype(dx_ref.dtype)
        read_state, written = cell.spread(grow, heads) * dy, leaves * x
        db = db + cell.dot_nt(written, dstate[:, lanes])
        dc = dc + cell.dot_nt(read_state, state[:, lanes])
        dstate_ref[0, 0, :, lanes] = cell.spread(through, heads) * dstate[:, lanes] + cell.dot_tn(c, read_state)
    # every head's per-position gradients at once: delta's own, and the log-decay's (the last position's holds what
    # the state's decay over the whole chunk and every position's decay to the chunk's end ask of cum_last)
    ddt = ddt + asked * decay_to_end
    dlast = jnp.sum(asked * to_end, axis=0, keepdims=True) + through * kept_state
    dcum = read * grow - ddt * dt + jnp.where(jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1, dlast, 0.0)
    drows_ref[0, 0] = pulled_rows + jnp.concatenate([ddt.T, dcum.T], axis=0)
    db_ref[0] = (db + cell.dot(dcb_t, c)).astype(db_ref.dtype)
    dc_ref[0] = (dc + cell.dot_tn(dcb_t, b)).astype(dc_ref.dtype)


def _specs(x, dt, dims, q, cd, walk):
    """``(the cell, the grid, the block specs both kernels share)`` for ``x
    [B, T, H P]``, ``dt [B, T, H]`` and ``dims = (G, N)``, the chunk axis read
    through ``walk`` (the backward's runs last to first)."""
    (batch, t, inner), heads, (groups, n) = x.shape, dt.shape[2], dims
    r, p, nc = heads // groups, inner // heads, t // q

    def tokens(width):  # [B, T, G x width] a chunk of a group at a time
        return pl.BlockSpec((1, q, width), lambda i, g, z: (i, walk(z), g), memory_space=pltpu.VMEM)

    rows = pl.BlockSpec((1, 1, 2 * r, q), lambda i, g, z: (i, g, 0, walk(z)), memory_space=pltpu.VMEM)
    carried = pl.BlockSpec((1, 1, n, r * p), lambda i, g, z: (i, g, 0, 0), memory_space=pltpu.VMEM)
    starts = pl.BlockSpec((1, 1, 1, n, r * p), lambda i, g, z: (i, walk(z), g, 0, 0), memory_space=pltpu.VMEM)
    return _Cell(q, r, p, cd), (batch, groups, nc), tokens, rows, carried, starts


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"))


def _rows(dt, a, groups: int, q: int):
    """The step sizes above the cumulative log-decays of their chunk, heads by
    group, positions along the lanes: ``[B, G, 2 r, T]``."""
    batch, t, heads = dt.shape
    both = jnp.stack([dt, _chunk_sums(dt * a, q)], axis=2).reshape(batch, t, 2, groups, heads // groups)
    return jnp.transpose(both, (0, 3, 2, 4, 1)).reshape(batch, groups, -1, t)


def _chunk_sums(m, q: int, reverse: bool = False):
    """``m [B, T, H]`` summed from its chunk's start to each position (or, in
    ``reverse``, from each position to its chunk's end): float32, one matmul."""
    batch, t, heads = m.shape
    upto = jnp.tril(jnp.ones((q, q), jnp.float32))  # [to, from]
    return jnp.einsum("kq,bcqh->bckh", upto.T if reverse else upto, m.reshape(batch, t // q, q, heads),
                      precision=_HIGHEST).reshape(batch, t, heads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _scan(x, dt, a, b, c, dims, q, cd, interpret):
    return _scan_fwd(x, dt, a, b, c, dims, q, cd, interpret, save=False)[0]


def _scan_fwd(x, dt, a, b, c, dims, q, cd, interpret, save=True):
    """``x [B, T, H P]``, ``dt [B, T, H]`` and ``a [H]`` float32, ``b``, ``c``
    ``[B, T, G N]``; ``dims`` is ``(G, N)``."""
    cell, grid, tokens, rows_spec, carried, starts = _specs(x, dt, dims, q, cd, lambda z: z)
    (batch, groups, nc), n, width, f32 = grid, dims[1], cell.r * cell.p, jnp.float32
    rows = _rows(dt, a, groups, q)
    out_shape = [jax.ShapeDtypeStruct(x.shape, f32), jax.ShapeDtypeStruct((batch, groups, n, width), f32)]
    out_specs = [tokens(width), carried]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((batch, nc, groups, n, width), f32))
        out_specs.append(starts)
    y, _, *saved = pl.pallas_call(
        functools.partial(_fwd_kernel, cell, save),
        grid=grid,
        in_specs=[tokens(width), tokens(n), tokens(n), rows_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=_PARAMS,
        name="ssd_scan_fwd",
    )(x, b, c, rows)
    return y, (x, dt, a, b, c, rows, *saved)


def _scan_bwd(dims, q, cd, interpret, res, dy):
    x, dt, a, b, c, rows, state_starts = res
    nc = x.shape[1] // q
    cell, grid, tokens, rows_spec, carried, starts = _specs(x, dt, dims, q, cd, lambda z: nc - 1 - z)
    (batch, groups, _), n, width, f32 = grid, dims[1], cell.r * cell.p, jnp.float32
    dx, db, dc, drows, _ = pl.pallas_call(
        functools.partial(_bwd_kernel, cell),
        grid=grid,
        in_specs=[tokens(width), tokens(n), tokens(n), rows_spec, starts, tokens(width)],
        out_specs=[tokens(width), tokens(n), tokens(n), rows_spec, carried],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype), jax.ShapeDtypeStruct(rows.shape, f32),
                   jax.ShapeDtypeStruct((batch, groups, n, width), f32)],
        interpret=interpret,
        compiler_params=_PARAMS,
        name="ssd_scan_bwd",
    )(x, b, c, rows, state_starts, dy)
    # back to [B, T, H]: the step sizes' own gradient and the log-decays'
    _, t, heads = dt.shape
    ddt, dcum = jnp.moveaxis(jnp.transpose(drows.reshape(batch, groups, 2, heads // groups, t), (0, 4, 2, 1, 3))
                             .reshape(batch, t, 2, heads), 2, 0)
    dda = _chunk_sums(dcum, q, reverse=True)  # cum_i sums delta A over j <= i
    return dx, (ddt + dda * a).astype(dt.dtype), jnp.sum(dda * dt, axis=(0, 1)).astype(a.dtype), db, dc


_scan.defvjp(_scan_fwd, _scan_bwd)


def scan_kernel_chunks(batch: int, t: int, heads: int, groups: int, chunk: int) -> int:
    """The (chunk, head) pairs one walk of either kernel covers: its grid's
    cells times the heads a cell takes."""
    return batch * groups * (t // chunk) * (heads // groups)


def ssd_scan(x, dt, a, b, c, chunk: int, compute_dtype=jnp.float32):
    """``y [B, T, H, P]`` float32 of the recurrence above through chunks of
    ``chunk`` positions (``T`` a multiple of it); arguments as
    ``reference_scan``'s, ``compute_dtype`` the matmuls' input type.
    Differentiable in all five (the backward is the second kernel)."""
    batch, t, heads, p = x.shape
    groups, n = b.shape[2:]
    if t % chunk or heads % groups:
        raise ValueError(f"the scan takes whole chunks of {chunk} positions and whole groups of heads; got "
                         f"T {t}, {heads} heads in {groups} groups")
    interpret = _interpreted()
    r = heads // groups
    if not interpret and (chunk % _LANES or (groups > 1 and (n % _LANES or r * p % _LANES))):
        raise ValueError(f"on the TPU the scan's kernels take chunks of a multiple of {_LANES} positions and, of "
                         f"several groups, states and head groups of a multiple of {_LANES} lanes; got chunk {chunk}, "
                         f"state {n}, {r} heads of {p} a group")
    f32 = jnp.float32
    y = _scan(x.reshape(batch, t, heads * p), dt.astype(f32), a.astype(f32), b.reshape(batch, t, groups * n),
              c.reshape(batch, t, groups * n), (groups, n), chunk, jnp.dtype(compute_dtype).name, interpret)
    return y.reshape(batch, t, heads, p)
