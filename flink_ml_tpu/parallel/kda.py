"""The gated delta rule - with a decay of its own on every key channel (Kimi
Delta Attention, arXiv:2510.26692 section 3; ``models/lm``'s ``solar_open2``
block kind) or with ONE decay a head (Gated DeltaNet, arXiv:2412.06464 section
3; the ``olmo_hybrid`` block kind: "One decay a head" below) - in its chunked
form as a pair of Pallas kernels, and the plain step-by-step recurrence it has
to agree with. ``parallel/ssd.py`` is its
sibling: there the decay is one scalar a head and the state takes a plain sum;
here the decay is a vector over the key channels and the state takes a
delta-rule correction, so inside a chunk there is a triangular system to solve
and no scalar to pull out of the contraction.

No analogue exists in the reference (its models are single coefficient
vectors). The recurrence, per sequence and head (``D_k`` key channels, ``D_v``
value channels, a state ``S [D_k, D_v]``)::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_(t-1) + beta_t k_t v_t^T        S_(-1) = 0
    o_t = S_t^T q_t

``g <= 0`` is the log-decay of each key channel, ``beta`` in (0, 2) the
strength of the correction (past 1 the transition has negative eigenvalues);
the normalisation of ``q`` and ``k``, the gates and the output norm are the
caller's. ``reference_delta`` is that loop, one position at a time, in float32.

``kda_scan`` computes the same ``o`` from chunks of ``chunk`` positions. With
``G_i`` the running sum of ``g`` inside the chunk, ``S_0`` the state the chunk
starts from, ``kb = beta k`` and ``vb = beta v`` (made outside the kernels, so
that no per-position scalar has to be laid along the sublanes inside them)::

    A_ij = sum_c kb_ic k_jc exp(G_ic - G_jc)   (j < i)      P_ij = sum_c q_ic k_jc exp(G_ic - G_jc)   (j <= i)
    W = (I + A)^-1 (vb - (kb * e^G) S_0)                    the chunk's corrected values, one row a position
    o = (q * e^G) S_0 + P W
    S_C = Diag(e^(G_C)) S_0 + (k * e^(G_C - G))^T W

**No exponential of a long positive sum.** ``exp(G_i - G_j)`` does not factor
into ``exp(G_i) exp(-G_j)`` safely: at the initialiser's strongest decay (1.6 a
position) ``exp(-G_j)`` passes float32's range inside one 64-position chunk. A
chunk is cut into SUB-CHUNKS of ``_SUB`` positions. The rows of sub-chunk ``I``
take their reference ``R_I`` at its middle position: the rows' factor is
``exp(G_i - R_I)``, the columns' ``exp(R_I - G_j)`` for ``j`` up to the
sub-chunk's end and 0 past it (those pairs are masked anyway). A column before
the sub-chunk has ``G_j >= R_I`` (a factor at most 1), and inside the sub-chunk
either exponent spans at most half of it: ``exp`` of 13 at the strongest
initial decay. Both factors ride the matmul's inputs, one ``[2 sub, D_k] x
[D_k, chunk]`` matmul a sub-chunk gives that sub-chunk's rows of ``A`` and
``P``.

**The solve.** ``(I + A)^-1`` by blockwise inversion, in float32 at the
highest precision: the inverse of the 1 x 1 diagonal blocks is 1; of a block
``[[X, 0], [C, Y]]`` it is ``[[X^-1, 0], [-Y^-1 C X^-1, Y^-1]]``, so each
doubling of the block size is ``T <- T - T (A masked to the blocks' lower left
quarters) T`` on the whole ``[chunk, chunk]`` matrix: ``log2(chunk)`` steps of
two small matmuls, the recursive form of forward substitution (a product of
powers of ``A`` would cancel catastrophically where neighbouring keys are
alike and ``beta`` is near 2).

**What lives in VMEM.** A grid cell is one chunk of one head of one sequence:
the chunk's ``q``, ``k``, ``kb``, ``vb`` and ``G`` (``[chunk, D]`` each), the
``[chunk, chunk]`` matrices and the head's state. The state is carried
TRANSPOSED (``[D_v, D_k]``), so that a decay over the key channels is a row
laid along the lanes, in an output block whose index does not move along the
chunk axis, the grid's last and sequential one (no scratch:
``flash.py::_fold_tiles`` says why).

**The two walks.** ``kda_scan_fwd`` walks a sequence's chunks first to last
carrying ``S``; under differentiation it also writes the state every chunk
STARTS from (``[B, T / chunk, H, D_v, D_k]`` float32). ``kda_scan_bwd`` walks
them last to first carrying ``dS``; it recomputes the chunk's matrices and its
solve from the same inputs (no ``[chunk, chunk]`` residual is saved) and emits
``dq``, ``dk``, ``d kb``, ``d vb`` and ``dG``. Around the kernels, in
``jax.numpy``, differentiated by JAX: ``kb`` and ``vb`` (whence ``d beta`` and
the rest of ``dk``, ``dv``) and the cumulative sum inside a chunk (one matmul
with a triangle of ones, float32 at the highest precision; its transpose gives
``dg``).

**One decay a head** (``g [B, T, H]``: the same recurrence with ``g`` alike on
a head's key channels). The scalar leaves the contraction: ``A = (kb k^T) *
Delta`` and ``P = (q k^T) * Delta`` with ONE ``[chunk, chunk]`` factor
``Delta_ij = exp(G_i - G_j)``, the ``exp`` of a masked difference that is never
positive: no sub-chunks, no references, no ``[chunk, D]`` exponentials, one
``[2 chunk, D_k] x [D_k, chunk]`` matmul a chunk for both products. The solve,
the state's terms and both walks are the per-channel form's, line for line
(``_Cell.chunk`` and the kernels' bodies are shared; ``_Cell.pairs`` and
``pairs_grads`` branch); ``dG`` is the per-channel expression summed over the
lanes. Heads need not be square nor tile a lane (Olmo-Hybrid's 96 key and 192
value channels; 15 heads x 96 = 1,440 flat channels tile none either), so the
arrays are HEAD-MAJOR, ``[B, H, T, D]``: a head's channels are a block's whole
last dimension, which Mosaic takes at any width. The chunk sums reach a cell
as ``[1, chunk]`` rows a head (``[B, H, T / chunk, 1, chunk]``); the column
the state's terms want is that row turned through the diagonal of a ``[chunk,
chunk]`` tile (``_Cell.turned``). A cell takes ``_HEADS_A_CELL`` heads: a cell
is latency, not work, and the heads' chains interleave.

Precision, the configuration's: the log-decays, their sums, every ``exp``, the
solve, the carried state and ``dS`` are float32 whatever the compute type; the
other matmuls take their inputs in the compute type (``bfloat16``: the MXU's
path; float32 at the highest precision otherwise) and accumulate in float32.

Compiled by Mosaic on a TPU backend, interpreted elsewhere (the CPU mesh of
the tests), decided here from the backend. On the TPU a chunk has to tile
the sublanes and, under a decay a key channel (token-major arrays, a head cut
out of the lanes), a head's channels the 128 lanes; a shape that does not is
refused, there is no other path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flink_ml_tpu.parallel.mesh import is_tpu_backend
from flink_ml_tpu.parallel.ssd import _chunk_sums  # a sum from a chunk's start to each position: one matmul

__all__ = ["kda_scan", "reference_delta", "kda_kernel_chunks"]

_HIGHEST = jax.lax.Precision.HIGHEST
_LANES = 128
#: Positions of a sub-chunk: the span over which a decay's exponent may be positive (half of it, either way).
_SUB = 16
#: Heads a cell of the one-decay form: a cell is latency, not work (a chain of small matmuls and a solve), and
#: heads are independent, so a cell's heads fill each other's waits: 15 heads of 96 x 192 at T 8,192, chunk 64, forward
#: and backward 14.16 ms a layer at one head a cell, 13.39 at three, 13.19 at five (chip runs, PERF.md PR 54); fifteen
#: pass the kernels' 16 MB of scoped VMEM.
_HEADS_A_CELL = 5
FWD_NAME, BWD_NAME = "kda_scan_fwd", "kda_scan_bwd"


def reference_delta(q, k, v, g, beta):
    """``o [B, T, H, D_v]`` float32 of the recurrence above, one position at a
    time: ``q``, ``k``, ``g`` ``[B, T, H, D_k]``, ``v [B, T, H, D_v]``, ``beta
    [B, T, H]``."""
    f32 = jnp.float32
    q, k, v, g, beta = (m.astype(f32) for m in (q, k, v, g, beta))

    def sequence(q, k, v, g, beta):
        def position(state, now):  # state [H, D_k, D_v]
            q_t, k_t, v_t, g_t, beta_t = now
            state = jnp.exp(g_t)[:, :, None] * state
            seen = jnp.einsum("hkv,hk->hv", state, k_t, precision=_HIGHEST)  # what the state already says of k_t
            state = state + (beta_t[:, None] * k_t)[:, :, None] * (v_t - seen)[:, None, :]
            return state, jnp.einsum("hkv,hk->hv", state, q_t, precision=_HIGHEST)

        _, o = jax.lax.scan(position, jnp.zeros(k.shape[1:] + v.shape[-1:], f32), (q, k, v, g, beta))
        return o

    return jax.vmap(sequence)(q, k, v, g, beta)


def _interpreted() -> bool:
    """Off the TPU the kernels run under the Pallas interpreter."""
    return not is_tpu_backend(jax.devices())


class _Cell:
    """What both kernels compute alike on one (sequence, head, chunk) cell.
    ``heads`` 0: a decay a key channel, a cell one head of token-major arrays
    (``[B, T, H D]``); ``heads`` > 0: ONE decay a head, a cell that many heads
    of head-major arrays (``[B, H, T, D]``), the decays a row a head."""

    def __init__(self, q: int, cd, heads: int = 0):
        self.q, self.sub, self.cd, self.heads = q, min(_SUB, q), jnp.dtype(cd), heads
        self.precision = _HIGHEST if self.cd == jnp.float32 else None

    def tok(self, j: int):
        """Where head ``j`` of the cell lies in a token array's block."""
        return (0, j) if self.heads else (0,)

    def turned(self, x):
        """A row ``[1, q]`` as a column ``[q, 1]`` or the other way, through the diagonal of a ``[q, q]`` tile."""
        diagonal = (jax.lax.broadcasted_iota(jnp.int32, (self.q, self.q), 0)
                    == jax.lax.broadcasted_iota(jnp.int32, (self.q, self.q), 1))
        return jnp.sum(jnp.where(diagonal, x, 0.0), axis=int(x.shape[0] == 1), keepdims=True)

    def factor(self, g):
        """One decay a head: ``exp(G_i - G_j)`` over the pairs ``j <= i`` (never past 1) and 0 elsewhere, ``[q, q]``,
        from the column ``g [q, 1]``."""
        _, upto = self.ordered()
        return jnp.exp(jnp.where(upto, g - self.turned(g), -jnp.inf))

    def decays(self, g_ref, j: int):
        """Head ``j``'s cumulative log-decays as ``chunk`` takes them: ``[q, D_k]``, or of one decay a head the
        column ``[q, 1]``."""
        return self.turned(g_ref[0, j, 0]) if self.heads else g_ref[0]

    def dot(self, lhs, rhs, contract=((1,), (0,))):
        """``lhs @ rhs`` (or the contraction named) on compute-type inputs into float32."""
        return jax.lax.dot_general(lhs.astype(self.cd), rhs.astype(self.cd), (contract, ((), ())),
                                   preferred_element_type=jnp.float32, precision=self.precision)

    def dot_nt(self, lhs, rhs):  # lhs @ rhs^T
        return self.dot(lhs, rhs, ((1,), (1,)))

    def dot_tn(self, lhs, rhs):  # lhs^T @ rhs
        return self.dot(lhs, rhs, ((0,), (0,)))

    @staticmethod
    def exact(lhs, rhs, contract=((1,), (0,))):
        """The same in float32 at the highest precision: the solve's."""
        return jax.lax.dot_general(lhs, rhs, (contract, ((), ())), preferred_element_type=jnp.float32,
                                   precision=_HIGHEST)

    def sub_chunks(self, g):
        """``(rows, exp(G_rows - R), exp(R - G) over every column up to the
        sub-chunk's end and 0 past it)`` a sub-chunk, ``R`` the sub-chunk's
        middle row of ``g [q, D]``."""
        position = jax.lax.broadcasted_iota(jnp.int32, (self.q, 1), 0)
        for lo in range(0, self.q, self.sub):
            hi = lo + self.sub
            ref = g[lo + self.sub // 2: lo + self.sub // 2 + 1]
            yield slice(lo, hi), jnp.exp(g[lo: hi] - ref), jnp.exp(jnp.where(position < hi, ref - g, -jnp.inf))

    def ordered(self):
        """``(i > j, i >= j)`` over a chunk's ``[i, j]`` pairs."""
        i = jax.lax.broadcasted_iota(jnp.int32, (self.q, self.q), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (self.q, self.q), 1)
        return i > j, i >= j

    def pairs(self, q, k, kb, g):
        """``(A, P)``: the masked pair matrices ``[i, j]`` of ``kb`` and of ``q`` on ``k`` under the decays."""
        before, upto = self.ordered()
        if self.heads:  # one decay a head: one [q, q] factor on both products
            both = self.dot_nt(jnp.concatenate([kb, q], axis=0), k)  # [2 q, q]
            decay = self.factor(g)
            return jnp.where(before, both[: self.q] * decay, 0.0), both[self.q:] * decay
        a, p = [], []
        for rows, rise, fall in self.sub_chunks(g):
            both = self.dot_nt(jnp.concatenate([kb[rows] * rise, q[rows] * rise], axis=0), k * fall)  # [2 sub, q]
            a.append(both[: self.sub])
            p.append(both[self.sub:])
        return jnp.where(before, jnp.concatenate(a, axis=0), 0.0), jnp.where(upto, jnp.concatenate(p, axis=0), 0.0)

    def pairs_grads(self, q, k, kb, g, da, dp):
        """What ``(dA, dP)`` (masked) ask of the pair matrices' own inputs: ``(dq, d kb, dk)``, made as ``pairs``
        made them."""
        if self.heads:
            decay = self.factor(g)
            asked = jnp.concatenate([da * decay, dp * decay], axis=0)  # [2 q, q]
            dleft = self.dot(asked, k)
            return dleft[self.q:], dleft[: self.q], self.dot_tn(asked, jnp.concatenate([kb, q], axis=0))
        dq, dkb, dk = [], [], jnp.zeros(k.shape, jnp.float32)
        for rows, rise, fall in self.sub_chunks(g):
            asked = jnp.concatenate([da[rows], dp[rows]], axis=0)  # [2 sub, q]
            left = jnp.concatenate([kb[rows] * rise, q[rows] * rise], axis=0)
            dleft = self.dot(asked, k * fall)
            dkb.append(dleft[: self.sub] * rise)
            dq.append(dleft[self.sub:] * rise)
            dk = dk + self.dot_tn(asked, left) * fall
        return jnp.concatenate(dq, axis=0), jnp.concatenate(dkb, axis=0), dk

    def inverse(self, a):
        """``(I + a)^-1`` of a strictly lower-triangular ``a [q, q]``, block size by block size."""
        i = jax.lax.broadcasted_iota(jnp.int32, (self.q, self.q), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (self.q, self.q), 1)
        t = jnp.where(i == j, 1.0, 0.0).astype(jnp.float32)
        bit = 0
        while (1 << bit) < self.q:
            # the lower left quarter of every diagonal block of twice the size solved so far
            quarter = ((jnp.right_shift(i, bit + 1) == jnp.right_shift(j, bit + 1))
                       & (jnp.right_shift(i, bit) != jnp.right_shift(j, bit)) & (i > j))
            corner = jnp.where(quarter, a, 0.0)
            t = t - (corner if bit == 0 else self.exact(t, self.exact(corner, t)))
            bit += 1
        return t

    def chunk(self, q, k, kb, vb, g, state):
        """What a chunk is made of, from its inputs and the (transposed) state
        it starts from: ``(P, T, W, e^G, e^(G_C - G), e^(G_C))``."""
        a, p = self.pairs(q, k, kb, g)
        t = self.inverse(a)
        grow = jnp.exp(g)
        last = g[self.q - 1:]
        w = self.exact(t, vb - self.dot_nt(kb * grow, state))
        return p, t, w, grow, jnp.exp(last - g), jnp.exp(last)


def _fwd_kernel(cell: _Cell, save: bool, q_ref, k_ref, kb_ref, vb_ref, g_ref, o_ref, state_ref, *starts_ref):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    for j in range(max(cell.heads, 1)):  # a cell's heads are independent: their chains interleave
        tok = cell.tok(j)
        state = state_ref[0, j]  # [D_v, D_k]: what this chunk starts from, transposed
        if save:
            starts_ref[0][0, 0, j] = state
        q, k = q_ref[tok], k_ref[tok]
        p, _, w, grow, to_end, through = cell.chunk(q, k, kb_ref[tok], vb_ref[tok], cell.decays(g_ref, j), state)
        o_ref[tok] = cell.dot_nt(q * grow, state) + cell.dot(p, w)
        state_ref[0, j] = through * state + cell.dot_tn(w, k * to_end)


def _bwd_kernel(cell: _Cell, q_ref, k_ref, kb_ref, vb_ref, g_ref, starts_ref, do_ref,
                dq_ref, dk_ref, dkb_ref, dvb_ref, dg_ref, dstate_ref):
    n = cell.q

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    for j in range(max(cell.heads, 1)):
        tok = cell.tok(j)
        dstate = dstate_ref[0, j]  # [D_v, D_k]: the gradient of the (transposed) state this chunk ENDS with
        state = starts_ref[0, 0, j]  # the state it started from
        q, k, kb, vb, g, do = q_ref[tok], k_ref[tok], kb_ref[tok], vb_ref[tok], cell.decays(g_ref, j), do_ref[tok]
        p, t, w, grow, to_end, through = cell.chunk(q, k, kb, vb, g, state)
        q_in, kb_in, k_end = q * grow, kb * grow, k * to_end  # what reads the state, and what is written into it
        # o = q_in S + P W;  S' = through S + k_end^T W
        dw = cell.dot_tn(p, do) + cell.dot_nt(k_end, dstate)
        before, upto = cell.ordered()
        dp = jnp.where(upto, cell.dot_nt(do, w), 0.0)
        dq_in = cell.dot(do, state)
        dk_end = cell.dot(w, dstate)
        # W = T (vb - kb_in S),  T = (I + A)^-1
        drhs = cell.exact(t, dw, ((0,), (0,)))
        da = jnp.where(before, -cell.dot_nt(drhs, w), 0.0)
        dkb_in = -cell.dot(drhs, state)
        dstate_ref[0, j] = through * dstate + cell.dot_tn(do, q_in) - cell.dot_tn(drhs, kb_in)
        dq_pairs, dkb_pairs, dk = cell.pairs_grads(q, k, kb, g, da, dp)
        # the log-decays: each exponent's own gradient is its factor's times the factor; one decay a head takes the
        # sum over its channels
        dlast = (jnp.sum(dk_end * k_end, axis=0, keepdims=True)
                 + through * jnp.sum(state * dstate, axis=0, keepdims=True))
        dg = (kb * dkb_pairs + q * dq_pairs - k * dk + dq_in * q_in + dkb_in * kb_in - dk_end * k_end
              + jnp.where(jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0) == n - 1, dlast, 0.0))
        dq_ref[tok] = dq_pairs + dq_in * grow
        dk_ref[tok] = dk + dk_end * to_end
        dkb_ref[tok] = dkb_pairs + dkb_in * grow
        dvb_ref[tok] = drhs
        if cell.heads:
            dg_ref[0, j, 0] = cell.turned(jnp.sum(dg, axis=1, keepdims=True))
        else:
            dg_ref[0] = dg


def _specs(dims, q: int, per_cell: int, walk):
    """``(the grid, a token array's block spec by its width, the decays', the
    carried state's, the chunk starts')`` for ``dims = (B, T, H, D_k, D_v)``,
    the chunk axis read through ``walk`` (the backward's runs last to first).
    ``per_cell`` 0: a decay a key channel on token-major arrays ``[B, T, H D]``;
    else one decay a head, ``per_cell`` heads a cell of head-major arrays ``[B,
    H, T, D]``, the decays ``[B, H, T / q, 1, q]``."""
    batch, t, heads, dk, dv = dims
    vmem = pltpu.VMEM
    if per_cell:
        def tokens(d):
            return pl.BlockSpec((1, per_cell, q, d), lambda i, h, z: (i, h, walk(z), 0), memory_space=vmem)

        decays = pl.BlockSpec((1, per_cell, 1, 1, q), lambda i, h, z: (i, h, walk(z), 0, 0), memory_space=vmem)
    else:
        def tokens(d):
            return pl.BlockSpec((1, q, d), lambda i, h, z: (i, walk(z), h), memory_space=vmem)

        decays = tokens(dk)
    n = per_cell or 1
    carried = pl.BlockSpec((1, n, dv, dk), lambda i, h, z: (i, h, 0, 0), memory_space=vmem)
    starts = pl.BlockSpec((1, 1, n, dv, dk), lambda i, h, z: (i, walk(z), h, 0, 0), memory_space=vmem)
    return (batch, heads // n, t // q), tokens, decays, carried, starts


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _scan(q, k, kb, vb, g, dims, chunk, per_cell, cd, interpret):
    return _scan_fwd(q, k, kb, vb, g, dims, chunk, per_cell, cd, interpret, save=False)[0]


def _scan_fwd(q, k, kb, vb, g, dims, chunk, per_cell, cd, interpret, save=True):
    """``q``, ``k``, ``kb``, ``vb`` and the cumulative log-decays ``g``, float32, laid out as ``_specs`` says."""
    grid, tokens, decays, carried, starts = _specs(dims, chunk, per_cell, lambda z: z)
    (batch, _, heads, dk, dv), f32 = dims, jnp.float32
    out_shape = [jax.ShapeDtypeStruct(vb.shape, f32), jax.ShapeDtypeStruct((batch, heads, dv, dk), f32)]
    out_specs = [tokens(dv), carried]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((batch, grid[2], heads, dv, dk), f32))
        out_specs.append(starts)
    o, _, *saved = pl.pallas_call(
        functools.partial(_fwd_kernel, _Cell(chunk, cd, per_cell), save),
        grid=grid,
        in_specs=[tokens(dk)] * 3 + [tokens(dv), decays],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=_PARAMS,
        name=FWD_NAME,
    )(q, k, kb, vb, g)
    return o, (q, k, kb, vb, g, *saved)


def _scan_bwd(dims, chunk, per_cell, cd, interpret, res, do):
    q, k, kb, vb, g, state_starts = res
    nc = dims[1] // chunk
    grid, tokens, decays, carried, starts = _specs(dims, chunk, per_cell, lambda z: nc - 1 - z)
    (batch, _, heads, dk, dv), f32 = dims, jnp.float32
    *grads, _ = pl.pallas_call(
        functools.partial(_bwd_kernel, _Cell(chunk, cd, per_cell)),
        grid=grid,
        in_specs=[tokens(dk)] * 3 + [tokens(dv), decays, starts, tokens(dv)],
        out_specs=[tokens(dk)] * 3 + [tokens(dv), decays, carried],
        out_shape=[jax.ShapeDtypeStruct(m.shape, f32) for m in (q, k, kb, vb, g)]
        + [jax.ShapeDtypeStruct((batch, heads, dv, dk), f32)],
        interpret=interpret,
        compiler_params=_PARAMS,
        name=BWD_NAME,
    )(q, k, kb, vb, g, state_starts, do.astype(f32))
    return tuple(grads)


_scan.defvjp(_scan_fwd, _scan_bwd)


def kda_kernel_chunks(batch: int, t: int, heads: int, chunk: int) -> int:
    """The (chunk, head) pairs one walk of either kernel covers: its grid's cells times the heads a cell takes."""
    return batch * heads * (t // chunk)


def _heads_a_cell(heads: int) -> int:
    """How many heads a cell of the one-decay form takes: the largest divisor of ``heads`` up to ``_HEADS_A_CELL``."""
    return max(n for n in range(1, _HEADS_A_CELL + 1) if heads % n == 0)


def kda_scan(q, k, v, g, beta, chunk: int, compute_dtype=jnp.float32):
    """``o [B, T, H, D_v]`` float32 of the recurrence above through chunks of
    ``chunk`` positions (``T`` a multiple of it); arguments as
    ``reference_delta``'s, or ``g [B, T, H]``: ONE log-decay a head and
    position, which takes the one-decay form; ``compute_dtype`` the matmuls'
    input type. Differentiable in all five (the backward is the second
    kernel)."""
    batch, t, heads, dk = q.shape
    dv = v.shape[-1]
    per_head = g.ndim == 3
    if (t % chunk or chunk & (chunk - 1) or k.shape != q.shape or v.shape[:3] != q.shape[:3]
            or g.shape != (q.shape[:3] if per_head else q.shape) or beta.shape != q.shape[:3]):
        raise ValueError(f"the delta rule takes whole chunks of a power of two of positions, keys as the queries, "
                         f"values a head and position, a log-decay a key channel or a head, a strength a head; got "
                         f"T {t}, chunk {chunk}, queries {q.shape}, keys {k.shape}, values {v.shape}, "
                         f"log-decays {g.shape}, strengths {beta.shape}")
    interpret = _interpreted()
    if not interpret and (chunk % _SUB or (not per_head and (dk % _LANES or dv % _LANES))):
        raise ValueError(f"on the TPU the delta rule's kernels take chunks of a multiple of {_SUB} positions and, "
                         f"under a decay a key channel, heads of a multiple of {_LANES} channels; got chunk {chunk}, "
                         f"{dk} key and {dv} value channels")
    f32 = jnp.float32
    q, k, v, g = (m.astype(f32) for m in (q, k, v, g))
    scale = beta.astype(f32)[..., None]
    dims, cd = (batch, t, heads, dk, dv), jnp.dtype(compute_dtype).name
    if per_head:  # head-major: a head's channels are a block's whole last dimension, whatever their count
        lead = lambda m: jnp.transpose(m, (0, 2, 1, 3))  # noqa: E731
        rows = jnp.transpose(_chunk_sums(g, chunk), (0, 2, 1)).reshape(batch, heads, t // chunk, 1, chunk)
        o = _scan(lead(q), lead(k), lead(scale * k), lead(scale * v), rows, dims, chunk, _heads_a_cell(heads), cd,
                  interpret)
        return jnp.transpose(o, (0, 2, 1, 3))
    flat = lambda m: m.reshape(batch, t, -1)  # noqa: E731
    o = _scan(flat(q), flat(k), flat(scale * k), flat(scale * v), _chunk_sums(flat(g), chunk), dims, chunk, 0, cd,
              interpret)
    return o.reshape(batch, t, heads, dv)
