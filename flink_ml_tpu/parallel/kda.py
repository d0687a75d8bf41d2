"""The gated delta rule with a decay of its own on every key channel (Kimi
Delta Attention, arXiv:2510.26692 section 3; ``models/lm``'s ``solar_open2``
block kind) in its chunked form as a pair of Pallas kernels, and the plain
step-by-step recurrence it has to agree with. ``parallel/ssd.py`` is its
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

Precision, the configuration's: the log-decays, their sums, every ``exp``, the
solve, the carried state and ``dS`` are float32 whatever the compute type; the
other matmuls take their inputs in the compute type (``bfloat16``: the MXU's
path; float32 at the highest precision otherwise) and accumulate in float32.

Compiled by Mosaic on a TPU backend, interpreted elsewhere (the CPU mesh of
the tests), decided here from the backend. On the TPU a head's channels have
to tile the 128 lanes and a chunk the sublanes; a shape that does not is
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
    """What both kernels compute alike on one (sequence, head, chunk) cell."""

    def __init__(self, q: int, cd):
        self.q, self.sub, self.cd = q, min(_SUB, q), jnp.dtype(cd)
        self.precision = _HIGHEST if self.cd == jnp.float32 else None

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
        a, p = [], []
        for rows, rise, fall in self.sub_chunks(g):
            both = self.dot_nt(jnp.concatenate([kb[rows] * rise, q[rows] * rise], axis=0), k * fall)  # [2 sub, q]
            a.append(both[: self.sub])
            p.append(both[self.sub:])
        before, upto = self.ordered()
        return jnp.where(before, jnp.concatenate(a, axis=0), 0.0), jnp.where(upto, jnp.concatenate(p, axis=0), 0.0)

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

    state = state_ref[0, 0]  # [D_v, D_k]: what this chunk starts from, transposed
    if save:
        starts_ref[0][0, 0, 0] = state
    q, k = q_ref[0], k_ref[0]
    p, _, w, grow, to_end, through = cell.chunk(q, k, kb_ref[0], vb_ref[0], g_ref[0], state)
    o_ref[0] = cell.dot_nt(q * grow, state) + cell.dot(p, w)
    state_ref[0, 0] = through * state + cell.dot_tn(w, k * to_end)


def _bwd_kernel(cell: _Cell, q_ref, k_ref, kb_ref, vb_ref, g_ref, starts_ref, do_ref,
                dq_ref, dk_ref, dkb_ref, dvb_ref, dg_ref, dstate_ref):
    f32, n = jnp.float32, cell.q

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    dstate = dstate_ref[0, 0]  # [D_v, D_k]: the gradient of the (transposed) state this chunk ENDS with
    state = starts_ref[0, 0, 0]  # the state it started from
    q, k, kb, vb, g, do = q_ref[0], k_ref[0], kb_ref[0], vb_ref[0], g_ref[0], do_ref[0]
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
    dstate_ref[0, 0] = through * dstate + cell.dot_tn(do, q_in) - cell.dot_tn(drhs, kb_in)
    # the pair matrices' own inputs, sub-chunk by sub-chunk as they were made
    dq, dkb, dk = [], [], jnp.zeros(k.shape, f32)
    for rows, rise, fall in cell.sub_chunks(g):
        asked = jnp.concatenate([da[rows], dp[rows]], axis=0)  # [2 sub, q]
        left = jnp.concatenate([kb[rows] * rise, q[rows] * rise], axis=0)
        dleft = cell.dot(asked, k * fall)
        dkb.append(dleft[: cell.sub] * rise)
        dq.append(dleft[cell.sub:] * rise)
        dk = dk + cell.dot_tn(asked, left) * fall
    dq_pairs, dkb_pairs = jnp.concatenate(dq, axis=0), jnp.concatenate(dkb, axis=0)
    # the log-decays: each exponent's own gradient is its factor's times the factor
    dlast = (jnp.sum(dk_end * k_end, axis=0, keepdims=True)
             + through * jnp.sum(state * dstate, axis=0, keepdims=True))
    dg = (kb * dkb_pairs + q * dq_pairs - k * dk + dq_in * q_in + dkb_in * kb_in - dk_end * k_end
          + jnp.where(jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0) == n - 1, dlast, 0.0))
    dq_ref[0] = dq_pairs + dq_in * grow
    dk_ref[0] = dk + dk_end * to_end
    dkb_ref[0] = dkb_pairs + dkb_in * grow
    dvb_ref[0] = drhs
    dg_ref[0] = dg


def _specs(shape, heads: int, q: int, walk):
    """``(the grid, a token array's block spec, the carried state's, the chunk
    starts')`` for arrays ``[B, T, H D]``, the chunk axis read through ``walk``
    (the backward's runs last to first)."""
    batch, t, width = shape
    d = width // heads
    tokens = pl.BlockSpec((1, q, d), lambda i, h, z: (i, walk(z), h), memory_space=pltpu.VMEM)
    carried = pl.BlockSpec((1, 1, d, d), lambda i, h, z: (i, h, 0, 0), memory_space=pltpu.VMEM)
    starts = pl.BlockSpec((1, 1, 1, d, d), lambda i, h, z: (i, walk(z), h, 0, 0), memory_space=pltpu.VMEM)
    return (batch, heads, t // q), tokens, carried, starts


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _scan(q, k, kb, vb, g, heads, chunk, cd, interpret):
    return _scan_fwd(q, k, kb, vb, g, heads, chunk, cd, interpret, save=False)[0]


def _scan_fwd(q, k, kb, vb, g, heads, chunk, cd, interpret, save=True):
    """``q``, ``k``, ``kb``, ``vb`` and the cumulative log-decays ``g``: ``[B, T, H D]`` float32."""
    (batch, _, nc), tokens, carried, starts = _specs(q.shape, heads, chunk, lambda z: z)
    d, f32 = q.shape[2] // heads, jnp.float32
    out_shape = [jax.ShapeDtypeStruct(q.shape, f32), jax.ShapeDtypeStruct((batch, heads, d, d), f32)]
    out_specs = [tokens, carried]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((batch, nc, heads, d, d), f32))
        out_specs.append(starts)
    o, _, *saved = pl.pallas_call(
        functools.partial(_fwd_kernel, _Cell(chunk, cd), save),
        grid=(batch, heads, nc),
        in_specs=[tokens] * 5,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=_PARAMS,
        name=FWD_NAME,
    )(q, k, kb, vb, g)
    return o, (q, k, kb, vb, g, *saved)


def _scan_bwd(heads, chunk, cd, interpret, res, do):
    q, k, kb, vb, g, state_starts = res
    nc = q.shape[1] // chunk
    (batch, _, _), tokens, carried, starts = _specs(q.shape, heads, chunk, lambda z: nc - 1 - z)
    d, f32 = q.shape[2] // heads, jnp.float32
    *grads, _ = pl.pallas_call(
        functools.partial(_bwd_kernel, _Cell(chunk, cd)),
        grid=(batch, heads, nc),
        in_specs=[tokens] * 5 + [starts, tokens],
        out_specs=[tokens] * 5 + [carried],
        out_shape=[jax.ShapeDtypeStruct(q.shape, f32)] * 5 + [jax.ShapeDtypeStruct((batch, heads, d, d), f32)],
        interpret=interpret,
        compiler_params=_PARAMS,
        name=BWD_NAME,
    )(q, k, kb, vb, g, state_starts, do.astype(f32))
    return tuple(grads)


_scan.defvjp(_scan_fwd, _scan_bwd)


def kda_kernel_chunks(batch: int, t: int, heads: int, chunk: int) -> int:
    """The (chunk, head) pairs one walk of either kernel covers: its grid's cells."""
    return batch * heads * (t // chunk)


def kda_scan(q, k, v, g, beta, chunk: int, compute_dtype=jnp.float32):
    """``o [B, T, H, D]`` float32 of the recurrence above through chunks of
    ``chunk`` positions (``T`` a multiple of it); arguments as
    ``reference_delta``'s with ``D_v = D_k``, ``compute_dtype`` the matmuls'
    input type. Differentiable in all five (the backward is the second
    kernel)."""
    batch, t, heads, d = q.shape
    if t % chunk or chunk & (chunk - 1) or v.shape != q.shape:
        raise ValueError(f"the delta rule takes whole chunks of a power of two of positions and values as wide as "
                         f"the keys; got T {t}, chunk {chunk}, keys {q.shape}, values {v.shape}")
    interpret = _interpreted()
    if not interpret and (d % _LANES or chunk % _SUB):
        raise ValueError(f"on the TPU the delta rule's kernels take heads of a multiple of {_LANES} channels and "
                         f"chunks of a multiple of {_SUB} positions; got {d} channels, chunk {chunk}")
    f32 = jnp.float32
    q, k, v, g = (m.astype(f32) for m in (q, k, v, g))
    scale = beta.astype(f32)[..., None]
    flat = lambda m: m.reshape(batch, t, heads * d)  # noqa: E731
    o = _scan(flat(q), flat(k), flat(scale * k), flat(scale * v), _chunk_sums(flat(g), chunk), heads, chunk,
              jnp.dtype(compute_dtype).name, interpret)
    return o.reshape(batch, t, heads, d)
