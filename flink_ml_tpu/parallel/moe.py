"""Dropless top-k mixture-of-experts feed-forward — the routed layer of a
decoder LM (``models/lm``).

No analogue exists in the reference (its models are single coefficient
vectors). One routing path, and no token is ever dropped:

- the router runs in float32 whatever the compute type: logits ``x @ router``,
  a softmax over ALL experts, the ``k`` largest probabilities chosen and used
  as they are (not renormalised - OLMoE's ``norm_topk_prob`` false) or, told
  so (``renormalise``: Qwen3-MoE's and SDAR's ``norm_topk_prob`` true),
  divided by their sum over ALL ``k`` chosen, held here or not: a row whose
  chosen experts are mostly absent keeps small gates, and the router's
  gradient flows through numerator and denominator;
- the ``tokens x k`` (token, expert) rows are sorted by expert (a stable
  argsort of the chosen expert ids), so each expert's rows are one contiguous
  group whose size is whatever the router made it - there is no capacity;
- the experts run as three grouped matmuls over those ragged groups
  (``jax.lax.ragged_dot``: SwiGLU, ``down(silu(gate(x)) * up(x))``), which
  XLA lowers for the TPU to its own grouped-matmul kernel (``ragged-dot`` on
  the device trace); the hand-written VJP recomputes the two hidden
  projections and runs its own grouped matmuls (rows against ``W^T``, and the
  ragged-contraction ``dW`` in f32), so neither direction holds a ``[tokens,
  experts, ...]`` tensor;
- rows return to token order by the inverse permutation and are summed over
  the ``k`` slots weighted by the router's probabilities. Both permutations
  are gathers in the forward AND in the backward (a custom VJP swaps the
  permutation for its inverse instead of transposing a gather to a scatter).

The router's statistics come back with the output, for the load-balancing
loss: ``f`` (per expert, the (token, slot) choices that fell on it over the
number of tokens - it sums to ``k``), ``P`` (the mean router probability) and
``rows`` (the group sizes; their sum is ``tokens x k``, always).

The layer names its three parts for a profiler with ``jax.named_scope``
(``route``, ``permute``, ``experts``: trace-time metadata on each operation's
name, nothing at run time). The names are relative: they nest under whatever
scope the caller opened (docs/observability.md, "The step's scopes").

The router is handed in: a matrix ``[d, E]`` (OLMoE's) or a function from the
tokens to their logits (ZAYA's MLP, closed over the state the block before
handed it); top-1 is ``k = 1``, the winner's probability the gate.

Sigmoid gates (``routed_scale`` > 0, ``route_sigmoid_top_k``: the family whose
router scores each expert by itself): ``s = sigmoid(logits)`` over all experts, the ``k`` largest
of ``s + select_bias`` chosen (the bias, a balancing rule's handle outside the
gradient, enters the choice and nothing else), and the chosen scores
renormalised to sum to one and scaled: ``w = routed_scale * s_sel /
sum(s_sel)``. A shared expert that every token passes beside the routed ones
is no part of this layer's routing: ``dense_swiglu`` computes it, and the
caller adds it once (each chip of an expert-parallel group computes it alike).

The expert function is what the layer is told: SwiGLU on three matrices, or,
told no gate matrix (``w_gate`` None), the non-gated ``down(relu(up(x))^2)``
on two (Nemotron-H's ``relu2``). Both go the one path below - the sort, the
held range, the windows and their written backward, ``dense_swiglu`` for a
shared expert - and a layer with a gate traces, operation for operation, what
it traced before the other existed.

The held range, what expert parallelism asks of this layer anyway: told which
experts it holds (``w_gate``/``w_up``/``w_down`` carry ``H`` experts,
``first_held .. first_held + H`` of the router's ``E``), it routes over all
``E`` and computes the part of the result that its own experts give. Rows
routed elsewhere sort past the last group and give, and are given, exactly
zero (``_in_a_group`` guards every grouped matmul's result, whatever the
kernel leaves past its groups); ``rows`` still counts all ``E`` experts, so
``routed = held + absent``. With every expert held and a matrix router this
is, operation for operation, the layer it was before it knew of a range. The
parts that all the shares give add up to the uncut layer
(``tests/test_decoder_lm_zaya.py``). The exchange itself (the all-to-all that
brings the other chips' rows here and takes these rows' results back) is not
here: on one chip the layer runs without it, and nothing stands in for the
absent chips (ROADMAP, Reach queue).

The held range in windows. The sort puts every held row before every absent
one, so a layer that holds a strict part of the router's experts need not
carry the absent rows through its row passes. Where it holds under a half of
them (``_window_rows``: twice the held experts' share under uniform routing,
``2 x t x k x H / E`` in whole 512-row tiles, is then smaller than ``t x
k``), it takes the sorted rows through the experts ``c`` at a time
(``_in_windows``): a window is ONE gather of ``c`` rows straight from the
tokens (no ``repeat``), the grouped matmuls over the part of each group that
lies in the window, and a scatter-add of ``c`` weighted rows into the tokens;
and it takes as many windows as hold a held row, ``ceil(held / c)``, a count
the step reads on the device (``lax.fori_loop`` to a traced bound: nothing
visits the host). Nothing is dropped whatever the router does: a step whose
held experts are popular takes more windows, up to all ``t x k`` rows; one
whose held experts nobody chose takes none. ``stats["carried"]`` is ``windows
x c``. The hand-written backward walks the same windows (``_in_windows_bwd``
says why the three ``dW`` matmuls run after the loop and not in it). Where
the windows do not exist - every expert held (OLMoE), a half of them (ZAYA's
8 of 16) - the function traces, operation for operation, what it traced
before it knew of windows: the whole range at once, and no ``carried``. Which it is hangs on the shapes alone; no option chooses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["moe_dropless", "route_top_k", "route_sigmoid_top_k", "dense_swiglu"]

_HIGHEST = jax.lax.Precision.HIGHEST


def _router_logits(x, router):
    if callable(router):
        return router(x)
    return jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32), precision=_HIGHEST)


def route_top_k(x, router, k: int):
    """Float32 routing of ``x [t, d]``: the softmax over all experts ``p [t,
    E]``, and the ``k`` largest of each row as ``(top_p, top_e) [t, k]`` (ties
    go to the lower expert id). ``router`` is a matrix ``[d, E]`` (logits ``x @
    router``) or a function ``x -> logits [t, E]`` in float32."""
    with jax.named_scope("route"):
        p = jax.nn.softmax(_router_logits(x, router), axis=-1)
        top_p, top_e = jax.lax.top_k(p, k)
        return p, top_p, top_e


def route_sigmoid_top_k(x, router, k: int, routed_scale: float, select_bias=None):
    """Float32 routing of ``x [t, d]`` by sigmoid gates: the scores ``p =
    sigmoid(logits) [t, E]``, the ``k`` largest of ``p + select_bias`` in each
    row chosen (ties go to the lower expert id) and ``(top_p, top_e) [t, k]``
    with ``top_p`` the chosen scores renormalised to sum to one, times
    ``routed_scale``. ``router`` as for ``route_top_k``."""
    with jax.named_scope("route"):
        p = jax.nn.sigmoid(_router_logits(x, router))
        _, top_e = jax.lax.top_k(p if select_bias is None else p + select_bias, k)
        chosen = jnp.take_along_axis(p, top_e, axis=1)
        return p, routed_scale * chosen / jnp.sum(chosen, axis=1, keepdims=True), top_e


def dense_swiglu(x, w_gate, w_up, w_down, compute_dtype=jnp.float32):
    """``down(silu(gate(x)) * up(x))`` of one SwiGLU ``[d, h]``, ``[d, h]``,
    ``[h, d]`` on every row of ``x [..., d]``: matmul inputs in the compute
    type, f32 accumulation and result. A dense layer's feed-forward, or the
    expert every token passes beside the routed ones. Without a gate matrix
    (``w_gate`` None): ``down(relu(up(x))^2)``."""
    cd = jnp.dtype(compute_dtype)
    precision = _HIGHEST if cd == jnp.float32 else None

    def dot(a, w):
        return jnp.dot(a.astype(cd), w.astype(cd), preferred_element_type=jnp.float32, precision=precision)

    if w_gate is None:
        return dot(jnp.square(jax.nn.relu(dot(x, w_up))), w_down)
    return dot(jax.nn.silu(dot(x, w_gate)) * dot(x, w_up), w_down)


@jax.custom_vjp
def _take_rows(a, index, inverse):
    """``a[index]`` for a permutation ``index`` of the rows whose inverse is
    ``inverse``: the cotangent is ``g[inverse]``, a gather too."""
    del inverse
    return a[index]


def _take_rows_fwd(a, index, inverse):
    return a[index], (index, inverse)


def _take_rows_bwd(res, g):
    index, inverse = res
    return g[inverse], None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


_RDN = jax.lax.RaggedDotDimensionNumbers
#: ``rows [r, a] x w [E, a, b] -> [r, b]`` (the forward's form; rows against
#: ``W^T`` use it on ``swapaxes(W, 1, 2)``, which XLA folds into the bf16 cast -
#: contracting ``W``'s last axis instead is NOT lowered to the TPU's grouped
#: kernel but to a masked convolution, 24 ms where the kernel takes 4: chip
#: run, PR 26), and ``x [r, a], g [r, b] -> [E, a, b]`` (``dW``: the ragged
#: rows contracted).
_ROWS = _RDN((([1], [1]), ([], [])), [0], [0])
_DW = _RDN((([0], [0]), ([], [])), [0], [])


def _grouped(a, w, group_sizes, dims, out_dtype, precision):
    return jax.lax.ragged_dot_general(a, w, group_sizes, dims, precision=precision,
                                      preferred_element_type=out_dtype)


def _swiglu_hidden(xs, wg, wu, group_sizes, cd, precision):
    gate = _grouped(xs, wg, group_sizes, _ROWS, cd, precision).astype(jnp.float32)
    up = _grouped(xs, wu, group_sizes, _ROWS, cd, precision).astype(jnp.float32)
    return gate, up


def _in_a_group(a, group_sizes):
    """``a`` with the rows past the last group set to zero: where the groups do
    not cover the rows (some experts are held elsewhere) a grouped matmul
    leaves those rows to chance."""
    covered = jnp.arange(a.shape[0], dtype=jnp.int32) < jnp.sum(group_sizes)
    return jnp.where(covered[:, None], a, jnp.zeros((), a.dtype))


def _row_guard(group_sizes, covered: bool):
    if covered:
        return lambda a: a
    return functools.partial(_in_a_group, group_sizes=group_sizes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _expert_swiglu(xs, w_gate, w_up, w_down, group_sizes, compute_dtype, covered=True):
    """``down(silu(gate(xs)) * up(xs))`` over rows ``xs [r, d]`` grouped by
    expert, in and out in the compute type (the grouped matmuls accumulate in
    f32). The weights come in as the f32 masters: the VJP recomputes ``gate``
    and ``up`` from the sorted rows instead of holding three ``[r, width]``
    tensors, and hands back each ``dW`` in f32 straight from the grouped
    matmul that made it (AD through ``w.astype(bf16)`` would round it to
    bfloat16 on the way). ``covered`` false: the groups may end before the
    rows do (the rows of experts held elsewhere, sorted last); every grouped
    matmul's result is then zeroed past the groups, so those rows give and are
    given exactly zero whatever the kernel leaves there. ``w_gate`` None: the
    experts are ``down(relu(up(xs))^2)``, two matrices each."""
    cd = jnp.dtype(compute_dtype)
    precision = _HIGHEST if cd == jnp.float32 else None
    guard = _row_guard(group_sizes, covered)
    if w_gate is None:
        up = _grouped(xs, w_up.astype(cd), group_sizes, _ROWS, cd, precision).astype(jnp.float32)
        hidden = jnp.square(jax.nn.relu(guard(up))).astype(cd)
    else:
        gate, up = _swiglu_hidden(xs, w_gate.astype(cd), w_up.astype(cd), group_sizes, cd, precision)
        hidden = (jax.nn.silu(guard(gate)) * guard(up)).astype(cd)
    return guard(_grouped(hidden, w_down.astype(cd), group_sizes, _ROWS, cd, precision))


def _expert_swiglu_fwd(xs, w_gate, w_up, w_down, group_sizes, compute_dtype, covered=True):
    out = _expert_swiglu(xs, w_gate, w_up, w_down, group_sizes, compute_dtype, covered)
    return out, (xs, w_gate, w_up, w_down, group_sizes)


def _expert_swiglu_bwd(compute_dtype, covered, res, dy):
    xs, w_gate, w_up, w_down, group_sizes = res
    cd = jnp.dtype(compute_dtype)
    precision = _HIGHEST if cd == jnp.float32 else None
    f32 = jnp.float32
    guard = _row_guard(group_sizes, covered)
    transposed = lambda w: jnp.swapaxes(w, 1, 2)  # noqa: E731
    if w_gate is None:
        wu, wd = w_up.astype(cd), w_down.astype(cd)
        dy = guard(dy)
        up = jax.nn.relu(guard(_grouped(xs, wu, group_sizes, _ROWS, cd, precision).astype(f32)))
        d_hidden = guard(_grouped(dy, transposed(wd), group_sizes, _ROWS, f32, precision))
        d_w_down = _grouped(jnp.square(up).astype(cd), dy, group_sizes, _DW, f32, precision)
        d_up = (d_hidden * 2.0 * up).astype(cd)
        d_w_up = _grouped(xs, d_up, group_sizes, _DW, f32, precision)
        d_xs = guard(_grouped(d_up, transposed(wu), group_sizes, _ROWS, f32, precision)).astype(xs.dtype)
        return d_xs, None, d_w_up, d_w_down, None
    wg, wu, wd = w_gate.astype(cd), w_up.astype(cd), w_down.astype(cd)
    dy = guard(dy)
    gate, up = _swiglu_hidden(xs, wg, wu, group_sizes, cd, precision)
    gate, up = guard(gate), guard(up)
    sig = jax.nn.sigmoid(gate)
    act = gate * sig  # silu(gate)
    d_hidden = guard(_grouped(dy, transposed(wd), group_sizes, _ROWS, f32, precision))
    d_w_down = _grouped((act * up).astype(cd), dy, group_sizes, _DW, f32, precision)
    d_up = (d_hidden * act).astype(cd)
    d_gate = (d_hidden * up * (sig + act * (1.0 - sig))).astype(cd)
    d_w_gate = _grouped(xs, d_gate, group_sizes, _DW, f32, precision)
    d_w_up = _grouped(xs, d_up, group_sizes, _DW, f32, precision)
    d_xs = guard(_grouped(d_gate, transposed(wg), group_sizes, _ROWS, f32, precision)
                 + _grouped(d_up, transposed(wu), group_sizes, _ROWS, f32, precision)).astype(xs.dtype)
    return d_xs, d_w_gate, d_w_up, d_w_down, None


_expert_swiglu.defvjp(_expert_swiglu_fwd, _expert_swiglu_bwd)


#: The grouped kernel's row tile (XLA's TPU expansion of ``ragged_dot_general``
#: at these widths: ``ragged_dot_tiling="512,512,512"`` in the step compiled
#: for the chip); a window of sorted rows is a whole number of them.
_ROW_TILE = 512


def _window_rows(rows: int, n_held: int, n_experts: int) -> int:
    """How many sorted rows a layer that holds ``n_held`` of the router's
    ``n_experts`` takes through its experts at a time: twice the share that
    uniform routing gives the held experts, in whole row tiles. 0 where that
    is not under ``rows`` (every expert held; a half or more of them held):
    the layer then takes all ``rows`` at once."""
    c = -(-2 * rows * n_held // (n_experts * _ROW_TILE)) * _ROW_TILE
    return c if c < rows else 0


def _sorted_rows(top_p, order, held_rows, c):
    """What every window reads: the flat weights with a zero past their end,
    the sorted order filled up to whole windows with rows that point at it,
    and the groups' ends in sorted order."""
    rows = order.shape[0]
    with jax.named_scope("route"):
        return (jnp.concatenate([top_p.reshape(-1), jnp.zeros((1,), top_p.dtype)]),
                jnp.concatenate([order, jnp.full((-rows % c,), rows, order.dtype)]),
                jnp.cumsum(held_rows))


def _window(x, weights, order, ends, lo, k, compute_dtype, c):
    """Of the sorted rows ``lo .. lo + c``: the flat (token, slot) row each is,
    its token, its weight, the part of every group that lies among them, and
    the tokens' rows in the compute type (ONE gather of ``c`` rows straight
    from the tokens). Rows past the groups are absent ones or padding (which
    reads the last token): weight 0, and every grouped result guarded."""
    with jax.named_scope("permute"):
        head = jax.lax.dynamic_slice_in_dim(order, lo, c)
        token = jnp.minimum(head // k, x.shape[0] - 1)
        xs = x[token].astype(compute_dtype)
    with jax.named_scope("route"):
        sizes = jnp.diff(jnp.clip(ends - lo, 0, c), prepend=0)
        return head, token, weights[head], sizes, xs


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def _in_windows(x, top_p, w_gate, w_up, w_down, order, held_rows, trips, k, compute_dtype, c):
    """The held experts' part of the result, the sorted rows taken ``c`` at a
    time through the first ``trips`` windows (a traced count: the windows
    that hold a held row; the rest hold absent rows alone and give nothing).
    A window is a gather of ``c`` rows from the tokens, the grouped matmuls
    over them, and a scatter-add of ``c`` weighted rows into the tokens:
    nothing in the loop, forward or backward, has ``t x k`` rows of width ``d``."""
    weights, order, ends = _sorted_rows(top_p, order, held_rows, c)

    def window(i, y):
        _, token, w, sizes, xs = _window(x, weights, order, ends, i * c, k, compute_dtype, c)
        with jax.named_scope("experts"):
            ys = _expert_swiglu(xs, w_gate, w_up, w_down, sizes, compute_dtype, False)
        with jax.named_scope("permute"):
            return y.at[token].add(ys.astype(jnp.float32) * w[:, None])

    return jax.lax.fori_loop(0, trips, window, jnp.zeros(x.shape, jnp.float32))


def _in_windows_fwd(x, top_p, w_gate, w_up, w_down, order, held_rows, trips, k, compute_dtype, c):
    y = _in_windows(x, top_p, w_gate, w_up, w_down, order, held_rows, trips, k, compute_dtype, c)
    return y, (x, top_p, w_gate, w_up, w_down, order, held_rows, trips)


def _in_windows_bwd(k, compute_dtype, c, res, dy):
    """A loop of a traced length has no transpose, so the backward is written
    out: it walks the forward's windows, recomputes each one's hidden
    projections (what the block's ``jax.checkpoint`` did for the whole range),
    scatter-adds the tokens' cotangents and parks what the three ``dW`` need,
    in sorted order, in buffers the loop carries; the three ragged-contraction
    matmuls then run ONCE over the parked rows, after the loop, each straight
    into its gradient (a ``dW`` summed over the windows inside the loop kept a
    second copy of every expert leaf's gradient alive). ``dy . down(hidden)``,
    the weight's cotangent, is taken as ``hidden . (dy @ W_down^T)``: the
    backward runs no ``down`` matmul. Experts without a gate matrix park one
    hidden gradient, not two, and run two ``dW`` matmuls."""
    x, top_p, w_gate, w_up, w_down, order, held_rows, trips = res
    cd, f32 = jnp.dtype(compute_dtype), jnp.float32
    precision = _HIGHEST if cd == f32 else None
    gated = w_gate is not None
    weights, order, ends = _sorted_rows(top_p, order, held_rows, c)
    with jax.named_scope("experts"):
        wg, wu, wd = (w if w is None else w.astype(cd) for w in (w_gate, w_up, w_down))
        # as _expert_swiglu_bwd: _ROWS' form
        wg_t, wu_t, wd_t = (w if w is None else jnp.swapaxes(w, 1, 2) for w in (wg, wu, wd))

    def window(i, carry):
        (dx, d_weights), parked = carry
        lo = i * c
        head, token, w, sizes, xs = _window(x, weights, order, ends, lo, k, compute_dtype, c)
        guard = functools.partial(_in_a_group, group_sizes=sizes)
        with jax.named_scope("permute"):
            g = dy[token].astype(cd)
        with jax.named_scope("experts"):
            if gated:
                gate, up = _swiglu_hidden(xs, wg, wu, sizes, cd, precision)
                gate, up = guard(gate), guard(up)
                sig = jax.nn.sigmoid(gate)
                act = gate * sig  # silu(gate)
                hidden = act * up
                through_down = guard(_grouped(g, wd_t, sizes, _ROWS, f32, precision))  # dy @ W_down^T, unweighted
                d_hidden = through_down * w[:, None]
                d_up = (d_hidden * act).astype(cd)
                d_gate = (d_hidden * up * (sig + act * (1.0 - sig))).astype(cd)
                d_xs = guard(_grouped(d_gate, wg_t, sizes, _ROWS, f32, precision)
                             + _grouped(d_up, wu_t, sizes, _ROWS, f32, precision))
                now = (xs, g, (hidden * w[:, None]).astype(cd), d_gate, d_up)
            else:
                up = jax.nn.relu(guard(_grouped(xs, wu, sizes, _ROWS, cd, precision).astype(f32)))
                hidden = jnp.square(up)
                through_down = guard(_grouped(g, wd_t, sizes, _ROWS, f32, precision))
                d_up = (through_down * w[:, None] * 2.0 * up).astype(cd)
                d_xs = guard(_grouped(d_up, wu_t, sizes, _ROWS, f32, precision))
                now = (xs, g, (hidden * w[:, None]).astype(cd), d_up)
            parked = tuple(jax.lax.dynamic_update_slice_in_dim(all_, a, lo, 0) for all_, a in zip(parked, now))
        with jax.named_scope("permute"):
            dx = dx.at[token].add(d_xs)
            d_weights = d_weights.at[head].add(jnp.sum(hidden * through_down, axis=1))
        return (dx, d_weights), parked

    rows, d, h = order.shape[0], x.shape[1], w_up.shape[2]
    with jax.named_scope("experts"):
        parked = tuple(jnp.zeros((rows, width), cd) for width in (d, d, h, h, h)[:4 + gated])
    (dx, d_weights), (xs, g, weighted_hidden, *d_gate, d_up) = jax.lax.fori_loop(
        0, trips, window, ((jnp.zeros_like(x), jnp.zeros_like(weights)), parked))
    with jax.named_scope("experts"):
        d_w_gate = _grouped(xs, d_gate[0], held_rows, _DW, f32, precision) if gated else None
        d_w_up = _grouped(xs, d_up, held_rows, _DW, f32, precision)
        d_w_down = _grouped(weighted_hidden, g, held_rows, _DW, f32, precision)
    return dx, d_weights[:-1].reshape(top_p.shape), d_w_gate, d_w_up, d_w_down, None, None, None


_in_windows.defvjp(_in_windows_fwd, _in_windows_bwd)


def moe_dropless(x, router, w_gate, w_up, w_down, k: int, compute_dtype=jnp.float32,
                 first_held: int = 0, routed_scale: float = 0.0, select_bias=None, renormalise: bool = False):
    """Top-``k`` experts for tokens ``x [t, d]``: SwiGLU, or with ``w_gate``
    None ``down(relu(up(x))^2)``.

    ``router`` is a matrix ``[d, E]`` or a function ``x -> logits [t, E]``
    (float32); ``w_gate``/``w_up`` ``[H, d, h]`` and ``w_down [H, h, d]`` are
    the ``H`` experts held here, experts ``first_held .. first_held + H`` of
    the ``E`` the router chooses among. ``compute_dtype`` is the grouped
    matmuls' input type (accumulation is f32); the router is f32 regardless.
    ``routed_scale`` > 0 asks for sigmoid gates, chosen with ``select_bias``,
    renormalised and scaled (``route_sigmoid_top_k``); ``renormalise`` for
    softmax gates divided by their sum over all ``k`` chosen, held here or not.
    Returns ``(y [t, d] f32, stats)`` with ``stats = {"f": [E], "P": [E],
    "rows": [E] int32}`` as the module docstring defines them: ``y`` is the
    part of the layer's result that the held experts give. Where the rows go
    through the experts a window at a time (``_window_rows``), ``stats`` also
    holds ``carried``: the rows this call's windows took.
    """
    t, _ = x.shape
    n_held = w_up.shape[0]
    if routed_scale:
        p, top_p, top_e = route_sigmoid_top_k(x, router, k, routed_scale, select_bias)
    else:
        p, top_p, top_e = route_top_k(x, router, k)
        if renormalise:
            with jax.named_scope("route"):
                top_p = top_p / jnp.sum(top_p, axis=1, keepdims=True)
    n_experts = p.shape[1]
    covered = n_held == n_experts
    if not 0 <= first_held <= n_experts - n_held:
        raise ValueError(f"experts {first_held}..{first_held + n_held} are not among the router's {n_experts}")
    window = _window_rows(t * k, n_held, n_experts)

    with jax.named_scope("route"):
        # row r of the flat (token, slot) list belongs to token r // k
        flat_e = top_e.reshape(-1)
        sort_key = flat_e
        if not covered:  # the rows of experts held elsewhere sort last, past every group
            local = flat_e - first_held
            here = (local >= 0) & (local < n_held)
            sort_key = jnp.where(here, local, n_held)
            top_p = jnp.where(here.reshape(top_p.shape), top_p, 0.0)
        order = jnp.argsort(sort_key, stable=True)  # sorted position -> flat row
        if not window:
            inverse = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0], dtype=order.dtype))
        rows = jnp.zeros((n_experts,), jnp.int32).at[flat_e].add(1)
        held_rows = rows if covered else jax.lax.dynamic_slice_in_dim(rows, first_held, n_held)
        if window:  # the held rows sort first: the windows that hold one
            trips = (jnp.sum(held_rows) + window - 1) // window

    if window:
        y = _in_windows(x, top_p, w_gate, w_up, w_down, order, held_rows, trips, k,
                        jnp.dtype(compute_dtype).name, window)
    else:
        with jax.named_scope("permute"):
            x_rows = jnp.repeat(x.astype(compute_dtype), k, axis=0)  # [t*k, d], token-major
            xs = _take_rows(x_rows, order, inverse)
        with jax.named_scope("experts"):
            ys = _expert_swiglu(xs, w_gate, w_up, w_down, held_rows, jnp.dtype(compute_dtype).name, covered)
        with jax.named_scope("permute"):
            y_rows = _take_rows(ys, inverse, order).reshape(t, k, -1)
            y = jnp.sum(y_rows.astype(jnp.float32) * top_p[:, :, None], axis=1)

    with jax.named_scope("route"):
        stats = {
            "f": rows.astype(jnp.float32) / t,
            "P": jnp.mean(p, axis=0),
            "rows": rows,
        }
        if window:
            stats["carried"] = jnp.minimum(trips * window, t * k)
    return y, stats
