"""Operations and bytes of the ``solar_open2_250b`` configuration's step, from
its shapes (``systems/solar_lm_fit.py::layout_dims``: ``layers`` layers of
which ``layers_gqa`` attend and the others run the gated delta rule on
``kda_heads`` heads of ``kda_head_dim`` channels; every layer has experts) and
from the rows the held experts ran (``rows_held`` a step, all layers together:
the count the program writes on ``train.drain``). Each function returns
``(flops, bytes)`` of what the algorithm needs for ONE optimizer step, forward
and backward; what an implementation recomputes (each layer's forward under
``jax.checkpoint``, the experts' hidden projections, the scores in the fold's
backward, a chunk's matrices and its solve in the delta rule's backward) is
not counted.

``kda_scan``: the delta rule's OWN recurrence, whatever implements it (one
position at a time, chunks of any size, a kernel): per position and head the
state's decay a key channel (``D^2``), what the state already says of the key,
``S^T k`` (``2 D^2``), the rank-one correction added to it (``2 D^2``) and the
read-out ``S^T q`` (``2 D^2``): ``7 D^2`` operations forward, and twice that
backward. Bytes: the recurrence's inputs read once (``q``, ``k``, ``v`` at 2
bytes a channel, the log-decays in float32, ``beta`` a float32 a head) and
``o`` written once (2 bytes), forward; the same again and their gradients
backward: three times the forward's. No chunk size enters: a chunked form's
``[chunk, chunk]`` matrices, its triangular solve and its chunk states are its
implementation's.

``gated_fold``: the attention layers' causal fold: ``T^2 / 2`` (query, key)
pairs a head and sequence; ``Q K^T`` and ``P V`` forward, ``dV``, ``dP``,
``dQ``, ``dK`` backward: ``6 x 2 x pairs x D``. Bytes: q, o and their
gradients once per QUERY head; k, v and their gradients once per KEY/VALUE
head, 2 bytes each (as ``nemotron_costs.nope_fold`` counts them; the gate is
outside the fold).

``held_experts``: the three grouped matmuls over the rows routed to the
experts held here, forward and twice again backward: ``3 x 2 x rows_held x 3 x
hidden x width``. Bytes: each held expert matrix read in bfloat16 by the
forward and by ``dX``, its float32 gradient written once, and the held rows'
activations in bfloat16 (``sdar_costs.held_experts``' count).

``model``: every matmul of the step and the delta rule's recurrence, ``3 x``
the forward's: a delta-rule layer's three projections, its two low-rank gates,
``Wb``, ``Wo`` and ``kda_scan``'s count; an attention layer's five projections
and its causal half of the scores; every layer's router and shared expert on
every token and the held experts on ``rows_held`` rows; the sliced untied head.
The embedding gather, the convolution's four taps and the element-wise work
count nothing. Bytes: the f32 weights, gradients and AdamW moments.
"""
from perfbench import sdar_costs


def _rule_forward(kda_heads, kda_head_dim, **_):
    """``(operations, bytes)`` of the recurrence forward, one position of one layer."""
    d = kda_head_dim
    return 7.0 * d * d * kda_heads, kda_heads * (4 * d * 2.0 + d * 4.0 + 4.0)


def kda_scan(tokens, layers, layers_gqa, **shapes):
    flops, nbytes = _rule_forward(**shapes)
    deltas = layers - layers_gqa
    return 3.0 * flops * tokens * deltas, 3.0 * nbytes * tokens * deltas


def gated_fold(batch, seq, heads, kv_heads, head_dim, layers_gqa, **_):
    flops = 6.0 * 2.0 * (seq * seq / 2.0) * head_dim * heads * batch * layers_gqa
    return flops, 4.0 * batch * (heads + kv_heads) * seq * head_dim * 2.0 * layers_gqa


#: the three grouped matmuls over the held rows: the count ``sdar_costs`` makes for the same SwiGLU experts
held_experts = sdar_costs.held_experts


def forward_flops_per_token(seq, hidden, layers, layers_gqa, kda_heads, kda_head_dim, heads, kv_heads, head_dim,
                            experts, shared_width, vocab, **_):
    """``(all layers without their held experts, head)`` forward FLOPs a token."""
    inner, rank = kda_heads * kda_head_dim, kda_head_dim
    rule, _ = _rule_forward(kda_heads, kda_head_dim)
    delta = (2.0 * hidden * 3 * inner + 2 * (2.0 * hidden * rank + 2.0 * rank * inner) + 2.0 * hidden * kda_heads
             + 2.0 * inner * hidden + rule)
    attention = (2.0 * hidden * (2 * heads + 2 * kv_heads) * head_dim + 2.0 * heads * head_dim * hidden
                 + 2 * 2.0 * (seq / 2.0) * head_dim * heads)
    feed_forward = 2.0 * hidden * experts + 3 * 2.0 * hidden * shared_width
    return (layers - layers_gqa) * delta + layers_gqa * attention + layers * feed_forward, 2.0 * hidden * vocab


def params(hidden, layers, layers_gqa, kda_heads, kda_head_dim, conv_kernel, heads, kv_heads, head_dim, experts,
           experts_held, width, shared_width, vocab, **_):
    inner, rank = kda_heads * kda_head_dim, kda_head_dim
    delta = (4 * hidden * inner + 3 * conv_kernel * inner + 2 * (hidden * rank + rank * inner) + kda_heads + inner
             + hidden * kda_heads + rank)
    attention = hidden * head_dim * (3 * heads + 2 * kv_heads)
    feed_forward = hidden * experts + experts + 3 * hidden * shared_width + 3 * experts_held * hidden * width
    return (2 * vocab * hidden + hidden + (layers - layers_gqa) * delta + layers_gqa * attention
            + layers * (feed_forward + 2 * hidden))


def model(tokens, rows_held, **shapes):
    layers, head = forward_flops_per_token(**shapes)
    experts = rows_held * 3 * 2.0 * shapes["hidden"] * shapes["width"]
    flops = 3.0 * (tokens * (layers + head) + experts)
    return flops, params(**shapes) * 4.0 * 7.0  # w, m, v read and written; the gradient read
