"""Operations and bytes of the ``sdar_30b_a3b`` configuration's step, from
its shapes (``systems/sdar_lm_fit.py::layout_dims``: ``batch`` sequences of
``seq`` tokens a step, each run through the stack as ``2 x seq`` positions,
the sequence and its noised copy, in blocks of ``block``) and from the rows the
held experts ran (``rows_held`` a step, all layers together: the count the
program writes on ``train.drain``). Each function returns ``(flops, bytes)`` of
what the algorithm needs for ONE optimizer step, forward and backward; what an
implementation recomputes (each layer's forward under ``jax.checkpoint``, the
experts' hidden projections, the scores in the fold's backward) or computes and
masks away (the entries of a tile the mask crosses) is not counted.

``block_diffusion_fold``: attention under the block-diffusion mask in every
layer. The mask keeps, a head and sequence, ``T (T + L) / 2`` pairs of clean
queries on clean keys (block-causal), ``T (T - L) / 2`` of noised queries on
clean keys (strictly block-causal) and ``T L`` inside the noised half's blocks:
``T^2 + T L`` of the ``4 T^2``, whatever tiles an implementation walks. ``Q
K^T`` and ``P V`` forward, ``dV``, ``dP``, ``dQ``, ``dK`` backward: ``6 x pairs
x 2 D``. Bytes, 2 each: q, o and their gradients once a QUERY head, K, V and
their gradients once a KEY/VALUE head, over the ``2 T`` positions: the same
work whatever implements the mask, no tile or layout in it.

``held_experts``: the three grouped matmuls over the rows routed to the
experts held here, forward and twice again backward: ``3 x 2 x rows_held x 3 x
hidden x width``. Bytes: each held expert matrix read in bfloat16 by the
forward and by ``dX``, its float32 gradient written once, and the held rows'
activations in bfloat16.

``model``: every matmul of the step, ``3 x`` the forward's: a layer's four
projections and its router at the DOUBLED positions, the fold at its KEPT
pairs, the held experts on ``rows_held`` rows, the sliced untied head over the
``T`` noised rows a sequence (the clean half's states pass no head). The
embedding gathers, the corruption and the element-wise work count nothing.
Bytes: the f32 weights, gradients and AdamW moments.
"""


def kept_pairs(seq, block, **_):
    """(query, key) pairs the block-diffusion mask keeps, a head and sequence."""
    return float(seq) * seq + float(seq) * block


def block_diffusion_fold(batch, seq, block, heads, kv_heads, head_dim, layers, **_):
    flops = 6.0 * kept_pairs(seq, block) * 2.0 * head_dim * heads * batch * layers
    per_position = 4.0 * head_dim * heads + 4.0 * head_dim * kv_heads  # q, dq, o, do a query head; k, dk, v, dv a kv head
    return flops, 2.0 * batch * 2.0 * seq * per_position * layers


def held_experts(rows_held, hidden, width, experts_held, layers, **_):
    flops = 3.0 * 2.0 * rows_held * 3.0 * hidden * width
    weights = 3.0 * experts_held * hidden * width * layers
    activations = rows_held * (2.0 * hidden + 3.0 * width) * 2.0 * 3.0
    return flops, weights * (2.0 + 2.0 + 4.0) + activations


def forward_flops_per_sequence(seq, block, hidden, heads, kv_heads, head_dim, experts, layers, vocab, **_):
    """``(the layers without their held experts, the head)`` forward matmul FLOPs a sequence."""
    projections = 2.0 * hidden * head_dim * (2 * heads + 2 * kv_heads)  # wq, wo; wk, wv
    per_position = projections + 2.0 * hidden * experts
    fold = 2.0 * kept_pairs(seq, block) * 2.0 * head_dim * heads
    return layers * (2.0 * seq * per_position + fold), seq * 2.0 * hidden * vocab


def params(layers, hidden, heads, kv_heads, head_dim, experts, experts_held, width, vocab, **_):
    attention = hidden * head_dim * (2 * heads + 2 * kv_heads) + 2 * head_dim + 2 * hidden
    return 2 * vocab * hidden + hidden + layers * (attention + hidden * experts + 3 * experts_held * hidden * width)


def model(batch, rows_held, **shapes):
    layers, head = forward_flops_per_sequence(**shapes)
    experts = rows_held * 3 * 2.0 * shapes["hidden"] * shapes["width"]
    flops = 3.0 * (batch * (layers + head) + experts)
    return flops, params(**shapes) * 4.0 * 7.0  # w, m, v read and written; the gradient read
