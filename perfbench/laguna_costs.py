"""Operations and bytes of the ``laguna_xs2`` configuration's step, from its
shapes (``systems/laguna_lm_fit.py::layout_dims``: ``layer_heads`` and
``layer_windows`` name each layer's query heads and sliding window, 0 for a
full layer) and from the rows the held experts ran (``rows_held`` a step, all
sparse layers together: the count the program writes on ``train.drain``). Each
function returns ``(flops, bytes)`` of what the algorithm needs for ONE
optimizer step, forward and backward; what an implementation recomputes (each
block's forward under ``jax.checkpoint``, the experts' hidden projections, the
scores in the fold's backward, the head's logits) or computes and masks away
(the part of a key chunk outside the band) is not counted.

``window_fold``: the windowed layers' attention: query ``t`` on the
``min(t + 1, window)`` keys ending at it, so ``window x T - window (window -
1) / 2`` (query, key) pairs a head and sequence; ``Q K^T`` and ``P V`` forward,
``dV``, ``dP``, ``dQ``, ``dK`` backward: ``6 x 2 x pairs x D``. Bytes: q, o and
their gradients once per QUERY head; k, v and their gradients once per
KEY/VALUE head, 2 bytes each (the lower bound: the fold's f32 outputs counted
as bfloat16): the same work whatever implements the window.

``held_experts``: the three grouped matmuls over the rows routed to the
experts held here, forward and twice again backward: ``3 x 2 x rows_held x 3 x
hidden x width``. Bytes: each held expert matrix read in bfloat16 by the
forward and by ``dX``, its float32 gradient written once, and the held rows'
activations in bfloat16.

``model``: every matmul of the step, ``3 x`` the forward's: each layer's
projections at its own head count, its head gate, its scores (the band on a
windowed layer, half the square on a full one), the dense SwiGLU of a leading
layer, the router, the shared expert on every token, the held experts on
``rows_held`` rows, the sliced untied head. The embedding gather and the
element-wise work count nothing. Bytes: the f32 weights, gradients and AdamW
moments.
"""


def _pairs(seq, window):
    """(query, key) pairs a head and sequence attends: the band, or half the square."""
    return window * seq - window * (window - 1) / 2.0 if 0 < window < seq else seq * seq / 2.0


def window_fold(batch, seq, head_dim, kv_heads, layer_heads, layer_windows, **_):
    flops = nbytes = 0.0
    for heads, window in zip(layer_heads, layer_windows):
        if window:
            flops += 6.0 * 2.0 * _pairs(seq, window) * head_dim * heads * batch
            nbytes += 4.0 * batch * (heads + kv_heads) * seq * head_dim * 2.0
    return flops, nbytes


def held_experts(rows_held, hidden, width, experts_held, layer_heads, dense_layers, **_):
    flops = 3.0 * 2.0 * rows_held * 3.0 * hidden * width
    weights = 3.0 * experts_held * hidden * width * (len(layer_heads) - dense_layers)
    activations = rows_held * (2.0 * hidden + 3.0 * width) * 2.0 * 3.0
    return flops, weights * (2.0 + 2.0 + 4.0) + activations


def forward_flops_per_token(seq, hidden, head_dim, kv_heads, layer_heads, layer_windows, dense_layers, dense_width,
                            experts, shared_width, vocab, **_):
    """``(all layers without their held experts, head)`` forward matmul FLOPs a token."""
    layers = 0.0
    for i, (heads, window) in enumerate(zip(layer_heads, layer_windows)):
        latent, kv = heads * head_dim, kv_heads * head_dim
        layers += 2.0 * hidden * (latent + 2 * kv) + 2.0 * latent * hidden + 2.0 * hidden * heads
        layers += 2 * 2.0 * (_pairs(seq, window) / seq) * head_dim * heads
        if i < dense_layers:
            layers += 3 * 2.0 * hidden * dense_width
        else:
            layers += 2.0 * hidden * experts + 3 * 2.0 * hidden * shared_width
    return layers, 2.0 * hidden * vocab


def params(hidden, head_dim, kv_heads, layer_heads, dense_layers, dense_width, experts, experts_held, width,
           shared_width, vocab, **_):
    total = 2 * vocab * hidden + hidden
    for i, heads in enumerate(layer_heads):
        total += hidden * (2 * heads * head_dim + 2 * kv_heads * head_dim + heads) + 2 * hidden
        if i < dense_layers:
            total += 3 * hidden * dense_width
        else:
            total += hidden * experts + experts + 3 * hidden * shared_width + 3 * experts_held * hidden * width
    return total


def model(tokens, rows_held, **shapes):
    layers, head = forward_flops_per_token(**shapes)
    experts = rows_held * 3 * 2.0 * shapes["hidden"] * shapes["width"]
    flops = 3.0 * (tokens * (layers + head) + experts)
    return flops, params(**shapes) * 4.0 * 7.0  # w, m, v read and written; the gradient read
