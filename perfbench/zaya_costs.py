"""Operations and bytes of the ``zaya1_8b`` configuration's step, from its
shapes (``systems/zaya_lm_fit.py::layout_dims``) and from the rows the held
experts ran (``rows_held`` a step, all layers together: the count the program
writes on ``train.drain``). Each function returns ``(flops, bytes)`` of what
the algorithm needs for ONE optimizer step, forward and backward; what an
implementation recomputes (each block's forward under ``jax.checkpoint``, the
experts' hidden projections, the scores in the fold's backward, the head's
logits) is not counted.

``attention_fold``: causal attention at ``heads`` query heads on ``kv_heads``
key/value heads, half the square: ``Q K^T`` and ``P V`` forward; ``dV``,
``dP``, ``dQ``, ``dK`` backward: ``6 x 2 x (T^2 / 2) x D`` per query head and
sequence. Bytes: q, o and their gradients once per QUERY head; k, v and their
gradients once per KEY/VALUE head (the grouped fold reads each K and V block
for its whole group; a copy per query head is not needed), 2 bytes each (the
lower bound: the fold's f32 outputs counted as bfloat16).

``held_experts``: the three grouped matmuls over the rows routed to the
experts held here, forward and twice again backward: ``3 x 2 x rows_held x 3 x
hidden x width``. Bytes: each held expert matrix read in bfloat16 by the
forward and by ``dX``, its float32 gradient written once, and the held rows'
activations in bfloat16.

``model``: every matmul of the step, ``3 x`` the forward's: the latent
projections (``wq``, ``wk``, two value heads, ``wo``), the grouped convolution
over each head's channels (two taps), the causal scores at ``heads`` heads,
the router (its input projection and the three matrices of its MLP, on every
token), the held experts on ``rows_held`` rows, the sliced tied head. The
depthwise convolution, the embedding gather and other element-wise work count
nothing. Bytes: the f32 weights, gradients and AdamW moments.
"""


def attention_fold(batch, heads, kv_heads, head_dim, seq, layers, **_):
    flops = 6.0 * 2.0 * (seq * seq / 2.0) * head_dim * heads * batch * layers
    nbytes = 4.0 * batch * (heads + kv_heads) * seq * head_dim * 2.0 * layers
    return flops, nbytes


def held_experts(rows_held, hidden, width, layers, experts_held, **_):
    flops = 3.0 * 2.0 * rows_held * 3.0 * hidden * width
    weights = 3.0 * experts_held * hidden * width * layers
    activations = rows_held * (2.0 * hidden + 3.0 * width) * 2.0 * 3.0
    return flops, weights * (2.0 + 2.0 + 4.0) + activations


def forward_flops_per_token(seq, hidden, heads, kv_heads, head_dim, router_width, experts, vocab, **_):
    """``(layer without its experts, head)`` forward matmul FLOPs a token."""
    latent, kv = heads * head_dim, kv_heads * head_dim
    projections = 2.0 * hidden * (latent + kv + kv) + 2.0 * latent * hidden
    convolution = 2 * 2.0 * (heads + kv_heads) * head_dim * head_dim
    scores = 2 * 2.0 * (seq / 2.0) * head_dim * heads
    router = 2.0 * hidden * router_width + 2 * 2.0 * router_width * router_width + 2.0 * router_width * experts
    return projections + convolution + scores + router, 2.0 * hidden * vocab


def params(hidden, heads, kv_heads, head_dim, router_width, experts, experts_held, width, vocab, layers, **_):
    latent, kv, groups = heads * head_dim, kv_heads * head_dim, heads + kv_heads
    layer = (hidden * (latent + 2 * kv) + latent * hidden + 3 * (latent + kv) + 2 * groups * head_dim ** 2
             + groups * head_dim + kv_heads + 10 * hidden + hidden * router_width + 2 * router_width
             + 2 * router_width ** 2 + router_width * experts + 3 * experts_held * hidden * width)
    return vocab * hidden + layers * layer - router_width + hidden  # the first layer has no gamma


def model(tokens, rows_held, layers, **shapes):
    layer, head = forward_flops_per_token(**shapes)
    experts = rows_held * 3 * 2.0 * shapes["hidden"] * shapes["width"]
    flops = 3.0 * (tokens * (layers * layer + head) + experts)
    return flops, params(layers=layers, **shapes) * 4.0 * 7.0  # w, m, v read and written; the gradient read
