"""Operations and bytes of the decoder LM's step, from its shapes
(``systems/decoder_lm_fit.py::layout_dims``). Each function returns
``(flops, bytes)`` of what the algorithm needs for ONE optimizer step, forward
and backward; what an implementation recomputes (the experts' hidden
projections in their backward, the scores in the fold's) is not counted.

``moe_experts``: the three grouped matmuls of every block over ``tokens x
top_k`` rows, forward and twice again backward (``dX`` and ``dW``): ``3 x 2 x
rows x 3 x hidden x width``. Bytes: each expert matrix read in bfloat16 by the
forward and by ``dX``, its float32 gradient written once, and the rows'
activations (in and out of each grouped matmul) in bfloat16.

``attention_fold``: causal attention, so half the square: ``Q K^T`` and ``P V``
forward; ``dV``, ``dP``, ``dQ``, ``dK`` backward: ``6 x 2 x (T^2 / 2) x D`` per
head and sequence. Bytes: q, k, v, o and their four gradients, 2 bytes each
(the fold takes bfloat16 and this counts its f32 outputs as bfloat16 too: the
lower bound).

``model``: every matmul of the step, ``3 x`` the forward's: projections,
causal scores, router, experts, head. The embedding gather and the
element-wise work count nothing. Bytes: the f32 weights, gradients and AdamW
moments read and written once.
"""


def moe_experts(tokens, top_k, hidden, width, layers, experts, **_):
    rows = tokens * top_k
    flops = 3.0 * 2.0 * rows * 3.0 * hidden * width * layers
    weights = 3.0 * experts * hidden * width * layers
    activations = rows * (2.0 * hidden + 3.0 * width) * 2.0 * 3.0
    return flops, weights * (2.0 + 2.0 + 4.0) + activations


def attention_fold(batch, heads, seq, hidden, layers, **_):
    head_dim = hidden // heads
    flops = 6.0 * 2.0 * (seq * seq / 2.0) * head_dim * heads * batch * layers
    nbytes = 8.0 * batch * heads * seq * head_dim * 2.0 * layers
    return flops, nbytes


def forward_flops_per_token(seq, hidden, heads, experts, top_k, width, vocab, layers, **_):
    head_dim = hidden // heads
    projections = 4 * 2.0 * hidden * hidden
    scores = 2 * 2.0 * (seq / 2.0) * head_dim * heads
    router = 2.0 * hidden * experts
    expert = top_k * 3 * 2.0 * hidden * width
    return layers * (projections + scores + router + expert), 2.0 * hidden * vocab


def model(tokens, **shapes):
    layer, head = forward_flops_per_token(**shapes)
    flops = 3.0 * tokens * (layer + head)
    n = shapes["vocab"] * shapes["hidden"] * 2 + shapes["layers"] * (
        4 * shapes["hidden"] ** 2 + shapes["hidden"] * shapes["experts"]
        + 3 * shapes["experts"] * shapes["hidden"] * shapes["width"])
    return flops, n * 4.0 * 7.0  # w, m, v read and written; the gradient read
