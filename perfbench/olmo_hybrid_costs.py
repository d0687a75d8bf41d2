"""Operations and bytes of the ``olmo_hybrid_7b`` configuration's step, from
its shapes (``systems/olmo_hybrid_lm_fit.py::layout_dims``: ``layers`` layers
of which ``layers_full`` attend and the others run the gated delta rule with
ONE decay a head on ``kda_heads`` heads of ``key_dim`` key and ``value_dim``
value channels; every layer has a dense SwiGLU of ``width``). Each function
returns ``(flops, bytes)`` of what the algorithm needs for ONE optimizer step,
forward and backward; what an implementation recomputes (each layer's forward
under ``jax.checkpoint``, the scores in the fold's backward, a chunk's matrices
and its solve in the delta rule's backward) is not counted.

``kda_scan``: the delta rule's OWN recurrence, whatever implements it (one
position at a time, chunks of any size, a kernel): per position and head the
state's decay (``D_k D_v``), what the state already says of the key, ``S^T k``
(``2 D_k D_v``), the rank-one correction added to it (``2 D_k D_v``) and the
read-out ``S^T q`` (``2 D_k D_v``): ``7 D_k D_v`` operations forward, and twice
that backward. Bytes: the recurrence's inputs read once (``q``, ``k`` of
``D_k`` and ``v`` of ``D_v`` channels at 2 bytes a channel, ONE float32
log-decay and one float32 ``beta`` a head) and ``o`` written once (``D_v``
channels, 2 bytes), forward; the same again and their gradients backward: three
times the forward's. No chunk size enters: a chunked form's ``[chunk, chunk]``
matrices, its triangular solve and its chunk states are its implementation's.

``nope_fold``: the attention layers' causal fold: ``T^2 / 2`` (query, key)
pairs a head and sequence; ``Q K^T`` and ``P V`` forward, ``dV``, ``dP``,
``dQ``, ``dK`` backward: ``6 x 2 x pairs x D``. Bytes: q, o and their
gradients once per QUERY head; k, v and their gradients once per KEY/VALUE
head, 2 bytes each (``nemotron_costs.nope_fold``'s count; the QK-norm is
outside the fold).

``dense_ffn``: the SwiGLU's three matmuls on every token, forward and twice
again backward: ``3 x 3 x 2 x tokens x hidden x width`` a layer. Bytes: each
matrix read in bfloat16 by the forward and by ``dX``, its float32 gradient
written once, and the activations in bfloat16 (the stream in and out, the two
hidden projections), forward and backward.

``model``: every matmul of the step and the delta rule's recurrence, ``3 x``
the forward's: a delta-rule layer's three projections, ``Wa`` and ``Wb``, the
full output gate ``Wg``, ``Wo`` and ``kda_scan``'s count; an attention layer's
four projections and its causal half of the scores; every layer's SwiGLU; the
sliced untied head. The embedding gather, the convolution's four taps and the
element-wise work count nothing. Bytes: the f32 weights, gradients and AdamW
moments.
"""


def _rule_forward(kda_heads, key_dim, value_dim, **_):
    """``(operations, bytes)`` of the recurrence forward, one position of one layer."""
    return (7.0 * key_dim * value_dim * kda_heads,
            kda_heads * ((2 * key_dim + 2 * value_dim) * 2.0 + 4.0 + 4.0))


def kda_scan(tokens, layers, layers_full, **shapes):
    flops, nbytes = _rule_forward(**shapes)
    deltas = layers - layers_full
    return 3.0 * flops * tokens * deltas, 3.0 * nbytes * tokens * deltas


def nope_fold(batch, seq, heads, kv_heads, head_dim, layers_full, **_):
    flops = 6.0 * 2.0 * (seq * seq / 2.0) * head_dim * heads * batch * layers_full
    return flops, 4.0 * batch * (heads + kv_heads) * seq * head_dim * 2.0 * layers_full


def dense_ffn(tokens, hidden, width, layers, **_):
    flops = 3.0 * 3 * 2.0 * tokens * hidden * width * layers
    weights = 3.0 * hidden * width * layers
    activations = tokens * (2.0 * hidden + 2.0 * width) * 2.0 * 3.0 * layers
    return flops, weights * (2.0 + 2.0 + 4.0) + activations


def forward_flops_per_token(seq, hidden, layers, layers_full, kda_heads, key_dim, value_dim, heads, kv_heads, head_dim,
                            width, vocab, **_):
    """``(all layers, head)`` forward FLOPs a token."""
    keys, values = kda_heads * key_dim, kda_heads * value_dim
    rule, _ = _rule_forward(kda_heads, key_dim, value_dim)
    delta = (2.0 * hidden * (2 * keys + values) + 2 * 2.0 * hidden * kda_heads + 2.0 * hidden * values
             + 2.0 * values * hidden + rule)
    attention = (2.0 * hidden * (heads + 2 * kv_heads) * head_dim + 2.0 * heads * head_dim * hidden
                 + 2 * 2.0 * (seq / 2.0) * head_dim * heads)
    return ((layers - layers_full) * delta + layers_full * attention + layers * 3 * 2.0 * hidden * width,
            2.0 * hidden * vocab)


def params(hidden, layers, layers_full, kda_heads, key_dim, value_dim, conv_kernel, heads, kv_heads, head_dim, width,
           vocab, **_):
    keys, values = kda_heads * key_dim, kda_heads * value_dim
    delta = (hidden * (2 * keys + 3 * values) + conv_kernel * (2 * keys + values) + 2 * hidden * kda_heads
             + 2 * kda_heads + value_dim)
    attention = hidden * head_dim * (2 * heads + 2 * kv_heads) + (heads + kv_heads) * head_dim
    return (2 * vocab * hidden + hidden + (layers - layers_full) * delta + layers_full * attention
            + layers * (3 * hidden * width + 2 * hidden))


def model(tokens, **shapes):
    layers, head = forward_flops_per_token(**shapes)
    return 3.0 * tokens * (layers + head), params(**shapes) * 4.0 * 7.0  # w, m, v read and written; the gradient read
