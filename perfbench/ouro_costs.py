"""Operations and bytes of the ``ouro_2_6b`` configuration's step, from its
shapes (``systems/ouro_lm_fit.py::layout_dims``): ``layers`` dense blocks run
``loops`` times over the same weights, so every per-layer count is taken over
``layers x loops`` block applications, and the head over ``loops`` passes. Each
function returns ``(flops, bytes)`` of what the algorithm needs for ONE
optimizer step, forward and backward; what an implementation recomputes (each
block's forward under ``jax.checkpoint``, the scores in the fold's backward,
the head's logits) is not counted.

``attention_fold``: causal attention, so half the square: ``Q K^T`` and ``P V``
forward; ``dV``, ``dP``, ``dQ``, ``dK`` backward: ``6 x 2 x (T^2 / 2) x D`` per
head, sequence and block application. Bytes: q, k, v, o and their four
gradients, 2 bytes each (the fold takes bfloat16 and this counts its f32
outputs as bfloat16 too: the lower bound), per application.

``model``: every matmul of the step, ``3 x`` the forward's: per block
application the four projections, the causal scores and the dense SwiGLU's
three matrices; per pass the head and the exit gate. The embedding gather and
the element-wise work count nothing. Bytes: the f32 weights, gradients and
AdamW moments read and written once (the weights are read once a pass, in
bfloat16 copies an implementation may or may not keep: not counted).
"""


def attention_fold(batch, heads, head_dim, seq, layers, loops, **_):
    applications = layers * loops
    flops = 6.0 * 2.0 * (seq * seq / 2.0) * head_dim * heads * batch * applications
    nbytes = 8.0 * batch * heads * seq * head_dim * 2.0 * applications
    return flops, nbytes


def forward_flops_per_token(seq, hidden, heads, head_dim, width, vocab, **_):
    """``(one block application, one pass of the head and the gate)`` forward
    matmul FLOPs a token."""
    latent = heads * head_dim
    projections = 4 * 2.0 * hidden * latent
    scores = 2 * 2.0 * (seq / 2.0) * head_dim * heads
    swiglu = 3 * 2.0 * hidden * width
    return projections + scores + swiglu, 2.0 * hidden * vocab + 2.0 * hidden


def params(hidden, heads, head_dim, width, vocab, layers, **_):
    layer = 4 * hidden * heads * head_dim + 3 * hidden * width + 4 * hidden
    return 2 * vocab * hidden + layers * layer + 2 * hidden + 1  # the final norm, the gate's weight and bias


def model(tokens, layers, loops, **shapes):
    block, head = forward_flops_per_token(**shapes)
    flops = 3.0 * tokens * loops * (layers * block + head)
    return flops, params(layers=layers, **shapes) * 4.0 * 7.0  # w, m, v read and written; the gradient read
