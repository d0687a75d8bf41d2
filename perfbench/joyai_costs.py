"""Operations and bytes of the ``joyai_llm_flash`` configuration's step, from
its shapes (``systems/joyai_lm_fit.py::layout_dims``: ``layers`` of the stack,
the first ``dense_layers`` of them dense, and ``mtp_depth`` multi-token-
prediction modules of one more expert layer each) and from the rows the held
experts ran (``rows_held`` a step, all expert layers together, the module's
among them: the count the program writes on ``train.drain``). Each function
returns ``(flops, bytes)`` of what the algorithm needs for ONE optimizer step,
forward and backward; what an implementation recomputes (each layer's forward
under ``jax.checkpoint``, the experts' hidden projections, the scores in the
fold's backward, the head's logits) or computes and masks away is not counted.

``mla_fold``: latent attention's causal fold in every layer and in the module's:
``T^2 / 2`` (query, key) pairs a head and sequence; ``Q K^T`` over the ``nope +
rope`` channels and ``P V`` over the ``v`` channels forward, ``dV``, ``dP``,
``dQ``, ``dK`` backward: ``6 x pairs x ((nope + rope) + v)``. Bytes, 2 each:
q, o and their gradients once a query head; a head's ``nope`` key channels and
its values, and their gradients, once a head; the rotary key and its gradient
once a TOKEN (every head reads the same): the same work whatever implements
the fold, no tile or layout in it.

``held_experts``: the three grouped matmuls over the rows routed to the
experts held here, forward and twice again backward: ``3 x 2 x rows_held x 3 x
hidden x width``. Bytes: each held expert matrix read in bfloat16 by the
forward and by ``dX``, its float32 gradient written once, and the held rows'
activations in bfloat16.

``model``: every matmul of the step, ``3 x`` the forward's: a layer's two
down-projections, two up-projections and output projection, the causal half of
its scores at ``nope + rope`` and of its values at ``v``, the dense SwiGLU of a
leading layer, the router, the shared expert on every token, the held experts
on ``rows_held`` rows; the module's projection of ``[h | e]``, its layer and
its pass over the head; the sliced untied head. The embedding gathers and the
element-wise work count nothing. Bytes: the f32 weights, gradients and AdamW
moments.
"""


def _folding_layers(layers, mtp_depth, **_):
    return layers + mtp_depth


def mla_fold(batch, seq, heads, nope_dim, rope_dim, v_dim, **shapes):
    n = _folding_layers(**shapes)
    pairs = seq * seq / 2.0
    flops = 6.0 * pairs * (nope_dim + rope_dim + v_dim) * heads * batch * n
    per_head = 2.0 * (nope_dim + rope_dim) + 2.0 * v_dim + 2.0 * nope_dim + 2.0 * v_dim  # q, dq; o, do; k, dk; v, dv
    return flops, 2.0 * batch * seq * (heads * per_head + 2.0 * rope_dim) * n


def held_experts(rows_held, hidden, width, experts_held, layers, dense_layers, mtp_depth, **_):
    flops = 3.0 * 2.0 * rows_held * 3.0 * hidden * width
    weights = 3.0 * experts_held * hidden * width * (layers - dense_layers + mtp_depth)
    activations = rows_held * (2.0 * hidden + 3.0 * width) * 2.0 * 3.0
    return flops, weights * (2.0 + 2.0 + 4.0) + activations


def _attention_flops_per_token(seq, hidden, heads, q_rank, kv_rank, nope_dim, rope_dim, v_dim, **_):
    projections = (2.0 * hidden * q_rank + 2.0 * q_rank * heads * (nope_dim + rope_dim)
                   + 2.0 * hidden * (kv_rank + rope_dim) + 2.0 * kv_rank * heads * (nope_dim + v_dim)
                   + 2.0 * heads * v_dim * hidden)
    return projections + 2.0 * (seq / 2.0) * heads * (nope_dim + rope_dim + v_dim)


def forward_flops_per_token(layers, dense_layers, dense_width, hidden, experts, shared_width, mtp_depth, vocab,
                            **shapes):
    """``(all layers and the module without their held experts, the head's passes)`` forward matmul FLOPs a token."""
    attention = _attention_flops_per_token(hidden=hidden, **shapes)
    sparse = attention + 2.0 * hidden * experts + 3 * 2.0 * hidden * shared_width
    stack = dense_layers * (attention + 3 * 2.0 * hidden * dense_width) + (layers - dense_layers) * sparse
    module = mtp_depth * (2.0 * 2 * hidden * hidden + sparse)
    return stack + module, (1 + mtp_depth) * 2.0 * hidden * vocab


def params(layers, dense_layers, dense_width, hidden, heads, q_rank, kv_rank, nope_dim, rope_dim, v_dim, experts,
           experts_held, width, shared_width, mtp_depth, vocab, **_):
    attention = (hidden * q_rank + q_rank + q_rank * heads * (nope_dim + rope_dim) + hidden * (kv_rank + rope_dim)
                 + kv_rank + kv_rank * heads * (nope_dim + v_dim) + heads * v_dim * hidden + 2 * hidden)
    sparse = attention + hidden * experts + experts + 3 * hidden * shared_width + 3 * experts_held * hidden * width
    stack = dense_layers * (attention + 3 * hidden * dense_width) + (layers - dense_layers) * sparse
    return 2 * vocab * hidden + hidden + stack + mtp_depth * (3 * hidden + 2 * hidden * hidden + sparse)


def model(tokens, rows_held, **shapes):
    layers, head = forward_flops_per_token(**shapes)
    experts = rows_held * 3 * 2.0 * shapes["hidden"] * shapes["width"]
    flops = 3.0 * (tokens * (layers + head) + experts)
    return flops, params(**shapes) * 4.0 * 7.0  # w, m, v read and written; the gradient read
