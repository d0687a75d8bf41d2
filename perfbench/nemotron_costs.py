"""Operations and bytes of the ``nemotron3_nano_30b`` configuration's step,
from its shapes (``systems/nemotron_lm_fit.py::layout_dims``: ``layer_kinds``
names each layer's one mixer, ``M`` a Mamba-2 scan, ``*`` attention, ``E``
experts) and from the rows the held experts ran (``rows_held`` a step, all
expert layers together: the count the program writes on ``train.drain``). Each
function returns ``(flops, bytes)`` of what the algorithm needs for ONE
optimizer step, forward and backward; what an implementation recomputes (each
block's forward under ``jax.checkpoint``, the experts' hidden projection, the
scores in the fold's backward, the head's logits) or computes and masks away
is not counted.

``ssd_scan``: the selective scan's own recurrence, whatever implements it (one
position at a time, chunks of any size, a kernel): per position and head the
state's decay, the outer product ``delta x B^T`` added to it and the read-out
``S C``, ``6 x P x N`` operations forward, and twice that backward. Bytes: the
recurrence's inputs read once (``x``, the gate ``z``, ``B`` and ``C`` per
GROUP, 2 bytes each; ``delta`` in float32) and ``y`` written once, forward;
the same again and their gradients backward: three times the forward's. No
chunk size enters: a chunked form's ``[chunk, chunk]`` matrices and chunk
states are its implementation's.

``nope_fold``: the attention layers' causal fold: ``T^2 / 2`` (query, key)
pairs a head and sequence; ``Q K^T`` and ``P V`` forward, ``dV``, ``dP``,
``dQ``, ``dK`` backward: ``6 x 2 x pairs x D``. Bytes: q, o and their
gradients once per QUERY head; k, v and their gradients once per KEY/VALUE
head, 2 bytes each (as ``zaya_costs.attention_fold`` counts them).

``held_experts``: the two grouped matmuls (``relu(x W_up)^2 W_down``) over the
rows routed to the experts held here, forward and twice again backward: ``3 x
2 x rows_held x 2 x hidden x width``. Bytes: each held expert matrix read in
bfloat16 by the forward and by ``dX``, its float32 gradient written once, and
the held rows' activations in bfloat16.

``model``: every matmul of the step and the scan's recurrence, ``3 x`` the
forward's: a Mamba-2 layer's two projections and ``ssd_scan``'s count; an
attention layer's projections and its causal half of the scores; an expert
layer's router and shared expert on every token and the held experts on
``rows_held`` rows; the sliced untied head. The embedding gather, the
convolution's four taps and the element-wise work count nothing. Bytes: the
f32 weights, gradients and AdamW moments.
"""


def _scan_forward(ssm_heads, ssm_head_dim, ssm_groups, ssm_state, **_):
    """``(operations, bytes)`` of the recurrence forward, one position of one layer."""
    inner, bc = ssm_heads * ssm_head_dim, ssm_groups * ssm_state
    return 6.0 * ssm_head_dim * ssm_state * ssm_heads, (3 * inner + 2 * bc) * 2.0 + ssm_heads * 4.0


def ssd_scan(tokens, layer_kinds, **shapes):
    flops, nbytes = _scan_forward(**shapes)
    layers = layer_kinds.count("M")
    return 3.0 * flops * tokens * layers, 3.0 * nbytes * tokens * layers


def nope_fold(batch, seq, heads, kv_heads, head_dim, layer_kinds, **_):
    layers = layer_kinds.count("*")
    flops = 6.0 * 2.0 * (seq * seq / 2.0) * head_dim * heads * batch * layers
    return flops, 4.0 * batch * (heads + kv_heads) * seq * head_dim * 2.0 * layers


def held_experts(rows_held, hidden, width, experts_held, layer_kinds, **_):
    flops = 3.0 * 2.0 * rows_held * 2.0 * hidden * width
    weights = 2.0 * experts_held * hidden * width * layer_kinds.count("E")
    activations = rows_held * (2.0 * hidden + 2.0 * width) * 2.0 * 3.0
    return flops, weights * (2.0 + 2.0 + 4.0) + activations


def forward_flops_per_token(seq, hidden, layer_kinds, ssm_heads, ssm_head_dim, ssm_groups, ssm_state, heads,
                            kv_heads, head_dim, experts, shared_width, vocab, **_):
    """``(all layers without their held experts, head)`` forward FLOPs a token."""
    inner, bc = ssm_heads * ssm_head_dim, ssm_groups * ssm_state
    scan, _ = _scan_forward(ssm_heads, ssm_head_dim, ssm_groups, ssm_state)
    mixers = {
        "M": 2.0 * hidden * (2 * inner + 2 * bc + ssm_heads) + 2.0 * inner * hidden + scan,
        "*": 2.0 * hidden * (heads + 2 * kv_heads) * head_dim + 2.0 * heads * head_dim * hidden
             + 2 * 2.0 * (seq / 2.0) * head_dim * heads,
        "E": 2.0 * hidden * experts + 2 * 2.0 * hidden * shared_width,
    }
    return sum(mixers[kind] for kind in layer_kinds), 2.0 * hidden * vocab


def params(hidden, layer_kinds, ssm_heads, ssm_head_dim, ssm_groups, ssm_state, conv_kernel, heads, kv_heads,
           head_dim, experts, experts_held, width, shared_width, vocab, **_):
    inner = ssm_heads * ssm_head_dim
    conv = inner + 2 * ssm_groups * ssm_state
    mixers = {
        "M": hidden * (inner + conv + ssm_heads) + (conv_kernel + 1) * conv + 3 * ssm_heads + inner + inner * hidden,
        "*": 2 * hidden * (heads + kv_heads) * head_dim,
        "E": hidden * experts + experts + 2 * hidden * shared_width + 2 * experts_held * hidden * width,
    }
    return 2 * vocab * hidden + hidden + sum(mixers[kind] + hidden for kind in layer_kinds)


def model(tokens, rows_held, **shapes):
    layers, head = forward_flops_per_token(**shapes)
    experts = rows_held * 2 * 2.0 * shapes["hidden"] * shapes["width"]
    flops = 3.0 * (tokens * (layers + head) + experts)
    return flops, params(**shapes) * 4.0 * 7.0  # w, m, v read and written; the gradient read
