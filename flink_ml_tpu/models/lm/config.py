"""The decoder LM's sizes and its parameter tree, shared by the stage
(``decoder_lm.py``) and the plain references (``reference.py``,
``reference_zaya.py``, ``reference_ouro.py``, ``reference_laguna.py``,
``reference_nemotron.py``) so that one set of weights can be handed to both.

One stack, five kinds of block (``LMConfig.block``). The tree: ``{"embed": [V,
d], "layers": [layer, ...], "final_norm": [d], "lm_head": [d, V]}``; a tied
head (``LMConfig.tied``) has no ``lm_head``: the head is ``embed`` transposed;
the ``ouro`` kind adds the exit gate ``"exit_gate_w": [d, 1], "exit_gate_b":
[1]`` after them.
A matrix maps ``x @ W`` (``[in, out]``: the transpose of a ``torch.nn.Linear``
weight). ``H`` below is the number of experts HELD here (``experts_held``, or
all ``n_experts``): experts ``first_held .. first_held + H`` of the ``E`` the
router chooses among.

``olmoe``: ``layer = {"attn_norm": [d], "wq"/"wk"/"wv"/"wo": [d, d],
"q_norm"/"k_norm": [d], "ffn_norm": [d], "router": [d, E], "w_gate"/"w_up":
[H, d, h], "w_down": [H, h, d]}``.

``zaya`` (compressed convolutional attention and an MLP router; the equations
are in ``reference_zaya.py``), with ``a = n_heads * head_dim`` the attention
latent, ``c = n_kv_heads * head_dim``, ``g = n_heads + n_kv_heads`` and ``r =
router_width``: per sublayer ``s`` in ``attn``, ``ffn`` the norm ``s_norm [d]``
and the residual scaling ``s_res_scale``, ``s_res_bias``, ``s_out_scale``,
``s_out_bias`` ``[d]``; ``"wq": [d, a], "wk": [d, c], "wv1"/"wv2": [d,
head_dim], "conv0_w": [2, a + c], "conv0_b": [a + c], "conv1_w": [2, g,
head_dim, head_dim], "conv1_b": [g, head_dim], "k_temp": [n_kv_heads], "wo":
[a, d]``; ``"router_in": [d, r], "router_gamma": [r]`` (layers past the first),
``"router_norm": [r], "router_w1"/"router_w2": [r, r], "router_w3": [r, E]``;
the experts as above.

``ouro`` (a dense sandwich-norm layer, run ``loops`` times over the same
leaves; the equations are in ``reference_ouro.py``), with ``a = n_heads *
head_dim`` and ``h = expert_width`` the width of the one dense SwiGLU:
``"attn_norm": [d], "wq"/"wk"/"wv": [d, a], "wo": [a, d], "attn_out_norm":
[d], "ffn_norm": [d], "w_gate"/"w_up": [d, h], "w_down": [h, d],
"ffn_out_norm": [d]``. It has no experts: ``n_experts`` and ``top_k`` are 0.

``laguna`` (layers that differ inside one stack; the equations are in
``reference_laguna.py``): layer ``i`` has ``H_i = layer_heads[i]`` query heads
on ``n_kv_heads`` key/value heads, ``a_i = H_i * head_dim``, ``c = n_kv_heads *
head_dim``: ``"attn_norm": [d], "wq": [d, a_i], "wk"/"wv": [d, c],
"head_gate": [d, H_i], "wo": [a_i, d], "ffn_norm": [d]``; then, in the first
``n_dense`` layers, one dense SwiGLU ``"w_gate"/"w_up": [d, dense_width],
"w_down": [dense_width, d]``, and in the others ``"router": [d, E],
"router_bias": [E]`` (added to the scores where the experts are CHOSEN and
nowhere else: no gradient reaches it), the shared expert ``"shared_gate"/
"shared_up": [d, shared_width], "shared_down": [shared_width, d]`` and the
held experts as above. Which layers attend through a sliding window
(``layer_windows[i]`` keys, 0: full attention) changes no leaf.

``nemotron_h`` (a layer is ONE mixer behind one norm, its kind
``layer_kinds[i]``; the equations are in ``reference_nemotron.py``): every
layer has ``"norm": [d]``; then a Mamba-2 layer (``M``), with ``i = ssm_heads *
ssm_head_dim`` its inner width and ``c = i + 2 * ssm_groups * ssm_state`` the
convolved channels: ``"in_proj": [d, i + c + ssm_heads]`` (gate ``z``, then
``x``, ``B``, ``C``, then the step size), ``"conv_w": [conv_kernel, c]`` (tap
``conv_kernel - 1`` reads the position itself), ``"conv_b": [c]``,
``"dt_bias"``, ``"A_log"``, ``"D"``: ``[ssm_heads]``, ``"gate_norm": [i]``,
``"out_proj": [i, d]``; an attention layer (``*``), ``n_heads`` query heads on
``n_kv_heads`` of ``head_dim``: ``"wq": [d, a], "wk"/"wv": [d, c], "wo": [a,
d]``; an expert layer (``E``): ``"router": [d, E], "router_bias": [E]`` (as
``laguna``'s), the shared expert ``"shared_up": [d, shared_width],
"shared_down": [shared_width, d]`` and the held experts ``"w_up": [H, d, h],
"w_down": [H, h, d]``: two matrices an expert, ``down(relu(up(x))^2)``.
"""
from __future__ import annotations

from typing import List, NamedTuple, Tuple

__all__ = ["LMConfig", "BLOCKS", "MIXERS", "param_shapes", "num_params", "ONES", "ZEROS", "NORMAL", "SMALL",
           "SMALL_SCALE", "DT_BIAS", "A_LOG", "DT_RANGE", "DT_FLOOR", "A_RANGE"]

BLOCKS = ("olmoe", "zaya", "ouro", "laguna", "nemotron_h")
#: The ``nemotron_h`` stack's layer kinds, as its published pattern spells them:
#: a Mamba-2 scan, attention without a position encoding, relu² experts.
MIXERS = ("M", "*", "E")
#: How a leaf starts: at one (norm weights, scales), at zero (biases, the
#: router's depth-averaging weight), at ``init_std * normal``, or - the zaya
#: block's attention output projection - at ``SMALL_SCALE * init_std * normal``.
#: At the full width the attention sublayer's output (a causal running mean of
#: the values, alike from one position to the next) is 30 times the 0.02-wide
#: embedding; six blocks on, nearly every token's router input is that mean and
#: one expert takes 5 to 6.5 times its share at step 1 (chip runs, PERF.md PR
#: 30). At a fiftieth the two are level and what is left is the data's own skew.
ONES, ZEROS, NORMAL, SMALL = "ones", "zeros", "normal", "small"
SMALL_SCALE = 0.02
#: A Mamba-2 layer's two leaves that start from a uniform draw ``u`` of the
#: leaf's own stream: the step size's bias at the inverse softplus of ``dt =
#: max(exp(log DT_RANGE[0] + u log(DT_RANGE[1] / DT_RANGE[0])), DT_FLOOR)``
#: (``time_step_min``, ``_max``, ``_floor`` of the published file), and ``A_log``
#: at ``log(A_RANGE[0] + u (A_RANGE[1] - A_RANGE[0]))``.
DT_BIAS, A_LOG = "dt_bias", "a_log"
DT_RANGE, DT_FLOOR, A_RANGE = (1e-3, 1e-1), 1e-4, (1.0, 16.0)


class LMConfig(NamedTuple):
    n_layers: int
    hidden: int
    n_heads: int
    n_experts: int
    top_k: int
    expert_width: int
    vocab: int
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    aux_coef: float = 0.01
    block: str = "olmoe"
    tied: bool = False
    experts_held: int = 0  # 0: all of them
    first_held: int = 0
    # the zaya block's own sizes
    n_kv_heads: int = 0  # 0: as many as query heads
    head_size: int = 0  # 0: hidden / n_heads
    rope_fraction: float = 1.0  # share of each head's channels RoPE turns
    router_width: int = 0
    # the ouro block's own: passes of the stack over the same leaves, and the
    # weight of the exit distribution's entropy in the loss
    loops: int = 1
    exit_beta: float = 0.0
    # the laguna block's own: query heads and sliding window (keys; 0: full
    # attention) layer by layer, the leading dense layers and their width, the
    # shared expert's width, the scale of the renormalised sigmoid gates, the
    # windowed layers' RoPE base (all channels; the full layers turn
    # ``rope_fraction`` of them at ``rope_theta``) and the full layers' YaRN:
    # ``(factor, original length, beta_fast, beta_slow, attention_factor)``
    layer_heads: Tuple[int, ...] = ()
    layer_windows: Tuple[int, ...] = ()
    n_dense: int = 0
    dense_width: int = 0
    shared_width: int = 0
    routed_scale: float = 0.0
    window_rope_theta: float = 10000.0
    yarn: Tuple[float, ...] = ()
    # the nemotron_h block's own: each layer's one mixer (``MIXERS``), and the
    # Mamba-2 layers' sizes: heads, channels a head, groups that share one B and
    # C, the state's width, the convolution's taps, the scan's chunk
    layer_kinds: Tuple[str, ...] = ()
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 0
    ssm_state: int = 0
    conv_kernel: int = 0
    chunk: int = 0

    @property
    def head_dim(self) -> int:
        return self.head_size or self.hidden // self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def held(self) -> int:
        return self.experts_held or self.n_experts


def _expert_leaves(cfg: LMConfig):
    d, e, h = cfg.hidden, cfg.held, cfg.expert_width
    return (("w_gate", (e, d, h), NORMAL), ("w_up", (e, d, h), NORMAL), ("w_down", (e, h, d), NORMAL))


def _olmoe_leaves(cfg: LMConfig, i: int):
    d = cfg.hidden
    return (
        ("attn_norm", (d,), ONES), ("wq", (d, d), NORMAL), ("wk", (d, d), NORMAL), ("wv", (d, d), NORMAL),
        ("wo", (d, d), NORMAL), ("q_norm", (d,), ONES), ("k_norm", (d,), ONES), ("ffn_norm", (d,), ONES),
        ("router", (d, cfg.n_experts), NORMAL),
    ) + _expert_leaves(cfg)


def _residual_scaling(sub: str, d: int):
    return ((f"{sub}_res_scale", (d,), ONES), (f"{sub}_res_bias", (d,), ZEROS),
            (f"{sub}_out_scale", (d,), ONES), (f"{sub}_out_bias", (d,), ZEROS))


def _zaya_leaves(cfg: LMConfig, i: int):
    d, hd, r = cfg.hidden, cfg.head_dim, cfg.router_width
    a, c, g = cfg.n_heads * hd, cfg.kv_heads * hd, cfg.n_heads + cfg.kv_heads
    gamma = (("router_gamma", (r,), ZEROS),) if i else ()  # the first layer has no layer before it
    return (
        (("attn_norm", (d,), ONES),) + _residual_scaling("attn", d) + (
            ("wq", (d, a), NORMAL), ("wk", (d, c), NORMAL), ("wv1", (d, hd), NORMAL), ("wv2", (d, hd), NORMAL),
            ("conv0_w", (2, a + c), NORMAL), ("conv0_b", (a + c,), ZEROS),
            ("conv1_w", (2, g, hd, hd), NORMAL), ("conv1_b", (g, hd), ZEROS),
            ("k_temp", (cfg.kv_heads,), ONES), ("wo", (a, d), SMALL),
            ("ffn_norm", (d,), ONES),
        ) + _residual_scaling("ffn", d) + (("router_in", (d, r), NORMAL),) + gamma + (
            ("router_norm", (r,), ONES), ("router_w1", (r, r), NORMAL), ("router_w2", (r, r), NORMAL),
            ("router_w3", (r, cfg.n_experts), NORMAL),
        ) + _expert_leaves(cfg)
    )


def _ouro_leaves(cfg: LMConfig, i: int):
    d, a, h = cfg.hidden, cfg.n_heads * cfg.head_dim, cfg.expert_width
    return (
        ("attn_norm", (d,), ONES), ("wq", (d, a), NORMAL), ("wk", (d, a), NORMAL), ("wv", (d, a), NORMAL),
        ("wo", (a, d), NORMAL), ("attn_out_norm", (d,), ONES), ("ffn_norm", (d,), ONES),
        ("w_gate", (d, h), NORMAL), ("w_up", (d, h), NORMAL), ("w_down", (h, d), NORMAL),
        ("ffn_out_norm", (d,), ONES),
    )


def _laguna_leaves(cfg: LMConfig, i: int):
    d, hd, heads = cfg.hidden, cfg.head_dim, cfg.layer_heads[i]
    a, c = heads * hd, cfg.kv_heads * hd
    attention = (
        ("attn_norm", (d,), ONES), ("wq", (d, a), NORMAL), ("wk", (d, c), NORMAL), ("wv", (d, c), NORMAL),
        ("head_gate", (d, heads), NORMAL), ("wo", (a, d), NORMAL), ("ffn_norm", (d,), ONES),
    )
    if i < cfg.n_dense:
        h = cfg.dense_width
        return attention + (("w_gate", (d, h), NORMAL), ("w_up", (d, h), NORMAL), ("w_down", (h, d), NORMAL))
    s = cfg.shared_width
    return attention + (
        ("router", (d, cfg.n_experts), NORMAL), ("router_bias", (cfg.n_experts,), ZEROS),
        ("shared_gate", (d, s), NORMAL), ("shared_up", (d, s), NORMAL), ("shared_down", (s, d), NORMAL),
    ) + _expert_leaves(cfg)


def _nemotron_leaves(cfg: LMConfig, i: int):
    d, kind = cfg.hidden, cfg.layer_kinds[i]
    norm = (("norm", (d,), ONES),)
    if kind == "M":
        heads, inner = cfg.ssm_heads, cfg.ssm_heads * cfg.ssm_head_dim
        conv = inner + 2 * cfg.ssm_groups * cfg.ssm_state
        return norm + (
            ("in_proj", (d, inner + conv + heads), NORMAL), ("conv_w", (cfg.conv_kernel, conv), NORMAL),
            ("conv_b", (conv,), ZEROS), ("dt_bias", (heads,), DT_BIAS), ("A_log", (heads,), A_LOG),
            ("D", (heads,), ONES), ("gate_norm", (inner,), ONES), ("out_proj", (inner, d), NORMAL),
        )
    if kind == "*":
        a, c = cfg.n_heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
        return norm + (("wq", (d, a), NORMAL), ("wk", (d, c), NORMAL), ("wv", (d, c), NORMAL), ("wo", (a, d), NORMAL))
    e, h, s = cfg.held, cfg.expert_width, cfg.shared_width
    return norm + (
        ("router", (d, cfg.n_experts), NORMAL), ("router_bias", (cfg.n_experts,), ZEROS),
        ("shared_up", (d, s), NORMAL), ("shared_down", (s, d), NORMAL),
        ("w_up", (e, d, h), NORMAL), ("w_down", (e, h, d), NORMAL),
    )


_LEAVES = {"olmoe": _olmoe_leaves, "zaya": _zaya_leaves, "ouro": _ouro_leaves, "laguna": _laguna_leaves,
           "nemotron_h": _nemotron_leaves}


def param_shapes(cfg: LMConfig) -> List[Tuple[tuple, tuple, str]]:
    """Every leaf as ``(path, shape, init)``, in the one order the initialiser
    numbers them by. ``path`` indexes the tree: ``("layers", 0, "wq")``;
    ``init`` is ``ONES``, ``ZEROS``, ``NORMAL``, ``SMALL``, ``DT_BIAS`` or ``A_LOG``."""
    out = [(("embed",), (cfg.vocab, cfg.hidden), NORMAL)]
    for i in range(cfg.n_layers):
        out += [(("layers", i, name), shape, init) for name, shape, init in _LEAVES[cfg.block](cfg, i)]
    out.append((("final_norm",), (cfg.hidden,), ONES))
    if not cfg.tied:
        out.append((("lm_head",), (cfg.hidden, cfg.vocab), NORMAL))
    if cfg.block == "ouro":  # the exit gate every pass ends in: a linear with a bias
        out += [(("exit_gate_w",), (cfg.hidden, 1), NORMAL), (("exit_gate_b",), (1,), ZEROS)]
    return out


def num_params(cfg: LMConfig) -> int:
    total = 0
    for _, shape, _ in param_shapes(cfg):
        n = 1
        for s in shape:
            n *= s
        total += n
    return total
