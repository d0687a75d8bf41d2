"""The decoder LM's sizes, what each layer of its stack IS, and its parameter
tree, shared by the stage (``decoder_lm.py``) and the plain references
(``reference.py``, ``reference_zaya.py``, ``reference_ouro.py``,
``reference_laguna.py``, ``reference_nemotron.py``, ``reference_joyai.py``,
``reference_sdar.py``, ``reference_solar.py``, ``reference_olmo_hybrid.py``) so
that one set of weights can be handed to both.

``layers(cfg)`` gives one hashable record a layer (``Layer``): a MIXER, a
FEED-FORWARD (either may be absent), the stream's width and the norms'
epsilon, and how a sublayer joins the residual: ``x + y``, or (``scaled``) a
learned per-channel scale and bias on both; a sublayer reads the stream
behind its norm (``norm``) or, no such leaf named, as it is, and its output
joins as it is or behind a norm of its own (``out_norm``). The parameter tree
(``param_shapes``), the forward (``decoder_lm._layer``), the fit's counts and
the stage's checks all read that record and nothing else about a layer.

``LMConfig.block`` names one of nine PRESETS over that description
(``_PRESETS``: the only place that names a model), each a published stack:

========== ================================================= ==========================================
kind       mixer                                             feed-forward
========== ================================================= ==========================================
olmoe      attention, QK-norm, RoPE on the whole head        experts, linear router
zaya       CCA, RoPE on part of each head; ``scaled`` joins  experts, MLP router with the carried state
ouro       attention, RoPE, a norm on the output             dense, a norm on the output
laguna     attention at ``layer_heads[i]`` heads with head   dense (the first ``n_dense`` layers), then
           gates; windowed (``layer_windows[i]``) with RoPE  experts with sigmoid gates beside a shared
           at ``window_rope_theta``, or full with part of    expert
           each head turned under YaRN
nemotron_h ONE sublayer a layer behind the norm ``norm``, by ``layer_kinds[i]``: ``M`` a Mamba-2 scan,
           ``*`` attention without rotation, ``E`` ungated experts with sigmoid gates beside a shared one
joyai      latent attention: queries, keys and values      dense (the first ``n_dense`` layers), then
           rebuilt from low-rank latents, one rotary key a   experts with sigmoid gates beside a shared
           token under every head, RoPE on interleaved pairs expert (laguna's record)
sdar       attention on grouped queries, a QK-norm over EACH experts, linear router, the chosen softmax
           head's channels, RoPE on the whole head, under    gates renormalised over all ``top_k``
           the block-diffusion mask of a doubled sequence
solar_open2 the gated delta rule with a decay a key channel   experts with sigmoid gates beside a shared
           (``KDA``); in the layers ``gqa_layers`` names,    expert (laguna's record), in every layer
           attention on grouped queries without rotation
           under an element-wise sigmoid gate on its output
olmo_hybrid the gated delta rule with ONE decay a head on     dense; NO norm before either sublayer:
           heads wider in their values than in their keys    each one's OUTPUT is normed before it joins
           (``GatedDelta``); in the layers ``gqa_layers``
           names, attention without rotation under a
           QK-norm over the whole projection
========== ================================================= ==========================================

``sdar`` is also the one kind whose OBJECTIVE is not next-token prediction:
``LMConfig.block_length`` > 0 trains by block diffusion (``reference_sdar.py``):
the stack runs over ``[x ; x~]``, the ``T`` tokens and a copy whose tokens are
masked (``mask_id``) with a probability drawn a sequence, and the noised
half's positions are scored on their own tokens.

The tree: ``{"embed": [V, d], "layers": [layer, ...], "final_norm": [d],
"lm_head": [d, V]}``; a tied head (``LMConfig.tied``) has no ``lm_head``: the
head is ``embed`` transposed; a stack whose passes end in an exit gate
(``exit_gate``: ``ouro``, run ``loops`` times over the same leaves) adds
``"exit_gate_w": [d, 1], "exit_gate_b": [1]`` after them; a multi-token-
prediction module (``LMConfig.mtp_depth`` 1: a training objective behind the
stack, ``reference_joyai.py``; ``mtp_layer``) is ONE more entry after those,
``"mtp": {"enorm": [d], "hnorm": [d], "eh_proj": [2 d, d], "layer": {one more
layer's leaves, the record of the stack's last}, "norm": [d]}``: it is no
layer of the stack, and shares ``embed`` and the head. A matrix maps ``x @
W`` (``[in, out]``: the transpose of a ``torch.nn.Linear`` weight).

A layer's leaves (``leaves``), mixer first: each sublayer's norm ``[d]`` (its
name is the record's: ``attn_norm``, ``ffn_norm``; ``norm`` in ``nemotron_h``),
then under ``scaled`` joins ``s_res_scale``, ``s_res_bias``, ``s_out_scale``,
``s_out_bias`` ``[d]`` (``s`` = ``attn``, ``ffn``), then the sublayer's own.
The sublayers, with ``a = heads * head_dim`` and ``c = kv_heads * head_dim``:

- ``Attention`` (causal softmax attention of ``heads`` query heads on
  ``kv_heads`` key/value heads through the fused fold; a ``Rotation`` of q and
  k or none; a sliding ``window`` of keys or 0; or, ``diffusion_block`` > 0, the
  block-diffusion mask over a doubled sequence in blocks of that many
  positions): ``"wq": [d, a], "wk"/"wv": [d, c]``, ``"head_gate": [d, heads]``
  under a sigmoid gate a head on the output, ``"wg": [d, a]`` under an
  element-wise sigmoid gate on it (``out_gate``: read from the normed input,
  applied before ``wo``), ``"wo": [a, d]``, ``"q_norm": [a],
  "k_norm": [c]`` under a QK-norm over the whole projection (``qk_norm``
  ``"projection"``) or ``"q_norm"/"k_norm": [head_dim]`` under one over each
  head's channels (``"head"``), the output's norm ``[d]`` if any
  (``attn_out_norm``).
- ``LatentAttention`` (multi-head latent attention, ``reference_joyai.py``:
  ``heads`` heads whose queries and keys are ``nope_dim + rope_dim`` wide and
  whose values ``v_dim``, through the same fold), ``r = rope_dim``: ``"wq_a":
  [d, q_rank], "q_a_norm": [q_rank], "wq_b": [q_rank, heads * (nope_dim + r)],
  "wkv_a": [d, kv_rank + r]`` (the latent, then the one rotary key a token),
  ``"kv_a_norm": [kv_rank], "wkv_b": [kv_rank, heads * (nope_dim + v_dim)]``
  (a head's keys without position, then its values), ``"wo": [heads * v_dim,
  d]``.
- ``CCA`` (ZAYA's compressed convolutional attention; ``reference_zaya.py`` has
  the equations), ``g = heads + kv_heads``: ``"wq": [d, a], "wk": [d, c],
  "wv1"/"wv2": [d, head_dim], "conv0_w": [2, a + c], "conv0_b": [a + c],
  "conv1_w": [2, g, head_dim, head_dim], "conv1_b": [g, head_dim], "k_temp":
  [kv_heads], "wo": [a, d]``.
- ``Mamba2`` (a selective state-space scan behind a short causal convolution;
  ``reference_nemotron.py``), ``i = heads * head_dim`` its inner width and ``c
  = i + 2 * groups * state`` the convolved channels: ``"in_proj": [d, i + c +
  heads]`` (gate ``z``, then ``x``, ``B``, ``C``, then the step size),
  ``"conv_w": [conv_kernel, c]`` (tap ``conv_kernel - 1`` reads the position
  itself), ``"conv_b": [c]``, ``"dt_bias"``, ``"A_log"``, ``"D"``: ``[heads]``,
  ``"gate_norm": [i]``, ``"out_proj": [i, d]``.
- ``KDA`` (the gated delta rule with a log-decay of its own on every key
  channel behind a short causal convolution; ``reference_solar.py``), ``i =
  heads * head_dim`` and the gates' rank ``r = head_dim``: ``"wq"/"wk"/"wv": [d,
  i]``, ``"conv_q"/"conv_k"/"conv_v": [conv_kernel, i]`` (no bias; tap
  ``conv_kernel - 1`` reads the position itself), the decay gate ``"Fa": [d,
  r], "Fb": [r, i], "A_log": [heads], "dt_bias": [i]``, the correction's
  strength ``"Wb": [d, heads]``, the output gate ``"Ga": [d, r], "Gb": [r,
  i]``, the output's norm over each head's channels ``"o_norm": [head_dim]``,
  ``"wo": [i, d]``. ``heads`` may be a chip's share of the layer's: ``wo``'s
  output is then the held heads' part of the sum.
- ``GatedDelta`` (the gated delta rule with ONE log-decay a head and position
  on heads of ``key_dim`` key and ``value_dim`` value channels behind a short
  causal convolution; ``reference_olmo_hybrid.py``), ``a = heads * key_dim``
  and ``c = heads * value_dim``: ``"wq"/"wk": [d, a], "wv": [d, c]``,
  ``"conv_q"/"conv_k": [conv_kernel, a], "conv_v": [conv_kernel, c]`` (no bias;
  tap ``conv_kernel - 1`` reads the position itself), the decay ``"Wa": [d,
  heads], "A_log"/"dt_bias": [heads]``, the correction's strength ``"Wb": [d,
  heads]``, the output gate ``"wg": [d, c]``, the output's norm over each
  head's channels ``"o_norm": [value_dim]``, ``"wo": [c, d]``, the norm of what
  joins the stream ``[d]`` (``out_norm``). ``heads`` may be a chip's share of
  the layer's, as ``KDA``'s.
- ``Dense`` (one SwiGLU): ``"w_gate"/"w_up": [d, width], "w_down": [width,
  d]``, the output's norm ``[d]`` if any (``ffn_out_norm``).
- ``Experts`` (``top_k`` of ``E`` routed experts, ``H`` of them HELD here:
  experts ``first_held .. first_held + H``; ``LMConfig.experts_held``, or all):
  the router ``"router": [d, E]`` or, an MLP of width ``r`` whose hidden state
  a layer hands the next, ``"router_in": [d, r], "router_gamma": [r]`` (where
  there is a layer before), ``"router_norm": [r], "router_w1"/"router_w2": [r,
  r], "router_w3": [r, E]``; the softmax probabilities of the chosen are kept
  as they are, or (``renormalise``) divided by their sum over all ``top_k``
  chosen, or, under ``routed_scale``, sigmoid gates renormalised and scaled
  with ``"router_bias": [E]`` added to the scores where the experts are CHOSEN
  and nowhere else (no gradient reaches it); a shared expert every token
  passes, ``"shared_gate"/"shared_up": [d, s], "shared_down": [s, d]``; the held
  ones ``"w_gate"/"w_up": [H, d, h], "w_down": [H, h, d]``. Ungated, an expert
  is ``down(relu(up(x))^2)``: the ``_gate`` matrices are not there.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, NamedTuple, Optional, Tuple, Union

__all__ = ["LMConfig", "BLOCKS", "MIXERS", "Rotation", "Attention", "LatentAttention", "CCA", "Mamba2", "KDA", "GatedDelta",
           "Dense", "Experts", "Layer", "layers", "exit_gate", "mtp_layer", "leaves", "param_shapes", "num_params", "ONES",
           "ZEROS", "NORMAL", "SMALL",
           "SMALL_SCALE", "DT_BIAS", "A_LOG", "DT_RANGE", "DT_FLOOR", "A_RANGE", "NOISE_EPS"]

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
#: A Mamba-2 layer's (and a delta-rule layer's) two leaves that start from a uniform draw ``u`` of the
#: leaf's own stream: the step size's bias at the inverse softplus of ``dt =
#: max(exp(log DT_RANGE[0] + u log(DT_RANGE[1] / DT_RANGE[0])), DT_FLOOR)``
#: (``time_step_min``, ``_max``, ``_floor`` of the published file), and ``A_log``
#: at ``log(A_RANGE[0] + u (A_RANGE[1] - A_RANGE[0]))``.
DT_BIAS, A_LOG = "dt_bias", "a_log"
DT_RANGE, DT_FLOOR, A_RANGE = (1e-3, 1e-1), 1e-4, (1.0, 16.0)
#: Block diffusion's linear schedule: a sequence's tokens are masked with probability ``p = (1 - NOISE_EPS) t +
#: NOISE_EPS``, ``t`` uniform in [0, 1): the floor keeps the loss's ``1 / p`` finite.
NOISE_EPS = 1e-3


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
    # the joyai block's own: latent attention's sizes (the queries' and the keys-and-values' latent widths, a
    # head's channels without position, its rotary channels, its value channels), and the multi-token-prediction
    # module behind the stack: how many (0 or 1), and the weight of its loss
    q_rank: int = 0
    kv_rank: int = 0
    nope_dim: int = 0
    rope_dim: int = 0
    v_dim: int = 0
    mtp_depth: int = 0
    mtp_coef: float = 0.0
    # the block-diffusion objective ('sdar'): positions a block (0: next-token prediction under the causal mask) and
    # the id a masked token is replaced with
    block_length: int = 0
    mask_id: int = 0
    # the solar_open2 block's own: the layers that attend (the others run the gated delta rule), and the delta rule's
    # heads and channels a head (its convolution's taps and its chunk are ``conv_kernel`` and ``chunk``)
    gqa_layers: Tuple[int, ...] = ()
    kda_heads: int = 0
    kda_head_dim: int = 0
    # the olmo_hybrid block's own beside those: a delta-rule head's value channels (``kda_head_dim`` counts its key
    # channels)
    kda_value_dim: int = 0

    @property
    def head_dim(self) -> int:
        return self.head_size or self.hidden // self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def held(self) -> int:
        return self.experts_held or self.n_experts


# -- what a layer is ------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Rotation:
    """RoPE on the first ``channels`` of each head (all of them: the whole
    head) at base ``theta``, stretched by YaRN (``yarn``: factor, original
    length, beta_fast, beta_slow, attention factor) or, empty, not. Pair ``j``
    is channels ``j`` and ``j + channels / 2`` (rotate-half) or, ``interleaved``,
    ``2 j`` and ``2 j + 1``."""
    channels: int
    theta: float
    yarn: Tuple[float, ...] = ()
    interleaved: bool = False


@dataclasses.dataclass(frozen=True)
class Attention:
    heads: int
    kv_heads: int
    head_dim: int
    rotation: Optional[Rotation] = None
    window: int = 0  # keys each query keeps, its own among them; 0: all before it
    qk_norm: str = ""  # an RMS norm on q and k: "projection" over all heads' channels together, "head" over each's
    head_gate: bool = False
    out_gate: bool = False  # an element-wise sigmoid gate on the heads' output, read from the normed input
    norm: str = "attn_norm"
    out_norm: str = ""  # the leaf of the norm on the output; empty: none
    diffusion_block: int = 0  # > 0: the sequence is doubled, [x ; x~], under the block-diffusion mask in such blocks


@dataclasses.dataclass(frozen=True)
class LatentAttention:
    heads: int
    q_rank: int  # the queries' latent
    kv_rank: int  # the latent a token's keys and values are rebuilt from
    nope_dim: int  # a head's query and key channels without position
    rope_dim: int  # its rotary channels: the key's are one tensor a token, under every head
    v_dim: int
    rotation: Rotation  # of the ``rope_dim`` channels, which are their own tensor
    norm: str = "attn_norm"

    @property
    def head_dim(self) -> int:
        return self.nope_dim + self.rope_dim


@dataclasses.dataclass(frozen=True)
class CCA:
    heads: int
    kv_heads: int
    head_dim: int
    rotation: Rotation
    norm: str = "attn_norm"


@dataclasses.dataclass(frozen=True)
class Mamba2:
    heads: int
    head_dim: int
    groups: int  # of heads that share one B and C, and of the gated norm
    state: int
    conv_kernel: int
    chunk: int
    norm: str = "norm"


@dataclasses.dataclass(frozen=True)
class KDA:
    heads: int  # held here: all of the layer's, or a chip's share of them
    head_dim: int  # key and value channels a head, and the rank of the decay and output gates
    conv_kernel: int
    chunk: int
    norm: str = "attn_norm"

    @property
    def conv_channels(self) -> int:  # a head's convolved channels: its queries', keys' and values'
        return 3 * self.head_dim

    @property
    def state_size(self) -> int:  # the entries of a head's state
        return self.head_dim ** 2


@dataclasses.dataclass(frozen=True)
class GatedDelta:
    heads: int  # held here: all of the layer's, or a chip's share of them
    key_dim: int  # key (and query) channels a head
    value_dim: int  # value channels a head: the state is [key_dim, value_dim]
    conv_kernel: int
    chunk: int
    norm: str = ""  # the leaf of the norm before the mixer; empty: it reads the stream as it is
    out_norm: str = ""  # the leaf of the norm on the output; empty: none

    @property
    def conv_channels(self) -> int:
        return 2 * self.key_dim + self.value_dim

    @property
    def state_size(self) -> int:
        return self.key_dim * self.value_dim


@dataclasses.dataclass(frozen=True)
class Dense:
    width: int
    norm: str = "ffn_norm"
    out_norm: str = ""


@dataclasses.dataclass(frozen=True)
class Experts:
    n_experts: int
    held: int
    first_held: int
    top_k: int
    width: int
    router_width: int = 0  # 0: a linear router; else the width of the MLP that routes
    carried: bool = False  # the layer before hands the router its hidden state
    routed_scale: float = 0.0  # 0: softmax probabilities kept as they are; else sigmoid gates renormalised, times this
    renormalise: bool = False  # softmax gates over their sum over all top_k chosen (with routed_scale 0)
    gated: bool = True  # SwiGLU experts; ungated: down(relu(up(x))^2)
    shared_width: Optional[int] = None  # None: no shared expert
    norm: str = "ffn_norm"


@dataclasses.dataclass(frozen=True)
class Layer:
    hidden: int
    eps: float
    mixer: Union[Attention, LatentAttention, CCA, Mamba2, KDA, GatedDelta, None]
    ffn: Union[Dense, Experts, None]
    scaled: bool = False  # a learned scale and bias on the residual and on each sublayer's output


def _experts(cfg: LMConfig, **own) -> Experts:
    return Experts(cfg.n_experts, cfg.held, cfg.first_held, cfg.top_k, cfg.expert_width, **own)


def _olmoe(cfg: LMConfig):
    mixer = Attention(cfg.n_heads, cfg.kv_heads, cfg.head_dim, Rotation(cfg.head_dim, cfg.rope_theta),
                      qk_norm="projection")
    return (Layer(cfg.hidden, cfg.norm_eps, mixer, _experts(cfg)),) * cfg.n_layers


def _zaya(cfg: LMConfig):
    mixer = CCA(cfg.n_heads, cfg.kv_heads, cfg.head_dim,
                Rotation(int(cfg.head_dim * cfg.rope_fraction), cfg.rope_theta))
    return tuple(Layer(cfg.hidden, cfg.norm_eps, mixer,  # the first layer has no layer before it
                       _experts(cfg, router_width=cfg.router_width, carried=i > 0), scaled=True)
                 for i in range(cfg.n_layers))


def _ouro(cfg: LMConfig):
    mixer = Attention(cfg.n_heads, cfg.kv_heads, cfg.head_dim, Rotation(cfg.head_dim, cfg.rope_theta),
                      out_norm="attn_out_norm")
    return (Layer(cfg.hidden, cfg.norm_eps, mixer, Dense(cfg.expert_width, out_norm="ffn_out_norm")),) * cfg.n_layers


def _laguna(cfg: LMConfig):
    # the heads differ from layer to layer, so their size is the stated one (0: not stated)
    full = Rotation(int(cfg.head_size * cfg.rope_fraction), cfg.rope_theta, cfg.yarn)
    windowed = Rotation(cfg.head_size, cfg.window_rope_theta)
    sparse = _experts(cfg, routed_scale=cfg.routed_scale, shared_width=cfg.shared_width)
    return tuple(
        Layer(cfg.hidden, cfg.norm_eps,
              Attention(cfg.layer_heads[i], cfg.kv_heads, cfg.head_size,
                        windowed if cfg.layer_windows[i] else full, cfg.layer_windows[i], head_gate=True),
              Dense(cfg.dense_width) if i < cfg.n_dense else sparse)
        for i in range(cfg.n_layers))


def _nemotron_h(cfg: LMConfig):
    by_letter = {  # MIXERS' letters: (mixer, feed-forward), one of them
        "M": (Mamba2(cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state, cfg.conv_kernel, cfg.chunk), None),
        "*": (Attention(cfg.n_heads, cfg.kv_heads, cfg.head_size, norm="norm"), None),
        "E": (None, _experts(cfg, routed_scale=cfg.routed_scale, gated=False, shared_width=cfg.shared_width,
                             norm="norm"))}
    return tuple(Layer(cfg.hidden, cfg.norm_eps, *by_letter[cfg.layer_kinds[i]]) for i in range(cfg.n_layers))


def _joyai(cfg: LMConfig):
    mixer = LatentAttention(cfg.n_heads, cfg.q_rank, cfg.kv_rank, cfg.nope_dim, cfg.rope_dim, cfg.v_dim,
                            Rotation(cfg.rope_dim, cfg.rope_theta, interleaved=True))
    sparse = _experts(cfg, routed_scale=cfg.routed_scale, shared_width=cfg.shared_width)
    return tuple(Layer(cfg.hidden, cfg.norm_eps, mixer, Dense(cfg.dense_width) if i < cfg.n_dense else sparse)
                 for i in range(cfg.n_layers))


def _sdar(cfg: LMConfig):
    mixer = Attention(cfg.n_heads, cfg.kv_heads, cfg.head_dim, Rotation(cfg.head_dim, cfg.rope_theta), qk_norm="head",
                      diffusion_block=cfg.block_length)
    return (Layer(cfg.hidden, cfg.norm_eps, mixer, _experts(cfg, renormalise=True)),) * cfg.n_layers


def _solar_open2(cfg: LMConfig):
    attention = Attention(cfg.n_heads, cfg.kv_heads, cfg.head_size, out_gate=True)
    delta = KDA(cfg.kda_heads, cfg.kda_head_dim, cfg.conv_kernel, cfg.chunk)
    sparse = _experts(cfg, routed_scale=cfg.routed_scale, shared_width=cfg.shared_width)
    return tuple(Layer(cfg.hidden, cfg.norm_eps, attention if i in cfg.gqa_layers else delta, sparse)
                 for i in range(cfg.n_layers))


def _olmo_hybrid(cfg: LMConfig):
    attention = Attention(cfg.n_heads, cfg.kv_heads, cfg.head_size, qk_norm="projection", norm="",
                          out_norm="attn_out_norm")
    delta = GatedDelta(cfg.kda_heads, cfg.kda_head_dim, cfg.kda_value_dim, cfg.conv_kernel, cfg.chunk,
                       out_norm="attn_out_norm")
    dense = Dense(cfg.expert_width, norm="", out_norm="ffn_out_norm")
    return tuple(Layer(cfg.hidden, cfg.norm_eps, attention if i in cfg.gqa_layers else delta, dense)
                 for i in range(cfg.n_layers))


#: kind -> (its layers, whether every pass of the stack ends in an exit gate: a linear with a bias)
_PRESETS = {"olmoe": (_olmoe, False), "zaya": (_zaya, False), "ouro": (_ouro, True), "laguna": (_laguna, False),
            "nemotron_h": (_nemotron_h, False), "joyai": (_joyai, False), "sdar": (_sdar, False),
            "solar_open2": (_solar_open2, False), "olmo_hybrid": (_olmo_hybrid, False)}
BLOCKS = tuple(_PRESETS)


def layers(cfg: LMConfig) -> Tuple[Layer, ...]:
    """What each layer of the stack is, first to last."""
    return _PRESETS[cfg.block][0](cfg)


def exit_gate(cfg: LMConfig) -> bool:
    return _PRESETS[cfg.block][1]


def mtp_layer(cfg: LMConfig) -> Optional[Layer]:
    """The one layer of the multi-token-prediction module behind the stack (``mtp_depth`` 1), or None: the record of
    the stack's last layer, with leaves of its own."""
    return layers(cfg)[-1] if cfg.mtp_depth else None


# -- the parameter tree ------------------------------------------------------------------


def _matrices(prefix: str, lead: tuple, d: int, h: int, gated: bool):
    """One feed-forward's matrices ``[d, h]``, ``[d, h]``, ``[h, d]`` (ungated: no ``gate``), ``lead`` of them."""
    return tuple((f"{prefix}_{name}", lead + ((h, d) if name == "down" else (d, h)), NORMAL)
                 for name in ("gate", "up", "down") if gated or name != "gate")


def _attention_own(m: Attention, d: int):
    a, c = m.heads * m.head_dim, m.kv_heads * m.head_dim
    return ((("wq", (d, a), NORMAL), ("wk", (d, c), NORMAL), ("wv", (d, c), NORMAL))
            + ((("head_gate", (d, m.heads), NORMAL),) if m.head_gate else ())
            + ((("wg", (d, a), NORMAL),) if m.out_gate else ()) + (("wo", (a, d), NORMAL),)
            + tuple((name, (m.head_dim if m.qk_norm == "head" else width,), ONES)
                    for name, width in (("q_norm", a), ("k_norm", c)) if m.qk_norm)
            + (((m.out_norm, (d,), ONES),) if m.out_norm else ()))


def _latent_own(m: LatentAttention, d: int):
    return (("wq_a", (d, m.q_rank), NORMAL), ("q_a_norm", (m.q_rank,), ONES),
            ("wq_b", (m.q_rank, m.heads * (m.nope_dim + m.rope_dim)), NORMAL),
            ("wkv_a", (d, m.kv_rank + m.rope_dim), NORMAL), ("kv_a_norm", (m.kv_rank,), ONES),
            ("wkv_b", (m.kv_rank, m.heads * (m.nope_dim + m.v_dim)), NORMAL), ("wo", (m.heads * m.v_dim, d), NORMAL))


def _cca_own(m: CCA, d: int):
    hd = m.head_dim
    a, c, g = m.heads * hd, m.kv_heads * hd, m.heads + m.kv_heads
    return (("wq", (d, a), NORMAL), ("wk", (d, c), NORMAL), ("wv1", (d, hd), NORMAL), ("wv2", (d, hd), NORMAL),
            ("conv0_w", (2, a + c), NORMAL), ("conv0_b", (a + c,), ZEROS),
            ("conv1_w", (2, g, hd, hd), NORMAL), ("conv1_b", (g, hd), ZEROS),
            ("k_temp", (m.kv_heads,), ONES), ("wo", (a, d), SMALL))


def _mamba2_own(m: Mamba2, d: int):
    inner = m.heads * m.head_dim
    conv = inner + 2 * m.groups * m.state
    return (("in_proj", (d, inner + conv + m.heads), NORMAL), ("conv_w", (m.conv_kernel, conv), NORMAL),
            ("conv_b", (conv,), ZEROS), ("dt_bias", (m.heads,), DT_BIAS), ("A_log", (m.heads,), A_LOG),
            ("D", (m.heads,), ONES), ("gate_norm", (inner,), ONES), ("out_proj", (inner, d), NORMAL))


def _kda_own(m: KDA, d: int):
    inner, rank = m.heads * m.head_dim, m.head_dim
    return (("wq", (d, inner), NORMAL), ("wk", (d, inner), NORMAL), ("wv", (d, inner), NORMAL),
            ("conv_q", (m.conv_kernel, inner), NORMAL), ("conv_k", (m.conv_kernel, inner), NORMAL),
            ("conv_v", (m.conv_kernel, inner), NORMAL),
            ("Fa", (d, rank), NORMAL), ("Fb", (rank, inner), NORMAL), ("A_log", (m.heads,), A_LOG),
            ("dt_bias", (inner,), DT_BIAS), ("Wb", (d, m.heads), NORMAL),
            ("Ga", (d, rank), NORMAL), ("Gb", (rank, inner), NORMAL), ("o_norm", (m.head_dim,), ONES),
            ("wo", (inner, d), NORMAL))


def _gated_delta_own(m: GatedDelta, d: int):
    keys, values = m.heads * m.key_dim, m.heads * m.value_dim
    return (("wq", (d, keys), NORMAL), ("wk", (d, keys), NORMAL), ("wv", (d, values), NORMAL),
            ("conv_q", (m.conv_kernel, keys), NORMAL), ("conv_k", (m.conv_kernel, keys), NORMAL),
            ("conv_v", (m.conv_kernel, values), NORMAL),
            ("Wa", (d, m.heads), NORMAL), ("A_log", (m.heads,), A_LOG), ("dt_bias", (m.heads,), DT_BIAS),
            ("Wb", (d, m.heads), NORMAL), ("wg", (d, values), NORMAL), ("o_norm", (m.value_dim,), ONES),
            ("wo", (values, d), NORMAL)) + (((m.out_norm, (d,), ONES),) if m.out_norm else ())


def _dense_own(f: Dense, d: int):
    return _matrices("w", (), d, f.width, True) + (((f.out_norm, (d,), ONES),) if f.out_norm else ())


def _experts_own(f: Experts, d: int):
    r = f.router_width
    if r:
        router = ((("router_in", (d, r), NORMAL),) + ((("router_gamma", (r,), ZEROS),) if f.carried else ()) + (
            ("router_norm", (r,), ONES), ("router_w1", (r, r), NORMAL), ("router_w2", (r, r), NORMAL),
            ("router_w3", (r, f.n_experts), NORMAL)))
    else:
        router = (("router", (d, f.n_experts), NORMAL),)
    return (router + ((("router_bias", (f.n_experts,), ZEROS),) if f.routed_scale else ())
            + (() if f.shared_width is None else _matrices("shared", (), d, f.shared_width, f.gated))
            + _matrices("w", (f.held,), d, f.width, f.gated))


_OWN_LEAVES = {Attention: _attention_own, LatentAttention: _latent_own, CCA: _cca_own, Mamba2: _mamba2_own,
               KDA: _kda_own, GatedDelta: _gated_delta_own, Dense: _dense_own,
               Experts: _experts_own}


def leaves(layer: Layer):
    """One layer's leaves ``(name, shape, init)`` in the tree's order: the mixer's, then the feed-forward's; of each
    its norm (where it has one before it), under ``scaled`` joins the residual scaling, then its own."""
    d, out = layer.hidden, ()
    for sub, part in (("attn", layer.mixer), ("ffn", layer.ffn)):
        if part is not None:
            scaling = tuple((f"{sub}_{name}", (d,), init) for name, init in (
                ("res_scale", ONES), ("res_bias", ZEROS), ("out_scale", ONES), ("out_bias", ZEROS)))
            out += ((((part.norm, (d,), ONES),) if part.norm else ()) + (scaling if layer.scaled else ())
                    + _OWN_LEAVES[type(part)](part, d))
    return out


def param_shapes(cfg: LMConfig) -> List[Tuple[tuple, tuple, str]]:
    """Every leaf as ``(path, shape, init)``, in the one order the initialiser
    numbers them by. ``path`` indexes the tree: ``("layers", 0, "wq")``;
    ``init`` is ``ONES``, ``ZEROS``, ``NORMAL``, ``SMALL``, ``DT_BIAS`` or ``A_LOG``. A node below the root is a
    dict, but ``layers``, a list."""
    out = [(("embed",), (cfg.vocab, cfg.hidden), NORMAL)]
    for i, layer in enumerate(layers(cfg)):
        out += [(("layers", i, name), shape, init) for name, shape, init in leaves(layer)]
    out.append((("final_norm",), (cfg.hidden,), ONES))
    if not cfg.tied:
        out.append((("lm_head",), (cfg.hidden, cfg.vocab), NORMAL))
    if exit_gate(cfg):
        out += [(("exit_gate_w",), (cfg.hidden, 1), NORMAL), (("exit_gate_b",), (1,), ZEROS)]
    module = mtp_layer(cfg)
    if module is not None:
        d = cfg.hidden
        own = [("enorm", (d,), ONES), ("hnorm", (d,), ONES), ("eh_proj", (2 * d, d), NORMAL)]
        out += ([(("mtp", name), shape, init) for name, shape, init in own]
                + [(("mtp", "layer", name), shape, init) for name, shape, init in leaves(module)]
                + [(("mtp", "norm"), (d,), ONES)])
    return out


def num_params(cfg: LMConfig) -> int:
    return sum(math.prod(shape) for _, shape, _ in param_shapes(cfg))
