"""``DecoderLM`` - a decoder-only language-model stage that trains through
``Estimator.fit``: an embedding, a stack of pre-norm residual layers, a head,
next-token cross-entropy (or, ``blockKind`` ``sdar``, the block-diffusion
objective below). What a layer IS is one record (``config.Layer``, from
``config.layers``): a mixer - causal self-attention through the fused fold of
``parallel/flash.py`` with the rotation, window, QK-norm, head gate and output
norm its record names (``_attend``); latent attention, whose queries, keys and
values are rebuilt from low-rank latents and whose heads are wider in their
keys than in their values (``_latent_attend``); ZAYA's compressed convolutional
attention (``_cca``); a Mamba-2 scan behind its convolution (``_mamba2``:
``parallel/causal_conv.py``, ``parallel/ssd.py``); the gated delta rule with a
decay a key channel behind the same convolution (``_kda``:
``parallel/kda.py``), or with ONE decay a head on heads wider in their values
than in their keys (``_gated_delta``: the same kernels' one-decay form) - and a feed-forward - one
dense SwiGLU or a dropless mixture of experts, ``parallel/moe.py``
(``_feed_forward``) - either of which may be absent, each joined to the
residual stream (``_layer``). ``blockKind`` names one of nine presets over that
description, each a published stack with its plain reference beside it
(``config.py`` has the table and every leaf): ``olmoe`` (``reference.py``),
``zaya`` (ZAYA1-8B, ``reference_zaya.py``), ``ouro`` (Ouro-2.6B's looped LM,
``reference_ouro.py``: the whole stack run ``numLoops`` times over the same
leaves; every pass ends in the final norm and an exit gate, the head reads
every pass, and the loss weights the passes' cross-entropies token by token
with the exit distribution the gates give, less ``exitEntropyCoef`` times its
entropy; ``transform`` scores the last pass), ``laguna`` (Laguna-XS.2,
``reference_laguna.py``: layers that differ inside one stack, by
``numHeadsPerLayer``, ``windowPerLayer`` and ``denseLayers``) and ``nemotron_h``
(Nemotron-3-Nano's hybrid stack, ``reference_nemotron.py``: a layer is ONE
sublayer behind one norm, its kind the layer's letter in ``layerPattern``) and
``joyai`` (JoyAI-LLM-Flash, ``reference_joyai.py``: latent attention in every
layer, ``denseLayers`` leading dense layers and then sigmoid-gated experts
beside a shared one, and behind the stack a multi-token-prediction module,
``mtpDepth`` 1: one more layer that reads the stack's output beside the next
token's embedding and predicts the token after it through the same head; the
loss is the next-token cross-entropy plus ``mtpLossCoef`` times the module's;
``transform`` scores with the main head alone, the module is a training
objective) and ``sdar`` (SDAR-30B-A3B-Chat, ``reference_sdar.py``: the
Qwen3-MoE layer - grouped queries under a QK-norm over each head's channels,
softmax gates renormalised over the chosen experts - trained by BLOCK
DIFFUSION: a step draws a masking probability a sequence and masks each token
with it (``lm.noise``: on the device, from a key folded from the stage's seed
and the job's step index), the stack runs ONCE over the doubled sequence ``[x
; x~]``, both halves at positions ``0 .. T - 1``, under a mask that is
block-causal on the clean half, strictly block-causal from the noised half
onto it and block-diagonal inside the noised half (``parallel/flash.py``, "The
block-diffusion mask"), and the head scores the noised half's masked positions
on their OWN tokens with weight ``1 / p``; ``transform`` reports a one-draw
estimate of that bound a row) and ``solar_open2`` (Solar-Open2-250B,
``reference_solar.py``: three layers in four run the gated delta rule on
normalised queries and keys with a low-rank decay gate a key channel, a
correction strength in (0, 2) and a gated norm on the output; the layers
``gqaLayers`` names attend on grouped queries without a position encoding under
an element-wise sigmoid gate; every layer has sigmoid-gated experts beside a
shared one; ``kdaNumHeads``, ``numHeads`` and ``numKvHeads`` may be ONE chip's
share of each layer's heads: ``wo``'s output is then the held heads' part of
the sum, and nothing stands in for the absent chips) and ``olmo_hybrid``
(Olmo-Hybrid-7B, ``reference_olmo_hybrid.py``: the layers ``gqaLayers`` does not
name run the gated delta rule with ONE log-decay a head on ``kdaNumHeads``
heads of ``kdaHeadSize`` key and ``kdaValueHeadSize`` value channels under a
gated norm, those it names attend without a position encoding under a QK-norm
over the whole projection; every layer has a dense SwiGLU; NO norm stands
before a sublayer: each one's output is normed before it joins the stream; the
head counts may be a chip's share, as ``solar_open2``'s: the held heads' part
of ``wo``'s sum is then normed as it is). The fit loop, the head, the loss's chunking, the
clip and the AdamW program are one.

Any expert kind may tie the head to the embedding (``tieEmbeddings``: one leaf)
and hold a range of each block's experts (``expertsHeld``,
``firstExpertHeld``: one chip's share of an expert-parallel layer; tokens
routed elsewhere get nothing from the block, and ``vocabSize`` is then the
slice of the vocabulary held here).

No analogue exists in the reference (SURVEY.md 2.9: no deep nets anywhere in
the tree); the Stage contract is the reference's: ``fit`` returns a ``Model``
(Estimator.java:31,38), the standard param plumbing, save/load and model-data
access like every other algorithm here.

One route. ``fit`` puts the token window on the device once, initialises
every parameter ON the device from the seed (``init_params``: leaf ``i`` of
``config.param_shapes`` is ``0.02 * normal(fold_in(key(seed), i))``, norm
weights are ones), and runs the whole step - forward, backward, clip at global
norm 1.0, AdamW - as ONE jitted program per minibatch with the parameters and
the optimizer state donated. Minibatches cycle over the window like
SGD.java:265, except that a tail which does not fill a batch is completed with
the rows before it: a language-model step has a fixed token batch. Losses,
gradient norms and expert loads stay on the device until the loop ends. Leaves
that do not start at ``0.02 * normal`` start at one (norm weights, scales), at
zero (biases) or, the zaya block's ``wo``, at a fiftieth of it: ``config.py``
says which and why.

Precision: ``computeType`` names the matmuls' input type (``bfloat16``: the
MXU's native path, f32 accumulation); the router, the softmaxes, the norms,
RoPE, the convolutions' depthwise pass (in ``jax.numpy`` or, a Mamba-2
layer's, in its kernels), the loss, the master weights and AdamW's state are
float32 regardless.

Memory (what lets 626 M parameters and 16,384 tokens a step share one 16 GB
chip): the experts' backward recomputes their two hidden projections from
the sorted rows (``parallel/moe.py``), the head's ``[tokens, vocabulary]``
logits exist one token chunk at a time and ONCE a step: every objective here
is a weighted sum of per-token cross-entropies with weights known before the
head runs (the mean's ``1 / (B (T - 1))``, a module's coefficient over its
targets, a looped stack's exit distribution, block diffusion's ``1 / (p B T)``
on the masked positions), so the pass that holds a chunk's
logits forms ``w (softmax - onehot)``, that chunk's ``dh`` and its share of
``dW`` there and then, and the backward only scales them (``_next_token_nll``,
``_weighted_nll``); and where there is more than one block each is
rematerialised in the backward (``jax.checkpoint``). The looped stack is a
``lax.scan`` over its passes (the traced program is one pass): what it holds
for the backward is the input of each of its ``layers x loops`` block
applications and each pass's output.
On the TPU the step is compiled into a stated size (``STEP_HBM_MIB``): XLA
rematerialises further, toward arguments and temporaries that fit it.

Names: the step program's parts carry ``jax.named_scope``s (``lm.noise``,
``lm.embed``, ``lm.block`` with each sublayer's parts under it, ``lm.final_norm``,
``lm.head``, ``lm.mtp``, ``lm.exit``, ``lm.aux``, ``lm.opt``; docs/observability.md, "The
step's scopes", has every path and what opens it), which reach each device
operation's name beside what JAX's transformations write there, so a profile
tells the parts, and forward from recomputed from backward, apart. They are
trace-time metadata: the jaxpr and the compiled program are what they were.

The fitted model keeps its parameters on the device; ``save`` and
``get_model_data`` fetch them (2.5 GB at OLMoE's widths is seconds of
device->host copy, which a fit that is followed by ``transform`` never needs).
"""
from __future__ import annotations

import functools
import math
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from flink_ml_tpu.api.core import Estimator, Model
from flink_ml_tpu.api.types import DataTypes
from flink_ml_tpu.metrics import MLMetrics, metrics
from flink_ml_tpu.models.lm.config import (
    A_LOG, A_RANGE, BLOCKS, CCA, DT_BIAS, DT_FLOOR, DT_RANGE, KDA, MIXERS, NOISE_EPS, NORMAL, ONES, SMALL, SMALL_SCALE, Attention,
    Dense, Experts, GatedDelta, LatentAttention, Layer, LMConfig, Mamba2, exit_gate, layers, mtp_layer, num_params, param_shapes,
)
from flink_ml_tpu.params.param import (
    BoolParam,
    FloatArrayParam,
    FloatParam,
    IntArrayParam,
    IntParam,
    Param,
    ParamValidators,
    StringParam,
    update_existing_params,
)
from flink_ml_tpu.params.shared import (
    HasFeaturesCol,
    HasGlobalBatchSize,
    HasLearningRate,
    HasMaxIter,
    HasPredictionCol,
    HasSeed,
)
from flink_ml_tpu.parallel.causal_conv import causal_conv, forward_positions
from flink_ml_tpu.parallel.flash import (
    ONE_BLOCK_ROW_STATS,
    TQ_TILE,
    BlockDiffusion,
    flash_available,
    fold_chunk_counts,
    fold_kernel_calls,
    fused_attention,
)
from flink_ml_tpu.parallel.kda import kda_kernel_chunks, kda_scan
from flink_ml_tpu.parallel.mesh import is_tpu_backend
from flink_ml_tpu.parallel.moe import dense_swiglu, moe_dropless
from flink_ml_tpu.parallel.ssd import scan_kernel_chunks, ssd_scan
from flink_ml_tpu.trace import CAT_COMPILE, CAT_INGEST, CAT_PRODUCTIVE, CAT_READBACK, tracer
from flink_ml_tpu.utils import read_write as rw

__all__ = ["DecoderLM", "DecoderLMModel", "init_params"]

#: AdamW as the OLMoE recipe sets it (arXiv:2409.02060), and its clipping.
ADAM_B1, ADAM_B2, ADAM_EPS, WEIGHT_DECAY, CLIP_NORM = 0.9, 0.95, 1e-8, 0.1, 1.0
INIT_STD = 0.02
#: Token rows of the head's logits that exist at one time.
_LOSS_CHUNK = 2048
#: The HBM a training step's program is compiled into on the TPU, in MiB: the 15.75e9 B that
#: ``tests/test_lm_chip_compile.py`` holds every cell's step to. Told so, XLA rematerialises and schedules toward
#: that size (and refuses a step far past it); left to its default it stops wherever the step first fits the chip
#: (the Nemotron cut: 16.52e9 B of the chip's 16.91e9 so, 15.39e9 told). A step that fits anyway compiles to the
#: same program with and without it.
STEP_HBM_MIB = 15_020
_HIGHEST = jax.lax.Precision.HIGHEST


class _LMParams(
    HasFeaturesCol,
    HasPredictionCol,
    HasMaxIter,
    HasLearningRate,
    HasGlobalBatchSize,
    HasSeed,
):
    NUM_LAYERS = IntParam("numLayers", "Decoder blocks.", 2, ParamValidators.gt(0))
    HIDDEN_SIZE = IntParam("hiddenSize", "Width of the residual stream.", 128, ParamValidators.gt(0))
    NUM_HEADS = IntParam(
        "numHeads", "Attention heads; hiddenSize must divide evenly by it.", 4, ParamValidators.gt(0)
    )
    NUM_EXPERTS = IntParam("numExperts", "Experts per block.", 8, ParamValidators.gt(0))
    EXPERTS_PER_TOKEN = IntParam(
        "expertsPerToken", "Experts each token is routed to (top-k; the softmax gates of the chosen are kept as they "
        "are, 'sdar' renormalises them over the chosen; the sigmoid-gated kinds: routedScale).", 2,
        ParamValidators.gt(0),
    )
    EXPERT_WIDTH = IntParam(
        "expertWidth", "Hidden width of one SwiGLU expert ('ouro', 'olmo_hybrid': of the block's one dense SwiGLU).", 64,
        ParamValidators.gt(0),
    )
    VOCAB_SIZE = IntParam(
        "vocabSize", "Vocabulary size; 0 infers max(token) + 1 from the training data, and one id more, the mask's, under an objective that masks ('sdar').", 0,
        ParamValidators.gt_eq(0),
    )
    ROPE_THETA = FloatParam("ropeTheta", "Base of the rotary embedding.", 10000.0, ParamValidators.gt(0))
    NORM_EPS = FloatParam("normEps", "Epsilon of every RMSNorm.", 1e-5, ParamValidators.gt(0))
    AUX_LOSS_COEF = FloatParam(
        "auxLossCoef", "Weight of the router's load-balancing loss ('olmoe'; 'zaya' has none).", 0.01,
        ParamValidators.gt_eq(0)
    )
    BLOCK_KIND = StringParam(
        "blockKind",
        "The decoder block: 'olmoe' (multi-head attention with QK-norm, a linear router) or "
        "'zaya' (compressed convolutional attention on grouped queries, an MLP router) or "
        "'ouro' (a dense sandwich-norm layer, the stack run numLoops times over the same weights) or "
        "'laguna' (windowed and full attention layers of different head counts, a per-head output "
        "gate, leading dense layers, sigmoid-gated experts beside a shared one) or "
        "'nemotron_h' (each layer one mixer, by layerPattern: a Mamba-2 scan, attention without a "
        "position encoding, or relu² experts beside a shared one) or "
        "'joyai' (latent attention on low-rank queries, keys and values, leading dense layers, sigmoid-gated "
        "experts beside a shared one, a multi-token-prediction module behind the stack) or "
        "'sdar' (grouped-query attention with a QK-norm a head, experts whose softmax gates are renormalised over the "
        "chosen, trained by block diffusion over the doubled sequence) or "
        "'solar_open2' (the gated delta rule with a decay a key channel in three layers of four, gated attention "
        "without a position encoding in the layers gqaLayers names, sigmoid-gated experts beside a shared one) or "
        "'olmo_hybrid' (the gated delta rule with one decay a head on heads wider in their values than in their "
        "keys, attention without a position encoding under a QK-norm in the layers gqaLayers names, a dense SwiGLU "
        "of expertWidth, each sublayer's output normed before it joins the stream).",
        "olmoe", ParamValidators.in_array(list(BLOCKS)),
    )
    TIE_EMBEDDINGS = BoolParam("tieEmbeddings", "The head is the embedding table transposed.", False)
    EXPERTS_HELD = IntParam(
        "expertsHeld",
        "Experts of each block held here, a contiguous range of numExperts; the router still "
        "chooses among all and tokens routed elsewhere get nothing from this block. 0 holds all. "
        "Under a half of them held, the rows routed here pass the experts a window of sorted rows at a "
        "time (twice the held experts' share under uniform routing) for as many windows as hold one: "
        "none is dropped, and train.drain counts what was carried.",
        0, ParamValidators.gt_eq(0),
    )
    FIRST_EXPERT_HELD = IntParam("firstExpertHeld", "First expert of the held range.", 0,
                                 ParamValidators.gt_eq(0))
    NUM_KV_HEADS = IntParam(
        "numKvHeads", "Key/value heads ('zaya', 'laguna', 'nemotron_h', 'sdar', 'solar_open2', 'olmo_hybrid'; the query heads divide evenly over them). "
        "0: numHeads.", 0,
        ParamValidators.gt_eq(0),
    )
    HEAD_SIZE = IntParam("headSize", "Channels per head ('zaya', 'laguna', 'nemotron_h', 'sdar', 'solar_open2', 'olmo_hybrid'). 0: hiddenSize / numHeads.", 0,
                         ParamValidators.gt_eq(0))
    ROPE_FRACTION = FloatParam(
        "ropeFraction", "Share of each head's channels the rotary embedding turns ('zaya'; 'laguna': in "
        "its full-attention layers).", 1.0,
        ParamValidators.in_range(0.0, 1.0, lower_inclusive=False),
    )
    ROUTER_WIDTH = IntParam("routerWidth", "Width of the router MLP ('zaya').", 256, ParamValidators.gt(0))
    NUM_LOOPS = IntParam(
        "numLoops", "Passes of the whole stack over the same weights, each ending in the final norm "
        "and the exit gate ('ouro').", 1, ParamValidators.gt(0),
    )
    EXIT_ENTROPY_COEF = FloatParam(
        "exitEntropyCoef", "Weight of the exit distribution's entropy, subtracted from the loss ('ouro').",
        0.1, ParamValidators.gt_eq(0),
    )
    NUM_HEADS_PER_LAYER = IntArrayParam(
        "numHeadsPerLayer", "Query heads of each layer, numLayers of them ('laguna').", [])
    WINDOW_PER_LAYER = IntArrayParam(
        "windowPerLayer", "Each layer's sliding window in keys, the query's own among them; 0: full causal "
        "attention ('laguna').", [])
    DENSE_LAYERS = IntParam(
        "denseLayers", "Leading layers whose feed-forward is one dense SwiGLU of denseWidth ('laguna', 'joyai').", 0,
        ParamValidators.gt_eq(0))
    DENSE_WIDTH = IntParam("denseWidth", "Hidden width of a dense layer's SwiGLU ('laguna', 'joyai').", 0,
                           ParamValidators.gt_eq(0))
    SHARED_EXPERT_WIDTH = IntParam(
        "sharedExpertWidth", "Hidden width of the expert every token passes beside the routed ones ('laguna', "
        "'nemotron_h', 'joyai', 'solar_open2').", 0,
        ParamValidators.gt_eq(0))
    ROUTED_SCALE = FloatParam(
        "routedScale", "The sigmoid gates of the chosen experts are renormalised to sum to one and scaled by "
        "this ('laguna', 'nemotron_h', 'joyai', 'solar_open2').", 1.0, ParamValidators.gt(0))
    WINDOW_ROPE_THETA = FloatParam("windowRopeTheta", "Base of the rotary embedding in windowed layers, which "
                                   "turn every channel ('laguna').", 10000.0, ParamValidators.gt(0))
    ROPE_YARN = FloatArrayParam(
        "ropeYarn", "YaRN on the full layers' rotary embedding ('laguna'): factor, original length, beta_fast, "
        "beta_slow, attention factor; empty: none.", [])
    LAYER_PATTERN = StringParam(
        "layerPattern", "Each layer's one mixer, a letter a layer ('nemotron_h'): M a Mamba-2 scan, * attention "
        "without a position encoding, E experts.", "")
    SSM_NUM_HEADS = IntParam("ssmNumHeads", "Heads of a Mamba-2 layer's scan ('nemotron_h').", 0,
                             ParamValidators.gt_eq(0))
    SSM_HEAD_SIZE = IntParam("ssmHeadSize", "Channels per scan head ('nemotron_h').", 0, ParamValidators.gt_eq(0))
    SSM_NUM_GROUPS = IntParam("ssmNumGroups", "Groups of scan heads that share one B and C, and the groups of the "
                              "gated norm ('nemotron_h').", 0, ParamValidators.gt_eq(0))
    SSM_STATE_SIZE = IntParam("ssmStateSize", "Width of a scan head's state ('nemotron_h').", 0,
                              ParamValidators.gt_eq(0))
    SSM_CONV_KERNEL = IntParam("ssmConvKernel", "Taps of the causal depthwise convolution before the scan "
                               "('nemotron_h') or the delta rule ('solar_open2', 'olmo_hybrid').", 4, ParamValidators.gt(0))
    SSM_CHUNK_SIZE = IntParam("ssmChunkSize", "Positions a chunk of the scan ('nemotron_h') or of the delta rule "
                              "('solar_open2', 'olmo_hybrid': a power of two); the sequence length is a multiple.", 128,
                              ParamValidators.gt(0))
    GQA_LAYERS = IntArrayParam(
        "gqaLayers", "The layers that attend (no position encoding; 'solar_open2': grouped queries, a gated output; "
        "'olmo_hybrid': a QK-norm); every other layer runs the gated delta rule.", [])
    KDA_NUM_HEADS = IntParam("kdaNumHeads", "Heads of a delta-rule layer held here: all of them, or one chip's share "
                             "('solar_open2', 'olmo_hybrid').", 0, ParamValidators.gt_eq(0))
    KDA_HEAD_SIZE = IntParam("kdaHeadSize", "Key and value channels of a delta-rule head, and the rank of its decay "
                             "and output gates ('solar_open2'); a head's key channels ('olmo_hybrid').", 0,
                             ParamValidators.gt_eq(0))
    KDA_VALUE_HEAD_SIZE = IntParam("kdaValueHeadSize", "Value channels of a delta-rule head ('olmo_hybrid').", 0,
                                   ParamValidators.gt_eq(0))
    Q_LORA_RANK = IntParam("qLoraRank", "Width of the latent the queries are rebuilt from ('joyai').", 0,
                           ParamValidators.gt_eq(0))
    KV_LORA_RANK = IntParam("kvLoraRank", "Width of the latent a token's keys and values are rebuilt from ('joyai').",
                            0, ParamValidators.gt_eq(0))
    QK_NOPE_HEAD_SIZE = IntParam("qkNopeHeadSize", "A head's query and key channels without position ('joyai').", 0,
                                 ParamValidators.gt_eq(0))
    QK_ROPE_HEAD_SIZE = IntParam("qkRopeHeadSize", "A head's rotary query channels, and the channels of the one "
                                 "rotary key a token that every head reads ('joyai').", 0, ParamValidators.gt_eq(0))
    V_HEAD_SIZE = IntParam("vHeadSize", "A head's value channels ('joyai').", 0, ParamValidators.gt_eq(0))
    MTP_DEPTH = IntParam("mtpDepth", "Multi-token-prediction modules behind the stack, 0 or 1 ('joyai'): one more "
                         "layer that predicts the token after next through the same head. A training objective: "
                         "transform does not run it.", 0, ParamValidators.in_array([0, 1]))
    MTP_LOSS_COEF = FloatParam("mtpLossCoef", "Weight of the multi-token-prediction module's loss ('joyai').", 0.3,
                               ParamValidators.gt_eq(0))
    BLOCK_LENGTH = IntParam(
        "blockLength", "Positions a block of the block-diffusion objective ('sdar'): a power of two that divides the "
        "sequence length. A masked position sees the clean blocks before its own and its own block's noised copy.", 4,
        ParamValidators.gt(0))
    MASK_TOKEN_ID = IntParam(
        "maskTokenId", "The id a masked token is replaced with ('sdar'). -1: the vocabulary's last id.", -1,
        ParamValidators.gt_eq(-1))
    COMPUTE_TYPE = StringParam(
        "computeType",
        "Matmul input dtype: 'bfloat16' runs every matmul and the attention "
        "fold on the MXU's bf16 path with float32 accumulation (router, "
        "softmaxes, norms, loss, weights and AdamW state stay float32); "
        "'float32' is exact.",
        "float32",
        ParamValidators.in_array(["float32", "bfloat16"]),
    )

    #: The ``LMConfig`` fields that not every kind reads, by the kinds each belongs to, with the param it comes from.
    #: The other kinds leave it at ``LMConfig``'s default (no experts; no balancing loss: a bias rule outside the
    #: gradient balances every kind but 'olmoe', reference_zaya.py).
    _OWN = {
        ("olmoe", "zaya", "laguna", "nemotron_h", "joyai", "sdar", "solar_open2"): (("n_experts", NUM_EXPERTS),
                                                                                    ("top_k", EXPERTS_PER_TOKEN)),
        ("olmoe",): (("aux_coef", AUX_LOSS_COEF),),
        ("zaya", "laguna", "nemotron_h", "sdar", "solar_open2", "olmo_hybrid"): (("n_kv_heads", NUM_KV_HEADS),
                                                                                 ("head_size", HEAD_SIZE)),
        ("zaya", "laguna"): (("rope_fraction", ROPE_FRACTION),),
        ("zaya",): (("router_width", ROUTER_WIDTH),),
        ("ouro",): (("loops", NUM_LOOPS), ("exit_beta", EXIT_ENTROPY_COEF)),
        ("laguna",): (("layer_heads", NUM_HEADS_PER_LAYER), ("layer_windows", WINDOW_PER_LAYER),
                      ("window_rope_theta", WINDOW_ROPE_THETA), ("yarn", ROPE_YARN)),
        ("laguna", "joyai"): (("n_dense", DENSE_LAYERS), ("dense_width", DENSE_WIDTH)),
        ("laguna", "nemotron_h", "joyai", "solar_open2"): (("shared_width", SHARED_EXPERT_WIDTH),
                                                           ("routed_scale", ROUTED_SCALE)),
        ("joyai",): (("q_rank", Q_LORA_RANK), ("kv_rank", KV_LORA_RANK), ("nope_dim", QK_NOPE_HEAD_SIZE),
                     ("rope_dim", QK_ROPE_HEAD_SIZE), ("v_dim", V_HEAD_SIZE), ("mtp_depth", MTP_DEPTH),
                     ("mtp_coef", MTP_LOSS_COEF)),
        ("sdar",): (("block_length", BLOCK_LENGTH), ("mask_id", MASK_TOKEN_ID)),
        ("nemotron_h",): (("layer_kinds", LAYER_PATTERN), ("ssm_heads", SSM_NUM_HEADS), ("ssm_head_dim", SSM_HEAD_SIZE),
                          ("ssm_groups", SSM_NUM_GROUPS), ("ssm_state", SSM_STATE_SIZE)),
        ("nemotron_h", "solar_open2", "olmo_hybrid"): (("conv_kernel", SSM_CONV_KERNEL), ("chunk", SSM_CHUNK_SIZE)),
        ("solar_open2", "olmo_hybrid"): (("gqa_layers", GQA_LAYERS), ("kda_heads", KDA_NUM_HEADS),
                                         ("kda_head_dim", KDA_HEAD_SIZE)),
        ("olmo_hybrid",): (("kda_value_dim", KDA_VALUE_HEAD_SIZE),),
    }
    #: The params refused where they are given a value under a kind they do not belong to.
    _REFUSED = (
        ((NUM_KV_HEADS, HEAD_SIZE), ("zaya", "laguna", "nemotron_h", "sdar", "solar_open2", "olmo_hybrid"),
         "numKvHeads and headSize belong to blockKind 'zaya', 'laguna', 'nemotron_h', 'sdar', 'solar_open2' or "
         "'olmo_hybrid'"),
        ((GQA_LAYERS, KDA_NUM_HEADS, KDA_HEAD_SIZE), ("solar_open2", "olmo_hybrid"),
         "gqaLayers, kdaNumHeads and kdaHeadSize belong to blockKind 'solar_open2' or 'olmo_hybrid'"),
        ((KDA_VALUE_HEAD_SIZE,), ("olmo_hybrid",), "kdaValueHeadSize belongs to blockKind 'olmo_hybrid'"),
        ((ROPE_FRACTION,), ("zaya", "laguna", "nemotron_h"),
         "ropeFraction belongs to blockKind 'zaya', 'laguna' or 'nemotron_h'"),
        ((BLOCK_LENGTH, MASK_TOKEN_ID), ("sdar",), "blockLength and maskTokenId belong to blockKind 'sdar'"),
        ((NUM_HEADS_PER_LAYER, WINDOW_PER_LAYER), ("laguna",),
         "numHeadsPerLayer and windowPerLayer belong to blockKind 'laguna'"),
        ((DENSE_LAYERS,), ("laguna", "joyai"), "denseLayers belongs to blockKind 'laguna' or 'joyai'"),
        ((SHARED_EXPERT_WIDTH,), ("laguna", "nemotron_h", "joyai", "solar_open2"),
         "sharedExpertWidth belongs to blockKind 'laguna', 'nemotron_h', 'joyai' or 'solar_open2'"),
        ((Q_LORA_RANK, KV_LORA_RANK, QK_NOPE_HEAD_SIZE, QK_ROPE_HEAD_SIZE, V_HEAD_SIZE, MTP_DEPTH), ("joyai",),
         "qLoraRank, kvLoraRank, qkNopeHeadSize, qkRopeHeadSize, vHeadSize and mtpDepth belong to blockKind 'joyai'"),
        ((LAYER_PATTERN, SSM_NUM_HEADS), ("nemotron_h",), "layerPattern and the ssm sizes belong to blockKind 'nemotron_h'"),
        ((NUM_LOOPS,), ("ouro",), "numLoops belongs to blockKind 'ouro'"),
        ((TIE_EMBEDDINGS, EXPERTS_HELD, FIRST_EXPERT_HELD),
         ("olmoe", "zaya", "laguna", "nemotron_h", "joyai", "sdar", "solar_open2"),
         "tieEmbeddings, expertsHeld and firstExpertHeld do not belong to blockKind 'ouro' or 'olmo_hybrid'"),
        ((TIE_EMBEDDINGS,), ("olmoe", "zaya", "ouro", "laguna", "joyai", "sdar", "solar_open2", "olmo_hybrid"),
         "tieEmbeddings does not belong to blockKind 'nemotron_h'"),
    )

    def lm_config(self, vocab: Optional[int] = None) -> LMConfig:
        kind = self.get(self.BLOCK_KIND)
        for params, kinds, message in self._REFUSED:
            if kind not in kinds and any(self.get(p) != p.default_value for p in params):
                raise ValueError(message)
        own = [pair for kinds, pairs in self._OWN.items() if kind in kinds for pair in pairs]
        values = {field: self.get(p) for field, p in own}
        cfg = LMConfig(**{
            "n_layers": self.get(self.NUM_LAYERS), "hidden": self.get(self.HIDDEN_SIZE),
            "n_heads": self.get(self.NUM_HEADS), "n_experts": 0, "top_k": 0,
            "expert_width": self.get(self.EXPERT_WIDTH), "vocab": self.get(self.VOCAB_SIZE) if vocab is None else vocab,
            "rope_theta": self.get(self.ROPE_THETA), "norm_eps": self.get(self.NORM_EPS), "aux_coef": 0.0,
            "block": kind, "tied": self.get(self.TIE_EMBEDDINGS), "experts_held": self.get(self.EXPERTS_HELD),
            "first_held": self.get(self.FIRST_EXPERT_HELD),
            **{field: tuple(v) if isinstance(v, (list, str)) else v for field, v in values.items()}})
        # the stack: every per-layer param names every layer, in letters the stack knows
        per_layer = [(p.name, len(values[field])) for field, p in own if field.startswith("layer_")]
        if any(n != cfg.n_layers for _, n in per_layer):
            raise ValueError(" and ".join(f"{name} ({n})" for name, n in per_layer)
                             + f" name{'s' * (len(per_layer) == 1)} each of the {cfg.n_layers} layers")
        if set(cfg.layer_kinds) - set(MIXERS):
            raise ValueError(f"layerPattern names each of the {cfg.n_layers} layers by one of {MIXERS}; got "
                             f"{''.join(cfg.layer_kinds)!r}")
        if cfg.gqa_layers and not all(0 <= i < cfg.n_layers for i in cfg.gqa_layers):
            raise ValueError(f"gqaLayers names layers among the {cfg.n_layers}; got {list(cfg.gqa_layers)}")
        if cfg.n_dense > cfg.n_layers:
            raise ValueError(f"denseLayers {cfg.n_dense} is at most numLayers {cfg.n_layers}")
        if not cfg.head_size and cfg.hidden % cfg.n_heads:
            raise ValueError(f"hiddenSize {cfg.hidden} must divide evenly by numHeads {cfg.n_heads}")
        if cfg.block_length:  # the objective's sizes
            if cfg.block_length & (cfg.block_length - 1):
                raise ValueError(f"blockLength is a power of two, got {cfg.block_length}")
            if cfg.mask_id < 0:
                cfg = cfg._replace(mask_id=cfg.vocab - 1)  # the vocabulary's last id (of an inferred vocabulary: in fit)
            if cfg.vocab and cfg.mask_id >= cfg.vocab:
                raise ValueError(f"maskTokenId {cfg.mask_id} is not among the vocabulary's {cfg.vocab} ids")
        for spec in dict.fromkeys(layers(cfg)):  # each distinct layer once: its mixer, then its feed-forward
            _check_mixer(spec.mixer)
            _check_feed_forward(spec.ffn)
        return cfg


def _check_mixer(m) -> None:
    """Refuse sizes the mixer cannot run at, in the stage params' names."""
    if isinstance(m, Attention) and (m.head_dim <= 0 or m.heads <= 0 or m.heads % m.kv_heads):
        raise ValueError(f"an attention layer's query heads ({m.heads}) divide evenly over numKvHeads {m.kv_heads}, at a "
                         f"stated headSize")
    if isinstance(m, Attention) and m.window < 0:
        raise ValueError(f"windowPerLayer counts keys, got {m.window}")
    if isinstance(m, LatentAttention) and not (m.heads > 0 and m.q_rank and m.kv_rank and m.nope_dim and m.rope_dim
                                               and m.v_dim):
        raise ValueError(f"latent attention needs qLoraRank ({m.q_rank}), kvLoraRank ({m.kv_rank}), qkNopeHeadSize "
                         f"({m.nope_dim}), qkRopeHeadSize ({m.rope_dim}) and vHeadSize ({m.v_dim})")
    if isinstance(m, CCA) and (m.kv_heads != 2 or m.heads % 2):
        raise ValueError(f"compressed convolutional attention's value shift makes two key/value heads (one of this "
                         f"position, one of the position before) under an even numHeads; got numKvHeads "
                         f"{m.kv_heads}, numHeads {m.heads}")
    if isinstance(m, Mamba2) and not (m.heads and m.head_dim and m.state and m.groups and m.heads % m.groups == 0):
        raise ValueError(f"a Mamba-2 layer needs ssmNumHeads ({m.heads}) in whole ssmNumGroups ({m.groups}), "
                         f"ssmHeadSize ({m.head_dim}) and ssmStateSize ({m.state})")
    if isinstance(m, KDA) and not (m.heads > 0 and m.head_dim > 0 and m.chunk & (m.chunk - 1) == 0):
        raise ValueError(f"a delta-rule layer needs kdaNumHeads ({m.heads}), kdaHeadSize ({m.head_dim}) and a chunk "
                         f"(ssmChunkSize) that is a power of two ({m.chunk})")
    if isinstance(m, GatedDelta) and not (m.heads > 0 and m.key_dim > 0 and m.value_dim > 0
                                          and m.chunk & (m.chunk - 1) == 0):
        raise ValueError(f"a delta-rule layer needs kdaNumHeads ({m.heads}), kdaHeadSize ({m.key_dim}), "
                         f"kdaValueHeadSize ({m.value_dim}) and a chunk (ssmChunkSize) that is a power of two "
                         f"({m.chunk})")
    rotation = getattr(m, "rotation", None)
    if rotation is not None and rotation.interleaved != isinstance(m, LatentAttention):
        raise ValueError("latent attention turns interleaved pairs of its rotary channels, the other mixers the two "
                         "halves of theirs")
    if rotation is not None and rotation.channels % 2:
        raise ValueError(f"the rotary embedding turns an even number of channels, got {rotation.channels} of "
                         f"{m.head_dim}")
    if rotation is not None and len(rotation.yarn) not in (0, 5):
        raise ValueError(f"ropeYarn has five numbers or none, got {len(rotation.yarn)}")


def _check_feed_forward(f) -> None:
    if isinstance(f, Dense) and not f.width:
        raise ValueError("a dense layer needs denseWidth")
    if isinstance(f, Experts):
        if f.shared_width == 0:
            raise ValueError("an expert layer beside a shared expert needs sharedExpertWidth")
        if f.top_k > f.n_experts:
            raise ValueError(f"expertsPerToken {f.top_k} > numExperts {f.n_experts}")
        if f.first_held + f.held > f.n_experts:
            raise ValueError(f"experts {f.first_held}..{f.first_held + f.held} are not among numExperts {f.n_experts}")


def _add_accessors(cls) -> None:
    """``get_x``/``set_x`` for each param the class declares (``NUM_LAYERS``: ``get_num_layers``), as every stage
    spells them."""
    for attr, param in list(vars(cls).items()):
        if isinstance(param, Param):
            setattr(cls, f"get_{attr.lower()}", lambda self, p=param: self.get(p))
            setattr(cls, f"set_{attr.lower()}", lambda self, value, p=param: self.set(p, value))


_add_accessors(_LMParams)


# -- parameters ----------------------------------------------------------------


def _build_tree(cfg: LMConfig, leaves) -> dict:
    tree = {"layers": [{} for _ in range(cfg.n_layers)]}
    for (path, _, _), leaf in zip(param_shapes(cfg), leaves):
        node = tree
        for key in path[:-1]:  # a node below the root is a dict, but ``layers``, the list made above
            node = node[key] if isinstance(node, list) else node.setdefault(key, {})
        node[path[-1]] = leaf
    return tree


def _ordered(params: dict, cfg: LMConfig) -> list:
    """The tree's leaves in ``param_shapes`` order."""
    out = []
    for path, _, _ in param_shapes(cfg):
        node = params
        for key in path:
            node = node[key]
        out.append(node)
    return out


def _flat_names(cfg: LMConfig) -> List[str]:
    return [".".join(str(p) for p in path) for path, _, _ in param_shapes(cfg)]


@functools.cache
def _init_program(cfg: LMConfig):
    def init(key):
        leaves = []
        for i, (_, shape, kind) in enumerate(param_shapes(cfg)):
            if kind in (NORMAL, SMALL):
                std = INIT_STD * (SMALL_SCALE if kind == SMALL else 1.0)
                leaves.append(std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32))
            elif kind in (DT_BIAS, A_LOG):  # a Mamba-2 layer's step sizes and decay rates (config.py)
                u = jax.random.uniform(jax.random.fold_in(key, i), shape, jnp.float32)
                if kind == A_LOG:
                    leaves.append(jnp.log(A_RANGE[0] + u * (A_RANGE[1] - A_RANGE[0])))
                else:
                    dt = jnp.maximum(jnp.exp(math.log(DT_RANGE[0]) + u * math.log(DT_RANGE[1] / DT_RANGE[0])),
                                     DT_FLOOR)
                    leaves.append(dt + jnp.log(-jnp.expm1(-dt)))  # softplus's inverse
            else:
                leaves.append((jnp.ones if kind == ONES else jnp.zeros)(shape, jnp.float32))
        return _build_tree(cfg, leaves)

    return jax.jit(init)


def init_params(cfg: LMConfig, seed: int) -> dict:
    """The parameter tree, made on the device from ``seed``: leaf ``i`` of
    ``param_shapes(cfg)`` is ``0.02 * normal(fold_in(key(seed), i))`` in
    float32 (the zaya block's ``wo`` a fiftieth of that); norm weights and
    scales are ones, biases zeros (``config.py``)."""
    return _init_program(cfg)(jax.random.key(seed))


# -- the forward pass -------------------------------------------------------------


def _rms_norm(x, w, eps):
    with jax.named_scope("norm"):
        x = x.astype(jnp.float32)
        return w * (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps))


def _read(x, layer, part, eps):
    """What a sublayer reads of the stream: behind the norm its record names, or as it is."""
    return _rms_norm(x, layer[part.norm], eps) if part.norm else x


def _rope_tables(t: int, d: int, theta: float):
    with jax.named_scope("rope"):
        inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
        freqs = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
        emb = jnp.concatenate([freqs, freqs], axis=-1)  # [T, D]
        return jnp.cos(emb), jnp.sin(emb)


def _rope(x, cos, sin):
    """Rotate-half RoPE on ``x [B, H, T, D]``."""
    with jax.named_scope("rope"):
        half = x.shape[-1] // 2
        rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
        return x * cos + rotated * sin


def _pair_tables(t: int, d: int, theta: float, lead: int = 0):
    """``cos, sin [T, lead + d]`` of RoPE on INTERLEAVED pairs: channels ``lead + 2 j`` and ``lead + 2 j + 1`` turn
    by ``pos * theta^(-2 j / d)``, each angle's cosine on both channels of its pair, its sine negative on the even
    one (``_rope_pairs``); the ``lead`` channels before them are not turned (cosine 1, sine 0)."""
    with jax.named_scope("rope"):
        inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
        freqs = jnp.repeat(jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :], 2, axis=-1)  # [T, d]
        sign = jnp.tile(jnp.asarray([-1.0, 1.0], jnp.float32), d // 2)
        return (jnp.pad(jnp.cos(freqs), ((0, 0), (lead, 0)), constant_values=1.0),
                jnp.pad(sign * jnp.sin(freqs), ((0, 0), (lead, 0))))


def _rope_pairs(x, cos, sin):
    """RoPE on interleaved pairs of ``x [..., T, D]`` by ``_pair_tables``' ``cos, sin [T, D]``: ``out[2 j] = x[2 j]
    c_j - x[2 j + 1] s_j``, ``out[2 j + 1] = x[2 j + 1] c_j + x[2 j] s_j``; a channel's partner is its neighbour, to
    the right of an even channel and to the left of an odd one (the tables' unturned lead starts on an even one)."""
    with jax.named_scope("rope"):
        even = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1) % 2 == 0
        partner = jnp.where(even, jnp.roll(x, -1, axis=-1), jnp.roll(x, 1, axis=-1))
        return x * cos + partner * sin


def _matmul(a, w, cd):
    return jnp.dot(a.astype(cd), w.astype(cd), preferred_element_type=jnp.float32,
                   precision=_HIGHEST if cd == jnp.float32 else None)


def _proj(a, w, cd):
    """One of attention's dense projections."""
    with jax.named_scope("proj"):
        return _matmul(a, w, cd)


def _fold(q, k, v, cd, interpret: bool, window: Optional[int] = None, blocks: Optional[BlockDiffusion] = None):
    """Softmax attention of ``q [B, H, T, D]`` on ``k [B, H_kv, T, D]`` and ``v
    [B, H_kv, T, D_v]`` (``[B, H, T, D_v]`` out in float32; ``D_v`` is ``D``
    but under latent attention) at scale ``D^-1/2`` through the fused fold's
    one-block form: a ring of one, the whole sequence is the resident KV
    block. The mask is one of the fold's three forms, passed on as given:
    causal (neither ``window`` nor ``blocks``); under a ``window`` each query
    keeps the ``window`` keys that end at itself; under ``blocks`` the ``T``
    positions are a clean sequence and its noised copy, and the mask is block
    diffusion's (``flash.BlockDiffusion``)."""
    with jax.named_scope("fold"):
        return fused_attention(q.astype(cd), k.astype(cd), v.astype(cd), float(q.shape[-1]) ** -0.5, window, interpret,
                               blocks)


def _heads(z, n: int):
    """``[B, T, n * D]`` (or ``[B, T, n, D]``) ``-> [B, n, T, D]``, the fold's layout."""
    with jax.named_scope("fold"):
        return jnp.transpose(z.reshape(*z.shape[:2], n, -1), (0, 2, 1, 3))


def _merged(o):
    """The fold's ``[B, n, T, D]`` back as ``[B, T, n * D]``."""
    with jax.named_scope("fold"):
        return jnp.transpose(o, (0, 2, 1, 3)).reshape(o.shape[0], o.shape[2], -1)


# -- the mixers and the feed-forward (the references carry each equation's origin) --


def _before(z):
    """``z [B, T, ...]`` one position earlier: ``out[t] = z[t - 1]``, zero at 0."""
    return jnp.pad(z[:, :-1], ((0, 0), (1, 0)) + ((0, 0),) * (z.ndim - 2))


def _unit_heads(z, eps):
    """Each head of ``z [..., D]`` at length ``sqrt(D)``."""
    return z * jax.lax.rsqrt(jnp.mean(z * z, axis=-1, keepdims=True) + eps)


def _rope_part(x, cos, sin):
    """RoPE on the first ``cos.shape[-1]`` channels of each head of ``x [B, H, T, D]``: none, some or all."""
    rot = cos.shape[-1]
    if rot == 0:
        return x
    if rot == x.shape[-1]:
        return _rope(x, cos, sin)
    with jax.named_scope("rope"):
        turned = x[..., :rot]
    turned = _rope(turned, cos, sin)
    with jax.named_scope("rope"):
        return jnp.concatenate([turned, x[..., rot:]], axis=-1)


def _cca(x, layer, m: CCA, eps: float, cd, interpret: bool):
    """Compressed convolutional attention: queries, keys and values projected
    into a latent of ``heads`` (``kv_heads``) heads, q and k mixed there by
    two short causal convolutions, attention and its output in the latent, one
    projection back."""
    b, t, _ = x.shape
    h, kv, hd = m.heads, m.kv_heads, m.head_dim
    group = h // kv
    a = _rms_norm(x, layer[m.norm], eps)
    q0 = _proj(a, layer["wq"], cd).reshape(b, t, kv, group, hd)
    k0 = _proj(a, layer["wk"], cd).reshape(b, t, kv, 1, hd)
    with jax.named_scope("conv"):
        # the two convolutions over the sequence, kernel 2: per channel, then per head
        z = jnp.concatenate([q0.reshape(b, t, -1), k0.reshape(b, t, -1)], axis=-1)
        w0, w1 = layer["conv0_w"], layer["conv1_w"]
        z1 = (w0[0] * _before(z) + w0[1] * z + layer["conv0_b"]).reshape(b, t, h + kv, hd)

        def per_head(zz, u):  # heads lead: the one batched form every backend's dot takes in bfloat16
            zz = jnp.moveaxis(zz.reshape(b * t, h + kv, hd), 1, 0)
            out = jnp.einsum("gni,gio->gno", zz.astype(cd), u.astype(cd), preferred_element_type=jnp.float32,
                             precision=_HIGHEST if cd == jnp.float32 else None)
            return jnp.moveaxis(out, 0, 1).reshape(b, t, h + kv, hd)

        z2 = per_head(_before(z1), w1[0]) + per_head(z1, w1[1]) + layer["conv1_b"]
        # the q-k mean: each query head with its key head, each key head with its query heads' mean
        q = z2[:, :, :h] + ((q0 + k0) / 2).reshape(b, t, h, hd)
        k = z2[:, :, h:] + ((jnp.mean(q0, axis=3, keepdims=True) + k0) / 2).reshape(b, t, kv, hd)
    with jax.named_scope("norm"):
        q = _unit_heads(q, eps)
        k = _unit_heads(k, eps) * layer["k_temp"][:, None]
    v1 = _proj(a, layer["wv1"], cd)
    with jax.named_scope("mix"):
        earlier = _before(a)  # the value shift
    with jax.named_scope("proj"):
        v = jnp.stack([v1, _matmul(earlier, layer["wv2"], cd)], axis=2)
    cos, sin = _yarn_tables(t, m.rotation.channels, m.rotation.theta, m.rotation.yarn)
    o = _fold(_rope_part(_heads(q, h), cos, sin), _rope_part(_heads(k, kv), cos, sin), _heads(v, kv),
              cd, interpret)
    return _proj(_merged(o), layer["wo"], cd)


def _router_state(u, layer, carry):
    """The router's hidden state ``[N, r]`` in float32: this block's projection
    plus ``router_gamma`` times the block before's (depth averaging)."""
    with jax.named_scope("route"):
        r = jnp.dot(u, layer["router_in"], precision=_HIGHEST)
        return r if carry is None else r + layer["router_gamma"] * carry


def _router_logits(r, layer, eps):
    n = _rms_norm(r, layer["router_norm"], eps)
    for w in ("router_w1", "router_w2"):
        n = jax.nn.gelu(jnp.dot(n, layer[w], precision=_HIGHEST), approximate=False)
    return jnp.dot(n, layer["router_w3"], precision=_HIGHEST)


def _scaled(x, y, layer, sub: str):
    """The learned residual scaling: a per-channel scale and bias on the
    residual and on the sublayer's output."""
    with jax.named_scope("mix"):
        return (layer[f"{sub}_res_scale"] * x + layer[f"{sub}_res_bias"]
                + layer[f"{sub}_out_scale"] * y + layer[f"{sub}_out_bias"])


def _yarn_tables(t: int, rot: int, theta: float, yarn):
    """``cos, sin [T, rot]`` of RoPE at base ``theta`` stretched by YaRN
    (``yarn = (factor, original length, beta_fast, beta_slow, attention
    factor)``): each frequency a blend of itself and itself over ``factor``, by
    a linear ramp over the channel pairs between the two correction dimensions
    (the pairs that turn ``beta_fast`` and ``beta_slow`` times within the
    original length); both tables times the attention factor. No ``yarn``:
    ``_rope_tables``."""
    if not yarn:
        return _rope_tables(t, rot, theta)
    factor, original, beta_fast, beta_slow, attention_factor = yarn
    with jax.named_scope("rope"):
        pos_freqs = theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)

        def correction_dim(rotations):
            return rot * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

        low = max(math.floor(correction_dim(beta_fast)), 0)
        high = min(math.ceil(correction_dim(beta_slow)), rot - 1)
        ramp = np.clip((np.arange(rot // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
        inv_freq = jnp.asarray(ramp / (factor * pos_freqs) + (1.0 - ramp) / pos_freqs, jnp.float32)
        freqs = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
        emb = jnp.concatenate([freqs, freqs], axis=-1)  # [T, rot]
        return jnp.float32(attention_factor) * jnp.cos(emb), jnp.float32(attention_factor) * jnp.sin(emb)


def _mamba2(x, layer, m: Mamba2, eps: float, cd, interpret: bool):
    """A Mamba-2 layer: one projection into the gate ``z``, the scan's ``x``,
    ``B``, ``C`` and the step sizes; a causal depthwise convolution and SiLU
    over ``x``, ``B``, ``C``; the selective scan in chunks; the grouped
    RMSNorm of ``y * silu(z)``; one projection back. The step sizes, the decay
    rates and the scan's state are float32 whatever ``cd`` is."""
    b, t, _ = x.shape
    heads, p, groups, n = m.heads, m.head_dim, m.groups, m.state
    inner, bc = heads * p, groups * n
    u = _proj(_rms_norm(x, layer[m.norm], eps), layer["in_proj"], cd)
    with jax.named_scope("conv"):
        # the convolution's kernels read x, B and C where they lie in u and write them activated as arrays of their
        # own, as the scan's kernels take them; the gate and the step sizes leave u beside them (parallel/causal_conv.py)
        z, (xs, bs, cs), dt = causal_conv(u, layer["conv_w"], layer["conv_b"], (inner, bc, bc), first=inner)
        xs, bs, cs = xs.reshape(b, t, heads, p), bs.reshape(b, t, groups, n), cs.reshape(b, t, groups, n)
    with jax.named_scope("scan"):
        y = ssd_scan(xs, jax.nn.softplus(dt + layer["dt_bias"]), -jnp.exp(layer["A_log"]), bs, cs, m.chunk, cd)
        y = y + layer["D"][:, None] * xs
    with jax.named_scope("gnorm"):
        y = (y.reshape(b, t, inner) * jax.nn.silu(z)).reshape(b, t, groups, inner // groups)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
        y = y.reshape(b, t, inner) * layer["gate_norm"]
    return _proj(y, layer["out_proj"], cd)


#: Added to a query's or key's squared length before its root is taken (the delta rule's unit vectors).
UNIT_EPS = 1e-6


def _kda(x, layer, m: KDA, eps: float, cd, interpret: bool):
    """A delta-rule layer (``reference_solar.py`` has the equations): q, k and
    v through ONE projection, a causal depthwise convolution and SiLU; q and k
    at unit length a head (q over ``sqrt(head_dim)`` more); a log-decay a key
    channel from a low-rank gate, ``-exp(A_log) softplus(.)``, and the
    correction's strength ``2 sigmoid(.)`` a head; the gated delta rule in
    chunks; each head's output RMS-normed and gated by a low-rank sigmoid
    gate; one projection back (of the heads held here: a share of the layer's
    sum where they are a share of its heads). The decays, the strengths, the
    unit vectors and the rule's state are float32 whatever ``cd`` is."""
    b, t, _ = x.shape
    h, d = m.heads, m.head_dim
    inner = h * d
    a = _rms_norm(x, layer[m.norm], eps)
    with jax.named_scope("proj"):
        u = _matmul(a, jnp.concatenate([layer["wq"], layer["wk"], layer["wv"]], axis=1), cd)
    with jax.named_scope("conv"):
        # the convolution's kernels read q, k and v where they lie in u and write each activated as an array of its own
        taps = jnp.concatenate([layer["conv_q"], layer["conv_k"], layer["conv_v"]], axis=1)
        _, (q, k, v), _ = causal_conv(u, taps, jnp.zeros((3 * inner,), jnp.float32), (inner,) * 3)
    with jax.named_scope("kgate"):
        dt = jax.nn.softplus(_matmul(_matmul(a, layer["Fa"], cd), layer["Fb"], cd) + layer["dt_bias"])
        g = -(jnp.exp(layer["A_log"])[:, None] * dt.reshape(b, t, h, d))
        beta = 2.0 * jax.nn.sigmoid(_matmul(a, layer["Wb"], cd))  # [B, T, H] in (0, 2)
    with jax.named_scope("kda"):
        q, k, v = (z.reshape(b, t, h, d) for z in (q, k, v))
        q = q * (jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + UNIT_EPS) * d ** -0.5)
        k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + UNIT_EPS)
        o = kda_scan(q, k, v, g, beta, m.chunk, cd)
    with jax.named_scope("gnorm"):
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * layer["o_norm"]
        y = o.reshape(b, t, inner) * jax.nn.sigmoid(_matmul(_matmul(a, layer["Ga"], cd), layer["Gb"], cd))
    return _proj(y, layer["wo"], cd)


def _gated_delta(x, layer, m: GatedDelta, eps: float, cd, interpret: bool):
    """A delta-rule layer with ONE decay a head (``reference_olmo_hybrid.py``
    has the equations): q, k and v through one projection, a causal depthwise
    convolution and SiLU; q and k at unit length a head (q over
    ``sqrt(key_dim)`` more); a log-decay a head, ``-exp(A_log) softplus(.)``,
    and the correction's strength ``2 sigmoid(.)``; the gated delta rule in
    chunks on a state ``[key_dim, value_dim]`` a head; each head's output
    RMS-normed and gated by ``silu`` of a full projection; one projection back
    (of the heads held here: a share of the layer's sum where they are a share
    of its heads), normed as it is where the record names a norm. The decays,
    the strengths, the unit vectors and the rule's state are float32 whatever
    ``cd`` is."""
    b, t, _ = x.shape
    h, dk, dv = m.heads, m.key_dim, m.value_dim
    keys, values = h * dk, h * dv
    a = _read(x, layer, m, eps)
    with jax.named_scope("proj"):
        u = _matmul(a, jnp.concatenate([layer["wq"], layer["wk"], layer["wv"]], axis=1), cd)
        gate = _matmul(a, layer["wg"], cd)  # a matmul of its own: behind q, k and v in ONE projection the gate has to be
        # cut out of the wider array and the wider gradient concatenated, which measured no faster (PERF.md, PR 54)
    with jax.named_scope("conv"):
        # the convolution's kernels walk q, k and v where they lie in u, as ONE part: their edges (15 heads of 96:
        # 1,440) tile no lane where the whole (5,760) does; what cuts them apart is fused into the unit vectors' pass
        taps = jnp.concatenate([layer["conv_q"], layer["conv_k"], layer["conv_v"]], axis=1)
        _, (qkv,), _ = causal_conv(u, taps, jnp.zeros((2 * keys + values,), jnp.float32), (2 * keys + values,))
    with jax.named_scope("kgate"):
        both = _matmul(a, jnp.concatenate([layer["Wa"], layer["Wb"]], axis=1), cd)  # [B, T, 2 H]
        g = -jnp.exp(layer["A_log"]) * jax.nn.softplus(both[..., :h] + layer["dt_bias"])  # one log-decay a head
        beta = 2.0 * jax.nn.sigmoid(both[..., h:])  # [B, T, H] in (0, 2)
    with jax.named_scope("kda"):
        q, k = qkv[..., :keys].reshape(b, t, h, dk), qkv[..., keys: 2 * keys].reshape(b, t, h, dk)
        v = qkv[..., 2 * keys:].reshape(b, t, h, dv)
        q = q * (jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + UNIT_EPS) * dk ** -0.5)
        k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + UNIT_EPS)
        o = kda_scan(q, k, v, g, beta, m.chunk, cd)
    with jax.named_scope("gnorm"):
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * layer["o_norm"]
        y = o.reshape(b, t, values) * jax.nn.silu(gate)
    y = _proj(y, layer["wo"], cd)
    return _rms_norm(y, layer[m.out_norm], eps) if m.out_norm else y


def _attend(x, layer, m: Attention, eps: float, cd, interpret: bool):
    """Causal attention on (grouped) queries through the fused fold, with what
    the record asks for around it: a QK-norm on the projections (over all
    heads' channels together, or over each head's), a rotation of q and k, a
    window, a sigmoid gate per head or per channel on the output, a norm on
    the projection back. Under ``diffusion_block`` the positions are a sequence and its noised
    copy, ``[x ; x~]``: each half turns at positions ``0 .. T - 1`` and the
    mask is block diffusion's."""
    a = _read(x, layer, m, eps)
    t = x.shape[1] // 2 if m.diffusion_block else x.shape[1]

    def head(w: str, n: int, norm: str):
        z = _proj(a, layer[w], cd)
        if m.qk_norm == "head" and norm:  # each head's channels by themselves, one weight for every head
            z = _rms_norm(z.reshape(*z.shape[:2], n, -1), layer[norm], eps)
        elif m.qk_norm and norm:
            z = _rms_norm(z, layer[norm], eps)
        return _heads(z, n)

    q, k, v = head("wq", m.heads, "q_norm"), head("wk", m.kv_heads, "k_norm"), head("wv", m.kv_heads, "")
    if m.rotation is not None:
        cos, sin = _yarn_tables(t, m.rotation.channels, m.rotation.theta, m.rotation.yarn)
        if m.diffusion_block:
            with jax.named_scope("rope"):  # the T positions' tables twice
                cos, sin = jnp.tile(cos, (2, 1)), jnp.tile(sin, (2, 1))
        q, k = _rope_part(q, cos, sin), _rope_part(k, cos, sin)
    mask = {"blocks": BlockDiffusion(t, m.diffusion_block)} if m.diffusion_block else {}
    o = _fold(q, k, v, cd, interpret, m.window or None, **mask)
    if m.head_gate:
        with jax.named_scope("gate"):
            g = jax.nn.sigmoid(_matmul(a, layer["head_gate"], cd))  # [B, T, H]
            o = o * jnp.transpose(g, (0, 2, 1))[..., None]
    o = _merged(o)
    if m.out_gate:
        with jax.named_scope("gate"):
            o = o * jax.nn.sigmoid(_matmul(a, layer["wg"], cd))  # [B, T, H D], before the projection back
    o = _proj(o, layer["wo"], cd)
    return _rms_norm(o, layer[m.out_norm], eps) if m.out_norm else o


def _latent_attend(x, layer, m: LatentAttention, eps: float, cd, interpret: bool):
    """Latent attention: the queries through a ``q_rank``-wide normed latent;
    a token's keys and values rebuilt from ONE ``kv_rank``-wide normed latent,
    a head's ``nope_dim`` key channels without position and its ``v_dim``
    values; beside the latent, one rotary key of ``rope_dim`` channels a token,
    which every head reads behind its own keys. RoPE (interleaved pairs) turns
    the last ``rope_dim`` channels of every query head and that one key. The
    fold contracts ``nope_dim + rope_dim`` channels and hands back ``v_dim``."""
    b, t, _ = x.shape
    h, nope, rope = m.heads, m.nope_dim, m.rope_dim
    a = _rms_norm(x, layer[m.norm], eps)
    with jax.named_scope("latent"):
        q = _matmul(_rms_norm(_matmul(a, layer["wq_a"], cd), layer["q_a_norm"], eps), layer["wq_b"], cd)
        down = _matmul(a, layer["wkv_a"], cd)  # [B, T, kv_rank + rope]: the latent, then the rotary key
        up = _matmul(_rms_norm(down[..., :m.kv_rank], layer["kv_a_norm"], eps), layer["wkv_b"], cd)
    q, up = _heads(q, h), _heads(up, h)  # [B, H, T, nope + rope], [B, H, T, nope + v]
    cos, sin = _pair_tables(t, rope, m.rotation.theta, lead=nope)
    q = _rope_pairs(q, cos, sin)
    k_rope = _rope_pairs(down[..., m.kv_rank:], cos[:, nope:], sin[:, nope:])  # [B, T, rope]
    with jax.named_scope("rope"):  # the assembly of the heads' keys: a head's own channels, then the shared key
        k = jnp.concatenate([up[..., :nope], jnp.broadcast_to(k_rope[:, None], (b, h, t, rope))], axis=-1)
    with jax.named_scope("fold"):
        v = up[..., nope:]
    return _proj(_merged(_fold(q, k, v, cd, interpret)), layer["wo"], cd)


_MIX = {Attention: _attend, LatentAttention: _latent_attend, CCA: _cca, Mamba2: _mamba2, KDA: _kda,
        GatedDelta: _gated_delta}


def _feed_forward(x, carry, layer, f, eps: float, cd):
    """``(y, carry, stats)``: one dense SwiGLU (no statistics), or the routed
    experts' part of the result (``parallel/moe.py``) plus the shared expert's
    where there is one. An MLP router's hidden state is the ``carry`` handed
    to the next layer's router."""
    b, t, d = x.shape
    u = _read(x, layer, f, eps)
    if isinstance(f, Dense):
        with jax.named_scope("ffn"):
            y = dense_swiglu(u, layer["w_gate"], layer["w_up"], layer["w_down"], cd)
        return (_rms_norm(y, layer[f.out_norm], eps) if f.out_norm else y), carry, {}
    u = u.reshape(b * t, d)
    if f.router_width:
        carry = _router_state(u, layer, carry)
        router = lambda _: _router_logits(carry, layer, eps)  # noqa: E731
    else:
        router = layer["router"]
    y, stats = moe_dropless(u, router, layer["w_gate"] if f.gated else None, layer["w_up"], layer["w_down"], f.top_k,
                            cd, f.first_held, f.routed_scale, layer["router_bias"] if f.routed_scale else None,
                            f.renormalise)
    if f.shared_width is not None:
        with jax.named_scope("shared"):
            y = y + dense_swiglu(u, layer["shared_gate"] if f.gated else None, layer["shared_up"],
                                 layer["shared_down"], cd)
    return y.reshape(b, t, d), carry, stats


def _layer(x, carry, layer, spec: Layer, cd, interpret: bool):
    """One layer of any stack, as its record (``config.layers``) says: the
    mixer, then the feed-forward, whichever are there, each joined to the
    residual stream. ``carry`` is what a layer hands the next beside the
    stream (an MLP router's hidden state); the statistics are the experts'."""
    def join(x, y, sub: str):
        if spec.scaled:
            return _scaled(x, y, layer, sub)
        with jax.named_scope("mix"):
            return x + y

    stats = {}
    if spec.mixer is not None:
        x = join(x, _MIX[type(spec.mixer)](x, layer, spec.mixer, spec.eps, cd, interpret), "attn")
    if spec.ffn is not None:
        y, carry, stats = _feed_forward(x, carry, layer, spec.ffn, spec.eps, cd)
        x = join(x, y, "ffn")
    return x, carry, stats


def _hidden(params, tok, cfg: LMConfig, cd, interpret: bool, mtp: bool = False, tail: int = 0):
    """The final-normed hidden states (``tail`` > 0: of the last ``tail``
    positions alone, the noised half of a doubled sequence: the others' states
    are read by no head), each expert layer's router statistics
    and the exit gate's logits. A stack without an exit gate passes once:
    ``[B, T, d]``, no logits. With one, the stack runs ``cfg.loops`` times over
    the same leaves, each pass's normed state feeding the next: ``[R, B, T,
    d]`` and the gate's logits ``[R, B, T]``. Last, under ``mtp`` (a stack
    with a multi-token-prediction module, in training: else None), the
    module's normed hidden states ``[B, T, d]``, position ``i``'s the state
    that predicts token ``i + 2``; its layer's statistics follow the stack's."""
    with jax.named_scope("lm.embed"):
        x = params["embed"][tok]

    @functools.cache
    def scoped(spec, scope="lm.block"):  # layers of one record trace to one shared sub-program where their leaves agree
        def block(x, carry, layer):  # the scope opens inside what is rematerialised
            with jax.named_scope(scope):
                return _layer(x, carry, layer, spec, cd, interpret)

        # a lone block's residuals are wanted as soon as the head's backward
        # ends: holding them costs nothing at the peak, recomputing them a forward
        return jax.checkpoint(block) if cfg.n_layers * cfg.loops > 1 else block

    def stack(x):
        routed, carry = [], None
        for spec, layer in zip(layers(cfg), params["layers"]):
            x, carry, stats = scoped(spec)(x, carry, layer)
            if stats:  # a layer without experts has nothing to report
                routed.append(stats)
        with jax.named_scope("lm.final_norm"):
            h = _rms_norm(x[:, -tail:] if tail else x, params["final_norm"], cfg.norm_eps)
        if not mtp:
            return h, routed, None
        # position i's stream beside the embedding of token i + 1, through one more layer: the state that predicts
        # token i + 2. The last position has no next token: it takes the first's as a filler, which causality keeps
        # from every other position and no loss reads (its routed rows ride the dropless experts and are counted).
        module = params["mtp"]
        with jax.named_scope("lm.mtp/proj"):
            nxt = params["embed"][jnp.roll(tok, -1, axis=1)]
            both = jnp.concatenate([_rms_norm(x, module["hnorm"], cfg.norm_eps),
                                    _rms_norm(nxt, module["enorm"], cfg.norm_eps)], axis=-1)
            y = _matmul(both, module["eh_proj"], cd)
        y, _, stats = scoped(mtp_layer(cfg), "lm.mtp/lm.block")(y, None, module["layer"])
        with jax.named_scope("lm.mtp"):
            return h, routed + ([stats] if stats else []), _rms_norm(y, module["norm"], cfg.norm_eps)

    if not exit_gate(cfg):
        h, routed, ahead = stack(x)
        return h, routed, None, ahead

    def one_pass(h, _):
        h, _, _ = stack(h)
        with jax.named_scope("lm.exit"):
            return h, (h, jnp.sum(h * params["exit_gate_w"][:, 0], axis=-1) + params["exit_gate_b"][0])

    _, (passes, gate) = jax.lax.scan(one_pass, x, None, length=cfg.loops)
    return passes, [], gate, None


def _head(params, cfg: LMConfig):
    """The head ``[d, V]``: its own matrix, or the embedding table transposed
    (one leaf, whose gradient is then the lookup's scatter plus the head's
    matmuls)."""
    if not cfg.tied:
        return params["lm_head"]
    with jax.named_scope("lm.head"):
        return params["embed"].T


def _chunk_nll(logits, tc):
    """``(logsumexp, nll)`` of one chunk's logits against its targets ``tc``."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    return lse, lse - jnp.take_along_axis(logits, tc[:, None], axis=1)[:, 0]


def _chunked_nll(h, lm_head, targets, cd):
    """``[chunks, chunk]`` f32: the per-token negative log-likelihoods of ``h
    [chunks, chunk, d]`` against ``targets``, a chunk's logits at a time."""
    w = lm_head.astype(cd)

    def one(args):
        hc, tc = args
        return _chunk_nll(_matmul(hc, w, cd), tc)[1]

    return jax.lax.map(one, (h, targets))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _weighted_nll(h, lm_head, targets, weight, cd):
    """``(sum_i weight_i nll_i, nll)`` over ``h [chunks, chunk, d]``: the
    training head. Differentiated, the pass that holds a chunk's logits forms
    that chunk's ``dh`` and its share of ``dW`` there and then (``_weighted_nll_fwd``),
    so the logits are computed once a step; the backward scales them. ``nll``
    carries no gradient: its cotangent is not read."""
    nll = _chunked_nll(h, lm_head, targets, cd)
    return jnp.sum(weight * nll), nll


def _weighted_nll_fwd(h, lm_head, targets, weight, cd):
    w = lm_head.astype(cd)

    def one(dw, args):
        hc, tc, wc = args
        # a chunk's rows are rounded inside the loop: left free, XLA hoists the cast into one [chunks, chunk, d] stack
        # in the compute type, and past the 8 chunks (64 MB) it can keep in VMEM the matmuls that read it stream it from
        # HBM a tile of their output at a time (Ouro's 16 chunks: ``dW`` 3.7 ms a chunk where 2.7 is what it takes)
        hc = jax.lax.optimization_barrier(hc.astype(cd))
        logits = _matmul(hc, w, cd)
        lse, nll = _chunk_nll(logits, tc)
        hit = jax.lax.broadcasted_iota(tc.dtype, logits.shape, 1) == tc[:, None]
        # d total / d logits, rounded where the two matmuls below take it in
        d = (wc[:, None] * (jnp.exp(logits - lse[:, None]) - hit.astype(jnp.float32))).astype(cd)
        precision = _HIGHEST if cd == jnp.float32 else None
        dh = jax.lax.dot_general(d, w, (((1,), (1,)), ((), ())), precision=precision,
                                 preferred_element_type=jnp.float32)
        dw = dw + jax.lax.dot_general(hc, d, (((0,), (0,)), ((), ())), precision=precision,
                                      preferred_element_type=jnp.float32)
        return dw, (nll, dh)

    dw, (nll, dh) = jax.lax.scan(one, jnp.zeros(lm_head.shape, jnp.float32), (h, targets, weight))
    return (jnp.sum(weight * nll), nll), (dh, dw, nll)


def _weighted_nll_bwd(cd, residuals, cotangents):
    dh, dw, nll = residuals
    g, _ = cotangents
    return g * dh, g * dw, None, g * nll


_weighted_nll.defvjp(_weighted_nll_fwd, _weighted_nll_bwd)


def _target_nll(h, lm_head, targets, cd, last: bool = True):
    """The head over ``h [B, T, d]`` against handed ``targets [B, T]``, bound to
    its inputs: what comes back is called with nothing, to score, and gives
    ``nll [B, T]`` f32, minus the log-probability of position ``t``'s target;
    or with token weights ``[B, T]`` f32, to train, and gives ``(sum_i weight_i
    nll_i, nll)``, whose first is the objective's part through the head - every
    caller's is linear in the per-token ``nll`` - and whose ``nll`` is for
    reporting and carries no gradient. ``last`` false: the last position has no
    target (its ``nll`` and its weight are 0, whatever is handed in). The
    ``[chunk, V]`` logits exist one chunk of token rows at a time and are
    computed ONCE a step: the pass that has them forms ``w_i (softmax_i -
    onehot_i)`` and from it that chunk's ``dh`` and its share of ``dW``
    (float32 over the chunks), and the backward multiplies both by the incoming
    scalar (``_weighted_nll``). The weights are differentiable (their cotangent
    is ``nll``'s)."""
    b, t, d = h.shape
    n = b * t
    chunk = _LOSS_CHUNK if n % _LOSS_CHUNK == 0 else t
    with jax.named_scope("lm.head"):
        targets = targets.reshape(n // chunk, chunk)
        rows = h.reshape(n // chunk, chunk, d)

    def whole(a):
        a = a.reshape(b, t)
        return a if last else a.at[:, -1].set(0.0)

    def score(weight=None):
        with jax.named_scope("lm.head"):
            if weight is None:
                return whole(_chunked_nll(rows, lm_head, targets, cd))
            weight = whole(weight.astype(jnp.float32)).reshape(n // chunk, chunk)
            total, nll = _weighted_nll(rows, lm_head, targets, weight, cd)
            return total, whole(jax.lax.stop_gradient(nll))

    return score


def _next_token_nll(h, lm_head, tok, cd):
    """``_target_nll`` of next-token prediction: position ``t``'s target is
    token ``t + 1``, and the last position has none."""
    with jax.named_scope("lm.head"):
        targets = jnp.concatenate([tok[:, 1:], jnp.zeros((tok.shape[0], 1), tok.dtype)], axis=1)
    return _target_nll(h, lm_head, targets, cd, last=False)


#: The stream of the stage's seed that block diffusion's corruption draws from: ``fold_in(key(seed), NOISE_STREAM)``,
#: then ``fold_in(., step index)`` a step. The parameters' streams are ``fold_in(key(seed), leaf index)``, far below.
NOISE_STREAM = 2 ** 30


def _corrupt(tok, noise, cfg: LMConfig):
    """Block diffusion's corruption of ``tok [B, T]`` from ``noise = (key,
    index)``, the stage's noise key (``NOISE_STREAM``) and the job's step
    index: with ``k = fold_in(key, index)`` and ``k_t, k_m = split(k)``, a
    level ``t = uniform(k_t, [B])`` a sequence, ``p = (1 - eps) t + eps`` (``config.NOISE_EPS``), and
    token ``i`` of sequence ``s`` masked where ``uniform(k_m, [B, T])[s, i] <
    p[s]`` (float32 draws). Returns the doubled input ``[x ; x~] [B, 2 T]``
    (``x~`` is ``x`` with ``mask_id`` at the masked positions), the mask ``[B,
    T]`` and ``p [B]``."""
    key, index = noise
    k_t, k_m = jax.random.split(jax.random.fold_in(key, index))
    level = jax.random.uniform(k_t, tok.shape[:1], jnp.float32)
    p = (1.0 - NOISE_EPS) * level + NOISE_EPS
    masked = jax.random.uniform(k_m, tok.shape, jnp.float32) < p[:, None]
    noised = jnp.where(masked, jnp.asarray(cfg.mask_id, tok.dtype), tok)
    return jnp.concatenate([tok, noised], axis=1), masked, p


def _diffusion_loss(params, tok, noise, cfg: LMConfig, cd, interpret: bool):
    """Block diffusion's objective (arXiv:2503.09573 section 3; ``reference_sdar.py``): ``1 / (B T) sum over masked i
    of (1 / p) x -log softmax(h~_i W_head)[x_i]``, a one-draw estimate of a bound on the sequences' negative
    log-likelihood a token. The stack runs once over ``[x ; x~]``; the head takes the noised half's ``T`` rows, the
    position's own token as its target and ``masked / (p B T)`` as its weight: nothing is read from the clean half's
    rows or from unmasked positions. Returns ``(loss, per-token weighted nll [B, T], routed, stats)``."""
    b, t = tok.shape
    with jax.named_scope("lm.noise"):
        both, masked, p = _corrupt(tok, noise, cfg)
    h, routed, _, _ = _hidden(params, both, cfg, cd, interpret, tail=t)
    with jax.named_scope("lm.head"):
        weight = masked.astype(jnp.float32) / (p[:, None] * (b * t))
    loss, nll = _target_nll(h, _head(params, cfg), tok, cd)(weight)
    with jax.named_scope("lm.noise"):
        stats = {"targets_masked": jnp.sum(masked.astype(jnp.int32)), "noise_level_sum": jnp.sum(p)}
    return loss, weight * nll, routed, stats


def _load_balancing(routed, cfg: LMConfig):
    """``E * sum_e f_e * P_e`` over all blocks' tokens together (equal token
    counts per block, so the means over blocks are the means over tokens)."""
    f = sum(s["f"] for s in routed) / len(routed)
    p = sum(s["P"] for s in routed) / len(routed)
    return cfg.n_experts * jnp.sum(f * p)


def _exit_distribution(gate):
    """From the gate's logits ``z [R, B, T]`` the log of ``p_r = sigmoid(z_r)
    prod_(j<r) (1 - sigmoid(z_j))`` for ``r < R`` and of ``p_R = prod_(j<R)
    (1 - sigmoid(z_j))``: each token's distribution over the pass it exits at."""
    stay = jnp.cumsum(jax.nn.log_sigmoid(-gate), axis=0)  # log prod_(j<=r) (1 - lambda_j)
    before = jnp.concatenate([jnp.zeros_like(gate[:1]), stay[:-1]], axis=0)
    # the last pass takes what is left: its own gate is not asked
    leave = jnp.concatenate([jax.nn.log_sigmoid(gate[:-1]), jnp.zeros_like(gate[:1])], axis=0)
    return leave + before


def _exit_loss(passes, gate, lm_head, tok, cfg: LMConfig, cd):
    """The looped stack's objective: the mean over target positions of ``sum_r
    p_r nll_r - beta H(p)``, every pass through the one head in its one chunked
    loop under the weights ``p / targets`` (the gate's gradient reaches it
    through them); and what ``train.drain`` reports: each pass's own mean
    cross-entropy (``trip_nll``) and, summed over the step's tokens, the
    expected exit pass, the mass left to the last pass and the exit
    distribution's entropy."""
    r, b, t, d = passes.shape
    targets = b * (t - 1)
    with jax.named_scope("lm.exit"):
        flat, tiled = passes.reshape(r * b, t, d), jnp.tile(tok, (r, 1))
        log_p = _exit_distribution(gate)
        every = jnp.exp(log_p)
        p = every.at[:, :, -1].set(0.0)  # the last position has no target
        weight = (p / targets).reshape(r * b, t)
    weighted, nll = _next_token_nll(flat, lm_head, tiled, cd)(weight)
    with jax.named_scope("lm.exit"):
        nll = nll.reshape(r, b, t)
        loss = weighted + cfg.exit_beta * jnp.sum(p * log_p) / targets
        trip = jnp.arange(1, r + 1, dtype=jnp.float32)[:, None, None]
        return loss, {"trip_nll": jnp.sum(nll, axis=(1, 2)) / targets, "exit_trip_sum": jnp.sum(trip * every),
                      "exit_last_mass": jnp.sum(every[-1]), "gate_entropy_sum": -jnp.sum(every * log_p)}


def _loss(params, tok, cfg: LMConfig, cd, interpret: bool, noise=None):
    """``(loss, stats)``: ``stats`` holds what the blocks have to report - the
    rows each expert took (``rows``), the rows each expert layer carried
    (``carried``), the exits' sums (``_exit_loss``), the multi-token-prediction
    module's summed cross-entropy and the positions it scored (``mtp_nll_sum``,
    ``mtp_targets``). The module's term is ``mtp_coef`` times its mean. Each
    term through the head is a weighted sum of per-token ``nll`` whose weights
    the head takes in (``_next_token_nll``): the mean's ``1 / (B (T - 1))``,
    the module's ``mtp_coef / mtp_targets`` on the positions it scores, the
    exits' ``p / (B (T - 1))``. Under ``cfg.block_length`` the objective is
    block diffusion's (``_diffusion_loss``, from ``noise``): its statistics
    are the positions it scored (``targets_masked``) and the sequences' summed
    masking probabilities (``noise_level_sum``)."""
    b, t = tok.shape
    ahead = None
    if cfg.block_length:
        loss, _, routed, stats = _diffusion_loss(params, tok, noise, cfg, cd, interpret)
    else:
        h, routed, gate, ahead = _hidden(params, tok, cfg, cd, interpret, mtp=bool(cfg.mtp_depth))
        if gate is None:
            with jax.named_scope("lm.head"):
                mean = jnp.full((b, t), 1.0 / (b * (t - 1)), jnp.float32)
            loss, _ = _next_token_nll(h, _head(params, cfg), tok, cd)(mean)
            stats = {}
        else:
            loss, stats = _exit_loss(h, gate, _head(params, cfg), tok, cfg, cd)
    if ahead is not None:
        with jax.named_scope("lm.mtp"):
            # the same head over targets one further on: position i scores token i + 2, the last two nothing
            scored = jnp.arange(t) < t - 2
            stats["mtp_targets"] = b * jnp.sum(scored.astype(jnp.int32))
            weight = jnp.broadcast_to(cfg.mtp_coef * scored / stats["mtp_targets"], (b, t))
            term, nll = _next_token_nll(ahead, _head(params, cfg), jnp.roll(tok, -1, axis=1), cd)(weight)
            stats["mtp_nll_sum"] = jnp.sum(jnp.where(scored, nll, 0.0))
            loss = loss + term
    if cfg.aux_coef:
        with jax.named_scope("lm.aux"):
            loss = loss + cfg.aux_coef * _load_balancing(routed, cfg)
    if routed:
        stats["rows"] = jnp.stack([s["rows"] for s in routed])
        if "carried" in routed[0]:  # the experts take their rows a window at a time (parallel/moe.py)
            stats["carried"] = jnp.stack([s["carried"] for s in routed])
    return loss, stats


def _optimizer(lr: float):
    return optax.chain(
        optax.clip_by_global_norm(CLIP_NORM),
        optax.adamw(lr, b1=ADAM_B1, b2=ADAM_B2, eps=ADAM_EPS, weight_decay=WEIGHT_DECAY),
    )


@functools.cache
def _train_program(cfg: LMConfig, compute_type: str, lr: float, batch: int, interpret: bool):
    """``(optimizer, step)``; ``step(params, opt_state, window, lo)`` trains on
    rows ``lo .. lo + batch`` of the device-resident window and returns the new
    state, the loss, every parameter's gradient norm before clipping (in
    ``param_shapes`` order) and the step's statistics (``_loss``). A stage
    that trains by block diffusion calls it with one more argument, ``noise =
    (the stage's noise key, the job's step index)``: what the step's
    corruption is drawn from (``_corrupt``).
    ``optimizer.init`` is jitted: the state a fit starts from is one device
    program's output however many leaves the tree has (eager, optax fills
    ``mu`` and ``nu`` a leaf at a time, the device idle between the fills)."""
    cd = jnp.dtype(compute_type)
    optimizer = _optimizer(lr)

    def step(params, opt_state, window, lo, noise=None):
        tok = jax.lax.dynamic_slice_in_dim(window, lo, batch, axis=0)
        (loss, stats), grads = jax.value_and_grad(_loss, has_aux=True)(params, tok, cfg, cd, interpret, noise)
        with jax.named_scope("lm.opt"):
            norms = jnp.stack([jnp.sqrt(jnp.sum(g * g)) for g in _ordered(grads, cfg)])
            updates, opt_state = optimizer.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss, norms, stats

    budget = None if interpret else {"xla_tpu_max_hbm_size_mib": STEP_HBM_MIB}
    return (optimizer._replace(init=jax.jit(optimizer.init)),
            jax.jit(step, donate_argnums=(0, 1), compiler_options=budget))


def _head_logit_matmuls(jaxpr, vocab: int, in_head: bool = False) -> int:
    """The matmuls ``[chunk, d] @ [d, vocab]`` under ``lm.head`` among
    ``jaxpr``'s equations and those of every jaxpr inside them (a loop's body,
    what a ``checkpoint`` recomputes): each is one pass over a chunk's logits."""
    found = 0
    for eqn in jaxpr.eqns:
        inside = in_head or "lm.head" in str(eqn.source_info.name_stack)
        if (inside and eqn.primitive.name == "dot_general" and eqn.outvars[0].aval.shape[1:] == (vocab,)
                and eqn.params["dimension_numbers"][0] == ((1,), (0,))):
            found += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _head_logit_matmuls(sub, vocab, inside)
    return found


#: What ``_traced_counts`` read off a step as traced, by step program and window shape.
_TRACED_COUNTS: dict = {}


def _noise_key(seed: int):
    """The stage's noise key: the stream of its seed that the corruption's draws come from (``_corrupt``)."""
    return jax.random.fold_in(jax.random.key(seed), NOISE_STREAM)


def _traced_counts(step, params, opt_state, window, cfg: LMConfig, *noise) -> dict:
    """What one ``step`` on ``window`` does, from the step as traced (``jit``
    keeps the trace: the first step's call finds it). ``conv_positions_kernel``:
    the positions x channels that the convolution's forward kernels cover, from
    their calls (a Mamba-2 layer that went around the kernels would not be
    counted). ``head_logit_matmuls``: the matmuls that make a chunk's ``[chunk,
    V]`` logits, a head call (the stack's; a multi-token-prediction module's):
    1 where the head forms its gradients in the pass that holds the logits, 2
    where a backward computes them again. ``fold_one_block``: the attention
    layers as traced (a looped stack's once) whose fold is the one-block form,
    by their backward kernel's calls, whatever it is called;
    ``fold_bwd_kernels``: the backward fold kernels of one layer, by their
    names (1: one walk gives ``dq``, ``dk`` and ``dv``; 2 where the ring form's
    dq and dkv kernels each walk); ``fold_row_stats``: the float32 row
    statistics that one layer's fold kernels take and hand back, the most of any
    call (2 in the one-block form: ``lse`` out of the forward and into the
    backward; 17 where the ring's carried state goes through three kernels)."""
    key = (step, window.shape)
    if key not in _TRACED_COUNTS:
        jaxpr = step.trace(params, opt_state, window, jax.ShapeDtypeStruct((), jnp.int32),
                           *noise).jaxpr.jaxpr  # runs nothing
        folds = fold_kernel_calls(jaxpr)
        parts = {part for part, _ in folds}  # the fold's kernels by name: fwd, bwd_dkv (and the ring form's bwd_dq)
        _TRACED_COUNTS[key] = {
            "conv_positions_kernel": forward_positions(jaxpr),
            "head_logit_matmuls": _head_logit_matmuls(jaxpr, cfg.vocab) // (1 + cfg.mtp_depth),
            "fold_one_block": sum(part.startswith("bwd") and stats == ONE_BLOCK_ROW_STATS.get(part)
                                  for part, stats in folds),
            "fold_bwd_kernels": sum(part.startswith("bwd") for part in parts),
            "fold_row_stats": sum(max(stats for p, stats in folds if p == part) for part in parts)}
    return _TRACED_COUNTS[key]


@functools.cache
def _log_likelihood_program(cfg: LMConfig, compute_type: str, interpret: bool):
    cd = jnp.dtype(compute_type)

    def run(params, tok, noise=None):
        if cfg.block_length:  # one draw's estimate of the bound the fit minimises, a row: minus its weighted nll
            _, weighted, _, _ = _diffusion_loss(params, tok, noise, cfg, cd, interpret)
            return -jnp.sum(weighted, axis=1) * tok.shape[0]
        h, _, gate, _ = _hidden(params, tok, cfg, cd, interpret)  # no module: it is a training objective
        if gate is not None:
            h = h[-1]  # the exit threshold is 1: no token leaves before the last pass
        nll = _next_token_nll(h, _head(params, cfg), tok, cd)()
        return -jnp.sum(nll, axis=1) / (tok.shape[1] - 1)

    return jax.jit(run)


def _fold_mode(t: int, cfg: LMConfig) -> bool:
    """Whether the fused fold runs interpreted (off the TPU), after checking
    that it (and a stack with Mamba-2 layers' scan or delta-rule layers, in chunks of ``cfg.chunk``)
    can serve this sequence at every layer's head sizes; there is no other
    attention path. Under block diffusion the fold's sequence is the doubled
    one, ``2 t`` positions in whole blocks."""
    if cfg.block_length:
        if t % cfg.block_length:
            raise ValueError(f"sequence length {t} must be whole blocks of blockLength {cfg.block_length}")
        t = 2 * t
    chunk = cfg.chunk
    if t % TQ_TILE or (chunk and t % chunk):
        raise ValueError(f"sequence length {t} must be a multiple of {TQ_TILE} (the fused fold's Q tile)"
                         + (f" and of {chunk} (the scan's chunk)" if chunk else ""))
    if not is_tpu_backend(jax.devices()):
        return True
    for m in {spec.mixer for spec in layers(cfg)}:
        if isinstance(m, (Attention, LatentAttention, CCA)):
            d_v = getattr(m, "v_dim", m.head_dim)
            if not flash_available(t, m.head_dim, Dv=d_v):
                raise ValueError(
                    f"the fused attention fold does not admit T={t} at {m.head_dim} query and key channels and {d_v} "
                    "value channels a head (parallel/flash.py::flash_available); DecoderLM has no other attention path"
                )
    return False


def _token_matrix(df, col: str) -> np.ndarray:
    tok = np.asarray(df.vectors(col))
    if tok.ndim != 2 or tok.shape[1] < 2:
        raise ValueError("the features column must hold equal-length token-id vectors of length >= 2")
    if tok.size and tok.min() < 0:
        raise ValueError("token ids must be non-negative")
    return tok.astype(np.int32)


class DecoderLMModel(Model, _LMParams):
    """Serving side: per-row mean next-token log-likelihood through the same
    forward, by the main head alone: a multi-token-prediction module is a
    training objective, saved and loaded with the tree and not run here. A
    model trained by block diffusion (``blockKind`` ``sdar``) has no next-token
    likelihood: its row's prediction is the one-draw estimate of the bound the
    fit minimises, ``-(1 / T) sum over masked i of (1 / p) nll_i``, batch ``i``
    of ``globalBatchSize`` rows corrupted from the stage's seed as step ``i``
    of a fit's would be.
    ``params`` holds device arrays after a fit, host arrays after
    ``load``/``set_model_data``; either is placed once per call."""

    def __init__(self):
        super().__init__()
        self.params: Optional[dict] = None

    def transform(self, *inputs):
        (df,) = inputs
        cfg = self.lm_config()
        tok = _token_matrix(df, self.get_features_col())
        if tok.size and tok.max() >= cfg.vocab:
            raise ValueError(f"token ids must be in [0, {cfg.vocab}); got up to {tok.max()}")
        n, t = tok.shape
        program = _log_likelihood_program(cfg, self.get_compute_type(), _fold_mode(t, cfg))
        params = jax.tree_util.tree_map(jnp.asarray, self.params)
        batch = min(self.get_global_batch_size(), n)
        out = np.empty(n, np.float64)
        noise_key = _noise_key(self.get_seed()) if cfg.block_length else None
        for i, lo in enumerate(range(0, n, batch)):
            at = min(lo, n - batch)  # the tail re-reads the rows before it
            noise = ((noise_key, jnp.int32(i)),) if cfg.block_length else ()  # batch i is corrupted as step i's is
            out[at: at + batch] = np.asarray(program(params, jnp.asarray(tok[at: at + batch]), *noise))
        result = df.clone()
        result.add_column(self.get_prediction_col(), DataTypes.DOUBLE, out)
        return result

    # --- persistence ---------------------------------------------------------
    def _host_leaves(self) -> dict:
        cfg = self.lm_config()
        leaves = jax.device_get(_ordered(self.params, cfg))
        return {name: np.asarray(a) for name, a in zip(_flat_names(cfg), leaves)}

    def save(self, path: str) -> None:
        rw.save_metadata(self, path)
        rw.save_model_arrays(path, self._host_leaves())

    @classmethod
    def load(cls, path: str):
        metadata = rw.load_metadata(path, rw.stage_class_name(cls))
        model = cls()
        model.load_param_map_from_json(metadata["paramMap"])
        arrays = rw.load_model_arrays(path)
        cfg = model.lm_config()
        model.params = _build_tree(cfg, [arrays[name] for name in _flat_names(cfg)])
        return model

    def get_model_data(self):
        from flink_ml_tpu.api.dataframe import DataFrame

        return [DataFrame(["params"], None, [[self._host_leaves()]])]

    def set_model_data(self, *model_data):
        arrays = model_data[0].column("params")[0]
        cfg = self.lm_config()
        self.params = _build_tree(cfg, [np.asarray(arrays[name]) for name in _flat_names(cfg)])
        return self


class DecoderLM(Estimator, _LMParams):
    """AdamW training of a decoder-only language model on token-id vectors: the objective is a weighted sum of per-token cross-entropies (next-token prediction; blockKind 'sdar': block diffusion over the sequence and its masked copy, and transform then reports a one-draw estimate of the bound a row), whose gradients the head forms in the one pass that holds its logits.

    The objective's terms through the head differ only in the weights they
    hand it (the mean over the targets; ``mtpLossCoef`` over the positions a
    multi-token-prediction module scores; a looped stack's exit distribution),
    so the ``[chunk, vocabulary]`` logits are computed once a step.

    The features column holds equal-length token-id vectors (length a
    multiple of 256). ``globalBatchSize`` counts ROWS (sequences): tokens a step =
    rows x length. After ``fit``, per step: ``loss_history``,
    ``grad_norm_history`` (global, before clipping), ``param_grad_norm_history``
    (``[steps, parameters]``, columns named by ``param_names``),
    ``expert_rows_history`` (``[steps, layers with experts, experts]`` routed
    rows; no experts, no columns; a multi-token-prediction module's layer follows
    the stack's), ``trip_loss_history`` (``[steps, passes]``: each pass's own mean
    cross-entropy; a stack passed once has no columns) and ``mtp_loss_history``
    (the module's own mean cross-entropy; no module, empty; ``loss_history`` holds
    the whole objective) and ``targets_masked_history`` (the positions block
    diffusion's objective scored; another objective, empty).

    ``blockKind`` ``sdar`` trains by block diffusion instead: each step masks
    its sequences' tokens with a probability drawn a sequence (from the seed
    and the step's index), runs the stack over ``[x ; x~]`` and scores the
    masked positions of ``x~`` on their own tokens with weight ``1 / p``
    (``blockLength``, ``maskTokenId``); the sequence length is
    then a multiple of 128 and of ``blockLength``.

    ``blockKind`` ``solar_open2`` runs the gated delta rule in the layers
    ``gqaLayers`` does not name (``kdaNumHeads`` heads of ``kdaHeadSize``
    channels, chunks of ``ssmChunkSize`` positions) and gated attention
    without a position encoding in those it names; the sequence length is a
    multiple of the chunk too. The head counts may be one chip's share of each
    layer's heads: the layer's output is then the held heads' part of it.

    ``blockKind`` ``olmo_hybrid`` runs the gated delta rule with ONE decay a
    head in the layers ``gqaLayers`` does not name (``kdaNumHeads`` heads of
    ``kdaHeadSize`` key and ``kdaValueHeadSize`` value channels) and attention
    without a position encoding under a QK-norm in those it names, a dense
    SwiGLU of ``expertWidth`` in every layer, each sublayer's output normed
    before it joins the stream; the head counts may be a chip's share too."""

    def fit(self, *inputs) -> DecoderLMModel:
        (df,) = inputs
        with tracer.phase("train.fit", CAT_PRODUCTIVE, rows=df.num_rows) as fit_phase:
            return self._fit(df, fit_phase)

    def _fit(self, df, fit_phase) -> DecoderLMModel:
        with tracer.phase("train.tokens_put", CAT_INGEST, rows=df.num_rows) as phase:
            tok = _token_matrix(df, self.get_features_col())
            n, t = tok.shape
            # left out, the vocabulary is the ids seen, and one more for the mask where the objective has one
            vocab = self.get(self.VOCAB_SIZE) or int(tok.max()) + 1 + (self.lm_config().block_length > 0)
            if tok.max() >= vocab:
                raise ValueError(f"token id {tok.max()} >= vocabSize {vocab}")
            window = jax.device_put(tok)
            phase.set_metadata(tokens=n * t, bytes=int(tok.nbytes))
        fit_phase.set_metadata(tokens=n * t)
        cfg = self.lm_config(vocab)
        interpret = _fold_mode(t, cfg)
        batch = min(self.get_global_batch_size(), n)
        steps = self.get_max_iter()
        positions = 2 * t if cfg.block_length else t  # of a sequence through the stack: block diffusion doubles it
        noise_key = _noise_key(self.get_seed()) if cfg.block_length else None

        with tracer.phase("train.init", CAT_COMPILE, params=num_params(cfg)) as phase:
            params = init_params(cfg, self.get_seed())
            phase.set_metadata(bytes=4 * num_params(cfg))
        with tracer.phase("train.program", CAT_COMPILE) as phase:
            misses = _train_program.cache_info().misses
            optimizer, step = _train_program(
                cfg, self.get_compute_type(), float(self.get_learning_rate()), batch, interpret
            )
            # what the fold's two kernels (the forward, the one backward) walk in one step, each counted once
            # (a rematerialised forward not again), and what the mask lets them skip;
            # the windowed layers' share of both beside them
            applications = cfg.n_layers * cfg.loops
            specs = layers(cfg) + ((mtp_layer(cfg),) if cfg.mtp_depth else ())  # the module's layer folds too
            mixers = [spec.mixer for spec in specs]
            latents = [m for m in mixers if isinstance(m, LatentAttention)]
            # each fold's heads and its mask: a window's keys and block diffusion's block (neither: causal)
            folds = [(m.heads, getattr(m, "window", 0), getattr(m, "diffusion_block", 0)) for m in mixers
                     if isinstance(m, (Attention, LatentAttention, CCA))]
            scans = [m for m in mixers if isinstance(m, Mamba2)]
            deltas = [m for m in mixers if isinstance(m, (KDA, GatedDelta))]
            # a step's chunks of the delta rule (chunks x heads x sequences, every such layer), those of them its
            # kernel pair walks (its grid's cells x the heads of a cell), and those that took the one-decay form
            kda_chunks = sum(batch * m.heads * (t // m.chunk) for m in deltas)
            kda_chunks_kernel = sum(kda_kernel_chunks(batch, t, m.heads, m.chunk) for m in deltas)
            kda_chunks_scalar = sum(kda_kernel_chunks(batch, t, m.heads, m.chunk) for m in deltas
                                    if isinstance(m, GatedDelta))
            # a step's chunks of the scan (chunks x heads x sequences, every Mamba-2 layer), and those of them the
            # scan's kernel pair walks: its grid's cells x the heads of a cell
            scan_chunks = sum(batch * m.heads * (t // m.chunk) for m in scans)
            scan_chunks_kernel = sum(scan_kernel_chunks(batch, t, m.heads, m.groups, m.chunk) for m in scans)
            one_head = {(w, block): np.asarray(fold_chunk_counts(positions, positions, 0, True, w or None,
                                                                 BlockDiffusion(t, block) if block else None, True))
                        for _, w, block in folds}
            chunks = np.zeros((3, 2), np.int64)  # [full, windowed, block diffusion] x [visited, all]
            for h, w, block in folds:
                chunks[2 if block else int(w > 0)] += cfg.loops * h * batch * one_head[w, block]
            opt_state = optimizer.init(params)  # one dispatch: fresh buffers, which the step donates
            state = jax.tree_util.tree_leaves(opt_state)
            # positions x channels of the Mamba-2 layers' convolutions in one step's forward, and those of them the
            # convolution's kernels cover: their calls' grids in the step as traced
            conv_positions = (sum(batch * t * (m.heads * m.head_dim + 2 * m.groups * m.state) for m in scans)
                              + sum(batch * t * m.heads * m.conv_channels for m in deltas))
            traced = _traced_counts(step, params, opt_state, window, cfg,
                                    *([(noise_key, jax.ShapeDtypeStruct((), jnp.int32))] if cfg.block_length else []))
            conv_positions_kernel = traced["conv_positions_kernel"]
            phase.set_metadata(built=int(_train_program.cache_info().misses > misses),
                               fold_chunks=int(chunks[:, 1].sum()), fold_chunks_visited=int(chunks[:, 0].sum()),
                               loop_trips=cfg.loops, layer_applications=applications,
                               state_leaves=len(state), state_bytes=sum(x.nbytes for x in state),
                               head_logit_matmuls=traced["head_logit_matmuls"])
            if folds:  # a stack that attends: how its step as traced calls the fold
                phase.set_metadata(fold_one_block=traced["fold_one_block"], fold_row_stats=traced["fold_row_stats"],
                                   fold_bwd_kernels=traced["fold_bwd_kernels"])
            if chunks[2, 1]:  # the doubled sequences' folds, and the positions a step takes through the stack
                phase.set_metadata(layers_diffusion=sum(block > 0 for _, _, block in folds),
                                   diffusion_block=cfg.block_length,
                                   fold_bd_chunks=int(chunks[2, 1]), fold_bd_chunks_visited=int(chunks[2, 0]),
                                   positions=batch * positions)
            if chunks[1, 1]:
                phase.set_metadata(layers_windowed=sum(w > 0 for _, w, _ in folds),
                                   layers_full=sum(w == 0 for _, w, _ in folds),
                                   fold_win_chunks=int(chunks[1, 1]), fold_win_chunks_visited=int(chunks[1, 0]))
            if latents:  # beside the count, the float32 latents and rotary keys a step's layers rebuild k and v from
                phase.set_metadata(layers_latent=len(latents), mtp_depth=cfg.mtp_depth,
                                   latent_bytes=sum(4 * batch * t * (m.kv_rank + m.rope_dim) for m in latents))
            if scans:  # beside the counts, the float32 chunk states one layer's recurrence carries
                phase.set_metadata(layers_scan=len(scans), layers_attn=sum(isinstance(m, Attention) for m in mixers),
                                   layers_moe=sum(isinstance(spec.ffn, Experts) for spec in specs),
                                   scan_chunks=scan_chunks, scan_chunks_kernel=scan_chunks_kernel,
                                   conv_positions=conv_positions, conv_positions_kernel=conv_positions_kernel,
                                   scan_state_bytes=max(4 * batch * m.heads * (t // m.chunk) * m.head_dim * m.state
                                                        for m in scans))

            if deltas:  # beside the counts, the float32 chunk states one layer's rule carries
                phase.set_metadata(layers_kda=len(deltas), layers_attn=sum(isinstance(m, Attention) for m in mixers),
                                   layers_moe=sum(isinstance(spec.ffn, Experts) for spec in specs),
                                   kda_chunks=kda_chunks, kda_chunks_kernel=kda_chunks_kernel,
                                   kda_chunks_scalar=kda_chunks_scalar,
                                   conv_positions=conv_positions, conv_positions_kernel=conv_positions_kernel,
                                   kda_state_bytes=max(4 * batch * m.heads * (t // m.chunk) * m.state_size
                                                       for m in deltas))

        losses, leaf_norms, stats = [], [], []  # device values, fetched once after the loop
        with tracer.phase("train.dispatch", CAT_PRODUCTIVE, steps=steps):
            offset = 0
            for i in range(steps):
                lo = min(offset, n - batch)
                noise = ((noise_key, jnp.int32(i)),) if cfg.block_length else ()
                params, opt_state, loss, norms, step_stats = step(params, opt_state, window, jnp.int32(lo), *noise)
                losses.append(loss)
                leaf_norms.append(norms)
                stats.append(step_stats)
                offset = 0 if offset + batch >= n else offset + batch
        with tracer.phase("train.drain", CAT_PRODUCTIVE, steps=steps) as phase:
            # what the blocks reported, by name, stacked over the steps on the device and fetched once
            stats = jax.device_get({k: jnp.stack([s[k] for s in stats]) for k in stats[0]})
            phase.set_metadata(tokens=steps * batch * t)
            loads = np.asarray(stats.get("rows", np.zeros((steps, cfg.n_layers, 0), np.int32)))
            if loads.size:  # [steps, layers with experts, experts]
                held = loads[:, :, cfg.first_held: cfg.first_held + cfg.held]
                rows_held = int(held.sum())
                rows_absent = int(loads.sum()) - rows_held
                phase.set_metadata(
                    expert_rows_max=int(loads.max()),
                    expert_rows_mean=batch * positions * cfg.top_k // cfg.n_experts,
                    dropped=int(steps * batch * positions * cfg.top_k * loads.shape[1] - loads.sum()),
                    rows_held=rows_held,
                    rows_absent=rows_absent,
                    held_rows_max=int(held.max()),
                    held_rows_mean=float(held.mean()),
                )
            carried = stats.get("carried")
            if carried is not None:  # [steps, layers with experts]: the rows each layer-step's windows took
                routed = batch * positions * cfg.top_k
                layer_steps_compact, rows_carried = int((carried < routed).sum()), int(carried.sum())
                phase.set_metadata(
                    moe_layer_steps=carried.size,
                    moe_layer_steps_compact=layer_steps_compact,
                    moe_rows_carried=rows_carried,
                    moe_rows_routed=routed * carried.size,
                )
            trips = np.asarray(stats.get("trip_nll", np.zeros((steps, 0))), np.float64)
            if trips.size:  # [steps, passes]
                phase.set_metadata(
                    exit_trip_sum=float(stats["exit_trip_sum"].sum()),
                    exit_last_mass=float(stats["exit_last_mass"].sum()),
                    gate_entropy_sum=float(stats["gate_entropy_sum"].sum()),
                    trip_nll=[float(x) for x in trips.mean(axis=0)],
                )
            ahead = np.asarray(stats.get("mtp_nll_sum", np.zeros(0)), np.float64)
            if ahead.size:  # [steps]: the module's summed cross-entropy over the positions it scored
                mtp_targets = int(stats["mtp_targets"].sum())
                phase.set_metadata(mtp_targets=mtp_targets, mtp_nll_sum=float(ahead.sum()))
            scored = stats.get("targets_masked")
            if scored is not None:  # [steps]: the positions block diffusion's objective scored
                targets_masked = int(scored.sum())
                phase.set_metadata(targets_masked=targets_masked, positions=steps * batch * positions,
                                   noise_level_sum=float(stats["noise_level_sum"].sum()))
        with tracer.phase("train.readback", CAT_READBACK, bytes=4 * steps * (1 + len(param_shapes(cfg)))):
            self.loss_history = [float(x) for x in jax.device_get(losses)]
            self.param_grad_norm_history = np.asarray(jax.device_get(jnp.stack(leaf_norms)), np.float64)
        self.param_names = _flat_names(cfg)
        self.grad_norm_history = [float(x) for x in np.sqrt((self.param_grad_norm_history ** 2).sum(axis=1))]
        self.expert_rows_history = loads
        self.trip_loss_history = trips
        self.mtp_loss_history = [float(x) for x in ahead / stats["mtp_targets"]] if ahead.size else []
        self.targets_masked_history = [] if scored is None else [int(x) for x in scored]
        metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LM_TOKENS, steps * batch * t)
        metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LM_FOLD_CHUNKS, steps * int(chunks[:, 1].sum()))
        metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LM_FOLD_CHUNKS_VISITED, steps * int(chunks[:, 0].sum()))
        if chunks[1, 1]:
            metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LM_FOLD_WIN_CHUNKS, steps * int(chunks[1, 1]))
            metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LM_FOLD_WIN_CHUNKS_VISITED,
                            steps * int(chunks[1, 0]))
        if chunks[2, 1]:
            metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LM_FOLD_BD_CHUNKS, steps * int(chunks[2, 1]))
            metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LM_FOLD_BD_CHUNKS_VISITED,
                            steps * int(chunks[2, 0]))
        if scored is not None:
            metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LM_DIFFUSION_TARGETS, targets_masked)
        if scans:
            metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LM_SCAN_CHUNKS, steps * scan_chunks)
            metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LM_SCAN_KERNEL_CHUNKS, steps * scan_chunks_kernel)
            metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LM_SCAN_LAYERS, steps * len(scans))
            metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LM_CONV_POSITIONS, steps * conv_positions)
            metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LM_CONV_KERNEL_POSITIONS,
                            steps * conv_positions_kernel)
        if deltas:
            metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LM_KDA_CHUNKS, steps * kda_chunks)
            metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LM_KDA_KERNEL_CHUNKS, steps * kda_chunks_kernel)
            metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LM_KDA_SCALAR_CHUNKS, steps * kda_chunks_scalar)
            metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LM_KDA_LAYERS, steps * len(deltas))
            metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LM_CONV_POSITIONS, steps * conv_positions)
            metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LM_CONV_KERNEL_POSITIONS,
                            steps * conv_positions_kernel)
        if latents:
            metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LM_MLA_LAYERS, steps * len(latents))
        if ahead.size:
            metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LM_MTP_TARGETS, mtp_targets)
        metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LM_LOOP_TRIPS, steps * cfg.loops)
        metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LM_LOOP_LAYER_APPLICATIONS, steps * applications)
        if loads.size:
            metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_MOE_ROWS, rows_held)
            metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_MOE_ROWS_ABSENT, rows_absent)
        if carried is not None:
            metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_MOE_LAYER_STEPS, carried.size)
            metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_MOE_LAYER_STEPS_COMPACT, layer_steps_compact)
            metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_MOE_ROWS_CARRIED, rows_carried)

        model = DecoderLMModel()
        update_existing_params(model, self)
        model.set(model.VOCAB_SIZE, vocab)
        model.params = params
        return model
