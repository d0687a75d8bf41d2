"""The LM step's kernels compiled for the real chip at OLMoE's published
shape, and the whole step program at ZAYA1-8B's, Ouro's, Laguna's and
Nemotron-3-Nano's, JoyAI-LLM-Flash's, SDAR-30B-A3B's, Solar-Open2-250B's and Olmo-Hybrid-7B's cuts, without the chip: libtpu's compiler runs here against a described
v5e (docs and recipe: the ``on-chip-measurement`` guide, section 2). It
catches what interpret mode cannot - Mosaic's lowering rules and the
scoped-VMEM limit - at no chip time. Nothing runs; no time is measured.

All such tests live in this one file, and the topology is described inside a
fixture: only the worker that is given this file loads the TPU's library.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B, H, T, D = 4, 16, 4096, 128  # four packed sequences of OLMoE-1B-7B
ROWS, HIDDEN, WIDTH, EXPERTS = 4 * 4096 * 8, 2048, 1024, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu from describing it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described device can be written to the persistent cache
    but not read back; keep these out of it."""
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _fold_grads(b, h, t, window=None):
    from flink_ml_tpu.parallel.flash import fused_fold

    def grads(q, k, v):
        def loss(q, k, v):
            m0 = jnp.full((b, h, t), -jnp.inf, jnp.float32)
            l0 = jnp.zeros((b, h, t), jnp.float32)
            acc0 = jnp.zeros((b, h, t, D), jnp.float32)
            zero = jnp.int32(0)
            _, l, acc = fused_fold(q, k, v, m0, l0, acc0, zero, zero, True, False, zero, D ** -0.5, False, window)
            return jnp.sum(acc / l[..., None])

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    return grads


#: ``(batch, query heads, key/value heads, T, window)``: four packed sequences of
#: OLMoE-1B-7B; two of ZAYA1-8B, 8 query heads on 2 key/value heads at 8,192; two
#: of Laguna-XS.2's windowed layers, 64 query heads on 8 at 4,096 through 512 keys.
FOLDS = {"olmoe_4x16x4096": (B, H, H, T, None), "zaya_2x8on2x8192": (2, 8, 2, 8192, None),
         "laguna_2x64on8x4096_w512": (2, 64, 8, 4096, 512)}
#: latent attention's heads: 192 query and key channels, 128 value channels
D_K = 192


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16", "float32"])
@pytest.mark.parametrize("fold", sorted(FOLDS))
def test_fused_fold_trains_at_the_cells_shapes(one_chip, dtype, fold):
    """Forward and both backward kernels in one training graph: the shape
    ``flash_train_available``'s 9 MB envelope refused and the kernels' stated
    VMEM limit admits, and the grouped-query fold at ``H_kv`` 2, T 8,192, whose
    K and V enter at their own ``[B, H_kv, T, D]`` (no repeated copy)."""
    b, h, h_kv, t, window = FOLDS[fold]
    q = jax.ShapeDtypeStruct((b, h, t, D), dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, h_kv, t, D), dtype, sharding=one_chip)
    compiled = _compile(_fold_grads(b, h, t, window), q, kv, kv)
    text = compiled.as_text()
    named = "flash_fold_win_" if window else "flash_fold_"  # a windowed fold's kernels have names of their own
    for kernel in ("fwd", "bwd_dq", "bwd_dkv"):
        assert named + kernel in text
    assert window is None or "flash_fold_fwd" not in text
    assert f"f32[{b},{h},{t},{t}]" not in text and f"f32[{b * h},{t},{t}]" not in text  # no score tensor
    # dk and dv come out per key/value head: the group's query heads are summed in the kernel
    _, dk, dv = jax.eval_shape(_fold_grads(b, h, t), q, kv, kv)
    assert dk.shape == dv.shape == (b, h_kv, t, D)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16", "float32"])
@pytest.mark.parametrize("batch", [1, 2])
def test_fused_fold_trains_at_a_value_head_of_its_own_size(one_chip, dtype, batch):
    """Forward and both backward kernels in one training graph at latent
    attention's shape: 32 heads x 8,192 positions, queries and keys 192
    channels wide, values 128 (``flash_available`` admits it with the value
    size stated and refuses 192 / 192): Mosaic takes the 1.5-tile contraction,
    K and V are staged at their own widths, ``dk`` comes out 192 wide and
    ``dv`` 128."""
    from flink_ml_tpu.parallel.flash import flash_available, fused_fold

    h, t = 32, 8192
    devices = list(one_chip.device_set)
    assert flash_available(t, D_K, devices, Dv=D) and not flash_available(t, D_K, devices)

    def grads(q, k, v):
        def loss(q, k, v):
            m0 = jnp.full((batch, h, t), -jnp.inf, jnp.float32)
            l0 = jnp.zeros((batch, h, t), jnp.float32)
            acc0 = jnp.zeros((batch, h, t, D), jnp.float32)
            zero = jnp.int32(0)
            _, l, acc = fused_fold(q, k, v, m0, l0, acc0, zero, zero, True, False, zero, D_K ** -0.5, False)
            return jnp.sum(acc / l[..., None])

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    qk = jax.ShapeDtypeStruct((batch, h, t, D_K), dtype, sharding=one_chip)
    v = jax.ShapeDtypeStruct((batch, h, t, D), dtype, sharding=one_chip)
    text = _compile(grads, qk, qk, v).as_text()
    for kernel in ("flash_fold_fwd", "flash_fold_bwd_dq", "flash_fold_bwd_dkv"):
        assert kernel in text
    assert f"f32[{batch * h},{t},{t}]" not in text
    dq, dk, dv = jax.eval_shape(grads, qk, qk, v)
    assert dq.shape == dk.shape == (batch, h, t, D_K) and dv.shape == (batch, h, t, D)


#: ``(batch, query heads, key/value heads, T, D, D_v, window)`` of the six LM cells' folds (Laguna's two kinds of layer)
ONE_BLOCK = {"olmoe_4x16x4096": (B, H, H, T, D, D, None), "ouro_2x16x4096": (2, H, H, T, D, D, None),
             "zaya_2x8on2x8192": (2, 8, 2, 8192, D, D, None), "laguna_2x64on8x4096_w512": (2, 64, 8, 4096, D, D, 512),
             "laguna_2x48on8x4096": (2, 48, 8, 4096, D, D, None), "nemotron_2x32on2x8192": (2, 32, 2, 8192, D, D, None),
             "joyai_1x32x8192_192_128": (1, 32, 32, 8192, D_K, D, None)}


def _one_block_shapes(fold, dtype, one_chip):
    b, h, h_kv, t, d, d_v, window = ONE_BLOCK[fold]
    return (jax.ShapeDtypeStruct((b, h, t, d), dtype, sharding=one_chip),
            jax.ShapeDtypeStruct((b, h_kv, t, d), dtype, sharding=one_chip),
            jax.ShapeDtypeStruct((b, h_kv, t, d_v), dtype, sharding=one_chip)), d ** -0.5, window


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16", "float32"])
@pytest.mark.parametrize("fold", sorted(ONE_BLOCK))
def test_fused_attention_trains_at_the_cells_shapes(one_chip, dtype, fold):
    """The one-block form's forward and its ONE backward kernel in one training
    graph at every LM cell's fold: Mosaic takes the row statistic along the
    lanes (``[B x H, 1, T]``: the column-to-row move inside the cells), the
    backward's one walk on transposed scores with its one transposed left
    operand (``dq += (ds^T)^T k``), the key/value head's whole ``dk`` and ``dv`` waiting in
    VMEM in float32 (10.5 MB at 8,192 x (192 + 128)) under the kernels' stated
    limit; the backward keeps the dkv kernel's name and there is no dq kernel;
    ``dq``, ``dk``, ``dv`` leave in the operands' type; no statistic is a ``[B
    x H, T, 1]`` column (128 times its bytes), and ``lse`` is the only one."""
    from flink_ml_tpu.parallel.flash import fused_attention

    shapes, scale, window = _one_block_shapes(fold, dtype, one_chip)
    b, h, h_kv, t, d, d_v, _ = ONE_BLOCK[fold]

    def grads(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(fused_attention(q, k, v, scale, window, False)),
                        argnums=(0, 1, 2))(q, k, v)

    text = _compile(grads, *shapes).as_text()
    named = "flash_fold_win_" if window else "flash_fold_"
    for kernel in ("fwd", "bwd_dkv"):
        assert named + kernel in text
    assert "bwd_dq" not in text and (window is None or "flash_fold_fwd" not in text)
    assert f"f32[{b * h},{t},{t}]" not in text and f"f32[{b * h},{t},1]" not in text
    assert len(set(re.findall(rf"%\S+ = f32\[{b * h},1,{t}\]", text))) == 1  # lse, and no delta beside it
    dq, dk, dv = jax.eval_shape(grads, *shapes)
    assert (dq.shape, dk.shape, dv.shape) == ((b, h, t, d), (b, h_kv, t, d), (b, h_kv, t, d_v))
    assert dq.dtype == dk.dtype == dv.dtype == dtype


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16", "float32"])
@pytest.mark.parametrize("block", [4, 32])
def test_fused_attention_trains_under_the_block_diffusion_mask(one_chip, dtype, block):
    """The third mask form at the ``sdar_30b_a3b`` cell's fold: 2 x 32 query
    heads on 4 key/value heads x 8,192 positions (4,096 tokens and their
    noised copies) x 128. Mosaic takes the mask's block ids worked out on one
    column of rows and one row of keys, the walk's two ranges (the clean
    chunks, the band) in the forward and in the one backward kernel, a group of
    eight query heads' cells adding into one key/value head's ``dk`` and
    ``dv``; the kernels carry names of their own and ``lse`` lies along the lanes."""
    from flink_ml_tpu.parallel.flash import BlockDiffusion, fused_attention

    b, h, h_kv, t = 2, 32, 4, 8192
    q = jax.ShapeDtypeStruct((b, h, t, D), dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, h_kv, t, D), dtype, sharding=one_chip)

    def grads(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(fused_attention(q, k, v, D ** -0.5, None, False,
                                                                BlockDiffusion(t // 2, block))),
                        argnums=(0, 1, 2))(q, k, v)

    text = _compile(grads, q, kv, kv).as_text()
    for kernel in ("fwd", "bwd_dkv"):
        assert "flash_fold_bd_" + kernel in text
    assert "flash_fold_fwd" not in text and "flash_fold_win_" not in text and "bwd_dq" not in text
    assert f"f32[{b * h},{t},{t}]" not in text and f"f32[{b * h},{t},1]" not in text
    assert f"f32[{b * h},1,{t}]" in text  # lse
    dq, dk, dv = jax.eval_shape(grads, q, kv, kv)
    assert (dq.shape, dk.shape, dv.shape) == ((b, h, t, D), (b, h_kv, t, D), (b, h_kv, t, D))
    assert dq.dtype == dk.dtype == dv.dtype == dtype


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16", "float32"])
def test_the_forward_alone_compiles_at_8192(one_chip, dtype):
    """What ``transform`` and ``log_likelihood`` run: the forward kernel with
    nothing behind it, 16 heads at T 8,192. The ring entry's forward alone did
    not compile there (XLA put its ``[16, 8192, 1]`` statistic ``l`` in VMEM
    beside the kernel's scoped 96 MiB: PERF.md section 7, PR 30); the
    one-block form's one lane-dense ``lse`` is 0.5 MB and it does."""
    from flink_ml_tpu.parallel.flash import fused_attention

    x = jax.ShapeDtypeStruct((1, 16, 8192, D), dtype, sharding=one_chip)
    text = _compile(lambda q, k, v: fused_attention(q, k, v, D ** -0.5, None, False), x, x, x).as_text()
    assert "flash_fold_fwd" in text and "flash_fold_bwd" not in text


def test_the_ring_fold_trains_at_the_largest_admitted_shape(one_chip):
    """What ``ring_attention`` hands the fold, at the most ``flash_available``
    admits (T 8,192 x D 128, float32): ``causal`` with TRACED positions, so
    the walk's bounds are read on the device, and ``n_valid`` cutting the
    block. The dq kernel's two score scratches are at their largest here."""
    from flink_ml_tpu.parallel.flash import flash_available, fused_fold

    b, h, t = 1, 4, 8192
    assert flash_available(t, D, list(one_chip.device_set)) and not flash_available(2 * t, D // 2, list(one_chip.device_set))

    def grads(q, k, v, q_pos0, k_pos0, n_valid):
        def loss(q, k, v):
            m0 = jnp.full((b, h, t), -jnp.inf, jnp.float32)
            l0 = jnp.zeros((b, h, t), jnp.float32)
            acc0 = jnp.zeros((b, h, t, D), jnp.float32)
            _, l, acc = fused_fold(q, k, v, m0, l0, acc0, q_pos0, k_pos0, True, True, n_valid, D ** -0.5)
            return jnp.sum(acc / l[..., None])

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    x = jax.ShapeDtypeStruct((b, h, t, D), jnp.float32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    text = _compile(grads, x, x, x, pos, pos, pos).as_text()
    for kernel in ("flash_fold_fwd", "flash_fold_bwd_dq", "flash_fold_bwd_dkv"):
        assert kernel in text
    assert f"f32[{b * h},{t},{t}]" not in text


def test_expert_matmuls_are_the_grouped_kernel_in_both_directions(one_chip):
    """131,072 rows over 64 experts: every grouped matmul of the forward and of
    the hand-written VJP lowers to XLA's ragged-dot kernel, none to the masked
    convolution a transposed contraction falls to."""
    from flink_ml_tpu.parallel.moe import _expert_swiglu

    def grads(xs, wg, wu, wd, sizes):
        def loss(xs, wg, wu, wd):
            return jnp.sum(_expert_swiglu(xs, wg, wu, wd, sizes, "bfloat16").astype(jnp.float32))

        return jax.grad(loss, argnums=(0, 1, 2, 3))(xs, wg, wu, wd)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = _compile(
        grads, sds((ROWS, HIDDEN), jnp.bfloat16), sds((EXPERTS, HIDDEN, WIDTH), jnp.float32),
        sds((EXPERTS, HIDDEN, WIDTH), jnp.float32), sds((EXPERTS, WIDTH, HIDDEN), jnp.float32),
        sds((EXPERTS,), jnp.int32),
    ).as_text()
    kernels = [ln for ln in text.splitlines() if ln.lstrip().startswith("%ragged-dot-none") and "custom-call(" in ln]
    # 3 dX + 3 dW + the two hidden projections (XLA shares them between the
    # forward and the VJP's recomputation here; the unused down projection is dead)
    assert len(kernels) >= 8
    assert "convolution_select_fusion" not in text
    assert f"[{ROWS},{EXPERTS}," not in text  # no [rows, experts, ...] tensor


def _cell_config(name):
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perfbench", "configs", f"{name}.json"), encoding="utf-8") as f:
        return json.load(f)


def _zaya_cut():
    """``(the benchmark's zaya1_8b configuration, its LMConfig)``."""
    from flink_ml_tpu.models.lm.config import LMConfig

    c = _cell_config("zaya1_8b")
    return c, LMConfig(
        c["num_hidden_layers"], c["hidden_size"], c["num_attention_heads"], c["num_experts_published"],
        c["num_experts_per_tok"], c["moe_intermediate_size"], c["vocab_size"],
        rope_theta=float(c["rope_parameters"]["hybrid"]["rope_theta"]), aux_coef=0.0, block="zaya",
        tied=c["tie_word_embeddings"], experts_held=c["num_experts"], first_held=c["first_expert_held"],
        n_kv_heads=c["num_key_value_heads"], head_size=c["head_dim"],
        rope_fraction=c["partial_rotary_factor"], router_width=c["router_hidden_size"])


def _ouro_cut():
    """``(the benchmark's ouro_2_6b configuration, its LMConfig)``."""
    from flink_ml_tpu.models.lm.config import LMConfig

    c = _cell_config("ouro_2_6b")
    return c, LMConfig(
        c["num_hidden_layers"], c["hidden_size"], c["num_attention_heads"], 0, 0, c["intermediate_size"],
        c["vocab_size"], rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]), aux_coef=0.0,
        block="ouro", loops=c["total_ut_steps"], exit_beta=c["exit_entropy_coef"])


def _step_and_shapes(c, cfg, one_chip):
    """``(the configuration's jitted step, the shapes of its arguments on the chip)``."""
    from flink_ml_tpu.models.lm import decoder_lm

    optimizer, step = decoder_lm._train_program(cfg, c["compute_dtype"], c["learning_rate"],
                                                c["global_batch_size"], False)
    params = jax.eval_shape(lambda: decoder_lm._init_program(cfg)(jax.random.key(0)))
    state = jax.eval_shape(optimizer.init, params)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)

    index = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    key = jax.eval_shape(lambda: jax.random.key(0))
    # a stage that trains by block diffusion hands its step the noise key and the step's index
    noise = ((jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip), index),) if cfg.block_length else ()
    return step, (on_chip(params), on_chip(state),
                  jax.ShapeDtypeStruct((c["num_sequences"], c["sequence_length"]), jnp.int32, sharding=one_chip),
                  index, *noise)


def _compiled_step(c, cfg, one_chip, held_to=15.75e9):
    """The configuration's whole jitted step compiled for the chip as ``fit``
    compiles it (``_train_program`` states the HBM it may take,
    ``decoder_lm.STEP_HBM_MIB``, where it jits the step), and XLA's analysis of
    it: the arguments hold the f32 weights and AdamW's moments, and arguments
    and temporaries together fit the 15.75e9 B a step is held to."""
    from flink_ml_tpu.models.lm.config import num_params

    step, shapes = _step_and_shapes(c, cfg, one_chip)
    compiled = step.lower(*shapes).compile()
    memory = compiled.memory_analysis()
    live = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert 12 * num_params(cfg) <= memory.argument_size_in_bytes and live < held_to, live
    return compiled, memory


def test_the_zaya_step_program_at_the_cells_shapes(one_chip):
    """The whole jitted step (forward, backward, clip, AdamW; six rematerialised
    blocks) of the ``zaya1_8b`` configuration at 2 x 8,192 tokens: it fits the
    chip (XLA's analysis, which counts what ``peak_bytes_in_use`` does not), the
    held range's grouped matmuls are the grouped kernel in both directions and
    the fold's two kernels (the forward, the one backward) are there at ``H_kv`` 2, T 8,192."""
    from flink_ml_tpu.models.lm.config import num_params

    c, cfg = _zaya_cut()
    assert 16 * num_params(cfg) > 11e9  # the fullest device holds at least 11 GB of f32 state
    batch, t = c["global_batch_size"], c["sequence_length"]
    compiled, memory = _compiled_step(c, cfg, one_chip)
    text = compiled.as_text()
    kernels = [ln for ln in text.splitlines() if ln.lstrip().startswith("%ragged-dot") and "custom-call(" in ln]
    assert len(kernels) >= 8 * cfg.n_layers
    assert "convolution_select_fusion" not in text
    for kernel in ("flash_fold_fwd", "flash_fold_bwd_dkv"):
        assert kernel in text
    assert "bwd_dq" not in text  # one backward kernel a fold
    kv = f"bf16[{batch * cfg.kv_heads},{t},{cfg.head_dim}]"
    assert kv in text  # K and V enter the kernels once per key/value head
    assert f"f32[{batch},{cfg.n_heads},{t},{t}]" not in text and f"f32[{batch * cfg.n_heads},{t},{t}]" not in text


def _laguna_cut():
    """``(the benchmark's laguna_xs2 configuration, its LMConfig)``."""
    from perfbench.systems import laguna_lm_fit

    c = _cell_config("laguna_xs2")
    return c, laguna_lm_fit.lm_config(c)


def test_the_laguna_step_program_at_the_cells_shapes(one_chip):
    """The whole jitted step of the ``laguna_xs2`` configuration at 2 x 4,096
    tokens: five rematerialised layers of three kinds (full attention on 48
    heads with the dense SwiGLU; windowed on 64 with the experts; full on 48
    with the experts). It fits the chip (XLA's analysis): 5.32 GB of
    temporaries beside 8.30 GB of arguments, 13.62e9 B in all (6.41 GB and
    14.71e9 B until PR 46, while the fold's kernels moved seventeen ``[128,
    4096, 1]`` float32 row statistics a layer, each padded 128 times to 256 MB,
    where the one-block form moves two along the lanes; 6.31 GB and 14.61e9 B
    until PR 45: the head's ``dW`` is one float32 ``[d, V]`` from
    the head's forward to AdamW where the parent's backward kept it in
    bfloat16 and XLA fused the cast into its readers); the windowed layers' two kernels are there under their own
    names beside the full layers'; K and V enter once per key/value head; the
    32 held experts' grouped matmuls are the grouped kernel in both
    directions, over a window of 16,384 sorted rows at a time: no float32
    array has the 65,536 routed rows' count."""
    from flink_ml_tpu.models.lm.config import num_params

    c, cfg = _laguna_cut()
    assert num_params(cfg) == 691_624_960  # 11.07 GB of f32 state at 16 bytes a parameter: 69% of 16 GB
    batch, t = c["global_batch_size"], c["sequence_length"]
    compiled, memory = _compiled_step(c, cfg, one_chip)
    # what it reads and 1%: 5.27e9 since PR 48 (one backward kernel a fold); 5.32e9 until then, 6.41e9 until PR 46
    assert memory.temp_size_in_bytes < 5.33e9, memory.temp_size_in_bytes
    text = compiled.as_text()
    for kernel in ("flash_fold_fwd", "flash_fold_bwd_dkv", "flash_fold_win_fwd", "flash_fold_win_bwd_dkv"):
        assert kernel in text
    assert "bwd_dq" not in text  # one backward kernel a fold, windowed or full
    kernels = [ln for ln in text.splitlines() if ln.lstrip().startswith("%ragged-dot") and "custom-call(" in ln]
    assert len(kernels) >= 8 * (cfg.n_layers - cfg.n_dense)
    assert "convolution_select_fusion" not in text
    routed = batch * t * cfg.top_k  # a window at a time; only what the dW matmuls read is parked at full length
    assert f"f32[{routed},{cfg.hidden}]" not in text and f"bf16[{routed // 4},{cfg.hidden}]" in text
    assert f"bf16[{batch * cfg.kv_heads},{t},{cfg.head_dim}]" in text  # K and V once per key/value head
    for heads in set(cfg.layer_heads):
        assert f"f32[{batch},{heads},{t},{t}]" not in text and f"f32[{batch * heads},{t},{t}]" not in text


def _nemotron_cut():
    """``(the benchmark's nemotron3_nano_30b configuration, its LMConfig)``."""
    from perfbench.systems import nemotron_lm_fit

    c = _cell_config("nemotron3_nano_30b")
    return c, nemotron_lm_fit.lm_config(c)


def test_the_nemotron_step_program_at_the_cells_shapes(one_chip, monkeypatch):
    """The whole jitted step of the ``nemotron3_nano_30b`` configuration at 2 x
    8,192 tokens: nine rematerialised layers of three kinds (four Mamba-2
    layers, one attention layer on 32 query heads over 2 key/value heads, four
    expert layers of 8 held relu² experts beside the shared one). It fits the
    HBM ``fit`` compiles a step into (``decoder_lm.STEP_HBM_MIB``: 15,020 MiB,
    the 15.75e9 B below): XLA's analysis reads 7.35 GB of temporaries beside
    8.00 GB of arguments, 15.35e9 B in all, and since PR 46 NOTHING in the
    compiled step is one of XLA's own rematerialisations (no ``.remat``
    instruction; the parent's held 14): without the fold's padded row
    statistics the step fits the stated size as scheduled, so the pass that
    recomputes until it fits has nothing to do, and what it reads is the
    schedule's own peak, above the 7.15 GB and 15.15e9 B that the parent was
    squeezed to (7.05 GB and 15.06e9 B until PR 45,
    whose head holds ``dW`` as a float32 ``[d, V]`` from its forward on where
    the parent's backward held it in bfloat16; 7.39 GB and 15.39e9 B while the
    Mamba-2 layers' convolution was a padded copy and four shifted slices, PR
    41; the step whose scan was ``jax.numpy`` too read 7.98 GB told the same
    and 7.37 GB, 15.38e9 B, told nothing). Left to its default XLA stops
    rematerialising once the step fits the chip and reads more - 7.55 GB
    here, 15.56e9 B of the chip's 16.91e9 (7.70 GB and 15.70e9 B until PR 45;
    8.51 GB, 16.52e9 B at PR 41) -
    which the second compile below holds where it was measured. The
    convolution is its two kernels by name, reading ``x``, ``B`` and ``C``
    where they lie in the in-projection's output: no padded copy of them is an
    array of the program. The scan is its two kernels by name: no ``[chunk,
    chunk]`` block of decays of 64 chunks a head is an array of the program,
    nor is a state a POSITION, and the state a chunk starts from is saved once
    a Mamba-2 layer, for the backward; the fold's two kernels take K and V
    once per key/value head; the held experts' grouped matmuls are the grouped
    kernel in both directions (two matrices an expert: a forward, a recomputed
    forward, two ``dX`` and two ``dW`` a layer at the least), over a window of
    12,288 sorted rows at a time."""
    from flink_ml_tpu.models.lm.config import num_params
    from flink_ml_tpu.parallel import causal_conv, ssd

    c, cfg = _nemotron_cut()
    assert num_params(cfg) == 666_963_456  # 10.67 GB of f32 state at 16 bytes a parameter: 67% of 16 GB
    batch, t = c["global_batch_size"], c["sequence_length"]
    for module in (ssd, causal_conv):  # the backend here is the CPU; the target is the chip
        monkeypatch.setattr(module, "_interpreted", lambda: False)
    compiled, memory = _compiled_step(c, cfg, one_chip)
    # what it reads and 1%: the schedule's own peak, nothing rematerialised by XLA to fit (PR 46; 7,349,152,768 B with
    # PR 48's one backward kernel, 32 KB under its parent); squeezed to fit it read 7.15e9 until then, 7.13e9 until
    # PR 45, 7.45e9 until PR 42
    assert memory.temp_size_in_bytes < 7.43e9, memory.temp_size_in_bytes
    assert ".remat" not in compiled.as_text()
    step, shapes = _step_and_shapes(c, cfg, one_chip)
    unbudgeted = jax.jit(step.__wrapped__, donate_argnums=(0, 1)).lower(*shapes).compile().memory_analysis()
    # of the chip's 15.75 GiB (16.91e9 B): what it reads and 1%; 15.86e9 until PR 45, 16.69e9 until PR 42
    assert unbudgeted.argument_size_in_bytes + unbudgeted.temp_size_in_bytes < 15.72e9
    text = compiled.as_text()
    for kernel in ("flash_fold_fwd", "flash_fold_bwd_dkv", "ssd_scan_fwd", "ssd_scan_bwd",
                   "causal_conv_fwd", "causal_conv_bwd"):
        assert kernel in text
    assert "flash_fold_bwd_dq" not in text  # one backward kernel a fold
    assert "flash_fold_win_" not in text
    convolved = cfg.ssm_heads * cfg.ssm_head_dim + 2 * cfg.ssm_groups * cfg.ssm_state
    assert f"f32[{batch},{t + cfg.conv_kernel - 1},{convolved}]" not in text  # no padded copy of x, B and C
    assert f"bf16[{batch * cfg.kv_heads},{t},{cfg.head_dim}]" in text  # K and V once per key/value head
    assert f"f32[{batch},{cfg.n_heads},{t},{t}]" not in text and f"f32[{batch * cfg.n_heads},{t},{t}]" not in text
    kernels = [ln for ln in text.splitlines() if ln.lstrip().startswith("%ragged-dot") and "custom-call(" in ln]
    assert len(kernels) >= 6 * cfg.layer_kinds.count("E")
    assert "convolution_select_fusion" not in text
    routed = batch * t * cfg.top_k  # 98,304 routed rows a layer, a window of an eighth of them at a time
    assert f"f32[{routed},{cfg.hidden}]" not in text and f"bf16[{routed // 8},{cfg.hidden}]" in text
    chunks, r = t // cfg.chunk, cfg.ssm_heads // cfg.ssm_groups
    assert f"{cfg.chunk},{cfg.chunk}]" not in text.replace(f"[{cfg.chunk},{cfg.chunk}]", "")  # no stack of chunk blocks
    assert f"f32[{batch},{chunks},{cfg.ssm_groups},{cfg.ssm_state},{r * cfg.ssm_head_dim}]" in text  # the chunks' states
    assert f"[{batch},{t},{cfg.ssm_heads},{cfg.ssm_head_dim},{cfg.ssm_state}]" not in text  # no state a position


def _joyai_cut():
    """``(the benchmark's joyai_llm_flash configuration, its LMConfig)``."""
    from perfbench.systems import joyai_lm_fit

    c = _cell_config("joyai_llm_flash")
    return c, joyai_lm_fit.lm_config(c)


def test_the_joyai_step_program_at_the_cells_shapes(one_chip):
    """The whole jitted step of the ``joyai_llm_flash`` configuration at 1 x
    8,192 tokens: five rematerialised layers of two kinds (latent attention
    with the dense SwiGLU; latent attention with 16 held experts beside the
    shared one) and the multi-token-prediction module's one more behind them,
    two passes over the head. It fits the HBM ``fit`` compiles a step into:
    XLA's analysis reads 6.18 GB of temporaries beside 8.17 GB of arguments,
    14.34e9 B in all (7.39 GB and 15.55e9 B until PR 46, while the fold's
    kernels moved seventeen ``[32, 8192, 1]`` float32 row statistics a layer,
    each padded 128 times to 128 MB, where the one-block form moves two along
    the lanes; 7.26 GB and 15.42e9 B until PR 45, whose head holds its
    ``dW`` as one float32 ``[d, V]`` from the first head call's forward on
    where the parent's backward held it in bfloat16; at two sequences a step, ISSUE 44's first choice, the
    compile still fails, by less: "Used 15.47G of 14.67G hbm", 17.40G until PR 46). The fold's two kernels are
    Mosaic's at a head of 192 query and key channels and 128 value channels,
    T 8,192, under their one set of names: K enters at ``[32, 8192, 192]`` and
    V at ``[32, 8192, 128]`` once a head, and no score tensor is an array of
    the program; the held experts' grouped matmuls are the grouped kernel in
    both directions over a window of 8,192 sorted rows at a time."""
    from flink_ml_tpu.models.lm.config import num_params

    c, cfg = _joyai_cut()
    assert num_params(cfg) == 680_441_088  # 10.89 GB of f32 state at 16 bytes a parameter: 68% of 16 GB
    batch, t = c["global_batch_size"], c["sequence_length"]
    compiled, memory = _compiled_step(c, cfg, one_chip)
    # what it reads and 1%: 6.04e9 since PR 48 (the one backward kernel: no ``delta``, no second kernel's operands
    # alive beside the first's); 6.18e9 until then, 7.39e9 until PR 46 (the fold's padded row statistics)
    assert memory.temp_size_in_bytes < 6.10e9, memory.temp_size_in_bytes
    text = compiled.as_text()
    for kernel in ("flash_fold_fwd", "flash_fold_bwd_dkv"):
        assert kernel in text
    assert "bwd_dq" not in text  # one backward kernel a fold
    assert "flash_fold_win_" not in text
    heads, d_k, d_v = batch * cfg.n_heads, cfg.nope_dim + cfg.rope_dim, cfg.v_dim
    assert f"bf16[{heads},{t},{d_k}]" in text and f"bf16[{heads},{t},{d_v}]" in text
    assert f"f32[{batch},{cfg.n_heads},{t},{t}]" not in text and f"f32[{heads},{t},{t}]" not in text
    kernels = [ln for ln in text.splitlines() if ln.lstrip().startswith("%ragged-dot") and "custom-call(" in ln]
    sparse = cfg.n_layers - cfg.n_dense + cfg.mtp_depth
    assert len(kernels) >= 8 * sparse
    assert "convolution_select_fusion" not in text
    routed = batch * t * cfg.top_k  # 65,536 routed rows a layer, a window of an eighth of them at a time
    assert f"f32[{routed},{cfg.hidden}]" not in text and f"bf16[{routed // 8},{cfg.hidden}]" in text
    # the module is in the step, by name: its layer's latents and its pass over the head under ``lm.mtp``
    from perfbench.op_scopes import classify

    scopes = {classify(name, "lm.")[0] for name in set(re.findall(r'op_name="([^"]*lm\.mtp[^"]*)"', text))}
    assert {("lm.mtp", "lm.block", "latent"), ("lm.mtp", "lm.head"), ("lm.mtp", "proj")} <= scopes


def _sdar_cut():
    """``(the benchmark's sdar_30b_a3b configuration, its LMConfig)``."""
    from perfbench.systems import sdar_lm_fit

    c = _cell_config("sdar_30b_a3b")
    return c, sdar_lm_fit.lm_config(c)


def test_the_sdar_step_program_at_the_cells_shapes(one_chip):
    """The whole jitted step of the ``sdar_30b_a3b`` configuration at 2 x
    4,096 tokens, 2 x 8,192 positions through the stack: the corruption, six
    rematerialised layers of one record (grouped queries under the
    block-diffusion mask, 16 held experts), ONE pass over the head, over the
    noised half's 8,192 rows. ISSUE 47's first choice compiles at the size the
    program states: XLA's analysis reads 7.75 GB of arguments and 8.05 GB of
    temporaries, 15.80e9 B in all (untold the step would take 8.76 GB of
    temporaries, 16.51e9 B: XLA rematerialises 0.71 GB of it toward the
    15,020 MiB; told 14,800 MiB it comes back with the same 8.05 GB, its
    floor), 0.05e9 over the 15.75e9 the other cells' steps are held to, and
    the compiler does not refuse it: the chip run is what says that it fits
    (PERF.md, PR 47). The fold's two kernels are the block-diffusion form's
    alone, K and V enter once a key/value head at ``[8, 8192, 128]``, no score
    tensor is an array of the program, and the head's logits are ``[2048,
    18992]`` a chunk of the noised rows."""
    from flink_ml_tpu.models.lm.config import num_params

    c, cfg = _sdar_cut()
    assert num_params(cfg) == 645_623_296  # 10.33 GB of f32 state at 16 bytes a parameter: 65% of 16 GB
    batch, t = c["global_batch_size"], c["sequence_length"]
    compiled, memory = _compiled_step(c, cfg, one_chip, held_to=15.85e9)
    assert memory.temp_size_in_bytes < 8.10e9, memory.temp_size_in_bytes  # what it reads (8.02e9; 8.05e9 until PR 48) and 1%
    text = compiled.as_text()
    for kernel in ("flash_fold_bd_fwd", "flash_fold_bd_bwd_dkv"):
        assert kernel in text
    assert "flash_fold_fwd" not in text and "flash_fold_win_" not in text and "bwd_dq" not in text
    positions = 2 * t
    assert f"bf16[{batch * cfg.kv_heads},{positions},{cfg.head_dim}]" in text  # K and V once per key/value head
    assert f"f32[{batch},{cfg.n_heads},{positions},{positions}]" not in text
    assert f"f32[{batch * cfg.n_heads},{positions},{positions}]" not in text
    kernels = [ln for ln in text.splitlines() if ln.lstrip().startswith("%ragged-dot") and "custom-call(" in ln]
    assert len(kernels) >= 8 * cfg.n_layers
    assert "convolution_select_fusion" not in text
    routed = batch * positions * cfg.top_k  # 131,072 routed rows a layer, a window of a quarter of them at a time
    assert f"f32[{routed},{cfg.hidden}]" not in text and f"bf16[{routed // 4},{cfg.hidden}]" in text
    assert f"[2048,{cfg.vocab}]" in text and f"[{batch * positions},{cfg.vocab}]" not in text
    from perfbench.op_scopes import classify

    scopes = {classify(name, "lm.")[0] for name in set(re.findall(r'op_name="([^"]*lm\.noise[^"]*)"', text))}
    assert ("lm.noise",) in scopes


def _solar_cut():
    """``(the benchmark's solar_open2_250b configuration, its LMConfig)``."""
    from perfbench.systems import solar_lm_fit

    c = _cell_config("solar_open2_250b")
    return c, solar_lm_fit.lm_config(c)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16", "float32"])
@pytest.mark.parametrize("batch", [1, 2])
def test_the_delta_rule_trains_at_the_cells_shape(one_chip, monkeypatch, dtype, batch):
    """The delta rule's kernel pair in one training graph at the cell's shape:
    8 held heads of 128 key and 128 value channels, T 4,096 in chunks of 64.
    Mosaic takes the sub-chunks' 16-row slices, the ``[64, 64]`` blockwise
    inversion at the highest precision and the transposed carried state; the
    state every chunk starts from is saved once, ``[B, 64, 8, 128, 128]``
    float32, and no ``[chunk, chunk]`` block a chunk is an array of the
    program."""
    from flink_ml_tpu.parallel import kda

    monkeypatch.setattr(kda, "_interpreted", lambda: False)  # the backend here is the CPU; the target is the chip
    heads, t, d, chunk = 8, 4096, 128, 64
    wide = jax.ShapeDtypeStruct((batch, t, heads, d), jnp.float32, sharding=one_chip)
    narrow = jax.ShapeDtypeStruct((batch, t, heads), jnp.float32, sharding=one_chip)

    def grads(q, k, v, g, beta):
        return jax.grad(lambda *a: jnp.sum(kda.kda_scan(*a, chunk, dtype)), argnums=range(5))(q, k, v, g, beta)

    compiled = _compile(grads, wide, wide, wide, wide, narrow)
    text = compiled.as_text()
    assert kda.FWD_NAME in text and kda.BWD_NAME in text
    assert f"f32[{batch},{t // chunk},{heads},{d},{d}]" in text  # the chunks' starting states, once
    assert f"{chunk},{chunk}]" not in text.replace(f"[{chunk},{chunk}]", "")  # no stack of chunk blocks
    assert f"[{batch},{t},{heads},{d},{d}]" not in text  # no state a position
    dq, dk, dv, dg, dbeta = jax.eval_shape(grads, wide, wide, wide, wide, narrow)
    assert dq.shape == dk.shape == dv.shape == dg.shape == wide.shape and dbeta.shape == narrow.shape


def test_the_solar_step_program_at_the_cells_shapes(one_chip, monkeypatch):
    """The whole jitted step of the ``solar_open2_250b`` configuration at ONE
    4,096-token sequence: four rematerialised layers of two records (the
    attention layer on 8 held query heads over 1 held key/value head under its
    output gate, three delta-rule layers on 8 held heads, every layer with 8
    held experts beside the shared one), 840,872,600 parameters. It fits the
    HBM ``fit`` compiles a step into (``decoder_lm.STEP_HBM_MIB``: 15,020 MiB,
    the 15.75e9 B below): XLA's analysis reads 5.02 GB of temporaries beside
    10.09 GB of arguments, 15.11e9 B in all, and nothing in the compiled step is
    one of XLA's own rematerialisations. TWO sequences a step - ISSUE 51's
    first choice - compile to 6.21 GB of temporaries, 16.30e9 B, 0.55e9 over
    (with ``.remat`` instructions: XLA was squeezing already; the same with a
    1,024-row head chunk): the largest of them are the experts' five parked
    buffers over all 68,096 padded routed rows (``parallel/moe.py``: 1.64 GB),
    so the cell takes the fallback the issue names. The delta rule and the
    convolution are their kernel pairs by name, the convolution reading q, k and
    v where they lie in ONE projection's output; the fold's two kernels take K
    and V once for the one held key/value head; the held experts' grouped
    matmuls are the grouped kernel in both directions over a window of 2,048
    sorted rows at a time."""
    from flink_ml_tpu.models.lm.config import num_params
    from flink_ml_tpu.parallel import causal_conv, kda

    c, cfg = _solar_cut()
    assert num_params(cfg) == 840_872_600  # 13.45 GB of f32 state at 16 bytes a parameter: 84% of 16 GB
    batch, t = c["global_batch_size"], c["sequence_length"]
    assert batch == 1
    for module in (kda, causal_conv):  # the backend here is the CPU; the target is the chip
        monkeypatch.setattr(module, "_interpreted", lambda: False)
    compiled, memory = _compiled_step(c, cfg, one_chip)
    assert memory.temp_size_in_bytes < 5.07e9, memory.temp_size_in_bytes  # what it reads (5.018e9) and 1%
    text = compiled.as_text()
    assert ".remat" not in text
    for kernel in ("flash_fold_fwd", "flash_fold_bwd_dkv", "kda_scan_fwd", "kda_scan_bwd", "causal_conv_fwd",
                   "causal_conv_bwd"):
        assert kernel in text
    assert "flash_fold_bwd_dq" not in text and "flash_fold_win_" not in text and "ssd_scan" not in text
    inner = cfg.kda_heads * cfg.kda_head_dim
    assert f"f32[{batch},{t},{3 * inner}]" in text  # q, k and v out of one projection
    assert f"f32[{batch},{t + cfg.conv_kernel - 1},{3 * inner}]" not in text  # and no padded copy of them
    assert f"bf16[{batch * cfg.kv_heads},{t},{cfg.head_dim}]" in text  # K and V once for the held key/value head
    assert f"f32[{batch},{cfg.n_heads},{t},{t}]" not in text and f"f32[{batch * cfg.n_heads},{t},{t}]" not in text
    kernels = [ln for ln in text.splitlines() if ln.lstrip().startswith("%ragged-dot") and "custom-call(" in ln]
    assert len(kernels) >= 8 * cfg.n_layers
    routed = batch * t * cfg.top_k  # 32,768 routed rows a layer, a window of a sixteenth of them at a time
    assert f"f32[{routed},{cfg.hidden}]" not in text and f"bf16[{routed // 16},{cfg.hidden}]" in text
    chunks = t // cfg.chunk
    assert f"f32[{batch},{chunks},{cfg.kda_heads},{cfg.kda_head_dim},{cfg.kda_head_dim}]" in text  # the chunks' states
    assert f"[{batch},{t},{cfg.kda_heads},{cfg.kda_head_dim},{cfg.kda_head_dim}]" not in text  # no state a position
    assert f"[2048,{cfg.vocab}]" in text  # the head's logits a chunk of 2,048 rows ([4096, 24576] is the head itself)


def test_two_sequences_a_step_of_the_solar_cut_do_not_fit(one_chip, monkeypatch):
    """ISSUE 51's first choice, two sequences a step, compiled as ``fit`` would
    compile it: arguments and temporaries pass the 15.75e9 B a step is held to
    (16.30e9 read), which is why the cell runs one."""
    from flink_ml_tpu.parallel import causal_conv, kda

    c, cfg = _solar_cut()
    for module in (kda, causal_conv):
        monkeypatch.setattr(module, "_interpreted", lambda: False)
    step, shapes = _step_and_shapes({**c, "global_batch_size": 2}, cfg, one_chip)
    memory = step.lower(*shapes).compile().memory_analysis()
    assert 15.75e9 < memory.argument_size_in_bytes + memory.temp_size_in_bytes < 16.6e9


def _olmo_hybrid_cut():
    """``(the benchmark's olmo_hybrid_7b configuration, its LMConfig)``."""
    from perfbench.systems import olmo_hybrid_lm_fit

    c = _cell_config("olmo_hybrid_7b")
    return c, olmo_hybrid_lm_fit.lm_config(c)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16", "float32"])
def test_the_one_decay_delta_rule_trains_at_the_cells_shape(one_chip, monkeypatch, dtype):
    """The delta rule's kernel pair in its one-decay form in one training graph
    at the Olmo-Hybrid cut's shape: 15 held heads of 96 key and 192 value
    channels - neither tiles the 128 lanes, nor do the 1,440 flat key channels -
    T 8,192 in chunks of 64. Mosaic takes the head-major blocks whose last
    dimension is a head's whole 96 or 192 channels, the ``[1, 64]`` decay rows
    turned through a ``[64, 64]`` tile's diagonal, the blockwise inversion and
    the transposed ``[192, 96]`` carried state; the state every chunk starts
    from is saved once, ``[1, 128, 15, 192, 96]`` float32; and the decays reach
    the kernels as rows a head: nothing ``[.., 96]`` wide carries one."""
    from flink_ml_tpu.parallel import kda

    monkeypatch.setattr(kda, "_interpreted", lambda: False)  # the backend here is the CPU; the target is the chip
    heads, t, dk, dv, chunk = 15, 8192, 96, 192, 64
    keys = jax.ShapeDtypeStruct((1, t, heads, dk), jnp.float32, sharding=one_chip)
    values = jax.ShapeDtypeStruct((1, t, heads, dv), jnp.float32, sharding=one_chip)
    narrow = jax.ShapeDtypeStruct((1, t, heads), jnp.float32, sharding=one_chip)

    def grads(q, k, v, g, beta):
        return jax.grad(lambda *a: jnp.sum(kda.kda_scan(*a, chunk, dtype)), argnums=range(5))(q, k, v, g, beta)

    compiled = _compile(grads, keys, keys, values, narrow, narrow)
    text = compiled.as_text()
    assert kda.FWD_NAME in text and kda.BWD_NAME in text
    assert f"f32[1,{t // chunk},{heads},{dv},{dk}]" in text  # the chunks' starting states, once
    assert f"f32[1,{heads},{t // chunk},1,{chunk}]" in text  # the decays: a row a head and chunk
    assert f"[1,{t},{heads},{dk},{dv}]" not in text and f"[1,{t},{heads},{dv},{dk}]" not in text  # no state a position
    dq, dk_, dv_, dg, dbeta = jax.eval_shape(grads, keys, keys, values, narrow, narrow)
    assert dq.shape == dk_.shape == keys.shape and dv_.shape == values.shape and dg.shape == dbeta.shape == narrow.shape


def test_the_olmo_hybrid_step_program_at_the_cells_shapes(one_chip, monkeypatch):
    """The whole jitted step of the ``olmo_hybrid_7b`` configuration at ONE
    8,192-token sequence: four rematerialised layers of two records (three
    delta-rule layers on 15 held heads of 96 x 192, the attention layer on 15
    held heads of 128 under its QK-norm, every layer with the whole 11,008-wide
    SwiGLU and two output norms), 766,241,946 parameters. ISSUE 54's FIRST
    choice, 15 of 30 heads, fits the HBM ``fit`` compiles a step into
    (``decoder_lm.STEP_HBM_MIB``: the 15.75e9 B below): XLA's analysis reads
    4.78 GB of temporaries beside 9.20 GB of arguments, 13.98e9 B in all, 1.77e9
    under, and nothing in the compiled step is one of XLA's own
    rematerialisations; the 10-head fallback is not needed. The delta rule and
    the convolution are their kernel pairs by name, the convolution walking q,
    k and v as ONE part of 5,760 channels (1,440 tiles no lane); the fold's two
    kernels are there at T 8,192."""
    from flink_ml_tpu.models.lm.config import num_params
    from flink_ml_tpu.parallel import causal_conv, kda

    c, cfg = _olmo_hybrid_cut()
    assert num_params(cfg) == 766_241_946  # 12.26 GB of f32 state at 16 bytes a parameter: 77% of 16 GB
    batch, t = c["global_batch_size"], c["sequence_length"]
    assert (batch, t) == (1, 8192)
    for module in (kda, causal_conv):  # the backend here is the CPU; the target is the chip
        monkeypatch.setattr(module, "_interpreted", lambda: False)
    compiled, memory = _compiled_step(c, cfg, one_chip)
    assert memory.temp_size_in_bytes < 4.83e9, memory.temp_size_in_bytes  # what it reads (4.780e9) and 1%
    text = compiled.as_text()
    assert ".remat" not in text
    for kernel in ("flash_fold_fwd", "flash_fold_bwd_dkv", "kda_scan_fwd", "kda_scan_bwd", "causal_conv_fwd",
                   "causal_conv_bwd"):
        assert kernel in text
    assert "flash_fold_bwd_dq" not in text and "flash_fold_win_" not in text and "ssd_scan" not in text
    channels = cfg.kda_heads * (2 * cfg.kda_head_dim + cfg.kda_value_dim)
    assert channels == 5760 and f"f32[{batch},{t},{channels}]" in text  # q, k and v out of one projection, one part
    assert f"f32[{batch},{t + cfg.conv_kernel - 1},{channels}]" not in text  # and no padded copy of them
    assert f"f32[{batch},{cfg.n_heads},{t},{t}]" not in text and f"f32[{batch * cfg.n_heads},{t},{t}]" not in text
    chunks = t // cfg.chunk
    assert f"f32[{batch},{chunks},{cfg.kda_heads},{cfg.kda_value_dim},{cfg.kda_head_dim}]" in text  # the chunks' states
    assert f"[{batch},{t},{cfg.kda_heads},{cfg.kda_head_dim},{cfg.kda_value_dim}]" not in text  # no state a position
    assert f"[2048,{cfg.vocab}]" in text and f"[{t},{cfg.vocab}]" not in text  # the head's logits a chunk of 2,048 rows


def test_the_ouro_step_program_at_the_cells_shapes(one_chip):
    """The whole jitted step of the ``ouro_2_6b`` configuration at 2 x 4,096
    tokens: six rematerialised dense blocks inside one scanned pass run four
    times, four passes of the head. It fits the chip (XLA's analysis), the
    program holds ONE pass and not four (a pass's 24 kernel calls, the block
    inputs stacked over the four trips), and the fold's two kernels are there."""
    from flink_ml_tpu.models.lm.config import num_params

    c, cfg = _ouro_cut()
    assert num_params(cfg) == 509_661_185  # 8.15 GB of f32 state at 16 bytes a parameter: 47% of 16 GiB
    batch, t = c["global_batch_size"], c["sequence_length"]
    compiled, memory = _compiled_step(c, cfg, one_chip)
    text = compiled.as_text()
    for kernel in ("flash_fold_fwd", "flash_fold_bwd_dkv"):
        assert kernel in text
    assert "bwd_dq" not in text  # one backward kernel a fold
    calls = len(re.findall(r"custom_call_target=\"tpu_custom_call\"", text))
    # one pass's kernels (a layer's forward, recomputed forward and the one backward), not four passes'
    assert 3 * cfg.n_layers <= calls < 2 * 3 * cfg.n_layers
    stacked = f"f32[{cfg.loops},{batch},{t},{cfg.hidden}]"  # what the forward loop holds for the backward, per pass
    assert stacked in text
    assert f"f32[{batch},{cfg.n_heads},{t},{t}]" not in text and f"f32[{batch * cfg.n_heads},{t},{t}]" not in text


def _olmoe_cut():
    """``(the benchmark's olmoe_1b_7b configuration, its LMConfig)``, through the stage's own params."""
    from flink_ml_tpu.models.lm import DecoderLM

    c = _cell_config("olmoe_1b_7b")
    stage = (DecoderLM().set_num_layers(c["num_hidden_layers"]).set_hidden_size(c["hidden_size"])
             .set_num_heads(c["num_attention_heads"]).set_num_experts(c["num_experts"])
             .set_experts_per_token(c["num_experts_per_tok"]).set_expert_width(c["intermediate_size"])
             .set_vocab_size(c["vocab_size"]).set_rope_theta(float(c["rope_theta"]))
             .set_norm_eps(float(c["rms_norm_eps"])).set_aux_loss_coef(c["router_aux_loss_coef"]))
    return c, stage.lm_config()


def test_the_olmoe_step_program_at_the_cells_shapes(one_chip):
    """The whole jitted step of the ``olmoe_1b_7b`` configuration at 4 x 4,096
    tokens: one block, not checkpointed, so the fold is its forward and its ONE
    backward kernel, two calls of the fold's kernels in all; it fits the chip."""
    from flink_ml_tpu.models.lm.config import num_params

    c, cfg = _olmoe_cut()
    assert num_params(cfg) == 625_616_896  # 10.0 GB of f32 state at 16 bytes a parameter
    batch, t = c["global_batch_size"], c["sequence_length"]
    compiled, _ = _compiled_step(c, cfg, one_chip)
    text = compiled.as_text()
    for kernel in ("flash_fold_fwd", "flash_fold_bwd_dkv"):
        assert kernel in text
    assert "bwd_dq" not in text and len(re.findall(r"%flash_fold_[\w.]+ = ", text)) == 2
    assert f"f32[{batch},{cfg.n_heads},{t},{t}]" not in text and f"f32[{batch * cfg.n_heads},{t},{t}]" not in text


@pytest.mark.parametrize("cut", [_zaya_cut, _ouro_cut, _laguna_cut, _nemotron_cut, _joyai_cut, _sdar_cut, _solar_cut,
                                 _olmo_hybrid_cut],
                         ids=["zaya", "ouro", "laguna", "nemotron", "joyai", "sdar", "solar", "olmo_hybrid"])
def test_the_state_program_at_the_cells_shapes(one_chip, cut):
    """AdamW's state as ``DecoderLM._fit`` makes it, ``optimizer.init`` jitted,
    at the cells' parameter trees: one program whose outputs are the whole
    state (``mu`` and ``nu`` a float32 leaf a parameter each, and the count),
    which reads no argument and holds nothing beside its outputs."""
    from flink_ml_tpu.models.lm import decoder_lm
    from flink_ml_tpu.models.lm.config import num_params, param_shapes

    c, cfg = cut()
    optimizer, _ = decoder_lm._train_program(cfg, c["compute_dtype"], c["learning_rate"], c["global_batch_size"], False)
    params = jax.eval_shape(lambda: decoder_lm._init_program(cfg)(jax.random.key(0)))
    compiled = optimizer.init.lower(jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), params)).compile()
    assert compiled.out_tree.num_leaves == 2 * len(param_shapes(cfg)) + 1
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == pytest.approx(8 * num_params(cfg) + 4, rel=1e-3)  # padding of small leaves
    assert memory.argument_size_in_bytes == 0 and memory.temp_size_in_bytes == 0


@pytest.mark.parametrize("row_hi", [128, 16])
def test_the_premat_crossings_at_the_criteo_cells_shapes(one_chip, row_hi):
    """The two premat crossing kernels of one ``criteo_lr.fit_resident`` step:
    four sub-batches of 956,753 entries, three windows of one-hots held with
    the entries on the lanes, the window picked by a TRACED index inside the
    BlockSpec; and the same at ``row_hi`` 16, where a ``[row_hi, tile]`` block
    is one bf16 sublane tile. Mosaic's verdict on the cells, and both kernels
    by the names the benchmark's trace reducers look for."""
    from flink_ml_tpu.linalg.onehot_sparse import (
        _premat_pad,
        dot_crossing_premat_pallas,
        mult_crossing_premat_pallas,
        premat_bytes,
    )

    n_windows, n_sub, n_flat = 3, 4, 956_753
    n_pad = _premat_pad(n_flat, row_hi)
    assert 2 * n_windows * n_sub * n_pad * (row_hi + 128) == premat_bytes(n_windows * n_sub, n_flat, row_hi)

    def crossings(q, mult3, oh_hi, oh_lo, wi):
        return (
            dot_crossing_premat_pallas(q, oh_hi, oh_lo, wi),
            mult_crossing_premat_pallas(mult3, oh_hi, oh_lo, wi)[:, :n_flat],
        )

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _compile(
        crossings,
        on_chip((n_sub, n_flat), jnp.float32),
        on_chip((n_sub, row_hi, 128), jnp.float32),
        on_chip((n_windows, n_sub, row_hi, n_pad), jnp.bfloat16),
        on_chip((n_windows, n_sub, 128, n_pad), jnp.bfloat16),
        on_chip((), jnp.int32),
    )
    text = compiled.as_text()
    for kernel in ("onehot_dot_crossing_premat", "onehot_mult_crossing_premat"):
        assert kernel in text
    # the window is chosen in the index map: no window-sized copy of the one-hots beside the kernels
    assert f"bf16[{n_sub},128,{n_pad}]" not in text
