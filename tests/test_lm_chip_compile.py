"""The LM step's kernels compiled for the real chip at OLMoE's published
shape, without the chip: libtpu's compiler runs here against a described
v5e (docs and recipe: the ``on-chip-measurement`` guide, section 2). It
catches what interpret mode cannot - Mosaic's lowering rules and the
scoped-VMEM limit - at no chip time. Nothing runs; no time is measured.

All such tests live in this one file, and the topology is described inside a
fixture: only the worker that is given this file loads the TPU's library.
"""
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


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16", "float32"])
def test_fused_fold_trains_at_4x16x4096x128(one_chip, dtype):
    """Forward and both backward kernels in one training graph: the shape
    ``flash_train_available``'s 9 MB envelope refused and the kernels' stated
    VMEM limit admits."""
    from flink_ml_tpu.parallel.flash import fused_fold

    def grads(q, k, v):
        def loss(q, k, v):
            m0 = jnp.full((B, H, T), -jnp.inf, jnp.float32)
            l0 = jnp.zeros((B, H, T), jnp.float32)
            acc0 = jnp.zeros((B, H, T, D), jnp.float32)
            zero = jnp.int32(0)
            _, l, acc = fused_fold(q, k, v, m0, l0, acc0, zero, zero, True, False, zero, D ** -0.5)
            return jnp.sum(acc / l[..., None])

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    shape = jax.ShapeDtypeStruct((B, H, T, D), dtype, sharding=one_chip)
    text = _compile(grads, shape, shape, shape).as_text()
    for kernel in ("flash_fold_fwd", "flash_fold_bwd_dq", "flash_fold_bwd_dkv"):
        assert kernel in text
    assert f"f32[{B},{H},{T},{T}]" not in text and f"f32[{B * H},{T},{T}]" not in text  # no score tensor


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
