"""``flash.fused_attention``, the one-block form of the fold (the LM's: a ring
of one), interpreted on the CPU at toy sizes and shrunken tiles: against
``jax.grad`` of plain float32 softmax attention (scores, mask, ``softmax``,
``@ v``), against the parent's ``_fold`` (the ring entry fed zeros, ``acc / l``
outside), the ONE backward kernel over every mask form, group, head size and
walk, and the counts ``train.program`` reads off the step as traced.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests import test_lm_scopes
from tests.test_decoder_lm_head import _noise, _traced_step
from flink_ml_tpu.models.lm import decoder_lm
from flink_ml_tpu.parallel import flash

CHUNK = 256  # the shrunken tiles: the forward's and the backward's rows and key chunks (and the ring form's dkv pair)
TILES = ("_TQ_CAUSAL", "_KEY_CHUNK", "_BWD_KEY_CHUNK", "_DKV_CAUSAL")


@pytest.fixture
def small_tiles(monkeypatch):
    for name in TILES:
        monkeypatch.setattr(flash, name, CHUNK)


def _plain(q, k, v, scale, window=None):
    """Causal softmax attention as ``jax.numpy`` writes it, K and V repeated over their groups."""
    group, t = q.shape[1] // k.shape[1], q.shape[2]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    keep = (i >= j) if window is None else (i >= j) & (j > i - window)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1), v)


def _parents_fold(q, k, v, scale, window=None):
    """``decoder_lm._fold``'s body before the one-block form: the ring's entry on zeros, ``acc / l`` outside."""
    b, h, t, _ = q.shape
    m0, l0 = jnp.full((b, h, t), -jnp.inf, jnp.float32), jnp.zeros((b, h, t), jnp.float32)
    acc0 = jnp.zeros((b, h, t, v.shape[-1]), jnp.float32)
    zero = jnp.int32(0)
    _, l, acc = flash.fused_fold(q, k, v, m0, l0, acc0, zero, zero, True, False, zero, scale, True, window)
    return acc / l[..., None]


def _inputs(b, h, h_kv, t, d, d_v, seed=0):
    rng = np.random.default_rng(seed)
    r = lambda *shape: jnp.asarray(rng.standard_normal(shape).astype(np.float32))  # noqa: E731
    return (r(b, h, t, d), r(b, h_kv, t, d), r(b, h_kv, t, d_v)), r(b, h, t, d_v)  # q, k, v; a cotangent that is not 1


def _out_and_grads(fn, qkv, cot):
    out, vjp = jax.vjp(fn, *qkv)
    return (out, *vjp(cot))


def _worst(got, want):
    return max(float(jnp.max(jnp.abs(g.astype(jnp.float32) - w)) / jnp.max(jnp.abs(w))) for g, w in zip(got, want))


#: name -> (batch, query heads, key/value heads, T, D, D_v, window): T is three or four key chunks of 256
CASES = {
    "causal-4-chunks": (2, 2, 2, 1024, 16, 16, None),
    "window-under-a-chunk": (1, 2, 2, 1024, 16, 16, 100),
    "window-a-chunk": (1, 2, 2, 1024, 16, 16, CHUNK),
    "window-over-a-chunk": (1, 2, 2, 1024, 16, 16, 700),
    "window-of-two": (1, 2, 2, 768, 16, 16, 2),  # a tile's first row keeps ONE key of the chunk before: exp(-inf - lse) is 0
    "group-1": (1, 4, 4, 768, 16, 16, None),
    "group-4": (1, 4, 1, 768, 16, 16, None),
    "group-16": (1, 16, 1, 768, 8, 8, None),
    "group-4-windowed": (1, 8, 2, 768, 16, 16, 300),
    "value-head-192-128": (1, 2, 2, 768, 192, 128, None),  # latent attention's head
    "value-head-wider": (1, 2, 1, 768, 8, 40, None),
    "one-chunk": (2, 2, 1, 256, 16, 16, None),  # the block in one piece: nothing parked, nothing walked
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_float32_is_plain_softmax_attention_and_its_gradients(case, small_tiles):
    b, h, h_kv, t, d, d_v, window = CASES[case]
    qkv, cot = _inputs(b, h, h_kv, t, d, d_v, seed=sorted(CASES).index(case))
    scale = d ** -0.5
    with jax.default_matmul_precision("highest"):
        want = _out_and_grads(lambda q, k, v: _plain(q, k, v, scale, window), qkv, cot)
    got = _out_and_grads(lambda q, k, v: flash.fused_attention(q, k, v, scale, window, True), qkv, cot)
    assert got[0].dtype == jnp.float32
    for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name  # dk at q's channels, dv at v's, per key/value head
        assert _worst([g], [w]) < 1e-5, name


@pytest.mark.parametrize("case", ["causal-4-chunks", "window-under-a-chunk", "group-4", "value-head-192-128"])
def test_bfloat16_operands_stay_inside_the_folds_tolerance(case, small_tiles):
    """bfloat16 q, k, v as the LM's compute type hands them: a float32 output,
    gradients in the operands' type, inside what the ring entry's bfloat16
    tests allow (3e-2 of the largest entry)."""
    b, h, h_kv, t, d, d_v, window = CASES[case]
    qkv, cot = _inputs(b, h, h_kv, t, d, d_v, seed=7)
    scale = d ** -0.5
    low = tuple(x.astype(jnp.bfloat16) for x in qkv)
    with jax.default_matmul_precision("highest"):
        want = _out_and_grads(lambda q, k, v: _plain(q, k, v, scale, window), qkv, cot)
    got = _out_and_grads(lambda q, k, v: flash.fused_attention(q, k, v, scale, window, True), low, cot)
    assert got[0].dtype == jnp.float32 and all(g.dtype == jnp.bfloat16 for g in got[1:])
    assert _worst(got, want) < 3e-2


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 3e-2)], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("window", [None, 300], ids=["full", "windowed"])
def test_against_the_parents_fold(dtype, tol, window, small_tiles):
    """The ring entry as ``decoder_lm._fold`` called it (``m0 = -inf``, ``l0 =
    0``, ``acc0 = 0``, ``acc / l`` outside) is a second reference: the same
    output and gradients, grouped queries on a value head of its own size."""
    qkv, cot = _inputs(1, 4, 2, 768, 24, 16, seed=3)
    qkv = tuple(x.astype(dtype) for x in qkv)
    scale = 24 ** -0.5
    want = _out_and_grads(lambda q, k, v: _parents_fold(q, k, v, scale, window), qkv, cot)
    got = _out_and_grads(lambda q, k, v: flash.fused_attention(q, k, v, scale, window, True), qkv, cot)
    assert [g.dtype for g in got] == [w.dtype for w in want]
    assert _worst(got, [w.astype(jnp.float32) for w in want]) < tol


def test_hidden_chunks_are_never_read(small_tiles):
    """Under a window of 40 keys with the loss on rows 0..511 alone, the keys
    from 512 on are kept by no row that counts: their dk and dv are exact
    zeros, not the rounding of terms that cancel."""
    (q, k, v), cot = _inputs(1, 2, 2, 1024, 16, 16, seed=5)
    window, scale = 40, 0.25
    cot = cot.at[:, :, 512:].set(0.0)
    got = _out_and_grads(lambda q, k, v: flash.fused_attention(q, k, v, scale, window, True), (q, k, v), cot)
    with jax.default_matmul_precision("highest"):
        want = _out_and_grads(lambda q, k, v: _plain(q, k, v, scale, window), (q, k, v), cot)
    assert _worst(got, want) < 1e-5
    assert not np.asarray(got[2])[:, :, 512:].any() and not np.asarray(got[3])[:, :, 512:].any()


def test_a_window_holds_at_least_the_query():
    (q, k, v), _ = _inputs(1, 1, 1, 256, 8, 8)
    with pytest.raises(ValueError, match="holds at least the query"):
        flash.fused_attention(q, k, v, 1.0, 0, True)


def _dense(q, k, v, scale, keep):
    """Softmax attention under a dense boolean mask, K and V repeated over their groups."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.where(jnp.asarray(keep), jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


#: walk -> (positions, shrunken tiles): 512 positions are one tile and one chunk of the backward's own (the block in one
#: piece); 1,024 at tiles and chunks of 256 are four tiles that walk one to four chunks each
WALKS = {"one-chunk": (512, False), "four-chunks": (1024, True)}
#: mask -> (window, block-diffusion block) at ``positions``: the window is 512 keys on the walk of four chunks
MASKS = {"causal": lambda n: (None, None), "window-512": lambda n: (n // 2, None),
         "block-diffusion": lambda n: (None, flash.BlockDiffusion(n // 2, 4))}


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 3e-2)], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("walk", sorted(WALKS))
@pytest.mark.parametrize("d,d_v", [(16, 16), (192, 128)], ids=["equal-heads", "heads-192-128"])
@pytest.mark.parametrize("group", [1, 8])
@pytest.mark.parametrize("mask", sorted(MASKS))
def test_the_one_backward_kernel_is_jax_ad_of_dense_masked_softmax(mask, group, d, d_v, walk, dtype, tol, monkeypatch):
    """``dq``, ``dk`` and ``dv`` out of the ONE walk against ``jax.vjp`` of the
    dense mask's softmax in float32: every mask form, a group of one and of
    eight query heads on a key/value head (whose ``dk`` and ``dv`` are summed
    in the kernel over the group's cells), a value head of its own size, the
    block in one piece and a walk of up to four chunks (the hidden ones
    skipped, the head's accumulators added into at each chunk's rows)."""
    positions, shrunken = WALKS[walk]
    if shrunken:
        for name in TILES:
            monkeypatch.setattr(flash, name, CHUNK)
    window, blocks = MASKS[mask](positions)
    qkv, cot = _inputs(1, group, 1, positions, d, d_v, seed=group + d)
    scale = d ** -0.5
    keep = np.asarray(flash._kept(positions, positions, 0, 0, True, None, window, blocks))
    with jax.default_matmul_precision("highest"):
        want = _out_and_grads(lambda q, k, v: _dense(q, k, v, scale, keep), qkv, cot)
    low = tuple(x.astype(dtype) for x in qkv)
    got = _out_and_grads(lambda q, k, v: flash.fused_attention(q, k, v, scale, window, True, blocks), low, cot)
    assert got[0].dtype == jnp.float32 and all(g.dtype == dtype for g in got[1:])
    for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        assert g.shape == w.shape, name  # dk at q's channels, dv at v's, one key/value head
        assert _worst([g], [w]) < tol, name


@pytest.mark.parametrize("mask", sorted(MASKS))
def test_the_backward_is_one_kernel_call(mask):
    """The VJP as traced: one ``pallas_call``, named as the dkv kernel was (the
    benchmark's patterns read the fold's time by name), that takes ``lse`` and
    hands back no statistic: ``delta`` is formed in the cells."""
    window, blocks = MASKS[mask](1024)
    qkv, cot = _inputs(1, 2, 1, 1024, 16, 16)
    _, vjp = jax.vjp(lambda q, k, v: flash.fused_attention(q, k, v, 0.25, window, True, blocks), *qkv)
    calls = flash.fold_kernel_calls(jax.make_jaxpr(vjp)(cot).jaxpr)
    assert calls == [("bwd_dkv", 1)]


def _attention_layers(cfg) -> int:
    """The layers of ``cfg`` that attend, as a step traces them (a looped stack's once; the module's layer too)."""
    from flink_ml_tpu.models.lm.config import Attention, CCA, LatentAttention, layers, mtp_layer

    specs = layers(cfg) + ((mtp_layer(cfg),) if cfg.mtp_depth else ())
    return sum(isinstance(spec.mixer, (Attention, LatentAttention, CCA)) for spec in specs)


@pytest.mark.parametrize("compute_type", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", sorted(test_lm_scopes.KINDS))
def test_every_attending_layer_goes_through_the_one_block_form(kind, compute_type):
    """``train.program``'s ``fold_one_block``, ``fold_bwd_kernels`` and
    ``fold_row_stats``, read off the step as traced: every attention layer,
    ONE backward kernel a layer, and the two row statistics of the one-block
    form (``lse`` out of the forward and into the backward; ``delta`` never
    leaves a cell) where the ring's carried state made seventeen."""
    cfg = test_lm_scopes.KINDS[kind][0]
    step, *shapes = _traced_step(cfg, compute_type)
    counts = decoder_lm._traced_counts(step, *shapes, cfg, *_noise(cfg))
    assert counts["fold_one_block"] == _attention_layers(cfg) > 0
    assert counts["fold_row_stats"] == sum(flash.ONE_BLOCK_ROW_STATS.values()) == 2
    assert counts["fold_bwd_kernels"] == 1
    jaxpr = step.trace(*shapes, jax.ShapeDtypeStruct((), jnp.int32), *_noise(cfg)).jaxpr.jaxpr
    calls = flash.fold_kernel_calls(jaxpr)
    assert {part for part, _ in calls} == set(flash.ONE_BLOCK_ROW_STATS)
    assert all(stats == flash.ONE_BLOCK_ROW_STATS[part] for part, stats in calls)


def _under_fold(jaxpr, inside=False):
    """The equations under a ``fold`` scope among ``jaxpr``'s and every jaxpr's inside them, a kernel's body apart."""
    for eqn in jaxpr.eqns:
        here = inside or "fold" in str(eqn.source_info.name_stack).split("/")
        if here:
            yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _under_fold(sub, here)


@pytest.mark.parametrize("kind", ["ouro", "laguna", "joyai"])
def test_nothing_of_the_rings_contract_is_left_around_the_kernels(kind):
    """Under the ``fold`` scope the step as traced holds the casts, the head
    transposes and the kernels' calls: no ``acc / l``, no zero-filled state,
    and no row statistic outside a kernel call's own operands and results
    (nothing reshapes one to ``[B, H, T]``)."""
    cfg = test_lm_scopes.KINDS[kind][0]
    step, *shapes = _traced_step(cfg, "bfloat16")
    jaxpr = step.trace(*shapes, jax.ShapeDtypeStruct((), jnp.int32)).jaxpr.jaxpr
    eqns = list(_under_fold(jaxpr))
    assert any(eqn.primitive.name == "pallas_call" for eqn in eqns)
    names = {eqn.primitive.name for eqn in eqns}
    assert not names & {"div", "broadcast_in_dim", "exp", "log", "reduce_sum", "reduce_max"}, sorted(names)
    for eqn in eqns:
        if eqn.primitive.name != "pallas_call":  # [B, H, T] and [B x H, T, 1] alike: no array without a channel axis
            assert all(v.aval.ndim >= 4 or v.aval.ndim == 3 and 1 not in v.aval.shape[1:] for v in eqn.outvars), eqn


def test_the_ring_entry_still_counts_seventeen(monkeypatch):
    """The same two counts on a step whose ``_fold`` is the parent's: no layer
    through the one-block form, seventeen row statistics a layer."""
    cfg = test_lm_scopes.KINDS["olmoe_stacked"][0]
    decoder_lm._train_program.cache_clear()
    monkeypatch.setattr(decoder_lm, "_fold", lambda q, k, v, cd, interpret, window=None: _parents_fold(
        q.astype(cd), k.astype(cd), v.astype(cd), float(q.shape[-1]) ** -0.5, window))
    try:
        step, *shapes = _traced_step(cfg, "float32")
        counts = decoder_lm._traced_counts(step, *shapes, cfg)
        assert (counts["fold_one_block"], counts["fold_row_stats"]) == (0, 17)
    finally:
        decoder_lm._train_program.cache_clear()


# -- the block-diffusion mask (the third form; the doubled sequence of ``blockKind`` ``sdar``) ---------------------


def _bd_rules(t, block, defect=None):
    """The dense ``[2T, 2T]`` mask from the four rules, one of them defective on request."""
    b = np.arange(t) // block
    clean_clean, noised_clean, noised_noised = b[None, :] <= b[:, None], b[None, :] < b[:, None], b[None, :] == b[:, None]
    clean_noised = np.zeros((t, t), bool)
    if defect == "noised_sees_its_own_clean_block":
        noised_clean = clean_clean
    elif defect == "clean_is_strictly_causal":
        clean_clean = np.tril(np.ones((t, t), bool))
    elif defect == "noised_is_causal_in_its_half":
        noised_noised = clean_clean
    elif defect == "clean_sees_its_noised_block":
        clean_noised = noised_noised
    return np.block([[clean_clean, clean_noised], [noised_clean, noised_noised]])


#: name -> (batch, query heads, key/value heads, tokens T, D, block, shrunken tiles): the fold runs over 2 T positions.
#: At the kernels' own tiles T 768 is 1,536 positions in three tiles and chunks of 512, the middle ones half clean and
#: half noised; T 1,024 has tiles of 512 on two chunks of 1,024 that end where the halves do; with tiles of 256 T 384
#: has a tile across the halves, T 512 none
BD_CASES = {
    "tiles-across-the-halves-block-4": (1, 4, 2, 768, 16, 4, False),
    "tiles-across-the-halves-block-32": (1, 4, 2, 768, 16, 32, False),
    "halves-of-whole-chunks-block-4": (1, 2, 1, 1024, 16, 4, False),
    "halves-of-whole-chunks-block-32": (1, 2, 2, 1024, 16, 32, False),
    "small-tiles-across-block-4": (2, 4, 1, 384, 16, 4, True),
    "small-tiles-across-block-32": (1, 8, 2, 384, 8, 32, True),
    "small-tiles-whole-block-4": (1, 2, 2, 512, 16, 4, True),
    "a-block-is-a-tile": (1, 2, 2, 512, 16, 256, True),
    "one-chunk": (1, 2, 1, 256, 16, 4, False),  # 512 positions in one piece: masked there, nothing walked
}


@pytest.mark.parametrize("case", sorted(BD_CASES))
def test_the_block_diffusion_fold_is_dense_masked_softmax_and_its_gradients(case, monkeypatch):
    b, h, h_kv, t, d, block, shrunken = BD_CASES[case]
    if shrunken:
        for name in TILES:
            monkeypatch.setattr(flash, name, CHUNK)
    qkv, cot = _inputs(b, h, h_kv, 2 * t, d, d, seed=sorted(BD_CASES).index(case))
    scale, blocks = d ** -0.5, flash.BlockDiffusion(t, block)
    keep = _bd_rules(t, block)
    assert keep.diagonal().all() and keep.sum() == t * t + t * block  # every row keeps itself; T^2 + T L pairs
    np.testing.assert_array_equal(np.asarray(flash._kept(2 * t, 2 * t, 0, 0, True, None, None, blocks)), keep)
    with jax.default_matmul_precision("highest"):
        want = _out_and_grads(lambda q, k, v: _dense(q, k, v, scale, keep), qkv, cot)
    got = _out_and_grads(lambda q, k, v: flash.fused_attention(q, k, v, scale, None, True, blocks), qkv, cot)
    assert got[0].dtype == jnp.float32
    for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert _worst([g], [w]) < 1e-5, name
    # and the ring's reference, told the same mask, is the same fold
    m0, l0 = jnp.full((b, h, 2 * t), -jnp.inf, jnp.float32), jnp.zeros((b, h, 2 * t), jnp.float32)
    k_rep, v_rep = (jnp.repeat(x, h // h_kv, axis=1) for x in qkv[1:])
    _, l, acc = flash.reference_fold(qkv[0], k_rep, v_rep, m0, l0, jnp.zeros_like(qkv[0]), 0, 0, True, None, scale,
                                     None, blocks)
    assert _worst([acc / l[..., None]], [want[0]]) < 1e-5


@pytest.mark.parametrize("rule", ["noised_sees_its_own_clean_block", "clean_is_strictly_causal",
                                  "noised_is_causal_in_its_half", "clean_sees_its_noised_block"])
def test_each_rule_of_the_block_diffusion_mask_is_told_apart(rule):
    """The four rules one by one: a mask with one of them wrong gives another
    output than the fold's, far past the tolerance the sound mask is held to."""
    b, h, h_kv, t, d, block, _ = BD_CASES["tiles-across-the-halves-block-4"]
    qkv, _ = _inputs(b, h, h_kv, 2 * t, d, d, seed=11)
    scale = d ** -0.5
    got = flash.fused_attention(*qkv, scale, None, True, flash.BlockDiffusion(t, block))
    with jax.default_matmul_precision("highest"):
        sound, wrong = (_dense(*qkv, scale, _bd_rules(t, block, defect)) for defect in (None, rule))
    assert _worst([got], [sound]) < 1e-5 < 1e-2 < _worst([got], [wrong])


def test_bfloat16_operands_under_the_block_diffusion_mask():
    b, h, h_kv, t, d, block, _ = BD_CASES["tiles-across-the-halves-block-32"]
    qkv, cot = _inputs(b, h, h_kv, 2 * t, d, d, seed=7)
    scale, blocks = d ** -0.5, flash.BlockDiffusion(t, block)
    low = tuple(x.astype(jnp.bfloat16) for x in qkv)
    with jax.default_matmul_precision("highest"):
        want = _out_and_grads(lambda q, k, v: _dense(q, k, v, scale, _bd_rules(t, block)), qkv, cot)
    got = _out_and_grads(lambda q, k, v: flash.fused_attention(q, k, v, scale, None, True, blocks), low, cot)
    assert got[0].dtype == jnp.float32 and all(g.dtype == jnp.bfloat16 for g in got[1:])
    assert _worst(got, want) < 3e-2


#: (tokens T, block): the cell's fold first, then tiles across the halves, a block as long as a tile, short sequences
@pytest.mark.parametrize("t,block", [(4096, 4), (4096, 32), (2048, 4), (768, 4), (768, 32), (1536, 32), (384, 4),
                                     (1024, 512), (256, 4), (128, 4)])
def test_fold_chunk_counts_under_the_block_diffusion_mask_is_a_count_of_the_mask(t, block):
    """``fold_chunk_counts`` against the mask itself, brute force: a (query
    tile, key chunk) pair is visited iff the mask keeps an entry of it, at the
    forward's tiles and at the one backward kernel's (a block of one chunk of
    its own is taken whole by either). At the cell's size the forward visits
    37.5% of its pairs and the backward, at chunks half as long, 31.25%, where
    a causal walk of the same 8,192 positions visits 56.25% and 53.1%."""
    p = 2 * t
    keep = _bd_rules(t, block)
    tq, kc, tq_bwd, kc_bwd = flash._attention_tiles(p)
    visited = total = 0
    for rows, keys in ((tq, kc), (tq_bwd, kc_bwd)):
        pairs = keep.reshape(p // rows, rows, p // keys, keys).any(axis=(1, 3))
        visited, total = visited + (int(pairs.sum()) if keys < p else pairs.size), total + pairs.size
    assert flash.fold_chunk_counts(p, p, 0, True, None, flash.BlockDiffusion(t, block)) == (visited, total)
    if t == 4096:
        assert (visited, total) == (48 + 80, 128 + 256)
        assert flash.fold_chunk_counts(p, p, 0, True, one_block=True) == (72 + 136, 128 + 256)


def test_a_block_diffusion_mask_the_counts_are_not_written_for_is_refused():
    (q, k, v), _ = _inputs(1, 1, 1, 512, 8, 8)
    for blocks, window, match in ((flash.BlockDiffusion(256, 6), None, "power of two"),
                                  (flash.BlockDiffusion(128, 4), None, "twice 128 tokens"),
                                  (flash.BlockDiffusion(256, 512), None, "power of two that"),
                                  (flash.BlockDiffusion(256, 4), 64, "no sliding window")):
        with pytest.raises(ValueError, match=match):
            flash.fused_attention(q, k, v, 1.0, window, True, blocks)
