"""``DecoderLM`` with ``blockKind`` ``zaya`` against its plain reference
(models/lm/reference_zaya.py) on seeded random weights at toy size: 2 layers,
hidden 128, a latent of 4 query heads on 2 key/value heads of 16, RoPE on half
of each head, a router MLP of width 32 over 8 experts of which 4 are held
(experts 2..5), top-1, expert width 64, a tied vocabulary of 512, T 256, batch
2, 2 steps. The same fit loop, head, loss chunking and AdamW program as the
``olmoe`` kind (tests/test_decoder_lm.py), chosen by a stage parameter.

Tolerances. float32: stage and reference compute the same mathematics in
different orders, so they differ by float32 rounding; read here the loss by
1e-7 relative, the gradient norm by 3e-7, the worst gradient leaf by 4e-6 of
its largest entry (the limits sit 10-25x above). bfloat16 matmul inputs: the
loss by 2e-4, the gradient norm by 4e-3, a gradient leaf's norm by up to 3e-2
(the small convolution and router leaves, whose gradients are sums of few
rounded products); the bands are 5e-3, 3e-2 and 1.5e-1. A token whose two
largest router probabilities lie within rounding could change expert between
the two sides and move both by far more: the seed is one where none does
(``test_routing_is_the_references``).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu.api.dataframe import DataFrame
from flink_ml_tpu.models.lm import DecoderLM, decoder_lm
from flink_ml_tpu.models.lm import reference_zaya as ref
from flink_ml_tpu.models.lm.config import LMConfig, num_params, param_shapes
from flink_ml_tpu.models.lm.decoder_lm import _flat_names, _ordered, init_params

CFG = LMConfig(n_layers=2, hidden=128, n_heads=4, n_experts=8, top_k=1, expert_width=64, vocab=512,
               rope_theta=5e6, aux_coef=0.0, block="zaya", tied=True, experts_held=4, first_held=2,
               n_kv_heads=2, head_size=16, rope_fraction=0.5, router_width=32)
N, T, BATCH, STEPS, LR, SEED = 4, 256, 2, 2, 1e-3, 11


def _estimator(compute_type="float32", cfg=CFG):
    return (
        DecoderLM().set_block_kind("zaya")
        .set_num_layers(cfg.n_layers).set_hidden_size(cfg.hidden).set_num_heads(cfg.n_heads)
        .set_num_kv_heads(cfg.n_kv_heads).set_head_size(cfg.head_size).set_rope_fraction(cfg.rope_fraction)
        .set_rope_theta(cfg.rope_theta).set_router_width(cfg.router_width)
        .set_num_experts(cfg.n_experts).set_experts_held(cfg.experts_held)
        .set_first_expert_held(cfg.first_held).set_experts_per_token(cfg.top_k)
        .set_expert_width(cfg.expert_width).set_vocab_size(cfg.vocab).set_tie_embeddings(cfg.tied)
        .set_compute_type(compute_type)
        .set_max_iter(STEPS).set_global_batch_size(BATCH).set_learning_rate(LR).set_seed(SEED)
    )


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, CFG.vocab, (N, T))


@pytest.fixture(scope="module")
def df(tokens):
    return DataFrame.from_dict({"features": tokens})


@pytest.fixture(scope="module")
def fitted(df):
    est = _estimator()
    return est, est.fit(df)


@pytest.fixture(scope="module")
def params():
    """The seed's weights with every leaf that starts at a constant moved off it,
    so that a gradient taken here has no term that is zero by construction."""
    leaves = _ordered(init_params(CFG, SEED), CFG)
    key = jax.random.key(99)
    # a bias moves every token's stream alike: kept a tenth of the embedding's 0.02, or it decides the routing
    step = {"normal": 0.0, "small": 0.0, "ones": 0.1, "zeros": 0.002}
    moved = [leaf + step[kind] * jax.random.normal(jax.random.fold_in(key, i), leaf.shape)
             for i, (leaf, (_, _, kind)) in enumerate(zip(leaves, param_shapes(CFG)))]
    return decoder_lm._build_tree(CFG, moved)


def _batches(tokens):
    return [jnp.asarray(tokens[lo: lo + BATCH]) for lo in (0, 2)]


@pytest.fixture(scope="module")
def reference_run(tokens):
    return ref.train_steps(init_params(CFG, SEED), _batches(tokens), CFG, LR)


def _rel(a, b):
    return float(np.max(np.abs(np.asarray(a) - np.asarray(b)) / np.abs(np.asarray(b))))


def test_the_stage_config_is_the_tests(fitted):
    est, _ = fitted
    assert est.lm_config(CFG.vocab) == CFG


def test_parameter_count_at_the_published_widths():
    """The issue's arithmetic, at 16 bytes a parameter: the sliced tied table
    and six layers of 8 held experts; and the whole published model."""
    def zaya(layers, held, vocab):
        return LMConfig(layers, 2048, 8, 16, 1, 2048, vocab, block="zaya", tied=True, experts_held=held,
                        n_kv_heads=2, head_size=128, rope_fraction=0.5, router_width=256)

    one = {name: int(np.prod(shape)) for (_, _, name), shape, _ in
           [(p, s, k) for p, s, k in param_shapes(zaya(2, 8, 32784)) if p[:2] == ("layers", 1)]}
    assert one["wq"] + one["wk"] + one["wv1"] + one["wv2"] + one["wo"] == 5_242_880
    assert one["conv0_w"] + one["conv0_b"] + one["conv1_w"] + one["conv1_b"] == 332_800
    assert sum(v for k, v in one.items() if k.startswith("router")) == 659_968
    assert one["w_gate"] + one["w_up"] + one["w_down"] == 8 * 12_582_912
    cut = num_params(zaya(6, 8, 32784))
    assert cut == 708_659_980 and 11.3e9 < 16 * cut < 11.4e9
    assert 16 * num_params(zaya(5, 8, 32784)) < 9.7e9
    assert num_params(zaya(40, 0, 262272)) == 8_840_443_728


def test_loss_and_gradient_norm_of_every_step(fitted, reference_run):
    est, _ = fitted
    _, losses, norms = reference_run
    assert len(est.loss_history) == STEPS == len(est.grad_norm_history)
    assert _rel(est.loss_history, losses) < 2e-6
    assert _rel(est.grad_norm_history, norms) < 5e-6
    assert est.param_names == _flat_names(CFG) and "lm_head" not in est.param_names
    assert est.param_grad_norm_history.shape == (STEPS, len(param_shapes(CFG)))


def test_every_parameter_after_two_steps(fitted, reference_run):
    _, model = fitted
    want, _, _ = reference_run
    for name, a, b in zip(_flat_names(CFG), _ordered(model.params, CFG), _ordered(want, CFG)):
        assert float(jnp.max(jnp.abs(a - b))) < 0.05 * STEPS * LR, name


@pytest.mark.parametrize("compute_type,leaf_tol,norm_tol", [("float32", 1e-4, 1e-4), ("bfloat16", None, 1.5e-1)])
def test_every_parameters_gradient(params, tokens, compute_type, leaf_tol, norm_tol):
    """Forward, loss and the gradient of every leaf, the tied table's among
    them (the lookup's scatter plus the head's matmuls), against ``jax.grad``
    of the plain reference."""
    tok = _batches(tokens)[0]
    want_loss, want = ref.loss_and_grads(params, tok, CFG)
    (loss, stats), got = jax.value_and_grad(decoder_lm._loss, has_aux=True)(
        params, tok, CFG, jnp.dtype(compute_type), True)
    assert _rel(loss, want_loss) < (2e-6 if leaf_tol else 5e-3)
    assert int(stats["rows"].sum()) == CFG.n_layers * BATCH * T
    for name, g, w in zip(_flat_names(CFG), _ordered(got, CFG), _ordered(want, CFG)):
        assert float(jnp.max(jnp.abs(w))) > 0, name  # no leaf's gradient is zero by construction
        if leaf_tol:
            assert float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w))) < leaf_tol, name
        assert _rel(jnp.sqrt(jnp.sum(g * g)), jnp.sqrt(jnp.sum(w * w))) < norm_tol, name


def test_bfloat16_fit_within_its_bands(df, reference_run):
    est = _estimator("bfloat16")
    est.fit(df)
    _, losses, norms = reference_run
    assert _rel(est.loss_history, losses) < 5e-3
    assert _rel(est.grad_norm_history, norms) < 3e-2


def test_routing_is_the_references(fitted, tokens):
    """Each layer's loads at step 1 are the reference's choices counted: routed
    = held + absent exactly, both kinds occur, and the tied rows are where the
    reference puts them."""
    est, _ = fitted
    loads = est.expert_rows_history
    assert loads.shape == (STEPS, CFG.n_layers, CFG.n_experts)
    assert (loads.sum(axis=2) == BATCH * T).all()
    with jax.default_matmul_precision("highest"):
        _, chosen = ref.forward(init_params(CFG, SEED), _batches(tokens)[0], CFG)
    want = np.stack([np.bincount(np.asarray(c).ravel(), minlength=CFG.n_experts) for c in chosen])
    np.testing.assert_array_equal(loads[0], want)
    held = loads[0][:, CFG.first_held: CFG.first_held + CFG.experts_held].sum()
    assert 0 < held < loads[0].sum()


def test_the_drain_counts_tell_held_from_absent(df):
    """``train.drain``'s counts and the two registry counters for a fit that
    holds 4 of its 8 experts: routed = held + absent exactly, nothing dropped,
    and ``train.init`` counts the parameters made here (docs/observability.md,
    "The LM fit")."""
    from flink_ml_tpu import trace
    from flink_ml_tpu.metrics import MLMetrics, metrics
    from flink_ml_tpu.trace import tracer

    def counted():
        return (metrics.get(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_MOE_ROWS) or 0,
                metrics.get(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_MOE_ROWS_ABSENT) or 0)

    est = _estimator()
    before = counted()
    try:
        with trace.capture() as recorder:
            est.fit(df)
    finally:
        tracer.disable()
    one = {s.name: s.attrs for s in recorder.snapshot()}
    loads = est.expert_rows_history
    held = loads[:, :, CFG.first_held: CFG.first_held + CFG.experts_held]
    drain = one["train.drain"]
    routed = STEPS * BATCH * T * CFG.n_layers
    assert drain["rows_held"] == held.sum() and drain["rows_absent"] == loads.sum() - held.sum()
    assert drain["rows_held"] + drain["rows_absent"] == routed and drain["dropped"] == 0
    assert 0 < drain["rows_held"] < routed
    assert drain["held_rows_max"] == held.max() and drain["held_rows_mean"] == pytest.approx(held.mean())
    assert one["train.init"] == {"params": num_params(CFG), "bytes": 4 * num_params(CFG)}
    after = counted()
    assert (after[0] - before[0], after[1] - before[1]) == (drain["rows_held"], drain["rows_absent"])


def test_transform_is_the_reference_log_likelihood(fitted, df, tokens):
    _, model = fitted
    got = np.asarray(model.transform(df).scalars("prediction"))
    want = np.asarray(ref.log_likelihood(model.params, jnp.asarray(tokens), CFG))
    np.testing.assert_allclose(got, want, rtol=2e-6)


def test_the_two_expert_shares_add_up_to_the_uncut_layer(params):
    """The ``model-configs`` guide's share test: one layer's MoE output from the
    chip that holds experts 0..3 plus the one from the chip that holds 4..7 is
    the uncut reference's layer (all 8 held), the program's and the
    reference's shares alike."""
    whole = CFG._replace(experts_held=0, first_held=0)
    leaves = dict(zip(_flat_names(CFG), _ordered(params, CFG)))
    key = jax.random.key(3)
    layer = {name.split(".", 2)[2]: leaf for name, leaf in leaves.items() if name.startswith("layers.1.")}
    for i, w in enumerate(("w_gate", "w_up", "w_down")):
        layer[w] = 0.05 * jax.random.normal(jax.random.fold_in(key, i), (8,) + layer[w].shape[1:])
    u = jax.random.normal(jax.random.fold_in(key, 7), (T, CFG.hidden))
    carry = jax.random.normal(jax.random.fold_in(key, 8), (T, CFG.router_width))
    with jax.default_matmul_precision("highest"):
        want, _, chosen = ref.moe(u, layer, carry, whole)
        assert len(np.unique(np.asarray(chosen))) > 2  # the routing is not one expert's
        shares, ref_shares = [], []
        for first in (0, 4):
            share = {**layer, **{w: layer[w][first: first + 4] for w in ("w_gate", "w_up", "w_down")}}
            cfg = CFG._replace(experts_held=4, first_held=first)
            ref_shares.append(ref.moe(u, share, carry, cfg)[0])
            state = decoder_lm._router_state(u, share, carry)  # the stage's own router and MoE layer
            y, stats = decoder_lm.moe_dropless(
                u, lambda _: decoder_lm._router_logits(state, share, CFG.norm_eps),  # noqa: B023
                share["w_gate"], share["w_up"], share["w_down"], 1, jnp.float32, first)
            assert int(stats["rows"].sum()) == T
            shares.append(y)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(ref_shares[0] + ref_shares[1] - want))) < 1e-6 * scale
    assert float(jnp.max(jnp.abs(shares[0] + shares[1] - want))) < 1e-5 * scale
    assert float(jnp.max(jnp.abs(shares[0]))) > 0 and float(jnp.max(jnp.abs(shares[1]))) > 0


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)], ids=["float32", "bfloat16"])
def test_grouped_query_fold_forward_and_backward(dtype, tol, monkeypatch):
    """8 query heads on 2 key/value heads (interpret mode): the fold reads K
    and V at ``[B, H_kv, T, D]`` and hands back ``dk``, ``dv`` summed over each
    group's query heads, against ``reference_fold`` on K and V repeated. The
    dkv kernel's accumulation axis walks two q tiles a head here."""
    from flink_ml_tpu.parallel import flash

    monkeypatch.setattr(flash, "_DKV_CAUSAL", 256)  # 2 x 2 pairs a head, one of them hidden
    B, H, H_KV, Tq, D = 2, 8, 2, 512, 32
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((B, H, Tq, D)).astype(np.float32))
    k, v = (jnp.asarray(rng.standard_normal((B, H_KV, Tq, D)).astype(np.float32)) for _ in range(2))
    w = jnp.asarray(rng.standard_normal((B, H, Tq, D)).astype(np.float32))
    m0 = jnp.full((B, H, Tq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Tq), jnp.float32)
    acc0 = jnp.zeros((B, H, Tq, D), jnp.float32)
    zero = jnp.int32(0)

    def fused(q_, k_, v_):
        _, l, acc = flash.fused_fold(q_.astype(dtype), k_.astype(dtype), v_.astype(dtype), m0, l0, acc0,
                                     zero, zero, True, False, zero, D ** -0.5, True)
        return acc / l[..., None]

    def plain(q_, k_, v_):
        kk, vv = jnp.repeat(k_, H // H_KV, axis=1), jnp.repeat(v_, H // H_KV, axis=1)
        _, l, acc = flash.reference_fold(q_, kk, vv, m0, l0, acc0, 0, 0, True, None, D ** -0.5)
        return acc / l[..., None]

    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(fused, q, k, v)
        want, want_vjp = jax.vjp(plain, q, k, v)
        assert float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))) < tol
        for name, g, x in zip("qkv", vjp(w), want_vjp(w)):
            assert g.shape == x.shape
            assert float(jnp.max(jnp.abs(g - x)) / jnp.max(jnp.abs(x))) < tol, name


def test_all_held_and_a_matrix_router_is_the_olmoe_program():
    """``moe_dropless`` told nothing of a held range, with a matrix router,
    traces to the same program as before it knew of one: no row guard, no
    relabelled sort key."""
    from flink_ml_tpu.parallel.moe import moe_dropless

    key = jax.random.key(0)
    x = jax.random.normal(key, (64, 16))
    router = jax.random.normal(jax.random.fold_in(key, 1), (16, 4))
    wg, wu = (jax.random.normal(jax.random.fold_in(key, i), (4, 16, 8)) for i in (2, 3))
    wd = jax.random.normal(jax.random.fold_in(key, 4), (4, 8, 16))
    from flink_ml_tpu.parallel.moe import _row_guard

    grads = lambda *a, **kw: jax.grad(lambda *b: jnp.sum(moe_dropless(*b, 2, **kw)[0]), argnums=(0, 2))(*a)  # noqa: E731
    whole = str(jax.make_jaxpr(grads)(x, router, wg, wu, wd))
    part = str(jax.make_jaxpr(functools.partial(grads, first_held=1))(x, router, wg[:2], wu[:2], wd[:2]))
    assert "dynamic_slice" not in whole and "dynamic_slice" in part  # the held groups' sizes
    assert part.count("select_n") > whole.count("select_n")  # the relabelled key, the masked gate, the row guards
    sizes = jnp.array([3, 1])
    assert _row_guard(sizes, True)(x) is x
    np.testing.assert_array_equal(_row_guard(sizes, False)(x)[4:], 0.0)
    with pytest.raises(ValueError, match="not among"):
        moe_dropless(x, router, wg[:2], wu[:2], wd[:2], 2, first_held=3)


def test_bad_sizes_are_refused(df):
    with pytest.raises(ValueError, match="two key/value heads"):
        _estimator().set_num_kv_heads(4).fit(df)
    with pytest.raises(ValueError, match="not among"):
        _estimator().set_first_expert_held(6).fit(df)
    with pytest.raises(ValueError, match="blockKind 'zaya'"):
        _estimator().set_block_kind("olmoe").fit(df)
