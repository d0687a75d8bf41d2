"""The LM step names its parts: ``jax.named_scope`` blocks in
``models/lm/decoder_lm.py`` and ``parallel/moe.py`` reach every HLO
instruction's ``metadata.op_name`` (docs/observability.md, "The step's
scopes"). Toy sizes on the CPU, the fold interpreted: each block kind's step
program is lowered and compiled, and the ``op_name``s of its text are read
with the classification the benchmark's reader uses
(``perfbench/op_scopes.py::classify``): which scopes are there, in which of
the three directions, and how much of the program they cover."""
import re

import jax
import jax.numpy as jnp
import pytest

from flink_ml_tpu.models.lm import decoder_lm
from flink_ml_tpu.models.lm.config import LMConfig
from perfbench.op_scopes import BWD, FWD, REMAT, classify

ROOT = "lm."
_OLMOE = LMConfig(n_layers=1, hidden=128, n_heads=4, n_experts=8, top_k=2, expert_width=64, vocab=512)
#: kind -> (configuration, the block is checkpointed, the scopes only this kind has)
KINDS = {
    "olmoe": (_OLMOE, False, {"lm.block/route", "lm.block/permute", "lm.block/experts", "lm.aux"}),
    "olmoe_stacked": (_OLMOE._replace(n_layers=2), True,
                      {"lm.block/route", "lm.block/permute", "lm.block/experts", "lm.aux"}),
    "zaya": (LMConfig(n_layers=2, hidden=128, n_heads=4, n_experts=8, top_k=1, expert_width=64, vocab=512,
                      rope_theta=5e6, aux_coef=0.0, block="zaya", tied=True, experts_held=4, first_held=2,
                      n_kv_heads=2, head_size=16, rope_fraction=0.5, router_width=32), True,
             {"lm.block/conv", "lm.block/route", "lm.block/route/norm", "lm.block/permute", "lm.block/experts"}),
    "ouro": (LMConfig(n_layers=2, hidden=128, n_heads=4, n_experts=0, top_k=0, expert_width=64, vocab=512,
                      aux_coef=0.0, block="ouro", loops=3, exit_beta=0.1), True, {"lm.block/ffn", "lm.exit"}),
    "laguna": (LMConfig(n_layers=3, hidden=64, n_heads=4, n_experts=8, top_k=2, expert_width=32, vocab=512,
                        rope_theta=5e5, norm_eps=1e-6, aux_coef=0.0, block="laguna", experts_held=4, first_held=2,
                        n_kv_heads=2, head_size=16, rope_fraction=0.5, layer_heads=(4, 8, 4),
                        layer_windows=(0, 96, 0), n_dense=1, dense_width=96, shared_width=32, routed_scale=2.5,
                        yarn=(4.0, 64.0, 8.0, 1.0, 1.1386)), True,
               {"lm.block/ffn", "lm.block/route", "lm.block/permute", "lm.block/experts", "lm.block/gate",
                "lm.block/shared"}),
    "nemotron_h": (LMConfig(n_layers=3, hidden=64, n_heads=4, n_experts=8, top_k=2, expert_width=32, vocab=512,
                            norm_eps=1e-5, aux_coef=0.0, block="nemotron_h", experts_held=2, first_held=2,
                            n_kv_heads=2, head_size=16, shared_width=48, routed_scale=2.5,
                            layer_kinds=tuple("M*E"), ssm_heads=8, ssm_head_dim=8, ssm_groups=2, ssm_state=16,
                            conv_kernel=4, chunk=64), True,
                   {"lm.block/scan", "lm.block/gnorm", "lm.block/conv", "lm.block/route", "lm.block/permute",
                    "lm.block/experts", "lm.block/shared"}),
    # the multi-token-prediction module's parts all lie under ``lm.mtp``: its layer's as ``lm.mtp/lm.block/...``,
    # its pass over the head as ``lm.mtp/lm.head``
    "joyai": (LMConfig(n_layers=2, hidden=64, n_heads=4, n_experts=8, top_k=2, expert_width=32, vocab=512,
                       rope_theta=3.2e7, norm_eps=1e-6, aux_coef=0.0, block="joyai", experts_held=4, first_held=2,
                       n_dense=1, dense_width=96, shared_width=32, routed_scale=2.5, q_rank=48, kv_rank=32,
                       nope_dim=16, rope_dim=8, v_dim=12, mtp_depth=1, mtp_coef=0.3), True,
              {"lm.block/latent", "lm.block/latent/norm", "lm.block/ffn", "lm.block/route", "lm.block/permute",
               "lm.block/experts", "lm.block/shared", "lm.mtp/proj", "lm.mtp/proj/norm", "lm.mtp/norm",
               "lm.mtp/lm.head", "lm.mtp/lm.block/norm", "lm.mtp/lm.block/latent", "lm.mtp/lm.block/latent/norm",
               "lm.mtp/lm.block/rope", "lm.mtp/lm.block/fold", "lm.mtp/lm.block/proj", "lm.mtp/lm.block/mix",
               "lm.mtp/lm.block/route", "lm.mtp/lm.block/permute", "lm.mtp/lm.block/experts",
               "lm.mtp/lm.block/shared"}),
    # block diffusion: the corruption and the doubled input under ``lm.noise``, before the embedding
    "sdar": (LMConfig(n_layers=2, hidden=64, n_heads=4, n_experts=8, top_k=2, expert_width=32, vocab=512,
                      rope_theta=1e6, norm_eps=1e-6, aux_coef=0.0, block="sdar", experts_held=4, first_held=2,
                      n_kv_heads=2, head_size=16, block_length=4, mask_id=511), True,
             {"lm.noise", "lm.block/route", "lm.block/permute", "lm.block/experts"}),
    # the delta rule's kernel pair under ``kda``, its decay and strength under ``kgate``, the attention layer's
    # output gate under ``gate`` (laguna's name for its head gates), the gated norm and the convolution under nemotron's
    "solar_open2": (LMConfig(n_layers=2, hidden=64, n_heads=4, n_experts=8, top_k=2, expert_width=32, vocab=512,
                             norm_eps=1e-5, aux_coef=0.0, block="solar_open2", experts_held=4, first_held=2,
                             n_kv_heads=2, head_size=16, shared_width=32, routed_scale=1.0, conv_kernel=4, chunk=64,
                             gqa_layers=(0,), kda_heads=2, kda_head_dim=16), True,
                    {"lm.block/kda", "lm.block/kgate", "lm.block/gnorm", "lm.block/conv", "lm.block/gate",
                     "lm.block/route", "lm.block/permute", "lm.block/experts", "lm.block/shared"}),
    # the one-decay delta rule under solar's names (``kda``, ``kgate``, ``gnorm``, ``conv``), the dense SwiGLU under ``ffn``
    "olmo_hybrid": (LMConfig(n_layers=2, hidden=64, n_heads=4, n_experts=0, top_k=0, expert_width=96, vocab=512,
                             norm_eps=1e-6, aux_coef=0.0, block="olmo_hybrid", n_kv_heads=4, head_size=16, conv_kernel=4,
                             chunk=64, gqa_layers=(1,), kda_heads=3, kda_head_dim=8, kda_value_dim=16), True,
                    {"lm.block/kda", "lm.block/kgate", "lm.block/gnorm", "lm.block/conv", "lm.block/ffn"}),
}
EVERY_KIND = {"lm.embed", "lm.block/norm", "lm.block/proj", "lm.block/fold", "lm.block/mix",
              "lm.final_norm/norm", "lm.head", "lm.opt"}
#: every kind but ``nemotron_h``, ``solar_open2`` and ``olmo_hybrid``, whose attention has no position encoding
ROPE = "lm.block/rope"
#: ``%name = shape opcode(``: the opcode is the first word followed by a parenthesis
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? ([a-z][\w\-]*)\(")
HEAVY = {"dot", "fusion", "custom-call", "scatter", "sort", "reduce"}
TOKENS = jax.ShapeDtypeStruct((4, 256), jnp.int32)


def _instructions(text):
    """``(opcode, op_name or None)`` of every instruction of an HLO text."""
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            name = re.search(r'op_name="([^"]*)"', line)
            yield m.group(1), name.group(1) if name else None


@pytest.fixture(scope="module")
def programs():
    """kind -> (the step program's instructions, the scoring program's), compiled once."""
    made = {}

    def of(kind):
        if kind not in made:
            cfg = KINDS[kind][0]
            optimizer, step = decoder_lm._train_program(cfg, "float32", 1e-3, 2, True)
            params = jax.eval_shape(lambda: decoder_lm._init_program(cfg)(jax.random.key(0)))
            state = jax.eval_shape(optimizer.init, params)
            # a stage that trains by block diffusion hands its step and its scoring the noise key and an index
            noise = ((jax.eval_shape(lambda: jax.random.key(0)), jax.ShapeDtypeStruct((), jnp.int32)),) \
                if cfg.block_length else ()
            step_text = step.lower(params, state, TOKENS, jax.ShapeDtypeStruct((), jnp.int32),
                                   *noise).compile().as_text()
            score_text = decoder_lm._log_likelihood_program(cfg, "float32", True).lower(
                params, jax.ShapeDtypeStruct((2, 256), jnp.int32), *noise).compile().as_text()
            made[kind] = list(_instructions(step_text)), list(_instructions(score_text))
        return made[kind]

    return of


def _found(instructions):
    """``{(scope path, direction)}`` of the instructions that carry a scope."""
    out = set()
    for _, op_name in instructions:
        scope, direction = classify(op_name, ROOT)
        if scope is not None:
            out.add(("/".join(scope), direction))
    return out


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_every_scope_the_kind_has_appears(programs, kind):
    step, _ = programs(kind)
    scopes = {scope for scope, _ in _found(step)}
    own = KINDS[kind][2]
    assert (EVERY_KIND | own) <= scopes, sorted((EVERY_KIND | own) - scopes)
    assert (ROPE in scopes) == (kind not in ("nemotron_h", "solar_open2", "olmo_hybrid"))
    # and none another kind alone has
    others = set().union(*(k[2] for k in KINDS.values())) - own
    assert not others & scopes, sorted(others & scopes)


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_head_is_never_recomputed_and_a_block_is_where_it_is_checkpointed(programs, kind):
    step, _ = programs(kind)
    found = _found(step)
    # the head forms its gradients in the pass that holds the logits (``decoder_lm._weighted_nll``): that pass is the
    # forward, the backward scales what it left (and transposes what lies around it: a tied head, the weights' mask)
    assert FWD in {d for scope, d in found if scope == "lm.head"} <= {FWD, BWD}
    block = {d for scope, d in found if scope.startswith("lm.block")}
    assert block == ({FWD, REMAT, BWD} if KINDS[kind][1] else {FWD, BWD})
    # a hand-written VJP's backward keeps the scope its forward was traced under
    assert ("lm.block/fold", BWD) in found
    if "lm.block/experts" in KINDS[kind][2]:
        assert {("lm.block/experts", BWD), ("lm.block/permute", BWD)} <= found
    if kind == "nemotron_h":  # the scan is AD's: each of its parts in all three directions
        assert {d for scope, d in found if scope == "lm.block/scan"} == {FWD, REMAT, BWD}
    if kind == "solar_open2":  # the delta rule's two kernels by name under its scope, in all three directions
        names = {n.split("/kda/")[1].split("/")[0] for _, n in step if n and "/kda/kda_scan" in n}
        assert names == {"kda_scan_fwd", "kda_scan_bwd"}
        for scope in ("lm.block/kda", "lm.block/kgate", "lm.block/gnorm", "lm.block/gate"):
            assert {d for s, d in found if s == scope} == {FWD, REMAT, BWD}, scope
        assert {REMAT, BWD} <= {d for s, d in found if s == "lm.block/conv"}  # (interpreted, its forward call fuses away)
    if kind == "olmo_hybrid":  # the same two kernels under the same scope: the one-decay form is theirs
        names = {n.split("/kda/")[1].split("/")[0] for _, n in step if n and "/kda/kda_scan" in n}
        assert names == {"kda_scan_fwd", "kda_scan_bwd"}
        for scope in ("lm.block/kda", "lm.block/kgate", "lm.block/gnorm", "lm.block/ffn"):
            assert {d for s, d in found if s == scope} == {FWD, REMAT, BWD}, scope
    if kind == "joyai":  # the module's parts in every direction, its layer recomputed like the stack's
        for scope in ("lm.mtp/lm.block/latent", "lm.mtp/lm.block/fold"):
            assert {d for s, d in found if s == scope} == {FWD, REMAT, BWD}, scope
        assert FWD in {d for s, d in found if s == "lm.mtp/lm.head"} <= {FWD, BWD}
        assert {d for s, d in found if s == "lm.mtp/proj"} == {FWD, BWD}
        assert {d for s, d in found if s == "lm.block/latent"} == {FWD, REMAT, BWD}
    if kind == "sdar":  # the doubled sequence's kernels under names of their own, and the corruption outside AD
        names = {n.split("/fold/")[1].split("/")[0] for _, n in step if n and "/fold/flash_fold" in n}
        assert names == {f"flash_fold_bd_{k}" for k in ("fwd", "bwd_dkv")}  # the one backward kernel keeps the dkv's name
        assert {d for s, d in found if s == "lm.noise"} == {FWD}
    if kind == "laguna":  # the windowed layer's kernels under names of their own, beside the full layers'
        names = {n.split("/fold/")[1].split("/")[0] for _, n in step if n and "/fold/flash_fold" in n}
        assert names == {f"flash_fold_{w}{k}" for w in ("", "win_") for k in ("fwd", "bwd_dkv")}
    # nothing of the loss is outside the gradient, nothing of the update inside it
    assert {d for scope, d in found if scope == "lm.opt"} == {FWD}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_update_is_outside_the_gradient(programs, kind):
    step, _ = programs(kind)
    names = [n for _, n in step if n and "lm.opt" in n]
    assert names
    assert not [n for n in names if "jvp" in n or "transpose" in n]


#: The share of a program's named heavy instructions that must carry a scope.
#: A looped stack's ``lax.scan`` stacks each pass's outputs and sums the shared
#: weights' cotangents over the passes (``dynamic_update_slice``, ``add_any``)
#: in code that is JAX's own, under no scope of the program.
COVERED = {"olmoe": 0.95, "olmoe_stacked": 0.95, "zaya": 0.95, "ouro": 0.90, "laguna": 0.95, "nemotron_h": 0.95,
           "joyai": 0.95, "sdar": 0.95, "solar_open2": 0.95, "olmo_hybrid": 0.95}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_scopes_cover_the_programs_heavy_instructions(programs, kind):
    """Of the ``dot``, ``fusion``, ``custom-call``, ``scatter``, ``sort`` and
    ``reduce`` instructions that carry a name at all (the compiler's own copies
    and broadcasts carry none), the share under an ``lm.`` scope."""
    step, _ = programs(kind)
    named = [n for opcode, n in step if opcode in HEAVY and n]
    scoped = [n for n in named if classify(n, ROOT)[0] is not None]
    assert len(named) > 100
    assert len(scoped) >= COVERED[kind] * len(named), (len(scoped), len(named))


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_scoring_program_shares_the_scopes_and_has_no_backward(programs, kind):
    _, score = programs(kind)
    found = _found(score)
    scopes = {scope for scope, _ in found}
    assert {"lm.embed", "lm.block/norm", "lm.block/fold", "lm.final_norm/norm", "lm.head"} <= scopes
    assert "lm.opt" not in scopes and "lm.aux" not in scopes
    assert not [s for s in scopes if s.startswith("lm.mtp")]  # the module is a training objective
    assert {d for _, d in found} == {FWD}
    assert not [n for _, n in score if n and "transpose(" in n]
