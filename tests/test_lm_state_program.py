"""AdamW's state at the start of a ``DecoderLM.fit`` is one device program's
output (``decoder_lm._train_program``: ``optimizer.init`` jitted), not optax's
eager fill of ``mu`` and ``nu`` a leaf at a time. Toy sizes on the CPU, one
configuration of each block kind at two depths: the state the first step is
given is, leaf for leaf, what the eager ``optimizer.init(params)`` gives; a
fit from it is bit for bit a fit from the eager state; and the programs the
runtime executes inside ``train.program`` on a second fit, counted in a
profiler session on the host plane where the phase's annotation lies, are one
at both depths (docs/observability.md, "The LM fit")."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu import trace
from flink_ml_tpu.api.dataframe import DataFrame
from flink_ml_tpu.models.lm import DecoderLM, decoder_lm
from flink_ml_tpu.models.lm.config import num_params, param_shapes
from flink_ml_tpu.trace import tracer

N, T, BATCH, STEPS, LR, SEED = 4, 256, 2, 2, 1e-3, 5
DEPTHS = (1, 3)
#: what the CPU runtime calls one execution of a compiled program, eager primitive or jitted function alike
EXECUTION = "PjRtCpuExecutable::Execute"


def _base(layers):
    return (DecoderLM().set_num_layers(layers).set_hidden_size(32).set_num_heads(2).set_vocab_size(64)
            .set_max_iter(STEPS).set_global_batch_size(BATCH).set_learning_rate(LR).set_seed(SEED))


KINDS = {
    "olmoe": lambda layers: _base(layers).set_num_experts(4).set_experts_per_token(2).set_expert_width(16),
    "zaya": lambda layers: (_base(layers).set_block_kind("zaya").set_num_kv_heads(2).set_head_size(16)
                            .set_rope_fraction(0.5).set_router_width(8).set_num_experts(4).set_experts_held(2)
                            .set_first_expert_held(1).set_experts_per_token(1).set_expert_width(16)
                            .set_tie_embeddings(True)),
    "ouro": lambda layers: _base(layers).set_block_kind("ouro").set_expert_width(48).set_num_loops(2),
}


@pytest.fixture(autouse=True)
def _tracer_off():
    tracer.disable()
    yield
    tracer.disable()


class _FirstState:
    """Stands where ``_train_program`` stands and keeps a host copy of the state
    each fit's first step is given (the step donates the buffers themselves)."""

    def __init__(self, program):
        self.program, self.cache_info, self.states = program, program.cache_info, []

    def __call__(self, *key):
        optimizer, step = self.program(*key)
        seen = []

        def first(params, opt_state, window, lo):
            if not seen:
                seen.append(True)
                self.states.append(jax.device_get(opt_state))
            return step(params, opt_state, window, lo)

        first.trace = step.trace  # ``train.program`` reads its counts off the step as traced
        return optimizer, first


def _executions_inside(trace_dir, name):
    """Per event ``name`` of the newest trace's host plane, in order, the number
    of program executions that lie inside it on its own thread line."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    counts = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events
                      if e.name in (name, EXECUTION)]
            for _, lo, hi in sorted((e for e in events if e[0] == name), key=lambda e: e[1]):
                counts.append(sum(1 for n, a, b in events if n == EXECUTION and lo <= a and b <= hi))
    return counts


@pytest.fixture(scope="module", params=sorted(KINDS))
def fits(request, tmp_path_factory):
    """One kind: a fit at each depth to compile, then a second at each inside one
    profiler session, the tracer recording and the first step's state kept."""
    kind = request.param
    df = DataFrame.from_dict({"features": np.random.default_rng(0).integers(0, 64, (N, T))})
    for layers in DEPTHS:
        KINDS[kind](layers).fit(df)
    spy = _FirstState(decoder_lm._train_program)
    trace_dir = str(tmp_path_factory.mktemp(f"profile_{kind}"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    estimators = [KINDS[kind](layers) for layers in DEPTHS]
    decoder_lm._train_program = spy
    try:
        with trace.capture() as recorder:
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            try:
                for est in estimators:
                    est.fit(df)
            finally:
                jax.profiler.stop_trace()
    finally:
        decoder_lm._train_program = spy.program
    phases = [s.attrs for s in sorted(recorder.snapshot(), key=lambda s: s.span_id) if s.name == "train.program"]
    return df, estimators, spy.states, phases, _executions_inside(trace_dir, "train.program")


def test_the_state_is_optax_init_leaf_for_leaf(fits):
    _, estimators, states, _, _ = fits
    for est, state in zip(estimators, states):
        cfg = est.lm_config()
        want = decoder_lm._optimizer(LR).init(decoder_lm.init_params(cfg, SEED))  # eager, as optax makes it
        assert jax.tree_util.tree_structure(state) == jax.tree_util.tree_structure(want)
        got, want = jax.tree_util.tree_leaves(state), jax.tree_util.tree_leaves(want)
        assert len(got) == 2 * len(param_shapes(cfg)) + 1
        for a, b in zip(got, want):
            assert (a.shape, a.dtype) == (b.shape, b.dtype)
            assert np.array_equal(a, b) and not np.asarray(a).any()  # mu, nu and the count: zeros


def test_the_phase_counts_the_state(fits):
    _, estimators, _, phases, _ = fits
    assert len(phases) == len(DEPTHS)
    for est, phase in zip(estimators, phases):
        cfg = est.lm_config()
        assert phase["built"] == 0
        assert phase["state_leaves"] == 2 * len(param_shapes(cfg)) + 1
        assert phase["state_bytes"] == 2 * 4 * num_params(cfg) + 4  # mu and nu in float32, the int32 count
    assert phases[0]["state_leaves"] < phases[1]["state_leaves"]


def test_one_program_makes_the_state_at_every_depth(fits):
    """Eager, the phase executes two programs and a transfer for every leaf of
    ``mu`` and of ``nu``: 61 and 157 at these depths of the ``olmoe`` kind."""
    *_, executed = fits
    assert executed == [1] * len(DEPTHS)


def test_a_fit_from_it_is_a_fit_from_the_eager_state(fits):
    """The histories of the traced fit against the same steps run by hand from
    ``optimizer.init`` as optax makes it: equal to the bit."""
    df, estimators, _, _, _ = fits
    window = jnp.asarray(np.asarray(df.vectors("features")), jnp.int32)
    for est in estimators:
        cfg = est.lm_config()
        _, step = decoder_lm._train_program(cfg, est.get_compute_type(), LR, BATCH, True)
        params = decoder_lm.init_params(cfg, SEED)
        opt_state = decoder_lm._optimizer(LR).init(params)
        losses, norms = [], []
        for lo in (0, BATCH):
            params, opt_state, loss, leaf_norms, _ = step(params, opt_state, window, jnp.int32(lo))
            losses.append(float(loss))
            norms.append(np.asarray(leaf_norms, np.float64))
        assert est.loss_history == losses
        assert np.array_equal(est.param_grad_norm_history, np.stack(norms))
