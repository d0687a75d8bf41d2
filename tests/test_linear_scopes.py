"""The linear step names its parts: ``jax.named_scope`` blocks in
``ops/optimizer.py`` and ``linalg/onehot_sparse.py`` reach every HLO
instruction's ``metadata.op_name`` (docs/observability.md, "The linear step's
scopes"). Toy sizes on the CPU's virtual mesh, the crossings in their XLA
form: the one-hot step program is lowered and compiled in each of its forms,
and the ``op_name``s of its text are read with the classification the
benchmark's reader uses
(``perfbench/op_scopes.py::classify``, root ``lin.``): which scopes are there,
which class of a round an instruction sits under, and how much of the scan's
body they cover. Each program's jaxpr is held by its digest since PR 50, which
moved the lane ids' unpack out of the scan's body (the digests before it were
those of the parent of the PR that brought the scopes: a scope is metadata)."""
import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu.linalg.onehot_sparse import BLOCK, OneHotSparseLayout, _premat_pad
from flink_ml_tpu.ops import BinaryLogisticLoss
from flink_ml_tpu.ops import optimizer
from flink_ml_tpu.parallel.mesh import MeshContext
from perfbench.op_scopes import FWD, classify
from tests.test_lm_scopes import INSTRUCTION, _instructions

ROOT = "lin."
LOSS = BinaryLogisticLoss.INSTANCE
CHUNK_LEN, LR, REG, ELASTIC_NET = 3, 0.1, 0.01, 0.5
ROWS, LOCAL_BATCH, SUB_ROWS, K, DIM = 512, 128, 64, 4, 40 * BLOCK
HEAVY = {"dot", "fusion", "custom-call", "scatter", "gather", "reduce", "all-reduce"}


def _rows():
    """``[ROWS, K]`` indices without a draw: two columns fill blocks 0 and 1
    (64 entries a 64-row unit each: heavy, one chunk), one spreads over six
    blocks (width 16), one over thirty-two (width 2)."""
    r = np.arange(ROWS)
    idx = np.stack([r % BLOCK, BLOCK + (r * 3) % BLOCK,
                    (2 + r % 6) * BLOCK + (r * 5) % BLOCK,
                    (8 + (r * 7) % 32) * BLOCK + r % BLOCK], axis=1)
    return idx.astype(np.int32), np.ones((ROWS, K), np.float32)


def _layout(n_data, n_model):
    idx, val = _rows()
    return OneHotSparseLayout.build(idx, val, DIM, n_data, LOCAL_BATCH, sub_rows=SUB_ROWS, n_model=n_model)


def _onehot(premat, n_data=2, n_model=1, use_pallas=False):
    ctx = MeshContext(n_data=n_data, n_model=n_model)
    lay = _layout(n_data, n_model)
    assert [m[1] for m in lay.class_meta] == [2, 16, 64] and len(lay.class_meta[-1]) == 5
    program = optimizer._fused_onehot_program(ctx, LOSS, lay, CHUNK_LEN, LR, REG, ELASTIC_NET, None, use_pallas,
                                              premat=premat, hoist=True)
    stack = (n_data, n_model, lay.n_windows, lay.n_sub, lay.n_flat)
    n_pad = _premat_pad(lay.n_flat, lay.row_hi)
    oh = [jax.ShapeDtypeStruct(stack[:-1] + (w, n_pad), jnp.bfloat16) for w in (lay.row_hi, 128)] if premat else []
    rows = [jax.ShapeDtypeStruct((ROWS,), jnp.float32)] * 3
    return program, (
        jax.ShapeDtypeStruct((lay.nblk_local * n_model * BLOCK,), jnp.float32), jax.ShapeDtypeStruct((), jnp.bool_),
        *_schedule(), jax.ShapeDtypeStruct(stack, jnp.int8), jax.ShapeDtypeStruct(stack, jnp.int16),
        jax.ShapeDtypeStruct(stack, jnp.float32), *oh, *rows)


def _schedule():
    return (jax.ShapeDtypeStruct((CHUNK_LEN,), jnp.int32), jax.ShapeDtypeStruct((CHUNK_LEN,), jnp.int32),
            jax.ShapeDtypeStruct((CHUNK_LEN,), jnp.bool_))


CLASSES = ("light/w2", "light/w16", "chunks")
#: a class's scope under each round, and under ``lin.unpack``: its cut of the lane ids, made before the scan
ROUNDS = {f"lin.{r}/{c}" for r in ("gather", "scatter", "unpack") for c in CLASSES}
ONEHOT = {"lin.unpack", "lin.gather", "lin.cross_dot", "lin.loss", "lin.cross_mult", "lin.scatter", "lin.reduce",
          "lin.update"} | ROUNDS
#: program -> (how it is made, the scopes it must show, sha256 of ``str(make_jaxpr(program))`` with addresses
#: blanked, as PR 50 left it)
PROGRAMS = {
    "onehot_premat": (lambda: _onehot(True), ONEHOT,
                      "7784dfa6f1f523d8a0a8804cb84fb6e0fa36bd1ce8a12a92f91f47b089ae6673"),
    "onehot_build": (lambda: _onehot(False), ONEHOT,
                     "566b94ac6170dbe5be3d1586b7a971a83de0e28abad767a742b578af34804265"),
    "onehot_premat_tp": (lambda: _onehot(True, n_data=2, n_model=2), ONEHOT,
                         "020283ca0bbf6c9b1ea77ba1fc210e1498eed04339e5bd5ab28007f29d914f63"),
    # the chip's form, the crossings as Pallas calls: traced here for its jaxpr, compiled only on the chip
    "onehot_premat_pallas": (lambda: _onehot(True, use_pallas=True), None,
                             "a624b0345eb31ad08987a929f33f281696a09d52c03394a4f7aa589644399153"),
    "onehot_build_pallas": (lambda: _onehot(False, use_pallas=True), None,
                            "346b35c3ba94ee01818a20a19c14848727a42c9674c8d77992f6afc2ba4df863"),
}


@pytest.fixture(scope="module")
def compiled_text():
    """program -> its compiled text, compiled once."""
    made = {}

    def of(name):
        if name not in made:
            program, shapes = PROGRAMS[name][0]()
            made[name] = program.lower(*shapes).compile().as_text()
        return made[name]

    return of


@pytest.fixture(scope="module")
def compiled(compiled_text):
    """program -> its instructions, read once."""
    return functools.lru_cache(maxsize=None)(lambda name: list(_instructions(compiled_text(name))))


def _scopes(instructions):
    return {"/".join(scope) for scope, _ in (classify(n, ROOT) for _, n in instructions) if scope is not None}


#: the programs the CPU compiles: those whose crossings are in their XLA form
COMPILED = sorted(n for n, (_, scopes, _) in PROGRAMS.items() if scopes)


@pytest.mark.parametrize("name,scope", [(n, s) for n in COMPILED for s in sorted(PROGRAMS[n][1])])
def test_the_scope_appears_in_the_compiled_program(compiled, name, scope):
    found = _scopes(compiled(name))
    assert scope in found, sorted(found)


@pytest.mark.parametrize("name", COMPILED)
def test_no_scope_outside_the_table(compiled, name):
    found = _scopes(compiled(name))
    # a class's scope under a round, a kernel's under its crossing: the table's names and what nests under them
    extra = {s for s in found if not any(s == t or s.startswith(t + "/") for t in PROGRAMS[name][1])}
    assert not extra, sorted(extra)
    assert {d for _, d in (classify(n, ROOT) for _, n in compiled(name)) if d} == {FWD}  # no AD in a linear step


@pytest.mark.parametrize("name", COMPILED)
def test_a_class_sits_under_the_round_that_owns_it(compiled, name):
    """``gather_round``, ``scatter_round`` and ``unpack_lane_ids`` open their
    classes RELATIVE: a class's instructions read ``lin.gather/light/w16``,
    ``lin.scatter/chunks`` or ``lin.unpack/light/w2``, never a class without
    its owner, never one round's under the other's."""
    for _, op_name in compiled(name):
        scope, _ = classify(op_name, ROOT)
        if scope is None or not {"light", "chunks"} & set(scope):
            continue
        assert scope[0] in ("lin.gather", "lin.scatter", "lin.unpack"), op_name
        assert scope[1:] in (("chunks",), ("light", "w2"), ("light", "w16")), op_name
    # the chunked class's row passes, each in its round: the sorted take of heavy rows, the segment sum back
    names = [n for _, n in compiled(name) if n]
    assert not [n for n in names if "lin.update" in n and ("lin.gather" in n or "lin.scatter" in n)]
    assert not [n for n in names if "lin.gather" in n and "lin.scatter" in n]


@pytest.mark.parametrize("name", COMPILED)
def test_the_scopes_cover_the_step_bodys_heavy_instructions(compiled, name):
    """Every ``dot``, ``fusion``, ``custom-call``, ``scatter``, ``gather``,
    ``reduce`` and ``all-reduce`` of one step (the scan's body function,
    ``while/body/closed_call``) that carries a name at all sits under a
    ``lin.`` scope. Outside it: the
    scan's own slices of the schedule and stacking of the losses, and the
    count of executed steps behind the loop."""
    named = [n for opcode, n in compiled(name) if n and "/while/body/closed_call/" in n and opcode in HEAVY]
    assert len(named) >= 8
    assert not [n for n in named if classify(n, ROOT)[0] is None]


#: what moves integers without computing on them
MOVES = {"slice", "dynamic-slice", "copy", "convert", "transpose", "reshape"}
TYPED = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]")


@pytest.mark.parametrize("name", COMPILED)
def test_the_scans_body_neither_casts_nor_cuts_the_lane_ids(compiled_text, name):
    """The unpack of the lane ids sits before the scan (these programs visit
    a window more than once): inside the while body no ``convert`` reads an
    ``s8`` array, and under a round's class scope no instruction merely cuts,
    copies or casts an integer array of the ids' rank. The body's pick of the
    step's window from each class's array sits under ``lin.unpack``."""
    dtype_of, body = {}, []
    for line in compiled_text(name).splitlines():
        typed, opcode = TYPED.match(line), INSTRUCTION.match(line)
        if not (typed and opcode):
            continue
        dtype_of[typed.group(1)] = typed.group(2)
        op_name = re.search(r'op_name="([^"]*)"', line)
        if op_name and "/while/body/" in op_name.group(1):
            operands = re.findall(r"%([\w.\-]+)", line.split("(", 1)[1].split(")", 1)[0])
            body.append((opcode.group(1), typed.group(2), typed.group(3).count(",") + 1, operands, op_name.group(1)))
    assert len(body) > 50
    assert not [b for b in body if b[0] == "convert" and "s8" in {dtype_of.get(o) for o in b[3]}]
    picks = 0
    for opcode, dtype, rank, _, op_name in body:
        scope, _ = classify(op_name, ROOT)
        if opcode in MOVES and dtype in ("s8", "s32") and rank >= 2 and scope:
            assert scope[0] not in ("lin.gather", "lin.scatter"), op_name
            picks += scope == ("lin.unpack",) and opcode == "dynamic-slice" and rank == 4
    assert picks >= len(CLASSES)


def digest(text):
    """sha256 of a jaxpr's text, addresses blanked and each ``frozenset``'s
    members in order (``shard_map``'s ``manual_axes`` print in the order of
    the process's string hashes)."""
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    text = re.sub(r"frozenset\(\{([^}]*)\}\)", lambda m: "frozenset({%s})" % ", ".join(sorted(m.group(1).split(", "))),
                  text)
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_the_step_program_is_the_parents(name):
    program, shapes = PROGRAMS[name][0]()
    assert digest(str(jax.make_jaxpr(program)(*shapes))) == PROGRAMS[name][2]
