"""The graftcheck static-analysis suite — and tier-1's enforcement of it.

Three layers of coverage:

1. **The shipped tree is clean** — running every rule over ``flink_ml_tpu``
   in-process makes each invariant (layer map, jit purity, lock order, fault
   points, error hygiene) a tier-1 gate, replacing the two ad-hoc scripts
   this framework absorbed.
2. **The analyzer works** — per-rule fixture trees (clean + seeded
   violations) prove each rule actually fires; the lock-order fixture plants
   a synthetic A→B / B→A cycle and a self-deadlock and asserts detection.
3. **The framework works** — suppression comments, JSON schema, severity
   overrides, CLI exit codes.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from tools.graftcheck import REGISTRY, Project, run_rules  # noqa: E402
from tools.graftcheck.engine import JSON_SCHEMA_VERSION, parse_suppressions  # noqa: E402
from tools.graftcheck.rules import layer_deps, lock_order  # noqa: E402

ALL_RULES = (
    "blocking-under-lock",
    "check-then-act",
    "elementwise-claim",
    "error-hygiene",
    "fault-points",
    "fusion-tier",
    "host-sync",
    "jit-purity",
    "kernel-cast-boundary",
    "kernel-spec-consistency",
    "layer-deps",
    "lock-order",
    "plan-key-completeness",
    "recompile-hazard",
    "registry-consistency",
    "shared-state-guard",
    "typed-error-escape",
)


def write_tree(root, files):
    """files: {relpath: source}. Creates package __init__s implicitly."""
    for rel, src in files.items():
        path = root / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(src).lstrip("\n"))
        d = path.parent
        while d != root:
            init = d / "__init__.py"
            if not init.exists():
                init.write_text("")
            d = d.parent
    return root


def run_on(root, files, rules=None, **kw):
    write_tree(root, files)
    return run_rules(Project(str(root), ["flink_ml_tpu"]), rules=rules, **kw)


# -----------------------------------------------------------------------------
# 1. tier-1 gate: the shipped tree passes every rule
# -----------------------------------------------------------------------------


def test_registry_has_the_advertised_rules():
    assert set(ALL_RULES) <= set(REGISTRY)


def test_shipped_tree_is_clean():
    result = run_rules(Project(REPO_ROOT, ["flink_ml_tpu"]))
    assert result.findings == [], "\n".join(f.render() for f in result.findings)
    assert result.exit_code == 0
    assert result.files_checked > 100  # the sweep actually covered the package


def test_lock_order_models_all_five_lock_sites():
    graph = lock_order.build_lock_graph(Project(REPO_ROOT, ["flink_ml_tpu"]))
    assert set(graph.nodes) >= {
        "flink_ml_tpu.serving.batcher.MicroBatcher._lock",
        "flink_ml_tpu.serving.registry.ModelRegistry._lock",
        "flink_ml_tpu.serving.server.InferenceServer._template_lock",
        "flink_ml_tpu.metrics.Histogram._lock",
        "flink_ml_tpu.metrics.MetricsRegistry._lock",
    }
    # The known cross-module hold: batcher metrics calls under its queue lock.
    assert (
        "flink_ml_tpu.serving.batcher.MicroBatcher._lock",
        "flink_ml_tpu.metrics.MetricsRegistry._lock",
    ) in graph.edges
    assert graph.cycles() == []


# -----------------------------------------------------------------------------
# 2. layer-deps
# -----------------------------------------------------------------------------


def test_layer_deps_flags_upward_import(tmp_path):
    result = run_on(
        tmp_path,
        {
            "flink_ml_tpu/serving/bad.py": """
                from flink_ml_tpu.iteration import Iterations
            """,
        },
        rules=["layer-deps"],
    )
    (f,) = result.findings
    assert f.rule == "layer-deps" and f.line == 1
    assert "iteration" in f.message and "upward" in f.message


def test_layer_deps_catches_lazy_function_local_imports(tmp_path):
    result = run_on(
        tmp_path,
        {
            "flink_ml_tpu/servable/lazy.py": """
                def transform(df):
                    from flink_ml_tpu.models.linear import LinearModel
                    return LinearModel
            """,
        },
        rules=["layer-deps"],
    )
    assert [f.line for f in result.findings] == [2]


def test_layer_deps_allows_downward_and_same_layer(tmp_path):
    result = run_on(
        tmp_path,
        {
            "flink_ml_tpu/serving/ok.py": """
                from flink_ml_tpu.checkpoint import scan_numbered_dirs
                from flink_ml_tpu.metrics import metrics
                from flink_ml_tpu.servable.api import load_servable
                import numpy as np
            """,
            "flink_ml_tpu/models/ok.py": """
                from flink_ml_tpu.iteration import Iterations
                from flink_ml_tpu.servable.api import load_servable
            """,
        },
        rules=["layer-deps"],
    )
    assert result.findings == []


def test_layer_deps_module_overrides_beat_package_layer(tmp_path):
    # ops is L1, but ops.optimizer is runtime-coupled (L2): only the latter
    # is forbidden from the servable tier.
    result = run_on(
        tmp_path,
        {
            "flink_ml_tpu/servable/kern.py": """
                from flink_ml_tpu.ops.kernels import compute_dots
                from flink_ml_tpu.ops.optimizer import SGD
            """,
        },
        rules=["layer-deps"],
    )
    (f,) = result.findings
    assert f.line == 2 and "ops.optimizer" in f.message


def test_layer_deps_covers_serving_plan_at_l1(tmp_path):
    """The serving fast path (serving/plan.py) sits at L1: composing servable
    kernel specs and ops kernels is allowed, pulling the runtime/library
    tiers into a fused executable is an upward import."""
    from tools.graftcheck.rules.layer_deps import layer_of

    assert layer_of("serving.plan") == 1
    result = run_on(
        tmp_path,
        {
            "flink_ml_tpu/serving/plan.py": """
                from flink_ml_tpu.servable.kernel_spec import KernelSpec
                from flink_ml_tpu.ops.kernels import scale_fn
                from flink_ml_tpu.models.clustering import KMeansModel
            """,
        },
        rules=["layer-deps"],
    )
    (f,) = result.findings
    assert f.line == 3 and "models" in f.message and "upward" in f.message


def test_layer_deps_flags_unmapped_package(tmp_path):
    result = run_on(
        tmp_path,
        {
            "flink_ml_tpu/linalg/x.py": """
                from flink_ml_tpu.brand_new_pkg import thing
            """,
        },
        rules=["layer-deps"],
    )
    (f,) = result.findings
    assert "not in the layer map" in f.message


def test_servable_shim_contract(tmp_path):
    """Lazy imports of the training stack are seen, file by file."""
    bad = tmp_path / "bad.py"
    bad.write_text(
        "def transform(df):\n"
        "    from flink_ml_tpu.models.linear import compute_dots\n"
        "    import flink_ml_tpu.iteration.datacache as dc\n"
        "    from flink_ml_tpu import builder\n"
        "    return compute_dots\n"
    )
    found = sorted(m for _, m in layer_deps.servable_violations_in_file(str(bad)))
    assert found == [
        "flink_ml_tpu.builder",
        "flink_ml_tpu.iteration.datacache",
        "flink_ml_tpu.models.linear",
    ]


# -----------------------------------------------------------------------------
# 3. jit-purity
# -----------------------------------------------------------------------------

JIT_BAD = """
    import time
    import numpy as np
    import jax
    from functools import partial

    @jax.jit
    def f(x):
        print("tracing")
        t = time.time()
        y = np.asarray(x)
        return x.sum().item() + float(x)

    @partial(jax.jit, static_argnums=0)
    def g(n, x):
        return x * np.random.uniform()

    def wrapped(x):
        print("hi")
        return x

    fast = jax.jit(wrapped)
"""

JIT_CLEAN = """
    import time
    import numpy as np
    import jax
    import jax.numpy as jnp

    started = time.time()          # host code: fine
    print("module import")         # host code: fine

    @jax.jit
    def f(x, key):
        y = jnp.asarray(x)
        noise = jax.random.normal(key, x.shape)
        scale = np.float32(2.0)    # numpy on a static constant: fine
        return y * noise * scale

    def host_helper(arr):
        return float(np.asarray(arr).sum())   # never jitted: fine
"""


def test_jit_purity_flags_host_syncs_and_impurities(tmp_path):
    result = run_on(tmp_path, {"flink_ml_tpu/ops/bad.py": JIT_BAD}, rules=["jit-purity"])
    hits = {(f.line, kind) for f in result.findings for kind in [f.message.split(":")[1].strip().split(" ")[0]]}
    msgs = "\n".join(f.render() for f in result.findings)
    assert any("print()" in f.message for f in result.findings), msgs
    assert any("time.time()" in f.message for f in result.findings), msgs
    assert any("np.asarray(x)" in f.message for f in result.findings), msgs
    assert any(".item()" in f.message for f in result.findings), msgs
    assert any("float(x)" in f.message for f in result.findings), msgs
    assert any("np.random.uniform" in f.message for f in result.findings), msgs
    # the function passed *by name* to jit is also in scope
    assert any("`wrapped`" in f.message for f in result.findings), msgs
    assert len(hits) >= 6


def test_jit_purity_clean_file_and_out_of_scope_package(tmp_path):
    result = run_on(tmp_path, {"flink_ml_tpu/ops/clean.py": JIT_CLEAN}, rules=["jit-purity"])
    assert result.findings == []
    # same bad source outside the scoped packages is out of scope
    result = run_on(tmp_path, {"flink_ml_tpu/utils/elsewhere.py": JIT_BAD}, rules=["jit-purity"])
    assert result.findings == []


def test_jit_purity_covers_servable_and_serving(tmp_path):
    """The serving fast path fuses servable kernel specs into AOT programs,
    so an impure jitted fn in servable/ or serving/ is in scope."""
    for i, rel in enumerate(("flink_ml_tpu/servable/bad.py", "flink_ml_tpu/serving/bad.py")):
        root = tmp_path / f"tree{i}"
        root.mkdir()
        result = run_on(root, {rel: JIT_BAD}, rules=["jit-purity"])
        assert any(".item()" in f.message for f in result.findings), rel


def test_jit_purity_covers_builder(tmp_path):
    """The batch fast path (builder/batch_plan.py) AOT-compiles kernel specs
    per chunk signature — builder/ is in scope."""
    result = run_on(tmp_path, {"flink_ml_tpu/builder/bad.py": JIT_BAD}, rules=["jit-purity"])
    assert any(".item()" in f.message for f in result.findings)


# -----------------------------------------------------------------------------
# 3b. kernel-spec-consistency
# -----------------------------------------------------------------------------

SPEC_CLEAN = """
    from flink_ml_tpu.ops.kernels import binarize_fn, binarize_kernel

    class Binarizerish:
        def transform(self, df):
            return binarize_kernel(0.5)(df)

        def kernel_spec(self):
            def kernel_fn(model, cols):
                return {"out": binarize_fn(cols["in"], 0.5)}
            return object()
"""

SPEC_DRIFT = """
    from flink_ml_tpu.ops.kernels import binarize_kernel, normalize_fn

    class Drifted:
        def transform(self, df):
            return binarize_kernel(0.5)(df)

        def kernel_spec(self):
            def kernel_fn(model, cols):
                return {"out": normalize_fn(cols["in"], 2.0)}
            return object()
"""

SPEC_HANDROLLED = """
    import jax.numpy as jnp

    class HandRolled:
        def transform(self, df):
            return df

        def kernel_spec(self):
            def kernel_fn(model, cols):
                return {"out": jnp.tanh(cols["in"])}
            return object()
"""

SPEC_ALIASED = """
    from flink_ml_tpu.ops.kernels import kmeans_assign_fn, kmeans_predict_kernel

    class KMeansish:
        def transform(self, df):
            return kmeans_predict_kernel("euclidean")(df, df)

        def kernel_spec(self):
            assign = kmeans_assign_fn("euclidean")
            def kernel_fn(model, cols):
                return {"out": assign(cols["in"], model["centroids"])}
            return object()
"""

SPEC_DEFAULT_HOOK = """
    class Base:
        def transform(self, df):
            return df

        def kernel_spec(self):
            return None
"""


def test_kernel_spec_consistency_clean_pairing(tmp_path):
    result = run_on(
        tmp_path,
        {"flink_ml_tpu/models/feature/ok.py": SPEC_CLEAN},
        rules=["kernel-spec-consistency"],
    )
    assert result.findings == []


def test_kernel_spec_consistency_flags_drift(tmp_path):
    result = run_on(
        tmp_path,
        {"flink_ml_tpu/models/feature/drift.py": SPEC_DRIFT},
        rules=["kernel-spec-consistency"],
    )
    assert len(result.findings) == 1
    assert "'normalize'" in result.findings[0].message


def test_kernel_spec_consistency_flags_hand_rolled_math(tmp_path):
    result = run_on(
        tmp_path,
        {"flink_ml_tpu/models/feature/hand.py": SPEC_HANDROLLED},
        rules=["kernel-spec-consistency"],
    )
    assert len(result.findings) == 1
    assert "references no ops/kernels.py body" in result.findings[0].message


def test_kernel_spec_consistency_resolves_fn_factory_aliases(tmp_path):
    """kmeans_predict_kernel jits kmeans_assign_fn — the alias table pairs
    them, so the historical naming does not flag."""
    result = run_on(
        tmp_path,
        {"flink_ml_tpu/models/clustering/km.py": SPEC_ALIASED},
        rules=["kernel-spec-consistency"],
    )
    assert result.findings == []


def test_kernel_spec_consistency_skips_declaration_only_hooks(tmp_path):
    result = run_on(
        tmp_path,
        {"flink_ml_tpu/servable/base.py": SPEC_DEFAULT_HOOK},
        rules=["kernel-spec-consistency"],
    )
    assert result.findings == []


def test_kernel_spec_consistency_shipped_transformers_all_pair():
    """Every shipped kernel_spec composes a body its transform path jits —
    the batch fast path's no-drift guarantee, as a tier-1 gate."""
    project = Project(REPO_ROOT, ["flink_ml_tpu"])
    result = run_rules(project, rules=["kernel-spec-consistency"])
    assert result.findings == [], "\n".join(f.render() for f in result.findings)


SPARSE_SPEC_DRIFT = """
    from flink_ml_tpu.ops.kernels import sparse_combine_kernel, sparse_dot_fn

    class SparseDrifted:
        def transform(self, df):
            return sparse_combine_kernel()(df)

        def sparse_kernel_spec(self, known):
            def kernel_fn(model, cols):
                return {"o": sparse_dot_fn(cols["v"], cols["i"], model["c"])}
            return object()
"""

SPARSE_SPEC_CLEAN = """
    from flink_ml_tpu.ops.kernels import sparse_combine_fn, sparse_combine_kernel

    class SparseCombiner:
        def transform(self, df):
            return sparse_combine_kernel()(df)

        def sparse_kernel_spec(self, known):
            def kernel_fn(model, cols):
                return {"o": sparse_combine_fn(cols["v"], cols["i"], cols["z"])}
            return object()
"""


def test_kernel_spec_consistency_covers_sparse_specs(tmp_path):
    """The sparse convention's ``sparse_kernel_spec`` hook is held to the
    same shared-body contract as ``kernel_spec``: a sparse spec composing a
    segment-reduce body the per-stage path never jits is drift."""
    result = run_on(
        tmp_path,
        {"flink_ml_tpu/models/feature/sdrift.py": SPARSE_SPEC_DRIFT},
        rules=["kernel-spec-consistency"],
    )
    assert len(result.findings) == 1
    assert "'sparse_dot'" in result.findings[0].message
    clean = run_on(
        tmp_path / "clean",
        {"flink_ml_tpu/models/feature/sok.py": SPARSE_SPEC_CLEAN},
        rules=["kernel-spec-consistency"],
    )
    assert clean.findings == []


# -----------------------------------------------------------------------------
# 4. lock-order
# -----------------------------------------------------------------------------

LOCK_CYCLE = """
    import threading

    class A:
        def __init__(self):
            self._lock = threading.Lock()

        def m1(self):
            with self._lock:
                b.m2()

    class B:
        def __init__(self):
            self._lock = threading.Lock()

        def m2(self):
            with self._lock:
                a.m1()

    a = A()
    b = B()
"""

LOCK_SELF_DEADLOCK = """
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()

        def outer(self):
            with self._lock:
                self.inner()

        def inner(self):
            with self._lock:
                return 1
"""

LOCK_CLEAN = """
    import threading

    class Outer:
        def __init__(self):
            self._lock = threading.Lock()
            self._inner = Inner()

        def step(self):
            with self._lock:
                self._inner.bump()

    class Inner:
        def __init__(self):
            self._lock = threading.Lock()

        def bump(self):
            with self._lock:
                return 1
"""


def test_lock_order_detects_synthetic_ab_ba_cycle(tmp_path):
    result = run_on(
        tmp_path, {"flink_ml_tpu/serving/cycle.py": LOCK_CYCLE}, rules=["lock-order"]
    )
    (f,) = result.findings
    assert "cycle" in f.message
    assert "A._lock" in f.message and "B._lock" in f.message


def test_lock_order_detects_self_deadlock(tmp_path):
    result = run_on(
        tmp_path,
        {"flink_ml_tpu/serving/selfdead.py": LOCK_SELF_DEADLOCK},
        rules=["lock-order"],
    )
    (f,) = result.findings
    assert "C._lock -> " in f.message and "C._lock" in f.message


def test_lock_order_consistent_ordering_is_clean(tmp_path):
    result = run_on(
        tmp_path, {"flink_ml_tpu/serving/ordered.py": LOCK_CLEAN}, rules=["lock-order"]
    )
    assert result.findings == []
    graph = lock_order.build_lock_graph(Project(str(tmp_path), ["flink_ml_tpu"]))
    assert (
        "flink_ml_tpu.serving.ordered.Outer._lock",
        "flink_ml_tpu.serving.ordered.Inner._lock",
    ) in graph.edges


def test_lock_order_condition_aliases_its_lock(tmp_path):
    result = run_on(
        tmp_path,
        {
            "flink_ml_tpu/serving/cond.py": """
                import threading

                class D:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self._cond = threading.Condition(self._lock)

                    def wait_then_self_lock(self):
                        with self._cond:
                            self.reenter()

                    def reenter(self):
                        with self._lock:
                            return 1
            """
        },
        rules=["lock-order"],
    )
    # entering the condition IS acquiring _lock -> reenter() self-deadlocks
    (f,) = result.findings
    assert "D._lock" in f.message


# -----------------------------------------------------------------------------
# 5. fault-points
# -----------------------------------------------------------------------------

FAULTS_FIXTURE = {
    "flink_ml_tpu/faults.py": """
        FAULT_POINTS = {
            "demo.tripped": "has a site and a test",
            "demo.dead": "registered, never tripped",
        }

        class _F:
            def trip(self, name, **kw):
                pass

        faults = _F()
    """,
    "flink_ml_tpu/runtime.py": """
        from flink_ml_tpu.faults import faults

        def step():
            faults.trip("demo.tripped")
            faults.trip("demo.typo")
    """,
    "tests/test_demo.py": """
        def test_demo():
            assert "demo.tripped"
    """,
}


def test_fault_points_rule_on_seeded_fixture(tmp_path):
    result = run_on(tmp_path, FAULTS_FIXTURE, rules=["fault-points"])
    msgs = [f.message for f in result.findings]
    assert any("'demo.dead'" in m and "no" in m and "call site" in m for m in msgs)
    assert any("'demo.dead'" in m and "not exercised" in m for m in msgs)
    assert any("'demo.typo'" in m and "unregistered" in m for m in msgs)
    assert not any("'demo.tripped'" in m for m in msgs)
    # the typo finding anchors at its call site
    typo = next(f for f in result.findings if "typo" in f.message)
    assert typo.path == "flink_ml_tpu/runtime.py" and typo.line == 5


def test_fault_points_rule_skips_trees_without_a_registry(tmp_path):
    result = run_on(
        tmp_path, {"flink_ml_tpu/x.py": "VALUE = 1\n"}, rules=["fault-points"]
    )
    assert result.findings == []


# -----------------------------------------------------------------------------
# 6. error-hygiene
# -----------------------------------------------------------------------------

HYGIENE_FIXTURE = """
    def bad_bare():
        try:
            work()
        except:
            return None

    def bad_silent():
        try:
            work()
        except Exception:
            pass

    def ok_narrow():
        try:
            work()
        except (ValueError, TypeError):
            pass

    def ok_handled():
        try:
            work()
        except Exception as e:
            log(e)

    class Holder:
        def __del__(self):
            try:
                self.close()
            except Exception:
                pass
"""


def test_error_hygiene_rule(tmp_path):
    result = run_on(
        tmp_path, {"flink_ml_tpu/utils/h.py": HYGIENE_FIXTURE}, rules=["error-hygiene"]
    )
    assert [(f.line, "bare" in f.message) for f in result.findings] == [
        (4, True),
        (10, False),
    ]


# -----------------------------------------------------------------------------
# 7. framework: suppressions, severities, JSON schema, CLI
# -----------------------------------------------------------------------------


def test_parse_suppressions():
    src = "x = 1\ny = 2  # graftcheck: disable=jit-purity, lock-order\nz = 3  # graftcheck: disable=all\n"
    assert parse_suppressions(src) == {
        2: {"jit-purity", "lock-order"},
        3: {"all"},
    }


def test_suppression_comment_silences_the_finding(tmp_path):
    files = {
        "flink_ml_tpu/serving/sup.py": """
            from flink_ml_tpu.iteration import Iterations  # graftcheck: disable=layer-deps
        """
    }
    result = run_on(tmp_path, files, rules=["layer-deps"])
    assert result.findings == [] and len(result.suppressed) == 1
    assert result.exit_code == 0
    # a different rule's tag would NOT have silenced it
    files2 = {
        "flink_ml_tpu/serving/sup2.py": """
            from flink_ml_tpu.iteration import Iterations  # graftcheck: disable=jit-purity
        """
    }
    result2 = run_on(tmp_path, files2, rules=["layer-deps"])
    assert len(result2.findings) == 1


def test_severity_override_downgrades_exit_code(tmp_path):
    files = {
        "flink_ml_tpu/serving/sev.py": """
            from flink_ml_tpu.iteration import Iterations
        """
    }
    result = run_on(
        tmp_path, files, rules=["layer-deps"], severity_overrides={"layer-deps": "warning"}
    )
    assert len(result.findings) == 1
    assert result.findings[0].severity == "warning"
    assert result.exit_code == 0


def test_unknown_rule_raises():
    with pytest.raises(KeyError):
        run_rules(Project(REPO_ROOT, ["tools/graftcheck/__init__.py"]), rules=["nope"])


def test_json_output_schema(tmp_path):
    files = {
        "flink_ml_tpu/serving/j.py": """
            from flink_ml_tpu.models import linear
        """
    }
    result = run_on(tmp_path, files)
    payload = result.to_json()
    assert payload["version"] == JSON_SCHEMA_VERSION
    assert {r["name"] for r in payload["rules"]} == set(ALL_RULES)
    for rule in payload["rules"]:
        assert set(rule) == {"name", "severity", "granularity", "description"}
        assert rule["severity"] in ("error", "warning")
        assert rule["granularity"] in ("project", "file")
    assert payload["summary"]["files_checked"] >= 1
    assert set(payload["summary"]["rule_times_ms"]) == set(ALL_RULES)
    assert all(t >= 0 for t in payload["summary"]["rule_times_ms"].values())
    assert payload["summary"]["findings"] == len(payload["findings"]) == 1
    assert payload["summary"]["by_rule"] == {"layer-deps": 1}
    (f,) = payload["findings"]
    assert set(f) == {"rule", "path", "line", "message", "severity"}
    assert f["path"] == "flink_ml_tpu/serving/j.py" and f["line"] == 1
    json.dumps(payload)  # round-trippable


def _cli(*args, cwd=REPO_ROOT):
    return subprocess.run(
        [sys.executable, "-m", "tools.graftcheck", *args],
        cwd=cwd,
        capture_output=True,
        text=True,
        timeout=120,
    )


def test_cli_shipped_tree_exits_zero():
    proc = _cli("flink_ml_tpu")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 error(s)" in proc.stdout


def test_cli_seeded_violation_exits_nonzero_with_rule_tags(tmp_path):
    write_tree(
        tmp_path,
        {
            "flink_ml_tpu/serving/bad.py": "from flink_ml_tpu.models import linear\n",
            "flink_ml_tpu/ops/bad.py": JIT_BAD,
        },
    )
    proc = _cli("--root", str(tmp_path), "flink_ml_tpu")
    assert proc.returncode == 1
    assert "[layer-deps]" in proc.stdout and "[jit-purity]" in proc.stdout
    proc_json = _cli("--root", str(tmp_path), "flink_ml_tpu", "--format", "json")
    assert proc_json.returncode == 1
    payload = json.loads(proc_json.stdout)
    assert payload["summary"]["errors"] > 0


def test_cli_list_rules_and_usage_errors():
    proc = _cli("--list-rules")
    assert proc.returncode == 0
    for rule in ALL_RULES:
        assert rule in proc.stdout
    assert _cli("no_such_dir").returncode == 2
    assert _cli("--rules", "bogus", "flink_ml_tpu").returncode == 2


# -----------------------------------------------------------------------------
# fusion-tier: exact partitions never span a reduction; Pallas behind fast only
# -----------------------------------------------------------------------------

FUSION_PLANNER_CLEAN = """
    PLAN_FUSED = "fused"

    def _partition_exact(specs):
        runs, i = [], 0
        while i < len(specs):
            j = i + 1
            if specs[i].elementwise:
                while j < len(specs) and specs[j].elementwise:
                    j += 1
            runs.append((i, j))
            i = j
        return runs

    def _partition_fast(specs):
        return [(0, len(specs))]

    def _fast_megakernels(programs):
        from flink_ml_tpu.servable.megakernels import build_megakernel_fn
        return {0: build_megakernel_fn(programs)}

    class FusedSegment:
        def __init__(self, specs, fusion=None):
            if fusion is not None and fusion.fast:
                self.runs = _partition_fast(specs)
                if fusion.megakernel:
                    self.mega = _fast_megakernels(self.runs)
            else:
                self.runs = _partition_exact(specs)
"""

FUSION_MEGAKERNELS = """
    from jax.experimental import pallas as pl

    def build_megakernel_fn(programs):
        return pl.pallas_call
"""


def test_fusion_tier_clean_fixture_passes(tmp_path):
    result = run_on(
        tmp_path,
        {
            "flink_ml_tpu/servable/planner.py": FUSION_PLANNER_CLEAN,
            "flink_ml_tpu/servable/megakernels.py": FUSION_MEGAKERNELS,
        },
        rules=["fusion-tier"],
    )
    assert result.findings == [], "\n".join(f.render() for f in result.findings)


def test_fusion_tier_flags_pallas_outside_megakernels(tmp_path):
    result = run_on(
        tmp_path,
        {
            "flink_ml_tpu/servable/planner.py": FUSION_PLANNER_CLEAN,
            "flink_ml_tpu/serving/plan.py": """
                from jax.experimental import pallas as pl
            """,
        },
        rules=["fusion-tier"],
    )
    assert len(result.findings) == 1
    assert result.findings[0].path == "flink_ml_tpu/serving/plan.py"
    assert "Pallas import in the plan tier" in result.findings[0].message


def test_fusion_tier_flags_exact_partition_merging_on_fusable(tmp_path):
    dirty = FUSION_PLANNER_CLEAN.replace(
        "if specs[i].elementwise:", "if specs[i].fusable:"
    ).replace(
        "while j < len(specs) and specs[j].elementwise:",
        "while j < len(specs) and specs[j].fusable:",
    )
    result = run_on(
        tmp_path,
        {"flink_ml_tpu/servable/planner.py": dirty},
        rules=["fusion-tier"],
    )
    msgs = [f.message for f in result.findings]
    assert any("never tests .elementwise" in m for m in msgs)
    assert any(".fusable" in m for m in msgs)


def test_fusion_tier_flags_missing_exact_partition(tmp_path):
    result = run_on(
        tmp_path,
        {"flink_ml_tpu/servable/planner.py": "def build(): pass\n"},
        rules=["fusion-tier"],
    )
    assert any("no _partition_exact" in f.message for f in result.findings)


def test_fusion_tier_flags_module_level_megakernel_import(tmp_path):
    dirty = (
        "from flink_ml_tpu.servable.megakernels import build_megakernel_fn\n"
        + textwrap.dedent(FUSION_PLANNER_CLEAN).lstrip("\n")
    )
    result = run_on(
        tmp_path,
        {"flink_ml_tpu/servable/planner.py": dirty},
        rules=["fusion-tier"],
    )
    msgs = [f.message for f in result.findings]
    assert any("import must be function-local" in m for m in msgs)


def test_fusion_tier_flags_unguarded_fast_machinery(tmp_path):
    dirty = FUSION_PLANNER_CLEAN.replace(
        """            if fusion is not None and fusion.fast:
                self.runs = _partition_fast(specs)
                if fusion.megakernel:
                    self.mega = _fast_megakernels(self.runs)
            else:
                self.runs = _partition_exact(specs)""",
        """            self.runs = _partition_fast(specs)
            self.mega = _fast_megakernels(self.runs)""",
    )
    assert "_partition_exact(specs)" not in dirty.split("class FusedSegment")[1]
    result = run_on(
        tmp_path,
        {"flink_ml_tpu/servable/planner.py": dirty},
        rules=["fusion-tier"],
    )
    unguarded = [
        f for f in result.findings if "outside a fusion-fast guard" in f.message
    ]
    assert len(unguarded) == 2  # _partition_fast and _fast_megakernels


def test_fusion_tier_shipped_tree_contract():
    """The real planner satisfies the rule with ZERO suppressions, and the
    real megakernel module is the plan tier's only Pallas user."""
    result = run_rules(Project(REPO_ROOT, ["flink_ml_tpu"]), rules=["fusion-tier"])
    assert result.findings == [], "\n".join(f.render() for f in result.findings)
    assert result.suppressed == []
