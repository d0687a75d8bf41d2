"""layer-deps: declarative layer map over ``flink_ml_tpu``; no upward imports.

The reference Flink ML encodes its discipline in Maven module boundaries
(``flink-ml-servable-core`` cannot see ``flink-ml-lib``); a single Python
package has no compiler-enforced equivalent, so this rule carries the layer
map explicitly:

    L0 foundation          config, utils, faults, metrics, native
    L1 compute / servable  linalg, params, api, ops, checkpoint, parallel,
                           servable, serving, trace
    L2 runtime             iteration, execution, builder
    L3 library             models, benchmark, loop, loadgen, the root package

A module may import same-layer or lower — importing *up* is the violation
(a servable-tier file importing the runtime, a kernel importing a model).
Three modules live at a different layer than their package (``MODULE_LAYERS``):
``ops.optimizer`` / ``native.cache`` / ``parallel.datastream_utils`` are
runtime-coupled (they import the iteration tier) and sit at L2, which is why
``ops/kernels.py`` — not ``ops/optimizer.py`` — is what the servable tier may
use. ``serving.plan`` (the compiled fast path) deliberately sits at the
package's L1: it composes ``servable`` kernel specs and ``ops/kernels.py``
``*_fn`` bodies only, so the runtime-free guarantee covers the fused
executables too. Imports *within* one top-level subpackage are not layered (a
package's internal structure is its own business), and an import of an
unmapped ``flink_ml_tpu`` subpackage is itself a finding so the map cannot
silently rot.

The L1 runtime-free guarantee (servable/serving never import iteration /
execution / builder / models, even lazily) is the
``layer(servable)=1 < layer(runtime)`` special case of this rule.
:func:`servable_violations_in_file` and :func:`servable_check` state it file
by file for ``tests/test_servable_imports.py``.
"""
from __future__ import annotations

import ast
import os
from typing import Iterable, List, Optional, Tuple

from tools.graftcheck.engine import Finding, Project, Rule, SourceFile, register

ROOT_PACKAGE = "flink_ml_tpu"

LAYER_NAMES = {0: "foundation", 1: "compute/servable", 2: "runtime", 3: "library"}

#: Layer of each top-level subpackage (or root-level module) of ROOT_PACKAGE.
PACKAGE_LAYERS = {
    "config": 0,
    "utils": 0,
    "faults": 0,
    "metrics": 0,
    "native": 0,
    "linalg": 1,
    "params": 1,
    "api": 1,
    "ops": 1,
    "checkpoint": 1,
    "parallel": 1,
    "servable": 1,
    "serving": 1,
    # graftscope tracing: consumed by every tier including the L1 serving
    # fast path, so it sits at L1 itself and only imports L0 (config,
    # metrics) — the runtime-free guarantee covers instrumented servables.
    "trace": 1,
    # The always-on flight recorder (journal / incidents / HTTP endpoint):
    # instrumented by the serving tier and the fast-path planners, so it
    # sits at L1 like trace and imports only L0 (config, faults, metrics)
    # plus trace itself. The L0 faults module reaches it through its
    # observer hook — never by importing upward.
    "telemetry": 1,
    "iteration": 2,
    "execution": 2,
    "builder": 2,
    "models": 3,
    "benchmark": 3,
    # The open-loop load harness drives the serving tier from the outside
    # (schedules, offered-load ramps, chaos accounting) — a measurement rig
    # over L1, not a dependency of it, so it sits at the library layer like
    # benchmark; nothing below may import it.
    "loadgen": 3,
    # The continuous-learning loop composes the serving tier's publish/swap
    # machinery WITH the model library's online estimators and the execution
    # supervisor, so it sits above all of them at the library layer — the
    # serving-tier pieces it drives (registry, poller, fast path) stay at L1
    # and keep their runtime-free guarantee; the loop is the one place the
    # two halves are allowed to meet (docs/continuous.md).
    "loop": 3,
    # Fleet serving composes L1 serving replicas with the L2 execution
    # supervisor's restart strategies and the L3 loop's drift/rollback
    # machinery (canary verdicts), so it sits at the library layer with
    # loop/loadgen — a single replica never knows it is part of a fleet,
    # and nothing below L3 may import the fleet tier (docs/fleet.md).
    "fleet": 3,
    # The retrieval tier (CandidateIndex + RetrievalClient) sits at the
    # library layer with models/fleet, but by contract imports only L0/L1
    # (api, linalg, params, servable, utils) — a published index loads in a
    # serving process with no training stack present (docs/retrieval.md).
    "retrieval": 3,
    # the root package surface (flink_ml_tpu/__init__.py) re-exports the API
    "": 3,
}

#: Module-granular overrides (longest prefix wins over PACKAGE_LAYERS).
MODULE_LAYERS = {
    "ops.optimizer": 2,  # fused trainers: imports iteration at module level
    "native.cache": 2,  # native-backed datacache: reaches into iteration.datacache
    "parallel.datastream_utils": 2,  # external sort / co-group over HostDataCache
    # The batch fast path sits at builder's own L2 but only consumes L0/L1
    # (servable.planner + kernel specs, api, config, metrics) — registered
    # explicitly so the fused batch tier's dependency story is auditable.
    "builder.batch_plan": 2,
    # Mesh placement for compiled plans (pod-scale fan-out): L1 like the
    # rest of servable — it may import parallel.mesh (same layer) but stays
    # inside the runtime-free guarantee; registered explicitly so the
    # sharded fast paths' dependency story is auditable.
    "servable.sharding": 1,
    # The persistent compiled-plan cache: L1 like the rest of servable — it
    # imports only L0 (config, faults, metrics) plus telemetry (same layer),
    # so the runtime-free guarantee covers cache-served executables too.
    # Its load/store surfaces are `# graftcheck: cold` and the host-sync
    # rule's file-I/O scope proves no hot root can reach cache disk I/O.
    "servable.plancache": 1,
    # The runtime-free retrieval serving heads (top-K over a published
    # CandidateIndex): L1 like the rest of servable — they import only L0
    # plus same-layer servable/ops/api/linalg/params modules. Registered
    # explicitly because the training-side models/feature/lsh.py imports
    # HASH_PRIME *from* here (L3 → L1, allowed), never the reverse.
    "servable.retrieval": 1,
    # Training-side mesh placement (the TrainSharding companion of
    # servable.sharding): L1 like the rest of parallel — it imports only L0
    # (config lazily, metrics) plus same-package mesh/collectives, and the
    # trainers that consume it (ops.optimizer L2, models L3) import DOWN into
    # it. Registered explicitly so the deterministic training tier's
    # dependency story is auditable next to its serving twin.
    "parallel.train_sharding": 1,
}

#: The runtime-free slice of the layer map (see module docstring).
RUNTIME_FREE_PACKAGES = ("flink_ml_tpu/servable", "flink_ml_tpu/serving")
FORBIDDEN_PREFIXES = (
    "flink_ml_tpu.iteration",
    "flink_ml_tpu.execution",
    "flink_ml_tpu.builder",
    "flink_ml_tpu.models",
)


def layer_of(subpath: str) -> Optional[int]:
    """Layer of a dotted path under ROOT_PACKAGE ('' = the root package).
    None when the first component is not in the map."""
    if subpath in MODULE_LAYERS:
        return MODULE_LAYERS[subpath]
    return PACKAGE_LAYERS.get(subpath.split(".", 1)[0] if subpath else "")


def iter_imports(sf: SourceFile) -> Iterable[Tuple[int, str]]:
    """Yield (lineno, absolute dotted module) for every import in ``sf``,
    with relative imports resolved against the file's module path and
    ``from pkg import sub`` expanded to ``pkg.sub`` (the importing code
    cannot know statically whether ``sub`` is a module or a symbol; for
    layering the longer path is looked up first and falls back)."""
    is_init = sf.rel.endswith("/__init__.py")
    parts = sf.module.split(".")
    package = parts if is_init else parts[:-1]
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield node.lineno, alias.name
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                base = package[: len(package) - (node.level - 1)]
                mod = ".".join(base + ([node.module] if node.module else []))
            else:
                mod = node.module or ""
            if not mod:
                continue
            yield node.lineno, mod
            for alias in node.names:
                yield node.lineno, f"{mod}.{alias.name}"


def _subpath(module: str) -> Optional[str]:
    if module == ROOT_PACKAGE:
        return ""
    if module.startswith(ROOT_PACKAGE + "."):
        return module[len(ROOT_PACKAGE) + 1 :]
    return None


@register
class LayerDepsRule(Rule):
    name = "layer-deps"
    severity = "error"
    granularity = "file"
    cache_version = 7  # v7: training-sharding tier registered (parallel.train_sharding L1)
    description = (
        "imports within flink_ml_tpu must not point at a higher layer "
        "(foundation < compute/servable < runtime < library)"
    )

    def check_file(self, project: Project, sf: SourceFile) -> List[Finding]:
        findings: List[Finding] = []
        if not sf.rel.startswith(ROOT_PACKAGE + "/"):
            return findings
        facts = project.facts().get(sf.rel)
        if facts is None:
            return findings
        src_sub = _subpath(sf.module)
        if src_sub is not None:
            src_layer = layer_of(src_sub)
            if src_layer is None:
                findings.append(
                    self.finding(
                        sf.rel,
                        1,
                        f"module {sf.module} is not in the layer map — add its "
                        "top-level package to PACKAGE_LAYERS",
                    )
                )
                return findings
            seen = set()
            for lineno, module in facts["imports"]:
                dst_sub = _subpath(module)
                if dst_sub is None:
                    continue  # stdlib / third-party
                # Intra-package imports are the package's own structure.
                if dst_sub and src_sub and dst_sub.split(".")[0] == src_sub.split(".")[0]:
                    continue
                dst_layer = layer_of(dst_sub)
                if dst_layer is None:
                    # ``from pkg import symbol`` expansion of an unmapped name:
                    # only report genuinely unmapped *packages*.
                    if layer_of(dst_sub.split(".", 1)[0]) is None and (lineno, dst_sub.split(".")[0]) not in seen:
                        seen.add((lineno, dst_sub.split(".")[0]))
                        findings.append(
                            self.finding(
                                sf.rel,
                                lineno,
                                f"import of {module} — not in the layer map; add it "
                                "to PACKAGE_LAYERS",
                            )
                        )
                    continue
                already = any(
                    ln == lineno and (dst_sub == flagged or dst_sub.startswith(flagged + "."))
                    for ln, flagged in seen
                )
                if dst_layer > src_layer and not already:
                    seen.add((lineno, dst_sub))
                    findings.append(
                        self.finding(
                            sf.rel,
                            lineno,
                            f"{sf.module} (L{src_layer} {LAYER_NAMES[src_layer]}) imports "
                            f"{ROOT_PACKAGE}.{dst_sub} (L{dst_layer} {LAYER_NAMES[dst_layer]}) "
                            "— upward imports break the layer discipline",
                        )
                    )
        return findings


# -- the runtime-free guarantee, file by file ---------------------------------


def _forbidden(module: str) -> bool:
    return any(module == p or module.startswith(p + ".") for p in FORBIDDEN_PREFIXES)


def servable_violations_in_file(path: str) -> Iterable[Tuple[int, str]]:
    """(lineno, module) for every import of a training-stack root, lazy
    (function-local) imports included; relative imports skipped (the servable
    tier has no runtime subpackages)."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if _forbidden(alias.name):
                    yield node.lineno, alias.name
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                continue
            module = node.module or ""
            if _forbidden(module):
                yield node.lineno, module
            elif module == ROOT_PACKAGE:
                for alias in node.names:
                    if _forbidden(f"{ROOT_PACKAGE}.{alias.name}"):
                        yield node.lineno, f"{ROOT_PACKAGE}.{alias.name}"


def servable_check(repo_root: str) -> Tuple[List[str], List[str]]:
    """(problems, checked_files) over the runtime-free packages; an empty
    problems list means pass."""
    problems: List[str] = []
    checked: List[str] = []
    for package in RUNTIME_FREE_PACKAGES:
        pkg_dir = os.path.join(repo_root, package)
        for dirpath, _, filenames in os.walk(pkg_dir):
            for name in sorted(filenames):
                if not name.endswith(".py"):
                    continue
                path = os.path.join(dirpath, name)
                rel = os.path.relpath(path, repo_root)
                checked.append(rel)
                for lineno, module in servable_violations_in_file(path):
                    problems.append(
                        f"{rel}:{lineno} imports {module} — the serving tier "
                        "must not depend on the training stack (L1 "
                        "runtime-free guarantee)"
                    )
    if not checked:
        problems.append("no files checked — package layout changed?")
    return problems, checked
