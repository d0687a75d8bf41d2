"""fault-points: injection seams must stay tripped and tested.

For every point in ``flink_ml_tpu.faults.FAULT_POINTS``:

1. the runtime has at least one ``faults.trip("<name>", ...)`` call site under
   ``flink_ml_tpu/`` (a registered point nobody trips is dead),
2. at least one test under ``tests/`` names the point (recovery paths CI never
   exercises are recovery paths that don't work),

and conversely every ``faults.trip(...)`` site names a registered point (a
typo'd name would only raise LookupError when reached). Trip sites are found
by AST (``faults.trip`` / bare ``trip`` imported from the faults module, first
argument a string literal); the test sweep is a substring scan because tests
arm points through several helpers (``faults.arm``, markers, config strings).
"""
from __future__ import annotations

import ast
import importlib.util
import os
from typing import Dict, List, Tuple

from tools.graftcheck.engine import Finding, Project, Rule, register

FAULTS_MODULE_REL = "flink_ml_tpu/faults.py"


def _load_fault_points(repo_root: str) -> Dict:
    """FAULT_POINTS from ``<repo_root>/flink_ml_tpu/faults.py`` — always the
    analyzed tree's own file, never a ``flink_ml_tpu`` that happens to be
    importable, so fixture trees are analyzed against their own registry."""
    path = os.path.join(repo_root, FAULTS_MODULE_REL)
    spec = importlib.util.spec_from_file_location("_graftcheck_faults", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.FAULT_POINTS


def _trip_name(node: ast.Call) -> str | None:
    func = node.func
    is_trip = (
        isinstance(func, ast.Attribute)
        and func.attr == "trip"
        and isinstance(func.value, ast.Name)
        and func.value.id == "faults"
    ) or (isinstance(func, ast.Name) and func.id == "trip")
    if is_trip and node.args and isinstance(node.args[0], ast.Constant):
        if isinstance(node.args[0].value, str):
            return node.args[0].value
    return None


def analyze(project: Project) -> Tuple[List[Tuple[str, str, int]], Dict[str, List[str]], set]:
    """(problems, trip_sites, tested). Problems are (message, rel, line).
    Trip sites come from the shared index facts (``facts["trip_sites"]``), so
    a cache-warm run discovers them without re-parsing a single file."""
    fault_points = _load_fault_points(project.repo_root)
    faults_sf = project.file(FAULTS_MODULE_REL)

    trip_sites: Dict[str, List[str]] = {}
    site_lines: Dict[str, Tuple[str, int]] = {}
    all_facts = project.facts()
    for sf in project.iter_files("flink_ml_tpu/"):
        if sf.rel == FAULTS_MODULE_REL:
            continue  # the framework itself (docstrings mention trip("<name>"))
        for point, lineno in all_facts.get(sf.rel, {}).get("trip_sites", []):
            trip_sites.setdefault(point, []).append(sf.rel)
            site_lines.setdefault(point, (sf.rel, lineno))

    tested = set()
    test_root = os.path.join(project.repo_root, "tests")
    for dirpath, _, filenames in os.walk(test_root):
        for name in sorted(filenames):
            if not name.endswith(".py"):
                continue
            with open(os.path.join(dirpath, name), encoding="utf-8") as f:
                content = f.read()
            for point in fault_points:
                if point in content:
                    tested.add(point)

    def registry_line(point: str) -> int:
        if faults_sf is not None:
            for lineno, line in enumerate(faults_sf.source.splitlines(), start=1):
                if f'"{point}"' in line or f"'{point}'" in line:
                    return lineno
        return 1

    problems: List[Tuple[str, str, int]] = []
    for point in sorted(fault_points):
        if point not in trip_sites:
            problems.append(
                (
                    f"fault point {point!r} is registered but has no "
                    "faults.trip() call site under flink_ml_tpu/",
                    FAULTS_MODULE_REL,
                    registry_line(point),
                )
            )
        if point not in tested:
            problems.append(
                (
                    f"fault point {point!r} is not exercised by any test under "
                    "tests/ — its recovery path is unproven",
                    FAULTS_MODULE_REL,
                    registry_line(point),
                )
            )
    for point in sorted(trip_sites):
        if point not in fault_points:
            rel, line = site_lines[point]
            problems.append(
                (
                    f"faults.trip({point!r}) at {trip_sites[point]} names an "
                    "unregistered fault point (typo?)",
                    rel,
                    line,
                )
            )
    return problems, trip_sites, tested


def check(repo_root: str) -> Tuple[List[str], Dict[str, List[str]]]:
    """``(problems, trip_sites)`` with the problems as plain messages — what
    ``tests/test_fault_points.py`` asserts on; an empty list means pass."""
    project = Project(repo_root, ["flink_ml_tpu"])
    problems, trip_sites, _ = analyze(project)
    return [p[0] for p in problems], trip_sites


@register
class FaultPointsRule(Rule):
    name = "fault-points"
    severity = "error"
    description = (
        "every registered fault point has a runtime trip site and a test; "
        "every trip site names a registered point"
    )

    def run(self, project: Project) -> List[Finding]:
        if project.file(FAULTS_MODULE_REL) is None:
            return []  # fixture trees without the faults registry: nothing to check
        problems, _, _ = analyze(project)
        return [self.finding(rel, line, msg) for msg, rel, line in problems]
