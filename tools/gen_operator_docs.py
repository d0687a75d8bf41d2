"""Generate the per-operator reference (docs/operators/, one page per stage).

Reference: the upstream docs site ships one page per operator under
``docs/content/docs/operators/<family>/<op>.md`` (66 markdown files) with
input/output column tables, a parameter table and a runnable example. Here
the pages are *generated from the param registry* — the single source of
truth the stages themselves run on — so the reference can never drift from
the code: ``tests/test_operator_docs.py`` regenerates and diffs on every CI
run, and the inline example code is the same file ``tests/test_examples.py``
executes.

Usage:  python tools/gen_operator_docs.py          # rewrites docs/operators/
        python tools/gen_operator_docs.py --check  # exit 1 if out of date
"""
from __future__ import annotations

import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

FAMILY_TITLES = {
    "classification": "Classification",
    "clustering": "Clustering",
    "evaluation": "Evaluation",
    "feature_stateless": "Feature engineering — stateless transforms",
    "feature_fitted": "Feature engineering — fitted estimators",
    "lm": "Language models",
    "online": "Online (streaming) algorithms",
    "recommendation": "Recommendation",
    "regression": "Regression",
    "stats": "Statistical tests",
}

# Column params naming an output the stage WRITES; all other *Col(s) params
# name inputs it reads. Evaluators are the exception: they consume
# rawPrediction/label columns and emit metric rows, not columns.
_OUTPUT_COL_NAMES = {"predictionCol", "rawPredictionCol", "outputCol", "outputCols"}


def _snake(name: str) -> str:
    return re.sub(r"(?<!^)(?=[A-Z][a-z])|(?<=[a-z0-9])(?=[A-Z])", "_", name).lower()


def _example_for(name: str) -> Path | None:
    want = {_snake(name).replace("_", "") + "example.py"}
    for p in (REPO / "examples").rglob("*_example.py"):
        if p.name.replace("_", "") in want:
            return p
    return None


def _family_of(name: str, dotted: str, cls) -> str:
    if name.startswith("Online"):
        return "online"
    family = dotted.split(".")[2]  # flink_ml_tpu.models.<family>...
    if family != "feature":
        return family
    from flink_ml_tpu.api.core import Estimator

    # fitted = an Estimator (or the Model half of one); stateless = transform-only
    if issubclass(cls, Estimator) or name.endswith("Model"):
        return "feature_fitted"
    return "feature_stateless"


def _fmt_default(v) -> str:
    if v is None:
        return "`null`"  # the reference's convention for optional columns
    if isinstance(v, str):
        return f'`"{v}"`'
    if isinstance(v, float) and (v != v):  # NaN
        return "`NaN`"
    return f"`{v}`"


def _param_rows(params) -> list:
    rows = ["| Param | Default | Description |", "|---|---|---|"]
    for p in params:
        desc = " ".join(p.description.split())
        rows.append(f"| `{p.name}` | {_fmt_default(p.default_value)} | {desc} |")
    return rows


def _stage_page(name: str, dotted: str, cls, model_names: set, family: str) -> str:
    stage = cls()
    doc = (cls.__doc__ or "").strip().splitlines()
    summary = doc[0].strip() if doc else ""
    module_doc = sys.modules[cls.__module__].__doc__ or ""
    ref = ""
    m = re.search(r"Reference:\s*(.+)", module_doc)
    if m:
        ref = m.group(1).strip().rstrip(".").replace("``", "")
        ref = f"`{ref.rstrip('/— ')}`" if ref else ""

    lines = [
        f"# {name}",
        "",
        "<!-- GENERATED by tools/gen_operator_docs.py — edit the stage",
        "     classes / param registry, then regenerate. -->",
        "",
    ]
    if summary:
        lines += [summary, ""]
    if ref:
        lines += [f"*Reference parity:* {ref}", ""]
    lines += ["```python", f"from {cls.__module__} import {name}", "```", ""]

    params = list(stage.get_param_map())
    col_params = [p for p in params if p.name.endswith(("Col", "Cols"))]
    if family == "evaluation":
        in_cols, out_cols = col_params, []
    else:
        in_cols = [p for p in col_params if p.name not in _OUTPUT_COL_NAMES]
        out_cols = [p for p in col_params if p.name in _OUTPUT_COL_NAMES]
    if in_cols:
        lines += ["## Input columns", ""] + _param_rows(in_cols) + [""]
    if out_cols:
        lines += ["## Output columns", ""] + _param_rows(out_cols) + [""]
    elif family == "evaluation":
        lines += [
            "## Output",
            "",
            "One row of metric values (no output columns are added to the "
            "input table).",
            "",
        ]
    other = [p for p in params if p not in col_params]
    if other:
        lines += ["## Parameters", ""] + _param_rows(other) + [""]

    ex = _example_for(name)
    if ex is not None:
        rel = Path("../../..") / ex.relative_to(REPO)
        lines += [
            "## Example",
            "",
            f"Runnable as [`examples/{ex.relative_to(REPO / 'examples').as_posix()}`]"
            f"({rel.as_posix()}) (executed by `tests/test_examples.py` on "
            "every CI run):",
            "",
            "```python",
            ex.read_text().rstrip(),
            "```",
            "",
        ]
    if name + "Model" in model_names:
        lines += [
            f"`fit` returns a `{name}Model` (same column params; "
            "save/load via `utils.read_write.load_stage`).",
            "",
        ]
    return "\n".join(lines)


def generate() -> dict:
    from flink_ml_tpu.models import STAGE_REGISTRY, get_stage_class

    model_names = set(STAGE_REGISTRY)
    families: dict = {k: [] for k in FAMILY_TITLES}  # fam -> [(name, relpath)]
    pages: dict = {}
    for name in sorted(STAGE_REGISTRY):
        if name.endswith("Model") and name[: -len("Model")] in STAGE_REGISTRY:
            continue  # documented with its estimator
        cls = get_stage_class(name)
        fam = _family_of(name, STAGE_REGISTRY[name], cls)
        relpath = f"{fam}/{_snake(name)}.md"
        pages[relpath] = _stage_page(name, STAGE_REGISTRY[name], cls, model_names, fam)
        families[fam].append((name, f"{_snake(name)}.md"))

    total = 0
    for fam, entries in families.items():
        total += len(entries)
        body = [
            f"# {FAMILY_TITLES[fam]}",
            "",
            "<!-- GENERATED by tools/gen_operator_docs.py -->",
            "",
            f"{len(entries)} operators. Every stage follows the Estimator/AlgoOperator",
            "contract: params via `set_*`/`get_*`, training via `fit(df)`,",
            "application via `transform(df)`, persistence via `save(path)` /",
            "`load_stage(path)`.",
            "",
        ]
        for name, link in entries:
            body.append(f"- [{name}]({link})")
        body.append("")
        pages[f"{fam}/README.md"] = "\n".join(body)

    index = [
        "# Operator reference",
        "",
        "<!-- GENERATED by tools/gen_operator_docs.py -->",
        "",
        f"{total} user-facing operators across {len(families)} families, one page",
        "per operator, generated from the live param registry "
        "(`flink_ml_tpu.models.STAGE_REGISTRY`).",
        "",
    ]
    for fam in sorted(families):
        index.append(
            f"- [{FAMILY_TITLES[fam]}]({fam}/README.md) — "
            f"{len(families[fam])} operators"
        )
    index.append("")
    pages["README.md"] = "\n".join(index)
    return pages


def main() -> int:
    out_dir = REPO / "docs" / "operators"
    pages = generate()
    if "--check" in sys.argv:
        stale = []
        for fname, body in pages.items():
            p = out_dir / fname
            if not p.exists() or p.read_text() != body:
                stale.append(fname)
        extra = {
            p.relative_to(out_dir).as_posix() for p in out_dir.rglob("*.md")
        } - set(pages)
        if stale or extra:
            print(f"out of date: {stale + sorted(extra)}; run tools/gen_operator_docs.py")
            return 1
        print(f"docs/operators/ up to date ({len(pages)} pages)")
        return 0
    # clean out the previous layout so orphan pages never linger
    if out_dir.exists():
        for p in out_dir.rglob("*.md"):
            p.unlink()
    for fname, body in pages.items():
        p = out_dir / fname
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(body)
    print(f"wrote {len(pages)} pages to {out_dir}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
