"""Every file a document names exists.

The README and the pages under ``docs/`` are what a new owner reads first; a
page that cites a deleted harness or record file sends them to numbers that
are not there. One case per document: each backticked token that names a file
by its path from the root (``tools/traceview.py``, ``PERF.md``) and each
relative Markdown link target must exist in the tree.

Not cases: the historical records (``CHANGES.md``, ``ROADMAP.md``, ``PERF.md``,
``VERDICT.md``, ``ISSUE.md``), which name deleted files on purpose.
"""
import glob
import os
import re

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = sorted(
    ["README.md", "BASELINE.md", "docs/operators/README.md", "examples/README.md"]
    + [
        os.path.relpath(p, REPO_ROOT)
        for p in glob.glob(os.path.join(REPO_ROOT, "docs", "*.md"))
    ]
)

_EXTENSIONS = (".py", ".json", ".md", ".sh", ".cpp")
_EXT = "(?:" + "|".join(map(re.escape, _EXTENSIONS)) + ")"
#: ``tools/traceview.py``: a path from the root of the checkout.
_ROOT_PATH = re.compile(
    rf"(?:flink_ml_tpu|tools|tests|perfbench|examples|docs|bin)/[\w./-]+{_EXT}"
)
#: ``PERF.md``, ``BASELINE.json``: a record kept at the root.
_ROOT_RECORD = re.compile(r"[A-Z][A-Za-z0-9_]*\.(?:md|json)")
#: Capitalised, but a file of a saved stage's directory (docs/persistence.md).
_NOT_ROOT_RECORDS = {"META.json"}
#: ``::test_name``, ``:120`` or ``:39-47`` after a path.
_SUFFIX = re.compile(r"(?:::[\w:.\[\]-]+|:\d+(?:-\d+)?)$")
_BACKTICKED = re.compile(r"`([^`\n]+)`")
_LINK_TARGET = re.compile(r"\]\(([^)\s]+)\)")


def _named_files(document):
    """``(token, path from the root)`` for every file the document names."""
    with open(os.path.join(REPO_ROOT, document), encoding="utf-8") as f:
        text = f.read()
    for token in _BACKTICKED.findall(text):
        if any(c in token for c in "<*… "):
            continue  # a pattern or a phrase, not one file
        path = _SUFFIX.sub("", token)
        if _ROOT_PATH.fullmatch(path) or (
            _ROOT_RECORD.fullmatch(path) and path not in _NOT_ROOT_RECORDS
        ):
            yield token, path
    for target in _LINK_TARGET.findall(text):
        target = target.split("#", 1)[0]
        if "://" in target or not target.endswith(_EXTENSIONS):
            continue
        yield target, os.path.normpath(
            os.path.join(os.path.dirname(document), target)
        )


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_named_file_exists(document):
    missing = sorted(
        {
            token
            for token, path in _named_files(document)
            if not os.path.exists(os.path.join(REPO_ROOT, path))
        }
    )
    assert not missing, f"{document} names files that do not exist: {missing}"
