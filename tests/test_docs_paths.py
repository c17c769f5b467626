"""The documents name files that exist: every back-ticked token in
``README.md`` and each ``docs/*.md`` that reads as a file name or path
(``.py``, ``.md``, ``.json``, ``.sh``, ``.toml``) is the tail of the path
of a file in the tree. The documents write ``vgg.py`` or
``twin/engine.py`` beside the directory they speak of, so a tail is
enough; a name no file ends with is a document behind the tree.
History files (CHANGES.md, ROADMAP.md, PERF.md) are not cases."""

import functools
import os
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
SKIP_DIRS = {".git", "__pycache__", ".jax_cache", ".pytest_cache",
             ".hypothesis", "chiprun_out", ".chip_checkout", ".probe_cache"}
TOKEN = re.compile(r"`([A-Za-z][A-Za-z0-9_\-./]*\.(?:py|md|json|sh|toml))`")
DOCUMENTS = ["README.md"] + sorted(
    f"docs/{p.name}" for p in (REPO / "docs").glob("*.md"))


@functools.lru_cache(maxsize=None)
def _tree_files():
    """Every file's path from the root, '/'-separated, by a walk (the
    tests may run in a copy with no ``.git``)."""
    out = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in SKIP_DIRS]
        rel = os.path.relpath(root, REPO).replace(os.sep, "/")
        out.extend(f if rel == "." else f"{rel}/{f}" for f in files)
    return out


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_file_a_document_names_exists(document):
    files = _tree_files()
    named = sorted(set(TOKEN.findall((REPO / document).read_text())))
    missing = [t for t in named
               if not any(f == t or f.endswith("/" + t) for f in files)]
    assert missing == [], f"{document} names files the tree does not hold"
