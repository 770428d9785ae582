"""The documents name files that exist.

One case per document: every back-quoted token that ends in ``.py``,
``.json``, ``.md`` or ``.sh`` names a file of the checkout. History
(``ROADMAP.md``, ``CHANGES.md``, ``PERF.md`` from §6 on) is not
checked: it may name what a PR deleted."""

import os
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent

#: where a token with a ``/`` may be rooted
BASES = ("", "predictionio_tpu/", "docs/", "benchmarks/", "tests/")

#: directories that hold nothing git would commit
PRUNED = {".git", "__pycache__", ".pytest_cache", ".jax_cache",
          ".chip_smoke", "chip_scratch", "chiprun_out", ".cache",
          ".hypothesis"}

#: files that a user of ``pio`` or a run creates, not the repository
#: (lower-case bare names such as ``engine.json``, ``best.json`` and
#: ``pio-env.sh`` are never checked)
CREATED = {
    "TRAIN_REPORT.json": "written by `pio train --profile`",
    "conf/pio-env.sh": "the operator's copy of conf/pio-env.sh.template",
}

_SPAN = re.compile(r"`([^`\n]+)`")
_SUFFIX = (".py", ".json", ".md", ".sh")
_SKIP = ("*", "<", "{", "NN", "…")


def _documents():
    docs = [ROOT / "README.md", ROOT / "PERF.md"]
    docs += sorted((ROOT / "docs").glob("*.md"))
    return docs


@pytest.fixture(scope="module")
def checkout():
    """Every file of the checkout by its path from the root, and the
    basenames: walked once for all the documents."""
    files = set()
    for top, dirs, names in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in PRUNED]
        rel = os.path.relpath(top, ROOT)
        for name in names:
            files.add(name if rel == "." else f"{rel}/{name}")
    return files, {f.rsplit("/", 1)[-1] for f in files}


def _text(doc: Path) -> str:
    text = doc.read_text()
    if doc.name == "PERF.md":          # §6 and §7 are history
        text = text[:text.index("\n## 6.")]
    return text


def _tokens(text: str):
    for span in _SPAN.findall(text):
        words = span.split()
        if not words:
            continue
        token = words[-1].split("::")[0]             # a pytest node id
        if token.startswith("/") or "://" in token:  # a route, a URL
            continue
        token = re.sub(r":[\d,\-–]+$", "", token)     # a trailing :line
        token = token.rsplit(":", 1)[-1]             # git's <rev>:<path>
        if token.endswith(_SUFFIX) and not any(s in token for s in _SKIP):
            yield token


def _missing(token: str, files: set, basenames: set) -> bool:
    if token in CREATED:
        return False
    if "/" in token:
        return not any(base + token in files for base in BASES)
    if token.endswith((".py", ".md")):
        return token not in basenames
    # a bare .json / .sh: only the root's records, which start with a capital
    return token[0].isupper() and token not in files


@pytest.mark.parametrize("doc", _documents(),
                         ids=lambda d: str(d.relative_to(ROOT)))
def test_document_names_files_that_exist(doc, checkout):
    files, basenames = checkout
    missing = sorted({t for t in _tokens(_text(doc))
                      if _missing(t, files, basenames)})
    assert not missing, f"{doc.name} names files that do not exist: {missing}"
