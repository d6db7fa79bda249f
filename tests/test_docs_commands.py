"""The documents name only what the tree holds.

One case a document (README.md, PERF.md, ROADMAP.md, docs/*.md): every
repo path it names in a code span or a fenced block exists, and it names
no environment variable that starts with BENCH_ (the deleted bench
script's family).  tools/check_env_docs.py checks the names of the
variables the library reads; nothing else checked the commands and the
paths a document sends an operator to.
"""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md", "PERF.md", "ROADMAP.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))

#: Top-level directories whose paths a document may cite.
ROOTS = ("tools", "tests", "byteps_tpu", "benchmark", "example")

_FENCE = re.compile(r"^```.*?$(.*?)^```\s*$", re.M | re.S)
_SPAN = re.compile(r"`([^`\n]+)`")
# A path under one of ROOTS, not the tail of a longer path or URL.
_PATH = re.compile(r"(?<![\w/.:-])((?:%s)/[^\s`'\"(),;]*)" % "|".join(ROOTS))
_SCRIPT = re.compile(r"\bpython3?\s+([\w./-]+\.py)\b")
_MODULE = re.compile(r"\bpython3?\s+-m\s+([\w.]+)")
_BENCH_VAR = re.compile(r"(?<![A-Za-z0-9_])BENCH_")


def code_of(text: str):
    """The text of every fenced block and every code span."""
    fenced = _FENCE.findall(text)
    return fenced + _SPAN.findall(_FENCE.sub("", text))


def resolves(token: str) -> bool:
    """Does a cited path exist?  `file.py:12-30` and `file.py::test` cite
    the file; a `*` is a glob that must match; a `<placeholder>` or
    `{a,b}` cites the directory it stands in."""
    token = re.split(r"::|:\d", token)[0].rstrip(".:")
    cut = re.search(r"[<{\[…]|\.\.\.", token)
    if cut:
        token = os.path.dirname(token[:cut.start()])
    full = os.path.join(REPO, token)
    return bool(glob.glob(full)) if "*" in token else os.path.exists(full)


def module_resolves(name: str) -> bool:
    parts = name.split(".")
    if parts[0] not in ROOTS:
        return True            # pytest, http.server, ...: not this repo's
    base = os.path.join(REPO, *parts)
    return os.path.isfile(base + ".py") or os.path.isdir(base)


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_what_exists(doc):
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    missing = []
    for code in code_of(text):
        missing += [p for p in _PATH.findall(code) if not resolves(p)]
        missing += [p for p in _SCRIPT.findall(code)
                    if not os.path.isfile(os.path.join(REPO, p))]
        missing += ["-m " + m for m in _MODULE.findall(code)
                    if not module_resolves(m)]
    assert not missing, f"{doc} names what the tree does not hold: {missing}"
    assert not _BENCH_VAR.search(text), \
        f"{doc} names variables of the deleted bench script"
