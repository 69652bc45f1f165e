"""The documents name only what is in the tree.

One case per document. In each, every backticked word that is a
repo-relative path (to a `.py`, `.cpp`, `.md`, `.json` or `.jsonl`
file, or ending in `/`) must exist, and every `--flag` in backticks
or in a code block must be an option of one of the tree's own command
lines. There is no allow-list: a document that has to name something
absent (a history, a reader's own file) does so without backticks.
`PERF.md`, `ROADMAP.md`, `CHANGES.md` and `ISSUE.md` are histories and
are not cases.
"""

import ast
import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DOCUMENTS = ["README.md", "BASELINE.md", "benchmarks/hbm_model.md"] + sorted(
    os.path.relpath(path, REPO)
    for pattern in ("docs/*.md", "docs/guides/*.md", "docs/server/*.md", "docs/tpu/*.md")
    for path in glob.glob(os.path.join(REPO, pattern))
)

# the command lines a document may give options of; read from the
# source, so nothing here imports jax or the benchmark
COMMAND_LINES = [
    "hocuspocus_tpu/cli.py",
    "hocuspocus_tpu/loadgen/__main__.py",
    "chip_smoke.py",
    "bench/run.py",
]

_FENCE = re.compile(r"^(```|~~~).*?^\1[ \t]*$", re.S | re.M)
_BACKTICKED = re.compile(r"`([^`\n]+)`")
_FLAG = re.compile(r"(?<![\w-])--[a-z][a-z0-9_-]*")
_PATH = re.compile(r"^[\w.<>*-][\w./<>*-]*(\.(py|cpp|md|json|jsonl)|/)$")
_LINE_SUFFIX = re.compile(r"(::[\w:\[\]-]+|:\d+(-\d+)?)$")


@functools.cache
def _known_options():
    return {
        arg.value
        for path in COMMAND_LINES
        for node in ast.walk(ast.parse(open(os.path.join(REPO, path)).read()))
        if isinstance(node, ast.Call) and getattr(node.func, "attr", "") == "add_argument"
        for arg in node.args
        if isinstance(arg, ast.Constant) and str(arg.value).startswith("--")
    }


def _exists(word, document):
    """A path counts from the root, from the package, or from the
    document's own directory; `*` and `<placeholder>` stand for any
    name, and at least one file has to be there."""
    pattern = re.sub(r"<[^>]*>", "*", word)
    bases = (REPO, os.path.join(REPO, "hocuspocus_tpu"), os.path.dirname(os.path.join(REPO, document)))
    return any(glob.glob(os.path.join(base, pattern)) for base in bases)


def _read(document):
    text = open(os.path.join(REPO, document)).read()
    blocks = [match.group(0) for match in _FENCE.finditer(text)]
    spans = _BACKTICKED.findall(_FENCE.sub("", text))
    return blocks, spans


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_what_exists(document):
    blocks, spans = _read(document)
    missing = []
    for span in spans:
        for word in span.split():
            word = _LINE_SUFFIX.sub("", word.strip("()[],;:'\""))
            # an absolute path or a URL never matches: _PATH starts at a name
            if _PATH.match(word) and not _exists(word, document):
                missing.append(f"path `{word}`")
    for text in blocks + spans:
        for flag in _FLAG.findall(text):
            if flag not in _known_options():
                missing.append(f"option `{flag}`")
    assert not missing, f"{document} names what the tree does not hold: {sorted(set(missing))}"
