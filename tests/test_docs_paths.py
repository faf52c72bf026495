"""The documents name no file that is not in the tree.

``README.md``, ``docs/ARCHITECTURE.md`` and the verify skill are what a
newcomer reads first: a path they give in backticks with a ``.py``, ``.json``
or ``.md`` ending has to be there, by its path or, where the document gives a
bare file name or a path inside the package, somewhere under the tree with
that tail. ``PERF.md``, ``ROADMAP.md`` and ``CHANGES.md`` are not held to this:
they name files of past PRs on purpose.
"""

from __future__ import annotations

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md", "docs/ARCHITECTURE.md",
             ".claude/skills/verify/SKILL.md"]
#: directories that hold copies, caches and outputs, never the program
_NOT_THE_TREE = {".git", "_archive", "_scratch", "chiprun_out", "__pycache__",
                 ".jax_cache", ".bench_trace", ".pytest_cache"}
_PATH = re.compile(
    r"(?<![\w./<>{}*-])([\w.-]+(?:/[\w.-]+)*\.(?:py|json|md))(?![\w/])")


@pytest.fixture(scope="module")
def tree():
    files = []
    for root, dirs, names in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in _NOT_THE_TREE]
        rel = os.path.relpath(root, REPO)
        files += [os.path.normpath(os.path.join(rel, n)) for n in names]
    return files


def _named(text):
    """The paths inside backticks; a span with a placeholder in it (``<cell>``,
    ``r{N}``, ``*``) names a family of files and not one."""
    names = set()
    for span in re.findall(r"`([^`\n]+)`", text):
        for m in _PATH.finditer(span):
            start, end = m.span(1)
            around = span[max(0, start - 1):end + 1]
            if not any(c in around for c in "<>{}*"):
                names.add(m.group(1))
    return names


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_path_a_document_names_is_in_the_tree(document, tree):
    with open(os.path.join(REPO, document)) as f:
        names = _named(f.read())
    assert names, f"{document} names no file at all: the pattern is broken"
    missing = sorted(
        name for name in names
        if not any(path == name or path.endswith("/" + name)
                   for path in tree))
    assert not missing, f"{document} names files that are not there: {missing}"
