"""The documents and smoke scripts name only files that are in the tree.

One case for each document that describes the tree as it is, and one for
each shell script under ``scripts/``. A case reads its file and asserts
that every repository path it names exists:

* a token under ``tools/``, ``scripts/``, ``tests/``, ``examples/``,
  ``benchmark/`` or ``tepdist_tpu/`` that ends in ``.py``, ``.sh``,
  ``.json``, ``.toml``, ``.c``, ``.cc`` or ``.md``, wherever it stands;
* inside backticks, any relative path with those endings: a file under
  the root, beside the document, or (the documents' shorthand, as in
  ``parallel/sync_free.py``) under ``tepdist_tpu/``;
* inside backticks, a bare ``name.py`` or ``name.md``: a file at the root
  or beside the document, or (shorthand again, as in "``engine.py``'s
  scheduler") the name of a file somewhere in the tree.

``PERF.md``, ``ROADMAP.md`` and ``CHANGES.md`` are left out on purpose:
they narrate history, files that are gone included. Nothing else is
skipped: no document tells its reader to create a file under a path the
finder reads.
"""
import functools
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TREES = ("tools", "scripts", "tests", "examples", "benchmark", "tepdist_tpu")
EXTS = r"(?:py|sh|json|toml|cc|c|md)"

DOCUMENTS = [
    "README.md",
    "TUTORIAL.md",
    "DESIGN.md",
    "examples/README.md",
    ".claude/skills/verify/SKILL.md",
]
SHELL_SCRIPTS = sorted(
    "scripts/" + name for name in os.listdir(os.path.join(ROOT, "scripts"))
    if name.endswith(".sh"))

# Not after "$TMP/" or another path's tail: those are not under the root.
TREE_PATH = re.compile(
    r"(?<![\w./$}-])(?:%s)/[\w./-]*\.%s(?![\w/-])" % ("|".join(TREES), EXTS))
BACKTICKED = re.compile(r"`([^`\n]+)`")
RELATIVE_PATH = re.compile(r"[\w.-]+(?:/[\w.-]+)+\.%s" % EXTS)
BARE_NAME = re.compile(r"[\w-]+(?:\.[\w-]+)*\.(?:py|md)")


def _backticked(text, shape):
    """Words inside backticks of that shape, less ``::test`` and ``:line``."""
    for span in BACKTICKED.findall(text):
        for word in span.split():
            word = re.split(r"::|:\d", word.strip("\"'(),;"))[0]
            if shape.fullmatch(word):
                yield word


@functools.lru_cache(maxsize=None)
def _names_in_tree():
    names = set(os.listdir(ROOT))
    for tree in TREES:
        for _, _, files in os.walk(os.path.join(ROOT, tree)):
            names.update(files)
    return frozenset(names)


def _missing(rel):
    with open(os.path.join(ROOT, rel), encoding="utf-8") as f:
        text = f.read()
    beside = os.path.dirname(os.path.join(ROOT, rel))
    bases = (ROOT, beside, os.path.join(ROOT, "tepdist_tpu"))
    missing = {p for p in TREE_PATH.findall(text)
               if not os.path.isfile(os.path.join(ROOT, p))}
    missing.update(
        p for p in _backticked(text, RELATIVE_PATH)
        if not any(os.path.isfile(os.path.join(b, p)) for b in bases))
    names = _names_in_tree() | set(os.listdir(beside))
    missing.update(n for n in _backticked(text, BARE_NAME) if n not in names)
    return sorted(missing)


@pytest.mark.parametrize("rel", DOCUMENTS + SHELL_SCRIPTS)
def test_named_paths_exist(rel):
    assert _missing(rel) == [], f"{rel} names files that are not in the tree"


def test_the_finder_sees_each_kind_of_name():
    """The guard guards something: each form is found in a known line."""
    line = ("run `python tools/plan_diff.py a.json b.json`, see `README.md`, "
            "`parallel/sync_free.py:12` and tests/test_observatory.py::test_x, "
            "not $TMP/tools/x.json")
    assert TREE_PATH.findall(line) == [
        "tools/plan_diff.py", "tests/test_observatory.py"]
    assert list(_backticked(line, RELATIVE_PATH)) == [
        "tools/plan_diff.py", "parallel/sync_free.py"]
    assert list(_backticked(line, BARE_NAME)) == ["README.md"]
