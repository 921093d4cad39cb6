"""The documents name only files the tree holds.

A deletion that leaves the documents behind is how the tree came to sell
itself on three generations of harness at once; this keeps the next one
from doing the same. Per document: every path it names that begins with a
tracked top-level directory, every ``*.py`` / ``*.md`` / ``*.json`` file
it names without a directory, and every file a ``make`` recipe or a
``python <file>`` line runs must exist. Exempt: a path after
``git show <rev>:`` (a pointer into history), anything ``.gitignore``
lists (made at run time), and names with a placeholder in them.
``PERF.md``, ``ROADMAP.md``, ``PARITY.md`` and ``CHANGES.md`` are
histories that name deleted files on purpose and stay out."""

from __future__ import annotations

import fnmatch
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
TOP_DIRS = ("pilosa_tpu", "tests", "scripts", "benchmarks", "docs")
DOCUMENTS = ["README.md", "Makefile",
             *sorted(f"docs/{p.name}" for p in (REPO / "docs").glob("*.md"))]

_PATH = re.compile(
    r"(?<![\w./<-])((?:%s)/[\w./<>*{}-]*[\w/>*}])" % "|".join(TOP_DIRS))
_BARE = re.compile(r"(?<![\w./<>*-])([\w-]+\.(?:py|md|json))(?![\w/])")
_RUN = re.compile(r"\bpython3?\s+(?!-)([\w./-]+\.py)\b")
_GIT_SHOW = re.compile(r"git show [\w.~^-]+:\S+")


def _ignored(patterns, path):
    parts = path.split("/")
    for pat in patterns:
        if pat.endswith("/"):
            if pat.rstrip("/") in parts[:-1] or path.startswith(pat):
                return True
        elif fnmatch.fnmatch(path, pat) or fnmatch.fnmatch(parts[-1], pat):
            return True
    return False


def named_paths(text):
    """(paths with a directory, bare file names, files a line runs)."""
    text = _GIT_SHOW.sub(" ", text)
    with_dir = {m.split("::")[0] for m in _PATH.findall(text)}
    ran = set(_RUN.findall(text))
    bare = set(_BARE.findall(text))
    return with_dir, bare, ran


def missing_from(text, here, tracked_names, ignore):
    """What ``text``, a document in directory ``here``, names and the
    tree does not hold."""
    with_dir, bare, ran = named_paths(text)
    missing = []
    for path in sorted(with_dir | {r for r in ran if "/" in r}):
        if any(c in path for c in "<>*{}") or _ignored(ignore, path):
            continue
        if not (REPO / path.rstrip("/")).exists():
            missing.append(path)
    for name in sorted(bare | {r for r in ran if "/" not in r}):
        if _ignored(ignore, name):
            continue
        # at the root, beside the document (a relative link), or the
        # short name of a module the text is about (``residency.py``)
        if not ((REPO / name).exists() or (here / name).exists()
                or name in tracked_names):
            missing.append(name)
    return missing


@pytest.fixture(scope="module")
def tree():
    ignore = [ln.strip()
              for ln in (REPO / ".gitignore").read_text().splitlines()
              if ln.strip() and not ln.startswith("#")]
    names = {p.name for d in TOP_DIRS for p in (REPO / d).rglob("*")
             if p.is_file() and not _ignored(ignore, str(p.relative_to(REPO)))}
    return names, ignore


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_files_the_tree_holds(document, tree):
    names, ignore = tree
    path = REPO / document
    assert missing_from(path.read_text(), path.parent, names, ignore) == []


def test_the_check_sees_a_deleted_file(tree):
    """The reader itself: a document naming a harness that is gone, a
    test file that never was and a script a recipe runs is caught on
    each; a ``git show`` pointer, a run-time file, a placeholder and a
    module's short name are not."""
    names, ignore = tree
    text = (
        "run `python old_suite.py --configs x` or `make t`:\n"
        "\tpython scripts/no_such_probe.py\n"
        "see tests/test_never_was.py::test_x, `OLD_RECORD_r07.json`,\n"
        "`git show 857c983:gone.py`, `.jax_cache/`, `chiprun_out/a.json`,\n"
        "`benchmarks/configs/<config>.json`, pilosa_tpu/wire/internal_pb2.py\n"
        "and `pilosa_tpu/server/http.py`, `residency.py`, docs/PQL.md.\n")
    assert missing_from(text, REPO / "docs", names, ignore) == [
        "scripts/no_such_probe.py", "tests/test_never_was.py",
        "OLD_RECORD_r07.json", "old_suite.py"]
