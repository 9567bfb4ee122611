"""The documents name only files that exist: every script a ``python`` /
``python3`` / ``bash`` command runs, every path quoted under one of the
repo's own directories, and every ``::name`` behind such a path."""

import glob
import os
import pathlib
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = (["README.md"]
             + sorted(os.path.relpath(p, REPO) for p in
                      glob.glob(os.path.join(REPO, "docs", "*.md")))
             + ["deploy/ci.sh", ".claude/skills/verify/SKILL.md"])

# Commands that are not this repo's: the reference project's own
# launcher, and the placeholder a reader replaces with their script.
NOT_OURS = {"run.sh", "your_drive.py"}

_COMMAND = re.compile(
    r"\b(?:python3?|bash)\s+(?:-[A-Za-z]\s+)*([\w./-]+\.(?:py|sh))\b")
_PATH = re.compile(
    r"(?<![\w./-])((?:tests|learningorchestra_tpu|scripts|deploy|benchmark"
    r"|docs)/[\w./*-]*)((?:::\w+)*)")


def _missing(text):
    """(what, why) for each reference in ``text`` the tree lacks."""
    bad = []
    for script in _COMMAND.findall(text):
        if script in NOT_OURS:
            continue
        rel = script.removeprefix("/root/repo/")
        if os.path.isabs(rel):
            continue  # a scratch path outside the checkout
        if not os.path.exists(os.path.join(REPO, rel)):
            bad.append((script, "command runs a file that is not there"))
    for path, names in _PATH.findall(text):
        path = path.rstrip(".")  # a sentence's full stop
        if not glob.glob(os.path.join(REPO, path)):
            bad.append((path, "path is not in the tree"))
            continue
        if names:
            source = pathlib.Path(REPO, path).read_text()
            for name in names.split("::")[1:]:
                if not re.search(
                        rf"^\s*(?:def|class)\s+{name}\b|^{name}\s*=",
                        source, re.M):
                    bad.append((f"{path}::{name}",
                                "name is not defined in that file"))
    return sorted(set(bad))


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_files_that_exist(document):
    bad = _missing(pathlib.Path(REPO, document).read_text())
    assert not bad, f"{document}: {bad}"


def test_a_deleted_launcher_is_caught():
    """The scan itself: a command or a ``::name`` that the tree lacks is
    reported, and one that it holds is not."""
    assert _missing("run `python no_such_launcher.py --phase tlm`") == [
        ("no_such_launcher.py", "command runs a file that is not there")]
    assert _missing("see tests/test_docs_paths.py::no_such_test.") == [
        ("tests/test_docs_paths.py::no_such_test",
         "name is not defined in that file")]
    assert _missing("`bash deploy/ci.sh`, `python3 benchmark/run.py`, "
                    "tests/test_docs_paths.py::_missing") == []
