"""Documents name files that exist.

Every backticked (or fenced) token of a document that starts with one of
the repo's tracked top-level directories is a path and must exist; so
must every ``COPY`` operand of the Dockerfile and every module or script
a ``valohai.yaml`` command runs.  ``file:line`` and ``file::test`` forms
are checked for the file only, globs must match something, and a token
with a placeholder (``<cell>``, ``{name}``, ``…``) is skipped.  Bare
module names (``bart.py``, ``config.json``) and paths relative to some
other directory (``harness/text.py``) are not checked: they carry no
top-level directory to resolve against.
"""

from __future__ import annotations

import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOP_DIRS = ("distributed_llms_example_tpu/", "scripts/", "benchmarks/", "tests/")

# what a run generates, and what a document names in order to say it is gone
with open(os.path.join(ROOT, "tests", "fixtures", "docs_allowed_paths.txt")) as _f:
    ALLOWED = {line.split("#")[0].strip() for line in _f} - {""}

_BACKTICKED = re.compile(r"```.*?```|`[^`\n]+`", re.S)
_PLACEHOLDER = re.compile(r"[<>{}…$]|\.\.\.")


def _tokens(text: str):
    for span in _BACKTICKED.findall(text):
        yield from span.strip("`").split()


def _path_of(token: str) -> str | None:
    token = token.strip("\"'()[],;")
    if not token.startswith(TOP_DIRS) or _PLACEHOLDER.search(token):
        return None
    token = token.split("::")[0]
    token = re.sub(r":[\d,\s:-]*$", "", token)  # file:line, file:10-20
    return token.rstrip(".:/") or None


def _exists(path: str) -> bool:
    if any(path == a or path.startswith(a + "/") for a in ALLOWED):
        return True
    return bool(glob.glob(os.path.join(ROOT, path)))


def _dockerfile_operands(text: str):
    for line in text.splitlines():
        if line.startswith("COPY "):
            yield from (p.rstrip("/") for p in line.split()[1:-1])


def _valohai_programs(text: str):
    # the items of a step's `command:` list, not the comments about the reference
    for m in re.finditer(r"^\s*-\s+python3?\s+(-m\s+)?([\w./-]+)", text, re.M):
        is_module, target = m.groups()
        yield target.replace(".", "/") + ".py" if is_module else target


# document -> what it names beside its backticked paths
DOCS = {
    "README.md": None,
    "PERF.md": None,
    "ROADMAP.md": None,
    "BASELINE.md": None,
    "Dockerfile": _dockerfile_operands,
    "valohai.yaml": _valohai_programs,
}


@pytest.mark.parametrize("doc", DOCS)
def test_named_paths_exist(doc):
    with open(os.path.join(ROOT, doc)) as f:
        text = f.read()
    paths = {p for p in map(_path_of, _tokens(text)) if p}
    if DOCS[doc] is not None:
        operands = set(DOCS[doc](text))
        assert operands, f"{doc}: the check found nothing to check"
        paths |= operands
    missing = sorted(p for p in paths if not _exists(p))
    assert not missing, f"{doc} names paths that do not exist: {missing}"
