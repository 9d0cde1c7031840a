"""The documents a newcomer reads first name only files that exist.

``README.md`` and the module docstrings of ``predictionio_tpu/`` send the
reader to files by name.  Every repo-relative ``*.py`` / ``*.json`` path
they put in backticks must resolve: against the repo root, against
``predictionio_tpu/`` (docstrings say ``obs/fleet.py``), or against the
module's own directory.  A bare file name must be the name of some file in
the tree.  HTTP routes (``/queries.json``) and placeholders (``<name>.json``)
are not paths.  ``CHANGES.md``, ``PERF.md``'s findings and ``ROADMAP.md``'s
"Recent" are history and are not checked.
"""

import ast
import os
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
PACKAGE = REPO / "predictionio_tpu"
_TOKEN = re.compile(
    r"`{1,2}([A-Za-z0-9_./<>*-]+\.(?:py|json))(?:::[\w.]+)?`{1,2}")


def _tree_names():
    names = {p.name for p in REPO.iterdir() if p.is_file()}
    for top in REPO.iterdir():
        if top.is_dir() and top.name[0] not in "._":
            for _, dirs, files in os.walk(top):
                dirs[:] = [d for d in dirs if d[0] not in "._"]
                names.update(files)
    return names


def _unresolved(text, base, names):
    missing = []
    for token in _TOKEN.findall(text):
        if token.startswith("/") or any(c in token for c in "<>*"):
            continue
        if "/" not in token:
            found = token in names
        else:
            found = any((root / token).exists()
                        for root in (REPO, PACKAGE, base))
        if not found:
            missing.append(token)
    return missing


def _readme():
    yield "README.md", (REPO / "README.md").read_text(), REPO


def _module_docstrings():
    for path in sorted(PACKAGE.rglob("*.py")):
        doc = ast.get_docstring(ast.parse(path.read_text()))
        if doc:
            yield str(path.relative_to(REPO)), doc, path.parent


@pytest.mark.parametrize("documents", [_readme, _module_docstrings],
                         ids=["readme", "module_docstrings"])
def test_every_path_in_backticks_exists(documents):
    names = _tree_names()
    missing = {where: bad for where, text, base in documents()
               if (bad := _unresolved(text, base, names))}
    assert not missing, missing
