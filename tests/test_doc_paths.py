"""Every path a document names in backticks exists.

A backticked token whose first path component is a top-level entry of the
repo (``benchmark/run.py``, ``tests/test_moe.py::test_x``, ``README.md``)
must name a file or directory of the checkout. A trailing ``:line`` or
``::test`` is dropped, ``<placeholder>`` and ``*`` must match something,
``{a,b}`` names both, and a dotted ``module.attr`` suffix
(``tools/graftlint/findings.register_rules``) is satisfied by the module's
``.py`` file or package directory. Top-level entries that ``.gitignore``
lists (``chiprun_out/``, ``.jax_cache/``) are made at run time and skipped.
"""

import fnmatch
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = (["README.md"]
        + sorted(os.path.relpath(p, REPO)
                 for p in glob.glob(os.path.join(REPO, "docs", "*.md")))
        + [os.path.join(".claude", "skills", "verify", "SKILL.md")])


def _top_level() -> set:
    with open(os.path.join(REPO, ".gitignore")) as f:
        ignored = [ln.strip().rstrip("/") for ln in f
                   if ln.strip() and not ln.startswith("#")]
    return {name for name in os.listdir(REPO)
            if not any(fnmatch.fnmatch(name, pat) for pat in ignored)}


def _tokens(text: str):
    """Words of fenced blocks and of inline code spans."""
    fenced = re.findall(r"^\s*```[^\n]*\n(.*?)^\s*```", text, re.S | re.M)
    text = re.sub(r"^\s*```[^\n]*\n.*?^\s*```", "", text, flags=re.S | re.M)
    spans = fenced + re.findall(r"`([^`]+)`", text)
    for span in spans:
        yield from span.split()


def _path(token: str) -> str:
    token = token.strip("\"'(),;")
    return re.split(r"[:(]", token, maxsplit=1)[0].rstrip(".")


def _expand(path: str) -> list:
    m = re.search(r"\{([^{}]*)\}", path)
    if not m:
        return [path]
    return [p for alt in m.group(1).split(",")
            for p in _expand(path[:m.start()] + alt + path[m.end():])]


def _exists(path: str) -> bool:
    pattern = re.sub(r"<[^>]*>", "*", path)
    full = os.path.join(REPO, pattern)
    if glob.has_magic(pattern):
        return bool(glob.glob(full))
    if os.path.exists(full):
        return True
    head, _, last = full.rpartition("/")
    parts = last.split(".")
    return any(os.path.isfile(f"{head}/{'.'.join(parts[:i])}.py")
               or os.path.isdir(f"{head}/{'.'.join(parts[:i])}")
               for i in range(1, len(parts)))


@pytest.mark.parametrize("doc", DOCS)
def test_named_paths_exist(doc):
    top = _top_level()
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    missing = sorted({
        path for token in _tokens(text)
        for path in _expand(_path(token))
        if path.split("/")[0] in top and not _exists(path)
    })
    assert not missing, f"{doc} names paths that do not exist: {missing}"
