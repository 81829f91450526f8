"""graftrep entry: scan → graftlint facts → D-rules → pragmas.

Mirrors :func:`tools.graftshard.analyzer.analyze_paths_with_model`, with
graftrep's own pragma marker (``# graftrep: disable=D001``) and baseline
file (``tools/graftrep/baseline.json``). The pass is pure AST — no jax
import — so the tree gate stays sub-second.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence

from ..graftlint.analyzer import Analyzer, collect_files, load_modules
from ..graftlint.baseline import find_repo_root
from ..graftlint.pragmas import is_suppressed, parse_pragmas
from .findings import Finding
from .rules import check_determinism

PRAGMA_TOOL = "graftrep"
DEFAULT_BASELINE_RELPATH = os.path.join("tools", "graftrep", "baseline.json")


def default_baseline_path(repo_root: str) -> str:
    return os.path.join(repo_root, DEFAULT_BASELINE_RELPATH)


def analyze_paths(paths: Sequence[str],
                  repo_root: Optional[str] = None) -> List[Finding]:
    """Analyze files/dirs → pragma-filtered findings.

    The baseline is NOT applied here — that's the CLI/caller's job, like
    the sibling suites.
    """
    if repo_root is None:
        repo_root = find_repo_root(paths[0] if paths else os.getcwd())
    files = collect_files(paths)
    modules = load_modules(files, repo_root)
    # graftlint's jit call graph marks the traced set — "traced code" means
    # the same thing to the D-rules as it does to the G-rules
    lint = Analyzer(modules)
    lint.compute_facts()
    lint.propagate()
    findings = check_determinism(modules, lint)

    out: List[Finding] = []
    pragma_cache: Dict[str, Dict] = {}
    mods_by_rel = {m.rel: m for m in modules.values()}
    for f in findings:
        mod = mods_by_rel.get(f.path)
        if mod is not None:
            pragmas = pragma_cache.setdefault(
                f.path, parse_pragmas(mod.source, tool=PRAGMA_TOOL))
            if is_suppressed(pragmas, f.rule, f.line):
                continue
        out.append(f)
    out.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return out
