"""graftrep CLI: ``python -m tools.graftrep [paths...]``.

Thin suite definition over the shared driver
(:mod:`tools.graftlint.clikit` — flags, baseline handling, rendering, and
the exit-code contract live there, shared with the sibling suites).
Exit codes: 0 clean (after baseline + pragmas), 1 findings, 2 usage error
OR analyzer crash. The one pass is pure AST (no jax import).
"""

from __future__ import annotations

import argparse
from typing import Dict, List, Optional, Tuple

from ..graftlint import clikit
from ..graftlint.findings import Finding
from .analyzer import DEFAULT_BASELINE_RELPATH, analyze_paths
from .findings import REP_RULES


def _analyze(args: argparse.Namespace,
             repo_root: str) -> Tuple[List[Finding], Dict]:
    return analyze_paths(args.paths, repo_root=repo_root), {}


def main(argv: Optional[List[str]] = None) -> int:
    return clikit.run_suite(
        argv,
        tool="graftrep",
        description="static determinism verification of the trust "
                    "pipeline: PRNG-key discipline, seed provenance, "
                    "unordered accumulation, dtype drift, run-identity "
                    "leaks",
        rules=REP_RULES,
        analyze=_analyze,
        baseline_relpath=DEFAULT_BASELINE_RELPATH,
    )


if __name__ == "__main__":
    raise SystemExit(main())
