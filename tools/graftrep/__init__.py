"""graftrep: static determinism verification.

The fourth static-analysis suite (after graftlint/graftproto/graftshard),
on the same shared driver (:mod:`tools.graftlint.clikit`):

- **D-rules** (pure AST, no jax import): PRNG-key discipline (D001),
  seed provenance (D002), unordered iteration into accumulation (D003),
  dtype-promotion drift (D004), run-identity leaks into ledger state
  (D005) — the static enforcement of every bitwise guarantee the parity
  tests pin at runtime.

Entry points: ``python -m tools.graftrep`` / ``fedml_tpu lint --rep``.
"""

from .analyzer import analyze_paths
from .findings import REP_RULES, Finding

__all__ = ["analyze_paths", "Finding", "REP_RULES"]
