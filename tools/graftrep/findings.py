"""graftrep rule registry (D001–D005), merged into the shared graftlint
Finding infrastructure so all four suites render/baseline/JSON identically.

The D-rules statically enforce the repo's determinism discipline — the
precondition for every bitwise guarantee the runtime parity tests pin
(kill/restart parity, sync≡async at alpha=0, delta-shipped ≡ full
broadcast).
"""

from __future__ import annotations

from typing import Dict, Tuple

from ..graftlint.findings import Finding, register_rules

# rule id -> (title, autofix hint)
REP_RULES: Dict[str, Tuple[str, str]] = {
    "D001": (
        "prng-key-reuse",
        "a key is dead once a sampler consumed it: derive per-use subkeys "
        "FIRST (`k_a, k_b = jax.random.split(k)` or "
        "`jax.random.fold_in(k, tag)` with distinct tags), then consume "
        "each subkey exactly once — reuse correlates streams that every "
        "parity proof assumes independent",
    ),
    "D002": (
        "nondeterministic-seed-provenance",
        "seed PRNGs from config only (args.random_seed, round index, rank): "
        "wall-clock, os.urandom, id() and unseeded random/np.random make "
        "the trajectory unreproducible — a kill/restart can never be "
        "bitwise-replayed from a seed nobody recorded",
    ),
    "D003": (
        "unordered-iteration-into-accumulation",
        "iterate `sorted(...)` (or a list with pinned order) before feeding "
        "a float sum, pytree build, or message fan-out — set order is "
        "process-dependent (hash randomization) and float addition does "
        "not commute bitwise",
    ),
    "D004": (
        "dtype-promotion-drift",
        "keep traced math in the model dtype: np.* reductions and "
        "float64/`dtype=float` casts inside round/aggregation code promote "
        "through float64 on some platforms and not others, breaking "
        "cross-platform bitwise parity — use jnp with an explicit narrow "
        "dtype",
    ),
    "D005": (
        "run-identity-leak",
        "ledger-committed round state must be a pure function of "
        "(seed, config, round): route wall-clock/hostname/pid to logs or "
        "telemetry, never into commit_round/ensure_meta payloads or the "
        "round-state dicts a resume replays",
    ),
}

register_rules(REP_RULES)

__all__ = ["Finding", "REP_RULES"]
