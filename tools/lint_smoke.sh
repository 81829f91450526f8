#!/usr/bin/env bash
# CI smoke for ALL SIX static-analysis gates:
#  - graftlint  (G001–G005, JAX trace/donation/recompile/thread safety)
#  - graftproto (P001–P009, comm-plane protocol + lock-order verification)
#  - graftshard (S001–S005, sharding/HBM verification of the TPU
#                execution plane)
#  - graftrep   (D001–D005, determinism discipline of the trust pipeline)
#  - graftiso   (I001–I005, serving-plane state ownership, tenant
#                isolation & thread lifecycle)
#  - graftmem   (M001–M005, serving-plane retention: bounded containers,
#                capped caches, fixed metric vocabularies, drained
#                parking, released payloads)
# The shipped tree must have ZERO non-baselined findings in each suite
# (tools/<suite>/baseline.json holds the suppressed-but-visible debt —
# graftshard's, graftrep's, graftiso's and graftmem's ship EMPTY), the
# JSON reports must parse, and each gate must bite on a known-bad fixture.
#
# Exit-code contract (all suites): 0 clean, 1 findings, 2 analyzer crash —
# a CI failure here is diagnosable at a glance.
#
# This is the cheap half of the tier-1 lint gate (tests/test_graftlint.py
# + test_graftproto.py + test_graftshard.py + test_graftrep.py +
# test_graftiso.py + test_graftmem.py are the full ones): pure-AST, no
# jax import, sub-second.
#
# Usage: tools/lint_smoke.sh          (CI: exits non-zero on any regression)
set -uo pipefail
cd "$(dirname "$0")/.."

out=$(timeout -k 10 120 python -m tools.graftlint fedml_tpu/ --format json)
rc=$?

if [ "$rc" -ne 0 ]; then
    echo "lint_smoke: FAIL — graftlint exited rc=$rc" >&2
    printf '%s\n' "$out" >&2
    exit 1
fi

python - "$out" <<'EOF'
import json
import sys

payload = json.loads(sys.argv[1])
assert payload["exit_code"] == 0, payload
assert payload["findings"] == [], payload["findings"]
print(f"lint_smoke: graftlint OK — 0 findings "
      f"({payload['baselined']} baselined)")
EOF
rc=$?
if [ "$rc" -ne 0 ]; then
    echo "lint_smoke: FAIL — graftlint JSON output did not validate" >&2
    exit 1
fi

# the gate must actually bite: a known-bad fixture has to exit non-zero
if python -m tools.graftlint tests/fixtures/graftlint/g001_bad.py \
        --no-baseline >/dev/null 2>&1; then
    echo "lint_smoke: FAIL — graftlint passed a known-bad fixture" >&2
    exit 1
fi

# ---- graftproto: the protocol pass, machine-readable -----------------------
proto_out=$(timeout -k 10 120 python -m tools.graftproto fedml_tpu/ --json)
rc=$?

if [ "$rc" -ne 0 ]; then
    echo "lint_smoke: FAIL — graftproto exited rc=$rc" >&2
    printf '%s\n' "$proto_out" >&2
    exit 1
fi

python - "$proto_out" <<'EOF'
import json
import sys

payload = json.loads(sys.argv[1])
assert payload["exit_code"] == 0, payload
assert payload["findings"] == [], payload["findings"]
# the flow graph must have classified every wire value — future PRs diff
# these counts to see protocol surface grow/shrink
cov = payload["coverage"]
assert cov, "empty flow-graph coverage"
bad = {v: c for v, c in cov.items()
       if c["classification"] != "sent+handled"}
assert bad == {}, f"unclassified wire values: {bad}"
print(f"lint_smoke: graftproto OK — 0 findings "
      f"({payload['baselined']} baselined, "
      f"{len(cov)} wire values sent+handled)")
EOF
rc=$?
if [ "$rc" -ne 0 ]; then
    echo "lint_smoke: FAIL — graftproto JSON output did not validate" >&2
    exit 1
fi

if python -m tools.graftproto tests/fixtures/graftproto/p008_bad.py \
        --no-baseline >/dev/null 2>&1; then
    echo "lint_smoke: FAIL — graftproto passed a known-bad fixture" >&2
    exit 1
fi

# ---- graftshard: the sharding pass, machine-readable -----------------------
shard_out=$(timeout -k 10 120 python -m tools.graftshard fedml_tpu/ --json)
rc=$?

if [ "$rc" -ne 0 ]; then
    echo "lint_smoke: FAIL — graftshard exited rc=$rc" >&2
    printf '%s\n' "$shard_out" >&2
    exit 1
fi

python - "$shard_out" <<'EOF'
import json
import sys

payload = json.loads(sys.argv[1])
assert payload["exit_code"] == 0, payload
assert payload["findings"] == [], payload["findings"]
# graftshard is the one suite whose baseline must stay EMPTY: the
# execution plane ships fully clean, debt is fixed not suppressed
assert payload["baselined"] == 0, payload
print(f"lint_smoke: graftshard OK — 0 findings (baseline empty)")
EOF
rc=$?
if [ "$rc" -ne 0 ]; then
    echo "lint_smoke: FAIL — graftshard JSON output did not validate" >&2
    exit 1
fi

if python -m tools.graftshard tests/fixtures/graftshard/s002_bad.py \
        --no-baseline >/dev/null 2>&1; then
    echo "lint_smoke: FAIL — graftshard passed a known-bad fixture" >&2
    exit 1
fi

# ---- graftrep: the determinism pass, machine-readable ----------------------
rep_out=$(timeout -k 10 120 python -m tools.graftrep fedml_tpu/ --json)
rc=$?

if [ "$rc" -ne 0 ]; then
    echo "lint_smoke: FAIL — graftrep exited rc=$rc" >&2
    printf '%s\n' "$rep_out" >&2
    exit 1
fi

python - "$rep_out" <<'EOF'
import json
import sys

payload = json.loads(sys.argv[1])
assert payload["exit_code"] == 0, payload
assert payload["findings"] == [], payload["findings"]
# graftrep's baseline must stay EMPTY: the determinism discipline holds
# everywhere the bitwise guarantees reach, debt is fixed not suppressed
assert payload["baselined"] == 0, payload
print("lint_smoke: graftrep OK — 0 findings (baseline empty)")
EOF
rc=$?
if [ "$rc" -ne 0 ]; then
    echo "lint_smoke: FAIL — graftrep JSON output did not validate" >&2
    exit 1
fi

if python -m tools.graftrep tests/fixtures/graftrep/d001_bad.py \
        --no-baseline >/dev/null 2>&1; then
    echo "lint_smoke: FAIL — graftrep passed a known-bad fixture" >&2
    exit 1
fi

# ---- graftiso: the isolation pass, machine-readable ------------------------
iso_out=$(timeout -k 10 120 python -m tools.graftiso fedml_tpu/ --json)
rc=$?

if [ "$rc" -ne 0 ]; then
    echo "lint_smoke: FAIL — graftiso exited rc=$rc" >&2
    printf '%s\n' "$iso_out" >&2
    exit 1
fi

python - "$iso_out" <<'EOF'
import json
import sys

payload = json.loads(sys.argv[1])
assert payload["exit_code"] == 0, payload
assert payload["findings"] == [], payload["findings"]
# graftiso's baseline must stay EMPTY: the serving plane's world-scoping
# contract holds everywhere, debt is fixed not suppressed
assert payload["baselined"] == 0, payload
# the serving model must actually have seen the plane — an empty closure
# would mean the gate silently stopped analyzing anything
serving = payload["serving"]
assert serving["classes"], "no serving classes found"
assert serving["closure_size"] > 0, serving
print(f"lint_smoke: graftiso OK — 0 findings (baseline empty, "
      f"{len(serving['classes'])} serving classes, "
      f"closure {serving['closure_size']})")
EOF
rc=$?
if [ "$rc" -ne 0 ]; then
    echo "lint_smoke: FAIL — graftiso JSON output did not validate" >&2
    exit 1
fi

if python -m tools.graftiso tests/fixtures/graftiso/i005_bad.py \
        --no-baseline >/dev/null 2>&1; then
    echo "lint_smoke: FAIL — graftiso passed a known-bad fixture" >&2
    exit 1
fi

# ---- graftmem: the retention pass, machine-readable ------------------------
mem_out=$(timeout -k 10 120 python -m tools.graftmem fedml_tpu/ --json)
rc=$?

if [ "$rc" -ne 0 ]; then
    echo "lint_smoke: FAIL — graftmem exited rc=$rc" >&2
    printf '%s\n' "$mem_out" >&2
    exit 1
fi

python - "$mem_out" <<'EOF'
import json
import sys

payload = json.loads(sys.argv[1])
assert payload["exit_code"] == 0, payload
assert payload["findings"] == [], payload["findings"]
# graftmem's baseline must stay EMPTY: every piece of serving-plane state
# is bounded/drained/released, debt is fixed not suppressed
assert payload["baselined"] == 0, payload
# the retention model must actually have seen the plane — an empty
# container inventory would mean the gate silently analyzed nothing
mem = payload["mem"]
assert mem["classes"], "no analyzed classes found"
assert mem["containers"] > 0, mem
print(f"lint_smoke: graftmem OK — 0 findings (baseline empty, "
      f"{len(mem['classes'])} analyzed classes, "
      f"{mem['containers']} containers)")
EOF
rc=$?
if [ "$rc" -ne 0 ]; then
    echo "lint_smoke: FAIL — graftmem JSON output did not validate" >&2
    exit 1
fi

if python -m tools.graftmem tests/fixtures/graftmem/m001_bad.py \
        --no-baseline >/dev/null 2>&1; then
    echo "lint_smoke: FAIL — graftmem passed a known-bad fixture" >&2
    exit 1
fi

echo "lint_smoke: PASS"
