"""graftrep determinism tests (tools/graftrep — ISSUE 10).

Pins four guarantees:

1. **Per-rule fixtures**: each of D001–D005 fires on its known-bad snippet
   with exact rule ids and line numbers, and stays silent on the known-good
   twin (``tests/fixtures/graftrep/``).
2. **Suppression machinery**: inline ``# graftrep: disable=D00X`` pragmas
   (graftlint's parser under graftrep's marker) and the baseline
   round-trip.
3. **Tier-1 gate**: the shipped tree has ZERO non-baselined findings and
   the checked-in baseline is EMPTY — the determinism discipline holds
   everywhere the bitwise guarantees reach (the D001 dogfood fixes in
   ml/local_train.py and cross_silo/trainer_dist_adapter.py stay fixed).
4. **Exit codes**: 0 clean / 1 findings / 2 analyzer crash, shared with
   the sibling suites.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from tools.graftlint import baseline as baseline_mod  # noqa: E402
from tools.graftrep.analyzer import (  # noqa: E402
    analyze_paths,
    default_baseline_path,
)

FIXTURES = os.path.join(REPO_ROOT, "tests", "fixtures", "graftrep")
TREE = os.path.join(REPO_ROOT, "fedml_tpu")


def _findings(*names):
    paths = [os.path.join(FIXTURES, n) for n in names]
    return analyze_paths(paths, repo_root=REPO_ROOT)


def _rule_lines(findings, rule):
    return sorted(f.line for f in findings if f.rule == rule)


class TestRuleFixtures:
    """Exact rule ids + line numbers on known-bad, silence on known-good."""

    def test_d001_bad(self):
        fs = _findings("d001_bad.py")
        assert {f.rule for f in fs} == {"D001"}
        # 7: sampler twice; 13: derive-after-consume; 20: loop consumption;
        # 28: consumed key captured by a closure; 39: reuse after a helper
        # whose summary consumes its key param
        assert _rule_lines(fs, "D001") == [7, 13, 20, 28, 39]

    def test_d001_good(self):
        assert _findings("d001_good.py") == []

    def test_d002_bad(self):
        fs = _findings("d002_bad.py")
        assert {f.rule for f in fs} == {"D002"}
        # 10: PRNGKey(time.time()); 15: RandomState from urandom (dataflow);
        # 19: bare np.random sampler; 24: wall-clock inside traced code
        assert _rule_lines(fs, "D002") == [10, 15, 19, 24]

    def test_d002_good(self):
        assert _findings("d002_good.py") == []

    def test_d003_bad(self):
        fs = _findings("d003_bad.py")
        assert {f.rule for f in fs} == {"D003"}
        # 8: float += over a set; 14: jnp.stack over a set-built list;
        # 24: message fan-out over a shared attr dict; 27: sum over a
        # shared attr set
        assert _rule_lines(fs, "D003") == [8, 14, 24, 27]

    def test_d003_good(self):
        assert _findings("d003_good.py") == []

    def test_d004_bad(self):
        fs = _findings("d004_bad.py")
        assert {f.rule for f in fs} == {"D004"}
        # 9: np.float64(); 10: astype(float); 11: dtype=np.float64 kw;
        # 17: numpy reducer inside traced code
        assert _rule_lines(fs, "D004") == [9, 10, 11, 17]

    def test_d004_good(self):
        assert _findings("d004_good.py") == []

    def test_d005_bad(self):
        fs = _findings("d005_bad.py")
        assert {f.rule for f in fs} == {"D005"}
        # 8: wall-clock into commit_round; 13: hostname into the
        # _ledger_world dict; 17: wall-clock gating send_message
        assert _rule_lines(fs, "D005") == [8, 13, 17]

    def test_d005_good(self):
        assert _findings("d005_good.py") == []


class TestSuppression:
    def test_pragma_suppresses_on_its_line(self):
        assert _findings("d001_pragma.py") == []

    def test_baseline_round_trip(self, tmp_path):
        fs = _findings("d001_bad.py")
        assert fs
        path = tmp_path / "baseline.json"
        baseline_mod.save(str(path), fs, tool="graftrep")
        new, old = baseline_mod.split(fs, baseline_mod.load(str(path)))
        assert new == []
        assert len(old) == len(fs)

    def test_baseline_is_line_number_free(self, tmp_path):
        fs = _findings("d001_bad.py")
        keys = {f.baseline_key() for f in fs}
        assert all("::" in k for k in keys)
        assert not any(str(f.line) in k.split("::")[0] for f, k in
                       zip(fs, sorted(keys)))


class TestTreeGate:
    """The shipped tree is clean and the checked-in baseline is EMPTY."""

    def test_tree_zero_findings(self):
        fs = analyze_paths([TREE], repo_root=REPO_ROOT)
        assert fs == [], "\n".join(f.render() for f in fs)

    def test_checked_in_baseline_empty(self):
        path = default_baseline_path(REPO_ROOT)
        with open(path) as f:
            payload = json.load(f)
        assert payload["findings"] == {}

    def test_dogfood_fixes_hold(self):
        """The two real D001 fixes: the epoch key fans out BEFORE the
        permutation consumes anything (a regression re-introducing
        fold_in on the consumed key would fire D001 again)."""
        for rel in ("fedml_tpu/ml/local_train.py",
                    "fedml_tpu/cross_silo/trainer_dist_adapter.py"):
            src = open(os.path.join(REPO_ROOT, rel)).read()
            assert "jax.random.split(erng)" in src, rel
            fs = analyze_paths([os.path.join(REPO_ROOT, rel)],
                               repo_root=REPO_ROOT)
            assert [f for f in fs if f.rule == "D001"] == []


class TestExitCodes:
    def _run(self, *argv):
        return subprocess.run(
            [sys.executable, "-m", "tools.graftrep", *argv],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
        )

    def test_clean_file_exits_zero(self):
        p = self._run(os.path.join(FIXTURES, "d001_good.py"),
                      "--no-baseline")
        assert p.returncode == 0, p.stdout + p.stderr

    def test_findings_exit_one_with_json(self):
        p = self._run(os.path.join(FIXTURES, "d001_bad.py"),
                      "--no-baseline", "--json")
        assert p.returncode == 1, p.stdout + p.stderr
        payload = json.loads(p.stdout)
        assert payload["exit_code"] == 1
        assert payload["counts"]["D001"] == 5

    def test_missing_path_exits_two(self):
        p = self._run(os.path.join(FIXTURES, "no_such_file.py"))
        assert p.returncode == 2

    def test_lint_rep_conflict_guards(self):
        p = subprocess.run(
            [sys.executable, "-m", "fedml_tpu.cli", "lint", "--rep",
             "--shard"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=60,
        )
        assert p.returncode == 2
