"""Convergence parity gate (VERDICT r2 next #1): the sp engine's per-round
global-parameter trajectories must exactly match (a) the reference's own
FedAvgAPI driven in-process on identical data/partition/cohorts/seeds, and
(b) independent numpy oracles of the published FedProx/SCAFFOLD update rules.
See tools/parity_check.py for the full design, including the reference's
round-0 state-aliasing quirk this pins down."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_lr_trajectory_parity(tmp_path):
    if not os.path.isdir("/root/reference/python/fedml"):
        pytest.skip("reference checkout not available")
    out = tmp_path / "PARITY.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # both stacks on CPU
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "parity_check.py"),
         "--skip-resnet", "--out", str(out)],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    results = json.loads(out.read_text())
    assert results["all_ok"], results
    # the head-to-head itself, not just the oracles
    head = results["results"]["fedavg_lr_vs_reference_aliasing_fixed"]
    assert head["rel_l2_max"] < 1e-3
    assert results["results"]["scaffold_lr_vs_oracle"]["rel_l2_max"] < 1e-3
