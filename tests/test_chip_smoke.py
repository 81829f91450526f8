"""chip_smoke.py's plumbing, checked on the CPU mesh before chip budget is
spent: the script refuses a host without a TPU, and its leg functions run
end to end at ``model_size: tiny`` / ``lr`` through the same entry points
they drive at full width on the chip.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke  # conftest.py puts the repo root on sys.path

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = dict(
    chip_smoke.FLAGSHIP, model_size="tiny", vocab_size=90, seq_len=64,
    batch_size=8, total_steps=4, learning_rate=3e-3,
    client_num_in_total=8, client_num_per_round=8,
)
LR = dict(
    chip_smoke.FEDAVG, dataset="synthetic", model="lr",
    client_num_in_total=16, client_num_per_round=8, comm_round=3,
    batch_size=16,
)


def test_refuses_a_host_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""  # no result line


def test_refuses_a_directory_without_the_repo(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "no fedml_tpu package" in proc.stderr
    assert proc.stdout.strip() == ""


def test_stdout_ends_with_the_result_and_nothing_else_in_it(
        tmp_path, monkeypatch, capsys):
    """The last stdout line has exactly the contract's keys; the report
    with versions, cache counts and legs is the line before it."""
    monkeypatch.setattr(chip_smoke, "HERE", str(tmp_path))
    monkeypatch.setattr(chip_smoke, "_require_repo", lambda: None)
    monkeypatch.setattr(chip_smoke, "_require_tpu", lambda: None)
    monkeypatch.setattr(chip_smoke, "_legs",
                        lambda n, run_dir: {"noop": lambda: {"ran": "noop"}})
    assert chip_smoke.main(["noop"]) == 0
    report, last = map(json.loads, capsys.readouterr().out.splitlines())
    assert list(last) == ["ok", "device"] and last["ok"] is True
    assert list(last["device"]) == ["platform", "kind", "count"]
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": 8}
    assert report["legs"] == {"noop": {"ran": "noop"}}
    assert report["claim"] is None and "hits" in report["compile_cache"]
    with open(tmp_path / "chiprun_out" / "chip_smoke" / "report.json") as f:
        assert json.load(f) == report


def test_cheetah_leg_and_sequence_sharded_runner(tmp_path):
    """``mesh_shape: sequence:N`` through the runner shards the batch over
    ``sequence`` (it used to build the axis and replicate the step over it)
    and trains to the same losses as the run without the axis."""
    seq = chip_smoke.leg_cheetah(
        dict(TINY, mesh_shape="data:4,sequence:2"), str(tmp_path))
    flat = chip_smoke.leg_cheetah(dict(TINY, mesh_shape="data:8"),
                                  str(tmp_path))
    assert seq["seq_sharded"] and not flat["seq_sharded"]
    assert "'sequence'" in seq["batch"]["spec"]
    assert seq["batch"]["shard_shape"] == [2, 32]  # 8 x 64 over data 4, seq 2
    assert flat["batch"]["shard_shape"] == [1, 64]
    assert seq["losses"] == pytest.approx(flat["losses"], abs=5e-3)
    assert seq["params"]["leaves_on_devices"] == 8
    assert seq["compiles_after_warmup"] == 0


@pytest.mark.parametrize("extra", [
    dict(backend="sp"),
    dict(backend="mesh", mesh_shape="clients:8"),
], ids=["sp", "mesh"])
def test_fedavg_leg(tmp_path, extra):
    out = chip_smoke.leg_fedavg(dict(LR, **extra), str(tmp_path))
    assert out["fused"] and out["compiles_after_warmup"] == 0
    assert out["losses"][-1] < out["losses"][0]
    if extra["backend"] == "mesh":
        assert out["cohort_x"]["sharded_leaf"]["shard_shape"][0] == 1
        assert out["round_state_devices"] == list(range(8))


def test_ring_kernel_leg_interpreted():
    out = chip_smoke.leg_ring_kernel(B=1, Lb=256, H=2, D=128, interpret=True)
    assert set(out["rel_l2_vs_einsum"]) == {"out", "dq", "dk", "dv"}
    assert max(out["rel_l2_vs_einsum"].values()) <= out["rel_l2_tolerance"]


def test_fedllm_leg(tmp_path):
    leg = chip_smoke._legs(4, str(tmp_path))["fedllm_2x2"]
    overrides, silos, run_dir = leg.args
    out = chip_smoke.leg_fedllm(dict(overrides, model_size="tiny"), silos,
                                run_dir)
    assert out["host_fallbacks"] == 0
    assert out["wire"]["comm.delta.s2c_delta_frames"] == 2
