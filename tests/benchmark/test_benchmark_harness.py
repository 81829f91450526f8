"""The harness end to end on the CPU at the tiny fixture configurations
(``fixture_root``: a ``BENCHMARK.json`` of its own and only the files it
adds; everything else is found in the real ``benchmark/``), and the entry
point's refusals."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import harness, run

ROOT = harness.ROOT
FIXTURE_ROOT = os.path.join(ROOT, "tests", "benchmark", "fixture_root")
CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _run(name, trace, tmp_path, root=FIXTURE_ROOT, seconds=1.0):
    cell = harness.load_cell(name, root=root)
    return cell, harness.run_cell(cell, seed=3, seconds=seconds, trace=trace,
                                  work_dir=str(tmp_path / name))


def _check_line(cell, result, trace):
    assert list(result) == CONTRACT_KEYS  # no device plane on the CPU: no breakdown
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"] == {"platform": "cpu", "kind": "cpu", "count": 8,
                                "memory_peak_bytes": 0}
    wanted = cell.per_layer if trace else cell.end_to_end
    known = {m["name"]: m["unit"] for m in wanted}
    assert set(result["metrics"]) <= set(known)
    for name, m in result["metrics"].items():
        assert set(m) == {"value", "unit"} and m["unit"] == known[name]
        assert isinstance(m["value"], float)
    json.dumps(result)


@pytest.mark.parametrize("name, rates", [
    ("tiny_fedavg", {"rounds_per_s"}),
    ("tiny_pretrain", {"tokens_per_s_per_chip", "wall_tokens_per_s_per_chip"})])
def test_run_cell_untraced(name, rates, tmp_path):
    cell, result = _run(name, False, tmp_path)
    _check_line(cell, result, trace=False)
    assert set(result["metrics"]) == rates | {"peak_hbm_gb", "setup_s"}
    assert all(result["metrics"][m]["value"] > 0 for m in rates)
    # the pretrain job's profiler over the window's last steps left nothing
    assert not os.path.exists(tmp_path / name / "trace")
    assert result["metrics"]["setup_s"]["value"] > 0
    assert result["attempted"] >= cell.traffic["min_units"]


@pytest.mark.parametrize("name, host_metric", [
    ("tiny_fedavg", "parrot_engine.host_s_per_round"),
    ("tiny_pretrain", "cheetah_runner.data_s_per_step")])
def test_run_cell_traced(name, host_metric, tmp_path):
    """With the program's tracking on and the profiler over the window: the
    readers of spans and counters report, the readers of the device trace find
    no TPU plane in an XLA:CPU trace and are left out, and no CPU number
    appears under a device metric's name."""
    cell, result = _run(name, True, tmp_path)
    _check_line(cell, result, trace=True)
    assert result["attempted"] == cell.traffic["trace_units"]
    got = set(result["metrics"])
    assert {"entry.compile_s", "entry.cache_misses", host_metric} <= got
    from_trace = {m["name"] for m in cell.per_layer if m["source"] == "device_trace"}
    assert from_trace and not (got & from_trace)
    assert "busy_s" not in result["device"] and "breakdown" not in result
    assert not os.path.exists(tmp_path / name / "trace")  # removed after reading
    if name == "tiny_fedavg":
        share = result["metrics"]["parrot_engine.useful_sample_share"]["value"]
        assert 10 < share < 100  # ragged clients packed to the largest
        assert result["metrics"]["fixture_only.units_seen"]["value"] == 3.0


@pytest.mark.parametrize("periods, seconds, want_period", [
    # a steady loop: the median step period is the rate
    ([0.1] * 20, 2.0, 0.1),
    # the host held up for 0.16 s in three steps (8% of the window): dropped
    ([0.1] * 9 + [0.15, 0.16, 0.15] + [0.1] * 8, 2.16, 0.1),
    # held up for more than a tenth of the window: the whole window counts
    ([0.1] * 16 + [0.3] * 4, 2.8, 0.14),
    # a loop that waits for nothing starts its steps in a burst and blocks at
    # the end: step starts say nothing, the whole window counts
    ([0.001] * 19 + [1.981], 2.0, 0.1),
])
def test_pretrain_wall_rate_is_the_median_step_unless_it_does_not_account_for_the_window(
        periods, seconds, want_period, tmp_path):
    import numpy as np

    cell = harness.load_cell("tiny_pretrain", root=FIXTURE_ROOT)
    job = harness.load_module(cell.root, "jobs", "pretrain").Job(
        cell, seed=0, tracked=False, work_dir=str(tmp_path), log=lambda s: None)
    job._periods = np.asarray(periods)
    got = job.throughput(len(periods), seconds, None)
    want = job.tokens_per_step() / cell.chips / want_period
    assert got["wall_tokens_per_s_per_chip"] == pytest.approx(want)
    # XLA:CPU has no device plane: the tests read the host's clock under both names
    assert got["tokens_per_s_per_chip"] == pytest.approx(want)


def test_pretrain_rate_is_read_on_the_devices_clock(tmp_path):
    """With a device plane (the trace recorded on a v5e), the rate is the
    step's tokens over the step program's median duration on the device,
    whatever the host's clock says."""
    import numpy as np

    from benchmark import trace_reduce as tr

    cell = harness.load_cell("tiny_pretrain", root=FIXTURE_ROOT)
    job = harness.load_module(cell.root, "jobs", "pretrain").Job(
        cell, seed=0, tracked=False, work_dir=str(tmp_path), log=lambda s: None)
    job._periods = np.asarray([0.5] * 10)
    trace = tr.load(os.path.join(ROOT, "benchmark", "fixtures",
                                 "cheetah_step_v5e.xplane.pb"))
    steps = tr.module_events(trace.devices[0], "_train_step_raw").duration
    assert len(steps) == 2 and 0.27 < np.median(steps) < 0.29
    got = job.throughput(10, 5.0, trace)
    per_chip = job.tokens_per_step() / cell.chips
    assert got["tokens_per_s_per_chip"] == pytest.approx(per_chip / np.median(steps))
    assert got["wall_tokens_per_s_per_chip"] == pytest.approx(per_chip / 0.5)


def test_cell_config_and_metric_added_as_files_only(tmp_path):
    """A later PR adds a configuration, a traffic mix, a per-layer metric and
    one ``workloads`` entry, and edits nothing: done here on a copy of the
    fixture's files, to which only new files and entries are added."""
    with open(os.path.join(FIXTURE_ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny_lr_wide", "source": "tests only",
                            "file": "benchmark/configs/tiny_lr_wide.json",
                            "reduced": [], "why": "added by a test"})
    spec["workloads"].append({"name": "added_cell", "config": "tiny_lr_wide",
                              "traffic": "added_mix", "chips": 1, "why": "added by a test"})
    spec["per_layer"].append({
        "name": "added.mean_phase_count", "unit": "phases", "better": "lower",
        "source": "program_span", "layer": "parrot_engine",
        "moves": "rounds_per_s", "workloads": ["added_cell"]})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m and "tiny_fedavg" in m["workloads"]:
            m["workloads"].append("added_cell")
    root = tmp_path / "root"
    shutil.copytree(os.path.join(FIXTURE_ROOT, "benchmark"), root / "benchmark")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    with open(os.path.join(FIXTURE_ROOT, "benchmark/configs/tiny_lr.json")) as f:
        config = json.load(f)
    config["name"] = "tiny_lr_wide"
    config["program"]["batch_size"] = 8
    (root / "benchmark/configs/tiny_lr_wide.json").write_text(json.dumps(config))
    (root / "benchmark/traffic/added_mix.json").write_text(json.dumps({
        "job": "fedavg", "program": {"backend": "sp", "partition_method": "homo",
                                     "client_num_per_round": 6},
        "data_seed": None, "warmup_rounds": 2, "trace_units": 2, "min_units": 3}))
    (root / "benchmark/layer_metrics/added.mean_phase_count.py").write_text(
        '"""Phases a round records."""\n\n\ndef read(run):\n'
        '    return sum(len(r["phases"]) for r in run.records) / len(run.records)\n')
    cell, result = _run("added_cell", True, tmp_path, root=str(root))
    assert cell.config["name"] == "tiny_lr_wide" and cell.traffic["job"] == "fedavg"
    assert result["correct"] and result["attempted"] == 2
    assert result["metrics"]["added.mean_phase_count"]["value"] >= 4
    assert result["metrics"]["parrot_engine.useful_sample_share"]["value"] > 50
    with pytest.raises(KeyError, match="no workload 'nowhere'"):
        harness.load_cell("nowhere", root=str(root))


def test_main_refuses_a_host_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "fedavg_resnet56_iid", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code == 1
    out = capsys.readouterr()
    assert out.out == ""  # no result line
    assert "platform 'cpu'" in out.err and "8 device(s)" in out.err
    assert "no CPU fallback" in out.err


def test_main_refuses_a_directory_without_the_repo(tmp_path):
    """Alone with ``BENCHMARK.json`` and the files under ``paths``: exit code
    other than 0, nothing on standard output."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "fedavg_resnet56_iid",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout == ""
    assert "no fedml_tpu package" in proc.stderr


def test_last_line_holds_exactly_the_contracts_keys(monkeypatch, capsys, tmp_path):
    """``main`` with the device check and the cell swapped for the fixture:
    the last line of standard output is one JSON object with the contract's
    keys and nothing else on it."""
    real_load = harness.load_cell
    monkeypatch.setattr(harness, "require_devices", lambda chips: None)
    monkeypatch.setattr(harness, "load_cell",
                        lambda name: real_load(name, root=FIXTURE_ROOT))
    real_run = harness.run_cell
    monkeypatch.setattr(harness, "run_cell", lambda cell, **kw: real_run(
        cell, **dict(kw, work_dir=str(tmp_path / "work"))))
    assert run.main(["--workload", "tiny_fedavg", "--seed", "5", "--seconds",
                     "0.5", "--trace", "0"]) == 0
    lines = capsys.readouterr().out.splitlines()
    last = json.loads(lines[-1])
    assert list(last) == CONTRACT_KEYS
    assert list(last["device"]) == ["platform", "kind", "count", "memory_peak_bytes"]
    assert set(last["metrics"]) == {"rounds_per_s", "peak_hbm_gb", "setup_s"}
