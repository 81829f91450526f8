"""Stall-proofing tests for the bench orchestrator (bench.py).

Round 4 recorded ``BENCH_r04.json: rc=124, parsed=null`` — a single wedged
leg zeroed the whole round. These tests pin the r5 guarantees with an
injected leg runner (no jax, no subprocesses):

- a cumulative JSON line is printed after EVERY leg, so an external kill
  leaves the most complete line as the tail;
- a leg that times out or crashes costs one key, never the headline;
- the global budget skips remaining legs with explicit markers;
- completed TPU legs are checkpointed to BENCH_PARTIAL.json and reused on a
  digest match (and NOT reused after a config/source change).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402  (repo root is not on sys.path under bare `pytest`)


@pytest.fixture()
def partial_path(tmp_path, monkeypatch):
    p = tmp_path / "BENCH_PARTIAL.json"
    monkeypatch.setattr(bench, "PARTIAL_PATH", str(p))
    return p


def _tpu_runner(argv, timeout):
    """Fake every leg succeeding on a TPU host."""
    joined = " ".join(argv)
    if "--leg fedavg" in joined:
        return {"rounds_per_sec": 1.25, "platform": "tpu",
                "device_kind": "TPU v5 lite"}
    if "--leg cheetah" in joined:
        return {"cheetah_mfu": 0.758, "cheetah_tokens_per_sec_per_chip": 1e5,
                "cheetah_device_kind": "TPU v5 lite", "platform": "tpu"}
    if "--leg million" in joined:
        return {"million_rounds_per_sec": 2.5, "million_registry_n": 1000000,
                "million_cohort_k": 10000, "million_prefetch_overlap": 0.9,
                "million_steady_compiles": 0, "platform": "tpu",
                "device_kind": "TPU v5 lite"}
    if "--leg wire" in joined:
        return {"wire_host_cpu_reduction_x": 3.3, "wire_parity": True,
                "wire_soak_ok": True, "wire_frame_mb": 16.0,
                "platform": "tpu", "device_kind": "TPU v5 lite"}
    if "--leg compressed" in joined:
        return {"compressed_reduction_x": 11.6, "compressed_acc": 0.999,
                "uncompressed_acc": 1.0, "compressed_bytes_per_round": 22000.0,
                "uncompressed_bytes_per_round": 257000.0, "platform": "tpu",
                "device_kind": "TPU v5 lite"}
    return {"mfu": 0.5, "tok_s": 9e4, "params_m": 600.0, "n_chips": 1,
            "step_s": 0.2, "device_kind": "TPU v5 lite"}


V5E = lambda: "TPU v5 lite"  # noqa: E731  — injected device prober


def _lines(capsys):
    return [json.loads(ln) for ln in capsys.readouterr().out.strip().splitlines()]


def test_emits_cumulative_line_after_every_leg(partial_path, capsys):
    final = bench.run_legs(budget_s=1e6, ttl_s=1e6, runner=_tpu_runner, device_prober=V5E)
    lines = _lines(capsys)
    # one startup line (parseable tail from second zero) + one per leg
    assert len(lines) == len(bench.leg_specs()) + 1
    # every line is a full headline line — the tail is always parseable
    for ln in lines:
        assert ln["metric"] == (
            "fedavg_rounds_per_sec_100clients_cifar10_resnet56")
        assert "unit" in ln and "vs_baseline" in ln
    assert lines[0]["value"] is None  # startup line precedes any leg
    assert lines[0]["bench_device_probe"] == "TPU v5 lite"
    assert lines[1]["value"] == 1.25  # headline present from the first leg
    assert final == lines[-1]
    assert final["cheetah_mfu"] == 0.758
    assert final["cheetah_moe_mfu"] == 0.5
    # all TPU legs checkpointed
    cache = json.loads(partial_path.read_text())
    assert set(cache["legs"]) == {n for n, *_ in bench.leg_specs()}


def test_one_wedged_leg_does_not_zero_the_round(partial_path, capsys):
    def runner(argv, timeout):
        if "--leg fedavg" in " ".join(argv):
            raise subprocess.TimeoutExpired(argv, timeout)
        return _tpu_runner(argv, timeout)

    final = bench.run_legs(budget_s=1e6, ttl_s=1e6, runner=runner, device_prober=V5E)
    assert final["value"] is None
    assert final["fedavg_error"] == "leg timeout"
    assert final["cheetah_mfu"] == 0.758  # later legs still ran
    cache = json.loads(partial_path.read_text())
    assert "fedavg" not in cache["legs"]  # failures are never cached


def test_budget_skips_remaining_legs_with_markers(partial_path, capsys):
    calls = []

    def runner(argv, timeout):
        calls.append(argv)
        return _tpu_runner(argv, timeout)

    # budget already below min_leg_s: every leg skipped, line still printed
    final = bench.run_legs(budget_s=10, ttl_s=1e6, min_leg_s=240,
                           runner=runner, device_prober=V5E)
    assert not calls
    for name, *_ in bench.leg_specs():
        assert final[f"{name}_skipped"] == "budget"
    assert final["value"] is None  # explicit null beats rc=124 and no line


def test_cache_reuse_and_invalidation(partial_path, capsys, monkeypatch):
    calls = []

    def runner(argv, timeout):
        calls.append(argv)
        return _tpu_runner(argv, timeout)

    # a row written by ANOTHER overlapping run must survive our writes
    partial_path.write_text(json.dumps(
        {"legs": {"foreign_leg": {"digest": "x", "t": 1, "platform": "tpu",
                                  "result": {}}}}))

    bench.run_legs(budget_s=1e6, ttl_s=1e6, runner=runner,
                   device_prober=V5E)
    n_first = len(calls)
    assert n_first == len(bench.leg_specs())
    assert "foreign_leg" in json.loads(partial_path.read_text())["legs"]

    # second run: every leg served from cache, zero subprocesses
    final = bench.run_legs(budget_s=1e6, ttl_s=1e6, runner=runner,
                           device_prober=V5E)
    assert len(calls) == n_first
    assert final["value"] == 1.25
    assert final["fedavg_cached"] is True and final["cheetah_cached"] is True

    # a config change invalidates exactly the changed leg
    monkeypatch.setitem(bench.MOE_CFG, "moe_capacity_factor", 9.9)
    bench.run_legs(budget_s=1e6, ttl_s=1e6, runner=runner, device_prober=V5E)
    assert len(calls) == n_first + 1
    assert "mfu_sweep" in " ".join(calls[-1])

    # an expired cache re-runs everything
    calls.clear()
    bench.run_legs(budget_s=1e6, ttl_s=0, runner=runner, device_prober=V5E)
    assert len(calls) == len(bench.leg_specs())


def test_cache_dropped_on_device_kind_mismatch(partial_path, capsys):
    calls = []

    def runner(argv, timeout):
        calls.append(argv)
        return _tpu_runner(argv, timeout)

    bench.run_legs(budget_s=1e6, ttl_s=1e6, runner=runner, device_prober=V5E)
    n = len(calls)

    # same chip generation → all cached
    bench.run_legs(budget_s=1e6, ttl_s=1e6, runner=runner, device_prober=V5E)
    assert len(calls) == n

    # a v6e host must NOT serve v5e numbers: every row re-measures fresh
    final = bench.run_legs(budget_s=1e6, ttl_s=1e6, runner=runner,
                           device_prober=lambda: "TPU v6e")
    assert len(calls) == 2 * n
    assert "fedavg_cached" not in final

    # unknown kind (wedged backend — the insurance case) accepts the cache
    calls.clear()
    bench.run_legs(budget_s=1e6, ttl_s=1e6, runner=runner,
                   device_prober=lambda: None)
    assert not calls


def test_cpu_results_are_not_cached_and_not_ref_compared(partial_path, capsys):
    def cpu_runner(argv, timeout):
        joined = " ".join(argv)
        if "--leg fedavg" in joined:
            return {"rounds_per_sec": 50.0, "platform": "cpu",
                    "device_kind": "cpu"}
        if "--leg cheetah" in joined:
            return {"cheetah_mfu": 0.01, "platform": "cpu"}
        return {"skipped": "not a tpu host"}

    final = bench.run_legs(budget_s=1e6, ttl_s=1e6, runner=cpu_runner, device_prober=V5E)
    # the smoke number must never masquerade as the resnet56 headline metric
    assert final["value"] is None
    assert final["fedavg_cpu_smoke_rounds_per_sec"] == 50.0
    assert final["vs_baseline"] is None
    assert "cpu smoke" in final["fedavg_note"]
    assert not partial_path.exists() or not json.loads(
        partial_path.read_text())["legs"]


def test_crashed_leg_records_error_and_continues(partial_path, capsys):
    def runner(argv, timeout):
        if "mfu_sweep" in " ".join(argv):
            raise RuntimeError("rc=1 <no output> XlaRuntimeError: oom")
        return _tpu_runner(argv, timeout)

    final = bench.run_legs(budget_s=1e6, ttl_s=1e6, runner=runner, device_prober=V5E)
    assert final["value"] == 1.25
    assert "oom" in final["cheetah_hd512_error"]
    assert "oom" in final["cheetah_moe_error"]


def test_bench_legs_env_filters_legs(partial_path, capsys, monkeypatch):
    calls = []

    def runner(argv, timeout):
        calls.append(argv)
        return _tpu_runner(argv, timeout)

    monkeypatch.setenv("BENCH_LEGS", "fedavg")
    final = bench.run_legs(budget_s=1e6, ttl_s=1e6, runner=runner,
                           device_prober=V5E)
    assert len(calls) == 1 and "--leg fedavg" in " ".join(calls[0])
    assert final["value"] == 1.25
    assert "cheetah_mfu" not in final  # unselected legs neither run nor skip
    assert "cheetah_skipped" not in final


def test_fedavg_compile_fields_pass_through(partial_path, capsys):
    """Compile wall and steady-state rounds/s are separate fields, so cache
    wins are visible in BENCH_*.json (ISSUE 1 satellite)."""

    def runner(argv, timeout):
        if "--leg fedavg" in " ".join(argv):
            return {"rounds_per_sec": 2.5, "platform": "tpu",
                    "device_kind": "TPU v5 lite", "fedavg_compile_s": 61.2,
                    "fedavg_round_fused": True, "fedavg_superround_k": 10}
        return _tpu_runner(argv, timeout)

    final = bench.run_legs(budget_s=1e6, ttl_s=1e6, runner=runner,
                           device_prober=V5E)
    assert final["value"] == 2.5
    assert final["fedavg_compile_s"] == 61.2
    assert final["fedavg_round_fused"] is True
    assert final["fedavg_superround_k"] == 10

    # the CPU smoke translation keeps them too (bench_smoke.sh reads them)
    res, platform = bench._translate_fedavg(
        {"rounds_per_sec": 9.0, "platform": "cpu", "device_kind": "cpu",
         "fedavg_compile_s": 1.5, "fedavg_round_fused": True})
    assert platform == "cpu"
    assert res["fedavg_compile_s"] == 1.5 and res["fedavg_round_fused"] is True


def test_unreachable_backend_fails_fast_with_parseable_tail(partial_path,
                                                            capsys):
    """Backend down (probe fails FAST with an error) + empty cache: legs
    shrink to the fast-fail timeout and the startup line already carries
    the probe verdict. A probe TIMEOUT must NOT shrink (a slow-but-healthy
    host can blow the probe budget and still serve 900s legs)."""
    seen_timeouts = []

    def runner(argv, timeout):
        seen_timeouts.append(timeout)
        raise subprocess.TimeoutExpired(argv, timeout)

    final = bench.run_legs(budget_s=1e6, ttl_s=1e6, runner=runner,
                           device_prober=lambda: (None, "error"))
    lines = _lines(capsys)
    assert lines[0]["bench_device_probe"] == "unreachable"
    assert all(t <= 240.0 for t in seen_timeouts)
    for name, *_ in bench.leg_specs():
        assert final[f"{name}_error"] == "leg timeout"

    # probe timeout: full leg timeouts retained, verdict disclosed
    seen_timeouts.clear()
    bench.run_legs(budget_s=1e6, ttl_s=1e6, runner=runner,
                   device_prober=lambda: (None, "timeout"))
    lines = _lines(capsys)
    assert lines[0]["bench_device_probe"] == "probe-timeout"
    assert any(t > 240.0 for t in seen_timeouts)
