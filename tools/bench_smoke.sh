#!/usr/bin/env bash
# Smoke-check the bench harness itself: a 2-round lr leg on XLA:CPU through
# the FULL orchestrator (probe -> leg subprocess -> cumulative JSON line),
# under a hard 120 s timeout. Guards the one failure mode that zeroed round 4
# (rc=124 with an empty tail): whatever happens, the bench must exit 0-ish
# fast and leave a parseable JSON tail.
#
# The leg runs its telemetry pass into BENCH_TRACKING_DIR, so this also
# asserts the observability contract: the tracked leg leaves a JSONL event
# log that read_events round-trips (with per-round RoundRecords) and a
# parseable Prometheus metrics exposition, and the bench line carries the
# per-phase breakdown.
#
# Usage: tools/bench_smoke.sh          (CI: exits non-zero on any regression)
set -uo pipefail
cd "$(dirname "$0")/.."

track_dir=$(mktemp -d /tmp/fedml_bench_smoke_track.XXXXXX)
trap 'rm -rf "$track_dir"' EXIT

out=$(timeout -k 10 240 env \
    JAX_PLATFORMS=cpu \
    BENCH_SMOKE=1 \
    BENCH_LEGS=fedavg,fedavg_million_client,fedavg_compressed_round,fedavg_wire \
    BENCH_REGISTRY_N=20000 \
    BENCH_COHORT_K=256 \
    BENCH_WIRE_DIM=262144 \
    BENCH_WIRE_REPS=3 \
    BENCH_BUDGET_S=220 \
    BENCH_MIN_LEG_S=5 \
    BENCH_LEG_TIMEOUT_S=100 \
    BENCH_CACHE_TTL_S=0 \
    BENCH_TRACKING_DIR="$track_dir" \
    python bench.py 2>/dev/null)
rc=$?

if [ "$rc" -eq 124 ] || [ "$rc" -eq 137 ]; then
    echo "bench_smoke: FAIL — bench hit the hard timeout (rc=$rc)" >&2
    exit 1
fi
if [ "$rc" -ne 0 ]; then
    echo "bench_smoke: FAIL — bench exited rc=$rc" >&2
    exit 1
fi

tail_line=$(printf '%s\n' "$out" | tail -n 1)
TRACK_DIR="$track_dir" python - "$tail_line" <<'EOF'
import json
import os
import sys

line = json.loads(sys.argv[1])
assert line["metric"] == "fedavg_rounds_per_sec_100clients_cifar10_resnet56", line
# the CPU smoke leg must have completed (not errored, not skipped)
ok = ("fedavg_cpu_smoke_rounds_per_sec" in line
      and "fedavg_error" not in line
      and "fedavg_skipped" not in line)
assert ok, f"fedavg smoke leg did not complete: {line}"

# telemetry contract: the tracked pass produced a per-phase breakdown...
assert line.get("fedavg_phases"), f"no phase breakdown in line: {line}"
assert line.get("fedavg_phase_rounds", 0) > 0, line

# ...a JSONL event log that read_events round-trips, with RoundRecords...
from fedml_tpu.core.mlops import read_events

track_dir = os.environ["TRACK_DIR"]
logs = [f for f in os.listdir(track_dir) if f.endswith(".jsonl")]
assert logs, f"no JSONL event log in {track_dir}"
events = read_events(os.path.join(track_dir, logs[0]))
records = [e for e in events if e.get("kind") == "round_record"]
assert records, f"no round_record events in {logs[0]}"

# ...and a parseable Prometheus metrics exposition
metrics_path = os.path.join(track_dir, "metrics.prom")
assert os.path.exists(metrics_path), f"no metrics file at {metrics_path}"
samples = 0
with open(metrics_path) as f:
    for raw in f:
        raw = raw.strip()
        if not raw or raw.startswith("#"):
            continue
        name, value = raw.rsplit(" ", 1)
        float(value)  # every sample line must parse
        samples += 1
assert samples > 0, "metrics exposition is empty"

# resume-overhead probe (BENCH_RESUME defaults on under BENCH_SMOKE):
# restart-to-first-dispatch must be present and sane so checkpoint-cadence
# tuning stays data-driven (docs/robustness.md)
assert "fedavg_resume_overhead_s" in line, f"no resume probe in line: {line}"
assert 0 < line["fedavg_resume_overhead_s"] < 120, line

# registry leg (fedml_tpu/scale/, scaled down to N=20k / K=256): the
# cohort substrate must sustain registry-scale rounds with ZERO
# steady-state compiles (cohort resampling is recompile-free by
# construction) and a measured prefetch overlap > 0 (docs/scale.md)
assert "fedavg_million_client_error" not in line, line
assert "fedavg_million_client_skipped" not in line, line
assert line.get("million_rounds_per_sec", 0) > 0, line
assert line.get("million_steady_compiles", -1) == 0, line
assert line.get("million_prefetch_overlap", 0) > 0, line
assert line.get("million_registry_n") == 20000, line

# delta-delivery leg (fedml_tpu/delivery/, docs/delivery.md): the delta
# path must ENGAGE (frames + decodes on the wire) and steady-state
# comm.bytes must drop >= 10x at parity accuracy (ISSUE 9 acceptance)
assert "fedavg_compressed_round_error" not in line, line
assert "fedavg_compressed_round_skipped" not in line, line
assert line.get("compressed_s2c_delta_frames", 0) > 0, line
assert line.get("compressed_c2s_delta_decodes", 0) > 0, line
assert line.get("compressed_reduction_x", 0) >= 10.0, line
acc_drop = line.get("uncompressed_acc", 1) - line.get("compressed_acc", 0)
assert acc_drop <= 0.05, f"accuracy not at parity: {line}"

# device-direct wire leg (fedml_tpu/delivery/device_codec.py, docs/
# delivery.md): the device kernels must ENGAGE (nonzero device encodes +
# decodes, zero host fallbacks in the soak) and the frames must be
# byte-identical to the host codec (the leg raises on divergence, so
# wire_parity present+true == the gate actually ran)
assert "fedavg_wire_error" not in line, line
assert "fedavg_wire_skipped" not in line, line
assert line.get("wire_parity") is True, line
assert line.get("wire_soak_ok") is True, line
assert line.get("wire_soak_device_encodes", 0) > 0, line
assert line.get("wire_soak_device_decodes", 0) > 0, line
assert line.get("wire_soak_host_fallbacks", -1) == 0, line
assert line.get("wire_host_cpu_ms_per_mb", {}).get("device_delta", 0) > 0, \
    line

print("bench_smoke: OK —",
      f"{line['fedavg_cpu_smoke_rounds_per_sec']:.2f} rounds/s,",
      f"compile {line.get('fedavg_compile_s', '?')}s,",
      f"fused={line.get('fedavg_round_fused')},",
      f"resume {line['fedavg_resume_overhead_s']:.2f}s,",
      f"registry {line['million_registry_n']}cl",
      f"@ {line['million_rounds_per_sec']:.2f} rounds/s",
      f"(overlap {line['million_prefetch_overlap']:.2f}),",
      f"delta {line['compressed_reduction_x']:.1f}x bytes",
      f"(acc {line['compressed_acc']:.3f} vs"
      f" {line['uncompressed_acc']:.3f}),",
      f"wire {line['wire_host_cpu_reduction_x']:.1f}x host-CPU",
      f"({line['wire_soak_device_encodes']} dev encodes),",
      f"{len(records)} round records, {samples} metric samples")
EOF
