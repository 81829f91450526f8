"""MLOps-lite: event tracing, metrics, and system stats.

reference: ``core/mlops/`` (2,217 LoC) — MLOpsProfilerEvent emitting
{run_id, edge_id, event_name, started/ended_time} to MQTT + wandb
(mlops_profiler_event.py:9-126), MLOpsMetrics status/metrics topics
(mlops_metrics.py:18-303), SysStats (system_stats.py:8-165), and the
``mlops.event/log/log_round_info`` facade (core/mlops/__init__.py:71-385).

TPU re-design: the platform plane (open.fedml.ai MQTT/HTTP agents) is
replaced by pluggable local sinks — python logging, a JSONL event file, and
wandb when importable — plus ``--profile_rounds`` windows of the
``jax.profiler`` (``telemetry.ProfilerWindow``) for device-level profiling. Event names used by the runtimes are kept from the reference
(train / agg / comm_c2s / server.wait) so dashboards translate 1:1.
"""

from __future__ import annotations

import atexit
import json
import logging
import os
import threading
import time
from typing import Any, Dict, List, Optional

logger = logging.getLogger("fedml_tpu.mlops")

# write-behind sink bounds: a flush happens when the buffer holds this many
# events regardless of the interval knob, so a burst can never grow the
# buffer unboundedly between interval ticks
BUFFER_EVENT_LIMIT = 256


class MLOpsStore:
    """Process-wide sink registry (reference: MLOpsStore at __init__.py:46).

    The JSONL sink is write-behind: ``_emit`` appends to ``_buffer`` and the
    emitting thread drains it to disk when ``flush_interval_s`` has elapsed
    since the last drain (or the buffer hits :data:`BUFFER_EVENT_LIMIT`, or
    someone calls :func:`flush`). ``flush_interval_s == 0`` restores the
    legacy syscall-per-event behavior. Zero-loss is guaranteed through the
    atexit-registered :func:`close` — including the preemption-drain exit 75
    path, which leaves via ``sys.exit`` and therefore runs atexit hooks.
    """

    _sink_lock = threading.Lock()
    enabled: bool = False
    run_id: str = "0"
    edge_id: int = 0
    jsonl_path: Optional[str] = None
    _jsonl_file = None
    _buffer: List[str] = []
    flush_interval_s: float = 0.5
    _last_flush: float = 0.0
    use_wandb: bool = False
    _wandb = None
    _atexit_registered: bool = False


def init(args) -> None:
    """reference: mlops.init(args) — binds run/edge ids, opens sinks."""
    if MLOpsStore._jsonl_file is not None:
        # re-init (tests, bench's post-measurement tracked pass): never leak
        # the previous run's file handle
        close()
    MLOpsStore.enabled = bool(getattr(args, "enable_tracking", False))
    MLOpsStore.run_id = str(getattr(args, "run_id", "0"))
    MLOpsStore.edge_id = int(getattr(args, "rank", 0))
    MLOpsStore.jsonl_path = None  # never point at a previous run's file
    MLOpsStore.use_wandb = False
    raw_interval = getattr(args, "tracking_flush_s", None)
    MLOpsStore.flush_interval_s = (
        0.5 if raw_interval is None else max(0.0, float(raw_interval)))
    with MLOpsStore._sink_lock:
        MLOpsStore._buffer = []
        MLOpsStore._last_flush = time.monotonic()
    if MLOpsStore.enabled:
        out_dir = str(getattr(args, "tracking_dir", "") or ".fedml_tpu_runs")
        os.makedirs(out_dir, exist_ok=True)
        MLOpsStore.jsonl_path = os.path.join(
            out_dir, f"run_{MLOpsStore.run_id}_edge_{MLOpsStore.edge_id}.jsonl"
        )
        MLOpsStore._jsonl_file = open(MLOpsStore.jsonl_path, "a")
        if bool(getattr(args, "enable_wandb", False)):
            try:
                import wandb

                MLOpsStore._wandb = wandb
                MLOpsStore.use_wandb = True
            except ImportError:
                logger.warning("wandb requested but not importable; skipping")
    from . import telemetry

    telemetry.init(args)
    if not MLOpsStore._atexit_registered:
        # durability: short runs must not lose their JSONL tail, and a
        # --profile_rounds window or --metrics_file configured WITHOUT
        # tracking still needs its trace stopped / exposition flushed when
        # the interpreter exits — so the hook registers regardless of
        # enable_tracking
        atexit.register(close)
        MLOpsStore._atexit_registered = True


def close() -> None:
    """Flush telemetry and close the JSONL sink (atexit-registered).

    Runs even when tracking is off: an open ``--profile_rounds`` trace must
    be stopped and a ``--metrics_file`` exposition force-written whether or
    not a JSONL sink exists."""
    from . import telemetry

    try:
        telemetry.close()  # summary event must land before the file shuts
    except Exception:  # pragma: no cover - shutdown must never raise
        logger.exception("telemetry close failed")
    with MLOpsStore._sink_lock:
        f, MLOpsStore._jsonl_file = MLOpsStore._jsonl_file, None
        pending, MLOpsStore._buffer = MLOpsStore._buffer, []
    if f is not None:
        try:
            if pending:
                f.write("".join(pending))
            f.flush()
            f.close()
        except OSError:
            pass


def flush() -> None:
    """Drain the write-behind buffer to disk now (shutdown paths, readers
    of the live file, and the flight recorder's post-mortem flush). Round
    records still waiting for their device scalars are realized first."""
    from . import telemetry

    telemetry.drain_records()
    with MLOpsStore._sink_lock:
        _flush_locked()


def _flush_locked() -> None:
    if MLOpsStore._jsonl_file is None or not MLOpsStore._buffer:
        MLOpsStore._last_flush = time.monotonic()
        return
    pending, MLOpsStore._buffer = MLOpsStore._buffer, []
    try:
        MLOpsStore._jsonl_file.write("".join(pending))
        MLOpsStore._jsonl_file.flush()
    except OSError:  # pragma: no cover - disk-full etc.; keep serving
        pass
    MLOpsStore._last_flush = time.monotonic()


def _emit(record: Dict[str, Any]) -> None:
    if not MLOpsStore.enabled:
        return
    record.setdefault("run_id", MLOpsStore.run_id)
    record.setdefault("edge_id", MLOpsStore.edge_id)
    record.setdefault("time", time.time())
    with MLOpsStore._sink_lock:
        if MLOpsStore._jsonl_file is not None:
            MLOpsStore._buffer.append(json.dumps(record) + "\n")
            now = time.monotonic()
            if (len(MLOpsStore._buffer) >= BUFFER_EVENT_LIMIT
                    or now - MLOpsStore._last_flush
                    >= MLOpsStore.flush_interval_s):
                _flush_locked()
    logger.debug("mlops: %s", record)


def event(event_name: str, event_started: bool = True,
          event_value: Optional[str] = None) -> None:
    """reference: mlops.event(...) → MLOpsProfilerEvent.log_event_started/
    ended; scenario code wraps train/agg/comm_c2s/server.wait phases."""
    _emit({
        "kind": "event",
        "event_name": event_name,
        "phase": "started" if event_started else "ended",
        "event_value": event_value,
    })


def log(metrics: Dict[str, Any], step: Optional[int] = None) -> None:
    """reference: mlops.log — scalar metrics (also to wandb when enabled)."""
    _emit({"kind": "metrics", "step": step, **metrics})
    if MLOpsStore.use_wandb:
        MLOpsStore._wandb.log(metrics, step=step)


def log_round_info(round_index: int, total_rounds: int) -> None:
    """reference: mlops.log_round_info (core/mlops/__init__.py:354-384)."""
    _emit({"kind": "round_info", "round_index": round_index,
           "total_rounds": total_rounds})


def log_cheetah_init(mesh: Dict[str, int],
                     loss_head_gathers_per_step: int,
                     layers: list, n_routed_experts: int,
                     experts_held: int, mhc_backward: str,
                     mixers: str, kda_path: str, kda_chunk: int,
                     objective: str, bd_block: int, attn_mask: Dict[str, Any],
                     head_dim: int, layer_pattern: str, ssd: Dict[str, Any],
                     ffn_act: str) -> None:
    """What a Cheetah trainer decided from its mesh and its configuration at
    trace time, once a run (docs/telemetry.md, ``cheetah_init``)."""
    _emit({"kind": "cheetah_init", "mesh": mesh,
           "loss_head_gathers_per_step": loss_head_gathers_per_step,
           "layers": layers, "n_routed_experts": n_routed_experts,
           "experts_held": experts_held, "mhc_backward": mhc_backward,
           "mixers": mixers, "kda_path": kda_path, "kda_chunk": kda_chunk,
           "objective": objective, "bd_block": bd_block,
           "attn_mask": attn_mask, "head_dim": head_dim,
           "layer_pattern": layer_pattern, "ssd": ssd, "ffn_act": ffn_act})


def log_training_status(status: str) -> None:
    _emit({"kind": "client_status", "status": status})


def log_aggregation_status(status: str) -> None:
    _emit({"kind": "server_status", "status": status})


def device_stats() -> list:
    """Per-accelerator memory stats (the reference's nvidia-smi fields,
    ``system_stats.py`` gpu_* — here from the jax backend's allocator)."""
    out = []
    try:
        import jax

        for d in jax.devices():
            stats = {}
            try:
                stats = d.memory_stats() or {}
            except Exception:
                pass
            used = int(stats.get("bytes_in_use", 0))
            limit = int(stats.get("bytes_limit", 0))
            out.append({
                "device": str(d),
                "kind": getattr(d, "device_kind", "?"),
                "mem_used_mb": round(used / 1e6, 1),
                "mem_limit_mb": round(limit / 1e6, 1),
                "mem_util": round(used / limit, 4) if limit else None,
                "peak_mb": round(
                    int(stats.get("peak_bytes_in_use", 0)) / 1e6, 1
                ),
            })
    except Exception:
        pass
    return out


def log_sys_perf() -> None:
    """reference: SysStats via psutil/nvidia (system_stats.py:8-165) —
    host CPU/mem plus per-device HBM utilization."""
    entry = {"kind": "sys_perf", "devices": device_stats()}
    try:
        import psutil

        p = psutil.Process()
        entry.update({
            "cpu_percent": psutil.cpu_percent(interval=None),
            "mem_rss_mb": p.memory_info().rss / 1e6,
            "mem_percent": psutil.virtual_memory().percent,
        })
    except ImportError:
        pass
    _emit(entry)


class MLOpsProfilerEvent:
    """Span helper (reference: mlops_profiler_event.py) + context manager."""

    def __init__(self, name: str):
        self.name = name
        self.t0 = 0.0

    def __enter__(self):
        self.t0 = time.perf_counter()
        event(self.name, event_started=True)
        return self

    def __exit__(self, *exc):
        event(self.name, event_started=False,
              event_value=f"{time.perf_counter() - self.t0:.6f}s")
        return False


def read_events(path: Optional[str] = None) -> List[Dict[str, Any]]:
    """Load a run's JSONL event log (test/debug helper)."""
    p = path or MLOpsStore.jsonl_path
    if p is not None and p == MLOpsStore.jsonl_path:
        flush()  # reading the live sink: drain the write-behind buffer first
    if p is None or not os.path.exists(p):
        return []
    with open(p) as f:
        return [json.loads(line) for line in f if line.strip()]


def phase_totals(events: List[Dict[str, Any]]) -> tuple:
    """Sum ``round_record`` phase durations over an event list.

    Returns ``({phase: total_seconds}, record_count)`` — the per-phase
    breakdown bench legs attach to BENCH_*.json."""
    totals: Dict[str, float] = {}
    n = 0
    for e in events:
        if e.get("kind") != "round_record":
            continue
        n += 1
        for name, dur in (e.get("phases") or {}).items():
            totals[name] = totals.get(name, 0.0) + float(dur)
    return totals, n
