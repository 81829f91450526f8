"""One cell, one run: find the cell's files by name, drive its job, reduce.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``benchmark/configs/<config>.json``) under a traffic mix
(``benchmark/traffic/<traffic>.json``). The traffic file names the job
(``benchmark/jobs/<job>.py``) that knows how to drive the program's own loop
for that kind of work; the configuration names its plain reference
(``benchmark/reference/<reference>.py``). Every per-layer metric listed for
the cell is a reader ``benchmark/layer_metrics/<metric>.py`` with one function
``read(run)``. Adding a cell, a configuration or a per-layer metric is adding
files and entries; nothing here names one.

A job module has one class ``Job(cell, seed, tracked, work_dir, log)`` with
``unit`` (what one unit of work is called), ``setup()`` (data, init, warm-up of
every shape, the reference check; returns ``{check: bool}`` and sets
``unit_s``, the steady seconds a unit takes), ``run(units, window)`` (the
program's own loop for that many units, calling ``window.start()`` and
``window.stop()`` around them; returns losses, RoundRecords and further
checks), ``throughput(units, seconds, trace)`` (its end-to-end rates by name;
``trace`` is what the profiler saw if the job called
``window.start_profiler()`` in an untraced run, else None) and
``facts(units)`` (what per-layer readers need: shapes, FLOPs a unit).

From the program the harness takes the system under test and its counters
(``telemetry``'s compile listeners, RoundRecords); clocks, the profiler, the
trace reduction, peaks, shape functions and references are the benchmark's.
"""

from __future__ import annotations

import dataclasses
import glob
import importlib.util
import json
import math
import os
import shutil
import statistics
import time
from typing import Any, Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: str

    @property
    def job(self) -> str:
        return str(self.traffic["job"])


def _read_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def find_file(root: str, *parts: str) -> str:
    """``<root>/<parts>``, or the same path in this checkout where ``root`` is
    another directory that lacks it: a test keeps only the files it adds."""
    for base in dict.fromkeys((root, ROOT)):
        path = os.path.join(base, *parts)
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(os.path.join(root, *parts))


def _applies(metric: Dict[str, Any], cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files read."""
    spec = _read_json(os.path.join(root, "BENCHMARK.json"))
    entries = {w["name"]: w for w in spec["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(entries)}")
    entry = entries[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _read_json(find_file(root, configs[entry["config"]]["file"]))
    traffic = _read_json(find_file(
        root, "benchmark", "traffic", entry["traffic"] + ".json"))
    end_to_end = [m for m in spec["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in end_to_end}
    return Cell(
        name=name, chips=int(entry["chips"]), config=config, traffic=traffic,
        end_to_end=end_to_end,
        per_layer=[m for m in spec["per_layer"]
                   if _applies(m, name) and m["moves"] in reported],
        root=root,
    )


def load_module(root: str, kind: str, name: str):
    """``<root>/benchmark/<kind>/<name>.py`` as a module. By path, so that a
    name may hold a dot and a test may keep its files in a directory of its
    own."""
    path = find_file(root, "benchmark", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def peaks_for(device_kind: str, root: str = ROOT) -> Dict[str, float]:
    table = _read_json(find_file(root, "benchmark", "peaks.json"))["kinds"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r} in benchmark/peaks.json"
            f" (known: {sorted(table)}); add it with its source")
    return table[device_kind]


# ---------------------------------------------------------------------------
# what every job needs from the program's tracking
# ---------------------------------------------------------------------------


def tracking_arguments(cell: Cell, seed: int, tracked: bool,
                       work_dir: str) -> Dict[str, Any]:
    """Program arguments that switch its RoundRecords on (traced runs only)
    and keep what it writes inside ``work_dir``. The JSONL sink appends, so a
    file an earlier run of the same cell and seed left is removed first."""
    run_id = f"{cell.name}_seed{seed}"
    runs = os.path.join(work_dir, "runs")
    stale = os.path.join(runs, f"run_{run_id}_edge_0.jsonl")
    if tracked and os.path.exists(stale):
        os.remove(stale)
    return {
        "enable_tracking": tracked, "tracking_dir": runs, "run_id": run_id,
        # a directory that holds no dataset: the synthetic fallback, from the seed
        "data_cache_dir": os.path.join(work_dir, "no_dataset_here"),
    }


def last_round_records(units: int, tracked: bool) -> List[Dict[str, Any]]:
    """The RoundRecords of the last ``units`` rounds or steps; closes the sink."""
    if not tracked:
        return []
    from fedml_tpu.core import mlops

    records = [e for e in mlops.read_events() if e.get("kind") == "round_record"]
    mlops.close()
    return records[-units:]


def flops_function(cell: Cell):
    """The shape function the configuration names (``flops.module`` under
    ``benchmark/flops/``, ``flops.function`` in it)."""
    named = cell.config["flops"]
    return getattr(load_module(cell.root, "flops", named["module"]),
                   named["function"])


# ---------------------------------------------------------------------------
# the timed window
# ---------------------------------------------------------------------------


def compile_counters() -> Dict[str, float]:
    """The program's ``jax.monitoring`` counters, as ``telemetry`` keeps them."""
    from fedml_tpu.core.mlops import telemetry

    snap = telemetry.registry().snapshot()
    counters = snap["counters"]
    return {
        "compiles": float(counters.get("jax.compiles", 0)),
        "compile_s": float(snap["histograms"].get(
            "jax.compile.seconds", {}).get("sum", 0.0)),
        "cache_hits": float(counters.get("jax.compilation_cache.hits", 0)),
        "cache_misses": float(counters.get("jax.compilation_cache.misses", 0)),
    }


class Window:
    """The timed window. The job calls ``start()`` when the device is idle and
    the next thing it does is the first unit of work, and ``stop()`` when the
    last unit is done and the device is idle again. The profiler runs over
    exactly that span in a traced run (``whole``), and in an untraced one only
    from where a job that reads the device's clock calls ``start_profiler()``
    to the window's end."""

    def __init__(self, trace_dir: str, whole: bool):
        self.trace_dir, self.whole = trace_dir, whole
        self.profiling = False
        self.t0 = self.t1 = None
        self.before: Dict[str, float] = {}
        self.after: Dict[str, float] = {}

    def start_profiler(self) -> None:
        if self.profiling:
            return
        import jax

        options = jax.profiler.ProfileOptions()
        # device planes only. The program has no annotations to read, and
        # host tracing is not free: at level 1 every host-to-device
        # transfer is an event, and the loop's evaluation batches took
        # 50 ms each under it against 5 ms without (my chip run, PR 22)
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self.profiling = True

    def start(self) -> None:
        if self.t0 is not None:
            raise RuntimeError("the window was started twice")
        if self.whole:
            self.start_profiler()
        self.before = compile_counters()
        self.t0 = time.perf_counter()

    def stop(self) -> None:
        self.t1 = time.perf_counter()
        self.after = compile_counters()
        if self.profiling:
            import jax

            jax.profiler.stop_trace()
            self.profiling = False

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    @property
    def compiles(self) -> int:
        return int(self.after["compiles"] - self.before["compiles"])


@dataclasses.dataclass
class TracedRun:
    """What a per-layer reader may read."""

    cell: Cell
    facts: Dict[str, Any]            # the job's: units, shapes, FLOPs per unit
    records: List[Dict[str, Any]]    # the window's RoundRecords, in order
    counters: Dict[str, float]       # compile counters at the window's start
    peaks: Dict[str, float]
    trace: Any                       # trace_reduce.Trace, or None


def _trace_file(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _phase_spans(records: List[Dict[str, Any]], origin_epoch_s: float):
    """(name, start, end) of every RoundRecord phase on the trace's clock.
    A record is stamped when it closes and holds its wall time and the
    durations of its phases in the order they ran; they are laid end to end
    from the record's start."""
    spans = []
    for rec in records:
        t = float(rec["time"]) - float(rec["wall_s"]) - origin_epoch_s
        for name, dur in (rec.get("phases") or {}).items():
            spans.append((f"{name} (unit {rec['round_idx']})", t, t + float(dur)))
            t += float(dur)
    return spans


def breakdown(trace, records, top_ops: int = 10, top_gaps: int = 5):
    """The ledger's only view of the trace: the device ops with most time
    under XLA's names, and the longest idle gaps of chip 0 by what the host
    was doing (the RoundRecord phase the middle of the gap falls in; the
    program has no trace annotations to say more)."""
    from benchmark import trace_reduce as tr

    dev = trace.devices[0]
    window = tr.device_window(trace)
    idle = sorted(tr.gaps(tr.busy_intervals(dev), *window),
                  key=lambda g: g[0] - g[1])[:top_gaps]
    spans = (_phase_spans(records, trace.start_epoch_ns * 1e-9)
             if trace.start_epoch_ns else [])
    labelled = []
    for s, e in idle:
        mid = (s + e) / 2
        label = next((n for n, a, b in spans if a <= mid < b),
                     "between units (evaluation, bookkeeping)" if spans
                     else "unattributed")
        labelled.append([label, e - s])
    return {
        "device_ops": [[n, s] for n, s in tr.op_table(dev.ops, top_ops)],
        "idle_gaps": labelled,
    }


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def require_devices(chips: int):
    """The TPU devices, or SystemExit naming what JAX found instead."""
    import sys

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        sys.stderr.write(
            f"benchmark: this cell needs {chips} TPU chip(s), but JAX reports "
            f"{len(devices)} device(s) of platform {devices[0].platform!r} "
            f"({devices[0].device_kind!r}). There is no CPU fallback: run it "
            f"through the chip tool.\n")
        raise SystemExit(1)
    return devices


def memory_peak_bytes(devices) -> int:
    """High-water mark of the fullest chip. On this runtime a program's temp
    buffers are not in ``peak_bytes_in_use``: they are a reservation that is
    sized by the largest program and kept (``peak_bytes_reserved``; a program
    with 2.15 GB of temps moved the first by 0.002 GB and the second by 2.15
    GB, my chip run, PR 22). The chip holds both, so the peak is their sum."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: Optional[float] = None, work_dir: Optional[str] = None,
             keep_trace: bool = False,
             log: Callable[[str], None] = lambda s: None) -> Dict[str, Any]:
    """Set up, warm up, check against the reference, measure one window and
    return the result line as a dict. ``t_start`` is the process's start on
    ``time.perf_counter``'s clock: set-up is everything from there to the
    window."""
    import jax

    from fedml_tpu.core.mlops import telemetry

    t_start = time.perf_counter() if t_start is None else t_start
    work_dir = work_dir or os.path.join(cell.root, "chiprun_out", "benchmark",
                                        cell.name)
    os.makedirs(work_dir, exist_ok=True)
    telemetry.install_jax_listeners()  # count compiles from the first jit on
    devices = jax.devices()

    runner = load_module(cell.root, "jobs", cell.job).Job(
        cell, seed=seed, tracked=trace, work_dir=work_dir, log=log)
    checks = dict(runner.setup())  # name -> bool, all must hold
    log(f"set-up done in {time.perf_counter() - t_start:.1f}s: unit "
        f"{runner.unit_s:.4f}s, checks {checks}")

    trace_dir = os.path.join(work_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    units = (int(cell.traffic["trace_units"]) if trace else
             max(int(cell.traffic["min_units"]),
                 int(seconds / runner.unit_s)))
    window = Window(trace_dir, whole=trace)
    outcome = runner.run(units, window)  # the program's own loop
    if window.t0 is None or window.t1 is None:
        raise RuntimeError(f"job {cell.job!r} never opened or closed the window")
    setup_s = window.t0 - t_start

    losses = [float(x) for x in outcome["losses"]]
    failed = sum(1 for x in losses if not math.isfinite(x))
    checks["losses_finite"] = failed == 0 and len(losses) == units
    checks["loss_falls"] = bool(losses) and losses[-1] < outcome["first_loss"]
    checks["zero_compiles_in_window"] = window.compiles == 0
    checks.update(outcome.get("checks", {}))
    log(f"window: {units} {runner.unit}s in {window.seconds:.3f}s, "
        f"{window.compiles} compile(s), first loss {outcome['first_loss']:.4f}"
        f", window losses {losses[:2]} .. {losses[-2:]}; checks {checks}")

    result: Dict[str, Any] = {
        "correct": all(checks.values()),
        "attempted": units,
        "failed": failed,
        "metrics": {},
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": memory_peak_bytes(devices),
        },
    }
    from benchmark import trace_reduce as tr

    path = _trace_file(trace_dir)
    reduced = tr.load(path) if path else None
    if reduced is not None and not reduced.devices:
        reduced = None  # a trace with no device plane (XLA:CPU in the tests)
    if not keep_trace:
        shutil.rmtree(trace_dir, ignore_errors=True)

    if not trace:
        values = dict(runner.throughput(units, window.seconds, reduced))
        values["peak_hbm_gb"] = result["device"]["memory_peak_bytes"] / 1e9
        values["setup_s"] = setup_s
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
        return result

    run = TracedRun(cell=cell, facts=runner.facts(units),
                    records=outcome["records"], counters=window.before,
                    peaks=(peaks_for(devices[0].device_kind, cell.root)
                           if devices[0].platform == "tpu" else {}),
                    trace=reduced)
    for m in cell.per_layer:
        value = load_module(cell.root, "layer_metrics", m["name"]).read(run)
        if value is not None:
            result["metrics"][m["name"]] = {"value": float(value),
                                            "unit": m["unit"]}
    if reduced is not None:
        lo, hi = tr.device_window(reduced)
        result["device"]["busy_s"] = statistics.fmean(
            tr.total(tr.busy_intervals(d)) for d in reduced.devices)
        result["device"]["window_s"] = hi - lo
        result["breakdown"] = breakdown(reduced, outcome["records"])
    return result
