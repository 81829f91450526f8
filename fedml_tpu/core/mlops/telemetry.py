"""Round telemetry plane: metrics registry, phase spans, profiler windows.

The reference ships a 2,217-LoC MLOps plane (MLOpsProfilerEvent spans,
MLOpsMetrics, SysStats) whose unit of observation is a *message* — fine for
an actor federation, blind for this port where PR 1 collapsed a whole FedAvg
round into one donated XLA dispatch. The unit of observation here is the
**round** (or the Cheetah step): where inside it time goes (sample / gather /
prep / dispatch, or data / h2d / step / loss_sync), how many rounds the host
runs ahead of the device, how HBM grows, and how often XLA recompiles.

Three layers, all process-wide:

- :class:`MetricsRegistry` — counters, gauges, and fixed-bucket histograms
  with p50/p95/p99 interpolation. Counter bumps are a dict update under a
  lock (always on — the comm plane counts bytes/messages whether or not a
  run is tracked). Rendered as Prometheus text exposition to
  ``--metrics_file``.
- **RoundRecord** — one structured JSONL event per round: phase span
  durations, the spans themselves on the profiler's clock (``spans``: name,
  ``ts_ns``, ``dur_ns``, ``parent``), HBM used/peak from
  :func:`device_stats`, examples processed, a rounds/s EMA, and compile
  events (via ``jax.monitoring`` listeners, which also count
  persistent-compilation-cache hits/misses and name each compiled program
  in a ``compile`` event).
- **Profiler windows** — ``--profile_rounds N:M`` opens a ``jax.profiler``
  trace for rounds [N, M) and closes it after, no code changes in the run.

Zero-cost contract: with tracking disabled, :func:`begin_round` returns
``None`` after one boolean check and :func:`phase` returns a shared no-op
context manager. Tracking on or off, the fused round path performs NO host
sync: a closed record waits on a pending queue and its device scalars are
read only once ``is_ready()`` says they are there (or at the loop's end) —
pinned by ``tests/test_telemetry.py``.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import itertools
import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

# latency buckets in seconds: 100 µs .. 2 min, the dispatch-to-superround span
DEFAULT_BUCKETS: Tuple[float, ...] = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0,
)

# Peak dense bf16 FLOP/s per chip, keyed by ``jax.Device.device_kind`` — the
# one MFU denominator (runner, bench legs and tools all read it here).
# Source: Google Cloud TPU documentation, the per-generation "System
# architecture" pages (v4 275, v5e 197, v5p 459, v6e 918 TFLOP/s). libtpu
# reports the v5e as "TPU v5 lite" and the v6e as "TPU v6 lite".
PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def peak_bf16_flops(device) -> Optional[float]:
    """Peak bf16 FLOP/s of ``device`` (a ``jax.Device``). ``None`` off-TPU,
    where MFU is "not measured"; a TPU kind missing from the table is an
    error, never a default."""
    if device.platform != "tpu":
        return None
    peak = PEAK_BF16_FLOPS.get(device.device_kind)
    if peak is None:
        raise KeyError(
            f"no peak FLOP/s for TPU device_kind {device.device_kind!r}; "
            f"add it to telemetry.PEAK_BF16_FLOPS with its source"
        )
    return peak


class Histogram:
    """Fixed-bucket histogram with interpolated quantiles."""

    __slots__ = ("buckets", "counts", "count", "sum")

    def __init__(self, buckets: Tuple[float, ...] = DEFAULT_BUCKETS):
        self.buckets = tuple(buckets)
        self.counts = [0] * (len(self.buckets) + 1)  # +1 overflow bucket
        self.count = 0
        self.sum = 0.0

    def observe(self, value: float) -> None:
        self.counts[bisect.bisect_left(self.buckets, value)] += 1
        self.count += 1
        self.sum += value

    def quantile(self, q: float) -> Optional[float]:
        """Linear interpolation inside the bucket holding quantile ``q``."""
        if self.count == 0:
            return None
        target = q * self.count
        acc = 0.0
        lo = 0.0
        for i, c in enumerate(self.counts):
            hi = self.buckets[i] if i < len(self.buckets) else self.buckets[-1]
            if c and acc + c >= target:
                if i >= len(self.buckets):  # overflow: no upper bound
                    return max(hi, self.sum / self.count)
                return lo + (hi - lo) * ((target - acc) / c)
            acc += c
            lo = hi
        return self.buckets[-1]

    def summary(self) -> Dict[str, Any]:
        return {
            "count": self.count,
            "sum": round(self.sum, 6),
            "p50": self.quantile(0.50),
            "p95": self.quantile(0.95),
            "p99": self.quantile(0.99),
        }


class MetricsRegistry:
    """Process-wide counters / gauges / histograms (thread-safe)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, float] = {}
        self._gauges: Dict[str, float] = {}
        self._hists: Dict[str, Histogram] = {}

    # -- write side ---------------------------------------------------------
    def inc(self, name: str, value: float = 1.0) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0.0) + value

    def gauge_set(self, name: str, value: float) -> None:
        with self._lock:
            self._gauges[name] = float(value)

    def observe(self, name: str, value: float,
                buckets: Tuple[float, ...] = DEFAULT_BUCKETS) -> None:
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = Histogram(buckets)
            h.observe(float(value))

    # -- read side ----------------------------------------------------------
    def counter(self, name: str) -> float:
        with self._lock:
            return self._counters.get(name, 0.0)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "histograms": {k: h.summary() for k, h in self._hists.items()},
            }

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()

    # -- Prometheus text exposition ----------------------------------------
    @staticmethod
    def _prom_name(name: str) -> str:
        safe = "".join(c if (c.isalnum() or c == "_") else "_" for c in name)
        return f"fedml_{safe}"

    def render_prometheus(self) -> str:
        """Text exposition: counters/gauges as single samples, histograms as
        cumulative ``_bucket{le=...}`` series only — a histogram family must
        not mix in summary-style quantile samples or expfmt parsers reject
        the whole file (quantiles stay available via ``snapshot()`` and
        ``histogram_quantile()`` server-side)."""
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            hists = {
                k: (h.buckets, list(h.counts), h.count, h.sum)
                for k, h in self._hists.items()
            }
        lines: List[str] = []
        for name, v in sorted(counters.items()):
            pn = self._prom_name(name) + "_total"
            lines += [f"# TYPE {pn} counter", f"{pn} {v:g}"]
        for name, v in sorted(gauges.items()):
            pn = self._prom_name(name)
            lines += [f"# TYPE {pn} gauge", f"{pn} {v:g}"]
        for name, (buckets, counts, count, total) in sorted(hists.items()):
            pn = self._prom_name(name)
            lines.append(f"# TYPE {pn} histogram")
            acc = 0
            for le, c in zip(buckets, counts):
                acc += c
                lines.append(f'{pn}_bucket{{le="{le:g}"}} {acc}')
            lines.append(f'{pn}_bucket{{le="+Inf"}} {count}')
            lines.append(f"{pn}_sum {total:g}")
            lines.append(f"{pn}_count {count}")
        return "\n".join(lines) + "\n"


_REG = MetricsRegistry()


def registry() -> MetricsRegistry:
    return _REG


def counter_inc(name: str, value: float = 1.0) -> None:
    _REG.inc(name, value)


def gauge_set(name: str, value: float) -> None:
    _REG.gauge_set(name, value)


def observe(name: str, value: float) -> None:
    _REG.observe(name, value)


# ---------------------------------------------------------------------------
# Run-scoped telemetry (the serving plane's world-keyed metrics facade)
# ---------------------------------------------------------------------------


class TelemetryScope:
    """A run identity's view of a metrics registry.

    Serving-plane handler/worker code bumps counters through the scope
    carried on its :class:`~fedml_tpu.core.world.WorldScope`
    (``self.world.telemetry.counter_inc(...)``) instead of the module
    helpers — the process-wide registry is then reachable from a handler
    only through an explicit run discriminator (graftiso I002,
    docs/graftiso.md). In a single-tenant process the default scope wraps
    the process-global registry, so every existing counter name, the
    Prometheus exposition, and ``fedml_tpu top`` are unchanged; the
    multi-tenant serving plane installs dedicated per-run registries via
    :func:`install_scope` without touching a single call site.
    """

    __slots__ = ("run_id", "registry")

    def __init__(self, run_id: Optional[str] = None,
                 registry: Optional[MetricsRegistry] = None):
        self.run_id = run_id
        self.registry = registry if registry is not None else MetricsRegistry()

    def counter_inc(self, name: str, value: float = 1.0) -> None:
        self.registry.inc(name, value)

    def gauge_set(self, name: str, value: float) -> None:
        self.registry.gauge_set(name, value)

    def observe(self, name: str, value: float,
                buckets: Tuple[float, ...] = DEFAULT_BUCKETS) -> None:
        self.registry.observe(name, value, buckets)

    def counter(self, name: str) -> float:
        return self.registry.counter(name)

    def snapshot(self) -> Dict[str, Any]:
        return self.registry.snapshot()


_DEFAULT_SCOPE = TelemetryScope(run_id=None, registry=_REG)

# dedicated per-run scopes (multi-tenant serving): run_id -> scope.
# Accessed only through scope_for/install_scope with the run discriminator.
_SCOPES: Dict[str, TelemetryScope] = {}
_SCOPES_LOCK = threading.Lock()


def default_scope() -> TelemetryScope:
    """The process-global scope (wraps the module registry)."""
    return _DEFAULT_SCOPE


def scope_for(run_id: Optional[str] = None) -> TelemetryScope:
    """The telemetry scope for a run identity.

    Returns the process-global default unless a dedicated scope was
    installed for ``run_id`` (:func:`install_scope` — the multi-tenant
    hook), so single-tenant behavior is bitwise what it always was."""
    if run_id is None:
        return _DEFAULT_SCOPE
    with _SCOPES_LOCK:
        return _SCOPES.get(str(run_id), _DEFAULT_SCOPE)


def install_scope(run_id: str) -> TelemetryScope:
    """Create (or return) a dedicated registry-backed scope for a run —
    the multi-tenant serving plane's per-tenant metrics namespace."""
    with _SCOPES_LOCK:
        scope = _SCOPES.get(str(run_id))
        if scope is None:
            scope = _SCOPES[str(run_id)] = TelemetryScope(run_id=str(run_id))
        return scope


def uninstall_scope(run_id: str) -> None:
    with _SCOPES_LOCK:
        _SCOPES.pop(str(run_id), None)


# ---------------------------------------------------------------------------
# Process state + init
# ---------------------------------------------------------------------------


class _State:
    enabled: bool = False
    metrics_file: Optional[str] = None
    profiler: Optional["ProfilerWindow"] = None
    ema_rounds_per_sec: Optional[float] = None
    # (perf_counter, rounds opened) of the last begin_round: the EMA's period
    last_begin: Optional[Tuple[float, int]] = None
    last_metrics_write: float = 0.0
    metrics_write_interval_s: float = 2.0


# per thread: .record — the in-flight RoundRecord, if any; .last — the record
# that closed last, which still takes the spans that run between rounds;
# .stack — the open spans, innermost last
_TLS = threading.local()

# guards _State's mutable run-state (EMA, metrics-file throttle) and the
# metrics tmp-file write: cross-silo rounds close on a comm receive thread
# while close()/atexit and the sys-perf sampler touch the same state
# (graftlint G005)
_STATE_LOCK = threading.Lock()

# closed records whose device scalars are not realized yet, oldest first.
# ``deque`` appends and pops are atomic; _DRAIN_LOCK keeps one drainer at a
# time so that records reach the sink in round order
_PENDING: "collections.deque[RoundRecord]" = collections.deque()
_DRAIN_LOCK = threading.RLock()

_SPAN_IDS = itertools.count(1)


def enabled() -> bool:
    return _State.enabled


def set_enabled(flag: bool) -> None:
    """Test / embedding hook; normal runs go through :func:`init`."""
    _State.enabled = bool(flag)


def init(args) -> None:
    """Configure the plane from a run's args (called by ``mlops.init``)."""
    _State.enabled = bool(getattr(args, "enable_tracking", False))
    drain_records()  # what an earlier run in this process left pending
    _State.metrics_file = str(getattr(args, "metrics_file", "") or "") or None
    _State.ema_rounds_per_sec = None
    _State.last_begin = None
    _State.last_metrics_write = 0.0
    _TLS.record = None
    _TLS.last = None
    spec = str(getattr(args, "profile_rounds", "") or "")
    if spec:
        log_dir = (str(getattr(args, "profile_dir", "") or "")
                   or str(getattr(args, "tracking_dir", "") or "")
                   or ".fedml_tpu_runs")
        _State.profiler = ProfilerWindow.parse(spec, log_dir)
    else:
        _State.profiler = None
    if _State.enabled:
        install_jax_listeners()


def close() -> None:
    """Flush-and-summarise hook (run at ``mlops`` shutdown, before the JSONL
    sink closes): force the metrics file out and emit one summary event with
    the full registry snapshot so ``fedml cache`` / post-mortems can read
    compile-cache hit rates from the run log alone."""
    prof = _State.profiler
    if prof is not None and prof.active:
        prof.force_stop()
    drain_records()
    if _State.enabled:
        from . import _emit

        _emit({"kind": "telemetry_summary", "metrics": _REG.snapshot(),
               "rounds_per_sec_ema": _State.ema_rounds_per_sec})
    write_metrics_file(force=True)


def write_metrics_file(force: bool = False) -> Optional[str]:
    """Write the Prometheus exposition to ``--metrics_file`` (throttled).

    The throttle check-and-set and the tmp-file write/replace both run under
    ``_STATE_LOCK``: two threads racing the same ``.tmp`` path would corrupt
    the exposition file."""
    path = _State.metrics_file
    if path is None:
        return None
    import os

    now = time.monotonic()
    with _STATE_LOCK:
        if (not force and now - _State.last_metrics_write
                < _State.metrics_write_interval_s):
            return None
        _State.last_metrics_write = now
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write(_REG.render_prometheus())
        os.replace(tmp, path)
    return path


# ---------------------------------------------------------------------------
# jax.monitoring listeners: compile events + compilation-cache hit/miss
# ---------------------------------------------------------------------------

_LISTENERS_INSTALLED = False

_EVENT_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "jax.compilation_cache.hits",
    "/jax/compilation_cache/cache_misses": "jax.compilation_cache.misses",
}


def install_jax_listeners() -> bool:
    """Count XLA compiles and persistent-cache hits/misses into the registry.

    ``jax.monitoring`` has no unregister API, so this installs once per
    process; the listeners only touch the registry (no jax state). The
    install-once latch is checked AND flipped under ``_STATE_LOCK``
    (graftiso I001): two runs initialising on different threads — the
    multi-tenant shape — must not both register and double-count every
    compile."""
    global _LISTENERS_INSTALLED
    try:
        from jax import monitoring
    except ImportError:  # pragma: no cover - jax is a hard dep in practice
        return False

    def on_event(event: str, **kw) -> None:
        name = _EVENT_COUNTERS.get(event)
        if name is not None:
            _REG.inc(name)

    def on_duration(event: str, duration_secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            _REG.inc("jax.compiles")
            _REG.observe("jax.compile.seconds", duration_secs)
            if _State.enabled:
                _emit_compile(str(kw.get("fun_name", "")), duration_secs)
        elif event == "/jax/compilation_cache/compile_time_saved_sec":
            _REG.inc("jax.compilation_cache.time_saved_s", duration_secs)

    with _STATE_LOCK:
        if _LISTENERS_INSTALLED:
            return True
        monitoring.register_event_listener(on_event)
        monitoring.register_event_duration_secs_listener(on_duration)
        _LISTENERS_INSTALLED = True
    return True


_COMPILE_SEEN = {"hits": 0.0, "misses": 0.0}


def _emit_compile(fun_name: str, seconds: float) -> None:
    """One ``compile`` event per backend compile: the program's name as JAX
    passes it to the listener (``jit(_train_step_raw)``), its seconds, and
    how far the persistent cache's counters moved since the previous compile,
    so a recompile or a cache miss names its program."""
    from . import _emit

    hits = _REG.counter("jax.compilation_cache.hits")
    misses = _REG.counter("jax.compilation_cache.misses")
    with _STATE_LOCK:
        seen = _COMPILE_SEEN
        # a registry reset (tests) starts the counters again from zero
        d_hits = hits - (seen["hits"] if hits >= seen["hits"] else 0.0)
        d_misses = misses - (seen["misses"] if misses >= seen["misses"]
                             else 0.0)
        seen["hits"], seen["misses"] = hits, misses
    _emit({"kind": "compile", "fun_name": fun_name,
           "seconds": round(float(seconds), 6),
           "cache_hits": int(d_hits), "cache_misses": int(d_misses)})


# ---------------------------------------------------------------------------
# Which scope every instruction of a compiled program belongs to
# ---------------------------------------------------------------------------


def compiles() -> float:
    """Backend compiles and persistent-cache loads so far (``jax.compiles``):
    a jitted call around which this moved has made itself a new program."""
    return _REG.counter("jax.compiles")


def abstract_of(args: Any) -> Any:
    """``args`` with every ``jax.Array`` leaf replaced by its shape, dtype,
    weak type and, where it is committed to one, its sharding: what
    ``jitted.lower`` needs to find the program that a call with ``args``
    compiled; a donated array still says all of these. (An uncommitted
    array's placement is the call's to choose; a sharding named for it would
    be another program.)"""
    import jax

    def leaf(x):
        if not isinstance(x, jax.Array):
            return x
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, weak_type=x.weak_type,
            sharding=x.sharding if x.committed else None)

    return jax.tree.map(leaf, args)


def record_program_scopes(lowered, vocabulary: Tuple[str, ...]) -> None:
    """One ``program_scopes`` event for a compiled program: the scope path
    and pass of every instruction of its optimized HLO
    (``scopes.program_map``), under the instruction names a profiler trace
    prints on its ``XLA Ops`` line. ``lowered`` is ``jitted.lower(..)`` of
    :func:`abstract_of` the arguments a call has just had: its ``compile()``
    then hands back the call's own executable, and the map is read from what
    runs, so it agrees with the trace even where the persistent cache served
    an executable compiled under other names (docs/telemetry.md). Where the
    lowering found another program than the call's, nothing is published:
    compiling a step a second time to name its instructions is not worth it.
    Callers publish once per compiled program (a call around which
    :func:`compiles` moved) and only where :func:`enabled`; a no-op after
    one bool check otherwise."""
    if not _State.enabled:
        return
    from . import _emit
    from . import scopes

    # private to jax (0.9.0): the lowering's cached executable, set by the
    # call path's compile; None or absent means compile() would compile
    if getattr(getattr(lowered, "_lowering", None), "_executable", None) is None:
        _REG.inc("program_scopes.skipped")
        logging.getLogger(__name__).warning(
            "program_scopes: the lowered program is not the one the call "
            "compiled; not published")
        return
    found = scopes.program_map(lowered.compile().as_text(), vocabulary)
    module = found.pop("module")
    _emit({"kind": "program_scopes",
           "program": module[len("jit_"):] if module.startswith("jit_") else module,
           "module": module, **found})


# ---------------------------------------------------------------------------
# Phase spans
# ---------------------------------------------------------------------------


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


def _span_stack() -> list:
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    return stack


class _Span:
    """One open span: a duration for ``phases`` and the histogram, a
    ``spans`` entry on the epoch clock (the clock of the xplane's
    ``profile_start_time``), and a ``TraceAnnotation`` so that a
    ``--profile_rounds`` trace shows it beside the device lines."""

    __slots__ = ("name", "record", "span_id", "parent", "ts_ns", "t0_ns",
                 "_annotation")

    def __init__(self, name: str, record: bool = True):
        self.name = name
        self.record = record
        self.span_id = next(_SPAN_IDS)
        self.parent: Optional[int] = None
        self.ts_ns = self.t0_ns = 0
        self._annotation = None

    def __enter__(self):
        import jax

        stack = _span_stack()
        self.parent = stack[-1].span_id if stack else None
        stack.append(self)
        rec = getattr(_TLS, "record", None) or getattr(_TLS, "last", None)
        self._annotation = jax.profiler.TraceAnnotation(
            self.name, unit=-1 if rec is None else rec.round_idx)
        self._annotation.__enter__()
        self.ts_ns = time.time_ns()
        self.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur_ns = time.perf_counter_ns() - self.t0_ns
        self._annotation.__exit__(*exc)
        stack = _span_stack()
        if self in stack:
            stack.remove(self)
        rec = getattr(_TLS, "record", None)
        if rec is not None:
            if self.record:
                rec.phases[self.name] = (rec.phases.get(self.name, 0.0)
                                         + dur_ns * 1e-9)
        else:
            # between rounds (evaluation, logging, checkpoint): the record
            # that closed last takes the span until the next one opens
            rec = getattr(_TLS, "last", None)
        if rec is not None and not rec.emitted:
            rec.spans.append({"name": self.name, "span": self.span_id,
                              "parent": self.parent, "ts_ns": self.ts_ns,
                              "dur_ns": dur_ns})
        _REG.observe(f"phase.{self.name}.seconds", dur_ns * 1e-9)
        return False


def phase(name: str, record: bool = True):
    """Span context manager, the one way the loops open a span: its duration
    goes to the in-flight RoundRecord's ``phases`` and to the
    ``phase.<name>.seconds`` histogram, the span itself (``ts_ns``,
    ``dur_ns``, ``parent``) to the record's ``spans``. A span that closes
    while no record is open lands in the ``spans`` of the record that closed
    last. A shared no-op when tracking is disabled.

    ``record=False`` keeps the span and the histogram but stays out of
    ``phases`` — for sub-spans nested inside a recorded phase (the mesh
    engine's placement spans run inside the sp base's sample/prep spans),
    whose double-counted time would push a record's phase sum past its wall."""
    if not _State.enabled:
        return _NULL_SPAN
    return _Span(name, record)


# ---------------------------------------------------------------------------
# RoundRecord lifecycle
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RoundRecord:
    """One round's (or one Cheetah step's) structured telemetry."""

    round_idx: int
    fused: bool = False
    superround: bool = False
    # FedAvg-family rounds: clients of the cohort that train in one batched
    # program (the cohort size where the cohort is one vmap)
    cohort_chunk: Optional[int] = None
    phases: Dict[str, float] = dataclasses.field(default_factory=dict)
    # every span that closed under this record or after it, before the next
    # record opened: {name, span, parent, ts_ns (epoch), dur_ns}
    spans: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    wall_s: float = 0.0
    time: float = 0.0  # epoch seconds at end_round: time - wall_s is the start
    # where the loop itself waits for the device (Cheetah's loss_sync):
    # dispatch → loss on the host. Null for a FedAvg round, which waits nowhere
    dispatch_latency_s: Optional[float] = None
    # earlier rounds dispatched and not yet ready when this one opened
    in_flight: int = 0
    examples: Optional[float] = None
    train_loss: Optional[float] = None
    # further device scalars of the unit, by name (a Cheetah step's routing
    # counters); realized with the two above
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    rounds_per_sec_ema: Optional[float] = None
    hbm_used_mb: Optional[float] = None
    hbm_peak_mb: Optional[float] = None
    compiles: int = 0
    # device scalars realized when the record leaves the pending queue
    lazy: Dict[str, Any] = dataclasses.field(default_factory=dict)
    emitted: bool = False
    t0_ns: int = 0
    _compiles0: float = 0.0
    _step_annotation: Any = None

    def to_event(self) -> Dict[str, Any]:
        skip = ("lazy", "emitted", "t0_ns", "_compiles0", "_step_annotation")
        d = {f.name: getattr(self, f.name) for f in dataclasses.fields(self)
             if f.name not in skip}
        d["phases"] = {k: round(v, 6) for k, v in self.phases.items()}
        d["spans"] = list(self.spans)
        d["wall_s"] = round(self.wall_s, 6)
        return {"kind": "round_record", **d}

    def ready(self) -> bool:
        """Are the record's device scalars on the host side already?"""
        return all(getattr(v, "is_ready", lambda: True)()
                   for v in self.lazy.values())


def current_record() -> Optional[RoundRecord]:
    return getattr(_TLS, "record", None)


def record_lazy(name: str, value: Any) -> None:
    """Stash a device scalar on the in-flight record; realized when the
    record is emitted. No-op without an active record."""
    rec = getattr(_TLS, "record", None)
    if rec is not None:
        rec.lazy[name] = value


def begin_round(round_idx: int, fused: bool = False,
                superround: bool = False, unit: str = "round",
                cohort_chunk: Optional[int] = None) -> Optional[RoundRecord]:
    """Open a RoundRecord; ``None`` (after one bool check) when disabled.

    Emits the earlier records whose device scalars are ready, in order, and
    never waits for one (the ``emit_record`` span). Called inside a span (the loops' ``hooks``), the
    record starts where that span started, so the span lies within it. The
    round or step runs under a ``StepTraceAnnotation`` named ``unit``."""
    if not _State.enabled:
        return None
    import jax

    now = time.perf_counter()
    _TLS.last = None  # the previous record takes no more spans
    rec = RoundRecord(round_idx=int(round_idx), fused=fused,
                      superround=superround, cohort_chunk=cohort_chunk)
    stack = _span_stack()
    rec.t0_ns = stack[-1].t0_ns if stack else time.perf_counter_ns()
    _TLS.record = rec  # the emit_record span below is already this record's
    with phase("emit_record", record=False):
        drain_records(block=False)
    rec.in_flight = len(_PENDING)
    rec._compiles0 = _REG.counter("jax.compiles")
    with _STATE_LOCK:  # read-modify-write shared with comm-thread rounds
        if _State.last_begin is not None:
            t_prev, units = _State.last_begin
            rate, prev = units / max(now - t_prev, 1e-9), _State.ema_rounds_per_sec
            _State.ema_rounds_per_sec = (
                rate if prev is None else 0.9 * prev + 0.1 * rate)
        _State.last_begin = (now, 1)
    rec._step_annotation = jax.profiler.StepTraceAnnotation(
        unit, step_num=rec.round_idx)
    rec._step_annotation.__enter__()
    return rec


def _hbm_fields(rec: RoundRecord) -> None:
    from . import device_stats

    stats = device_stats()
    if stats:
        rec.hbm_used_mb = stats[0].get("mem_used_mb")
        rec.hbm_peak_mb = stats[0].get("peak_mb")


def _realize(value: Any) -> Optional[float]:
    if value is None:
        return None
    try:
        import numpy as np

        return float(np.asarray(value))
    except Exception:
        return None


def _stamp(rec: RoundRecord, wall_s: Optional[float] = None) -> None:
    """The host side of closing a record; touches no device value."""
    rec.wall_s = ((time.perf_counter_ns() - rec.t0_ns) * 1e-9
                  if wall_s is None else wall_s)
    rec.time = time.time()
    if rec._step_annotation is not None:
        rec._step_annotation.__exit__(None, None, None)
        rec._step_annotation = None
    rec.compiles = int(_REG.counter("jax.compiles") - rec._compiles0)
    rec.rounds_per_sec_ema = _State.ema_rounds_per_sec
    _hbm_fields(rec)
    _TLS.record = None


def end_round(rec: Optional[RoundRecord],
              train_loss: Any = None, wall_s: Optional[float] = None) -> None:
    """Close a RoundRecord on the host side: stamp wall, epoch time, HBM and
    compile count, and queue it. No host sync: its device scalars
    (``train_loss``, ``examples``) are realized, and the record emitted, by a
    later :func:`begin_round` once they are ready, or by
    :func:`drain_records` at the loop's end. Until the next record opens it
    still takes the spans that close (evaluation, logging, checkpoint)."""
    if rec is None:
        return
    _stamp(rec, wall_s)
    if train_loss is not None:
        rec.lazy["train_loss"] = train_loss
    _TLS.last = rec
    _PENDING.append(rec)


def _emit_record(rec: RoundRecord) -> None:
    import jax

    from . import _emit

    try:
        # the record's device scalars in one go: their copies to the host
        # overlap, where one np.asarray each waits for each in turn (a step's
        # routing counters cost 2 to 3 ms of begin_round so: PERF.md, PR 36)
        lazy = jax.device_get(rec.lazy)
    except Exception:  # a value that cannot be read reads as None below
        lazy = rec.lazy
    rec.train_loss = _realize(lazy.pop("train_loss", None))
    rec.examples = _realize(lazy.pop("examples", None))
    rec.counters = {k: _realize(v) for k, v in lazy.items()}
    rec.lazy.clear()
    rec.emitted = True
    _REG.inc("rounds.total")
    if rec.examples:
        _REG.inc("examples.total", rec.examples)
    _REG.observe("round.wall.seconds", rec.wall_s)
    _emit(rec.to_event())


def drain_records(block: bool = True) -> None:
    """Emit the pending records, oldest first. ``block=False`` stops at the
    first whose device scalars are not ready (``begin_round``); the default
    waits for each (the end of ``train()`` / ``run()``, ``mlops.flush()``,
    ``close()``). A drained record takes no further spans."""
    if not _PENDING:
        return
    with _DRAIN_LOCK:
        while _PENDING and (block or _PENDING[0].ready()):
            _emit_record(_PENDING.popleft())
    if block:
        with _STATE_LOCK:
            _State.last_begin = None  # the next loop starts a new period
    write_metrics_file()


def end_superround(rec: Optional[RoundRecord], k: int,
                   scan_metrics: Dict[str, Any]) -> None:
    """Close the record of a K-round scan as K records, unpacked host-side
    from the scan's stacked per-round outputs (``train_loss[k]``,
    ``examples[k]``): the one wait a tracked scan adds. The scan is one device
    program, so its wall is divided evenly over the rounds — honest about
    what a fused superround can know. ``rec`` (opened at the scan's first
    round) keeps the spans that closed under it; the last of the K takes the
    spans that follow."""
    if rec is None:
        return
    import numpy as np

    losses = np.asarray(scan_metrics.get("train_loss"))  # waits for the scan
    ex = scan_metrics.get("examples")
    ex = None if ex is None else np.asarray(ex)
    _stamp(rec)
    per = rec.wall_s / max(k, 1)
    for j in range(k):
        r = rec if j == 0 else dataclasses.replace(
            rec, round_idx=rec.round_idx + j, spans=[], in_flight=0,
            compiles=0)
        r.wall_s = per
        r.time = rec.time - (k - 1 - j) * per
        r.phases = {"superround_scan": per}
        r.lazy = {"train_loss": float(losses[j] if losses.shape else losses),
                  "examples": None if ex is None else float(ex[j])}
        _PENDING.append(r)
        _TLS.last = r
    with _STATE_LOCK:
        if _State.last_begin is not None:
            _State.last_begin = (_State.last_begin[0], k)


# ---------------------------------------------------------------------------
# Profiler windows (--profile_rounds N:M)
# ---------------------------------------------------------------------------


def _start_trace(log_dir: str) -> None:  # monkeypatchable in tests
    import jax

    jax.profiler.start_trace(log_dir)


def _stop_trace() -> None:
    import jax

    jax.profiler.stop_trace()


class ProfilerWindow:
    """``jax.profiler`` trace over rounds [start, stop) — device-level truth
    (op timelines, HBM traffic) for the window the host-side spans flag."""

    def __init__(self, start_round: int, stop_round: int, log_dir: str):
        self.start_round = int(start_round)
        self.stop_round = int(stop_round)
        self.log_dir = log_dir
        self.active = False
        self.done = False

    @classmethod
    def parse(cls, spec: str, log_dir: str) -> "ProfilerWindow":
        """``"N:M"`` traces rounds [N, M); bare ``"N"`` traces round N."""
        lo, _, hi = str(spec).partition(":")
        start = int(lo)
        stop = int(hi) if hi else start + 1
        if stop <= start:
            raise ValueError(
                f"profile_rounds expects N:M with M > N, got {spec!r}")
        return cls(start, stop, log_dir)

    def on_round_start(self, round_idx: int) -> None:
        if (not self.done and not self.active
                and self.start_round <= round_idx < self.stop_round):
            _start_trace(self.log_dir)
            self.active = True

    def on_round_end(self, round_idx: int) -> None:
        if self.active and round_idx + 1 >= self.stop_round:
            self.force_stop()

    def force_stop(self) -> None:
        if self.active:
            _stop_trace()
            self.active = False
            self.done = True

    def intersects(self, lo: int, hi: int) -> bool:
        """Does [lo, hi) overlap the (not yet finished) window?"""
        return (not self.done and lo < self.stop_round
                and hi > self.start_round)


def on_round_start(round_idx: int) -> None:
    p = _State.profiler
    if p is not None:
        p.on_round_start(round_idx)


def on_round_end(round_idx: int) -> None:
    p = _State.profiler
    if p is not None:
        p.on_round_end(round_idx)


def profiler_blocks_chunk(lo: int, hi: int) -> bool:
    """True when a K-round scan over [lo, hi) would swallow a profiler
    boundary — the chunker then falls back to single rounds so the trace
    starts/stops exactly on the requested rounds."""
    p = _State.profiler
    return p is not None and p.intersects(lo, hi)


# ---------------------------------------------------------------------------
# Periodic host/device sampler (daemon thread)
# ---------------------------------------------------------------------------


class SysPerfSampler:
    """Periodic ``log_sys_perf()`` on a daemon thread: host CPU/RSS + HBM
    time series for long runs, no calls sprinkled through scenario code."""

    def __init__(self, interval_s: float):
        self.interval_s = float(interval_s)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "SysPerfSampler":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name="sys-perf-sampler", daemon=True
            )
            self._thread.start()
        return self

    def _run(self) -> None:
        from . import log_sys_perf

        while not self._stop.wait(self.interval_s):
            try:
                log_sys_perf()
            except Exception:  # sampling must never kill a run
                pass

    def stop(self, timeout: float = 2.0) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None


def start_sys_perf_sampler(args) -> Optional[SysPerfSampler]:
    """Start the sampler when tracking is on and ``--sys_perf_interval_s``
    is positive; else ``None`` (the runner calls this unconditionally)."""
    interval = float(getattr(args, "sys_perf_interval_s", 0.0) or 0.0)
    if not _State.enabled or interval <= 0:
        return None
    return SysPerfSampler(interval).start()


# ---------------------------------------------------------------------------
# MFU estimate (Cheetah)
# ---------------------------------------------------------------------------


def mfu_estimate(tokens_per_sec: float, flops_per_tok: float,
                 peak_flops: float, n_chips: int = 1) -> float:
    """Model FLOP/s utilization against ``peak_flops`` per chip
    (:func:`peak_bf16_flops`)."""
    return (tokens_per_sec * flops_per_tok) / (peak_flops * n_chips)
