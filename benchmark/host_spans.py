"""The program's own spans on the trace's clock.

With tracking on, every RoundRecord carries ``spans``: each span the loop
opened through ``telemetry.phase`` as ``{name, span, parent, ts_ns, dur_ns}``,
``ts_ns`` on ``time.time_ns()``'s clock. The xplane's ``Task Environment``
plane gives the profile's start on that same clock
(``trace_reduce.Trace.start_epoch_ns``), and device events are nanoseconds
from that start, so ``ts_ns - start_epoch_ns`` puts a host span beside the
device lines with no further arithmetic. The benchmark's profiler session
records device planes only, so the spans come from the records, not from the
trace's host plane.

Every reader of a span metric goes through :func:`spans`; it returns ``None``
where the run has no trace or the records carry no ``spans`` (a program from
before they existed), and the reader then reports nothing.
"""

from __future__ import annotations

import statistics
import sys
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from benchmark import trace_reduce as tr


class Span(NamedTuple):
    name: str
    start: float   # seconds from the start of the profile
    end: float
    unit: int      # the round or step whose record carries the span


def spans(run) -> Optional[List[Span]]:
    """Every span of the run's records on the trace's clock, by start."""
    trace = run.trace
    if trace is None or not trace.start_epoch_ns:
        return None
    origin = int(trace.start_epoch_ns)
    out = [Span(str(s["name"]), (int(s["ts_ns"]) - origin) * 1e-9,
                (int(s["ts_ns"]) + int(s["dur_ns"]) - origin) * 1e-9,
                int(rec["round_idx"]))
           for rec in run.records for s in rec.get("spans") or ()]
    return sorted(out, key=lambda s: (s.start, -s.end)) or None


def named(all_spans: Sequence[Span], name: str) -> List[Span]:
    return [s for s in all_spans if s.name == name]


def innermost(all_spans: Sequence[Span]) -> List[Tuple[str, tr.Interval]]:
    """``(name, interval)`` pieces in which the named span is the innermost
    one open: each span less the spans nested inside it."""
    pieces = []
    for s in all_spans:
        inner = [(o.start, o.end) for o in all_spans
                 if o is not s and s.start <= o.start and o.end <= s.end
                 and (o.start, o.end) != (s.start, s.end)]
        pieces += [(s.name, iv) for iv in tr.subtract([(s.start, s.end)], inner)]
    return pieces


def intersect(a: Sequence[tr.Interval], b: Sequence[tr.Interval]) -> List[tr.Interval]:
    return tr.subtract(a, tr.subtract(a, b))


def idle_by_span(run) -> Optional[Dict[str, float]]:
    """Chip 0's idle seconds between its first and last device op, by the
    innermost program span they fall in; what falls in none is under ``""``."""
    all_spans = spans(run)
    if all_spans is None:
        return None
    dev = run.trace.devices[0]
    if not len(dev.ops):
        return None
    idle = tr.gaps(tr.busy_intervals(dev), float(dev.ops.start.min()),
                   float(dev.ops.end.max()))
    table: Dict[str, float] = {}
    for name, piece in innermost(all_spans):
        inside = tr.total(intersect(idle, [piece]))
        if inside:
            table[name] = table.get(name, 0.0) + inside
    table[""] = max(tr.total(idle) - sum(table.values()), 0.0)
    return table


def idle_attributed_share(run) -> Optional[float]:
    """Percent of chip 0's idle seconds that lie inside some program span.
    Logs the table of idle seconds per innermost span name to stderr."""
    table = idle_by_span(run)
    if table is None:
        return None
    total = sum(table.values())
    if not total:
        return None
    rows = sorted(table.items(), key=lambda kv: -kv[1])
    sys.stderr.write(
        "benchmark: idle seconds of chip 0 by innermost span: "
        + ", ".join(f"{name or '(no span)'} {s:.6f}" for name, s in rows)
        + f"; total {total:.6f}\n")
    return 100.0 * (total - table[""]) / total


# ---------------------------------------------------------------------------
# one synchronous step: launch, device, wake, between
# ---------------------------------------------------------------------------


class StepTimes(NamedTuple):
    unit: int
    launch: float            # step span's start -> program's start on chip 0
    device: float            # program's start on chip 0 -> end on the last chip
    wake: float              # program's end on the last chip -> loss_sync's end
    between: Optional[float]  # loss_sync's end -> the next step span's start


def _execution_in(events: tr.Events, lo: float, hi: float) -> Optional[int]:
    """Index of the event that overlaps [lo, hi] most, if by more than half
    its length: the pairing survives a shift between the two clocks that is
    small against a step."""
    if not len(events):
        return None
    overlap = np.minimum(events.end, hi) - np.maximum(events.start, lo)
    best = int(np.argmax(overlap))
    return best if overlap[best] > 0.5 * events.duration[best] else None


def step_times(run) -> Optional[List[StepTimes]]:
    """Per traced step of a loop that waits for every step's loss: where the
    host's clock and the device's meet. A step is a ``step`` span, the
    ``loss_sync`` span that follows it and the execution of the step program
    (``facts["module"]``) that lies between the two.

    ``launch`` and ``wake`` each hold the offset between the profiler's
    device timeline and the host's clock, with opposite signs: a negative
    value of either (logged to stderr) is that offset showing. It cancels in
    their sum, in ``device`` and in ``between``."""
    if hasattr(run, "_step_times"):  # the readers share one reduction
        return run._step_times
    run._step_times = None
    all_spans = spans(run)
    if all_spans is None:
        return None
    steps, syncs = named(all_spans, "step"), named(all_spans, "loss_sync")
    module = run.facts["module"]
    on_chips = [tr.module_events(d, module) for d in run.trace.devices]
    first = on_chips[0]
    out = []
    for i, (step, sync) in enumerate(zip(steps, syncs)):
        k = _execution_in(first, step.start, sync.end)
        if k is None:
            continue
        start = float(first.start[k])
        ends = []
        for ev in on_chips:
            j = _execution_in(ev, step.start, sync.end)
            if j is not None:
                ends.append(float(ev.end[j]))
        end = max(ends)
        nxt = steps[i + 1].start if i + 1 < len(steps) else None
        out.append(StepTimes(step.unit, start - step.start, end - start,
                             sync.end - end,
                             None if nxt is None else nxt - sync.end))
    if out:
        tiled = [t.launch + t.device + t.wake + t.between for t in out
                 if t.between is not None]
        period = statistics.median(
            (first.start[1:] - first.start[:-1]).tolist()) if len(first) > 1 else None
        sys.stderr.write(
            f"benchmark: {len(out)} steps on one clock: launch min "
            f"{min(t.launch for t in out):.6f}s median "
            f"{statistics.median(t.launch for t in out):.6f}s, wake min "
            f"{min(t.wake for t in out):.6f}s median "
            f"{statistics.median(t.wake for t in out):.6f}s, between min "
            f"{min((t.between for t in out if t.between is not None), default=0.0):.6f}s"
            + (f"; median launch+device+wake+between "
               f"{statistics.median(tiled):.6f}s against the step program's "
               f"median start-to-start period {period:.6f}s on the device"
               if tiled and period else "") + "\n")
    run._step_times = out or None
    return run._step_times


def median_of(run, field: str) -> Optional[float]:
    """Median over the traced steps of one of StepTimes' fields, or of
    ``launch_wake``, the sum in which the clocks' offset cancels."""
    times = step_times(run)
    if field == "launch_wake":
        values = [t.launch + t.wake for t in times or ()]
    else:
        values = [getattr(t, field) for t in times or ()
                  if getattr(t, field) is not None]
    return statistics.median(values) if values else None
