"""From a profiler trace (``.xplane.pb``) to intervals, and from intervals to numbers.

Read with ``jax.profiler.ProfileData`` and nothing else. What a TPU trace
holds (first read in PR 22): one plane ``/device:TPU:<n>`` per chip with the
lines ``XLA Modules`` (one event per executed program, named
``jit_<function>(<fingerprint>)``), ``XLA Ops`` (one event per executed HLO
instruction, named by its full HLO text ``%name = shape opcode(...)``; the ops
of a ``while`` or ``conditional`` body nest inside their parent's event) and
``Async XLA Ops`` (``*-start`` to ``*-done`` spans: copies, slices and, across
chips, collectives). Times are nanoseconds from the start of the profile; the
``Task Environment`` plane gives that start on the host's clock.

Everything below the loader works on plain ``(start, end)`` pairs so that it
can be checked on synthetic intervals.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

Interval = Tuple[float, float]
# Times are float seconds built from picosecond counts: an event that starts
# where the last one ended may compare a rounding error (1e-17) early.
_EPS = 5e-13

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OP_TEXT = re.compile(r"^%?(?P<name>\S+) = .*? (?P<opcode>[\w\-]+)\(")
# HLO opcodes (and their async forms) that move data between chips
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
                "collective-permute", "all-to-all", "collective-broadcast")
# ops whose event only encloses the events of their body
_CONTROL_FLOW = ("while", "conditional", "call")


@dataclasses.dataclass
class Events:
    """The events of one trace line, by column."""

    names: List[str]          # unique event names (HLO text for XLA Ops)
    name_id: np.ndarray       # int: index into ``names`` per event
    start: np.ndarray         # float64 seconds from the start of the profile
    end: np.ndarray

    def __len__(self) -> int:
        return len(self.start)

    @property
    def duration(self) -> np.ndarray:
        return self.end - self.start

    def select(self, mask: np.ndarray) -> "Events":
        return Events(self.names, self.name_id[mask], self.start[mask],
                      self.end[mask])

    def where_name(self, predicate) -> "Events":
        keep = np.array([bool(predicate(n)) for n in self.names], bool)
        return self.select(keep[self.name_id]) if len(self) else self


EMPTY = Events([], np.zeros(0, int), np.zeros(0), np.zeros(0))


@dataclasses.dataclass
class DeviceTrace:
    ordinal: int
    modules: Events
    ops: Events
    async_ops: Events


@dataclasses.dataclass
class Trace:
    devices: List[DeviceTrace]
    start_epoch_ns: Optional[int]          # profile start on the host's clock


def _events(line) -> Events:
    ids: Dict[str, int] = {}
    name_id, start, dur = [], [], []
    for e in line.events:
        name_id.append(ids.setdefault(e.name, len(ids)))
        start.append(e.start_ns)
        dur.append(e.duration_ns)
    start_s = np.asarray(start, np.float64) * 1e-9
    return Events(list(ids), np.asarray(name_id, int), start_s,
                  start_s + np.asarray(dur, np.float64) * 1e-9)


def load(path: str) -> Trace:
    """Read the device planes of one trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, start_epoch = [], None
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            devices.append(DeviceTrace(
                ordinal=int(m.group(1)),
                modules=_events(lines["XLA Modules"]) if "XLA Modules" in lines else EMPTY,
                ops=_events(lines["XLA Ops"]) if "XLA Ops" in lines else EMPTY,
                async_ops=_events(lines["Async XLA Ops"]) if "Async XLA Ops" in lines else EMPTY,
            ))
        elif plane.name == "Task Environment":
            stats = dict(plane.stats)
            if "profile_start_time" in stats:
                start_epoch = int(stats["profile_start_time"])
    devices.sort(key=lambda d: d.ordinal)
    return Trace(devices, start_epoch)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of ``a`` that no interval of ``b`` covers (both get merged)."""
    out: List[Interval] = []
    b = merge(b)
    j = 0
    for s, e in merge(a):
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi] given merged busy intervals."""
    return subtract([(lo, hi)], busy)


def as_intervals(ev: Events) -> List[Interval]:
    return list(zip(ev.start.tolist(), ev.end.tolist()))


# ---------------------------------------------------------------------------
# reading XLA's names
# ---------------------------------------------------------------------------


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    m = _OP_TEXT.match(text)
    return m.group("name") if m else text.split(" ")[0].lstrip("%")


def opcode(text: str) -> str:
    m = _OP_TEXT.match(text)
    return m.group("opcode") if m else ""


def collective_kind(text: str) -> Optional[str]:
    """Which collective an op is (``all-gather`` for ``all-gather-start``
    too), or None. Decided by the opcode, or for an ``async-start`` wrapper
    and for ops XLA renamed (``all-gather.3``) by the op's own name."""
    for probe in (opcode(text), op_name(text)):
        for kind in _COLLECTIVES:
            if probe.startswith(kind):
                return kind
    return None


def is_mosaic_kernel(text: str) -> bool:
    """A Pallas/Mosaic kernel: a custom call to ``tpu_custom_call``."""
    return opcode(text) == "custom-call" and "tpu_custom_call" in text


def is_control_flow(text: str) -> bool:
    return opcode(text) in _CONTROL_FLOW


def module_function(name: str) -> str:
    """``jit__train_step_raw(123)`` -> ``_train_step_raw``."""
    base = name.split("(")[0]
    return base[len("jit_"):] if base.startswith("jit_") else base


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


def self_seconds(ev: Events) -> np.ndarray:
    """Per event, its duration less that of the events nested directly inside
    it: what a ``while`` spends outside its body's ops is the loop's own."""
    self_s = ev.duration.copy()
    order = np.lexsort((-ev.end, ev.start))
    stack: List[int] = []
    for i in order:
        while stack and ev.end[stack[-1]] <= ev.start[i] + _EPS:
            stack.pop()
        if stack:
            self_s[stack[-1]] -= ev.end[i] - ev.start[i]
        stack.append(i)
    return np.maximum(self_s, 0.0)


def top_level(ev: Events) -> Events:
    """The events that lie inside no other event of the line."""
    keep = np.zeros(len(ev), bool)
    frontier = -np.inf
    for i in np.lexsort((-ev.end, ev.start)):
        if ev.start[i] + _EPS >= frontier:
            keep[i] = True
            frontier = ev.end[i]
    return ev.select(keep)


def busy_intervals(dev: DeviceTrace) -> List[Interval]:
    return merge(as_intervals(top_level(dev.ops)))


def device_window(trace: Trace) -> Optional[Interval]:
    """From the first to the last device op of the trace, over all chips: the
    traced window with the profiler's own start and stop cut off."""
    starts = [d.ops.start.min() for d in trace.devices if len(d.ops)]
    ends = [d.ops.end.max() for d in trace.devices if len(d.ops)]
    return (float(min(starts)), float(max(ends))) if starts else None


def idle_share(trace: Trace) -> float:
    """1 - busy / window on the chip that idles most."""
    lo, hi = device_window(trace)
    return max(1.0 - total(busy_intervals(d)) / (hi - lo)
               for d in trace.devices)


def op_table(ev: Events, top: int = 10) -> List[Tuple[str, float]]:
    """The ops with most self time, summed by XLA's op name."""
    self_s = self_seconds(ev)
    per_name = np.bincount(ev.name_id, weights=self_s, minlength=len(ev.names))
    best = np.argsort(-per_name)[:top]
    return [(op_name(ev.names[i]), float(per_name[i])) for i in best
            if per_name[i] > 0]


def module_events(dev: DeviceTrace, function: str) -> Events:
    return dev.modules.where_name(lambda n: module_function(n) == function)


def collective_intervals(dev: DeviceTrace) -> List[Interval]:
    """Every interval in which a collective was in flight on this chip:
    synchronous ones from ``XLA Ops``, start-to-done spans from ``Async XLA
    Ops``."""
    sync = dev.ops.where_name(lambda n: collective_kind(n) is not None
                              and not opcode(n).endswith(("-start", "-done")))
    asyn = dev.async_ops.where_name(lambda n: collective_kind(n) is not None)
    return merge(as_intervals(sync) + as_intervals(asyn))


def compute_intervals(dev: DeviceTrace) -> List[Interval]:
    """Intervals in which an op that is neither a collective, nor control flow
    around other ops, nor the issue or wait of an async op, ran."""
    def computes(n: str) -> bool:
        code = opcode(n)
        return (collective_kind(n) is None and not is_control_flow(n)
                and not code.endswith(("-start", "-done")))

    return merge(as_intervals(dev.ops.where_name(computes)))


def exposed_collective_seconds(dev: DeviceTrace) -> float:
    """The part of the collectives' time in which nothing else computed."""
    return total(subtract(collective_intervals(dev), compute_intervals(dev)))
