"""Device seconds by the program's own scopes: forward, backward,
recomputation, optimizer, and any named part of them.

A trace names a device op by XLA's instruction name (``fusion.302``). With
tracking on, the program publishes one ``program_scopes`` event per compiled
program (``fedml_tpu/core/mlops/telemetry.record_program_scopes``): for every
instruction name of the executable that runs, the scope path it was traced
under (``Transformer/CheckpointBlock/MoEFeedForward/moe_experts``: names of
``core/mlops/scopes.py`` and flax modules) and its pass (``fwd``, ``bwd``,
``remat``: the forward run again inside the backward). :func:`by_scope` joins
that map to chip 0's ``XLA Ops`` line, op by op inside each execution of the
cell's program, and sums self time by ``(path, pass)``: a ``while`` counts for
what it spends outside its body's ops. The attribution is the program's
record; the seconds are the device trace's.

Every reader goes through :func:`by_scope`. It returns ``None`` where the run
has no trace or the program published no map (a program from before the event
existed), and where the map does not account for the busy time of an
execution to ``TILES_WITHIN``; the reader then reports nothing.
"""

from __future__ import annotations

import statistics
import sys
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from benchmark import trace_reduce as tr

Key = Tuple[str, str]            # (scope path, pass); the path "" is unnamed
TILES_WITHIN = 0.005             # of an execution's busy time
_SLACK = 1e-9                    # an op may end where its program's event ends


class ScopeTimes(NamedTuple):
    seconds: List[Dict[Key, float]]  # per execution: self seconds by key
    busy: List[float]                # per execution: union of its ops' intervals


def program_scopes(run) -> Optional[dict]:
    """The newest ``program_scopes`` event of the cell's program
    (``facts["module"]``) in the run's event log, or None."""
    if hasattr(run, "_program_scopes"):
        return run._program_scopes
    from fedml_tpu.core import mlops

    found = [e for e in mlops.read_events()
             if e.get("kind") == "program_scopes"
             and e.get("program") == run.facts["module"]]
    run._program_scopes = found[-1] if found else None
    return run._program_scopes


def _executions(dev: tr.DeviceTrace, function: str) -> tr.Events:
    """The executions of the program that ran most under that function's
    name: a step whose arguments arrive placed otherwise once (fsdp's first
    step) is another program, and the map is the steady one's."""
    events = tr.module_events(dev, function)
    if not len(events):
        return events
    return events.select(events.name_id == np.bincount(events.name_id).argmax())


def by_scope(run) -> Optional[ScopeTimes]:
    """Chip 0's self seconds by ``(path, pass)`` in each execution of the
    cell's program; one reduction shared by the readers."""
    if hasattr(run, "_by_scope"):
        return run._by_scope
    run._by_scope = None
    if run.trace is None:
        return None
    published = program_scopes(run)
    if published is None:
        return None
    dev = run.trace.devices[0]
    ops = dev.ops
    executions = _executions(dev, run.facts["module"])
    if not len(ops) or not len(executions):
        return None
    keys: List[Key] = [(str(p), str(w)) for p, w in published["scopes"]]
    listed = published["ops"]
    # per unique op text its key's index; the last index is "not in the map"
    of_name = np.array([listed.get(tr.op_name(text), len(keys))
                        for text in ops.names], int)
    key_of = of_name[ops.name_id]
    self_s = tr.self_seconds(ops)
    seconds, busy = [], []
    for lo, hi in tr.as_intervals(executions):
        inside = (ops.start >= lo - _SLACK) & (ops.end <= hi + _SLACK)
        sums = np.bincount(key_of[inside], weights=self_s[inside],
                           minlength=len(keys) + 1)
        union = tr.total(tr.merge(tr.as_intervals(ops.select(inside))))
        accounted = float(sums[:len(keys)].sum())
        if abs(accounted - union) > TILES_WITHIN * union:
            missing = sorted({tr.op_name(ops.names[i]) for i in
                              set(ops.name_id[inside & (key_of == len(keys))])})
            sys.stderr.write(
                f"benchmark: the program's scope map accounts for "
                f"{accounted:.6f}s of an execution's {union:.6f}s busy on "
                f"chip 0; {len(missing)} op name(s) it does not list, e.g. "
                f"{missing[:5]}: no seconds by scope are reported\n")
            return None
        seconds.append({key: s for key, s in zip(keys, sums.tolist()) if s})
        busy.append(union)
    run._by_scope = ScopeTimes(seconds, busy)
    _log_table(run._by_scope)
    return run._by_scope


def _log_table(times: ScopeTimes, top: int = 12) -> None:
    """The largest rows in ms a unit, as a traced run's stderr shows them."""
    n = len(times.seconds)
    total: Dict[Key, float] = {}
    for row in times.seconds:
        for key, s in row.items():
            total[key] = total.get(key, 0.0) + s
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    busy = sum(times.busy)
    unnamed = sum(s for (path, _), s in total.items() if not path)
    sys.stderr.write(
        f"benchmark: device ms a unit by scope and pass (chip 0, mean of {n} "
        f"executions, busy {1e3 * busy / n:.3f}): "
        + ", ".join(f"{path or '(no name)'}:{which} {1e3 * s / n:.3f}"
                    for (path, which), s in rows)
        + f"; under no scope {100.0 * unnamed / busy:.2f}% of busy\n")


# ---------------------------------------------------------------------------
# what the readers sum
# ---------------------------------------------------------------------------

Keep = Callable[[Sequence[str], str], bool]  # (path's elements, pass) -> bool


def holds(name: str) -> Keep:
    return lambda elements, which: name in elements


def in_module(elements: Sequence[str]) -> bool:
    """Is some element a flax module? The vocabulary is all lower case."""
    return any(e[:1].isupper() for e in elements)


# the four that, with the unnamed seconds, tile a step program's busy time
def optimizer(elements, which) -> bool:
    return "optimizer" in elements


def forward(elements, which) -> bool:
    return bool(elements) and which == "fwd" and "optimizer" not in elements


def backward(elements, which) -> bool:
    return bool(elements) and which == "bwd" and "optimizer" not in elements


def remat(elements, which) -> bool:
    return bool(elements) and which == "remat" and "optimizer" not in elements


def unnamed(elements, which) -> bool:
    return not elements


def per_execution(run, keep: Keep) -> Optional[List[float]]:
    times = by_scope(run)
    if times is None:
        return None
    return [sum(s for (path, which), s in row.items()
                if keep(path.split("/") if path else [], which))
            for row in times.seconds]


def median_seconds(run, keep: Keep) -> Optional[float]:
    """Median over the traced executions of the seconds ``keep`` selects."""
    values = per_execution(run, keep)
    return statistics.median(values) if values else None


def unnamed_share(run) -> Optional[float]:
    """Percent of the program's busy time under no scope and no module, or
    under an instruction the map does not list; median over executions."""
    named = per_execution(run, lambda elements, which: bool(elements))
    if not named:
        return None
    return statistics.median(
        100.0 * (1.0 - n / b) for n, b in zip(named, by_scope(run).busy))
