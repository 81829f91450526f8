"""Share of the step's device time in which a collective is in flight and no
other op computes on chip 0, in percent: the communication the schedule did
not hide. Layer: sharding. Moves ``tokens_per_s_per_chip``."""

from benchmark import trace_reduce as tr


def read(run):
    if run.trace is None:
        return None
    dev = run.trace.devices[0]
    step_s = tr.total(tr.as_intervals(tr.module_events(dev, run.facts["module"])))
    return 100.0 * tr.exposed_collective_seconds(dev) / step_s if step_s else None
