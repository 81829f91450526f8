"""Seconds a step has a collective in flight on chip 0 (all-gather,
reduce-scatter, all-reduce, ...; union of their intervals on ``XLA Ops`` and
``Async XLA Ops``), over the traced steps. Layer: sharding
(``parallel/sharding.py``, XLA's collectives). Moves
``tokens_per_s_per_chip`` only through the part that is exposed."""

from benchmark import trace_reduce as tr


def read(run):
    if run.trace is None:
        return None
    dev = run.trace.devices[0]
    steps = len(tr.module_events(dev, run.facts["module"]))
    return tr.total(tr.collective_intervals(dev)) / steps if steps else None
