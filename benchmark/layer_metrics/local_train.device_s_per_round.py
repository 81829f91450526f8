"""Device seconds of a round's local training: the longest top-level ``while``
(the cohort's scan over epochs and batches) inside each execution of the round
program, median over the traced rounds, on the ``XLA Ops`` line of chip 0.
Layer: local training (``ml/local_train.py`` under ``vmap``). Moves
``rounds_per_s``."""

import statistics

from benchmark import trace_reduce as tr


def per_round(run):
    if run.trace is None:
        return []
    dev = run.trace.devices[0]
    loops = tr.top_level(dev.ops).where_name(lambda n: tr.opcode(n) == "while")
    out = []
    for s, e in tr.as_intervals(tr.module_events(dev, run.facts["module"])):
        inside = loops.duration[(loops.start >= s) & (loops.end <= e)]
        if len(inside):
            out.append(float(inside.max()))
    return out


def read(run):
    seconds = per_round(run)
    return statistics.median(seconds) if seconds else None
