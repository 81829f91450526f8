"""Device seconds of one training step: the duration of the step program's
executions on the ``XLA Modules`` line, median over the traced steps, on the
slowest chip. Layer: Cheetah step (``parallel/train_step.py``). Moves
``tokens_per_s_per_chip``."""

import statistics

from benchmark import trace_reduce as tr


def read(run):
    if run.trace is None:
        return None
    medians = []
    for dev in run.trace.devices:
        steps = tr.module_events(dev, run.facts["module"])
        if len(steps):
            medians.append(statistics.median(steps.duration.tolist()))
    return max(medians) if medians else None
