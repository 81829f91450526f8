"""Idle share of the device over the traced window, in percent: 1 minus the
union of the ``XLA Ops`` intervals over the span from the first to the last
device op, on the chip that idles most. The traced loop runs with the
program's tracking on, which makes every round wait for the device before the next is
prepared: host time the untracked loop overlaps shows here as idle. Layer: device. Moves ``rounds_per_s``."""

from benchmark import trace_reduce as tr


def read(run):
    return None if run.trace is None else 100.0 * tr.idle_share(run.trace)
