"""Of chip 0's idle seconds between its first and last device op, the percent
that lie inside some span of the program's round loop (RoundRecord ``spans``
on the trace's clock, ``benchmark/host_spans.py``): how much of the idle time
has an owner. The traced run logs the idle seconds per innermost span name to
stderr. Layer: device. Moves ``rounds_per_s`` as ``device.round_idle_share``
does."""

from benchmark import host_spans


def read(run):
    return host_spans.idle_attributed_share(run)
