"""Share of the step program's busy time on chip 0 spent in the gated
short-convolution mixers, in percent: self time of the instructions whose
scope path holds ``shortconv`` (``ShortConvMixer``'s two projections, its
gates and its convolution), forward, recomputation and backward alike, over
the execution's busy time (the program's ``program_scopes`` map,
``benchmark/scope_time.py``); median over the traced steps. Nothing where the
step holds no such layer. Layer: short_conv (``ShortConvMixer`` in
``parallel/transformer.py``). Moves ``tokens_per_s_per_chip``."""

import statistics

from benchmark import scope_time


def read(run):
    seconds = scope_time.per_execution(run, scope_time.holds("shortconv"))
    if not seconds or not any(seconds):
        return None
    return statistics.median(
        100.0 * s / b for s, b in zip(seconds, scope_time.by_scope(run).busy))
