"""Device seconds of a step's optimizer: chip 0's self time of the step
program's instructions whose scope path holds ``optimizer`` (AdamW's update
and ``apply_updates``, with the global-norm ``clip`` inside it; the program's
``program_scopes`` map, ``benchmark/scope_time.py``); median over the traced
steps. Layer: Cheetah step. Moves ``tokens_per_s_per_chip``."""

from benchmark import scope_time


def read(run):
    return scope_time.median_seconds(run, scope_time.optimizer)
