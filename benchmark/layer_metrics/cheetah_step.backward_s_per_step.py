"""Device seconds of a step's backward pass proper: chip 0's self time of
the step program's named instructions traced under ``transpose(..)`` and not
under ``rematted_computation`` (pass ``bwd`` of the program's
``program_scopes`` map, ``benchmark/scope_time.py``); median over the traced
steps. Layer: Cheetah step. Moves ``tokens_per_s_per_chip``."""

from benchmark import scope_time


def read(run):
    return scope_time.median_seconds(run, scope_time.backward)
