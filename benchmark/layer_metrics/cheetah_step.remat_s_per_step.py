"""Device seconds a step spends running its forward again inside the
backward (activation recomputation): chip 0's self time of the step program's
named instructions traced under ``rematted_computation`` (pass ``remat`` of the
program's ``program_scopes`` map, ``benchmark/scope_time.py``); median over the
traced steps. What a remat policy moves. Layer: Cheetah step. Moves
``tokens_per_s_per_chip``."""

from benchmark import scope_time


def read(run):
    return scope_time.median_seconds(run, scope_time.remat)
