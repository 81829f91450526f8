"""Device seconds a step spends in the expert layers' dispatch and combine:
chip 0's self time of the step program's instructions whose scope path holds
``moe_experts``, all passes: the gathers and scatters that bring a token's
rows to its experts and back (the program's ``program_scopes`` map,
``benchmark/scope_time.py``); median over the traced steps. The grouped
products themselves carry no name stack and are read by
``moe_experts.kernel_roofline``. Layer: expert layer (``parallel/moe.py``).
Moves ``tokens_per_s_per_chip``."""

from benchmark import scope_time


def read(run):
    return scope_time.median_seconds(run, scope_time.holds("moe_experts"))
