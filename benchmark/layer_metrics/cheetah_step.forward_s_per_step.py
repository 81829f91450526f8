"""Device seconds of a step's forward pass: chip 0's self time of the step
program's instructions that the program places under a scope or a module, in
the pass ``fwd``, outside ``optimizer`` (``benchmark/scope_time.py`` over the
program's ``program_scopes`` map); median over the traced steps. With
``backward``, ``remat``, ``optimizer`` and the unnamed seconds it tiles the
step's busy time. Layer: Cheetah step. Moves ``tokens_per_s_per_chip``."""

from benchmark import scope_time


def read(run):
    return scope_time.median_seconds(run, scope_time.forward)
