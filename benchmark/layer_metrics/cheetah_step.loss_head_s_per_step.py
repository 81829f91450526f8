"""Device seconds of a step's head and loss: chip 0's self time of the step
program's instructions whose scope path holds ``loss``, forward and backward
(the head product, the cross entropy's chunk scan and, under a mesh, the
head's gather and reduce-scatter; the program's ``program_scopes`` map,
``benchmark/scope_time.py``); median over the traced steps. These seconds are
also inside the forward's and the backward's. Layer: Cheetah step. Moves
``tokens_per_s_per_chip``."""

from benchmark import scope_time


def read(run):
    return scope_time.median_seconds(run, scope_time.holds("loss"))
