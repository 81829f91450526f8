"""Share of the step program's busy time on chip 0 that no scope owns, in
percent: self time of instructions whose name stack holds neither a scope of
the program's vocabulary nor a flax module, whatever their pass, or that the
program's ``program_scopes`` map does not list (``benchmark/scope_time.py``);
median over the traced steps. The grouped products of an expert layer stand
here (XLA's ``ragged-dot-*`` calls carry no name stack). What the other
``cheetah_step.*_s_per_step`` metrics cannot see. Layer: Cheetah step. Moves
``tokens_per_s_per_chip``."""

from benchmark import scope_time


def read(run):
    return scope_time.unnamed_share(run)
