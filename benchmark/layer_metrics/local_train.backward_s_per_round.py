"""Device seconds of a round's local training spent in the backward pass:
chip 0's self time of the round program's instructions under ``local_train``
traced under ``transpose(..)`` (pass ``bwd`` of the program's
``program_scopes`` map, ``benchmark/scope_time.py``); median over the traced
rounds. Layer: local training. Moves ``rounds_per_s``."""

from benchmark import scope_time


def read(run):
    return scope_time.median_seconds(
        run, lambda elements, which: "local_train" in elements and which == "bwd")
