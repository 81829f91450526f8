"""Device seconds of a round's local training spent outside the model:
chip 0's self time of the round program's instructions under ``local_train``
and under no flax module: the batch scan's copies of its carry, the batch
gather, the loss outside the model, the clients' optimizer (the program's
``program_scopes`` map, ``benchmark/scope_time.py``); median over the traced
rounds. Layer: local training. Moves ``rounds_per_s``."""

from benchmark import scope_time


def read(run):
    return scope_time.median_seconds(
        run, lambda elements, which: "local_train" in elements
        and not scope_time.in_module(elements))
