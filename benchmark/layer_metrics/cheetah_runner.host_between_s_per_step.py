"""Seconds the loop spends between having a step's loss and calling the next
``train_step``: the end of the ``loss_sync`` span to the start of the next
step's ``step`` span (the loop's ``record``, ``checkpoint``, ``hooks``,
``data`` and ``h2d`` spans; RoundRecord ``spans``, ``benchmark/
host_spans.py``), median over the traced steps. Layer: Cheetah runner. Moves
``tokens_per_s_per_chip`` as ``cheetah_runner.data_s_per_step`` does: the
device idles for all of it; bounded end to end by
``wall_tokens_per_s_per_chip`` on four chips only."""

from benchmark import host_spans


def read(run):
    return host_spans.median_of(run, "between")
