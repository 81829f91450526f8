"""Model FLOP/s utilization of local training, in percent: the real samples of
a round times the model's forward and backward FLOPs (``benchmark/flops``),
over the round's local-training device time and the chip's peak. Masked
padding and recomputation do not count as work. Layer: local training. Moves
``rounds_per_s``."""

import statistics

from benchmark import harness


def read(run):
    reader = harness.load_module(run.cell.root, "layer_metrics",
                                 "local_train.device_s_per_round")
    seconds = reader.per_round(run)
    examples = [r["examples"] for r in run.records if r.get("examples")]
    if not seconds or not examples:
        return None
    flops = (statistics.fmean(examples) * run.facts["epochs"]
             * run.facts["train_flops_per_sample"])
    return 100.0 * flops / (statistics.median(seconds)
                            * run.peaks["bf16_flops_per_s"])
