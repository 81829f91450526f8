"""Model FLOP/s utilization of the step on the device, in percent: tokens a
step times the model's FLOPs a token (block and head matmuls, causal
attention, no embedding gather, no recomputation; ``benchmark/flops``), over
the step's device time, the chips and the peak. The end-to-end utilization is
``tokens_per_s_per_chip`` times FLOPs a token over the peak; this one leaves
the host's share out. Layer: Cheetah step. Moves ``tokens_per_s_per_chip``."""

from benchmark import harness


def read(run):
    seconds = harness.load_module(
        run.cell.root, "layer_metrics", "cheetah_step.device_s_per_step").read(run)
    if not seconds:
        return None
    flops = run.facts["tokens_per_step"] * run.facts["train_flops_per_token"]
    return 100.0 * flops / (seconds * run.facts["chips"]
                            * run.peaks["bf16_flops_per_s"])
