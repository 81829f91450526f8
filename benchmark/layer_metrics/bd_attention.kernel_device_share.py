"""Share of chip 0's busy time spent in the attention kernels under the
block-diffusion mask (the custom calls to ``tpu_custom_call`` named
``splash_*``), in percent: their self time over the union of the device's op
intervals. ``attention_kernels.device_share`` counts every Mosaic call, the
experts' grouped products among them; this one the masked attention alone.
Nothing to read where the configuration names no ``block_length``. Layer:
attention kernels. Moves ``tokens_per_s_per_chip``."""

from benchmark import harness
from benchmark import trace_reduce as tr


def read(run):
    if run.trace is None or "block_length" not in run.cell.config:
        return None
    dev = run.trace.devices[0]
    busy = tr.total(tr.busy_intervals(dev))
    found = harness.load_module(
        run.cell.root, "layer_metrics", "bd_attention.kernel_roofline"
    ).calls_by_kind(dev)
    if not busy or not found:
        return None
    return 100.0 * sum(s for _, s in found.values()) / busy
