"""Share of chip 0's busy time spent in the KDA chunk kernels (the custom calls
to ``tpu_custom_call`` named ``kda_chunk_fwd`` / ``kda_chunk_bwd``), in
percent: their self time over the union of the device's op intervals. Layer:
linear attention. Moves ``tokens_per_s_per_chip``."""

from benchmark import harness
from benchmark import trace_reduce as tr


def read(run):
    if run.trace is None:
        return None
    dev = run.trace.devices[0]
    busy = tr.total(tr.busy_intervals(dev))
    found = harness.load_module(
        run.cell.root, "layer_metrics", "kda.kernel_roofline").calls_by_kind(dev)
    if not busy or not found:
        return None
    return 100.0 * sum(s for _, s in found.values()) / busy
