"""Share of chip 0's busy time spent in the window layers' attention kernels,
in percent: the self time of the splash calls the program's scope map puts
under ``window_attention`` (as ``window_attention.kernel_roofline`` finds
them) over the union of the device's op intervals.
``attention_kernels.device_share`` counts every Mosaic call, the full layer's
attention and the experts' grouped products among them; this one the window
layers' attention alone. Nothing to read where the run published no map or
the step holds no window layer. Layer: attention kernels. Moves
``tokens_per_s_per_chip``."""

from benchmark import harness
from benchmark import trace_reduce as tr


def read(run):
    if run.trace is None:
        return None
    found = harness.load_module(
        run.cell.root, "layer_metrics", "window_attention.kernel_roofline"
    ).calls_by_kind(run)
    busy = tr.total(tr.busy_intervals(run.trace.devices[0]))
    if not busy or not found:
        return None
    return 100.0 * sum(s for _, s in found.values()) / busy
