"""Share of chip 0's busy time spent in Pallas/Mosaic kernels (custom calls to
``tpu_custom_call``: the splash forward, dq and dkv kernels), in percent.
Layer: attention kernels (splash via ``parallel/transformer.py``). Moves
``tokens_per_s_per_chip``."""

import numpy as np

from benchmark import trace_reduce as tr


def read(run):
    if run.trace is None:
        return None
    dev = run.trace.devices[0]
    busy = tr.total(tr.busy_intervals(dev))
    if not busy:
        return None
    mosaic = np.array([tr.is_mosaic_kernel(n) for n in dev.ops.names], bool)
    return 100.0 * float(tr.self_seconds(dev.ops)[mosaic[dev.ops.name_id]].sum()) / busy
