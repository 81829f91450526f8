"""Roofline share of the experts' grouped products, in percent: the least time
their calls can take (``benchmark/flops/mla_moe.grouped_product_least_seconds``:
per call the larger of ``2 x rows x k x n`` over the bf16 peak and the bytes of
the held experts' matrices, the rows in and the rows out over the HBM peak)
over their self time on chip 0. ``rows`` is the mean number of assignments
that reached a held expert in one expert layer (the program's
``moe_assignments_held`` counter over the expert layers); ``k x n`` is read
from the one 3-D ``[experts held, k, n]`` shape in the call's HLO text (the
matrices operand, or the result where the call yields their gradient). The
calls are the custom calls to ``tpu_custom_call`` that XLA names
``ragged-dot`` (``jax.lax.ragged_dot`` on a TPU), their ``ragged-dot-metadata``
companions' time included; the attention kernels are named ``splash_``.
Layer: expert layer (``parallel/moe.py``). Moves ``tokens_per_s_per_chip``."""

import re
import statistics

from benchmark import harness
from benchmark import trace_reduce as tr

_SHAPE = re.compile(r"\b(?:bf16|f32)\[(\d+),(\d+),(\d+)\]")


def read(run):
    held = [r["counters"]["moe_assignments_held"] for r in run.records
            if "moe_assignments_held" in (r.get("counters") or {})]
    if run.trace is None or not held:
        return None
    dev = run.trace.devices[0]
    rows = statistics.fmean(held) / run.facts["expert_layers"]
    least = harness.load_module(
        run.cell.root, "flops", run.cell.config["flops"]["module"]
    ).grouped_product_least_seconds
    self_s = tr.self_seconds(dev.ops)
    need = seconds = 0.0
    for i, text in enumerate(dev.ops.names):
        if not (tr.is_mosaic_kernel(text)
                and tr.op_name(text).startswith("ragged-dot")):
            continue
        calls = dev.ops.name_id == i
        seconds += float(self_s[calls].sum())
        shapes = [tuple(map(int, m.groups())) for m in _SHAPE.finditer(text)
                  if int(m.group(1)) == run.facts["experts_held"]]
        if shapes and "metadata" not in tr.op_name(text):
            groups, k, n = shapes[0]
            need += least(rows, groups, k, n, run.peaks)[0] * int(calls.sum())
    return 100.0 * need / seconds if seconds else None
