"""Roofline share of the latent-attention kernels, in percent: the operations
their calls need (``benchmark/flops/mla_moe.attention_kernel_flops``: causal
scores at the 192-wide query/key head and values at the 128-wide value head,
by kernel kind) over their self time on chip 0 and the chip's bf16 peak; the
kernels are bound by compute, so the share is of the FLOP peak. The calls are
the custom calls to ``tpu_custom_call`` whose op name starts with ``splash_``
and carries ``_fwd``, ``_dq`` or ``_dkv`` (the grouped products are named
``ragged-dot``). Layer: attention kernels. Moves ``tokens_per_s_per_chip``."""

import numpy as np

from benchmark import harness
from benchmark import trace_reduce as tr

KINDS = ("fwd", "dq", "dkv")


def kind_of(text):
    """Which splash kernel an op is, or None."""
    name = tr.op_name(text)
    if not (tr.is_mosaic_kernel(text) and name.startswith("splash_")):
        return None
    return next((k for k in KINDS if f"_{k}" in name), None)


def read(run):
    if run.trace is None or "sequences_per_step_per_chip" not in run.facts:
        return None
    dev = run.trace.devices[0]
    kinds = [kind_of(n) for n in dev.ops.names]
    if not any(kinds):
        return None
    per_call = harness.load_module(
        run.cell.root, "flops", run.cell.config["flops"]["module"]
    ).attention_kernel_flops(run.cell.config, run.facts["seq_len"],
                             run.facts["sequences_per_step_per_chip"])
    self_s = tr.self_seconds(dev.ops)
    flops = seconds = 0.0
    for i, kind in enumerate(kinds):
        if kind is not None:
            calls = dev.ops.name_id == i
            flops += per_call[kind] * int(calls.sum())
            seconds += float(self_s[calls].sum())
    return 100.0 * flops / (seconds * run.peaks["bf16_flops_per_s"])
