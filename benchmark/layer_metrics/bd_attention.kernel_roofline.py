"""Roofline share of the attention kernels under the block-diffusion mask, in
percent: the operations their calls need
(``benchmark/flops/bd_gqa_moe.attention_kernel_flops``: scores and values over
the ``L^2 + L B`` pairs a head that the mask lets through, by kernel kind)
over their self time on chip 0 and the chip's bf16 peak; the kernels are bound
by compute, so the share is of the FLOP peak. A tile the mask crosses costs
the kernel the whole tile, and an empty tile its bookkeeping: both lower the
share. The calls are the custom calls to ``tpu_custom_call`` whose op name
starts with ``splash_`` and carries ``_fwd``, ``_dq`` or ``_dkv``. Nothing to
read where the program's configuration names no ``block_length`` (every
next-token cell). Layer: attention kernels. Moves ``tokens_per_s_per_chip``."""

from benchmark import harness
from benchmark import trace_reduce as tr


def calls_by_kind(dev):
    """{kind: (calls, self seconds)} of the splash kernels on ``dev``, told
    apart as ``mla_attention.kernel_roofline`` tells them."""
    kind_of = harness.load_module(
        harness.ROOT, "layer_metrics", "mla_attention.kernel_roofline").kind_of
    self_s = tr.self_seconds(dev.ops)
    found = {}
    for i, text in enumerate(dev.ops.names):
        kind = kind_of(text)
        if kind is not None:
            calls = dev.ops.name_id == i
            n, s = found.get(kind, (0, 0.0))
            found[kind] = (n + int(calls.sum()), s + float(self_s[calls].sum()))
    return found


def read(run):
    if (run.trace is None or "block_length" not in run.cell.config
            or "sequences_per_step_per_chip" not in run.facts):
        return None
    found = calls_by_kind(run.trace.devices[0])
    seconds = sum(s for _, s in found.values())
    if not seconds:
        return None
    per_call = harness.load_module(
        run.cell.root, "flops", run.cell.config["flops"]["module"]
    ).attention_kernel_flops(run.cell.config, run.facts["seq_len"],
                             run.facts["sequences_per_step_per_chip"])
    flops = sum(per_call[kind] * n for kind, (n, _) in found.items())
    return 100.0 * flops / (seconds * run.peaks["bf16_flops_per_s"])
