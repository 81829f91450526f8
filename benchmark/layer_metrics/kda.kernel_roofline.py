"""Roofline share of the KDA chunk kernels, in percent: the least time their
calls can take (``kda_kernel_least_seconds`` of the configuration's flops
module: per call the larger of the sequential part's operations over the bf16
peak and the bytes of its operands, output and gradients over the HBM peak)
over their self time on chip 0. The calls are the custom calls to
``tpu_custom_call`` whose op name starts with ``kda_chunk`` and carries
``_fwd`` or ``_bwd`` (``parallel/kda.py`` names them). Layer: linear
attention. Moves ``tokens_per_s_per_chip``."""

from benchmark import harness
from benchmark import trace_reduce as tr

KINDS = ("fwd", "bwd")


def kind_of(text):
    """Which KDA chunk kernel an op is, or None."""
    name = tr.op_name(text)
    if not (tr.is_mosaic_kernel(text) and name.startswith("kda_chunk")):
        return None
    return next((k for k in KINDS if f"_{k}" in name), None)


def calls_by_kind(dev):
    """{kind: (number of calls, their self seconds)} on one device."""
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
    if run.trace is None or "sequences_per_step_per_chip" not in run.facts:
        return None
    found = calls_by_kind(run.trace.devices[0])
    least = getattr(harness.load_module(
        run.cell.root, "flops", run.cell.config["flops"]["module"]),
        "kda_kernel_least_seconds", None)
    seconds = sum(s for _, s in found.values())
    if not found or least is None or not seconds:
        return None
    need = sum(n * least(run.cell.config, run.facts["seq_len"],
                         run.facts["sequences_per_step_per_chip"], kind,
                         run.peaks)[0] for kind, (n, _) in found.items())
    return 100.0 * need / seconds
