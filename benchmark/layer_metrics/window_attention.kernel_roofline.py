"""Roofline share of the window layers' attention kernels, in percent: the
operations their calls need (``attention_kernel_flops`` of the configuration's
flops module, ``benchmark/flops/swa_moe.py``: scores and values over the
``sum_i min(i + 1, W)`` pairs a head of the band ``(i - W, i]``, by kernel
kind) over their self time on chip 0 and the chip's bf16 peak; the kernels
are bound by compute, so the share is of the FLOP peak. The calls are the
custom calls to ``tpu_custom_call`` named ``splash_*`` with ``_fwd``, ``_dq``
or ``_dkv`` whose scope path holds ``window_attention`` (the program's
``program_scopes`` map, ``benchmark/scope_time.py``): the attention of the
``sliding_attention`` layers, not of the full ones, whatever their shapes.

The kernel computes whole tiles. At blocks of 512 queries x 512 keys and a
window of 1,024 a query block's band reaches into 3 key tiles and holds 2
tiles' worth of pairs, so even a kernel at the peak inside every tile it
computes reads at most about 67% here; the tiles the band crosses also
evaluate the mask pair by pair. Nothing to read where the run published no
map or the step holds no window layer. Layer: attention kernels. Moves
``tokens_per_s_per_chip``."""

from benchmark import harness
from benchmark import scope_time
from benchmark import trace_reduce as tr

SCOPE = "window_attention"


def calls_by_kind(run):
    """{kind: (calls, self seconds)} on chip 0 of the splash kernels the
    program's map puts under ``window_attention`` ({} without a map)."""
    published = scope_time.program_scopes(run)
    if run.trace is None or published is None:
        return {}
    kind_of = harness.load_module(
        harness.ROOT, "layer_metrics", "mla_attention.kernel_roofline").kind_of
    scopes, where = published["scopes"], published["ops"]
    dev = run.trace.devices[0]
    self_s = tr.self_seconds(dev.ops)
    found = {}
    for i, text in enumerate(dev.ops.names):
        kind, key = kind_of(text), where.get(tr.op_name(text))
        if kind is None or key is None or SCOPE not in str(
                scopes[key][0]).split("/"):
            continue
        calls = dev.ops.name_id == i
        n, s = found.get(kind, (0, 0.0))
        found[kind] = (n + int(calls.sum()), s + float(self_s[calls].sum()))
    return found


def read(run):
    if "sequences_per_step_per_chip" not in run.facts:
        return None
    found = calls_by_kind(run)
    seconds = sum(s for _, s in found.values())
    if not seconds:
        return None
    per_call = harness.load_module(
        run.cell.root, "flops", run.cell.config["flops"]["module"]
    ).attention_kernel_flops(run.cell.config, run.facts["seq_len"],
                             run.facts["sequences_per_step_per_chip"])
    flops = sum(per_call[kind] * n for kind, (n, _) in found.items())
    return 100.0 * flops / (seconds * run.peaks["bf16_flops_per_s"])
