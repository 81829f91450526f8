"""Roofline share of the gated short convolution, in percent: the least time
a step's passes over it can take (``shortconv_least_seconds`` of the
configuration's flops module: per layer, forward and backward, the bytes of
``B``, ``C``, ``x``, the output and, backward, their cotangents over the HBM
peak, or its 3-tap operations over the bf16 peak where that is larger;
running the forward again under remat is not needed work), times the
``conv`` layers of ``layer_types``, over the device seconds a step spends on
it on chip 0, whatever implements it: the self time of the instructions whose
scope path holds ``shortconv_conv`` (the program's ``program_scopes`` map,
``benchmark/scope_time.py``), all passes, plus that of custom calls to
``tpu_custom_call`` named ``shortconv_*`` that the map puts under no such
scope, should a kernel ever stand there; median over the traced steps. Layer:
short_conv (``ShortConvMixer`` in ``parallel/transformer.py``). Moves
``tokens_per_s_per_chip``."""

import statistics

from benchmark import harness
from benchmark import scope_time
from benchmark import trace_reduce as tr

SCOPE = "shortconv_conv"


def kernel_seconds_outside_the_scope(run) -> float:
    """Self seconds on chip 0 of Mosaic calls named ``shortconv_*`` whose scope
    path does not hold ``shortconv_conv`` (those that do are in the scope's
    seconds)."""
    published = scope_time.program_scopes(run)
    ops = run.trace.devices[0].ops
    self_s = tr.self_seconds(ops)
    seconds = 0.0
    for i, text in enumerate(ops.names):
        name = tr.op_name(text)
        if not (tr.is_mosaic_kernel(text) and name.startswith("shortconv_")):
            continue
        key = published["ops"].get(name)
        path = published["scopes"][key][0] if key is not None else ""
        if SCOPE not in path.split("/"):
            seconds += float(self_s[ops.name_id == i].sum())
    return seconds


def conv_seconds_per_step(run):
    """Median device seconds a step spends on the gates and the convolution,
    or None where the step holds none of them."""
    in_scope = scope_time.per_execution(run, scope_time.holds(SCOPE))
    if not in_scope:
        return None
    seconds = (statistics.median(in_scope)
               + kernel_seconds_outside_the_scope(run) / len(in_scope))
    return seconds or None


def read(run):
    if "sequences_per_step_per_chip" not in run.facts:
        return None
    least = getattr(harness.load_module(
        run.cell.root, "flops", run.cell.config["flops"]["module"]),
        "shortconv_least_seconds", None)
    seconds = conv_seconds_per_step(run) if least is not None else None
    if not seconds:
        return None
    layers = list(run.cell.config["layer_types"]).count("conv")
    need = layers * sum(
        least(run.cell.config, run.facts["seq_len"],
              run.facts["sequences_per_step_per_chip"], kind, run.peaks)[0]
        for kind in ("fwd", "bwd"))
    return 100.0 * need / seconds
