"""Roofline share of the experts' grouped products, in percent, whatever
implements them: the least time their calls can take
(``grouped_product_least_seconds`` of the configuration's flops module: per
call the larger of ``2 x rows x k x n`` over the bf16 peak and the bytes of
the matrices the call must touch, the rows in and the rows out over the HBM
peak) over their self time on chip 0. ``rows`` is the mean number of
assignments that reached a held expert in one expert layer (the program's
``moe_assignments_held`` counter over the expert layers); ``k x n`` is read
from the one 3-D ``[experts held, k, n]`` shape in the call's HLO text.

Which matrices a call must touch follows from where that shape stands. Where
it is an operand (the forward product and the rows' gradient), the call must
read the matrices of the held experts that received rows, and no other: the
program's ``moe_held_experts_with_rows`` counter ((expert layer, held expert)
pairs with at least one assignment in the step) over the expert layers. A
program without that counter is charged every held matrix, which counts too
much wherever a held expert gets no rows. Where the shape is the call's
result (the matrices' gradient), the call reads two row arrays and writes
every held matrix, an empty expert's as zeros, and reads none.

The calls are the custom calls to ``tpu_custom_call`` whose scope path holds
``moe_products`` (the program's ``program_scopes`` map,
``benchmark/scope_time.py``), and those named ``ragged-dot*`` (XLA's kernel of
``jax.lax.ragged_dot``, its ``ragged-dot-metadata`` companions' time included)
or ``expert_gmm*`` (``parallel/expert_gmm.py``) that stand under no such
scope: so XLA's calls, which carry no name stack, and the repo's kernels read
as the same work. Layer: expert layer (``parallel/moe.py``). Moves
``tokens_per_s_per_chip``."""

import re
import statistics

from benchmark import harness
from benchmark import scope_time
from benchmark import trace_reduce as tr

SCOPE = "moe_products"
_SHAPE = re.compile(r"\b(?:bf16|f32)\[(\d+),(\d+),(\d+)\]")


def _in_scope(run):
    """Op name -> whether the program's map puts it under ``moe_products``
    (nothing where the run published no map)."""
    published = scope_time.program_scopes(run)
    if published is None:
        return {}
    scopes = published["scopes"]
    return {name: SCOPE in str(scopes[key][0]).split("/")
            for name, key in published["ops"].items()}


def _mean_counter(run, name):
    found = [r["counters"][name] for r in run.records
             if name in (r.get("counters") or {})]
    return statistics.fmean(found) if found else None


def read(run):
    assigned = _mean_counter(run, "moe_assignments_held")
    if run.trace is None or assigned is None:
        return None
    dev = run.trace.devices[0]
    layers, held = run.facts["expert_layers"], run.facts["experts_held"]
    rows = assigned / layers
    with_rows = _mean_counter(run, "moe_held_experts_with_rows")
    read_matrices = held if with_rows is None else with_rows / layers
    least = harness.load_module(
        run.cell.root, "flops", run.cell.config["flops"]["module"]
    ).grouped_product_least_seconds
    in_scope = _in_scope(run)
    self_s = tr.self_seconds(dev.ops)
    need = seconds = 0.0
    for i, text in enumerate(dev.ops.names):
        name = tr.op_name(text)
        if not (tr.is_mosaic_kernel(text)
                and (in_scope.get(name)
                     or name.startswith(("ragged-dot", "expert_gmm")))):
            continue
        calls = dev.ops.name_id == i
        seconds += float(self_s[calls].sum())
        shapes = [m for m in _SHAPE.finditer(text) if int(m.group(1)) == held]
        if shapes and "metadata" not in name:
            _, k, n = map(int, shapes[0].groups())
            written = shapes[0].start() < text.find("custom-call(")
            matrices = held if written else read_matrices
            need += least(rows, matrices, k, n, run.peaks)[0] * int(calls.sum())
    return 100.0 * need / seconds if seconds else None
