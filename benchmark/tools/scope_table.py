#!/usr/bin/env python3
"""Device self time by scope, from a recorded ``.xplane.pb``.

    python3 benchmark/run.py --workload <cell> --seed 1 --seconds 20 --trace 1 --keep-trace
    python3 benchmark/tools/scope_table.py chiprun_out/benchmark/<cell>/trace/plugins/profile/*/*.xplane.pb

A TPU trace names every device op twice: by XLA's name (``fusion.583``) and,
in the op's metadata, by the JAX name stack it was traced under (the ``tf_op``
stat: ``jit(_train_step_raw)/transpose(jvp(Transformer))/CheckpointBlock_0/
FeedForward_0/bld,df->blf/dot_general``), with XLA's ``flops`` and
``bytes_accessed`` beside it. This tool cuts each name stack down to the
elements that are a scope of the program's vocabulary
(``fedml_tpu/core/mlops/scopes.py``) or a flax module (``Attention_0``;
indices are merged unless ``--layers``), notes whether the op belongs to the
forward pass, the backward pass (``transpose(..)``) or the recomputation of
the forward inside the backward (``rematted_computation``), and sums the self time of chip
0's ``XLA Ops`` line by that key: a ``while`` counts for what it spends
outside its body's ops. It prints the share of busy time whose stack holds no
such element, and for ``--op <XLA name>`` the full stack of that op.

A builder's tool like ``cut_xplane.py``: it needs the ``xplane_pb2`` schema
that ships with TensorFlow, which the benchmark itself never imports.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from collections import defaultdict

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
_MODULE = re.compile(r"^[A-Z][A-Za-z0-9]*(_\d+)?$")
_RECOMPUTE = "rematted_computation"
NO_NAME = "(no name)"


def split_stack(tf_op: str):
    """``a/transpose(jvp(B))/c:`` -> ["a", "transpose(jvp(B))", "c"]; a slash
    inside parentheses (an einsum spec has none, a nested jit may) stays."""
    parts, depth, cur = [], 0, ""
    for ch in tf_op.rstrip(":"):
        if ch == "/" and depth == 0:
            parts.append(cur)
            cur = ""
            continue
        depth += ch == "("
        depth -= ch == ")"
        cur += ch
    return parts + [cur] if cur else parts


def peel(element: str):
    """``transpose(jvp(Transformer))`` -> (["transpose", "jvp"], "Transformer")."""
    wrappers = []
    while True:
        m = _WRAPPED.match(element)
        if not m:
            return wrappers, element
        wrappers.append(m.group(1))
        element = m.group(2)


def scope_key(tf_op: str, vocabulary, layers: bool = False):
    """(scope path, pass) of one name stack. The first element is the
    program (``jit(core)``) and the last the primitive; neither is a scope."""
    kept, backward, recompute = [], False, False
    for element in split_stack(tf_op)[1:-1]:
        wrappers, name = peel(element)
        backward |= "transpose" in wrappers
        recompute |= name == _RECOMPUTE
        if "jit" in wrappers or "pjit" in wrappers:
            continue  # a nested jit's function name (jit(_take)) is no scope
        if name in vocabulary or _MODULE.match(name):
            name = name if layers else re.sub(r"_\d+$", "", name)
            if not kept or kept[-1] != name:  # transpose(jvp(M))/jvp(M)/..
                kept.append(name)
    # under transpose(..), ``checkpoint/rematted_computation/..`` is the
    # forward run again and ``checkpoint/..`` alone the backward proper
    which = "remat" if recompute else ("bwd" if backward else "fwd")
    return "/".join(kept) or NO_NAME, which


def read_ops(path: str, ordinal: int = 0):
    """Chip ``ordinal``'s ``XLA Ops`` events with their metadata."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    from benchmark import trace_reduce as tr

    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    plane = next(p for p in space.planes if p.name == f"/device:TPU:{ordinal}")
    stat_names = {k: v.name for k, v in plane.stat_metadata.items()}

    def stats_of(meta):
        out = {}
        for s in meta.stats:
            name = stat_names.get(s.metadata_id)
            if name == "tf_op":
                out[name] = s.str_value or stat_names.get(s.ref_value, "")
            elif name in ("flops", "bytes_accessed"):
                out[name] = float(s.double_value or s.uint64_value or s.int64_value)
        return out

    line = next(ln for ln in plane.lines if ln.name == "XLA Ops")
    ids, metas = {}, []
    name_id, start, dur = [], [], []
    for e in line.events:
        if e.metadata_id not in ids:
            ids[e.metadata_id] = len(metas)
            meta = plane.event_metadata[e.metadata_id]
            metas.append((meta.name, stats_of(meta)))
        name_id.append(ids[e.metadata_id])
        start.append(e.offset_ps)
        dur.append(e.duration_ps)
    start_s = np.asarray(start, np.float64) * 1e-12
    events = tr.Events([m[0] for m in metas], np.asarray(name_id, int),
                       start_s, start_s + np.asarray(dur, np.float64) * 1e-12)
    return events, [m[1] for m in metas]


def table(events, stats, vocabulary, layers: bool = False):
    """Rows of {scope, pass, self_s, share, flops, bytes, ops} by self time,
    and per XLA op name its (scope, pass, tf_op, self_s)."""
    from benchmark import trace_reduce as tr

    self_s = tr.self_seconds(events)
    per_meta = np.bincount(events.name_id, weights=self_s,
                           minlength=len(events.names))
    counts = np.bincount(events.name_id, minlength=len(events.names))
    busy = float(per_meta.sum())
    rows = defaultdict(lambda: {"self_s": 0.0, "flops": 0.0, "bytes": 0.0,
                                "ops": defaultdict(float)})
    by_op = {}
    for i, text in enumerate(events.names):
        tf_op = stats[i].get("tf_op", "")
        key = scope_key(tf_op, vocabulary, layers) if tf_op else (NO_NAME, "fwd")
        row = rows[key]
        row["self_s"] += float(per_meta[i])
        if not tr.is_control_flow(text):  # a while's count holds its body's
            row["flops"] += counts[i] * stats[i].get("flops", 0.0)
            row["bytes"] += counts[i] * stats[i].get("bytes_accessed", 0.0)
        row["ops"][tr.op_name(text)] += float(per_meta[i])
        by_op[tr.op_name(text)] = (key[0], key[1], tf_op, float(per_meta[i]))
    out = []
    for (scope, which), row in sorted(rows.items(), key=lambda kv: -kv[1]["self_s"]):
        top = sorted(row["ops"].items(), key=lambda kv: -kv[1])[:2]
        out.append({"scope": scope, "pass": which, "self_s": row["self_s"],
                    "share": 100.0 * row["self_s"] / busy if busy else 0.0,
                    "flops": row["flops"], "bytes": row["bytes"],
                    "ops": [[n, 100.0 * s / busy if busy else 0.0] for n, s in top]})
    return out, by_op, busy


def vocabulary():
    from fedml_tpu.core.mlops import scopes

    return set(scopes.TRAIN_STEP) | set(scopes.ROUND) | set(scopes.EVALUATE)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("xplane")
    parser.add_argument("--chip", type=int, default=0)
    parser.add_argument("--top", type=int, default=25)
    parser.add_argument("--layers", action="store_true",
                        help="keep module indices (Attention_0, Attention_1)")
    parser.add_argument("--op", action="append", default=[],
                        help="also print this XLA op's full name stack")
    parser.add_argument("--json", help="write the whole table there")
    opts = parser.parse_args(argv)

    events, stats = read_ops(opts.xplane, opts.chip)
    rows, by_op, busy = table(events, stats, vocabulary(), opts.layers)
    print(f"{opts.xplane}: chip {opts.chip}, {len(events)} op events, busy "
          f"{busy:.6f}s")
    print("| Scope | Pass | Self s | % busy | GFLOP | GB | Largest ops (% busy) |")
    print("| --- | --- | --- | --- | --- | --- | --- |")
    for r in rows[:opts.top]:
        ops = ", ".join(f"`{n}` {s:.1f}" for n, s in r["ops"])
        print(f"| `{r['scope']}` | {r['pass']} | {r['self_s']:.5f} | "
              f"{r['share']:.2f} | {r['flops'] / 1e9:.1f} | "
              f"{r['bytes'] / 1e9:.2f} | {ops} |")
    rest = rows[opts.top:]
    if rest:
        print(f"| {len(rest)} more | | {sum(r['self_s'] for r in rest):.5f} | "
              f"{sum(r['share'] for r in rest):.2f} | | | |")
    unnamed = sum(r["share"] for r in rows if r["scope"] == NO_NAME)
    print(f"busy time under no vocabulary or module name: {unnamed:.2f}%")
    for name in opts.op:
        scope, which, tf_op, seconds = by_op.get(name, ("?", "?", "not in the trace", 0.0))
        print(f"{name}: {100.0 * seconds / busy:.2f}% of busy, scope `{scope}` "
              f"({which}), stack {tf_op}")
    if opts.json:
        with open(opts.json, "w") as f:
            json.dump({"busy_s": busy, "unnamed_share": unnamed, "rows": rows,
                       "ops": {k: list(v) for k, v in by_op.items()}}, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
