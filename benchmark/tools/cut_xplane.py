#!/usr/bin/env python3
"""Cut a small fixture out of a recorded ``.xplane.pb``.

    python3 benchmark/tools/cut_xplane.py <in.xplane.pb> <out.xplane.pb> \
        --module _train_step_raw --executions 2

Keeps chip 0's device plane with the first ``--executions`` executions of the
program whose function is ``--module`` and every event of the ``XLA Ops`` and
``Async XLA Ops`` lines that lies inside them, plus the ``Task Environment``
plane (the profile's start on the host clock). Drops the host planes, the
other chips and every event-metadata entry nothing refers to any more.

A builder's tool, run by hand once per fixture: it needs the ``xplane_pb2``
schema that ships with TensorFlow, which the benchmark itself never imports
(``trace_reduce`` reads traces with ``jax.profiler.ProfileData`` alone).
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("source")
    parser.add_argument("target")
    parser.add_argument("--module", required=True)
    parser.add_argument("--executions", type=int, default=2)
    opts = parser.parse_args(argv)

    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    from benchmark import trace_reduce as tr

    space = xplane_pb2.XSpace()
    with open(opts.source, "rb") as f:
        space.ParseFromString(f.read())
    out = xplane_pb2.XSpace()
    for plane in space.planes:
        if plane.name == "Task Environment":
            out.planes.add().CopyFrom(plane)
        if plane.name != "/device:TPU:0":
            continue
        modules = next(ln for ln in plane.lines if ln.name == "XLA Modules")
        runs = [e for e in modules.events if tr.module_function(
            plane.event_metadata[e.metadata_id].name) == opts.module]
        runs = sorted(runs, key=lambda e: e.offset_ps)[:opts.executions]
        spans = [(modules.timestamp_ns * 1000 + e.offset_ps,
                  modules.timestamp_ns * 1000 + e.offset_ps + e.duration_ps)
                 for e in runs]
        kept = out.planes.add()
        kept.id, kept.name = plane.id, plane.name
        kept.stats.extend(plane.stats)
        used = set()
        for line in plane.lines:
            if line.name not in ("XLA Modules", "XLA Ops", "Async XLA Ops"):
                continue
            new = kept.lines.add()
            new.id, new.name = line.id, line.name
            new.timestamp_ns = line.timestamp_ns
            for e in line.events:
                start = line.timestamp_ns * 1000 + e.offset_ps
                if any(s <= start and start + e.duration_ps <= t for s, t in spans):
                    new.events.add().CopyFrom(e)
                    used.add(e.metadata_id)
        for key in used:
            kept.event_metadata[key].CopyFrom(plane.event_metadata[key])
        stat_ids = ({s.metadata_id for ln in kept.lines for e in ln.events
                     for s in e.stats} | {s.metadata_id for s in kept.stats}
                    | {s.metadata_id for m in kept.event_metadata.values()
                       for s in m.stats})
        for key in stat_ids:
            kept.stat_metadata[key].CopyFrom(plane.stat_metadata[key])
    with open(opts.target, "wb") as f:
        f.write(out.SerializeToString())
    print(f"{opts.target}: {os.path.getsize(opts.target)} bytes, "
          f"{sum(len(ln.events) for p in out.planes for ln in p.lines)} events")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
