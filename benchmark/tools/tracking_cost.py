#!/usr/bin/env python3
"""What the program's tracking costs when it is on: one cell's loop, tracked
against untracked, in one process.

    python3 benchmark/tools/tracking_cost.py --workload <cell> --seed 1 --units 12 --repeats 3

Sets the cell up once as a traced run does (tracking on, every shape warm),
then runs ``--repeats`` pairs of windows of ``--units`` rounds or steps
through the program's own loop, alternately untracked and tracked, with no
profiler: the switch is ``telemetry.set_enabled`` and the sink's own flag, so
both sides run the same compiled programs on the same runner. Prints one JSON
line: per side the rate of each window on the host's clock (FedAvg: rounds a
second; Cheetah: the median step period in seconds) and, for the tracked
side, the median seconds of each span name, which says where the host's time
between two device steps goes when no profiler runs.

A builder's tool like ``cut_xplane.py``; no metric of the benchmark reads it.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--units", type=int, default=12)
    parser.add_argument("--repeats", type=int, default=3)
    opts = parser.parse_args(argv)

    import jax
    import numpy as np

    from benchmark import harness
    from fedml_tpu.core import mlops
    from fedml_tpu.core.mlops import telemetry
    from fedml_tpu.device import enable_compilation_cache

    def log(message):
        sys.stderr.write(f"tracking_cost: {message}\n")

    cell = harness.load_cell(opts.workload)
    harness.require_devices(cell.chips)
    enable_compilation_cache()
    work_dir = os.path.join(ROOT, "chiprun_out", "tracking_cost", cell.name)
    os.makedirs(work_dir, exist_ok=True)
    telemetry.install_jax_listeners()
    job = harness.load_module(cell.root, "jobs", cell.job).Job(
        cell, seed=opts.seed, tracked=True, work_dir=work_dir, log=log)
    job.setup()

    def window(tracked: bool):
        telemetry.set_enabled(tracked)
        mlops.MLOpsStore.enabled = tracked
        seen = len(mlops.read_events()) if tracked else 0
        if cell.job == "fedavg":
            job.args.comm_round = opts.units
            jax.block_until_ready(job.api.global_params)
            t0 = time.perf_counter()
            job.runner.run()
            jax.block_until_ready(job.api.global_params)
            rate = opts.units / (time.perf_counter() - t0)
        else:
            job._loop(opts.units)
            # steps 0 and 1 follow the state's re-initialisation
            rate = float(np.median(np.diff(job._step_started)[2:]))
        spans = {}
        if tracked:
            records = [e for e in mlops.read_events()[seen:]
                       if e.get("kind") == "round_record"][2:]
            for rec in records:
                for s in rec["spans"]:
                    spans.setdefault(s["name"], []).append(s["dur_ns"] * 1e-9)
            spans = {k: statistics.median(v) for k, v in spans.items()}
            spans["wall_s"] = statistics.median(r["wall_s"] for r in records)
        return rate, spans

    out = {"workload": cell.name, "units": opts.units,
           "what": "rounds/s" if cell.job == "fedavg" else "median step period, s",
           "untracked": [], "tracked": [], "tracked_spans_median_s": []}
    for _ in range(opts.repeats):
        rate, _spans = window(False)
        out["untracked"].append(rate)
        rate, spans = window(True)
        out["tracked"].append(rate)
        out["tracked_spans_median_s"].append(spans)
        log(f"untracked {out['untracked'][-1]:.6f}, tracked {rate:.6f}")
    u, t = statistics.median(out["untracked"]), statistics.median(out["tracked"])
    out["tracked_over_untracked"] = t / u
    out["device"] = {"platform": jax.devices()[0].platform,
                     "kind": jax.devices()[0].device_kind,
                     "count": len(jax.devices())}
    mlops.close()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
