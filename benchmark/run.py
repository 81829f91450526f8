#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads ``BENCHMARK.json`` at the root of the checkout for the cell, warms up
every shape the cell uses (set-up, with the plain-reference check), measures
one window of about ``--seconds`` through the program's own loop and prints,
as the last line of standard output, one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device``: with ``--trace 0`` the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics from a
short window under the profiler, plus ``breakdown``. Progress goes to standard
error and ``chiprun_out/benchmark/<cell>/``.

Exits non-zero, printing no result, off a TPU, with fewer chips than the cell
asks for, or in a directory without the repo. One process holds the chip:
this script starts no child that touches JAX. The compile cache is where
``fedml_tpu.device.enable_compilation_cache`` puts it
(``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(message: str) -> None:
    sys.stderr.write(f"benchmark [{time.perf_counter() - T_START:7.1f}s] {message}\n")
    sys.stderr.flush()


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--keep-trace", action="store_true",
                        help="leave the .xplane.pb under chiprun_out/ (for "
                             "cutting a fixture or reading a trace by hand)")
    opts = parser.parse_args(argv)

    if not os.path.isfile(os.path.join(ROOT, "fedml_tpu", "__init__.py")):
        sys.stderr.write(
            f"benchmark: no fedml_tpu package in {ROOT}: the benchmark drives "
            f"the repo and measures nothing alone.\n")
        return 1
    sys.path.insert(0, ROOT)
    from benchmark import harness

    cell = harness.load_cell(opts.workload)
    harness.require_devices(cell.chips)
    from fedml_tpu.device import enable_compilation_cache

    log(f"cell {cell.name}, seed {opts.seed}, compile cache "
        f"{enable_compilation_cache()}")
    result = harness.run_cell(cell, seed=opts.seed, seconds=opts.seconds,
                              trace=bool(opts.trace), t_start=T_START,
                              keep_trace=opts.keep_trace, log=log)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
