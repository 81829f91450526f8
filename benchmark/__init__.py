"""The repo's benchmark: one cell (a configuration under a traffic mix) per run.

``python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
is the only entry point; ``BENCHMARK.json`` at the repo root lists the cells
and metrics. Everything that belongs to one configuration, one traffic mix or
one per-layer metric is a file of its own, found by the name in
``BENCHMARK.json``; see ``PERF.md`` for why each exists.
"""
