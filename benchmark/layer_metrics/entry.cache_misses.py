"""Programs the persistent compilation cache did not hold, before the window
opened. Layer: entry. Source: the program's ``jax.monitoring`` listener
(``/jax/compilation_cache/cache_misses``). Moves ``setup_s``: 0 on every run
after a checkout's first; anything else is a program whose cache key changes
from run to run."""


def read(run):
    return run.counters["cache_misses"]
