"""Seconds JAX spent compiling, or loading compiled programs from the
persistent cache, before the window opened. Layer: entry
(``fedml_tpu.init``, ``device.enable_compilation_cache``). Source: the
program's ``jax.monitoring`` listener (``/jax/core/compile/
backend_compile_duration``, summed by ``telemetry``). Moves ``setup_s``: cold
it is most of set-up, warm it is what deserialising the cache costs."""


def read(run):
    return run.counters["compile_s"]
