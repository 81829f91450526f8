"""Test bootstrap: force an 8-device virtual CPU platform BEFORE jax backend
initialisation. Tests run on the 8-device CPU mesh, so multi-chip sharding
paths are exercised without TPU hardware (SURVEY.md §4: multi-host emulation
via --xla_force_host_platform_device_count).
"""

import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: XLA:CPU compiles dominate suite wall-clock;
# caching them across runs cuts repeat suites substantially. (Repo root on
# sys.path first: bare `pytest` only adds tests/.)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from fedml_tpu.device import enable_compilation_cache  # noqa: E402

enable_compilation_cache()


import pytest  # noqa: E402


@pytest.fixture
def fused_mhc_backward(monkeypatch):
    """The hyper-connections' one-pass stream backward that a TPU takes
    (``parallel/mhc_streams.py``), on this CPU: the choice forced, the
    kernels' bodies run by Pallas' TPU interpreter. Yields the list of the
    backward kernels traced so far (``"read"`` / ``"write"``). Called eagerly
    a kernel is waited for: the interpreter runs JAX operations in callbacks
    from inside the program, and a backward pass that dispatches on ahead of
    it can fill XLA:CPU's queue of programs in flight and starve them."""
    import jax
    from jax.experimental.pallas import tpu as pltpu

    from fedml_tpu.parallel import mhc_streams

    traced = []
    for op in ("read", "write"):
        rule = getattr(mhc_streams, f"{op}_bwd")
        monkeypatch.setattr(
            mhc_streams, f"{op}_bwd",
            lambda *a, op=op, rule=rule: traced.append(op)
            or jax.block_until_ready(rule(*a)))
    monkeypatch.setattr(mhc_streams, "backward_path", lambda *a: "fused")
    with pltpu.force_tpu_interpret_mode():
        yield traced
