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
