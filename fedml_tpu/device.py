"""Device & mesh discovery — the TPU-native replacement for the reference's
``python/fedml/device/device.py:51-166`` (process→GPU mapping via yaml files).

On TPU there is no per-process GPU mapping to manage: JAX exposes all local
chips, and parallelism is expressed as a `jax.sharding.Mesh` over them. This
module is the single place that builds meshes for the three runtimes:

- simulation "sp": a trivial 1-device context (reference: device.py:52-60)
- simulation "mesh": a 1-D ``clients`` mesh over all chips (replaces
  gpu_mapping_mpi.py — FL clients become shards of a mesh axis)
- distributed "Cheetah": an N-D mesh (data/fsdp/tensor/sequence/...) built from
  ``args.mesh_shape``
"""

from __future__ import annotations

import logging
import os
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from . import constants

logger = logging.getLogger(__name__)

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable_compilation_cache() -> str:
    """Place XLA's persistent compilation cache and return its directory.

    The one place that decides. With ``JAX_COMPILATION_CACHE_DIR`` set, JAX
    reads the variable itself and nothing is set here; otherwise the cache is
    ``<checkout>/.jax_cache`` (git-ignored). Must run before the first
    compile: JAX opens the cache once per process. A low min-compile-time
    floor keeps mid-sized programs cached; disk is the only cost.
    """
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return cache_dir


def device_kind() -> str:
    return jax.devices()[0].platform


def get_device(args=None):
    """Return the default device (reference API: ``fedml.device.get_device``).

    Honors ``args.device_type`` ("auto" | "tpu" | "cpu"): a non-auto value
    selects that JAX platform explicitly (reference analog: device.py:52-60's
    cpu/gpu/mps dispatch).
    """
    device_type = getattr(args, "device_type", "auto") if args is not None else "auto"
    if device_type and device_type != "auto":
        return jax.devices(device_type)[0]
    return jax.devices()[0]


def build_mesh(
    axis_sizes: Optional[Dict[str, int]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a Mesh from ``{axis_name: size}``.

    If ``axis_sizes`` is empty/None, builds a 1-D ``clients`` mesh over all
    devices. Sizes may include one ``-1`` entry meaning "all remaining devices".
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if not axis_sizes:
        axis_sizes = {constants.MESH_AXIS_CLIENTS: n}
    names = list(axis_sizes.keys())
    sizes = list(axis_sizes.values())
    if sizes.count(-1) > 1:
        raise ValueError("at most one -1 axis size allowed")
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        if n % known != 0:
            raise ValueError(f"cannot infer -1 axis: {n} devices, known product {known}")
        sizes[sizes.index(-1)] = n // known
    total = int(np.prod(sizes))
    if total != n:
        raise ValueError(
            f"mesh {dict(zip(names, sizes))} needs {total} devices, have {n}"
        )
    dev_array = np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, axis_names=tuple(names))


def get_mesh(args) -> Mesh:
    """Mesh for a config namespace (replaces device.py:51-166 dispatch)."""
    axis_sizes = args.parse_mesh_shape() if args is not None else {}
    mesh = build_mesh(axis_sizes)
    logger.info(
        "mesh: %s over %d %s device(s)",
        dict(zip(mesh.axis_names, mesh.devices.shape)),
        mesh.devices.size,
        device_kind(),
    )
    return mesh
