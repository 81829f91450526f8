"""Sequence-parallel activation context.

The transformer module needs to know, at trace time, whether activations are
sharded over the ``sequence`` mesh axis (→ use ring attention via shard_map)
— but Flax modules can't take a Mesh as a call argument without threading it
through every layer. A context manager scopes it instead; CheetahTrainer
enters it around jit tracing when ``seq_sharded=True``.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import Optional

import jax
from jax.sharding import Mesh

from .. import constants


@dataclass(frozen=True)
class SeqParallelCtx:
    mesh: Mesh
    axis_name: str
    size: int


# contextvars, not module globals: FL runtimes trace models from several
# FSM threads at once (one per silo client in-process), and one thread's
# parallelism context must never leak into another thread's trace
import contextvars

_ACTIVE: contextvars.ContextVar[Optional[SeqParallelCtx]] = (
    contextvars.ContextVar("fedml_tpu_seq_ctx", default=None)
)


@contextlib.contextmanager
def sequence_parallelism(mesh: Mesh, axis_name: str = constants.MESH_AXIS_SEQUENCE):
    """Activate sequence parallelism for model traces inside the block."""
    size = int(mesh.shape[axis_name]) if axis_name in mesh.axis_names else 1
    token = _ACTIVE.set(
        SeqParallelCtx(mesh, axis_name, size) if size > 1 else None
    )
    try:
        yield _ACTIVE.get()
    finally:
        _ACTIVE.reset(token)


def get_seq_context() -> Optional[SeqParallelCtx]:
    return _ACTIVE.get()


# -- ambient mesh (batch/tensor sharding) ------------------------------------
# Pallas kernels cannot be auto-partitioned by pjit ("Mosaic kernels cannot
# be automatically partitioned") — the attention kernels must be wrapped in
# shard_map over whatever mesh the step is jitted under. Same pattern as the
# sequence context: CheetahTrainer scopes its mesh here around tracing, and
# the Attention module reads it at trace time.

_MESH: "contextvars.ContextVar[Optional[Mesh]]" = (
    contextvars.ContextVar("fedml_tpu_mesh_ctx", default=None)
)


@contextlib.contextmanager
def mesh_context(mesh: Optional[Mesh]):
    """Scope the ambient mesh for model traces inside the block."""
    token = _MESH.set(mesh if mesh is not None and mesh.size > 1 else None)
    try:
        yield _MESH.get()
    finally:
        _MESH.reset(token)


def get_mesh_context() -> Optional[Mesh]:
    return _MESH.get()


def one_tpu_device(mesh: Optional[Mesh]) -> bool:
    """Whether a program traced under ``mesh`` (the ambient one, or a
    trainer's) runs on one TPU device: no mesh, or one of a single device,
    on the TPU back-end. The one condition every kernel family's path
    function shares (Mosaic kernels are not partitioned by pjit, and no
    wrapper shards the newer ones yet)."""
    return (mesh is None or mesh.size == 1) and jax.devices()[0].platform == "tpu"
