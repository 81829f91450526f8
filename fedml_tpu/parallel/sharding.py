"""Sharding rules: logical axis names → mesh axes.

This is the heart of the Cheetah design (SURVEY.md §2.5): where the reference
scales by NCCL process groups + DDP wrappers, the TPU build picks a mesh,
annotates shardings, and lets XLA insert collectives (scaling-book recipe).

Mesh axes (constants.py): ``data`` (pure DP), ``fsdp`` (parameter-sharded DP),
``tensor`` (Megatron-style TP over ICI), ``sequence`` (context parallelism /
ring attention), ``pipeline``, ``expert``. Any axis can be size 1 — the same
rules serve 1 chip to a pod.

Parameter sharding follows the standard recipe:
- attention QKV [d, heads*hd]: (fsdp, tensor) — column-parallel
- attention out [heads*hd, d]: (tensor, fsdp) — row-parallel
- MLP gate/up  [d, ff]:        (fsdp, tensor)
- MLP down     [ff, d]:        (tensor, fsdp)
- embedding    [vocab, d]:     (tensor, fsdp) — vocab-parallel
- lm head      [d, vocab]:     (fsdp, tensor)
- latent attention: down-projections [d, rank] (fsdp, -), up-projections
  [rank, heads*hd] (-, tensor), out [heads*v, d] (tensor, fsdp)
- hyper-connection maps [streams*d, 2n + n^2]: (fsdp, -)
- experts [held, d, 2f] / [held, f, d]: (expert, fsdp, tensor) /
  (expert, tensor, fsdp); router [d, E]: (fsdp, -)
- norms, biases, gains: replicated

Activations: batch over (data, fsdp), sequence over (sequence).

XLA places every collective these shardings imply but one: the chunked loss
(``train_step.lm_loss_chunked``) runs per batch shard in a ``shard_map`` and
moves the LM head itself: one all-gather over ``fsdp`` of the head's compute
dtype cast before the chunk scan, one reduce-scatter of its gradient after
the backward scan (the partitioner put both inside every chunk). Under
``tensor`` > 1 the gathered head keeps its vocabulary shard and the scan is
vocabulary-parallel: per chunk only [B, chunk] reductions over ``tensor``.
"""

from __future__ import annotations

import logging
from typing import Any, Optional, Tuple

import jax
import numpy as np
from flax import linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import constants
from .transformer import BATCH, EMBED, HEADS, KV, LENGTH, LORA, MLP, VOCAB

logger = logging.getLogger(__name__)

PyTree = Any

DATA = constants.MESH_AXIS_DATA
FSDP = constants.MESH_AXIS_FSDP
TENSOR = constants.MESH_AXIS_TENSOR
SEQUENCE = constants.MESH_AXIS_SEQUENCE
PIPELINE = constants.MESH_AXIS_PIPELINE
EXPERT = constants.MESH_AXIS_EXPERT

from .moe import EXPERT_AXIS  # noqa: E402  (no cycle: moe imports names only)

# logical → mesh axis (t5x-style rules)
LOGICAL_RULES = (
    (EXPERT_AXIS, EXPERT),
    (EMBED, FSDP),
    (VOCAB, TENSOR),
    (HEADS, TENSOR),
    (KV, None),
    (LORA, None),   # the low-rank side of a latent projection
    (MLP, TENSOR),
    (BATCH, (DATA, FSDP)),
    (LENGTH, SEQUENCE),
)


def make_mesh(
    shape: Optional[dict] = None, devices=None
) -> Mesh:
    """Build the Cheetah mesh. Default: all devices on ``fsdp``.

    ``shape`` e.g. ``{"data": 1, "fsdp": 2, "tensor": 2, "sequence": 2}``;
    missing axes get size 1 so downstream PartitionSpecs always resolve.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if not shape:
        shape = {FSDP: n}
    full = {DATA: 1, FSDP: 1, TENSOR: 1, SEQUENCE: 1, EXPERT: 1}
    full.update(shape)
    if -1 in full.values():
        known = int(np.prod([s for s in full.values() if s != -1]))
        for k, v in full.items():
            if v == -1:
                full[k] = n // known
    total = int(np.prod(list(full.values())))
    if total != n:
        raise ValueError(f"mesh {full} needs {total} devices, have {n}")
    dev_array = np.asarray(devices).reshape(list(full.values()))
    return Mesh(dev_array, axis_names=tuple(full.keys()))


def compat_shard_map(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the varying-manual-axes check (the Pallas
    kernels and the hand-scheduled ring/pipeline bodies do not annotate
    it). Pipeline, attention kernels and ring attention all wrap here."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def batch_mesh_axes(mesh: Mesh) -> Tuple[str, ...]:
    """The mesh axes that shard the batch dimension of activations — the
    canonical layout every constraint/kernel wrap must agree on."""
    from .. import constants as _c

    return tuple(
        a for a in (_c.MESH_AXIS_DATA, _c.MESH_AXIS_FSDP)
        if int(mesh.shape.get(a, 1)) > 1
    )


def logical_to_mesh_spec(logical_axes: Tuple) -> P:
    rules = dict(LOGICAL_RULES)
    return P(*(rules.get(a) if a is not None else None for a in logical_axes))


def param_shardings(mesh: Mesh, params: PyTree) -> PyTree:
    """NamedShardings for a param tree produced by modules that used
    ``nn.with_partitioning`` (boxed params carry their logical axis names)."""

    def _one(p):
        if isinstance(p, nn.Partitioned):
            spec = logical_to_mesh_spec(p.names)
        else:
            spec = P()
        return NamedSharding(mesh, spec)

    return jax.tree.map(
        _one, params, is_leaf=lambda x: isinstance(x, nn.Partitioned)
    )


def unbox(params: PyTree) -> PyTree:
    """Strip nn.Partitioned boxes → raw arrays (after placement)."""
    return jax.tree.map(
        lambda p: p.value if isinstance(p, nn.Partitioned) else p,
        params,
        is_leaf=lambda x: isinstance(x, nn.Partitioned),
    )


def unboxed_param_shardings(mesh: Mesh, boxed_params: PyTree) -> PyTree:
    """Shardings matching the *unboxed* tree structure."""
    shardings = param_shardings(mesh, boxed_params)
    # shardings tree has NamedSharding at the positions of boxed leaves;
    # structure already matches the unboxed tree (one leaf per param)
    return shardings


def batch_sharding(mesh: Mesh, seq_sharded: bool = False) -> NamedSharding:
    """Sharding for token batches [B, L]."""
    if seq_sharded:
        return NamedSharding(mesh, P((DATA, FSDP), SEQUENCE))
    return NamedSharding(mesh, P((DATA, FSDP), None))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
