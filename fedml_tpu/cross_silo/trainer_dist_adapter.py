"""Intra-silo data parallelism: one silo client training over its local chips.

reference: ``cross_silo/client/fedml_trainer_dist_adapter.py:24-36`` — wraps
the trainer in torch DDP over the silo's process group and
``fedml_client_slave_manager.py`` keeps non-master ranks training in step.

TPU-native re-design: the silo's chips are ICI-connected, so instead of a
DDP wrapper + per-step NCCL all-reduce, the whole local-training loop runs as
ONE ``shard_map`` program over the silo mesh (``process_group.SiloProcessGroup``):

- each device holds a contiguous ``cap/k`` slice of the client's packed shard
- every optimizer step draws ``batch_size`` samples per device (global batch
  = k x batch_size, the torch-DDP convention) and weighted-``psum``s the
  gradients over the ``silo_dp`` axis — the exact global-batch gradient,
  with padding masked per device
- the optimizer update is computed identically on every device, so params
  stay replicated without any broadcast

The master/slave message FSM survives only for DCN-separated silo members
(``client_slave_manager.ClientSlaveManager``) where per-step psum is not
economical.
"""

from __future__ import annotations

import logging
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.containers import BoundedDict
from ..ml.losses import get_loss_fn
from ..ml.optimizer import create_client_optimizer
from .process_group import SILO_AXIS, SiloProcessGroup

logger = logging.getLogger(__name__)

PyTree = Any


def make_silo_dp_train_fn(bundle, args, local_cap: int, mesh, axis=SILO_AXIS):
    """Per-device local training with per-step gradient psum over the silo.

    Returns a jitted fn ``(global_params, x, y, n_per_dev, rng) -> (params,
    metrics)`` where ``x``/``y`` are [k*local_cap, ...] (sharded over devices
    on axis 0) and ``n_per_dev`` is [k] real-sample counts per device slice.
    """
    k = int(mesh.shape[axis])
    batch_size = int(args.batch_size)
    epochs = int(args.epochs)
    num_batches = max(local_cap // batch_size, 1)
    loss_fn_raw = get_loss_fn(bundle.task)
    opt = create_client_optimizer(args)

    def loss_fn(params, bx, by, bmask, rng):
        logits = bundle.apply(params, bx, train=True, rngs={"dropout": rng})
        loss, metrics = loss_fn_raw(logits.astype(jnp.float32), by, bmask)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def device_train(global_params, x, y, n_dev, rng):
        """One device's view: x [local_cap, ...], n_dev [1]."""
        n_local = n_dev[0].astype(jnp.float32)
        # distinct sampling stream per device, same param trajectory
        drng = jax.random.fold_in(rng, jax.lax.axis_index(axis))
        opt_state = opt.init(global_params)

        def epoch_body(carry, e):
            params, opt_state = carry
            erng = jax.random.fold_in(drng, e)
            # key discipline (graftrep D001): shuffle key and per-batch base
            # derived up front — the consumed perm key is never reused
            perm_rng, step_rng = jax.random.split(erng)
            perm = jax.random.permutation(perm_rng, local_cap)

            def batch_body(carry, i):
                params, opt_state = carry
                idx = jax.lax.dynamic_slice(
                    perm, (i * batch_size,), (batch_size,)
                )
                bx = jnp.take(x, idx, axis=0)
                by = jnp.take(y, idx, axis=0)
                bmask = (idx < n_local).astype(jnp.float32)
                brng = jax.random.fold_in(step_rng, i)
                (loss, _), grads = grad_fn(params, bx, by, bmask, brng)
                # weighted all-reduce: exact global-batch gradient with
                # per-device padding masked out
                w = bmask.sum()
                wsum = jax.lax.psum(w, axis)
                safe = jnp.maximum(wsum, 1.0)
                grads = jax.tree.map(
                    lambda g: jax.lax.psum(g * w, axis) / safe, grads
                )
                loss = jax.lax.psum(loss * w, axis) / safe
                has_data = (wsum > 0).astype(jnp.float32)
                grads = jax.tree.map(lambda g: g * has_data, grads)
                updates, opt_state = opt.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
                return (params, opt_state), loss

            (params, opt_state), losses = jax.lax.scan(
                batch_body, (params, opt_state), jnp.arange(num_batches)
            )
            return (params, opt_state), losses.mean()

        (params, _), epoch_losses = jax.lax.scan(
            epoch_body, (global_params, opt_state), jnp.arange(epochs)
        )
        n_total = jax.lax.psum(n_local, axis)
        steps = jnp.ceil(n_total / (k * batch_size))
        metrics = {
            "train_loss": epoch_losses.mean(),
            "num_samples": n_total,
            "tau": jnp.maximum(steps * epochs, 1.0),
        }
        return params, metrics

    data_spec = P(axis)
    fn = shard_map(
        device_train,
        mesh=mesh,
        in_specs=(P(), data_spec, data_spec, data_spec, P()),
        out_specs=(P(), P()),
    )
    return jax.jit(fn)


class TrainerDistAdapter:
    """Adapts a ClientTrainer so ``train()`` runs silo-data-parallel.

    reference: ``fedml_trainer_dist_adapter.py:24-36`` (DDP wrap + update_model
    / update_dataset). Holds the silo ``SiloProcessGroup``; with one device it
    degrades to the plain trainer.
    """

    def __init__(self, args, trainer, process_group: Optional[SiloProcessGroup] = None):
        self.args = args
        self.trainer = trainer
        self.model = trainer.model  # bundle passthrough for manager FSMs
        self.group = process_group or SiloProcessGroup()
        # jit cache keyed by padded per-device capacity (graftmem M002):
        # capacities are batch-multiples of a fixed geometry, so a handful
        # of entries is steady state — the bound is a backstop against a
        # pathological shard-size walk recompiling (and retaining) forever
        self._jitted: Dict[int, Any] = BoundedDict(8, lru=True,
                                                   name="trainer.jit_cache")

    # trainer facade ---------------------------------------------------------
    def get_model_params(self) -> PyTree:
        return self.trainer.get_model_params()

    def set_model_params(self, params: PyTree) -> None:
        self.trainer.set_model_params(params)

    def train(self, train_data, device, args) -> Dict[str, Any]:
        """train_data = (x [cap, ...], y [cap, ...], n) for this client."""
        k = self.group.size
        if k <= 1:
            return self.trainer.train(train_data, device, args)
        x, y, n = train_data
        # shared split geometry with the DCN path (client_slave_manager):
        # per-device capacity a non-zero batch multiple, contiguous real rows
        from .client_slave_manager import padded_silo_split

        x, y, local_cap, n_dev = padded_silo_split(
            x, y, int(n), k, int(self.args.batch_size)
        )
        if local_cap not in self._jitted:
            self._jitted[local_cap] = make_silo_dp_train_fn(
                self.trainer.model, self.args, local_cap, self.group.mesh
            )
        fn = self._jitted[local_cap]
        rng = jax.random.fold_in(
            jax.random.PRNGKey(int(getattr(args, "random_seed", 0))),
            int(getattr(args, "round_idx", 0)) * 100003
            + int(getattr(self.trainer, "id", 0)),
        )
        shard = NamedSharding(self.group.mesh, P(SILO_AXIS))
        with self.group.mesh:
            params, metrics = fn(
                self.trainer.get_model_params(),
                jax.device_put(jnp.asarray(x), shard),
                jax.device_put(jnp.asarray(y), shard),
                jax.device_put(jnp.asarray(n_dev), shard),
                rng,
            )
        self.trainer.set_model_params(params)
        return {key: float(v) for key, v in metrics.items()}
