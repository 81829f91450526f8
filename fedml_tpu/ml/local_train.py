"""The functional core of local client training.

Replaces the reference's per-client Python epoch/batch loops
(``ml/trainer/my_model_trainer_classification.py:15-100``: for epoch → for
batch → loss.backward → optimizer.step) with one pure, jit-compatible
function per model:

    local_train(global_params, x, y, n, rng) -> (new_params, metrics)

- batches are a static grid over the packed capacity; a per-epoch
  ``jax.random.permutation`` provides shuffling; padding is masked out
- epochs × batches run under ``lax.scan`` (one XLA while loop, no unrolling)
- the whole function ``vmap``s over a cohort axis — a round of K clients is a
  single fused device program instead of K sequential torch loops
- FedProx's proximal term (reference ``simulation/mpi/fedprox``) is a flag

This is the kernel both simulators (sp/mesh) and cross-silo trainers share.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import optax

# names for what runs outside the flax model: the clients' loss and optax
# update, inside ``local_train``
from ..core.mlops.scopes import round_scope as _scope
from .losses import get_loss_fn
from .optimizer import create_client_optimizer

PyTree = Any
LocalTrainFn = Callable[..., Tuple[PyTree, Dict[str, jnp.ndarray]]]


def make_local_train_fn(
    bundle,
    args,
    cap: int,
    scaffold: bool = False,
) -> LocalTrainFn:
    """Build the pure local-training function for one client shard.

    ``cap`` is the packed per-client capacity; batch grid = cap // batch_size
    (the data layer pads cap to a batch multiple). With ``scaffold=True`` the
    signature grows control variates: ``local_train(params, x, y, n, rng,
    c_global, c_local)`` (SCAFFOLD: stochastic controlled averaging).
    """
    batch_size = int(args.batch_size)
    epochs = int(args.epochs)
    num_batches = max(cap // batch_size, 1)
    loss_fn_raw = get_loss_fn(bundle.task)
    opt = create_client_optimizer(args)
    fedprox_mu = (
        float(getattr(args, "fedprox_mu", 0.0))
        if str(getattr(args, "federated_optimizer", "")).lower() == "fedprox"
        else 0.0
    )
    # bf16 compute (MXU-native) with fp32 master weights: forward/backward in
    # bfloat16, gradients cast back for the fp32 optimizer update. Default
    # fp32 keeps exactness for the parity tests; bench configs turn this on.
    bf16 = str(getattr(args, "train_dtype", "fp32")).lower() in (
        "bf16", "bfloat16"
    )

    def loss_fn(params, bx, by, bmask, rng, global_params):
        if bf16:
            params = jax.tree.map(
                lambda p: p.astype(jnp.bfloat16)
                if jnp.issubdtype(p.dtype, jnp.floating) else p,
                params,
            )
            if jnp.issubdtype(bx.dtype, jnp.floating):
                bx = bx.astype(jnp.bfloat16)
        logits = bundle.apply(params, bx, train=True, rngs={"dropout": rng})
        with _scope("loss"):
            logits = logits.astype(jnp.float32)
            loss, metrics = loss_fn_raw(logits, by, bmask)
            if fedprox_mu > 0.0:
                sq = sum(
                    jnp.sum((p - g) ** 2)
                    for p, g in zip(jax.tree.leaves(params),
                                    jax.tree.leaves(global_params))
                )
                loss = loss + 0.5 * fedprox_mu * sq
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    @_scope("local_train")
    def local_train(global_params, x, y, n, rng, c_global=None, c_local=None):
        """x [cap, ...], y [cap, ...], n = true sample count (scalar)."""
        opt_state = opt.init(global_params)
        nf = n.astype(jnp.float32)

        def epoch_body(carry, erng):
            params, opt_state = carry
            # key discipline (graftrep D001): the epoch key fans out into a
            # shuffle key and a per-batch base BEFORE anything samples —
            # a consumed key is never reused as a fold_in base
            perm_rng, step_rng = jax.random.split(erng)
            perm = jax.random.permutation(perm_rng, cap)

            def batch_body(carry, i):
                params, opt_state = carry
                idx = jax.lax.dynamic_slice(perm, (i * batch_size,), (batch_size,))
                bx = jnp.take(x, idx, axis=0)
                by = jnp.take(y, idx, axis=0)
                bmask = (idx < n).astype(jnp.float32)
                brng = jax.random.fold_in(step_rng, i)
                (loss, _), grads = grad_fn(
                    params, bx, by, bmask, brng, global_params
                )
                with _scope("optimizer"):
                    if scaffold:
                        grads = jax.tree.map(
                            lambda g, cg, cl: g + cg - cl,
                            grads, c_global, c_local,
                        )
                    # guard fully-padded batches: freeze params there
                    has_data = (bmask.sum() > 0).astype(jnp.float32)
                    grads = jax.tree.map(lambda g: g * has_data, grads)
                    updates, opt_state = opt.update(grads, opt_state, params)
                    params = optax.apply_updates(params, updates)
                return (params, opt_state), loss

            (params, opt_state), losses = jax.lax.scan(
                batch_body, (params, opt_state), jnp.arange(num_batches)
            )
            return (params, opt_state), losses.mean()

        erngs = jax.random.split(rng, epochs)
        (params, opt_state), epoch_losses = jax.lax.scan(
            epoch_body, (global_params, opt_state), erngs
        )
        # actual optimizer steps taken on real data (for FedNova tau)
        steps_per_epoch = jnp.ceil(nf / batch_size)
        tau = jnp.maximum(steps_per_epoch * epochs, 1.0)
        metrics = {"train_loss": epoch_losses.mean(), "num_samples": nf, "tau": tau}
        if scaffold:
            # c_local' = c_local - c_global + (global - local)/(tau * lr)
            lr = float(getattr(args, "learning_rate", 0.03))
            new_c = jax.tree.map(
                lambda cl, cg, gp, p: cl - cg + (gp - p) / (tau * lr),
                c_local, c_global, global_params, params,
            )
            return params, metrics, new_c
        return params, metrics

    return local_train


def make_grad_fn(bundle, args, cap: int):
    """One full-batch gradient over a client shard (FedSGD: the reference's
    gradient-level averaging, ``simulation/sp/fedsgd/fedsgd_api.py``)."""
    loss_fn_raw = get_loss_fn(bundle.task)

    def loss_fn(params, x, y, mask, rng):
        logits = bundle.apply(params, x, train=True, rngs={"dropout": rng})
        with _scope("loss"):
            loss, _ = loss_fn_raw(logits, y, mask)
        return loss

    grad = jax.value_and_grad(loss_fn)

    @_scope("local_train")
    def client_grad(global_params, x, y, n, rng):
        mask = (jnp.arange(cap) < n).astype(jnp.float32)
        loss, g = grad(global_params, x, y, mask, rng)
        return g, {
            "train_loss": loss,
            "num_samples": n.astype(jnp.float32),
        }

    return client_grad
