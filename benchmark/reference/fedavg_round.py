"""One FedAvg round (McMahan et al. 2017, Algorithm 1) as plain loops.

For each sampled client: start from the global parameters, run ``epochs``
passes of minibatch SGD over its shard, one jitted step per batch; then
average the clients' parameters weighted by their sample counts. No vmap, no
scan, no fused round.

To land on the same batches as the system, the replay follows the system's
documented randomness (``simulation/sp_api.py``, ``ml/local_train.py``):

- the cohort of round ``r`` is ``RandomState(r).choice(clients, per_round,
  replace=False)`` (the reference FedML's seeding), all clients if equal;
- round key = ``fold_in(PRNGKey(seed), r)``, one key per cohort slot by
  ``split``; per client one key per epoch by ``split``; each epoch key splits
  into (shuffle key, step key); the epoch's order is
  ``permutation(shuffle key, cap)`` over the padded capacity, batch ``i`` takes
  ``order[i * batch : (i + 1) * batch]``;
- a slot ``>= n`` (the client's true count) is padding: it is masked out of
  the loss, the loss is the mean over real samples, and a batch with no real
  sample leaves the parameters alone.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def sample_cohort(round_idx: int, client_num: int, per_round: int) -> np.ndarray:
    if client_num == per_round:
        return np.arange(client_num)
    return np.random.RandomState(round_idx).choice(
        client_num, per_round, replace=False)


def masked_cross_entropy(logits, labels, mask):
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def replay_round(forward, global_params, shards_x, shards_y, counts, seed: int,
                 round_idx: int, batch_size: int, epochs: int, lr: float):
    """``forward(params, x) -> logits``. ``shards_x``: [cohort, cap, ...] for
    the cohort of this round, in cohort order. Returns (aggregated params,
    mean of the clients' mean batch losses)."""
    cohort, cap = shards_x.shape[0], shards_x.shape[1]
    num_batches = max(cap // batch_size, 1)

    @jax.jit
    def sgd_step(params, x, y, idx, n):
        mask = (idx < n).astype(jnp.float32)

        def loss_fn(p):
            return masked_cross_entropy(forward(p, x[idx]), y[idx], mask)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        keep = (mask.sum() > 0).astype(jnp.float32)
        return jax.tree.map(lambda p, g: p - lr * keep * g, params, grads), loss

    round_key = jax.random.fold_in(jax.random.PRNGKey(seed), round_idx)
    client_keys = jax.random.split(round_key, cohort)
    trained, losses = [], []
    for c in range(cohort):
        params = global_params
        x, y, n = jnp.asarray(shards_x[c]), jnp.asarray(shards_y[c]), jnp.int32(counts[c])
        epoch_losses = []
        for epoch_key in jax.random.split(client_keys[c], epochs):
            shuffle_key, _ = jax.random.split(epoch_key)
            order = jax.random.permutation(shuffle_key, cap)
            batch_losses = []
            for i in range(num_batches):
                idx = order[i * batch_size:(i + 1) * batch_size]
                params, loss = sgd_step(params, x, y, idx, n)
                batch_losses.append(loss)
            epoch_losses.append(jnp.stack(batch_losses).mean())
        trained.append(params)
        losses.append(jnp.stack(epoch_losses).mean())
    w = jnp.asarray(counts, jnp.float32)
    w = w / w.sum()
    aggregated = jax.tree.map(
        lambda *leaves: sum(wi * leaf for wi, leaf in zip(w, leaves)), *trained)
    return aggregated, float(jnp.stack(losses).mean())
