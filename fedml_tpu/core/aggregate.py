"""Aggregation kernels — the TPU-native replacement for the reference's
``python/fedml/ml/aggregator/agg_operator.py:4-29`` (``FedMLAggOperator.agg``,
an O(params × clients) Python dict loop).

Design: client updates live *stacked* — every leaf carries a leading
``[num_clients]`` axis — so aggregation is one ``tensordot`` per leaf that XLA
fuses and tiles onto the MXU, and the same arrays shard directly over a
``clients`` mesh axis for the mesh-parallel simulator (aggregation then rides
ICI as a weighted ``psum``).
"""

from __future__ import annotations

from typing import Any, List, Sequence

import jax
import jax.numpy as jnp

PyTree = Any


def stack_trees(trees: Sequence[PyTree]) -> PyTree:
    """Stack a list of identically-shaped pytrees along a new leading axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def unstack_tree(stacked: PyTree, num: int) -> List[PyTree]:
    """Inverse of :func:`stack_trees`."""
    return [jax.tree.map(lambda x: x[i], stacked) for i in range(num)]


def weighted_average(stacked: PyTree, weights: jax.Array) -> PyTree:
    """Weighted mean over the leading (clients) axis of every leaf.

    ``weights`` are unnormalised sample counts (reference semantics:
    ``agg_operator.py:23-29`` divides by total training number). A zero weight
    sum (e.g. a fully-masked cohort) yields a zero aggregate, not NaN —
    callers that can hit that case should keep the previous global model.
    """
    w = weights / jnp.maximum(jnp.sum(weights), 1e-12)

    def _leaf(x):
        return jnp.tensordot(w.astype(x.dtype), x, axes=1)

    return jax.tree.map(_leaf, stacked)


def masked_weighted_average(
    stacked: PyTree, weights: jax.Array, mask: jax.Array
) -> PyTree:
    """Weighted mean where ``mask`` (0/1 per client) disables padded slots.

    Padded cohort slots are how dynamic client sampling becomes static-shaped
    under jit (SURVEY.md §7 "Hard parts": fixed cohort + padded schedules).
    """
    w = weights * mask
    return weighted_average(stacked, w)


class FedMLAggOperator:
    """API-compatible facade (reference: ``FedMLAggOperator.agg``).

    The reference implements only FedAvg-style weighted averaging here and
    raises for other optimizers; server-side optimizers (FedOpt/FedNova) apply
    optax transforms to the pseudo-gradient in the simulation layer.
    """

    @staticmethod
    def agg(args, stacked: PyTree, weights: jax.Array) -> PyTree:
        return weighted_average(stacked, weights)


def fednova_normalized_direction(
    global_params: PyTree, stacked: PyTree, tau: jax.Array
) -> PyTree:
    """Per-client normalized direction (w_g - w_i)/tau_i, leaf-wise.

    Used by the round (``simulation/round_engine.py``) for FedNova.
    """
    return jax.tree.map(
        lambda g, s: (g[None] - s) / tau.reshape((-1,) + (1,) * (s.ndim - 1)),
        global_params,
        stacked,
    )


def pseudo_gradient(w_global: PyTree, w_aggregated: PyTree) -> PyTree:
    """Server pseudo-gradient: g = w_global - avg(w_clients).

    This is the quantity FedOpt-family server optimizers step on
    (reference: ``simulation/sp/fedopt/fedopt_api.py`` set_model_global_grads).
    """
    return jax.tree.map(jnp.subtract, w_global, w_aggregated)
