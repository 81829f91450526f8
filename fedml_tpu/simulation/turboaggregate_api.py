"""TurboAggregate: secure aggregation via additive shares in a ring of groups.

reference: ``simulation/sp/turboaggregate/`` (TA_trainer.py, mpc_function.py
281 LoC — additive shares + Lagrange coding demo). Demo semantics preserved:
each client splits its update into additive shares so no single party (or
sub-threshold coalition) sees an individual update, yet the group sums —
passed along the ring — reconstruct the exact aggregate. The share split is
over the LightSecAgg finite field (core/mpc/lightsecagg.py) so the demo is
information-theoretically hiding, not just float-noise masking.
"""

from __future__ import annotations

import logging
from typing import Dict

import jax
import numpy as np

from ..core.mpc import lightsecagg as lsa
from ..utils.tree import tree_flatten_to_vector, tree_unflatten_from_vector
from .sp_api import FedAvgAPI

logger = logging.getLogger(__name__)


class TurboAggregateAPI(FedAvgAPI):
    """FedAvg where the server only ever sees share-sums, not raw updates."""

    def __init__(self, args, device, dataset, model, client_trainer=None,
                 server_aggregator=None):
        super().__init__(args, device, dataset, model, client_trainer,
                         server_aggregator)
        self.q_bits = int(getattr(args, "ta_quantize_bits", 8))
        self.group_size = int(getattr(args, "ta_group_size", 2))

    def _aggregate(self, stacked, weights, rng, n_valid, client_ids):
        """The round's host aggregation rule (``FedAvgAPI._host_rule``):
        replace the trusted-server average with additive-share aggregation.

        Each client i quantizes its weighted update and splits it into
        ``group_size`` additive shares mod p; share s goes to ring position
        (i+s). Every position sums what it received; the server adds the
        position sums — algebraically Σ_i update_i, with no position ever
        holding a complete individual update.
        """
        import jax.numpy as jnp

        n = n_valid  # the rows are the real clients: padding is dropped
        w = np.asarray(weights, np.float64)
        w = w / max(w.sum(), 1e-12)
        _, treedef, shapes = tree_flatten_to_vector(self.global_params)
        flat = np.asarray(
            jax.vmap(lambda t: tree_flatten_to_vector(t)[0])(stacked)
        )
        d = flat.shape[1]
        rs = np.random.RandomState(
            int(getattr(self.args, "random_seed", 0)) + 17
        )
        S = min(self.group_size, n)
        position_sums = np.zeros((n, d), np.int64)
        for i in range(n):
            q = lsa.quantize_to_field(flat[i] * w[i], self.q_bits)
            shares = rs.randint(0, lsa.FIELD_P, size=(S - 1, d)).astype(np.int64)
            last = (q - shares.sum(axis=0)) % lsa.FIELD_P
            all_shares = np.concatenate([shares, last[None]], axis=0)
            for s in range(S):
                position_sums[(i + s) % n] = (
                    position_sums[(i + s) % n] + all_shares[s]
                ) % lsa.FIELD_P
        total = np.zeros(d, np.int64)
        for i in range(n):
            total = (total + position_sums[i]) % lsa.FIELD_P
        agg = lsa.dequantize_from_field(total, self.q_bits)
        return tree_unflatten_from_vector(
            jnp.asarray(agg, jnp.float32), treedef, shapes
        )
