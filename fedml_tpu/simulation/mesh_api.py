"""Mesh-parallel Parrot simulation — FL clients sharded over TPU chips.

The TPU-native replacement for BOTH of the reference's multi-process backends
(``simulation/mpi/fedavg/*`` — one OS process per worker exchanging pickled
MPI messages — and ``simulation/nccl/base_framework/*`` — per-GPU local
aggregators doing torch.distributed broadcast/reduce; see SURVEY.md §3.2/§3.3):

- the cohort's packed arrays are sharded over the mesh by RULE-DRIVEN
  ``NamedSharding`` specs: an ordered list of ``(regex, PartitionSpec)``
  rules over named pytree leaves (``scale/partition_rules.py``, the
  ``match_partition_rules`` pattern from the large-model JAX ecosystem —
  SNIPPETS.md [2]/[3]). The defaults reproduce the original hard-coded
  behavior exactly — cohort arrays split on the leading ``clients`` axis,
  round state replicated — and ``--mesh_partition_rules`` /
  ``--mesh_state_rules`` override per-leaf placement without code changes
  (pinned bitwise-equal in ``tests/test_scale.py``)
- the round runs the SAME engine as the sp backend (`FedAvgAPI._train_round`
  around `round_engine.build_round_core`): vmap(local_train) over the sharded
  cohort → attack → defend → weighted average → DP. XLA propagates the input
  shardings through the jit'd round program and lowers the cross-shard
  reduction to collectives over ICI — the explicit `dist.reduce(SUM)` +
  2-rank gather groups of the reference (``params.py:98-127``) become
  compiler-inserted collectives
- cohort padding (to a multiple of the axis size, zero weight) replaces the
  reference's padded schedule tensors (``Server.py:124-128``)

There are no messages, no pickling, no per-worker processes: a round is one
device program launch. Because the whole FedAvg-family engine is inherited,
every federated optimizer (FedProx/FedOpt/FedNova/FedSGD/SCAFFOLD), the
full trust pipeline (attack → defend → aggregate → DP, ``round_engine.py``) and
the million-client registry/prefetch substrate (``scale/``) work
identically on the multi-chip path.
"""

from __future__ import annotations

import logging
import threading

import jax
import numpy as np

from .. import constants
from ..core.mlops import telemetry
from ..device import build_mesh
from ..scale.partition_rules import (
    DEFAULT_COHORT_RULES,
    DEFAULT_STATE_RULES,
    is_scalar_leaf,
    make_shardings,
    match_partition_rules,
    parse_partition_rules,
)
from .sp_api import FedAvgAPI

logger = logging.getLogger(__name__)


class MeshFedAvgAPI(FedAvgAPI):
    """FedAvg-family rounds with the cohort sharded over a ``clients`` axis."""

    # cohorts are host-gathered and placed sharded over the mesh — the
    # single-device HBM-resident fast path must not allocate in __init__
    hbm_resident_default = False
    # the cohort axis is SHARDED over devices: the cohort stays one vmap
    # whatever the model (sp_api.cohort_chunk_rule)
    cohort_sharded = True

    def __init__(self, args, device, dataset, model, client_trainer=None,
                 server_aggregator=None):
        super().__init__(args, device, dataset, model, client_trainer,
                         server_aggregator)
        axis_sizes = args.parse_mesh_shape() or None
        self.mesh = build_mesh(axis_sizes)
        if constants.MESH_AXIS_CLIENTS not in self.mesh.axis_names:
            raise ValueError(
                f"mesh {self.mesh.axis_names} lacks a "
                f"'{constants.MESH_AXIS_CLIENTS}' axis"
            )
        self.axis_size = self.mesh.shape[constants.MESH_AXIS_CLIENTS]
        # rule-driven placement (scale/partition_rules.py): cohort-plane
        # leaves are named "cohort/{x,y,counts,aux}" (aux = the per-round
        # rngs and padding weight mask), round-state leaves keep their
        # pytree paths ("global_params/...", "server_opt_state/...") —
        # the defaults reproduce the legacy first-axis sharding byte for
        # byte
        self.cohort_rules = (
            parse_partition_rules(getattr(args, "mesh_partition_rules", ""))
            or list(DEFAULT_COHORT_RULES)
        )
        self.state_rules = (
            parse_partition_rules(getattr(args, "mesh_state_rules", ""))
            or list(DEFAULT_STATE_RULES)
        )
        # rule resolution is derivable from (rule set, tree structure,
        # scalar pattern) — cache the resulting NamedSharding pytrees so
        # the per-round hot path never re-runs regex matching (the
        # prefetch worker thread also resolves through here, hence the
        # lock around the memo)
        self._sharding_cache = {}
        self._sharding_lock = threading.Lock()
        logger.info(
            "mesh simulator: %d-way client sharding over %s "
            "(%d cohort rules, %d state rules)",
            self.axis_size, self.mesh,
            len(self.cohort_rules), len(self.state_rules),
        )

    def _ledger_world(self):
        """Pin the mesh topology into the run ledger's run_meta: a resumed
        run on a different chip count would silently change cohort padding
        (and so the padded-row math) — ``RunLedger.ensure_meta`` turns that
        into a loud mismatch error instead."""
        world = super()._ledger_world()
        world["mesh_axes"] = {
            str(name): int(self.mesh.shape[name])
            for name in self.mesh.axis_names
        }
        world["device_count"] = int(len(self.mesh.devices.flat))
        return world

    # -- rule resolution ----------------------------------------------------
    def _resolve_shardings(self, which: str, rules, tree):
        """Rules + named pytree → ``NamedSharding`` pytree, memoized on
        (rule set, tree structure, scalar pattern) — all static per run."""
        from jax.tree_util import tree_leaves, tree_structure

        key = (
            which,
            tree_structure(tree),
            # the SAME scalar predicate match_partition_rules applies —
            # the memo is only sound if the key classifies leaves
            # identically to the resolver
            tuple(is_scalar_leaf(leaf) for leaf in tree_leaves(tree)),
        )
        with self._sharding_lock:
            hit = self._sharding_cache.get(key)
        if hit is None:
            hit = make_shardings(
                self.mesh, match_partition_rules(rules, tree)
            )
            with self._sharding_lock:
                self._sharding_cache[key] = hit
        return hit

    def _cohort_shardings(self, named):
        """Resolve the cohort rules over named host arrays → shardings."""
        return self._resolve_shardings("cohort", self.cohort_rules, named)

    # -- FedAvgAPI placement hooks ------------------------------------------
    def _pad_cohort(self, cohort: np.ndarray):
        pad = (-len(cohort)) % self.axis_size
        wmask = np.ones(len(cohort) + pad, np.float32)
        if pad:
            wmask[len(cohort):] = 0.0
            cohort = np.concatenate([cohort, np.zeros(pad, cohort.dtype)])
        return cohort, wmask

    def _place_cohort(self, arrays):
        # one rule resolution + sharded device_put per gather; this is the
        # mesh path's own "gather" phase AND the streamed-cohort placement
        # hook (the prefetcher's worker thread calls it for round r+1)
        cx, cy, cn = arrays
        named = {
            "cohort/x": np.asarray(cx),
            "cohort/y": np.asarray(cy),
            "cohort/counts": np.asarray(cn, np.int32),
        }
        sh = self._cohort_shardings(named)
        return (
            jax.device_put(named["cohort/x"], sh["cohort/x"]),
            jax.device_put(named["cohort/y"], sh["cohort/y"]),
            jax.device_put(named["cohort/counts"], sh["cohort/counts"]),
        )

    def _gather_resident(self, cohort: np.ndarray):
        # host-side gather + sharded device_put: the sp base times this
        # callsite — this shard placement is what its span measures here
        return self._place_cohort((
            self.ds.train_x[cohort],
            self.ds.train_y[cohort],
            self.ds.train_counts[cohort],
        ))

    def _place(self, arr):
        # per-client auxiliaries (per-round rngs, the padding weight mask)
        # ride the cohort rules under "cohort/aux" — leading axis = clients.
        # device_put reshards device-to-device: staging through the host
        # (device_get) here was a per-round gather of the whole aux array
        # over ICI (graftshard S004)
        named = {"cohort/aux": arr}
        sh = self._cohort_shardings(named)
        return jax.device_put(named["cohort/aux"], sh["cohort/aux"])

    def _prepare_round(self):
        # keep global params placed per the state rules (default replicated)
        # so the cohort program reads them without broadcast in the hot loop
        with telemetry.phase("place_params", record=False):
            self.global_params = self._place_state(
                {"global_params": self.global_params}
            )["global_params"]

    def _place_state(self, state):
        # the fused program's donated state must live on the SAME device set
        # as the sharded cohort inputs: commit every leaf per the state
        # rules (default: replicated over the mesh — a no-op copy once
        # steady state re-feeds program outputs). XLA then propagates the
        # input shardings through the fused round and lowers the
        # cross-shard reduction to collectives over ICI.
        with telemetry.phase("place_state", record=False):
            sh = self._resolve_shardings("state", self.state_rules, state)
            return jax.tree.map(jax.device_put, state, sh)
