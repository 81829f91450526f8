"""Single-process Parrot simulation — the canonical FL loop, TPU-first.

Replaces the reference's ``simulation/sp/fedavg/fedavg_api.py:65-232`` (Python
loop: per-client deepcopy → torch train → dict-average) and its per-optimizer
clones (``sp/fedopt``, ``sp/fedprox``, ``sp/fednova``, ``sp/fedsgd``) with ONE
engine:

- the round's cohort trains as ``local_train`` over a stacked
  ``[cohort, cap, ...]`` gather of the packed dataset: one ``vmap``, or for a
  convolutional model a few clients at a time (``cohort_chunk_rule``)
- what happens to the cohort's results is ONE function,
  ``round_engine.build_round_core``: local DP / clipping → attack → defense or
  aggregation → server update → central DP (the reference's hook order). The
  federated optimizer enters as (a) a flag inside the local loss (FedProx),
  (b) a server-side optax transform on the pseudo-gradient
  (FedOpt/FedAdam/FedYogi/FedAdagrad), (c) normalized averaging (FedNova),
  (d) gradient-level averaging (FedSGD), or (e) control variates (SCAFFOLD)
- this module owns everything around that function: sampling, gather and
  placement (the hooks the mesh engine overrides), the round state, how the
  round is executed (``_host_rule`` / ``_setup_round``: one donated program,
  or op by op when the aggregation rule is host Python), superrounds,
  checkpoint / resume and the training loop

Client sampling stays host-side and round-seeded exactly like the reference
(``fedavg_api.py:125-140``: ``np.random.seed(round_idx)`` + choice).
"""

from __future__ import annotations

import logging
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import constants
from ..core.dp import FedPrivacyMechanism
from ..core.mlops import telemetry
from ..core.mlops.scopes import ROUND
from ..core.security.attacker import FedMLAttacker
from ..core.security.defender import FedMLDefender
from ..ml.evaluate import make_eval_fn
from ..ml.local_train import make_grad_fn, make_local_train_fn
from ..ml.optimizer import create_server_optimizer
from ..utils.tree import tree_zeros_like

logger = logging.getLogger(__name__)

PyTree = Any

SERVER_OPT_FAMILY = (
    constants.FEDML_FEDERATED_OPTIMIZER_FEDOPT,
    constants.FEDML_FEDERATED_OPTIMIZER_FEDSGD,
)


# Clients of a convolutional model's cohort that train in one batched program.
# ``jax.vmap`` over per-client kernels makes every convolution a grouped one
# (``feature_group_count`` = clients), and the TPU compiler realises the groups
# as one more spatial dimension: a window of as many taps over an input dilated
# as many times, of which one tap in each window meets a real value (v5e,
# ResNet-56, cohort 10: ``window={size=3x3x10 stride=1x1x9 pad=1_1x1_1x0_0
# lhs_dilate=1x1x10}, dim_labels=b012f_201io->b012f``, forward and backward; the
# filter gradients ``size=32x32x10``). Its cost grows with the clients that
# share it, faster than their number. Measured on the chip (PERF.md section 6,
# PR 31: v5e, ResNet-56, cohort 10, rounds/s in `fedavg_resnet56_iid` / `_lda`):
# 10 clients a convolution (one vmap) 1.425 / 0.845, 5: 1.613, 2: 2.643 / 1.568,
# 1: 3.638 / 2.163, with `peak_hbm_gb` 5.37 -> 1.72 (a tenth of the temporaries).
M_CONV = 1


def cohort_chunk_rule(conv_model: bool, sharded: bool, cohort: int) -> int:
    """How many of a cohort's clients train in one batched program; the
    cohort size means one ``vmap`` over all of them. A convolutional model's
    cohort runs ``M_CONV`` at a time (above); batched matmuls (lr, mlp, the
    RNNs, the transformer heads) pay nothing for a cohort axis and stay one
    ``vmap``, and so does a cohort axis that is sharded over a mesh, which
    ``lax.map`` would serialize onto one program."""
    if conv_model and not sharded:
        return min(M_CONV, cohort)
    return cohort


def _over_cohort(fn: Callable, in_axes: tuple, chunk: int,
                 cohort: int) -> Callable:
    """``fn`` for every client of a cohort: arguments whose ``in_axes`` entry
    is 0 carry a leading cohort axis, ``None`` marks one shared by all.
    ``chunk`` clients at a time are batched by ``vmap`` and the chunks run one
    after another under ``lax.map`` (a remainder as its own, smaller chunk);
    a chunk of the whole cohort is a bare ``vmap``. Identical math, the same
    stacked outputs."""
    if chunk >= cohort:
        return jax.vmap(fn, in_axes=in_axes)
    stacked = [i for i, axis in enumerate(in_axes) if axis is not None]

    def one_client(args, rows):
        args = list(args)
        for i, row in zip(stacked, rows):
            args[i] = row
        return fn(*args)

    return lambda *args: jax.lax.map(
        lambda rows: one_client(args, rows), tuple(args[i] for i in stacked),
        batch_size=None if chunk == 1 else chunk,
    )


class FedAvgAPI:
    """One engine for the sp FedAvg-family optimizers.

    ``federated_optimizer`` ∈ {FedAvg, FedAvg_seq, FedProx, FedOpt, FedNova,
    FedSGD, SCAFFOLD}. (FedAvg_seq is identical to FedAvg here: "sequential
    multi-client per device" is an artifact of the reference's MPI process
    model — under vmap the whole cohort is already one device program.)
    """

    # subclasses whose placement hooks gather host-side (mesh) flip this OFF
    # so __init__ never parks a dead dataset copy in device-0 HBM
    hbm_resident_default = True

    # is the cohort axis sharded over a mesh's devices (MeshFedAvgAPI)? Read
    # by ``cohort_chunk_rule``; the engine's answer is ``self.cohort_chunk``
    cohort_sharded = False

    @staticmethod
    def _hbm_budget() -> int:
        """60% of the device's memory limit. XLA:CPU reports no limit and
        gets 4 GB; a TPU that reports none is an error, not a guess."""
        dev = jax.devices()[0]
        limit = int((dev.memory_stats() or {}).get("bytes_limit", 0))
        if limit > 0:
            return int(limit * 0.6)
        if dev.platform == "tpu":
            raise RuntimeError(
                f"{dev} reports no bytes_limit in memory_stats(); cannot "
                "size the HBM-resident dataset budget"
            )
        return 4 * 1024**3

    def __init__(self, args, device, dataset, model, client_trainer=None,
                 server_aggregator=None):
        self.args = args
        self.device = device
        self.ds = dataset
        self.bundle = model
        self.opt_name = str(args.federated_optimizer)
        self.custom_trainer = client_trainer
        self.custom_aggregator = server_aggregator

        # million-client cohort substrate (fedml_tpu/scale/ — docs/scale.md):
        # when --client_registry is set, WHO participates each round comes
        # from a registry of N virtual clients (on-device seeded K-of-N
        # sampling) and the cohort's shards stream in through a
        # double-buffered prefetcher instead of a resident gather. The round
        # math below is untouched — cohorts are still dataset rows.
        from ..scale import build_cohort_engine

        self.cohort_engine = build_cohort_engine(args, dataset)
        if (self.cohort_engine is not None
                and self.opt_name
                == constants.FEDML_FEDERATED_OPTIMIZER_SCAFFOLD
                and not self.cohort_engine.registry.injective_shards()):
            # aliased shard pointers put duplicate rows in every cohort;
            # SCAFFOLD's per-client variate scatter (.at[rows].set) is
            # order-unspecified under duplicates — refuse loudly rather
            # than silently break the bitwise-determinism guarantee
            raise ValueError(
                "SCAFFOLD needs per-client control variates, but this "
                "registry aliases multiple clients onto the same data "
                "shard (non-injective shard pointers). Use an injective "
                "registry (ClientRegistry.from_dataset) or a different "
                "federated_optimizer."
            )
        if self.cohort_engine is not None:
            self.cohort_engine.set_host_gather(self._host_gather_rows)
            self.cohort_engine.set_cohort_transform(
                lambda rows: self._pad_cohort(rows)[0]
            )
            logger.info(
                "cohort engine: %d registered clients, cohort %d, "
                "prefetch depth %d",
                self.cohort_engine.registry.num_clients,
                self.cohort_engine.cohort_size,
                self.cohort_engine.prefetcher.depth,
            )

        seed = int(getattr(args, "random_seed", 0))
        self.root_rng = jax.random.PRNGKey(seed)
        self.global_params = model.init(self.root_rng)

        self.scaffold = self.opt_name == constants.FEDML_FEDERATED_OPTIMIZER_SCAFFOLD
        self.fedsgd = self.opt_name == constants.FEDML_FEDERATED_OPTIMIZER_FEDSGD
        self.fednova = self.opt_name == constants.FEDML_FEDERATED_OPTIMIZER_FEDNOVA

        per = self._cohort_size()
        self.cohort_chunk = cohort_chunk_rule(
            bool(getattr(model, "conv_model", False)), self.cohort_sharded,
            per)
        logger.info("sp engine: cohort of %d trains %d clients at a time",
                    per, self.cohort_chunk)
        if self.fedsgd:
            fn = make_grad_fn(model, args, self.ds.cap)
        else:
            fn = make_local_train_fn(model, args, self.ds.cap,
                                     scaffold=self.scaffold)
        # (params, x, y, counts, rngs), and SCAFFOLD's (c_global, c_locals)
        axes = (None, 0, 0, 0, 0) + ((None, 0) if self.scaffold else ())
        self.cohort_fn = jax.jit(
            _over_cohort(fn, axes, self.cohort_chunk, per))

        # server optimizer over pseudo-gradients (FedOpt family + FedSGD)
        self.server_opt = None
        self.server_opt_state = None
        if self.opt_name in SERVER_OPT_FAMILY:
            self.server_opt = create_server_optimizer(args)
            self.server_opt_state = self.server_opt.init(self.global_params)

        if self.scaffold:
            self.c_global = tree_zeros_like(self.global_params)
            # per-client control variates, stacked [clients, ...]
            self.c_locals = jax.tree.map(
                lambda x: jnp.zeros((self.ds.client_num,) + x.shape, x.dtype),
                self.global_params,
            )

        # HBM-resident federation (SURVEY.md §7 "Heterogeneous per-client data
        # residency"): park the whole packed dataset on device once and gather
        # cohorts there — no per-round host→device transfer. Falls back to
        # host-side gather for datasets too large for HBM. The budget is
        # queried from the device (60% of its memory limit, leaving room for
        # params/grads/cohort working set); 4 GB on XLA:CPU, which reports none.
        total_bytes = self.ds.train_x.nbytes + self.ds.train_y.nbytes
        self.hbm_resident = (self.hbm_resident_default
                             and total_bytes < self._hbm_budget())
        if (self.cohort_engine is not None
                and max(int(getattr(args, "superround_k", 0) or 0), 0) <= 1):
            # registry rounds stream through the prefetcher — a resident
            # dataset copy would be dead HBM for the whole run. Superround
            # is the exception: its scan gathers on device and needs
            # _dev_x et al.
            self.hbm_resident = False
        if self.hbm_resident:
            self._dev_x = jax.device_put(self.ds.train_x)
            self._dev_y = jax.device_put(self.ds.train_y)
            self._dev_counts = jax.device_put(
                self.ds.train_counts.astype(np.int32)
            )

        self.evaluate = make_eval_fn(model)
        self.attacker = FedMLAttacker.get_instance()
        self.attacker.init(args)
        self.defender = FedMLDefender.get_instance()
        self.defender.init(args)
        if self.custom_aggregator is not None and self.defender.is_defense_enabled():
            # a robust defense (krum/median/...) IS the aggregation rule — it
            # cannot compose with a user ServerAggregator override. Silently
            # dropping either one would betray whoever configured it, so fail
            # fast. (Model attacks DO compose: they transform client rows
            # before whatever aggregation runs — see round_engine.)
            raise ValueError(
                "enable_defense and a custom ServerAggregator are mutually "
                f"exclusive: defense_type={self.defender.defense_type!r} "
                "replaces the aggregation rule. Disable one of them."
            )
        self.dp = (
            FedPrivacyMechanism.from_args(args)
            if bool(getattr(args, "enable_dp", False))
            else None
        )
        self.history: List[Dict[str, float]] = []

        # -- the round (round_engine.py), built lazily on the first round so
        # that a subclass's __init__ (mesh's sharding setup) has completed:
        # ``_round`` is what a round calls, ``_round_step`` the jitted,
        # donated program, None where a host rule makes the round run eagerly
        self._round = None
        self._round_step = None
        self._superround_step = None
        self._superround_k = max(int(getattr(args, "superround_k", 0) or 0), 0)

    # -- sampling (reference: fedavg_api.py:125-140) ------------------------
    def _cohort_size(self) -> int:
        """Real (unpadded) clients per round — registry cohort size when the
        scale substrate is on, the reference min() rule otherwise."""
        if self.cohort_engine is not None:
            return self.cohort_engine.cohort_size
        return min(int(self.args.client_num_per_round), self.ds.client_num)

    def _client_sampling(self, round_idx: int) -> np.ndarray:
        if self.cohort_engine is not None:
            # registry path: seeded on-device K-of-N over the population,
            # mapped through shard pointers to dataset rows (scale/)
            return self.cohort_engine.data_cohort(round_idx)
        total = self.ds.client_num
        per_round = min(int(self.args.client_num_per_round), total)
        if total == per_round:
            return np.arange(total)
        rs = np.random.RandomState(round_idx)
        return rs.choice(total, per_round, replace=False)

    # -- cohort placement hooks (overridden by the mesh backend) ------------
    def _pad_cohort(self, cohort: np.ndarray):
        """Return (cohort, wmask): pad the cohort for even device sharding.

        wmask is None (no padding) on the single-device path; the mesh backend
        pads to a multiple of the ``clients`` axis size and returns a 0/1 mask
        (1 for real clients, 0 for padding) — the reference's padded schedule
        tensors (``Server.py:124-128``) reborn as a weight mask.
        """
        return cohort, None

    def _gather_cohort(self, cohort: np.ndarray):
        """Gather the cohort's packed shards → (cx, cy, cn) on device.

        Registry mode streams through the cohort engine's prefetcher
        (round r's gather was scheduled while round r-1 trained);
        otherwise the resident gather below runs."""
        if self.cohort_engine is not None:
            return self.cohort_engine.gather(cohort, self._place_cohort)
        return self._gather_resident(cohort)

    def _host_gather_rows(self, rows: np.ndarray):
        """Host-side shard read for the streaming path (runs on the
        prefetcher's worker thread)."""
        return (
            self.ds.train_x[rows],
            self.ds.train_y[rows],
            self.ds.train_counts[rows].astype(np.int32),
        )

    def _place_cohort(self, arrays):
        """Commit gathered host shards to device (mesh: rule-sharded)."""
        cx, cy, cn = arrays
        return jnp.asarray(cx), jnp.asarray(cy), jnp.asarray(cn)

    def _gather_resident(self, cohort: np.ndarray):
        if self.hbm_resident:
            idx = jnp.asarray(cohort)
            cx = jnp.take(self._dev_x, idx, axis=0)
            cy = jnp.take(self._dev_y, idx, axis=0)
            cn = jnp.take(self._dev_counts, idx, axis=0)
        else:
            from .. import native

            # host gather through the C++ threaded path when available
            cx = jnp.asarray(native.gather_rows(self.ds.train_x, cohort))
            cy = jnp.asarray(
                native.gather_rows(self.ds.train_y, cohort)
                if self.ds.train_y.dtype in (np.float32, np.int32)
                else self.ds.train_y[cohort]
            )
            cn = jnp.asarray(self.ds.train_counts[cohort])
        return cx, cy, cn

    def _place(self, arr):
        """Place a per-client array (leading cohort dim); mesh shards it."""
        return arr

    def _prepare_round(self) -> None:
        """Pre-round placement hook (mesh re-commits params replicated)."""

    def _place_state(self, state):
        """Commit the round state's placement (mesh: replicated)."""
        return state

    # -- the round (round_engine.py) ----------------------------------------
    def _host_rule(self) -> Optional[Callable]:
        """The aggregation rule that has to run as host Python, or None.

        The one place that decides how a round is executed: with a rule the
        round function is called eagerly, op by op, so that the rule gets
        concrete arrays and client ids; without one it is jitted and donated.
        A rule is called as ``rule(stacked, weights, rng, n_valid,
        client_ids)`` on the real clients' rows, where the weighted average
        or the defense would stand, and returns the aggregate.
        """
        if (self.custom_aggregator is not None
                or type(self)._aggregate is not FedAvgAPI._aggregate):
            return self._aggregate
        if (self.defender.is_defense_enabled()
                and self.defender.defense_type == "wbc"):
            from .round_engine import host_defense_rule

            return host_defense_rule(self)
        return None

    def _aggregate(self, stacked: PyTree, weights: jax.Array, rng,
                   n_valid: int, client_ids) -> PyTree:
        """Host aggregation rule (see ``_host_rule``): the user
        ServerAggregator's hook chain over the real clients' rows. A subclass
        that aggregates on the host overrides this (TurboAggregateAPI)."""
        raw = [
            (float(weights[i]), jax.tree.map(lambda x: x[i], stacked))
            for i in range(n_valid)
        ]
        raw = self.custom_aggregator.on_before_aggregation(raw)
        agg = self.custom_aggregator.aggregate(raw)
        return self.custom_aggregator.on_after_aggregation(agg)

    def _setup_round(self) -> None:
        """Build the round once (lazily, post-subclass-init): one jitted,
        donated program, or with a host rule the same function as it is."""
        if type(self)._train_round is not FedAvgAPI._train_round:
            return  # the subclass's round is the round (HierarchicalFLAPI)
        from .round_engine import build_round_core, make_superround_step

        per = self._cohort_size()
        cohort0, wmask0 = self._pad_cohort(
            np.arange(per) % self.ds.client_num
        )
        core = build_round_core(self, n_cohort=len(cohort0), n_valid=per)
        if self._host_rule() is not None:
            logger.info("round runs eagerly: its aggregation rule is host "
                        "Python (custom aggregator, _aggregate override or "
                        "FL-WBC)")
            self._round = core  # no superround either: it scans the program
            return
        self._round_step = jax.jit(core, donate_argnums=(0,))
        self._round = self._round_step
        if self._superround_k > 1:
            if self.hbm_resident and wmask0 is None:
                self._superround_step = make_superround_step(
                    self, self._superround_k, n_cohort=per
                )
            else:
                logger.info(
                    "superround off: needs the HBM-resident single-device "
                    "path (hbm_resident=%s, padded=%s)",
                    self.hbm_resident, wmask0 is not None,
                )
                self._superround_k = 0

    def _round_state(self) -> Dict:
        """The donated round state (also the checkpoint payload)."""
        state = {"global_params": self.global_params}
        if self.server_opt_state is not None:
            state["server_opt_state"] = self.server_opt_state
        if self.scaffold:
            state["c_global"] = self.c_global
            state["c_locals"] = self.c_locals
        return state

    def _set_round_state(self, state: Dict) -> None:
        """Adopt the round state a round returned. A donated program has
        CONSUMED the previous buffers — never read them again."""
        self.global_params = state["global_params"]
        if "server_opt_state" in state:
            self.server_opt_state = state["server_opt_state"]
        if self.scaffold:
            self.c_global = state["c_global"]
            self.c_locals = state["c_locals"]

    def run_round(self, round_idx: int) -> Dict[str, float]:
        """One federated round.

        With ``--enable_tracking`` each round opens a telemetry RoundRecord
        (phase spans on the profiler's clock, rounds in flight, HBM, compile
        events) and may open or close a ``--profile_rounds`` jax.profiler
        window. Disabled, both are one boolean check. Neither waits for the
        device: the record's loss is read once it is ready."""
        if self._round is None:
            self._setup_round()
        with telemetry.phase("hooks"):
            telemetry.on_round_start(round_idx)
            rec = telemetry.begin_round(
                round_idx, fused=self._round_step is not None,
                cohort_chunk=self.cohort_chunk,
            )
        out = self._train_round(round_idx)
        with telemetry.phase("record"):
            telemetry.end_round(rec, train_loss=out.get("train_loss"))
            telemetry.on_round_end(round_idx)
        return out

    def run_rounds(self, start_round: int, k: int) -> Dict[str, Any]:
        """Run rounds [start_round, start_round + k) — ONE superround launch
        when the config compiled one for exactly ``k`` rounds, else a Python
        loop of single rounds. Returns ``{"train_loss": losses}`` with one
        (device-resident) loss per round."""
        if self._round is None:
            self._setup_round()
        if self._superround_step is not None and k == self._superround_k:
            with telemetry.phase("hooks"):
                telemetry.on_round_start(start_round)
                rec = telemetry.begin_round(start_round, fused=True,
                                            superround=True,
                                            cohort_chunk=self.cohort_chunk)
            with telemetry.phase("dispatch"):
                self._prepare_round()
                state, scan_metrics = self._superround_step(
                    self._place_state(self._round_state()),
                    jnp.int32(start_round),
                )
                self._set_round_state(state)
                if self.cohort_engine is not None:
                    # the scan sampled rounds [start, start+k) on device with
                    # the registry's own sampler; replay them host-side so
                    # the participation/staleness counters stay truthful
                    self.cohort_engine.note_rounds(start_round, k)
            with telemetry.phase("record"):
                # one record per scanned round, unpacked from the scan's
                # stacked on-device counters (the only host sync tracking
                # adds — the untracked path stays fully asynchronous)
                telemetry.end_superround(rec, k, scan_metrics)
                telemetry.on_round_end(start_round + k - 1)
            return {"train_loss": scan_metrics["train_loss"]}
        return {"train_loss": [
            self.run_round(start_round + j)["train_loss"] for j in range(k)
        ]}

    def _round_inputs(self, round_idx: int) -> tuple:
        """Sample, gather and place what the round function takes:
        ``(state, cohort_idx, cx, cy, cn, rngs, wmask, round_rng)``."""
        with telemetry.phase("sample"):
            self._prepare_round()
            cohort, wmask = self._pad_cohort(self._client_sampling(round_idx))
        with telemetry.phase("gather"):
            cx, cy, cn = self._gather_cohort(cohort)
        with telemetry.phase("prep"):
            round_rng = jax.random.fold_in(self.root_rng, round_idx)
            rngs = self._place(jax.random.split(round_rng, len(cohort)))
            wm = None if wmask is None else self._place(jnp.asarray(wmask))
            cohort_idx = jnp.asarray(cohort, jnp.int32)
            st = self._place_state(self._round_state())
        return st, cohort_idx, cx, cy, cn, rngs, wm, round_rng

    def _train_round(self, round_idx: int) -> Dict[str, float]:
        """One round: sample, gather, then the round function
        (round_engine.py), as one donated program or eagerly.

        Returns train_loss as a DEVICE scalar — no host sync, tracked or
        not. train() keeps dispatch asynchronous: while the device executes
        round r, the host already samples and gathers round r+1's cohort.
        A subclass may replace the whole round (HierarchicalFLAPI).
        """
        inputs = self._round_inputs(round_idx)
        tracked = telemetry.enabled()
        if tracked:
            compiles = telemetry.compiles()
        with telemetry.phase("dispatch"):
            state, metrics = self._round(*inputs)
            self._set_round_state(state)
            telemetry.record_lazy("examples", metrics.get("examples"))
            if (tracked and self._round_step is not None
                    and telemetry.compiles() != compiles):
                # the jitted round made itself a program in this call: which
                # scope each of its instructions belongs to (program_scopes,
                # docs/telemetry.md). Lowering the types the call had finds
                # the call's own executable: nothing compiles here
                with telemetry.phase("program_scopes", record=False):
                    telemetry.record_program_scopes(
                        self._round_step.lower(
                            *telemetry.abstract_of(inputs)), ROUND)
        return {"train_loss": metrics["train_loss"]}

    # -- the training loop (reference: fedavg_api.py:65-123) ----------------
    # -- round checkpoint / resume ------------------------------------------
    # The reference has NO round-resume anywhere (SURVEY §5); killed runs
    # restart from round 0. With args.checkpoint_dir set, the global model
    # (+ round index, and the server optimizer / SCAFFOLD variates when
    # present) persists via Orbax every checkpoint_every_rounds rounds and
    # train() resumes mid-federation after a crash.
    def _ckpt_state(self) -> Dict:
        # same structure as the donated round state; CheckpointManager.save
        # copies every leaf to host BEFORE the next round's donation can
        # invalidate these buffers (tests/test_round_fusion.py)
        return self._round_state()

    def _maybe_resume(self, ckpt) -> int:
        """Restore the newest round checkpoint; returns the round to START."""
        step = ckpt.latest_step()
        if step is None:
            return 0
        restored = ckpt.restore_latest(self._ckpt_state())
        self.global_params = restored["global_params"]
        if "server_opt_state" in restored:
            self.server_opt_state = restored["server_opt_state"]
        if self.scaffold:
            self.c_global = restored["c_global"]
            self.c_locals = restored["c_locals"]
        telemetry.counter_inc("run.resumes")
        logger.info("sp engine: resumed federation at round %d", step + 1)
        return step + 1

    def _ledger_world(self) -> Dict[str, Any]:
        """Run-identity fields pinned into the ledger's run_meta line; the
        mesh engine extends this with its device topology so a resumed run
        on a mismatched mesh fails loudly instead of silently resharding."""
        world = {
            "engine": type(self).__name__,
            "optimizer": self.opt_name,
            "client_num_in_total": int(self.ds.client_num),
            "client_num_per_round": int(self.args.client_num_per_round),
        }
        if self.cohort_engine is not None:
            # registry identity (population size, seed, column digest):
            # resuming against a DIFFERENT registry would silently resample
            # every remaining cohort — ensure_meta turns that into an error
            world["registry"] = self.cohort_engine.ledger_identity()
        return world

    def train(self) -> Dict[str, float]:
        from ..core import mlops, runstate

        rounds = int(self.args.comm_round)
        freq = max(int(getattr(self.args, "frequency_of_the_test", 5)), 1)
        ckpt = None
        ledger = None
        guard = None
        start_round = 0
        ckpt_dir = str(getattr(self.args, "checkpoint_dir", "") or "")
        every = runstate.checkpoint_cadence(self.args)
        mode = runstate.resume_mode(self.args)
        if ckpt_dir:
            from ..checkpoint import CheckpointManager

            ckpt = CheckpointManager(ckpt_dir)
            try:
                if mode == "never" and ckpt.latest_step() is not None:
                    raise RuntimeError(
                        f"--resume never, but {ckpt_dir} already holds a "
                        f"checkpoint (step {ckpt.latest_step()}) — point at "
                        "a fresh checkpoint_dir or use --resume auto"
                    )
                if mode == "require" and ckpt.latest_step() is None:
                    raise RuntimeError(
                        f"--resume require, but {ckpt_dir} holds no "
                        "checkpoint to resume from"
                    )
                start_round = self._maybe_resume(ckpt)
                ledger = runstate.RunLedger.for_checkpoint_dir(ckpt_dir)
                ledger.ensure_meta(
                    seed=int(getattr(self.args, "random_seed", 0)),
                    world=self._ledger_world(),
                )
            except Exception:
                # a refused resume (mode conflict, world-identity mismatch)
                # must not leak the orbax manager's worker threads — a
                # lingering executor racing a later jax trace is a
                # process-killing segfault on CPU hosts
                ckpt.close()
                raise
            last_committed = ledger.last_round()
            if last_committed is not None \
                    and last_committed != start_round - 1:
                logger.warning(
                    "run ledger %s ends at round %d but the checkpoint "
                    "resumes at round %d — ledger history may be from an "
                    "uncommitted crash window", ledger.path, last_committed,
                    start_round,
                )
            # preemption-safe drain: SIGTERM/SIGINT latches, the in-flight
            # chunk finishes, checkpoint + ledger commit, and train raises
            # PreemptionError (exit EXIT_PREEMPTED at the CLI)
            guard = runstate.preemption_guard()
            if bool(getattr(self.args, "preempt_signals", True)):
                guard.install()
            guard.reset()
        last_eval: Dict[str, float] = {}
        try:
            if start_round >= rounds:
                # re-invoking a COMPLETED federation: evaluate the restored
                # model instead of returning an empty dict to consumers
                last_eval = self.evaluate(
                    self.global_params, self.ds.test_x, self.ds.test_y
                )
                return last_eval
            round_idx = start_round
            pending: List[tuple] = []  # (round, cohort) awaiting a commit
            while round_idx < rounds:
                k = self._chunk_len(round_idx, rounds, freq,
                                    every if ckpt is not None else 0)
                self.args.round_idx = round_idx + k - 1
                t0 = time.perf_counter()
                # every statement below lies in a telemetry span (hooks,
                # sample .. record inside run_round(s), then log, eval,
                # ledger, checkpoint): tracked, the spans tile the iteration
                if k > 1:
                    # superround: K rounds in one donated scan program;
                    # per-round losses come back stacked [K]
                    losses = self.run_rounds(round_idx, k)["train_loss"]
                    with telemetry.phase("log"):
                        dt = time.perf_counter() - t0
                        for j in range(k):
                            mlops.log_round_info(round_idx + j, rounds)
                            self.history.append({
                                "round": round_idx + j,
                                "round_time_s": dt / k,
                                "train_loss": losses[j],
                            })
                else:
                    with telemetry.phase("log"):
                        mlops.log_round_info(round_idx, rounds)
                    train_metrics = self.run_round(round_idx)
                    with telemetry.phase("log"):
                        dt = time.perf_counter() - t0
                        self.history.append({
                            "round": round_idx, "round_time_s": dt,
                            **train_metrics,
                        })
                last_round = round_idx + k - 1
                entry = self.history[-1]
                if last_round % freq == 0 or last_round == rounds - 1:
                    # runs BETWEEN rounds: the span lands on the record that
                    # closed last, never in a record's phases
                    with telemetry.phase("eval"):
                        last_eval = self.evaluate(
                            self.global_params, self.ds.test_x, self.ds.test_y
                        )
                    with telemetry.phase("log"):
                        entry.update(last_eval)
                        mlops.log({"round": last_round, **last_eval},
                                  step=last_round)
                        logger.info(
                            "round %d: loss=%.4f acc=%.4f (%.3fs)",
                            last_round, last_eval["test_loss"],
                            last_eval["test_acc"], dt / k,
                        )
                if ledger is not None:
                    # cohorts are host-sampled per round except under a
                    # superround scan (on-device sampling) — deterministic
                    # either way, but only the host path is recordable
                    with telemetry.phase("ledger"):
                        for j in range(round_idx, last_round + 1):
                            pending.append((
                                j,
                                None if k > 1 else
                                [int(c) for c in self._client_sampling(j)],
                            ))
                round_idx += k
                with telemetry.phase("checkpoint"):
                    if ckpt is not None and (
                        (last_round + 1) % every == 0
                        or last_round == rounds - 1
                    ):
                        step = ckpt.save(self._ckpt_state(), step=last_round)
                        for r, cohort in pending:
                            ledger.commit_round(r, ckpt_step=step,
                                                cohort=cohort)
                        pending.clear()
                    if guard is not None and guard.requested() \
                            and round_idx < rounds:
                        from ..core.runstate import PreemptionError

                        # drain commit: the chunk above completed; persist
                        # its state NOW (even off the checkpoint cadence) so
                        # the restart resumes exactly here instead of
                        # re-training
                        if ckpt.latest_step() != last_round:
                            step = ckpt.save(self._ckpt_state(),
                                             step=last_round)
                            for r, cohort in pending:
                                ledger.commit_round(r, ckpt_step=step,
                                                    cohort=cohort)
                            pending.clear()
                        telemetry.counter_inc("run.preemptions")
                        raise PreemptionError(last_round)
        finally:
            if ckpt is not None:  # release Orbax threads even on a crash
                ckpt.close()
            if self.cohort_engine is not None:
                self.cohort_engine.close()
            # the records still waiting for their device scalars: all are in
            # the sink, in round order, when train() returns
            telemetry.drain_records()
            self._finalize_history()
        return last_eval

    def _chunk_len(self, r: int, rounds: int, freq: int, every: int) -> int:
        """Superround chunk length starting at round ``r``.

        Returns the configured K only when no round STRICTLY INSIDE the chunk
        needs a host-side action (eval or checkpoint) — those may only land on
        the chunk's last round, where the scan has already returned. Anything
        else runs as a single round, so the observable eval/checkpoint
        schedule is identical to the unchunked loop. At most two programs ever
        compile: the K-scan and the single round.
        """
        k = self._superround_k
        if k <= 1 or r + k > rounds:
            return 1
        if every:
            from ..core import runstate

            if runstate.preemption_guard().requested():
                # step-granular drain: SIGTERM already latched — never
                # launch another K-round scan program (it cannot be
                # interrupted mid-scan); single rounds bound the drain
                # latency to ONE round, and the train loop's guard check
                # commits + exits right after it
                return 1
        if self._round is None:
            self._setup_round()
        if self._superround_step is None:
            return 1
        if telemetry.profiler_blocks_chunk(r, r + k):
            # a --profile_rounds boundary inside the chunk: single rounds so
            # the trace window opens/closes exactly on the requested rounds
            return 1
        for ri in range(r, r + k - 1):
            if ri % freq == 0:
                return 1
            if every and (ri + 1) % every == 0:
                return 1
        return k

    def _finalize_history(self) -> None:
        """Realize any still-on-device train_loss scalars (dispatch stays
        async — metrics are only pulled here or at evals)."""
        for e in self.history:
            tl = e.get("train_loss")
            if tl is not None and not isinstance(tl, float):
                e["train_loss"] = float(np.asarray(tl))
