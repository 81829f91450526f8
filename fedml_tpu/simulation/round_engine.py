"""The FedAvg-family round, written once: ``build_round_core``.

``core(state, cohort_idx, cx, cy, cn, rngs, wmask, round_rng) -> (state,
metrics)`` is the whole round after the cohort is gathered: data attack ->
the cohort's local training -> local DP or clipping -> model attack ->
defense or aggregation -> server update (FedOpt / FedSGD optimizer, FedNova,
SCAFFOLD's variates) -> central DP, with the round's ``train_loss`` and
``examples`` as device scalars. Every engine runs this function
(``FedAvgAPI._host_rule`` decides how, ``_setup_round`` builds it):

- **jitted and donated**, when every step is jit-safe: one
  ``jax.jit(core, donate_argnums=(0,))`` program per round. A steady-state
  round is ONE launch (``tests/test_round_fusion.py`` pins one compile per
  config), and the model, server-optimizer and control-variate buffers are
  updated in place instead of held twice.
- **eagerly, op by op**, when the configuration has a *host aggregation rule*
  (``FedAvgAPI._host_rule``): a custom ``ServerAggregator``, a subclass's
  ``_aggregate`` (TurboAggregate's additive shares) or FL-WBC, whose
  per-client history lives on the host under concrete client ids. The rule
  stands where the weighted average or the defense stands; the chain around
  it is the same code. ``cohort_fn`` is itself a ``jax.jit``, so local
  training is still one program; nothing is donated.

Superround mode (``make_superround_step``) additionally moves client sampling
on-device (fold-in PRNG choice over client ids) and runs K rounds under
``jax.lax.scan`` — steady-state throughput is then bounded by device compute,
not Python dispatch. It requires the jitted round and the HBM-resident
dataset (the cohort gather happens inside the program) and uses device-side
sampling, so its cohort trajectory differs from the host-side
``np.random.RandomState(round_idx)`` reference semantics EXCEPT under full
participation, where both degenerate to ``arange`` and the trajectories
coincide exactly (the parity tests rely on this).

Round state is a flat dict — ``{"global_params", "server_opt_state"?,
"c_global"?, "c_locals"?}`` — matching ``FedAvgAPI._round_state``. Callers
of a jitted round must treat the state they passed in as CONSUMED (donation
invalidates the buffers) and adopt the returned state;
``checkpoint.CheckpointManager.save`` copies leaves to host before the next
round can be dispatched.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import optax

from .. import constants
from ..core.aggregate import (
    fednova_normalized_direction,
    pseudo_gradient,
    weighted_average,
)
# names for the round's device work outside the flax model; ``local_train``
# with its ``loss`` and ``optimizer`` is named where it is built
# (ml/local_train.py)
from ..core.mlops.scopes import round_scope as _scope
from ..utils.tree import tree_flatten_to_vector, tree_unflatten_from_vector

PyTree = Any
RoundState = Dict[str, PyTree]


def _masked_mean(values, wmask):
    """Mean of per-client scalars over the real (mask 1) clients, on device."""
    if values is None:
        return jnp.float32(jnp.nan)
    if wmask is None:
        return jnp.mean(values)
    return (values * wmask).sum() / jnp.maximum(wmask.sum(), 1.0)


def build_round_core(api, n_cohort: int, n_valid: int):
    """Build the round function for ``api``'s config.

    ``n_cohort`` is the (padded) cohort length, ``n_valid`` the number of real
    clients — both static per config, so the zero-weight-padding slices are
    static slices.

    Returns ``core(state, cohort_idx, cx, cy, cn, rngs, wmask, round_rng) ->
    (state, metrics)``. With a host aggregation rule (``api._host_rule()``) it
    is meant to be called as it is, without ``jax.jit``: the rule gets concrete
    arrays.

    jit-safety note: the attacker's host-side ``np.random`` mask draws are
    seeded by config only (``random_seed``; ``attack_model``'s round offset
    defaults to 0), so under trace they bake into compile-time constants
    IDENTICAL to what an eager call computes every round.
    """
    attacker, defender, dp = api.attacker, api.defender, api.dp
    fedsgd, fednova, scaffold = api.fedsgd, api.fednova, api.scaffold
    fedopt = api.opt_name == constants.FEDML_FEDERATED_OPTIMIZER_FEDOPT
    server_opt = api.server_opt
    cohort_fn = api.cohort_fn
    client_num = api.ds.client_num
    host_rule = api._host_rule()

    @_scope("aggregate")
    def aggregate(gp, stacked, weights, rng, cohort_idx):
        if dp is not None and dp.dp_type == "ldp":
            with _scope("dp"):
                keys = jax.random.split(jax.random.fold_in(rng, 3), n_cohort)
                stacked = jax.vmap(dp.randomize)(stacked, keys)
        elif dp is not None and dp.dp_type == "cdp":
            with _scope("dp"):
                stacked = dp.clip_client_updates(stacked, gp)

        # a host rule stands where the in-program rule (the defense or the
        # weighted average) stands; a model attack runs before either
        needs_flat = attacker.is_model_attack() or (
            defender.is_defense_enabled() and host_rule is None)
        if not needs_flat and host_rule is None:
            return weighted_average(stacked, weights)

        # drop zero-weight padding: rank-based defenses, the attack kernels
        # and host rules see the real clients only
        if n_valid < n_cohort:
            stacked = jax.tree.map(lambda x: x[:n_valid], stacked)
            weights = weights[:n_valid]
        if needs_flat:
            _, treedef, shapes = tree_flatten_to_vector(gp)
            flat = jax.vmap(lambda t: tree_flatten_to_vector(t)[0])(stacked)
            gvec, _, _ = tree_flatten_to_vector(gp)
            if attacker.is_model_attack():
                with _scope("attack"):
                    flat = attacker.attack_model(
                        flat, weights, jax.random.fold_in(rng, 1)
                    )
            if host_rule is None:
                if defender.is_defense_enabled():
                    with _scope("defense"):
                        agg_vec = defender.defend(
                            flat, weights, gvec, jax.random.fold_in(rng, 2),
                            client_ids=None,
                        )
                else:
                    w = weights / jnp.maximum(weights.sum(), 1e-12)
                    agg_vec = (w[:, None] * flat).sum(0)
                return tree_unflatten_from_vector(agg_vec, treedef, shapes)
            # the rule aggregates whatever rows the attack left
            stacked = jax.vmap(
                lambda v: tree_unflatten_from_vector(v, treedef, shapes)
            )(flat)
        return host_rule(stacked, weights, rng, n_valid, cohort_idx[:n_valid])

    def round_metrics(metrics, weights, wmask):
        with _scope("metrics"):
            return {
                "train_loss": _masked_mean(metrics["train_loss"], wmask),
                # on-device round counter: telemetry RoundRecords realize it
                # host-side AFTER the round (no sync on the dispatch path)
                "examples": weights.sum(),
            }

    def core(state: RoundState, cohort_idx, cx, cy, cn, rngs, wmask,
             round_rng) -> Tuple[RoundState, Dict[str, jax.Array]]:
        gp = state["global_params"]
        if attacker.is_data_attack():
            with _scope("attack"):
                cx, cy = attacker.attack_data(cx, cy, n_valid)

        if fedsgd:
            grads, metrics = cohort_fn(gp, cx, cy, cn, rngs)
            weights = (metrics["num_samples"] if wmask is None
                       else metrics["num_samples"] * wmask)
            agg_grad = aggregate(gp, grads, weights, round_rng, cohort_idx)
            with _scope("server_update"):
                updates, opt_state = server_opt.update(
                    agg_grad, state["server_opt_state"], gp
                )
                gp = optax.apply_updates(gp, updates)
            new_state = dict(state, global_params=gp,
                             server_opt_state=opt_state)
            # (FedSGD averages gradients: central DP's model noise has no place)
            return new_state, round_metrics(metrics, weights, wmask)

        if scaffold:
            with _scope("select_cohort"):
                c_cohort = jax.tree.map(lambda x: x[cohort_idx],
                                        state["c_locals"])
            stacked, metrics, new_c = cohort_fn(
                gp, cx, cy, cn, rngs, state["c_global"], c_cohort
            )
            with _scope("server_update"):
                real = cohort_idx[:n_valid]
                new_c_r = jax.tree.map(lambda x: x[:n_valid], new_c)
                c_cohort_r = jax.tree.map(lambda x: x[:n_valid], c_cohort)
                delta_c = jax.tree.map(
                    lambda n, o: (n - o).mean(0), new_c_r, c_cohort_r
                )
                scale = n_valid / client_num
                c_global = jax.tree.map(
                    lambda cg, d: cg + scale * d, state["c_global"], delta_c
                )
                c_locals = jax.tree.map(
                    lambda all_c, nc: all_c.at[real].set(nc),
                    state["c_locals"], new_c_r,
                )
            state = dict(state, c_global=c_global, c_locals=c_locals)
        else:
            stacked, metrics = cohort_fn(gp, cx, cy, cn, rngs)

        weights = (metrics["num_samples"] if wmask is None
                   else metrics["num_samples"] * wmask)

        if fednova:
            # w_new = w_g - tau_eff * sum_i p_i (w_g - w_i) / tau_i
            tau = metrics["tau"]
            with _scope("aggregate"):
                norm_dir = fednova_normalized_direction(gp, stacked, tau)
                d = weighted_average(norm_dir, weights)
            with _scope("server_update"):
                p = weights / jnp.maximum(weights.sum(), 1e-12)
                tau_eff = (p * tau).sum()
                gp = jax.tree.map(lambda g, dd: g - tau_eff * dd, gp, d)
        elif fedopt:
            w_agg = aggregate(gp, stacked, weights, round_rng, cohort_idx)
            with _scope("server_update"):
                pg = pseudo_gradient(gp, w_agg)
                updates, opt_state = server_opt.update(
                    pg, state["server_opt_state"], gp
                )
                gp = optax.apply_updates(gp, updates)
            state = dict(state, server_opt_state=opt_state)
        else:
            gp = aggregate(gp, stacked, weights, round_rng, cohort_idx)

        if dp is not None and dp.dp_type == "cdp":
            with _scope("dp"):
                gp = dp.randomize_global(gp, jax.random.fold_in(round_rng, 7))
        new_state = dict(state, global_params=gp)
        return new_state, round_metrics(metrics, weights, wmask)

    return core


def host_defense_rule(api):
    """FL-WBC as a host aggregation rule: the defender keeps each client's
    last pseudo-gradient on the host under the client's id, so it is called
    outside any trace, with the ids concrete."""

    def rule(stacked, weights, rng, n_valid, client_ids):
        gvec, treedef, shapes = tree_flatten_to_vector(api.global_params)
        flat = jax.vmap(lambda t: tree_flatten_to_vector(t)[0])(stacked)
        agg_vec = api.defender.defend(
            flat, weights, gvec, jax.random.fold_in(rng, 2),
            client_ids=client_ids,
        )
        return tree_unflatten_from_vector(agg_vec, treedef, shapes)

    return rule


def make_superround_step(api, k: int, n_cohort: int):
    """K rounds per launch: on-device sampling + ``lax.scan`` pipelining.

    Requires the HBM-resident dataset (``api._dev_x`` et al.) — the per-round
    cohort gather is a device-side ``jnp.take`` inside the scan body, so the
    host does nothing between rounds. Client sampling is a fold-in PRNG
    ``jax.random.choice`` over client ids (without replacement), keyed by the
    same per-round key the single-round path uses for everything else.

    Returns ``superround(state, start_round) -> (state, metrics)`` where
    ``metrics`` holds stacked per-round outputs (``train_loss[k]``,
    ``examples[k]``) — the host-side unpack point for per-round telemetry —
    jit'd with the state donated.
    """
    core = build_round_core(api, n_cohort, n_valid=n_cohort)
    dev_x, dev_y, dev_counts = api._dev_x, api._dev_y, api._dev_counts
    total = int(api.ds.client_num)
    per = int(n_cohort)
    root_rng = api.root_rng
    # registry mode (fedml_tpu/scale/): cohorts come from the SAME jit'd
    # Gumbel-top-K sampler the host-driven path uses — keyed only by
    # (registry seed, round), so the scan's cohort trajectory is identical
    # to per-round launches and the engine can replay it for accounting
    eng = getattr(api, "cohort_engine", None)
    if eng is not None:
        reg_sample = eng.registry.device_sampler(per)
        reg_ptrs = eng.registry.device_shard_ptrs()

    def superround(state: RoundState, start_round):
        def body(st, r):
            rkey = jax.random.fold_in(root_rng, r)
            with _scope("select_cohort"):
                if eng is not None:  # registry K-of-N → backing shard rows
                    cohort = jnp.take(reg_ptrs, reg_sample(r), axis=0)
                elif total == per:  # full participation: as the host path
                    cohort = jnp.arange(per, dtype=jnp.int32)
                else:
                    cohort = jax.random.choice(
                        jax.random.fold_in(rkey, 13), total, (per,),
                        replace=False,
                    ).astype(jnp.int32)
                cx = jnp.take(dev_x, cohort, axis=0)
                cy = jnp.take(dev_y, cohort, axis=0)
                cn = jnp.take(dev_counts, cohort, axis=0)
            rngs = jax.random.split(rkey, per)
            st, metrics = core(st, cohort, cx, cy, cn, rngs, None, rkey)
            return st, {"train_loss": metrics["train_loss"],
                        "examples": metrics["examples"]}

        rr = start_round + jnp.arange(k, dtype=jnp.int32)
        state, scan_metrics = jax.lax.scan(body, state, rr)
        return state, scan_metrics

    return jax.jit(superround, donate_argnums=(0,))
