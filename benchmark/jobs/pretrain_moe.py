"""Job ``pretrain_moe``: ``pretrain``'s steps for a configuration whose blocks
are not Mistral's (latent attention, expert layers of which this chip holds a
share, hyper-connected residual streams).

The loop, the window, the device's clock and the rates are ``pretrain.Job``'s,
unchanged. What ``pretrain.py`` keeps as module constants comes from the
configuration file and the reference module here:

- the program's arguments are all under the configuration's ``program``;
  ``program_argument_of`` pairs each published key with the argument that
  carries it, and the job refuses a file in which the two disagree, and a
  program whose ``TransformerConfig`` lacks an argument (the parent of the PR
  that brought a configuration: it cannot build it, and says so at once);
- the parameter mapping is the reference module's ``reference_params``;
- the tolerances are the configuration's ``tolerances``, each beside its
  reason.

The comparison that decides ``reference_agrees`` is ``pretrain``'s (the step's
loss of one seeded batch against the reference over every row, the last
``reference_tail`` logits of row 0, on the system's own initial parameters)
with one addition. Top-k routing is discrete: where the reference's selection
scores come within ``tolerances.routing_margin`` of a choice that changes what
this chip computes (an expert held here leaving the selected set or entering
it; the reference module's ``route``), in some layer, bfloat16 inputs can make
the other choice, and the token's logits then move by far more than rounding.
Those positions are left out of the logit comparison; their share is logged and bounded by
``tolerances.near_tie_share_max``; every other position must agree.

Adds the check ``no_assignment_dropped``: the step's ``moe_dropped`` counter is
zero in every step of the window, and the facts the routing readers need.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import harness

pretrain = harness.load_module(harness.ROOT, "jobs", "pretrain")


def _published(config, dotted):
    value = config
    for key in dotted.split("."):
        value = value[key]
    return value


class Job(pretrain.Job):
    def __init__(self, cell, seed, tracked, work_dir, log):
        super().__init__(cell, seed, tracked, work_dir, log)
        config = cell.config
        for key, arg in config["program_argument_of"].items():
            want, have = _published(config, key), config["program"][arg]
            same = (float(want) == float(have)
                    if isinstance(have, (int, float)) and not isinstance(have, bool)
                    else want == have)
            if not same:
                raise ValueError(
                    f"{config['name']}: {key} is {want!r} but the program's "
                    f"argument {arg} is {have!r}")
        from fedml_tpu.parallel.transformer import TransformerConfig

        fields = set(getattr(TransformerConfig, "__dataclass_fields__", ()))
        lacking = sorted(set(config["program_argument_of"].values()) - fields)
        if lacking:
            raise RuntimeError(
                f"this program cannot build {config['name']}: its "
                f"TransformerConfig has no {lacking}")
        self.tolerances = config["tolerances"]

    # -- instrumentation: the routing counter beside the loss ----------------
    def _instrument(self):
        super()._instrument()
        recorded_step, job = self.trainer.train_step, self
        self._step_dropped = []

        def counted_step(state, tokens, mask):
            state, metrics = recorded_step(state, tokens, mask)
            job._step_dropped.append(metrics["moe_dropped"])
            return state, metrics

        self.trainer.train_step = counted_step

    def _loop(self, steps, window=None):
        self._step_dropped = []
        return super()._loop(steps, window)

    # -- the reference ------------------------------------------------------
    def _reference_check(self):
        import jax
        import jax.numpy as jnp
        from fedml_tpu.parallel.context import mesh_context
        from fedml_tpu.parallel.sharding import batch_sharding

        trainer, config, tol = self.trainer, self.cell.config, self.tolerances
        ref = harness.load_module(self.cell.root, "reference",
                                  config["reference"])
        tail = int(self.cell.traffic["reference_tail"])
        init_state, train_step = self._uninstrumented
        state = init_state(jax.random.PRNGKey(self.seed))
        tokens = next(self.cheetah._batches(np.random.RandomState(self.seed)))
        rows = tokens.reshape(-1, tokens.shape[-1])
        tok, mask = jnp.asarray(tokens), jnp.ones_like(jnp.asarray(tokens))

        checks = {}
        if jax.devices()[0].platform == "tpu":
            checks["mosaic_call_in_lowered_step"] = (
                "tpu_custom_call" in trainer.lower_step(state, tok, mask).as_text())

        @jax.jit
        def tail_logits(variables, toks):
            return trainer.model.apply(variables, toks)[:1, -tail:]

        first = jnp.asarray(rows[:self.batch])
        with trainer.mesh, mesh_context(trainer.mesh):
            got_logits = np.asarray(tail_logits(
                {"params": state.params, **state.model_state},
                jax.device_put(first, batch_sharding(trainer.mesh))))[0]
        host_params = jax.device_get(state.params)
        host_router = jax.device_get(state.model_state).get("router_state")
        state, metrics = train_step(state, tok, mask)
        got_loss = float(metrics["loss"])
        del state, metrics

        t0 = time.perf_counter()
        one = jax.devices()[0]

        @jax.jit
        def reference_row(params, router, toks):
            return ref.loss_sum_and_tail_logits(
                ref.reference_params(params, config, router), toks, config, tail)

        params_ref = jax.device_put(host_params, one)
        router_ref = jax.device_put(host_router, one)
        total, want_logits, margin = 0.0, None, None
        for i, row in enumerate(rows):
            loss_sum, _, logits, m = reference_row(
                params_ref, router_ref, jax.device_put(row, one))
            total += float(loss_sum)
            if i == 0:
                want_logits, margin = np.asarray(logits), np.asarray(m)
        want_loss = total / (rows.shape[0] * (rows.shape[1] - 1))
        del params_ref, router_ref, host_params

        clear = margin >= float(tol["routing_margin"])
        near_tie_share = 1.0 - float(clear.mean())
        per_position = (np.linalg.norm(got_logits - want_logits, axis=-1)
                        / np.linalg.norm(want_logits, axis=-1))
        err = float(np.linalg.norm((got_logits - want_logits)[clear])
                    / np.linalg.norm(want_logits[clear]))
        worst = np.argsort(-per_position)[:5]
        self.first_loss = got_loss
        self.log(f"reference ({len(rows)} rows, {time.perf_counter() - t0:.1f}s):"
                 f" loss {got_loss:.5f} vs {want_loss:.5f} (tolerance "
                 f"{tol['loss_abs']}); last {tail} logits of row 0: rel-L2 "
                 f"{err:.3g} over the {int(clear.sum())} positions whose "
                 f"routing margin is at least {tol['routing_margin']} "
                 f"(tolerance {tol['logits_rel_l2']}), near-tie share "
                 f"{near_tie_share:.3f} (at most {tol['near_tie_share_max']});"
                 f" worst positions (rel-L2, margin): "
                 f"{[(round(float(per_position[i]), 4), round(float(margin[i]), 5)) for i in worst]}"
                 f"; median per-position rel-L2 {float(np.median(per_position)):.3g}")
        checks["reference_agrees"] = bool(
            abs(got_loss - want_loss) <= float(tol["loss_abs"])
            and err <= float(tol["logits_rel_l2"])
            and near_tie_share <= float(tol["near_tie_share_max"]))
        return checks

    # -- the window ---------------------------------------------------------
    def run(self, units, window):
        import jax

        outcome = super().run(units, window)
        dropped = [int(x) for x in jax.device_get(self._step_dropped)]
        outcome.setdefault("checks", {})["no_assignment_dropped"] = (
            len(dropped) == units and not any(dropped))
        return outcome

    def facts(self, units):
        cfg = self.trainer.cfg
        expert_layers = sum(1 for kind in cfg.layer_kinds if kind == "moe")
        return {
            **super().facts(units),
            "seq_len": self.seq_len,
            "sequences_per_step_per_chip": self.batch * self.accum // self.cell.chips,
            "layers": len(cfg.layer_kinds), "expert_layers": expert_layers,
            "experts_held": int(cfg.experts_held),
            "assignments_per_step": (self.tokens_per_step() * int(cfg.moe_top_k)
                                     * expert_layers),
        }
