"""Job ``pretrain_bd``: ``pretrain_moe``'s steps for a configuration trained by
diffusion over blocks (``objective`` ``block_diffusion``: a step reads the
``2L`` rows ``[noised ; clean]`` of each sequence of ``L`` data tokens under
one structured attention mask and takes a weighted, unshifted loss on the
noised half).

The loop, the window, the device's clock, the rates, the refusal of a program
that lacks an argument and ``no_assignment_dropped`` are ``pretrain_moe.Job``'s,
unchanged; a step's tokens are its *data* tokens (the ``2L`` rows are what the
objective costs, not throughput). These are this job's:

- the reference check. The noise is data: the job draws ``(x_t, masked,
  weight)`` with the program's own ``block_diffusion.noise`` under the key the
  step uses at step 0 and hands the same draw to the program's model and to
  the reference (``benchmark/reference/<reference>.py``, its own mask, routing
  and loss). Compared: the step's loss of the seeded batch (the weighted mean
  a sequence plus the auxiliary term) against the reference's, and the first
  ``reference_head`` and the last ``reference_tail`` noised-half logits of
  row 0, each group held to ``tolerances.logits_rel_l2`` over the positions
  whose routing margin is clear (``tolerances.routing_margin``,
  ``near_tie_share_max``: as ``pretrain_moe`` has them). Both ends because
  the mask's clauses differ in what they move: a row of the last blocks sees
  thousands of keys and four more or fewer change nothing that rounding does
  not (a noised row that sees its own clean block reads 0.0006 there), a row
  of the first blocks sees four to a few dozen;
- the check ``masked_share_in_band``: the step's ``bd_masked_tokens`` counter
  over the step's data tokens lies within ``masked_share_sigmas`` standard
  deviations of its mean in every step of the window. One ``t`` a block of
  ``B`` tokens, uniform on ``[t_min, 1]``, and a token masked with
  probability ``t``: a block's count has mean ``B E[t]`` and variance ``B
  (E[t] - E[t^2]) + B^2 Var(t)``, the blocks are independent;
- the check ``draw_is_sound``: the draw the reference is handed is the
  program's, so the job first holds it to what the objective says of it, on
  the timed shapes (:func:`draw_faults`): ``x_t`` is the mask token where
  ``masked`` and the data token elsewhere; ``weight`` is one value a block,
  inside ``[1, 1 / t_min]``; ``t = 1 / weight`` has a uniform variable's
  first two moments on ``[t_min, 1]``; and the blocks' masked counts scatter
  around ``B t_b`` as binomial counts do (one ``t`` a token, a weight from
  another block or a clipped ``t`` each fail one of these);
- the check ``routing_spread``: in every step of the window the busiest held
  expert of any layer got less than ``max_load_share_max`` of the step's
  rows. A stack whose rows are alike at the router's input sends all of them
  to the same eight experts, and the expert layer then runs shapes no
  deployment sends (PERF.md section 6, PR 34).

``tokens_per_s_per_chip`` is ``pretrain``'s: a step's data tokens over the
median duration of the step program on the device's clock over the window's
last ``CLOCK_STEPS`` steps.

The losses the harness compares (``loss_falls``: the window's last step
against the reference check's step) are each step's loss over its own draw's
weight, ``bd_weight_sum / data tokens``: the weighted *mean* cross entropy of
the masked positions. The step's loss is that mean times the draw's weight
sum a token, and the draw is a pure function of the step's number: 0.954 at
step 0, 0.918 to 1.109 over the first 48 steps of 4,096 tokens. Raw, the
check would set step 0's light draw against the last step's, whatever the
steps between had learnt; over its weight a step's loss repeats to 0.04 nats
at weights that stand still (PERF.md section 6, PR 34). The reference
comparison itself is on the raw loss.

The weights the steps start from, in the warm-up and in the window: the
program's own draw under the configuration's ``window_weights_seed`` (one
draw for every ``--seed``, which still drives the data, the batch order and
the reference check's weights) with the embedding table times
``initial_embedding_scale``. The reference check runs on the program's own
draw under ``--seed`` as it is, where its tolerances were set and a mistake
in a layer shows most. Why: the configuration's ``assumed.initial_weights``
and PERF.md section 6, PR 34.
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmark import harness

pretrain_moe = harness.load_module(harness.ROOT, "jobs", "pretrain_moe")


def masked_share_band(tokens: int, block: int, t_min: float, sigmas: float):
    """(mean, half-width) of the share of ``tokens`` data tokens a step's
    draw masks."""
    mean = (1.0 + t_min) / 2.0
    second = (1.0 + t_min + t_min * t_min) / 3.0          # E[t^2]
    var_t = (1.0 - t_min) ** 2 / 12.0
    per_block = block * (mean - second) + block * block * var_t
    return mean, sigmas * math.sqrt(tokens / block * per_block) / tokens


def draw_faults(x_0, x_t, masked, weight, block: int, mask_token: int,
                t_min: float, sigmas: float) -> list:
    """What is wrong with a draw ``(x_t, masked, weight)`` of ``x_0`` [.., L]
    as the objective describes it (numpy arrays); empty when nothing is."""
    x_0, x_t, masked = np.asarray(x_0), np.asarray(x_t), np.asarray(masked, bool)
    weight = np.asarray(weight, np.float64)
    faults = []
    if not (x_t[masked] == mask_token).all() or not (x_t[~masked] == x_0[~masked]).all():
        faults.append("x_t is not the mask token where masked and x_0 elsewhere")
    if (x_0 == mask_token).any():
        faults.append("the data holds the mask token")
    per_block = weight.reshape(-1, block)
    if not (per_block == per_block[:, :1]).all():
        faults.append("the weight is not one value a block")
    if weight.min() < 1.0 or weight.max() > 1.0 / t_min * (1 + 1e-6):
        faults.append(f"weights {weight.min():.4g} to {weight.max():.4g} lie "
                      f"outside [1, {1 / t_min:.4g}]")
        return faults
    t = 1.0 / per_block[:, 0]                       # one a block
    n = t.size
    # a uniform variable on [t_min, 1]: its first two moments
    for power, name in ((1, "mean"), (2, "mean square")):
        want = (1 - t_min ** (power + 1)) / ((power + 1) * (1 - t_min))
        var = ((1 - t_min ** (2 * power + 1)) / ((2 * power + 1) * (1 - t_min))
               - want * want)
        if abs(float((t ** power).mean()) - want) > sigmas * math.sqrt(var / n):
            faults.append(f"the {name} of t is {float((t ** power).mean()):.4f}, "
                          f"not {want:.4f} +- {sigmas * math.sqrt(var / n):.4f}")
    # the blocks' masked counts are binomial(block, t_b): their squared
    # distance from block * t_b sums to the binomial variances' sum
    counts = masked.reshape(-1, block).sum(-1)
    u = t * (1 - t)
    spread, want = float(((counts - block * t) ** 2).sum()), float(block * u.sum())
    var = float((block * u * (1 + 3 * (block - 2) * u) - (block * u) ** 2).sum())
    if abs(spread - want) > sigmas * math.sqrt(var):
        faults.append(f"the blocks' masked counts lie {spread:.1f} (squared) from "
                      f"block x t, not {want:.1f} +- {sigmas * math.sqrt(var):.1f}")
    return faults


class Job(pretrain_moe.Job):
    def __init__(self, cell, seed, tracked, work_dir, log):
        super().__init__(cell, seed, tracked, work_dir, log)
        from fedml_tpu.parallel import block_diffusion

        t_min = getattr(block_diffusion, "T_MIN", None)
        if t_min is not None and float(cell.config["t_min"]) != t_min:
            raise ValueError(
                f"the program's lowest noise level is {t_min} and cannot be "
                f"set from its arguments; the configuration wants "
                f"{cell.config['t_min']}")
        self.t_min = float(cell.config["t_min"])
        self.embedding_scale = float(cell.config["initial_embedding_scale"])
        self.weights_seed = int(cell.config["window_weights_seed"])

    # -- instrumentation: the draw's counter beside the loss -----------------
    def _instrument(self):
        import jax

        trainer, job = self.trainer, self
        drawn_init = trainer.init_state

        def window_init(rng):  # one draw, whatever --seed the loop passes
            state = drawn_init(jax.random.PRNGKey(job.weights_seed))
            return state.replace(params={
                **state.params,
                "embed": state.params["embed"] * job.embedding_scale})

        trainer.init_state = window_init  # inside the stamped one: set-up
        super()._instrument()
        # the reference check runs on the program's own draw
        self._uninstrumented = (drawn_init, self._uninstrumented[1])
        counted_step = trainer.train_step
        self._step_masked, self._step_max_load, self._step_weight = [], [], []

        def drawn_step(state, tokens, mask):
            state, metrics = counted_step(state, tokens, mask)
            job._step_masked.append(metrics["bd_masked_tokens"])
            job._step_weight.append(metrics["bd_weight_sum"])
            job._step_max_load.append(metrics["moe_max_expert_load"])
            return state, metrics

        trainer.train_step = drawn_step

    def _loop(self, steps, window=None):
        """The steps' losses, each over its own draw's weight (the module's
        docstring says why)."""
        import jax

        self._step_masked, self._step_max_load, self._step_weight = [], [], []
        losses = super()._loop(steps, window)
        weights = [float(w) for w in jax.device_get(self._step_weight)]
        return [loss * self.tokens_per_step() / weight
                for loss, weight in zip(losses, weights)]

    # -- the reference ------------------------------------------------------
    def _reference_check(self):
        import jax
        import jax.numpy as jnp
        from fedml_tpu.parallel import block_diffusion
        from fedml_tpu.parallel.context import mesh_context
        from fedml_tpu.parallel.sharding import batch_sharding

        trainer, config, tol = self.trainer, self.cell.config, self.tolerances
        cfg = trainer.cfg
        if self.accum != 1:
            raise ValueError("the reference check draws one pattern a step: "
                             "accum_steps must be 1")
        ref = harness.load_module(self.cell.root, "reference",
                                  config["reference"])
        tail = int(self.cell.traffic["reference_tail"])
        head = int(self.cell.traffic["reference_head"])
        init_state, train_step = self._uninstrumented
        state = init_state(jax.random.PRNGKey(self.seed))
        tokens = next(self.cheetah._batches(np.random.RandomState(self.seed)))
        tok, mask = jnp.asarray(tokens), jnp.ones_like(jnp.asarray(tokens))
        # the draw the step makes at step 0, as data for both sides
        x_t, masked, weight = block_diffusion.noise(
            block_diffusion.step_key(0), tok, cfg.bd_block, cfg.bd_mask_token)
        rows, positions = block_diffusion.model_rows(x_t, tok)
        faults = draw_faults(
            tokens, x_t, masked, weight, cfg.bd_block, cfg.bd_mask_token,
            self.t_min, float(self.cell.traffic["masked_share_sigmas"]))
        self.log(f"the draw of step 0 over {tokens.size} tokens: "
                 f"{'; '.join(faults) or 'sound'}")

        checks = {"draw_is_sound": not faults}
        if jax.devices()[0].platform == "tpu":
            checks["mosaic_call_in_lowered_step"] = (
                "tpu_custom_call" in trainer.lower_step(state, tok, mask).as_text())

        @jax.jit
        def tail_logits(variables, rows, positions):
            logits = trainer.model.apply(variables, rows, positions=positions)
            return jnp.concatenate([logits[:1, :head], logits[:1, -tail:]], 1)

        with trainer.mesh, mesh_context(trainer.mesh):
            got_logits = np.asarray(tail_logits(
                {"params": state.params, **state.model_state},
                jax.device_put(rows, batch_sharding(trainer.mesh)),
                positions))[0]
        host_params = jax.device_get(state.params)
        state, metrics = train_step(state, tok, mask)
        got_loss = float(metrics["loss"])
        got_masked = int(metrics["bd_masked_tokens"])
        got_weight = float(metrics["bd_weight_sum"]) / tokens.size
        del state, metrics

        t0 = time.perf_counter()
        one = jax.devices()[0]

        @jax.jit
        def reference_row(params, x_t, x_0, masked, weight):
            return ref.loss_sum_and_tail_logits(
                ref.reference_params(params, config), x_t, x_0, masked, weight,
                config, tail, head)

        params_ref = jax.device_put(host_params, one)
        total, counts, prob_sums = 0.0, 0.0, 0.0
        want_logits = margin = None
        for i in range(tokens.shape[0]):
            loss_sum, logits, m, c, s = reference_row(
                params_ref, *(jax.device_put(a[i], one)
                              for a in (x_t, tok, masked, weight)))
            total += float(loss_sum)
            counts, prob_sums = counts + np.asarray(c), prob_sums + np.asarray(s)
            if i == 0:
                want_logits, margin = np.asarray(logits), np.asarray(m)
        n_rows = 2 * tokens.size
        aux = sum(float(ref.aux_loss(c, s, n_rows, config))
                  for c, s in zip(counts, prob_sums))
        want_loss = total / tokens.size + float(config["aux_weight"]) * aux
        del params_ref, host_params

        clear = margin >= float(tol["routing_margin"])
        near_tie_share = 1.0 - float(clear.mean())
        per_position = (np.linalg.norm(got_logits - want_logits, axis=-1)
                        / np.linalg.norm(want_logits, axis=-1))

        def rel_l2(group):
            keep = clear & group
            return float(np.linalg.norm((got_logits - want_logits)[keep])
                         / np.linalg.norm(want_logits[keep]))

        first = np.arange(head + tail) < head
        err_head, err = rel_l2(first) if head else 0.0, rel_l2(~first)
        worst = np.argsort(-per_position)[:5]
        self.first_loss = got_loss / got_weight  # as _loop reports a step's
        self.log(f"reference ({tokens.shape[0]} rows, "
                 f"{time.perf_counter() - t0:.1f}s): loss {got_loss:.5f} vs "
                 f"{want_loss:.5f} (tolerance {tol['loss_abs']}; "
                 f"{got_masked} of {tokens.size} tokens masked, auxiliary "
                 f"term {float(config['aux_weight']) * aux:.5f}); noised-half "
                 f"logits of row 0: rel-L2 {err_head:.3g} over the first "
                 f"{head} and {err:.3g} over the last {tail}, of them the "
                 f"{int(clear.sum())} positions whose routing margin is at "
                 f"least {tol['routing_margin']} (tolerance "
                 f"{tol['logits_rel_l2']} each), near-tie share "
                 f"{near_tie_share:.3f} (at most {tol['near_tie_share_max']});"
                 f" worst positions (rel-L2, margin): "
                 f"{[(round(float(per_position[i]), 4), round(float(margin[i]), 5)) for i in worst]}"
                 f"; median per-position rel-L2 {float(np.median(per_position)):.3g}")
        checks["reference_agrees"] = bool(
            got_masked == int(np.asarray(masked).sum())
            and abs(got_loss - want_loss) <= float(tol["loss_abs"])
            and max(err, err_head) <= float(tol["logits_rel_l2"])
            and near_tie_share <= float(tol["near_tie_share_max"]))
        return checks

    # -- the window ---------------------------------------------------------
    def run(self, units, window):
        import jax

        outcome = super().run(units, window)
        cfg, traffic = self.trainer.cfg, self.cell.traffic
        tokens = self.tokens_per_step()
        mean, half = masked_share_band(
            tokens, cfg.bd_block, self.t_min,
            float(traffic["masked_share_sigmas"]))
        shares = [int(x) / tokens for x in jax.device_get(self._step_masked)]
        self.log(f"masked share a step: {min(shares, default=0):.4f} to "
                 f"{max(shares, default=0):.4f}; the band is {mean:.4f} +- "
                 f"{half:.4f}")
        outcome["checks"]["masked_share_in_band"] = (
            len(shares) == units
            and all(abs(s - mean) <= half for s in shares))
        rows = 2 * tokens  # a layer routes both copies of every sequence
        loads = [int(x) / rows for x in jax.device_get(self._step_max_load)]
        limit = float(traffic["max_load_share_max"])
        self.log(f"busiest held expert of any layer, share of a step's "
                 f"{rows} rows: {min(loads, default=0):.3f} to "
                 f"{max(loads, default=0):.3f} (under {limit}; a balanced "
                 f"router gives {cfg.moe_top_k / cfg.moe_experts:.4f})")
        outcome["checks"]["routing_spread"] = (
            len(loads) == units and all(x < limit for x in loads))
        return outcome

    def facts(self, units):
        # every one of a sequence's 2L rows is routed: twice the assignments
        # of a next-token step over the same data tokens
        facts = super().facts(units)
        return {**facts, "rows_per_step": 2 * self.tokens_per_step(),
                "assignments_per_step": 2 * facts["assignments_per_step"]}
