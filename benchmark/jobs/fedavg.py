"""Job ``fedavg``: FedAvg-family rounds through ``FedMLRunner.run`` -> ``FedAvgAPI.train``.

The unit of work is one round. The window calls the program's own training
loop (async dispatch, chunking, its evaluations at the first and last round)
on the runner that was warmed up, with ``comm_round`` set to the number of
rounds that fills the window: a change that removes a host sync or overlaps
the gather shows here, which a re-implemented loop would hide.

Reads from the configuration file ``program`` (arguments of the program that
belong to the model and its training recipe), ``shapes`` (for the FLOP count)
and ``reference``; from the traffic file ``program`` (partition, cohort,
backend: what varies between mixes), ``data_seed`` (pins data and partition
where the packed capacity must be one constant shape; ``null`` lets them
follow ``--seed``), ``expect_cap``, ``warmup_rounds``, ``trace_units`` and
``min_units``.
"""

from __future__ import annotations

import time

from benchmark import harness

# Round 0, system against the plain replay. On a convex model the two agree to
# float32 rounding (1e-5 on the aggregated update; tests/benchmark, which is
# where masks, weights, keys and batch order are held exactly). On a ResNet
# they cannot: 16 to 27 SGD steps at lr 0.1 through ReLUs and GroupNorms are
# chaotic in the parameters. With exact float32 on both sides (XLA:CPU,
# ResNet-20, one round) the system's aggregated update differs from the
# replay's by 0.40 relative L2, and the replay differs from itself by 0.44
# when its initial parameters are perturbed by 1e-6; the round's mean loss
# agrees to 3e-4 there. On the v5e (ResNet-56, both partitions, 15 seeded
# runs) the updates differ by 0.31 to 0.56 and the losses by 0.5 to 2.8% (my
# chip runs, PR 22). So on the chip this check can hold only what survives chaos: the
# mean training loss of the round within 10%, and an aggregated update that
# is nearer to the replay's than no update at all would be (relative L2 under
# 0.9; two independent directions of equal length give 1.4, a cohort summed
# instead of averaged gives 9). A fault subtler than that is for the CPU
# tests to catch, not for this check.
LOSS_REL_TOL = 0.10
UPDATE_REL_L2_TOL = 0.9

BASE = dict(training_type="simulation", federated_optimizer="FedAvg",
            frequency_of_the_test=10 ** 9)  # the loop still evaluates at its first and last round


def rel_l2(got, want) -> float:
    import jax

    num = sum(float(((g - w) ** 2).sum()) for g, w in zip(
        jax.tree.leaves(got), jax.tree.leaves(want)))
    den = sum(float((w ** 2).sum()) for w in jax.tree.leaves(want))
    return (num / max(den, 1e-30)) ** 0.5


class Job:
    unit = "round"

    def __init__(self, cell, seed, tracked, work_dir, log):
        self.cell, self.seed, self.tracked, self.log = cell, int(seed), tracked, log
        self.work_dir = work_dir
        self.program = {**BASE, **cell.config["program"],
                        **cell.traffic["program"]}
        self.unit_s = None

    # -- set-up: data, init, compile, reference check, steady estimate ------
    def setup(self):
        import jax
        import jax.numpy as jnp

        import fedml_tpu as fedml
        from fedml_tpu import data as data_mod
        from fedml_tpu import get_device
        from fedml_tpu import models as model_mod
        from fedml_tpu.arguments import Arguments
        from fedml_tpu.runner import FedMLRunner

        args = fedml.init(Arguments(overrides={
            **self.program, "random_seed": self.seed, "comm_round": 1,
            **harness.tracking_arguments(self.cell, self.seed, self.tracked,
                                         self.work_dir),
        }), should_init_logs=False)
        data_seed = self.cell.traffic.get("data_seed")
        if data_seed is not None:
            args.random_seed = int(data_seed)
        ds, output_dim = data_mod.load(args)
        args.random_seed = self.seed
        expect_cap = self.cell.traffic.get("expect_cap")
        if expect_cap is not None and ds.cap != int(expect_cap):
            raise RuntimeError(
                f"packed capacity is {ds.cap}, the cell was defined at "
                f"{expect_cap}: another cap is another shape and another cell")
        self.args, self.ds = args, ds
        self.log(f"data packed: {ds.client_num} clients, cap {ds.cap}")
        self.runner = FedMLRunner(args, get_device(args), ds,
                                  model_mod.create(args, output_dim))
        self.api = api = self.runner.runner.fl_trainer
        self.cohort = min(int(args.client_num_per_round), ds.client_num)
        self.log("engine built, dataset on the device")

        # round 0 alone, from the initial parameters (the round donates them)
        params0 = jax.tree.map(jnp.copy, api.global_params)
        self.runner.run()
        params1 = jax.tree.map(jnp.copy, api.global_params)
        self.first_loss = float(api.history[0]["train_loss"])
        self.log("round 0 and its evaluation done (traced, lowered, compiled "
                 "or loaded)")

        checks = {"reference_agrees": self._replay_round0(params0, params1)}
        del params0, params1

        # steady rounds: the same loop, all programs compiled
        warm = int(self.cell.traffic["warmup_rounds"])
        args.comm_round = warm
        jax.block_until_ready(api.global_params)
        t0 = time.perf_counter()
        self.runner.run()
        jax.block_until_ready(api.global_params)
        loop_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        api.evaluate(api.global_params, ds.test_x, ds.test_y)
        eval_s = time.perf_counter() - t0
        # the loop evaluated twice (first and last round); a round is the rest
        self.unit_s = max(loop_s - 2 * eval_s, 0.5 * loop_s) / warm
        self.log(f"{warm} warm rounds in {loop_s:.3f}s, one evaluation "
                 f"{eval_s:.3f}s, cap {ds.cap}, cohort {self.cohort}")
        return checks

    def _replay_round0(self, params0, params1) -> bool:
        import jax

        from benchmark.reference import fedavg_round

        ref = harness.load_module(self.cell.root, "reference",
                                  self.cell.config["reference"])
        shapes = self.cell.config.get("reference_args", {})
        program, ds = self.program, self.ds
        cohort = fedavg_round.sample_cohort(0, ds.client_num, self.cohort)
        t0 = time.perf_counter()
        want, ref_loss = fedavg_round.replay_round(
            lambda p, x: ref.forward(p, x, **shapes), params0,
            ds.train_x[cohort], ds.train_y[cohort], ds.train_counts[cohort],
            seed=self.seed, round_idx=0,
            batch_size=int(program["batch_size"]),
            epochs=int(program["epochs"]), lr=float(program["learning_rate"]))
        delta = lambda new: jax.tree.map(lambda a, b: a - b, new, params0)
        err = rel_l2(delta(params1), delta(want))
        loss_err = abs(self.first_loss - ref_loss) / abs(ref_loss)
        self.log(f"round 0 replayed by the plain reference in "
                 f"{time.perf_counter() - t0:.1f}s: update rel-L2 {err:.3g} "
                 f"(tolerance {UPDATE_REL_L2_TOL}), loss {self.first_loss:.4f}"
                 f" vs {ref_loss:.4f} (relative {loss_err:.3g}, tolerance "
                 f"{LOSS_REL_TOL})")
        return err <= UPDATE_REL_L2_TOL and loss_err <= LOSS_REL_TOL

    # -- the window ---------------------------------------------------------
    def run(self, units: int, window):
        import jax

        api = self.api
        self.args.comm_round = int(units)
        seen = len(api.history)
        jax.block_until_ready(api.global_params)
        window.start()
        self.runner.run()
        jax.block_until_ready(api.global_params)
        window.stop()
        records = harness.last_round_records(units, self.tracked)
        checks = {"every_round_fused":
                  getattr(api, "_round_step", None) is not None
                  and all(r["fused"] for r in records)}
        return {
            "losses": [h["train_loss"] for h in api.history[seen:]],
            "first_loss": self.first_loss, "records": records,
            "checks": checks,
        }

    def throughput(self, units: int, seconds: float, trace=None):
        return {"rounds_per_s": units / seconds}

    def facts(self, units: int):
        flops = harness.flops_function(self.cell)
        return {
            "unit": self.unit, "units": units, "module": "core",
            "cohort": self.cohort, "cap": int(self.ds.cap),
            "epochs": int(self.program["epochs"]),
            "train_flops_per_sample": flops(**self.cell.config["flops"]["args"]),
            "chips": self.cell.chips,
        }
