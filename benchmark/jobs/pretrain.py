"""Job ``pretrain``: LM pretraining steps through ``FedMLRunner.run`` -> ``CheetahRunner.run``.

The unit of work is one optimizer step. The window calls the program's own
loop (its ``data`` gather, its step, its per-step ``float(loss)``) on the
runner that was warmed up, with ``total_steps`` set to the number of steps
that fills the window. ``CheetahRunner.run`` builds a fresh train state first;
the window opens when that state is on the device, so the benchmark wraps the
trainer's ``init_state`` and ``train_step`` on the instance to see where the
steps begin, to keep each step's loss, and to start the profiler before the
window's last ``CLOCK_STEPS`` steps (the end-to-end rate is read on the
device's clock, see ``Job.throughput``). Neither wrapper touches what runs.

Reads from the configuration file the model's sizes under their Hugging Face
names, ``program`` (recipe: remat, kernel blocks, learning rate, mesh),
``schedule_total_steps`` and, where it has one, ``program_argument_of`` (each
published key beside the program argument that carries it: the job refuses a
value that differs and an argument the program lacks). A ``sliding_window``
shorter than ``seq_len`` is refused unless that mapping hands it to the
program. From the traffic file ``seq_len``,
``batch_per_chip``, ``program`` (e.g. ``accum_steps``), ``warmup_steps``,
``trace_units``, ``min_units`` and ``reference_tail``.

The program's own seed (``random_seed``: its weights, its synthetic corpus
and the windows each step reads from it) is ``--seed``, or, where the
configuration has one, its ``window_seed`` for every ``--seed``; the
reference check runs on the program's draw under ``--seed`` and on a batch
drawn by ``--seed`` in either case. A pinned seed is for a cell whose work
follows the values: which experts a drawn router favours, and how that
shifts as the steps learn, decides how many rows reach this chip's experts
(the configuration says why it is pinned).
"""

from __future__ import annotations

import statistics
import time

import numpy as np

from benchmark import harness

# System (bfloat16 activations and matmul inputs, float32 accumulation, splash
# kernels, chunked loss) against the plain float32 reference on the system's
# own initial parameters. Every matmul input is rounded to 8 bits of mantissa
# (4e-3 relative); through two or three blocks and the head that leaves 1.35e-2
# to 1.66e-2 relative L2 on the logits and at most 7e-4 on the mean loss at
# Mistral's widths (20 seeded runs over both cells, my chip runs, PR 22; 5e-3
# on the logits at the tests' width 64). A dropped rotation, swapped k and v
# or a wrong causal mask move the logits by more than 5e-2 at these widths,
# where the initial scores are O(1)
# (tests/benchmark/test_benchmark_shapes_references.py), and float8's 3 bits
# of mantissa round each input by 6e-2. So the bounds are 2.4x and 7x what
# bfloat16 gives.
LOGITS_REL_L2_TOL = 0.04
LOSS_ABS_TOL = 5e-3

# how far ``units`` median step periods may lie from the window's length for the
# median to be the rate (see ``Job.throughput``): a run in which the host was
# held up for more than a tenth of the window reports the whole window instead
MEDIAN_HOLDS_WITHIN = 0.10

STEP_MODULE = "_train_step_raw"  # the step program's name on the device
# how many of the window's last steps run under the profiler, for the device's
# clock: the step's duration repeats to 0.01% (PERF.md), so few are enough,
# and the trace stays as small as a traced run's
CLOCK_STEPS = 8

BASE = dict(training_type="distributed", model="transformer",
            model_size="from_config")  # any name but the program's presets

# Hugging Face config key -> the program's argument
SIZES = dict(vocab_size="vocab_size", hidden_size="d_model",
             num_hidden_layers="n_layers", num_attention_heads="n_heads",
             num_key_value_heads="n_kv_heads", intermediate_size="d_ff")


def reference_params(params, config):
    """The program's parameter tree in the reference's plain layout. The
    program fuses q, k, v into ``wqkv`` (columns in that order) and gate, up
    into ``w_gate_up``; blocks are ``[Checkpoint]Block_<i>``."""
    H = int(config["num_attention_heads"])
    Hkv = int(config["num_key_value_heads"])
    hd = int(config["hidden_size"]) // H
    F = int(config["intermediate_size"])
    blocks = sorted((k for k in params if "Block_" in k),
                    key=lambda k: int(k.rsplit("_", 1)[1]))
    layers = []
    for name in blocks:
        b = params[name]
        wqkv, wgu = b["Attention_0"]["wqkv"], b["FeedForward_0"]["w_gate_up"]
        layers.append({
            "attn_norm": b["RMSNorm_0"]["weight"],
            "wq": wqkv[:, :H * hd], "wk": wqkv[:, H * hd:(H + Hkv) * hd],
            "wv": wqkv[:, (H + Hkv) * hd:], "wo": b["Attention_0"]["wo"],
            "ffn_norm": b["RMSNorm_1"]["weight"],
            "w_gate": wgu[:, :F], "w_up": wgu[:, F:],
            "w_down": b["FeedForward_0"]["w_down"],
        })
    return {"embed": params["embed"], "layers": layers,
            "final_norm": params["RMSNorm_0"]["weight"],
            "lm_head": params["w_lm_head"]}


def _published(config, dotted):
    value = config
    for key in dotted.split("."):
        value = value[key]
    return value


def _check_program_arguments(config, handed):
    """Refuse a file whose ``program_argument_of`` pairs a published key with
    a program argument of another value, and a program whose
    ``TransformerConfig`` lacks one of those arguments (the parent of the PR
    that brought a configuration: it cannot build it, and says so at once;
    the program ignores an argument it has no field for)."""
    for key, arg in handed.items():
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
    lacking = sorted(set(handed.values()) - fields)
    if lacking:
        raise RuntimeError(
            f"this program cannot build {config['name']}: its "
            f"TransformerConfig has no {lacking}")


class Job:
    unit = "step"

    def __init__(self, cell, seed, tracked, work_dir, log):
        self.cell, self.seed, self.tracked, self.log = cell, int(seed), tracked, log
        self.work_dir = work_dir
        traffic, config = cell.traffic, cell.config
        self.seq_len = int(traffic["seq_len"])
        handed = config.get("program_argument_of", {})
        window = config.get("sliding_window")
        if (window is not None and self.seq_len > int(window)
                and "sliding_window" not in handed):
            raise ValueError(
                f"seq_len {self.seq_len} exceeds the configuration's "
                f"sliding_window {window}, and its program_argument_of hands "
                "sliding_window to no program argument")
        _check_program_arguments(config, handed)
        self.batch = int(traffic["batch_per_chip"]) * cell.chips
        self.program = {
            **BASE, **{arg: int(config[key]) for key, arg in SIZES.items()},
            "seq_len": self.seq_len, "batch_size": self.batch,
            "total_steps": int(config["schedule_total_steps"]),
            **config["program"], **traffic.get("program", {}),
        }
        self.accum = int(self.program.get("accum_steps", 1))
        self.unit_s = None

    # -- instrumentation around the trainer's calls --------------------------
    def _instrument(self):
        import jax

        trainer = self.trainer
        init_state, train_step = trainer.init_state, trainer.train_step
        job = self

        def stamped_init(rng):
            state = jax.block_until_ready(init_state(rng))
            if job._window is not None:
                job._window.start()
            return state

        def recorded_step(state, tokens, mask):
            if len(job._step_started) == job._clock_from:
                job._window.start_profiler()  # the device's clock, from here on
            job._step_started.append(time.perf_counter())
            state, metrics = train_step(state, tokens, mask)
            job._step_losses.append(metrics["loss"])
            return state, metrics

        trainer.init_state, trainer.train_step = stamped_init, recorded_step
        self._uninstrumented = (init_state, train_step)

    def _loop(self, steps: int, window=None):
        """The program's loop for ``steps`` steps; returns the losses. With a
        window, the profiler runs over the last ``CLOCK_STEPS`` steps."""
        import jax

        self._window, self._step_started, self._step_losses = window, [], []
        self._clock_from = (max(0, steps - CLOCK_STEPS)
                            if window is not None else None)
        self.cheetah.total_steps = int(steps)
        self.runner.run()  # ends in block_until_ready(state.params)
        if window is not None:
            window.stop()
        return [float(x) for x in jax.device_get(self._step_losses)]

    # -- set-up --------------------------------------------------------------
    def setup(self):
        import fedml_tpu as fedml
        from fedml_tpu import data as data_mod
        from fedml_tpu import get_device
        from fedml_tpu.arguments import Arguments
        from fedml_tpu.runner import FedMLRunner

        window_seed = int(self.cell.config.get("window_seed", self.seed))
        args = fedml.init(Arguments(overrides={
            **self.program, "random_seed": window_seed,
            **harness.tracking_arguments(self.cell, self.seed, self.tracked,
                                         self.work_dir),
        }), should_init_logs=False)
        ds, _ = data_mod.load(args)
        self.log("data loaded")
        self.runner = FedMLRunner(args, get_device(args), ds, None)
        self.cheetah = self.runner.runner
        self.trainer = self.cheetah.trainer
        cfg, config = self.trainer.cfg, self.cell.config
        for key, have in (("rms_norm_eps", cfg.norm_eps),
                          ("rope_theta", cfg.rope_theta)):
            if float(config[key]) != float(have):
                raise ValueError(
                    f"the program's {key} is {have} and cannot be set from "
                    f"its arguments; the configuration wants {config[key]}")
        if self.cheetah._token_stream() is None:
            raise RuntimeError("the dataset gave no token stream: the run "
                               "would train on uniform random tokens")
        self._instrument()

        warm = int(self.cell.traffic["warmup_steps"])
        losses = self._loop(warm)
        steady = np.diff(self._step_started)[2:]  # steps 0 and 1 compile
        self.unit_s = float(statistics.median(steady))
        self.log(f"{warm} warm-up steps, steady step {self.unit_s:.4f}s, "
                 f"losses {losses[0]:.4f} .. {losses[-1]:.4f}")
        return self._reference_check()

    def _reference_check(self):
        """One seeded batch of the cell's shape from the system's own initial
        parameters: the step's loss against the reference's mean over every
        row, and the logits of the last ``reference_tail`` positions of row 0
        (with random weights the loss is ln V whatever the layers do; the
        logits are what a wrong mask, rotation or head grouping moves)."""
        import jax
        import jax.numpy as jnp
        from fedml_tpu.parallel.context import mesh_context
        from fedml_tpu.parallel.sharding import batch_sharding

        trainer, config = self.trainer, self.cell.config
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
        def tail_logits(params, toks):
            return trainer.model.apply({"params": params}, toks)[:1, -tail:]

        first = jnp.asarray(rows[:self.batch])
        with trainer.mesh, mesh_context(trainer.mesh):
            got_logits = np.asarray(tail_logits(
                state.params,
                jax.device_put(first, batch_sharding(trainer.mesh))))[0]
        host_params = jax.device_get(state.params)
        state, metrics = train_step(state, tok, mask)
        got_loss = float(metrics["loss"])
        del state, metrics

        t0 = time.perf_counter()
        one = jax.devices()[0]

        @jax.jit
        def reference_row(params, toks):
            return ref.loss_sum_and_tail_logits(
                reference_params(params, config), toks, config, tail)

        params_ref = jax.device_put(host_params, one)
        total, want_logits = 0.0, None
        for i, row in enumerate(rows):
            loss_sum, logits = reference_row(params_ref, jax.device_put(row, one))
            total += float(loss_sum)
            if i == 0:
                want_logits = np.asarray(logits)
        want_loss = total / (rows.shape[0] * (rows.shape[1] - 1))
        del params_ref, host_params
        err = float(np.linalg.norm(got_logits - want_logits)
                    / np.linalg.norm(want_logits))
        self.first_loss = got_loss
        self.log(f"reference ({len(rows)} rows, {time.perf_counter() - t0:.1f}s):"
                 f" loss {got_loss:.5f} vs {want_loss:.5f} (tolerance "
                 f"{LOSS_ABS_TOL}), last {tail} logits of row 0 rel-L2 "
                 f"{err:.3g} (tolerance {LOGITS_REL_L2_TOL})")
        checks["reference_agrees"] = (abs(got_loss - want_loss) <= LOSS_ABS_TOL
                                      and err <= LOGITS_REL_L2_TOL)
        return checks

    # -- the window ---------------------------------------------------------
    def run(self, units: int, window):
        losses = self._loop(units, window)
        # one period a step: from its start to the next step's, the last one
        # to the window's end (after the loop's block_until_ready)
        self._periods = np.diff(np.append(self._step_started, window.t1))
        med = float(np.median(self._periods))
        slow = self._periods > 1.02 * med
        # where the host's share of a step goes: a steady few ms, or hiccups
        self.log(f"step periods: median {med:.4f}s, min {self._periods.min():.4f}s"
                 f", max {self._periods.max():.4f}s, {int(slow.sum())} of "
                 f"{len(self._periods)} over 1.02x the median, holding "
                 f"{float((self._periods - med)[slow].sum()):.3f}s")
        return {"losses": losses, "first_loss": self.first_loss,
                "records": harness.last_round_records(units, self.tracked)}

    def tokens_per_step(self) -> int:
        return self.batch * self.accum * self.seq_len

    def throughput(self, units: int, seconds: float, trace):
        """Tokens a second a chip, on the device's clock and on the host's.

        ``tokens_per_s_per_chip`` is a step's tokens over the step program's
        duration on the device (``XLA Modules`` line, median over the window's
        last ``CLOCK_STEPS`` steps, slowest chip): what the chip costs a
        token. The loop waits for every step's loss, so the host's clock adds
        its own few ms to each step, and on one chip, whose machine shares its
        host's cores, that part does not repeat: 3.1 ms in some runs and 6.0
        ms in others around a device step that repeats to 0.01% (section 6 of
        PERF.md), and single periods 60 to 90 ms long. At depth 2 the step is
        a sixteenth of the published model's, so the host's share is sixteen
        times a deployment's; the device's clock leaves it to the per-layer
        metrics (``device.step_idle_share``).

        ``wall_tokens_per_s_per_chip`` is the same tokens over the median step
        period on the host's clock: what the loop delivers, for cells whose
        machine is theirs alone. The median drops the periods in which the
        host was held up, where the mean keeps them. It stands only while the
        steps' starts account for the window, ``units`` median periods within
        ``MEDIAN_HOLDS_WITHIN`` of its length; a loop that no longer waits
        every step starts its steps in a burst and fails that, and then the
        rate is whole steps over the whole window."""
        import jax

        per_chip = self.tokens_per_step() / self.cell.chips
        whole = units * per_chip / seconds
        med = float(np.median(self._periods))
        holds = abs(units * med / seconds - 1.0) <= MEDIAN_HOLDS_WITHIN
        wall = per_chip / med if holds else whole

        from benchmark import trace_reduce as tr

        steps = [tr.module_events(dev, STEP_MODULE).duration
                 for dev in (trace.devices if trace is not None else [])]
        if steps and all(len(d) for d in steps):
            device_s = max(float(np.median(d)) for d in steps)
        elif jax.devices()[0].platform == "tpu":
            raise RuntimeError("the profiler recorded no execution of "
                               f"{STEP_MODULE} on some chip")
        else:
            # XLA:CPU has no device plane. run.py never gets here off a TPU;
            # the tests do, and read the host's clock under both names
            device_s = per_chip / wall
        self.log(f"tokens/s/chip: {per_chip / device_s:.1f} on the device's "
                 f"clock (step {device_s:.5f}s, {[len(d) for d in steps]} "
                 f"steps read), {per_chip / med:.1f} at the host's median "
                 f"period, {whole:.1f} over the whole window; the host's "
                 f"{'median' if holds else 'whole window'} is reported")
        return {"tokens_per_s_per_chip": per_chip / device_s,
                "wall_tokens_per_s_per_chip": wall}

    def facts(self, units: int):
        return {
            "unit": self.unit, "units": units, "module": STEP_MODULE,
            "tokens_per_step": self.tokens_per_step(),
            "train_flops_per_token": harness.flops_function(self.cell)(
                self.cell.config, self.seq_len),
            "chips": self.cell.chips,
        }
