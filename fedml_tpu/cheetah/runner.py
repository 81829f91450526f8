"""Cheetah runner: config → mesh → sharded pretraining loop.

The ``training_type: distributed`` branch of FedMLRunner (absent in the
reference — ``runner.py:29-38`` handles only simulation/cross_silo/
cross_device). Consumes the packed FedDataset (token streams) or a synthetic
stream, builds the mesh from ``args.mesh_shape``, and drives
``parallel.CheetahTrainer`` with optional per-step logging + checkpointing.
"""

from __future__ import annotations

import logging
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import constants
from ..core.mlops import telemetry
from ..parallel.sharding import make_mesh
from ..parallel.train_step import CheetahTrainer, make_optimizer
from ..parallel.transformer import TransformerConfig, train_flops_per_token

logger = logging.getLogger(__name__)


def _parse_bool(v) -> bool:
    """YAML-robust bool: unregistered keys reach us as raw strings, and
    bool(\"false\") would silently mean True."""
    if isinstance(v, str):
        lowered = v.strip().lower()
        if lowered in ("1", "true", "yes", "on"):
            return True
        if lowered in ("0", "false", "no", "off", ""):
            return False
        raise ValueError(f"not a boolean: {v!r}")
    return bool(v)


def config_from_args(args) -> TransformerConfig:
    size = str(getattr(args, "model_size", "tiny")).lower()
    if size in ("7b", "llama2_7b"):
        cfg = TransformerConfig.llama2_7b()
    elif size == "tiny":
        cfg = TransformerConfig.tiny(
            vocab_size=int(getattr(args, "vocab_size", 256))
        )
    else:
        cfg = TransformerConfig(
            vocab_size=int(getattr(args, "vocab_size", 32000)),
            d_model=int(getattr(args, "d_model", 1024)),
            n_layers=int(getattr(args, "n_layers", 8)),
            n_heads=int(getattr(args, "n_heads", 8)),
            n_kv_heads=int(getattr(args, "n_kv_heads", 8)),
            d_ff=int(getattr(args, "d_ff", 2816)),
            max_seq_len=int(getattr(args, "seq_len", 1024)),
        )
    # every other field of TransformerConfig is an argument of the same name
    # (attention kind and its ranks, the GQA head's size and q/k norms, YaRN,
    # the layer list, the expert layer's routing and capacity rules,
    # hyper-connections, MTP, the objective and its block length, norm_eps,
    # rope_theta, splash blocks, remat ...), applied to EVERY size: the one
    # place the args→config mapping lives, and the dataclass defaults stay
    # the single source of truth. A field's default gives the cast.
    import dataclasses as _dc

    extra = {}
    for field in _dc.fields(TransformerConfig):
        value = getattr(args, field.name, None)
        if value is None or field.name in _SHAPE_FIELDS:
            continue
        cast = _parse_bool if isinstance(field.default, bool) else type(
            field.default)
        extra[field.name] = cast(value)
    return _dc.replace(cfg, **extra) if extra else cfg


# set from arguments under other names above (or not from arguments at all)
_SHAPE_FIELDS = ("vocab_size", "d_model", "n_layers", "n_heads", "n_kv_heads",
                 "d_ff", "max_seq_len", "dtype", "param_dtype")


class CheetahRunner:
    def __init__(self, args, device, dataset, model=None):
        self.args = args
        self.cfg = config_from_args(args)
        mesh_shape = args.parse_mesh_shape() or None
        self.mesh = make_mesh(mesh_shape)
        self.batch_size = int(getattr(args, "batch_size", 8))
        self.seq_len = int(getattr(args, "seq_len", 128))
        self.total_steps = int(getattr(args, "total_steps", 10))
        self.accum_steps = int(getattr(args, "accum_steps", 1))
        self.trainer = CheetahTrainer(
            self.cfg,
            self.mesh,
            optimizer=make_optimizer(
                learning_rate=float(getattr(args, "learning_rate", 3e-4)),
                warmup_steps=int(getattr(args, "warmup_steps", 10)),
                total_steps=self.total_steps,
            ),
            accum_steps=self.accum_steps,
            # a sequence axis in the mesh means sequence parallelism — left
            # off, the axis is built and the whole step replicated over it
            seq_sharded=int(
                self.mesh.shape[constants.MESH_AXIS_SEQUENCE]) > 1,
        )
        self.dataset = dataset
        self.checkpoint_dir = str(getattr(args, "checkpoint_dir", "") or "")

    def _token_stream(self) -> Optional[np.ndarray]:
        """The packed dataset's tokens as one contiguous stream, or None.

        The data layer packs NWP datasets as [clients, cap, seq] int token
        windows; pretraining doesn't care about client boundaries, so the
        whole corpus flattens into a single stream that random seq_len
        windows are drawn from. Token ids are clipped into the model's
        vocab (a staged corpus may use a smaller alphabet — fine; a larger
        one would silently alias, so clip and warn once).
        """
        ds = self.dataset
        if ds is None or getattr(ds, "task", "") != "nwp":
            return None
        # only each client's REAL rows — the packed layout zero-pads beyond
        # train_counts[c], and training on runs of pad token 0 poisons loss
        tx = np.asarray(ds.train_x)
        counts = np.asarray(ds.train_counts)
        parts = [
            tx[c, : int(counts[c])].reshape(-1)
            for c in range(tx.shape[0])
            if int(counts[c]) > 0
        ]
        if not parts:
            return None
        stream = np.concatenate(parts).astype(np.int32)
        if stream.size < (self.seq_len + 1) * 2:
            return None
        vmax = int(stream.max())
        if vmax >= self.cfg.vocab_size:
            logger.warning(
                "cheetah: corpus vocab %d exceeds model vocab %d; clipping",
                vmax + 1, self.cfg.vocab_size,
            )
            stream = np.minimum(stream, self.cfg.vocab_size - 1)
        return stream

    def _batches(self, rng: np.random.RandomState):
        """Token batches from the dataset's packed stream, else synthetic."""
        V = self.cfg.vocab_size
        shape = (self.batch_size, self.seq_len)
        if self.accum_steps > 1:
            shape = (self.accum_steps,) + shape
        stream = self._token_stream()
        if stream is None:
            while True:
                yield rng.randint(0, V, shape).astype(np.int32)
        from .. import native

        n_rows = int(np.prod(shape[:-1]))
        while True:
            starts = rng.randint(0, stream.size - self.seq_len, size=n_rows)
            # threaded C++ window gather: this slice runs on the host
            # critical path between device steps
            rows = native.gather_windows(stream, starts, self.seq_len)
            yield rows.reshape(shape)

    def run(self) -> dict:
        state = self.trainer.init_state(
            jax.random.PRNGKey(int(getattr(self.args, "random_seed", 0)))
        )
        start_step = 0
        guard = None
        if self.checkpoint_dir:
            from ..checkpoint import CheckpointManager
            from ..core import runstate

            ckpt = CheckpointManager(self.checkpoint_dir)
            restored = ckpt.restore_latest(state)
            if restored is not None:
                state = restored
                start_step = int(state.step)
                logger.info("cheetah: resumed from step %d", start_step)
            # step-granular preemption drain (docs/robustness.md): SIGTERM
            # during a long pretrain exits within ONE step's latency with
            # the state checkpointed at the step boundary it latched on
            guard = runstate.preemption_guard()
            if bool(getattr(self.args, "preempt_signals", True)):
                guard.install()
            guard.reset()
        rng = np.random.RandomState(int(getattr(self.args, "random_seed", 0)))
        gen = self._batches(rng)
        losses = []
        t0 = time.perf_counter()
        tokens_done = 0
        every = int(getattr(self.args, "checkpoint_every_rounds", 0) or 0)
        # per-step telemetry denominators (the Cheetah "round" is a step):
        # model FLOPs/token of this configuration (its attention kind, layer
        # list and experts held) for the live MFU gauge, chip peak by kind
        flops_tok = train_flops_per_token(self.cfg, self.seq_len)
        # None off-TPU (MFU "not measured"); an unknown TPU kind raises
        peak = telemetry.peak_bf16_flops(jax.devices()[0])
        n_chips = jax.device_count()
        for step in range(start_step, self.total_steps):
            # every statement of an iteration lies in a telemetry span:
            # tracked, the spans tile the step (docs/telemetry.md)
            with telemetry.phase("hooks"):
                telemetry.on_round_start(step)
                rec = telemetry.begin_round(step, unit="step")
            with telemetry.phase("data"):
                tokens = next(gen)
                mask = np.ones_like(tokens)
            with telemetry.phase("h2d"):
                tokens_dev, mask_dev = jnp.asarray(tokens), jnp.asarray(mask)
            t_dispatch = time.perf_counter()
            with telemetry.phase("step"):
                state, metrics = self.trainer.train_step(
                    state, tokens_dev, mask_dev
                )
            with telemetry.phase("loss_sync"):
                losses.append(float(metrics["loss"]))
            with telemetry.phase("record"):
                tokens_done += tokens.size
                if rec is not None:
                    rec.dispatch_latency_s = time.perf_counter() - t_dispatch
                    rec.lazy["examples"] = tokens.size
                    # the step's routing and noise-draw counters: device
                    # scalars, realized with the loss when the record is
                    # emitted (no sync here)
                    rec.lazy.update((k, v) for k, v in metrics.items()
                                    if k.startswith(("moe_", "bd_")))
                telemetry.end_round(rec, train_loss=losses[-1])
                if rec is not None and rec.wall_s > 0:
                    tps = tokens.size / rec.wall_s
                    telemetry.gauge_set("cheetah.tokens_per_sec", tps)
                    if peak is not None:
                        telemetry.gauge_set(
                            "cheetah.mfu_estimate",
                            telemetry.mfu_estimate(tps, flops_tok, peak,
                                                   n_chips),
                        )
                telemetry.on_round_end(step)
            with telemetry.phase("checkpoint"):
                if every and (step + 1) % every == 0 and self.checkpoint_dir:
                    ckpt.save(state)
                if guard is not None and guard.requested() \
                        and step + 1 < self.total_steps:
                    from ..core.runstate import PreemptionError

                    # drain commit: this step completed — persist it NOW
                    # (even off the checkpoint cadence) so the restart
                    # resumes at exactly step + 1 instead of re-training the
                    # window
                    if ckpt.latest_step() != int(state.step):
                        ckpt.save(state)
                    ckpt.close()
                    telemetry.counter_inc("run.preemptions")
                    telemetry.drain_records()
                    raise PreemptionError(step)
        telemetry.drain_records()  # every step's record is in the sink
        jax.block_until_ready(state.params)
        dt = time.perf_counter() - t0
        tps = tokens_done / max(dt, 1e-9)
        result = {
            "final_loss": losses[-1] if losses else float("nan"),
            "steps": self.total_steps - start_step,
            "tokens_per_sec": tps,
        }
        if peak is not None:
            result["mfu_estimate"] = round(
                telemetry.mfu_estimate(tps, flops_tok, peak, n_chips), 4
            )
        else:
            logger.info("cheetah: mfu_estimate not measured (platform %s)",
                        jax.devices()[0].platform)
        if self.checkpoint_dir:
            ckpt.save(state)
            ckpt.close()  # release orbax worker threads with the run
        logger.info("cheetah: %s", result)
        return result
