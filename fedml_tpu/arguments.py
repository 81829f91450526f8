"""Config system: one YAML file with sectioned families flattened into a single
typed attribute namespace.

Mirrors the reference's ``python/fedml/arguments.py:33-190`` (argparse ``--cf`` /
``--run_id`` / ``--rank`` / ``--role`` + YAML section families flattened into flat
attributes, last key wins) and upgrades it with what the survey flags as missing
(SURVEY.md §5 "Config / flag system"): a typed, validated schema with defaults and
helpful errors, while keeping the one-file UX.
"""

from __future__ import annotations

import argparse
from typing import Any, Dict, Optional

import yaml

from . import constants

# The reference flattens these YAML families into one namespace
# (arguments.py:163-166). We accept arbitrary families but recognise these.
KNOWN_FAMILIES = (
    "common_args",
    "data_args",
    "model_args",
    "train_args",
    "validation_args",
    "device_args",
    "comm_args",
    "tracking_args",
    "security_args",
    "attack_args",
    "defense_args",
    "dp_args",
    "parallel_args",
    "checkpoint_args",
)

# Typed schema: name -> (type, default). Anything not listed is passed through
# untyped (the reference has no schema at all; we validate what we know).
_SCHEMA: Dict[str, tuple] = {
    # common
    "training_type": (str, constants.FEDML_TRAINING_PLATFORM_SIMULATION),
    "random_seed": (int, 0),
    "scenario": (str, constants.FEDML_CROSS_SILO_SCENARIO_HORIZONTAL),
    "config_version": (str, "release"),
    # data
    "dataset": (str, "synthetic"),
    "data_cache_dir": (str, "./data_cache"),
    "partition_method": (str, "hetero"),
    "partition_alpha": (float, 0.5),
    "batch_size": (int, 32),
    # model
    "model": (str, "lr"),
    # train
    "federated_optimizer": (str, constants.FEDML_FEDERATED_OPTIMIZER_FEDAVG),
    "client_id_list": (str, "[]"),
    "client_num_in_total": (int, 10),
    "client_num_per_round": (int, 10),
    "comm_round": (int, 10),
    "epochs": (int, 1),
    "client_optimizer": (str, "sgd"),
    "learning_rate": (float, 0.03),
    "momentum": (float, 0.0),
    "weight_decay": (float, 0.0),
    "server_optimizer": (str, "sgd"),
    "server_lr": (float, 1.0),
    "server_momentum": (float, 0.0),
    "fedprox_mu": (float, 0.1),
    "clip_grad": (float, 0.0),
    # validation
    "frequency_of_the_test": (int, 5),
    # device
    "using_gpu": (bool, False),  # kept for config compat; TPU/CPU decided by JAX
    "device_type": (str, "auto"),  # auto | tpu | cpu
    "mesh_shape": (str, ""),  # e.g. "clients:8" or "data:2,tensor:4"
    # comm
    "backend": (str, constants.FEDML_SIMULATION_TYPE_SP),
    "grpc_ipconfig_path": (str, ""),
    "comm_host": (str, "127.0.0.1"),
    "comm_port": (int, 8890),
    # tracking / telemetry (core/mlops/telemetry.py)
    "enable_tracking": (bool, False),
    "tracking_dir": (str, ""),  # JSONL event sink dir (default .fedml_tpu_runs)
    # write-behind JSONL sink drain interval (core/mlops/__init__.py):
    # events buffer in memory and hit the disk every this-many seconds
    # (or at 256 buffered events, or at shutdown). 0 = flush per event.
    "tracking_flush_s": (float, 0.5),
    # distributed tracing (core/mlops/tracing.py, docs/tracing.md):
    # cross-process causal spans + flight recorder. trace_sample is the
    # deterministic per-round sampling probability for soak-scale runs;
    # trace_dir overrides where flight-recorder post-mortems land
    # (default: tracking_dir).
    "enable_tracing": (bool, False),
    "trace_sample": (float, 1.0),
    "trace_dir": (str, ""),
    "enable_wandb": (bool, False),
    # Prometheus-style text exposition of the metrics registry, refreshed
    # during the run and at exit. Empty = no file.
    "metrics_file": (str, ""),
    # jax.profiler trace window over rounds/steps [N, M): "N:M" (bare "N"
    # traces one round). Works with or without enable_tracking.
    "profile_rounds": (str, ""),
    "profile_dir": (str, ""),  # trace output dir (default: tracking dir)
    # periodic host CPU/RSS + HBM sampler (daemon thread); 0 = off
    "sys_perf_interval_s": (float, 0.0),
    "run_id": (str, "0"),
    "rank": (int, 0),
    "local_rank": (int, 0),
    "node_rank": (int, 0),
    "role": (str, "client"),
    # security
    "enable_attack": (bool, False),
    "attack_type": (str, ""),
    "enable_defense": (bool, False),
    "defense_type": (str, ""),
    # dp
    "enable_dp": (bool, False),
    "mechanism_type": (str, "laplace"),
    "epsilon": (float, 1.0),
    "delta": (float, 1e-5),
    "sensitivity": (float, 1.0),
    "dp_type": (str, "cdp"),  # cdp (central) | ldp (local)
    # checkpointing (absent in reference — SURVEY.md §5 "Checkpoint / resume")
    "checkpoint_dir": (str, ""),
    "checkpoint_every_rounds": (int, 0),
    # crash-safe rounds (core/runstate.py): checkpoint_rounds is the
    # preferred cadence knob (checkpoint_every_rounds kept as an alias);
    # resume ∈ auto|never|require decides what an existing checkpoint dir
    # means at startup; preempt_signals installs the SIGTERM/SIGINT
    # drain-and-commit handler whenever checkpointing is on
    "checkpoint_rounds": (int, 0),
    "resume": (str, "auto"),
    "preempt_signals": (bool, True),
    # idempotent at-least-once delivery (core/distributed/delivery.py):
    # sender-side retry budget (exponential backoff + jitter) and the
    # receiver-side dedup window (per-sender seqs remembered)
    "comm_retry_max_attempts": (int, 4),
    "comm_retry_backoff_s": (float, 0.05),
    "comm_retry_backoff_max_s": (float, 2.0),
    "comm_dedup_window": (int, 4096),
    # MQTT subscribe-confirmation retry budget (mqtt_backend.py)
    "mqtt_subscribe_retries": (int, 5),
    "mqtt_subscribe_timeout_s": (float, 6.0),
    # round engine (simulation/round_engine.py)
    # superround_k > 1 runs K rounds per device-program launch under
    # lax.scan with ON-DEVICE client sampling (needs the HBM-resident
    # single-device path; cohort trajectory differs from host sampling
    # except under full participation). 0/1 = off.
    "superround_k": (int, 0),
    # million-client cohort substrate (fedml_tpu/scale/ — docs/scale.md).
    # client_registry: a client count ("1000000" registers N virtual
    # clients over the dataset's shards) or a path to a registry saved
    # with ClientRegistry.save; empty = off (legacy sampling).
    "client_registry": (str, ""),
    # sampled clients per round at registry scale (0 = client_num_per_round
    # capped to the registry). Static per run — never a recompile source.
    "cohort_size": (int, 0),
    # cohorts prefetched ahead of the round (host→HBM double buffering);
    # 0 disables streaming (synchronous gather, same semantics)
    "cohort_prefetch": (int, 1),
    # synthetic-registry sampling-weight skew: Gamma(k) heterogeneous
    # participation propensities; 0 = uniform weights
    "registry_weight_concentration": (float, 0.0),
    # mesh placement rules (scale/partition_rules.py syntax, e.g.
    # "cohort/.*=clients;.*="): cohort-plane and round-state leaf
    # placement; empty = the built-in first-axis/replicated defaults
    "mesh_partition_rules": (str, ""),
    "mesh_state_rules": (str, ""),
    # async traffic plane (fedml_tpu/traffic/ — docs/traffic.md).
    # aggregation_mode: sync keeps the per-round cohort barrier (the
    # reference semantics, bitwise-unchanged); async is FedBuff-style
    # buffered aggregation — staleness-weighted updates fold as they
    # arrive, a server step fires per async_buffer_size accepted updates.
    "aggregation_mode": (str, "sync"),
    # updates per server step (K); 0 = min(10, client count), the FedBuff
    # paper default capped to the world size
    "async_buffer_size": (int, 0),
    # staleness decay exponent: weight = num_samples * (1+s)^-alpha;
    # 0 = flat weights (the sync-parity setting)
    "async_staleness_alpha": (float, 0.0),
    # drop updates staler than this many versions (the sender gets a fresh
    # model so it rejoins at version head); 0 = accept any staleness
    "async_max_staleness": (int, 0),
    # flush a partial buffer after this many seconds without progress so a
    # dropped-out tail cohort can't wedge the federation; 0 = never
    "async_flush_s": (float, 10.0),
    # admission control on C2S_SEND_MODEL: token-bucket rate (updates/s;
    # 0 = unlimited) + burst (0 = 2x buffer) and the bounded fold-queue
    # depth (0 = 4x buffer). Overload degrades to shed/NACK-retry-after.
    "async_admit_rate": (float, 0.0),
    "async_admit_burst": (int, 0),
    "async_queue_limit": (int, 0),
    # delta delivery plane (fedml_tpu/delivery/ — docs/delivery.md).
    # C2S update compression (core/compression.UpdateCodec): "" = off;
    # topk | eftopk | qsgd | quantize, with the scheme knobs below. Deltas
    # decode against the version-indexed model store, so compression now
    # composes with aggregation_mode=async.
    "compression": (str, ""),
    "compression_ratio": (float, 0.1),
    "quantize_bits": (int, 8),
    "qsgd_levels": (int, 256),
    # S2C delta shipping: auto (default — codec-encoded LOSSLESS delta
    # against the client's last-ACKed version whenever that base is still
    # in the store, loud full-frame fallback otherwise) | off
    "s2c_delta": (str, "auto"),
    # which implementation serves delta encode/decode: host (numpy
    # reference), device (jit'd kernels + dlpack emission), or auto
    # (device when JAX is importable). PERFORMANCE knob only — frames are
    # byte-identical across paths, so this is deliberately NOT part of
    # delivery_identity
    "wire_path": (str, "auto"),
    # bounded ring of committed global versions both wire ends keep
    # (VersionedModelStore capacity); also bounds how stale a compressed
    # C2S delta can be and still decode
    "delta_store_versions": (int, 8),
    # adapter-only payloads: regex over named pytree leaves (the
    # scale/partition_rules naming); matching leaves ride the C2S wire,
    # the rest stay frozen at the server's global. "" = full payloads.
    "payload_filter": (str, ""),
    # FedBuff dispatch policy (aggregation_mode=async): sync_on_consume
    # (dispatch to a step's contributors — the FedBuff default) |
    # server_push (push every version bump to all live clients) |
    # client_pull (clients request via c2s_pull_request; the server
    # answers when the version advances)
    "async_dispatch": (str, "sync_on_consume"),
    # gRPC wire format: raw (zero-copy tensor frames, the default) | npz
    # (the self-describing fallback; mixed worlds interoperate — decode
    # sniffs the body magic)
    "grpc_wire_format": (str, "raw"),
    # gRPC rank→port multiplexing: N ranks share one port/server process
    # (port = comm_port + ceil(rank / N)); 1 = legacy port-per-rank
    "grpc_ranks_per_port": (int, 1),
    # survivable serving plane (docs/robustness.md). round_deadline_s
    # closes a sync round after this many seconds with the K' <= K
    # updates that arrived (reweighted exactly — bitwise-equal to
    # full-cohort FedAvg when nobody straggles) and folds LATE arrivals
    # into the current round through the async staleness path
    # ((1+s)^-async_staleness_alpha) instead of discarding them; 0 = off
    # (the legacy round_timeout knob keeps its drop-the-stragglers
    # semantics). min_clients_per_round bounds how small a deadline
    # cohort may get.
    "round_deadline_s": (float, 0.0),
    "min_clients_per_round": (int, 1),
    # client liveness/resync FSM: heartbeat_s > 0 sends a heartbeat
    # lease every interval; heartbeat_miss_limit missed intervals
    # without ANY server traffic declare the connection lost and start
    # the bounded-exponential resync loop (c2s_resync every
    # resync_backoff_s * 2^k, capped, at most resync_max_attempts).
    "heartbeat_s": (float, 0.0),
    "heartbeat_miss_limit": (int, 3),
    "resync_backoff_s": (float, 0.5),
    "resync_backoff_max_s": (float, 10.0),
    "resync_max_attempts": (int, 30),
}

COMPRESSION_SCHEMES = ("", "topk", "eftopk", "qsgd", "quantize")
ASYNC_DISPATCH_POLICIES = ("sync_on_consume", "server_push", "client_pull")


class Arguments:
    """Flat attribute namespace loaded from a sectioned YAML file.

    Reference behavior preserved (arguments.py:62-166): families flattened,
    last key wins, command-line rank/run_id/role merged in. Added: typed
    coercion + defaults from ``_SCHEMA``.
    """

    def __init__(
        self,
        cmd_args: Optional[argparse.Namespace] = None,
        training_type: Optional[str] = None,
        comm_backend: Optional[str] = None,
        overrides: Optional[Dict[str, Any]] = None,
    ):
        # defaults first
        for key, (_, default) in _SCHEMA.items():
            setattr(self, key, default)
        # YAML config, then explicitly passed CLI flags back on top: an
        # absent flag (None) defers to the YAML key, a passed flag wins
        if cmd_args is not None:
            passed = {k: v for k, v in vars(cmd_args).items()
                      if v is not None}
            for k, v in passed.items():
                setattr(self, k, v)
            cf = getattr(cmd_args, "yaml_config_file", None) or getattr(
                cmd_args, "cf", None
            )
            if cf:
                self.load_yaml_config(cf)
                for k, v in passed.items():
                    if k not in ("yaml_config_file", "cf"):
                        self._set_typed(k, v)
        if training_type:
            self.training_type = training_type
        if comm_backend:
            self.backend = comm_backend
        if overrides:
            for k, v in overrides.items():
                self._set_typed(k, v)
        self.validate()

    # -- YAML loading (reference: arguments.py:62-166) ----------------------
    def load_yaml_config(self, yaml_path: str) -> None:
        with open(yaml_path, "r") as f:
            cfg = yaml.safe_load(f) or {}
        self.set_attr_from_config(cfg)
        self.yaml_config_file = yaml_path

    def set_attr_from_config(self, configuration: Dict[str, Any]) -> None:
        for family, family_cfg in configuration.items():
            if isinstance(family_cfg, dict):
                for k, v in family_cfg.items():
                    self._set_typed(k, v)
            else:
                self._set_typed(family, family_cfg)

    def _set_typed(self, key: str, value: Any) -> None:
        if key in _SCHEMA:
            typ, _ = _SCHEMA[key]
            if value is not None and not isinstance(value, typ):
                try:
                    if typ is bool and isinstance(value, str):
                        lowered = value.strip().lower()
                        if lowered in ("1", "true", "yes", "on"):
                            value = True
                        elif lowered in ("0", "false", "no", "off", ""):
                            value = False
                        else:
                            raise ValueError(f"not a boolean: {value!r}")
                    else:
                        value = typ(value)
                except (TypeError, ValueError) as e:
                    raise ValueError(
                        f"config key '{key}' expects {typ.__name__}, got "
                        f"{value!r}: {e}"
                    ) from None
        setattr(self, key, value)

    # -- validation (absent in reference; SURVEY.md §5 flags this gap) ------
    def validate(self) -> None:
        if self.training_type not in (
            constants.FEDML_TRAINING_PLATFORM_SIMULATION,
            constants.FEDML_TRAINING_PLATFORM_CROSS_SILO,
            constants.FEDML_TRAINING_PLATFORM_CROSS_DEVICE,
            constants.FEDML_TRAINING_PLATFORM_DISTRIBUTED,
        ):
            raise ValueError(f"unknown training_type: {self.training_type!r}")
        if (
            self.training_type == constants.FEDML_TRAINING_PLATFORM_SIMULATION
            and self.backend not in constants.SIMULATION_BACKENDS
        ):
            raise ValueError(
                f"simulation backend must be one of {constants.SIMULATION_BACKENDS},"
                f" got {self.backend!r}"
            )
        if self.client_num_per_round > self.client_num_in_total:
            raise ValueError(
                f"client_num_per_round ({self.client_num_per_round}) > "
                f"client_num_in_total ({self.client_num_in_total})"
            )
        if int(getattr(self, "cohort_size", 0) or 0) > 0 and not str(
            getattr(self, "client_registry", "") or ""
        ).strip():
            raise ValueError(
                "cohort_size requires client_registry (the registry defines "
                "the population the cohort is sampled from)"
            )
        if int(getattr(self, "cohort_size", 0) or 0) < 0:
            raise ValueError("cohort_size must be >= 0")
        mode = str(getattr(self, "aggregation_mode", "sync") or "sync")
        if mode.lower() not in ("sync", "async"):
            raise ValueError(
                f"aggregation_mode must be sync|async, got {mode!r}"
            )
        for non_negative in ("async_buffer_size", "async_max_staleness",
                             "async_admit_rate", "async_queue_limit",
                             "async_staleness_alpha", "async_flush_s",
                             "async_admit_burst", "round_deadline_s",
                             "heartbeat_s", "resync_backoff_s",
                             "resync_backoff_max_s", "resync_max_attempts"):
            if float(getattr(self, non_negative, 0) or 0) < 0:
                raise ValueError(f"{non_negative} must be >= 0")
        if float(getattr(self, "tracking_flush_s", 0.5) or 0) < 0:
            raise ValueError("tracking_flush_s must be >= 0")
        sample = float(getattr(self, "trace_sample", 1.0) or 0.0)
        if not 0.0 <= sample <= 1.0:
            raise ValueError(
                f"trace_sample must be in [0, 1], got {sample}")
        # delta delivery plane (docs/delivery.md)
        scheme = str(getattr(self, "compression", "") or "").lower()
        if scheme not in COMPRESSION_SCHEMES:
            raise ValueError(
                f"compression must be one of {COMPRESSION_SCHEMES}, "
                f"got {scheme!r}"
            )
        s2c = str(getattr(self, "s2c_delta", "auto") or "auto").lower()
        if s2c not in ("auto", "off"):
            raise ValueError(f"s2c_delta must be auto|off, got {s2c!r}")
        wire = str(getattr(self, "wire_path", "auto") or "auto").lower()
        if wire not in ("host", "device", "auto"):
            raise ValueError(
                f"wire_path must be host|device|auto, got {wire!r}")
        if int(getattr(self, "delta_store_versions", 8) or 0) < 1:
            raise ValueError("delta_store_versions must be >= 1")
        dispatch = str(
            getattr(self, "async_dispatch", "sync_on_consume")
            or "sync_on_consume").lower()
        if dispatch not in ASYNC_DISPATCH_POLICIES:
            raise ValueError(
                f"async_dispatch must be one of {ASYNC_DISPATCH_POLICIES}, "
                f"got {dispatch!r}"
            )
        if dispatch != "sync_on_consume" and mode.lower() != "async":
            raise ValueError(
                f"async_dispatch={dispatch} is a FedBuff dispatch policy — "
                "it requires aggregation_mode=async"
            )
        pattern = str(getattr(self, "payload_filter", "") or "")
        if pattern:
            import re as _re

            try:
                _re.compile(pattern)
            except _re.error as e:
                raise ValueError(
                    f"bad payload_filter regex {pattern!r}: {e}") from None
        if str(getattr(self, "grpc_wire_format", "raw")).lower() not in (
                "raw", "npz"):
            raise ValueError(
                f"grpc_wire_format must be raw|npz, got "
                f"{getattr(self, 'grpc_wire_format')!r}"
            )
        if int(getattr(self, "grpc_ranks_per_port", 1) or 1) < 1:
            raise ValueError("grpc_ranks_per_port must be >= 1")
        for positive in ("batch_size", "comm_round", "epochs"):
            if getattr(self, positive) <= 0:
                raise ValueError(f"{positive} must be positive")

    # -- misc ---------------------------------------------------------------
    def get(self, key: str, default: Any = None) -> Any:
        return getattr(self, key, default)

    def to_dict(self) -> Dict[str, Any]:
        return {k: v for k, v in self.__dict__.items() if not k.startswith("_")}

    def __repr__(self) -> str:  # pragma: no cover
        return f"Arguments({self.to_dict()!r})"

    def parse_mesh_shape(self) -> Dict[str, int]:
        """Parse ``mesh_shape`` like ``"data:2,tensor:4"`` into an ordered dict."""
        return parse_mesh_shape(self.mesh_shape)


def parse_mesh_shape(value) -> Dict[str, int]:
    """The one parser for ``"axis:size,..."`` mesh strings (Arguments method
    and bare-namespace callers like ``cross_silo/fedllm.py`` share it)."""
    out: Dict[str, int] = {}
    if not value:
        return out
    for part in str(value).split(","):
        name, _, size = part.strip().partition(":")
        if not name or not size or not (size.lstrip("-").isdigit()):
            raise ValueError(
                f"bad mesh_shape entry {part!r}; expected 'axis:size'"
            )
        out[name] = int(size)
    return out


def add_args() -> argparse.Namespace:
    """CLI surface matching the reference (arguments.py:33-59)."""
    parser = argparse.ArgumentParser(description="fedml_tpu")
    parser.add_argument(
        "--yaml_config_file", "--cf", type=str, default="", help="yaml config file"
    )
    # defaults None throughout: _SCHEMA supplies the real defaults, and a
    # None means "not passed" so YAML keys win only for absent flags (an
    # explicitly passed flag beats YAML — see Arguments.__init__)
    parser.add_argument("--run_id", type=str, default=None)
    parser.add_argument("--rank", type=int, default=None)
    parser.add_argument("--local_rank", type=int, default=None)
    parser.add_argument("--node_rank", type=int, default=None)
    parser.add_argument("--role", type=str, default=None)
    parser.add_argument(
        "--silo_device_indices", type=int, nargs="*", default=None,
        help="chips this silo trains over (intra-silo data parallelism)",
    )
    # crash-safe rounds (core/runstate.py)
    parser.add_argument(
        "--checkpoint_dir", type=str, default=None,
        help="Orbax checkpoint + run-ledger dir; enables round resume and "
        "the SIGTERM/SIGINT drain-and-commit handler",
    )
    parser.add_argument(
        "--checkpoint_rounds", type=int, default=None, metavar="N",
        help="commit a checkpoint + ledger entry every N rounds",
    )
    parser.add_argument(
        "--resume", type=str, default=None,
        choices=("auto", "never", "require"),
        help="what an existing checkpoint means at startup: auto resumes "
        "when present, never demands a fresh dir, require errors without one",
    )
    # million-client cohort substrate (fedml_tpu/scale/ — docs/scale.md)
    parser.add_argument(
        "--client_registry", type=str, default=None, metavar="N|PATH",
        help="register N virtual clients over the dataset's shards (or "
        "load a saved ClientRegistry npz); cohorts sample K-of-N on device",
    )
    parser.add_argument(
        "--cohort_size", type=int, default=None, metavar="K",
        help="clients sampled per round from the registry "
        "(0 = client_num_per_round)",
    )
    parser.add_argument(
        "--cohort_prefetch", type=int, default=None, metavar="D",
        help="cohorts prefetched ahead of the round (0 disables streaming)",
    )
    parser.add_argument(
        "--mesh_partition_rules", type=str, default=None,
        help="regex=axes;... placement rules for the mesh cohort plane "
        "(docs/scale.md)",
    )
    parser.add_argument(
        "--mesh_state_rules", type=str, default=None,
        help="regex=axes;... placement rules for the mesh round state "
        "(docs/scale.md)",
    )
    # survivable serving plane (docs/robustness.md)
    parser.add_argument(
        "--round_deadline_s", type=float, default=None, metavar="S",
        help="close a sync round after S seconds with the K' <= K updates "
        "that arrived (reweighted exactly); late stragglers fold into the "
        "open round via the staleness path instead of being dropped",
    )
    parser.add_argument(
        "--heartbeat_s", type=float, default=None, metavar="S",
        help="client heartbeat/lease interval; silence past "
        "heartbeat_miss_limit intervals enters the bounded-exponential "
        "resync loop (0 = liveness plane off)",
    )
    parser.add_argument(
        "--min_clients_per_round", type=int, default=None, metavar="K",
        help="smallest cohort a round deadline may close with",
    )
    # async traffic plane (fedml_tpu/traffic/ — docs/traffic.md)
    parser.add_argument(
        "--aggregation_mode", type=str, default=None,
        choices=("sync", "async"),
        help="sync = per-round cohort barrier (reference semantics); "
        "async = FedBuff-style buffered aggregation with staleness "
        "weighting and admission control",
    )
    parser.add_argument(
        "--async_buffer_size", type=int, default=None, metavar="K",
        help="server step fires per K accepted updates "
        "(0 = min(10, client count))",
    )
    parser.add_argument(
        "--async_staleness_alpha", type=float, default=None,
        help="staleness decay exponent: weight = n * (1+s)^-alpha "
        "(0 = flat weights)",
    )
    parser.add_argument(
        "--async_max_staleness", type=int, default=None,
        help="drop updates staler than this many versions (0 = unlimited)",
    )
    parser.add_argument(
        "--async_flush_s", type=float, default=None,
        help="flush a partial async buffer after this stall (0 = never)",
    )
    parser.add_argument(
        "--async_admit_rate", type=float, default=None,
        help="token-bucket admission rate on C2S_SEND_MODEL, updates/s "
        "(0 = unlimited)",
    )
    parser.add_argument(
        "--async_admit_burst", type=int, default=None,
        help="token-bucket burst (0 = 2x buffer size)",
    )
    parser.add_argument(
        "--async_queue_limit", type=int, default=None,
        help="bounded fold-queue depth; overflow is shed with retry-after "
        "(0 = 4x buffer size)",
    )
    # delta delivery plane (fedml_tpu/delivery/ — docs/delivery.md)
    parser.add_argument(
        "--compression", type=str, default=None,
        choices=("", "topk", "eftopk", "qsgd", "quantize"),
        help="C2S update compression scheme; deltas decode against the "
        "version-indexed model store (composes with async aggregation)",
    )
    parser.add_argument(
        "--compression_ratio", type=float, default=None,
        help="top-k fraction kept by topk/eftopk",
    )
    parser.add_argument(
        "--quantize_bits", type=int, default=None,
        help="bit width for --compression quantize",
    )
    parser.add_argument(
        "--qsgd_levels", type=int, default=None,
        help="quantization levels for --compression qsgd",
    )
    parser.add_argument(
        "--s2c_delta", type=str, default=None, choices=("auto", "off"),
        help="S2C sync frames: auto ships a lossless delta against the "
        "client's last-ACKed version (full-frame fallback on store "
        "eviction); off always broadcasts full models",
    )
    parser.add_argument(
        "--wire_path", type=str, default=None,
        choices=("host", "device", "auto"),
        help="delta codec implementation: host (numpy reference), device "
        "(jit'd kernels, zero-copy emission), auto (device when JAX is "
        "available); frames are byte-identical either way",
    )
    parser.add_argument(
        "--delta_store_versions", type=int, default=None, metavar="V",
        help="committed global versions each wire end keeps for delta "
        "encode/decode (the VersionedModelStore ring size)",
    )
    parser.add_argument(
        "--payload_filter", type=str, default=None, metavar="REGEX",
        help="adapter-only payloads: leaves whose a/b/c path matches ride "
        "the C2S wire, the rest stay frozen at the server's global",
    )
    parser.add_argument(
        "--async_dispatch", type=str, default=None,
        choices=("sync_on_consume", "server_push", "client_pull"),
        help="FedBuff dispatch policy for aggregation_mode=async",
    )
    parser.add_argument(
        "--grpc_wire_format", type=str, default=None, choices=("raw", "npz"),
        help="gRPC frame format: raw zero-copy tensor frames (default) or "
        "the npz fallback",
    )
    parser.add_argument(
        "--grpc_ranks_per_port", type=int, default=None, metavar="N",
        help="gRPC rank multiplexing: N ranks share one port/server "
        "(1 = legacy port-per-rank)",
    )
    # telemetry plane (defaults None so YAML keys win when the flag is absent)
    parser.add_argument(
        "--enable_tracking", action="store_true", default=None,
        help="emit JSONL events + per-round telemetry RoundRecords",
    )
    parser.add_argument(
        "--tracking_dir", type=str, default=None,
        help="JSONL event sink directory (default .fedml_tpu_runs)",
    )
    parser.add_argument(
        "--metrics_file", type=str, default=None,
        help="write the metrics registry as Prometheus text exposition here",
    )
    parser.add_argument(
        "--profile_rounds", type=str, default=None, metavar="N:M",
        help="open a jax.profiler trace window over rounds [N, M)",
    )
    parser.add_argument(
        "--profile_dir", type=str, default=None,
        help="profiler trace output dir (default: tracking dir)",
    )
    parser.add_argument(
        "--sys_perf_interval_s", type=float, default=None,
        help="sample host CPU/RSS + HBM every N seconds (0 = off)",
    )
    parser.add_argument(
        "--tracking_flush_s", type=float, default=None, metavar="S",
        help="write-behind JSONL sink drain interval (0 = per-event)",
    )
    parser.add_argument(
        "--enable_tracing", action="store_true", default=None,
        help="cross-process causal spans + crash flight recorder "
        "(docs/tracing.md); implies a JSONL sink for span records",
    )
    parser.add_argument(
        "--trace_sample", type=float, default=None, metavar="P",
        help="deterministic per-round trace sampling probability in [0,1]",
    )
    parser.add_argument(
        "--trace_dir", type=str, default=None,
        help="flight-recorder post-mortem dir (default: tracking dir)",
    )
    args, _ = parser.parse_known_args()
    return args


def load_arguments(
    training_type: Optional[str] = None, comm_backend: Optional[str] = None
) -> Arguments:
    cmd_args = add_args()
    return Arguments(cmd_args, training_type, comm_backend)
