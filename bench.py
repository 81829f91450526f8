"""Headline benchmark — run on real TPU by the driver each round.

Measurements (one cumulative JSON line, re-printed as legs complete):

1. **Parrot FedAvg rounds/sec** (BASELINE.json north star #1): 100 simulated
   clients on CIFAR-10-shaped data, ResNet-56, 10 clients/round, 1 local
   epoch. ``vs_baseline`` divides by the *measured* throughput of the
   reference's own single-process torch loop on the same config
   (``tools/measure_ref_baseline.py`` → ``REF_BASELINE.json``). ResNet-56 is
   used on both sides because it is the reference's CIFAR ResNet
   (``model/cv/resnet.py:257`` — it ships no resnet20).

2. **Cheetah tokens/sec/chip + MFU** (north star #2): single-chip pretraining
   of the flagship decoder-only transformer (~490M params: d2048 x 8L, GQA
   16q/4kv — Llama-standard head_dim 128 — seq 2048, bf16, native-GQA splash
   attention with (512, 512) blocks, chunked fused CE; a remat ladder falls
   back only if no-remat doesn't fit). MFU = achieved model FLOPs/s over
   chip peak bf16 FLOPs/s, with model FLOPs per token = 6·N +
   12·L·layers·d_model (PaLM appendix B convention). Three secondary shapes
   ride along: the r2 wide-head hd512 flagship, the remat-on rung
   (d2048 x 24L, full-block remat — the regime every 7B-class run lives in),
   and the MoE flagship (8 experts, top-2, MFU on ACTIVE FLOPs).

Stall-proofing (round 5 — VERDICT r4 #1; r4 recorded rc=124 and NOTHING):

- The parent process NEVER imports jax (a chip belongs to one process at a
  time). Every measurement runs in its own subprocess leg with its own
  timeout; a wedged backend costs one leg, not the round.
- After EVERY completed leg the parent re-prints the full cumulative JSON
  line, so an external kill at any moment leaves the most complete line as
  the output tail (the driver parses the tail).
- A global deadline (env ``BENCH_BUDGET_S``, default 2400) skips remaining
  legs with explicit ``"<leg>_skipped": "budget"`` markers instead of dying
  with rc=124.
- Completed TPU legs are checkpointed to ``BENCH_PARTIAL.json`` keyed by a
  digest of the leg config + the source files that produce the number; a
  later run reuses any matching row younger than ``BENCH_CACHE_TTL_S``
  (default 7 days). A bench run earlier in the round therefore insures the
  driver's end-of-round run against a slow backend: cached legs are merged
  in milliseconds and marked ``"<leg>_cached": true``.

Timed sections end in ``jax.block_until_ready`` (checked against a fetched
scalar on the v5e by ``chip_smoke.py``: the two agree). Off-TPU driving uses
plain ``JAX_PLATFORMS=cpu``.
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
PARTIAL_PATH = os.path.join(HERE, "BENCH_PARTIAL.json")

# ---------------------------------------------------------------------------
# Leg configs — module-level so the parent can hash them without importing
# jax and the children run exactly what was hashed.
# ---------------------------------------------------------------------------

FEDAVG_OVERRIDES = dict(
    dataset="cifar10", model="resnet56", client_num_in_total=100,
    client_num_per_round=10, comm_round=12, epochs=1, batch_size=32,
    learning_rate=0.1, frequency_of_the_test=1000,
)

# Million-client cohort leg (fedml_tpu/scale/ — ROADMAP "Million-client
# simulation substrate"): N registered clients in a packed registry,
# 10k-client cohorts sampled K-of-N on device, shards streamed through the
# double-buffered prefetcher. Deliberately CPU-runnable (lr on synthetic
# shapes): the leg measures the SUBSTRATE — rounds/s at population scale,
# prefetch overlap fraction, and zero cohort-driven recompiles in steady
# state — not model FLOPs. BENCH_REGISTRY_N / BENCH_COHORT_K scale it down
# for smoke runs.
MILLION_OVERRIDES = dict(
    dataset="synthetic", model="lr", client_num_in_total=64,
    client_num_per_round=16, comm_round=16, epochs=1, batch_size=8,
    learning_rate=0.05, frequency_of_the_test=1000,
)
MILLION_REGISTRY_N = 1_000_000
MILLION_COHORT_K = 10_000

# Delta-delivery leg (fedml_tpu/delivery/ — ISSUE 9, docs/delivery.md):
# the SAME cross-silo federation twice — full pytrees vs the delta plane
# (EF-top-k C2S deltas decoded against the version store + lossless sparse
# S2C delta frames) — and reports steady-state comm bytes per round for
# both, the reduction factor, and accuracy at parity. mnist-lr is the
# deliberate shape: big enough (7,850 params, ~31 KB/frame) that frame
# headers don't dominate, small enough to run in seconds on a CPU host.
COMPRESSED_OVERRIDES = dict(
    training_type="cross_silo", dataset="mnist", model="lr",
    client_num_in_total=4, client_num_per_round=4, epochs=1, batch_size=32,
    learning_rate=0.05, backend="LOOPBACK", frequency_of_the_test=1,
    random_seed=0,
)
COMPRESSED_SCHEME = dict(compression="eftopk", compression_ratio=0.01)

# Device-direct wire leg (fedml_tpu/delivery/device_codec.py — docs/
# delivery.md "Device-direct wire path"): host-CPU cost of putting one S2C
# frame on the wire, full vs host-delta vs device-delta, at a frame size
# where per-call overhead vanishes (~16 MB fp32). "Host CPU" is SERVING-
# THREAD CPU time (``time.thread_time``): the resource the device path
# frees — jit'd kernels run off the serving thread (off-host entirely on
# TPU; on the CPU backend they land in XLA's pool, so wall time there is
# a stand-in, flagged by ``platform``). The parity gate is absolute: the
# device frames must be byte-identical to the host codec's before any
# timing is believed. BENCH_WIRE_DIM / BENCH_WIRE_REPS scale it down for
# smoke runs.
WIRE_DIM = 4_000_000
WIRE_CHANGED_FRAC = 0.01  # steady-state sparse-ish round delta
WIRE_SOAK = dict(clients=8, steps=3, think_s=0.01, seed=7)

# The flagship is the PRODUCT shape: Llama-standard head_dim 128 with GQA
# 16q/4kv on a wide-shallow d2048 x 8L body — chosen product-shape-first,
# not max-MFU-first. Two levers got it to 75.7% MFU on the v5e
# (tools/mfu_sweep.py): wide-shallow beats deep-narrow (~2.1x the MFU of
# d1024x24), and native-GQA splash attention (make_splash_mqa — K/V never
# repeated to 16 heads) with explicit (512, 512) kernel blocks: 42% -> 75.7%.
CHEETAH_BASE = dict(
    vocab_size=32000, d_model=2048, n_layers=8, n_heads=16,
    n_kv_heads=4, d_ff=5632, max_seq_len=2048,
    attn_block_q=512, attn_block_kv=512,
)
# memory/recompute ladder, fastest first: no-remat needs the most HBM;
# "dots" saves matmul outputs only; full-block remat always fits
CHEETAH_LADDER = [
    dict(remat=False),
    dict(remat=True, remat_policy="dots"),
    dict(remat=True, remat_policy="full"),
]
CHEETAH_RUN = dict(batch=8, seq=2048, steps=20, warmup=3)

HD512_CFG = dict(
    vocab_size=32000, d_model=2048, n_layers=8, n_heads=4,
    n_kv_heads=2, d_ff=5632, max_seq_len=2048, remat=False,
    remat_policy="full", attn_impl="auto", batch=8, seq=2048,
    steps=10, loss_chunk=256, mu_bf16=True,
    attn_block_q=512, attn_block_kv=512,  # clamped; 79.4% measured
)

# The remat-on MFU rung: d2048 x 24L (1.21B — the flagship deepened past the
# no-remat HBM wall; 24L no-remat OOMs at bs8/seq2048, measured) with
# remat_policy="full". "full" (save block inputs only) wins here — measured,
# "dots" SAVES every matmul output and needs MORE HBM than no-remat once
# splash attention keeps scores out of HBM.
REMAT_CFG = dict(
    vocab_size=32000, d_model=2048, n_layers=24, n_heads=16,
    n_kv_heads=4, d_ff=5632, max_seq_len=2048, remat=True,
    remat_policy="full", attn_impl="auto", batch=8, seq=2048,
    steps=8, loss_chunk=256, mu_bf16=True,
    attn_block_q=512, attn_block_kv=512,
)

# MoE flagship: 8 experts, top-2, sort-based grouped dispatch
# (parallel/moe.py). MFU is reported on ACTIVE FLOPs (top_k/E of expert FFN
# params per token — the standard MoE convention).
MOE_CFG = dict(
    vocab_size=32000, d_model=2048, n_layers=4, n_heads=16,
    n_kv_heads=4, d_ff=2816, max_seq_len=2048, remat=True,
    remat_policy="full", attn_impl="auto", batch=8, seq=2048,
    steps=8, loss_chunk=256, mu_bf16=True,
    attn_block_q=512, attn_block_kv=512,
    moe_experts=8, moe_top_k=2, moe_capacity_factor=1.25,
)

# source files whose content feeds each leg's cache digest: editing the
# engine invalidates the cached number
_CHEETAH_SOURCES = [
    "fedml_tpu/parallel/transformer.py", "fedml_tpu/parallel/train_step.py",
    "fedml_tpu/parallel/sharding.py", "fedml_tpu/parallel/ring_attention.py",
    "fedml_tpu/parallel/moe.py", "tools/mfu_sweep.py", "bench.py",
]
_FEDAVG_SOURCES = [
    "fedml_tpu/simulation/sp_api.py", "fedml_tpu/simulation/round_engine.py",
    "fedml_tpu/ml/local_train.py", "fedml_tpu/core/mlops/telemetry.py",
    "fedml_tpu/models/vision.py", "fedml_tpu/data/datasets.py", "bench.py",
]
_MILLION_SOURCES = [
    "fedml_tpu/scale/registry.py", "fedml_tpu/scale/cohort_engine.py",
    "fedml_tpu/scale/prefetch.py", "fedml_tpu/simulation/sp_api.py",
    "fedml_tpu/simulation/round_engine.py", "bench.py",
]
_COMPRESSED_SOURCES = [
    "fedml_tpu/delivery/model_store.py", "fedml_tpu/delivery/delta_codec.py",
    "fedml_tpu/delivery/device_codec.py", "fedml_tpu/core/compression.py",
    "fedml_tpu/cross_silo/server_manager.py",
    "fedml_tpu/cross_silo/client_manager.py",
    "fedml_tpu/core/distributed/message.py", "bench.py",
]
_WIRE_SOURCES = [
    "fedml_tpu/delivery/device_codec.py", "fedml_tpu/delivery/delta_codec.py",
    "fedml_tpu/delivery/model_store.py",
    "fedml_tpu/core/distributed/tensor_transport.py",
    "fedml_tpu/traffic/swarm.py", "bench.py",
]


def _ref_rounds_per_sec() -> float | None:
    """Measured reference throughput (tools/measure_ref_baseline.py)."""
    path = os.path.join(HERE, "REF_BASELINE.json")
    try:
        with open(path) as f:
            return float(json.load(f)["ref_rounds_per_sec"])
    except (OSError, KeyError, ValueError):
        return None


def _same_substrate() -> dict:
    """Both-stacks-on-CPU measurement (tools/measure_same_substrate.py):
    the ratio isolating architecture from hardware."""
    path = os.path.join(HERE, "SELF_CPU_BASELINE.json")
    try:
        with open(path) as f:
            d = json.load(f)
        out = {
            "vs_baseline_same_substrate": d.get("same_substrate_ratio"),
            "same_substrate_config": d.get("config"),
        }
        legs = d.get("legs")
        if legs:
            out["same_substrate_legs"] = {
                m: leg.get("same_substrate_ratio") for m, leg in legs.items()
            }
        return out
    except (OSError, ValueError):
        return {"vs_baseline_same_substrate": None}


# ---------------------------------------------------------------------------
# Leg children (run in subprocesses; print one JSON line on stdout)
# ---------------------------------------------------------------------------


def bench_fedavg() -> dict:
    """Headline FedAvg leg, on the fused round engine (round_engine.py).

    Reports the compile wall SEPARATELY from steady-state throughput:
    ``fedavg_compile_s`` is the first-round wall time (lowering + XLA compile
    + the round itself), ``rounds_per_sec`` is measured over post-warmup
    rounds only. ``fedml.init`` places the persistent XLA compilation cache
    (``device.enable_compilation_cache``), so repeat runs skip the compile
    wall and ``fedavg_compile_s`` collapses to deserialization time.
    """
    import jax

    import fedml_tpu as fedml
    from fedml_tpu import data as data_mod
    from fedml_tpu import models as model_mod
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.mlops import telemetry
    from fedml_tpu.simulation.sp_api import FedAvgAPI

    # count compiles + compilation-cache hits from the very first jit, so
    # the telemetry the leg reports covers the compile wall too
    telemetry.install_jax_listeners()

    platform = jax.devices()[0].platform
    if platform == "tpu":
        overrides = dict(FEDAVG_OVERRIDES)
        n_rounds, warmup = 10, 2
    elif os.environ.get("BENCH_SMOKE"):
        # harness smoke (tools/bench_smoke.sh): a seconds-scale synthetic
        # 2-round leg proving the orchestrator never regresses to rc=124
        overrides = dict(
            dataset="synthetic", model="lr", client_num_in_total=8,
            client_num_per_round=4, comm_round=3, epochs=1, batch_size=16,
            learning_rate=0.03, frequency_of_the_test=1000,
        )
        n_rounds, warmup = 2, 1
    else:
        # XLA:CPU lowers the vmapped ResNet grouped-conv path pathologically
        # (>60 min compiles — SELF_CPU_BASELINE.json); off-TPU the leg runs a
        # seconds-scale LR smoke so the bench degrades instead of wedging.
        # The parent marks it and suppresses vs_baseline (different config).
        overrides = dict(
            dataset="mnist", model="lr", client_num_in_total=10,
            client_num_per_round=4, comm_round=6, epochs=1, batch_size=32,
            learning_rate=0.03, frequency_of_the_test=1000,
        )
        n_rounds, warmup = 4, 1
    args = Arguments(overrides=overrides)
    args.train_dtype = "bf16"  # MXU-native compute, fp32 master weights
    # superround: n_rounds rounds per device-program launch (lax.scan with
    # on-device client sampling) — steady state is bounded by device
    # compute, not Python dispatch. Falls back to per-round launches on
    # configs that can't scan (run_rounds handles both).
    args.superround_k = n_rounds
    args = fedml.init(args, should_init_logs=False)
    ds, output_dim = data_mod.load(args)
    bundle = model_mod.create(args, output_dim)
    api = FedAvgAPI(args, fedml.get_device(args), ds, bundle)

    t0 = time.perf_counter()
    args.round_idx = 0
    api.run_rounds(0, n_rounds)  # compile wall + first launch
    # global params depend on every round in flight
    jax.block_until_ready(api.global_params)
    compile_s = time.perf_counter() - t0
    for w in range(1, warmup):
        api.run_rounds(w * n_rounds, n_rounds)
    jax.block_until_ready(api.global_params)

    t0 = time.perf_counter()
    api.run_rounds(warmup * n_rounds, n_rounds)
    jax.block_until_ready(api.global_params)
    dt = time.perf_counter() - t0

    # tracked pass (telemetry plane): runs AFTER the timed window so
    # tracking can never tax the steady-state number. One RoundRecord per
    # round supplies the per-phase breakdown BENCH_*.json carries; the
    # JSONL log + metrics exposition land in BENCH_TRACKING_DIR when set
    # (tools/bench_smoke.sh asserts both parse), a temp dir otherwise.
    import tempfile

    from fedml_tpu.core import mlops

    track_dir = (os.environ.get("BENCH_TRACKING_DIR")
                 or tempfile.mkdtemp(prefix="fedml_bench_track_"))
    args.enable_tracking = True
    args.tracking_dir = track_dir
    # pid-unique run id: a persistent BENCH_TRACKING_DIR must not append
    # this run's records onto a previous run's JSONL (read_events would
    # then sum stale rounds into the phase breakdown)
    args.run_id = f"bench_fedavg_{os.getpid()}"
    args.metrics_file = os.path.join(track_dir, "metrics.prom")
    mlops.init(args)
    t0 = time.perf_counter()
    api.run_rounds((warmup + 1) * n_rounds, n_rounds)
    tracked_wall = time.perf_counter() - t0
    phases, n_records = mlops.phase_totals(mlops.read_events())
    counters = telemetry.registry().snapshot()["counters"]
    mlops.close()  # emits the telemetry summary + forces the metrics file

    # optional resume-overhead probe (BENCH_RESUME=1; on by default in the
    # smoke config): train a short checkpointed run, then measure the time
    # from "process restart" (fresh engine construction) to the first
    # post-resume round DISPATCH — the number that makes checkpoint-cadence
    # tuning data-driven (core/runstate.py resume path)
    resume_overhead_s = None
    want_resume = os.environ.get(
        "BENCH_RESUME", "1" if os.environ.get("BENCH_SMOKE") else "0"
    ) == "1"
    if want_resume:
        from fedml_tpu.checkpoint import CheckpointManager

        import shutil

        ckpt_dir = tempfile.mkdtemp(prefix="fedml_bench_resume_")
        try:
            # preempt_signals=False: the probe must not install the
            # process-wide SIGTERM/SIGINT latch — the operator's Ctrl-C has
            # to keep killing the remaining bench legs
            args_r = Arguments(overrides=dict(
                overrides, checkpoint_dir=ckpt_dir, checkpoint_rounds=1,
                comm_round=2, superround_k=0, preempt_signals=False,
            ))
            args_r = fedml.init(args_r, should_init_logs=False)
            FedAvgAPI(args_r, fedml.get_device(args_r), ds, bundle).train()
            t0 = time.perf_counter()
            api_r = FedAvgAPI(args_r, fedml.get_device(args_r), ds, bundle)
            ckpt_r = CheckpointManager(ckpt_dir)
            start = api_r._maybe_resume(ckpt_r)
            args_r.round_idx = start
            api_r.run_round(start)  # returns at dispatch, not at ready
            resume_overhead_s = time.perf_counter() - t0
            ckpt_r.close()
        finally:
            shutil.rmtree(ckpt_dir, ignore_errors=True)

    return {
        **({"fedavg_resume_overhead_s": round(resume_overhead_s, 4)}
           if resume_overhead_s is not None else {}),
        "rounds_per_sec": n_rounds / dt,
        "fedavg_compile_s": round(compile_s, 3),
        "fedavg_round_fused": api._round_step is not None,
        "fedavg_superround_k": api._superround_k or 0,
        "fedavg_phases": {k: round(v, 4) for k, v in phases.items()},
        "fedavg_phase_rounds": n_records,
        "fedavg_tracked_wall_s": round(tracked_wall, 4),
        "fedavg_compile_cache_hits": int(
            counters.get("jax.compilation_cache.hits", 0)),
        "fedavg_compile_cache_misses": int(
            counters.get("jax.compilation_cache.misses", 0)),
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
    }


def bench_million_client() -> dict:
    """FedAvg over a million-client registry with 10k-client streamed
    cohorts (fedml_tpu/scale/). Headline numbers:

    - ``million_rounds_per_sec`` — steady-state rounds/s with N registered
      clients and K-client cohorts streaming through the prefetcher;
    - ``million_prefetch_overlap`` — fraction of shard-gather time hidden
      behind device compute over the measured window (>0 required: the
      pipeline must actually overlap, not serialize);
    - ``million_steady_compiles`` — XLA compiles during the measured
      window (must be 0: cohort resampling every round is recompile-free
      by construction — pad-to-bucket static shapes + jit'd K-of-N
      sampling with a traced round index).
    """
    import numpy as np  # noqa: F401  (jax init ordering)

    import jax

    import fedml_tpu as fedml
    from fedml_tpu import data as data_mod
    from fedml_tpu import models as model_mod
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.mlops import telemetry
    from fedml_tpu.simulation.sp_api import FedAvgAPI

    # count compiles from the very first jit so the steady-state window's
    # delta is trustworthy
    telemetry.install_jax_listeners()

    n = int(os.environ.get("BENCH_REGISTRY_N", MILLION_REGISTRY_N))
    k = int(os.environ.get("BENCH_COHORT_K", MILLION_COHORT_K))
    warmup, measured = 2, 6
    args = Arguments(overrides=dict(
        MILLION_OVERRIDES, client_registry=str(n), cohort_size=k,
        cohort_prefetch=1,
    ))
    args = fedml.init(args, should_init_logs=False)
    ds, output_dim = data_mod.load(args)
    bundle = model_mod.create(args, output_dim)
    api = FedAvgAPI(args, fedml.get_device(args), ds, bundle)

    t0 = time.perf_counter()
    args.round_idx = 0
    for r in range(warmup):
        api.run_round(r)
    jax.block_until_ready(api.global_params)
    compile_s = time.perf_counter() - t0

    reg = telemetry.registry()
    compiles0 = reg.counter("jax.compiles")
    pf0 = api.cohort_engine.stats()
    t0 = time.perf_counter()
    for r in range(warmup, warmup + measured):
        api.run_round(r)
    jax.block_until_ready(api.global_params)
    dt = time.perf_counter() - t0
    steady_compiles = reg.counter("jax.compiles") - compiles0
    pf1 = api.cohort_engine.stats()
    api.cohort_engine.close()

    win_gather = pf1["gather_s"] - pf0["gather_s"]
    win_wait = pf1["wait_s"] - pf0["wait_s"]
    overlap = (
        max(0.0, min(1.0, 1.0 - win_wait / win_gather))
        if win_gather > 1e-12 else 0.0
    )
    return {
        "million_rounds_per_sec": round(measured / dt, 4),
        "million_registry_n": n,
        "million_cohort_k": k,
        "million_prefetch_overlap": round(overlap, 4),
        "million_prefetch_gather_s": round(win_gather, 4),
        "million_prefetch_wait_s": round(win_wait, 4),
        "million_steady_compiles": int(steady_compiles),
        "million_compile_s": round(compile_s, 3),
        "million_round_fused": api._round_step is not None,
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
    }


def bench_compressed_round() -> dict:
    """Delta-delivery leg (ISSUE 9): steady-state ``comm.bytes`` per round,
    full pytrees vs the delta plane, at parity accuracy.

    Per-round bytes are measured MARGINALLY — each config runs a short and
    a long federation and reports ``(bytes_long − bytes_short) / Δrounds``
    — so the INIT/FINISH full-model frames (identical in both configs)
    cancel instead of diluting the reduction factor. The acceptance gate
    (``tools/bench_smoke.sh``): the delta path engages (S2C delta frames +
    C2S delta decodes both nonzero) and bytes drop ≥10x with final
    accuracy within 0.05 of the uncompressed run.
    """
    import threading

    import jax

    import fedml_tpu as fedml
    from fedml_tpu import data as data_mod
    from fedml_tpu import models as model_mod
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.mlops import telemetry

    def run_world(run_id, rounds, extra):
        from fedml_tpu.cross_silo import (
            FedMLCrossSiloClient,
            FedMLCrossSiloServer,
        )

        def mk(role, rank=0):
            over = dict(COMPRESSED_OVERRIDES, comm_round=rounds, role=role,
                        rank=rank, run_id=run_id, **extra)
            return fedml.init(Arguments(overrides=over),
                              should_init_logs=False)

        args_s = mk("server")
        ds, od = data_mod.load(args_s)
        bundle = model_mod.create(args_s, od)
        server = FedMLCrossSiloServer(args_s, None, ds, bundle)
        n = int(COMPRESSED_OVERRIDES["client_num_in_total"])
        clients = [FedMLCrossSiloClient(mk("client", r), None, ds, bundle)
                   for r in range(1, n + 1)]
        threads = [threading.Thread(target=c.run, daemon=True)
                   for c in clients]
        for t in threads:
            t.start()
        result = server.run()
        for t in threads:
            t.join(timeout=60)
        return result

    reg = telemetry.registry()
    short_r, long_r = 2, 10
    per_round, accs = {}, {}
    for tag, extra in (("uncompressed", dict(compression="", s2c_delta="off")),
                       ("compressed", dict(COMPRESSED_SCHEME))):
        b0 = reg.counter("comm.bytes_sent")
        run_world(f"bench-delta-{tag}-short-{os.getpid()}", short_r, extra)
        b_short = reg.counter("comm.bytes_sent") - b0
        b1 = reg.counter("comm.bytes_sent")
        res = run_world(f"bench-delta-{tag}-long-{os.getpid()}", long_r,
                        extra)
        b_long = reg.counter("comm.bytes_sent") - b1
        per_round[tag] = (b_long - b_short) / float(long_r - short_r)
        accs[tag] = float(res["test_acc"]) if res else 0.0

    counters = reg.snapshot()["counters"]
    reduction = (per_round["uncompressed"] / per_round["compressed"]
                 if per_round["compressed"] else 0.0)
    return {
        "compressed_bytes_per_round": round(per_round["compressed"], 1),
        "uncompressed_bytes_per_round": round(per_round["uncompressed"], 1),
        "compressed_reduction_x": round(reduction, 2),
        "compressed_acc": round(accs["compressed"], 4),
        "uncompressed_acc": round(accs["uncompressed"], 4),
        "compressed_scheme": "{compression}@{compression_ratio}".format(
            **COMPRESSED_SCHEME),
        "compressed_s2c_delta_frames": int(
            counters.get("comm.delta.s2c_delta_frames", 0)),
        "compressed_c2s_delta_decodes": int(
            counters.get("comm.delta.c2s_delta_decodes", 0)),
        "compressed_s2c_bytes_saved": int(
            counters.get("comm.delta.s2c_bytes_saved", 0)),
        "compressed_c2s_bytes_saved": int(
            counters.get("comm.delta.c2s_bytes_saved", 0)),
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
    }


def bench_fedavg_wire() -> dict:
    """Device-direct wire leg: serving-thread CPU s/MB to emit one S2C
    frame, full vs host-delta vs device-delta (see WIRE_DIM comment).

    Three parts, strict order: (1) the PARITY GATE — device frames must be
    byte-identical to the host codec's at the bench dim, or the leg raises
    and no number is reported; (2) the codec timing at WIRE_DIM; (3) an
    engagement proof — a short loopback swarm soak with ``--wire_path
    device`` whose report must show nonzero device encodes/decodes and
    zero host fallbacks.
    """
    import argparse

    import numpy as np

    import jax
    import jax.numpy as jnp

    from fedml_tpu.core.distributed.tensor_transport import encode_frames
    from fedml_tpu.delivery import DeltaCodec, WireCodec

    dim = int(os.environ.get("BENCH_WIRE_DIM", WIRE_DIM))
    reps = int(os.environ.get("BENCH_WIRE_REPS", "10"))
    rng = np.random.default_rng(0)
    base = rng.standard_normal(dim).astype(np.float32)
    new = base.copy()
    changed = rng.choice(dim, size=max(1, int(dim * WIRE_CHANGED_FRAC)),
                         replace=False)
    new[changed] += 0.01
    base_d, new_d = jnp.asarray(base), jnp.asarray(new)
    wire = WireCodec("device")

    # (1) parity gate — before any timing is believed
    h_arrays, h_meta = DeltaCodec.encode(base, new)
    d_arrays, d_meta = wire.encode(base_d, new_d)
    if h_meta != d_meta or (
            [np.asarray(a).tobytes() for a in h_arrays]
            != [np.asarray(a).tobytes() for a in d_arrays]):
        raise RuntimeError(
            f"device frames diverge from host codec at dim={dim} "
            f"(host {h_meta} vs device {d_meta})")

    # (2) timing: serving-thread CPU + wall, per path, after jit warmup
    def clock(fn):
        fn()  # warmup (compiles on the device path)
        w0, c0 = time.perf_counter(), time.thread_time()
        for _ in range(reps):
            fn()
        return ((time.perf_counter() - w0) / reps,
                (time.thread_time() - c0) / reps)

    mb = dim * 4 / 1e6
    paths = {
        "full": lambda: encode_frames([new]),
        "host_delta": lambda: DeltaCodec.encode(base, new),
        "device_delta": lambda: wire.encode(base_d, new_d),
    }
    timing = {}
    for tag, fn in paths.items():
        wall, cpu = clock(fn)
        timing[tag] = {"host_cpu_ms_per_mb": round(cpu / mb * 1e3, 4),
                       "wall_ms_per_mb": round(wall / mb * 1e3, 4)}

    # (3) engagement proof: short device-path soak, fallbacks must be zero
    from fedml_tpu.traffic.swarm import swarm_soak

    soak = swarm_soak(argparse.Namespace(
        clients=WIRE_SOAK["clients"], steps=WIRE_SOAK["steps"],
        buffer=0, staleness_alpha=0.5, max_staleness=0, flush_s=5.0,
        admit_rate=0.0, admit_burst=0, queue_limit=0,
        think_s=WIRE_SOAK["think_s"], dropout=0.0, seed=WIRE_SOAK["seed"],
        backend="loopback", procs=1, ranks_per_port=0, port=0,
        s2c_delta="auto", wire_path="device", timeout=120.0,
        run_id=f"bench-wire-{os.getpid()}",
    ))

    host_cpu = {t: v["host_cpu_ms_per_mb"] for t, v in timing.items()}
    reduction = (host_cpu["host_delta"] / host_cpu["device_delta"]
                 if host_cpu["device_delta"] else 0.0)
    return {
        "wire_dim": dim,
        "wire_frame_mb": round(mb, 1),
        "wire_scheme": h_meta["scheme"],
        "wire_parity": True,  # the gate above raised otherwise
        "wire_host_cpu_ms_per_mb": host_cpu,
        "wire_wall_ms_per_mb": {t: v["wall_ms_per_mb"]
                                for t, v in timing.items()},
        "wire_host_cpu_reduction_x": round(reduction, 2),
        "wire_soak_ok": bool(soak.get("ok")),
        "wire_soak_device_encodes": int(soak.get("wire_device_encodes") or 0),
        "wire_soak_device_decodes": int(soak.get("wire_device_decodes") or 0),
        "wire_soak_host_fallbacks": int(soak.get("wire_host_fallbacks") or 0),
        "wire_soak_s2c_delta_frames": int(soak.get("s2c_delta_frames") or 0),
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
    }


def bench_cheetah() -> dict:
    """Single-chip flagship-transformer pretrain throughput + MFU."""
    import gc

    import numpy as np

    import jax
    import jax.numpy as jnp

    from fedml_tpu.parallel.sharding import make_mesh
    from fedml_tpu.parallel.train_step import CheetahTrainer, make_optimizer
    from fedml_tpu.parallel.transformer import TransformerConfig

    platform = jax.devices()[0].platform
    if platform == "tpu":
        base, ladder = CHEETAH_BASE, CHEETAH_LADDER
        run = CHEETAH_RUN
    else:  # CPU smoke config so the bench degrades gracefully off-TPU
        base = dict(
            vocab_size=1024, d_model=256, n_heads=8,
            n_kv_heads=8, d_ff=704, max_seq_len=512, n_layers=4,
        )
        ladder = [dict(remat=False)]
        run = dict(batch=2, seq=256, steps=4, warmup=1)
    batch, seq = run["batch"], run["seq"]
    steps, warmup = run["steps"], run["warmup"]

    mesh = make_mesh()  # all local devices on the data axis
    rng = np.random.RandomState(0)

    state = trainer = cfg = None
    last_err = ""
    for rung in ladder:
        cfg = TransformerConfig(**{**base, **rung})
        trainer = CheetahTrainer(
            cfg, mesh,
            optimizer=make_optimizer(learning_rate=3e-4, warmup_steps=10,
                                     total_steps=steps + warmup,
                                     mu_dtype=jnp.bfloat16),
        )
        try:
            state = trainer.init_state(jax.random.PRNGKey(0))
            mask = jnp.ones((batch, seq), jnp.int32)
            tok = jnp.asarray(
                rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
            )
            state, metrics = trainer.train_step(state, tok, mask)
            jax.block_until_ready(metrics)
            break  # this rung compiles and fits
        except Exception as e:  # OOM at this rung: drop to more remat
            # keep only the repr — the traceback would pin the OOMed
            # trainer's buffers and poison the next rung's HBM headroom
            last_err = f"{type(e).__name__}: {e}"[:500]
            state = trainer = None
            gc.collect()
    if state is None:
        raise RuntimeError(f"no cheetah config fit on this chip: {last_err}")
    n_params = sum(int(p.size) for p in jax.tree.leaves(state.params))

    def batch_tokens():
        return jnp.asarray(
            rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
        )

    for _ in range(warmup):
        state, metrics = trainer.train_step(state, batch_tokens(), mask)
    jax.block_until_ready(metrics)

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = trainer.train_step(state, batch_tokens(), mask)
    jax.block_until_ready(metrics)
    dt = time.perf_counter() - t0

    # tracked pass: two telemetry-instrumented steps AFTER the timed window
    # give the leg its data/step/loss_sync phase breakdown
    import tempfile
    import types

    from fedml_tpu.core import mlops
    from fedml_tpu.core.mlops import telemetry

    targs = types.SimpleNamespace(
        enable_tracking=True, run_id=f"bench_cheetah_{os.getpid()}", rank=0,
        tracking_dir=(os.environ.get("BENCH_TRACKING_DIR")
                      or tempfile.mkdtemp(prefix="fedml_bench_track_")),
    )
    mlops.init(targs)
    for i in range(2):
        rec = telemetry.begin_round(i)
        with telemetry.phase("data"):
            tok = batch_tokens()
        with telemetry.phase("step"):
            state, metrics = trainer.train_step(state, tok, mask)
        with telemetry.phase("loss_sync"):
            jax.block_until_ready(metrics)
        if rec is not None:
            rec.lazy["examples"] = tok.size
        telemetry.end_round(rec)
    phases, _ = mlops.phase_totals(mlops.read_events())
    mlops.close()

    tokens = steps * batch * seq
    tps = tokens / dt
    # model FLOPs per token (fwd+bwd): 6N matmul + 12·L·layers·d_model attn
    flops_per_token = 6.0 * n_params + 12.0 * seq * cfg.n_layers * cfg.d_model
    achieved = tps * flops_per_token
    kind = jax.devices()[0].device_kind
    peak = telemetry.peak_bf16_flops(jax.devices()[0])
    n_chips = jax.device_count()
    out = {
        "cheetah_tokens_per_sec_per_chip": round(tps / n_chips, 1),
        "cheetah_params_m": round(n_params / 1e6, 1),
        "cheetah_seq_len": seq,
        "cheetah_device_kind": kind,
        "cheetah_remat": cfg.remat_policy if cfg.remat else "none",
        "cheetah_phases": {k: round(v, 4) for k, v in phases.items()},
        "platform": platform,
    }
    if peak is not None:
        out["cheetah_mfu"] = round(achieved / (peak * n_chips), 4)
    return out


# ---------------------------------------------------------------------------
# Parent orchestrator (never imports jax)
# ---------------------------------------------------------------------------


def _digest(cfg, src_paths) -> str:
    """Cache key for a leg: its config + the source files that produce it."""
    h = hashlib.md5(json.dumps(cfg, sort_keys=True).encode())
    for rel in src_paths:
        p = os.path.join(HERE, rel)
        try:
            with open(p, "rb") as f:
                h.update(f.read())
        except OSError:
            h.update(b"missing:" + rel.encode())
    return h.hexdigest()


def _load_partial() -> dict:
    try:
        with open(PARTIAL_PATH) as f:
            d = json.load(f)
        if isinstance(d.get("legs"), dict):
            return d
    except (OSError, ValueError):
        pass
    return {"legs": {}}


def _write_partial(name: str, row: dict) -> None:
    """Checkpoint one completed leg. Read-modify-write per leg (not a dump of
    this run's start-of-run snapshot) so two overlapping bench runs — the
    insurance scenario — merge rather than clobber each other. The file is
    deliberately TRACKED in git: a TPU-measured row committed mid-round lets
    the driver's end-of-round run survive a wedged backend."""
    lock_path = PARTIAL_PATH + ".lock"
    with open(lock_path, "w") as lock:
        try:
            import fcntl

            fcntl.flock(lock, fcntl.LOCK_EX)  # overlapping runs serialize
        except ImportError:  # non-POSIX: best-effort read-modify-write
            pass
        cache = _load_partial()
        cache["legs"][name] = row
        cache["updated"] = time.time()
        tmp = PARTIAL_PATH + ".tmp"
        with open(tmp, "w") as f:
            json.dump(cache, f, indent=1)
        os.replace(tmp, PARTIAL_PATH)


def _translate_mfu(prefix: str, parsed: dict):
    """mfu_sweep.py --one output → prefixed bench keys (+ platform)."""
    if "skipped" in parsed:  # CPU-only host: the child declined the TPU shape
        return {}, "cpu"
    res = {
        f"{prefix}_mfu": parsed["mfu"],
        f"{prefix}_tokens_per_sec_per_chip": parsed["tok_s"],
        f"{prefix}_device_kind": parsed.get("device_kind"),
    }
    if "params_active_m" in parsed:
        res[f"{prefix}_params_active_m"] = parsed["params_active_m"]
        res[f"{prefix}_params_total_m"] = parsed["params_m"]
    return res, "tpu"


def _translate_fedavg(parsed: dict):
    platform = parsed.get("platform")
    extras = {
        k: parsed[k]
        for k in ("fedavg_compile_s", "fedavg_round_fused",
                  "fedavg_superround_k", "fedavg_phases",
                  "fedavg_phase_rounds", "fedavg_tracked_wall_s",
                  "fedavg_compile_cache_hits", "fedavg_compile_cache_misses",
                  "fedavg_resume_overhead_s")
        if k in parsed
    }
    if platform != "tpu":
        # never let the smoke config masquerade as the resnet56 metric:
        # the headline "value" stays null off-TPU
        return {"fedavg_cpu_smoke_rounds_per_sec": parsed["rounds_per_sec"],
                "fedavg_note": "cpu smoke (lr/mnist) — not reference-comparable",
                "fedavg_device_kind": parsed.get("device_kind"),
                **extras}, platform
    return {"rounds_per_sec": parsed["rounds_per_sec"],
            "fedavg_device_kind": parsed.get("device_kind"),
            **extras}, platform


def _translate_cheetah(parsed: dict):
    platform = parsed.pop("platform", None)
    return parsed, platform


def _translate_million(parsed: dict):
    platform = parsed.pop("platform", None)
    out = {"million_device_kind": parsed.pop("device_kind", None), **parsed}
    return out, platform


def _translate_compressed(parsed: dict):
    platform = parsed.pop("platform", None)
    out = {"compressed_device_kind": parsed.pop("device_kind", None),
           **parsed}
    return out, platform


def _translate_wire(parsed: dict):
    platform = parsed.pop("platform", None)
    out = {"wire_device_kind": parsed.pop("device_kind", None), **parsed}
    return out, platform


def leg_specs() -> list:
    """(name, argv, digest, translate) per leg, priority order: the headline
    FedAvg metric first, then the flagship, then the secondary shapes."""
    mfu = os.path.join(HERE, "tools", "mfu_sweep.py")
    me = os.path.join(HERE, "bench.py")
    py = sys.executable
    million_n = int(os.environ.get("BENCH_REGISTRY_N", MILLION_REGISTRY_N))
    million_k = int(os.environ.get("BENCH_COHORT_K", MILLION_COHORT_K))
    return [
        ("fedavg", [py, me, "--leg", "fedavg"],
         _digest(FEDAVG_OVERRIDES, _FEDAVG_SOURCES), _translate_fedavg),
        ("fedavg_million_client", [py, me, "--leg", "million"],
         _digest({"cfg": MILLION_OVERRIDES, "n": million_n, "k": million_k},
                 _MILLION_SOURCES), _translate_million),
        ("fedavg_compressed_round", [py, me, "--leg", "compressed"],
         _digest({"cfg": COMPRESSED_OVERRIDES, "scheme": COMPRESSED_SCHEME},
                 _COMPRESSED_SOURCES), _translate_compressed),
        ("fedavg_wire", [py, me, "--leg", "wire"],
         _digest({"dim": WIRE_DIM, "frac": WIRE_CHANGED_FRAC,
                  "soak": WIRE_SOAK}, _WIRE_SOURCES), _translate_wire),
        ("cheetah", [py, me, "--leg", "cheetah"],
         _digest({"base": CHEETAH_BASE, "ladder": CHEETAH_LADDER,
                  "run": CHEETAH_RUN}, _CHEETAH_SOURCES), _translate_cheetah),
        ("cheetah_hd512", [py, mfu, "--one", json.dumps(HD512_CFG)],
         _digest(HD512_CFG, _CHEETAH_SOURCES),
         lambda p: _translate_mfu("cheetah_hd512", p)),
        ("cheetah_remat", [py, mfu, "--one", json.dumps(REMAT_CFG)],
         _digest(REMAT_CFG, _CHEETAH_SOURCES),
         lambda p: _translate_mfu("cheetah_remat", p)),
        ("cheetah_moe", [py, mfu, "--one", json.dumps(MOE_CFG)],
         _digest(MOE_CFG, _CHEETAH_SOURCES),
         lambda p: _translate_mfu("cheetah_moe", p)),
    ]


def build_line(results: dict, ref: float | None, meta: dict) -> dict:
    """Assemble the cumulative JSON line from completed leg results."""
    fed = results.get("fedavg", {})
    value = fed.get("rounds_per_sec")
    comparable = value is not None and "fedavg_note" not in fed
    line = {
        "metric": "fedavg_rounds_per_sec_100clients_cifar10_resnet56",
        "value": round(value, 4) if value is not None else None,
        "unit": "rounds/s",
        # TPU vs the reference's torch CPU (its only substrate here) —
        # conflates hardware with architecture, hence the companion below
        "vs_baseline": round(value / ref, 2) if (comparable and ref) else None,
        "ref_rounds_per_sec_measured": ref,
        # ours-on-CPU / reference-on-CPU: the architectural win alone
        **_same_substrate(),
    }
    for name, res in results.items():
        for k, v in res.items():
            if k != "rounds_per_sec":
                line[k] = v
    line.update(meta)
    return line


def _probe_device_kind(timeout: float = 90.0):
    """Ask a SUBPROCESS for the device kind (the parent stays off jax, and
    a wedged backend hangs the probe, not the bench; the probe exits before
    any leg starts, so it never holds the chip against one). Returns
    ``(kind, reason)``:

    - ``(str, "ok")`` — chip identified;
    - ``(None, "timeout")`` — probe exceeded its budget: could be a DOWN
      backend or merely a SLOW-but-healthy host, so callers must NOT treat
      this as proof of unreachability;
    - ``(None, "error")`` — backend init failed fast (e.g. UNAVAILABLE):
      the one case where legs are certain to fail too.

    None kinds ACCEPT cached rows (the insurance case) rather than
    discarding them."""
    snippet = "import jax; print(jax.devices()[0].device_kind)"
    try:
        p = subprocess.run(
            [sys.executable, "-c", snippet],
            capture_output=True, text=True, timeout=timeout,
        )
        if p.returncode == 0 and p.stdout.strip():
            return p.stdout.strip().splitlines()[-1], "ok"
        return None, "error"
    except subprocess.TimeoutExpired:
        return None, "timeout"
    except Exception:
        return None, "error"


def _usable(cached, digest: str, ttl_s: float) -> bool:
    return bool(
        cached and cached.get("digest") == digest
        and cached.get("platform") == "tpu"
        and time.time() - cached.get("t", 0) < ttl_s
    )


def run_legs(budget_s: float, ttl_s: float, min_leg_s: float = 240.0,
             leg_timeout_s: float = 900.0, runner=None,
             device_prober=None) -> dict:
    """Run all legs under a global deadline, emitting the cumulative line
    after every completed leg. ``runner``/``device_prober`` are injectable
    for tests."""
    t_start = time.monotonic()
    cache = _load_partial()
    ref = _ref_rounds_per_sec()
    results: dict = {}

    # one up-front device probe (in a SUBPROCESS — a wedged backend hangs the
    # probe, not the bench). Purpose is twofold: (a) a cache row measured on
    # a DIFFERENT TPU generation must not be served as this round's number —
    # mismatched rows are dropped and re-run; (b) when the backend is
    # UNREACHABLE, every leg would hang to its full timeout at backend init,
    # so leg timeouts shrink to fail fast and the line carries explicit
    # errors within minutes instead of rc=124.
    specs = leg_specs()
    # BENCH_LEGS=fedavg,cheetah runs a subset (smoke checks / re-measuring
    # one leg without paying for the rest); unknown names are ignored
    only = os.environ.get("BENCH_LEGS", "").strip()
    if only:
        wanted = {n.strip() for n in only.split(",") if n.strip()}
        specs = [s for s in specs if s[0] in wanted]
    probe = (device_prober or _probe_device_kind)()
    # tolerate simple probers that return a bare kind (tests inject these)
    kind, reason = probe if isinstance(probe, tuple) else (probe, "ok")
    if kind is None and reason == "error":
        # backend init fails FAST and deterministically (backend down): legs
        # would each hang their full timeout at init, so fail fast instead.
        # A probe TIMEOUT is NOT proof of unreachability (a loaded host can
        # blow the 90s budget and still serve legs fine) — keep timeouts.
        leg_timeout_s = min(leg_timeout_s, 240.0)
    for n, _, d, _ in specs:
        row = cache["legs"].get(n)
        if (_usable(row, d, ttl_s) and kind and row.get("device_kind")
                and row["device_kind"] != kind):
            del cache["legs"][n]

    def emit():
        elapsed = round(time.monotonic() - t_start, 1)
        line = build_line(results, ref, {"bench_elapsed_s": elapsed,
                                         "bench_budget_s": budget_s,
                                         "bench_device_probe":
                                         kind or {"error": "unreachable",
                                                  "timeout": "probe-timeout"}
                                         .get(reason, "unknown")})
        print(json.dumps(line), flush=True)
        return line

    # a parseable tail exists from second zero: even a driver timeout before
    # the FIRST leg resolves leaves this line, not an empty capture (r4
    # recorded rc=124 with tail="")
    emit()

    def default_runner(argv, timeout):
        env = dict(os.environ)
        env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
        p = subprocess.run(argv, capture_output=True, text=True,
                           timeout=timeout, env=env)
        out = (p.stdout.strip().splitlines() or ["<no output>"])[-1]
        if p.returncode != 0:
            err = (p.stderr.strip().splitlines() or [""])[-1]
            raise RuntimeError(f"rc={p.returncode} {out[:120]} {err[:200]}")
        return json.loads(out)

    runner = runner or default_runner
    line = {}
    for name, argv, digest, translate in specs:
        cached = cache["legs"].get(name)
        if _usable(cached, digest, ttl_s):
            results[name] = {**cached["result"], f"{name}_cached": True}
            line = emit()
            continue
        remaining = budget_s - (time.monotonic() - t_start)
        if remaining < min_leg_s:
            results[name] = {f"{name}_skipped": "budget"}
            line = emit()
            continue
        t0 = time.time()
        try:
            parsed = runner(argv, min(leg_timeout_s, remaining))
            res, platform = translate(parsed)
        except subprocess.TimeoutExpired:
            res, platform = {f"{name}_error": "leg timeout"}, None
        except Exception as e:
            res, platform = (
                {f"{name}_error": f"{type(e).__name__}: {e}"[:300]}, None)
        results[name] = res
        if platform == "tpu":  # only real-config TPU numbers are cacheable
            _write_partial(name, {
                "digest": digest, "t": time.time(), "platform": platform,
                "dur_s": round(time.time() - t0, 1), "result": res,
                "device_kind": next(
                    (v for k2, v in res.items()
                     if k2.endswith("device_kind") and v), None),
            })
        line = emit()
    return line


def main() -> None:
    if len(sys.argv) > 2 and sys.argv[1] == "--leg":
        fn = {"fedavg": bench_fedavg, "cheetah": bench_cheetah,
              "million": bench_million_client,
              "compressed": bench_compressed_round,
              "wire": bench_fedavg_wire}[sys.argv[2]]
        print(json.dumps(fn()), flush=True)
        return
    budget = float(os.environ.get("BENCH_BUDGET_S", "2400"))
    ttl = float(os.environ.get("BENCH_CACHE_TTL_S", str(7 * 86400)))
    min_leg = float(os.environ.get("BENCH_MIN_LEG_S", "240"))
    leg_timeout = float(os.environ.get("BENCH_LEG_TIMEOUT_S", "900"))
    run_legs(budget, ttl, min_leg_s=min_leg, leg_timeout_s=leg_timeout)


if __name__ == "__main__":
    main()
