#!/usr/bin/env python3
"""Chip smoke: the quickest proof that the system still starts on the TPU.

    python3 chip_smoke.py              # the default legs for this host
    python3 chip_smoke.py LEG [LEG…]   # named legs (``_legs`` below)

Drives the two hot paths through the entry points a user calls
(``fedml_tpu.init(Arguments(overrides=…))`` → ``FedMLRunner(...).run()``), at
the full width of the flagship LM and of ResNet-56 FedAvg, on every chip the
process sees, plus the ring-attention kernel forward and backward against the
einsum path. Weights are random from a seed, steps and rounds are few: cold,
compiling is most of the run.

Exits non-zero, naming the platform it found, unless
``jax.devices()[0].platform == "tpu"``. Any leg that raises fails the run.
It also exits non-zero, printing no result, in a directory that holds nothing
else of the repo. On success stdout is two lines. First the report, one JSON
object: versions, the compile cache in use with hit/miss counts, and per leg
what ran (also written to ``chiprun_out/chip_smoke/report.json``); times in
it are labelled with the device and are informational, not records of speed.
Last the result, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as JAX reports it. One process holds the chip: this script
starts no child.
"""

from __future__ import annotations

import gc
import importlib.metadata
import json
import math
import os
import statistics
import sys
import time
from functools import partial

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

# The repo's flagship LM at full width (d2048, 16 heads of 128, 4 kv heads,
# d_ff 5632, vocab 32000) with depth 8, on the packed `shakespeare` fallback
# stream so the loss can fall (uniform random tokens cannot go below ln V).
# One stated remat setting, no ladder: full-block remat always fits 16 GB.
FLAGSHIP = dict(
    training_type="distributed", dataset="shakespeare", model="transformer",
    model_size="flagship", vocab_size=32000, d_model=2048, n_layers=8,
    n_heads=16, n_kv_heads=4, d_ff=5632, seq_len=2048, batch_size=8,
    attn_block_q=512, attn_block_kv=512, remat=True, remat_policy="full",
    total_steps=6, learning_rate=1e-3, warmup_steps=2,
    client_num_in_total=100, client_num_per_round=100,
)
# ResNet-56 on CIFAR-10-shaped data: 100 clients, 10 a round, batch 32, one
# local epoch, the jitted, donated round. Round 0 is the warm-up.
FEDAVG = dict(
    training_type="simulation", backend="sp", dataset="cifar10",
    model="resnet56", client_num_in_total=100, client_num_per_round=10,
    comm_round=5, epochs=1, batch_size=32, learning_rate=0.1,
    frequency_of_the_test=1000,
)
RING = dict(B=1, Lb=4096, H=16, D=128)
# Kernel vs einsum path, relative L2 on bf16 inputs. The two differ by
# design: the einsum path's bf16 x bf16 score einsum rounds the [Lq, Lk]
# logits to bf16 (8 mantissa bits, ~0.4% each) before its fp32 softmax, the
# splash kernels keep them fp32 in VMEM, and both round outputs and grads to
# bf16. Measured on the v5e at Lb 4096 (my chip run, PR 21): out 4.5e-3,
# dq 5.2e-3, dk 5.2e-3, dv 4.6e-3, so the bound is 4x what rounding gives;
# a wrong mask, scale or lse merge is O(1).
RING_REL_L2_TOL = 2e-2
WARMUP = 2  # Cheetah steps 0 and 1 both compile (see PERF.md, Findings)


def _require_repo():
    if not os.path.isfile(os.path.join(HERE, "fedml_tpu", "__init__.py")):
        sys.stderr.write(
            f"chip_smoke: no fedml_tpu package next to {__file__}: this "
            f"script drives the repo and proves nothing alone.\n")
        raise SystemExit(1)


def _require_tpu():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.stderr.write(
            f"chip_smoke: needs a TPU, but jax.devices()[0] is platform "
            f"{dev.platform!r} ({dev.device_kind!r}, {len(jax.devices())} "
            f"device(s)). Run it through the chip tool.\n"
        )
        raise SystemExit(1)


def _peak_bytes() -> list:
    """``peak_bytes_in_use`` per device: the PROCESS high-water mark so far
    (legs run small to large so each leg's figure is its own)."""
    import jax

    return [
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.devices()
    ]


def _init_tracked(overrides: dict, run_dir: str):
    """``fedml_tpu.init`` with the repo's telemetry on: the run then emits
    one RoundRecord per step/round (wall, loss, compiles) — the repo's own
    means of saying what happened."""
    import fedml_tpu as fedml
    from fedml_tpu.arguments import Arguments

    return fedml.init(Arguments(overrides={
        "enable_tracking": True, "tracking_dir": run_dir,
        "run_id": f"chip_smoke_{os.getpid()}_{time.monotonic_ns()}",
        **overrides,
    }), should_init_logs=False)


def _round_records() -> list:
    """The RoundRecords of the run just finished; closes its event sink."""
    from fedml_tpu.core import mlops

    records = [e for e in mlops.read_events()
               if e.get("kind") == "round_record"]
    mlops.close()
    return records


def _check_losses(name: str, losses: list) -> None:
    if not losses or not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"{name}: non-finite or missing losses {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{name}: loss did not fall: {losses}")


def _sharding_facts(tree, mesh) -> dict:
    """Is the work on every chip of ``mesh``? Every leaf must live on the
    whole mesh, and a leaf whose spec names an axis wider than 1 must hold
    only a slice per device. Returns one sharded leaf as the witness."""
    import jax

    want = set(mesh.devices.flat)
    witness = None
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = jax.tree_util.keystr(path)
        if set(leaf.sharding.device_set) != want:
            raise AssertionError(
                f"{name} lives on {len(leaf.sharding.device_set)} device(s), "
                f"mesh has {len(want)}")
        axes = [a for part in leaf.sharding.spec if part is not None
                for a in ((part,) if isinstance(part, str) else part)]
        if any(int(mesh.shape[a]) > 1 for a in axes):
            shard = leaf.addressable_shards[0].data.shape
            if tuple(shard) == tuple(leaf.shape):
                raise AssertionError(
                    f"{name}: spec {leaf.sharding.spec} but every device "
                    f"holds the full {leaf.shape}")
            if witness is None:
                witness = {
                    "leaf": name, "spec": str(leaf.sharding.spec),
                    "shape": list(leaf.shape), "shard_shape": list(shard),
                    "devices": sorted(d.id for d in leaf.sharding.device_set),
                }
    return {"leaves_on_devices": len(want), "sharded_leaf": witness}


def _check_peaks_balanced(name: str, peaks: list) -> None:
    if len(peaks) > 1 and min(peaks) * 4 < max(peaks):
        raise AssertionError(
            f"{name}: peak_bytes_in_use not of one order across devices: "
            f"{peaks}")


# ---------------------------------------------------------------------------
# Legs
# ---------------------------------------------------------------------------


def leg_cheetah(overrides: dict, run_dir: str) -> dict:
    """Cheetah pretraining steps through FedMLRunner, then three probes on
    the runner's own trainer: what the step lowered to, whether
    ``block_until_ready`` waits, and where the parameters live."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu import data as data_mod
    from fedml_tpu import get_device
    from fedml_tpu.runner import FedMLRunner

    t0 = time.perf_counter()
    args = _init_tracked(overrides, run_dir)
    ds, _ = data_mod.load(args)
    runner = FedMLRunner(args, get_device(args), ds, None)
    result = runner.run()
    run_s = time.perf_counter() - t0
    records = _round_records()
    cheetah = runner.runner
    trainer = cheetah.trainer
    losses = [r["train_loss"] for r in records]
    if not len(records) == result["steps"] == int(overrides["total_steps"]):
        raise AssertionError(
            f"cheetah: {len(records)} step records, runner says "
            f"{result['steps']} steps, expected {overrides['total_steps']}")
    _check_losses("cheetah", losses)
    if cheetah._token_stream() is None:
        raise AssertionError("cheetah: ran on uniform random tokens, not on "
                             "the packed dataset stream")
    steady = [r["wall_s"] for r in records[WARMUP:]]

    # probes: a fresh state on the runner's trainer (run() kept none)
    state = trainer.init_state(jax.random.PRNGKey(0))
    tokens = next(cheetah._batches(np.random.RandomState(0)))
    tok, mask = jnp.asarray(tokens), jnp.ones_like(jnp.asarray(tokens))
    hlo = trainer.lower_step(state, tok, mask).as_text()
    mosaic = "tpu_custom_call" in hlo
    del hlo
    seq_sharded = trainer.seq_sharded
    # every Cheetah leg below is sized for the kernel path (the ring needs
    # Lb >= 4096 a device, which is why the sequence leg runs at 16k)
    if jax.devices()[0].platform == "tpu" and not mosaic:
        raise AssertionError(
            "cheetah: no tpu_custom_call in the lowered step — attention "
            "quietly took the XLA path")

    # block_until_ready against a fetched scalar, same step, alternating
    for _ in range(WARMUP):
        state, metrics = trainer.train_step(state, tok, mask)
    jax.block_until_ready(metrics)
    t_dispatch, t_ready, t_fetch = [], [], []
    for i in range(6):
        t0 = time.perf_counter()
        state, metrics = trainer.train_step(state, tok, mask)
        t1 = time.perf_counter()
        if i % 2 == 0:
            jax.block_until_ready(metrics)
            t_ready.append(time.perf_counter() - t0)
        else:
            float(np.asarray(metrics["loss"]))
            t_fetch.append(time.perf_counter() - t0)
        t_dispatch.append(t1 - t0)
    sync = {
        "step_s_to_block_until_ready": [round(t, 4) for t in t_ready],
        "step_s_to_fetched_scalar": [round(t, 4) for t in t_fetch],
        "dispatch_return_s": [round(t, 4) for t in t_dispatch],
    }
    ready, fetch = statistics.median(t_ready), statistics.median(t_fetch)
    sync["agree"] = abs(ready - fetch) <= 0.1 * max(ready, fetch)

    facts = _sharding_facts(state.params, trainer.mesh)
    btok, _ = trainer.shard_batch(tok, mask)
    batch = {"spec": str(btok.sharding.spec), "shape": list(btok.shape),
             "shard_shape": list(btok.addressable_shards[0].data.shape)}
    if seq_sharded and btok.addressable_shards[0].data.shape[-1] * int(
            trainer.mesh.shape["sequence"]) != btok.shape[-1]:
        raise AssertionError(f"cheetah: batch not split over sequence: {batch}")
    peaks = _peak_bytes()
    _check_peaks_balanced("cheetah", peaks)
    n_params = sum(int(p.size) for p in jax.tree.leaves(state.params))
    del state, metrics
    return {
        "ran": f"CheetahRunner {n_params / 1e6:.1f}M params, mesh "
               f"{ {k: v for k, v in trainer.mesh.shape.items() if v > 1} }, "
               f"batch {overrides['batch_size']} x seq {overrides['seq_len']},"
               f" remat {trainer.cfg.remat_policy if trainer.cfg.remat else 'none'}",
        "data": "real files" if ds.meta.get("real_files") else
                "synthetic fallback stream",
        "seq_sharded": seq_sharded,
        "losses": [round(x, 4) for x in losses],
        "run_s": round(run_s, 2),
        "first_step_s_with_compile": round(records[0]["wall_s"], 2),
        "compiles_per_step": [r["compiles"] for r in records],
        "compiles_after_warmup": sum(r["compiles"] for r in records[WARMUP:]),
        "steady_step_s_informational": round(statistics.median(steady), 4),
        "steady_tokens_per_sec_informational": round(
            int(overrides["batch_size"]) * int(overrides["seq_len"])
            / statistics.median(steady), 1),
        "mosaic_call_in_lowered_step": mosaic,
        "block_until_ready": sync,
        "params": facts,
        "batch": batch,
        "peak_bytes_in_use": peaks,
    }


def leg_fedavg(overrides: dict, run_dir: str) -> dict:
    """FedAvg rounds through FedMLRunner (backend sp, or mesh with the
    cohort sharded over a ``clients`` axis)."""
    import jax
    import numpy as np

    from fedml_tpu import data as data_mod
    from fedml_tpu import get_device
    from fedml_tpu import models as model_mod
    from fedml_tpu.runner import FedMLRunner

    t0 = time.perf_counter()
    args = _init_tracked(overrides, run_dir)
    ds, output_dim = data_mod.load(args)
    runner = FedMLRunner(args, get_device(args), ds,
                         model_mod.create(args, output_dim))
    result = runner.run()
    run_s = time.perf_counter() - t0
    records = _round_records()
    api = runner.runner.fl_trainer
    losses = [r["train_loss"] for r in records]
    if len(records) != int(overrides["comm_round"]):
        raise AssertionError(
            f"fedavg: {len(records)} round records, expected "
            f"{overrides['comm_round']}")
    _check_losses("fedavg", losses)
    if not math.isfinite(result["test_loss"]):
        raise AssertionError(f"fedavg: eval not finite: {result}")
    if api._round_step is None or not all(r["fused"] for r in records):
        raise AssertionError("fedavg: the round did not run fused")
    if any(r["cohort_chunk"] != api.cohort_chunk for r in records):
        raise AssertionError("fedavg: records lack the engine's cohort_chunk")
    out = {
        "ran": f"{type(api).__name__} {overrides['model']} on "
               f"{overrides['dataset']}, {overrides['client_num_in_total']} "
               f"clients, {overrides['client_num_per_round']} a round, batch "
               f"{overrides['batch_size']}, {overrides['epochs']} epoch",
        "data": "real files" if ds.meta.get("real_files") else
                "synthetic fallback",
        "fused": True,
        "cohort_chunk": api.cohort_chunk,
        "losses": [round(x, 4) for x in losses],
        "test_acc": round(float(result["test_acc"]), 4),
        "run_s": round(run_s, 2),
        "first_round_s_with_compile": round(records[0]["wall_s"], 2),
        "compiles_per_round": [r["compiles"] for r in records],
        "compiles_after_warmup": sum(r["compiles"] for r in records[1:]),
        "steady_round_s_informational": round(
            statistics.median(r["wall_s"] for r in records[1:]), 4),
    }
    mesh = getattr(api, "mesh", None)
    if mesh is not None:
        cohort, _ = api._pad_cohort(
            np.arange(int(overrides["client_num_per_round"])))
        cx, _, _ = api._gather_cohort(cohort)
        out["cohort_x"] = _sharding_facts({"cohort_x": cx}, mesh)
        if out["cohort_x"]["sharded_leaf"] is None:
            raise AssertionError("fedavg: cohort is not sharded over clients")
        state = api._place_state(api._round_state())
        _sharding_facts(state, mesh)
        out["round_state_devices"] = sorted(
            d.id for d in jax.tree.leaves(state)[0].sharding.device_set)
        del cx, state
    out["peak_bytes_in_use"] = _peak_bytes()
    if mesh is not None:
        _check_peaks_balanced("fedavg", out["peak_bytes_in_use"])
    return out


def leg_ring_kernel(B: int, Lb: int, H: int, D: int,
                    interpret: bool = False) -> dict:
    """Ring attention on a ``sequence`` mesh of one device: the Pallas kernel
    path (forward and the splash dq/dkv backward) against the einsum path —
    output and the three gradients."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from fedml_tpu.parallel.ring_attention import make_ring_attention
    from fedml_tpu.parallel.sharding import compat_shard_map

    mesh = Mesh(np.asarray(jax.devices()[:1]), axis_names=("sequence",))
    rng = np.random.RandomState(0)
    q, k, v, w = (jnp.asarray(rng.standard_normal((B, Lb, H, D)),
                              jnp.bfloat16) for _ in range(4))
    spec = P(None, "sequence", None, None)

    def build(use_kernel: bool):
        ring = make_ring_attention(1, "sequence", use_kernel=use_kernel,
                                   interpret=interpret)
        sm = compat_shard_map(ring, mesh=mesh, in_specs=(spec,) * 3,
                              out_specs=spec)

        @jax.jit
        def fwd_bwd(q, k, v, w):
            out, vjp = jax.vjp(sm, q, k, v)
            return (out,) + vjp(w)

        return fwd_bwd

    kernel = build(True)
    mosaic = "tpu_custom_call" in kernel.lower(q, k, v, w).as_text()
    if not interpret and not mosaic:
        raise AssertionError("ring kernel: no tpu_custom_call in the lowering")
    t0 = time.perf_counter()
    got = jax.block_until_ready(kernel(q, k, v, w))
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(kernel(q, k, v, w))
    second_s = time.perf_counter() - t0
    want = jax.block_until_ready(build(False)(q, k, v, w))

    def rel_l2(a, b):
        a = np.asarray(a, np.float32).ravel()
        b = np.asarray(b, np.float32).ravel()
        if not np.isfinite(a).all():
            raise AssertionError("ring kernel: non-finite values")
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    errs = {n: rel_l2(g, r)
            for n, g, r in zip(("out", "dq", "dk", "dv"), got, want)}
    bad = {n: e for n, e in errs.items() if not e <= RING_REL_L2_TOL}
    if bad:
        raise AssertionError(
            f"ring kernel disagrees with the einsum path: {bad} "
            f"(tolerance {RING_REL_L2_TOL})")
    return {
        "ran": f"make_ring_attention(use_kernel=True, interpret={interpret})"
               f" fwd+bwd, B{B} Lb{Lb} H{H} D{D} bf16, sequence mesh of 1",
        "rel_l2_vs_einsum": {n: float(f"{e:.3g}") for n, e in errs.items()},
        "rel_l2_tolerance": RING_REL_L2_TOL,
        "mosaic_call_in_lowering": mosaic,
        "first_call_s_with_compile": round(first_s, 2),
        "second_call_s_informational": round(second_s, 4),
        "peak_bytes_in_use": _peak_bytes(),
    }


def leg_fedllm(overrides: dict, silos: list, run_dir: str) -> dict:
    """Cross-silo FedLLM rounds as ``__graft_entry__`` lays them out: a
    loopback server and one client per silo in this process, each silo's
    local Cheetah steps sharded over its own chips (``silo_device_indices``).
    Off the CPU, ``wire_path: auto`` picks the device delta codec."""
    import threading

    from fedml_tpu import data as data_mod
    from fedml_tpu import models as model_mod
    from fedml_tpu.core.mlops import telemetry
    from fedml_tpu.cross_silo import (
        FedMLCrossSiloClient,
        FedMLCrossSiloServer,
    )

    common = dict(
        overrides, client_num_in_total=len(silos),
        client_num_per_round=len(silos),
        run_id=f"chip_smoke_fedllm_{os.getpid()}",
    )
    before = telemetry.registry().snapshot()["counters"]
    t0 = time.perf_counter()
    sargs = _init_tracked(dict(common, role="server", rank=0), run_dir)
    ds, output_dim = data_mod.load(sargs)
    bundle = model_mod.create(sargs, output_dim)
    server = FedMLCrossSiloServer(sargs, None, ds, bundle)
    clients = [
        FedMLCrossSiloClient(
            _init_tracked(dict(common, role="client", rank=rank,
                               silo_device_indices=list(chips)), run_dir),
            None, ds, bundle)
        for rank, chips in enumerate(silos, start=1)
    ]
    threads = [threading.Thread(target=c.run, daemon=True) for c in clients]
    for t in threads:
        t.start()
    result = server.run()
    for t in threads:
        t.join(timeout=300)
    if any(t.is_alive() for t in threads):
        raise AssertionError("fedllm: a silo client did not finish")
    run_s = time.perf_counter() - t0
    _round_records()
    if result is None or not math.isfinite(result["test_loss"]):
        raise AssertionError(f"fedllm: no finite eval: {result}")
    after = telemetry.registry().snapshot()["counters"]
    wire = {k: after.get(k, 0) - before.get(k, 0) for k in sorted(after)
            if k.startswith(("comm.wire.", "comm.delta."))}
    for rank, (client, chips) in enumerate(zip(clients, silos), start=1):
        mesh = client.manager.trainer.mesh
        if sorted(d.id for d in mesh.devices.flat) != sorted(chips):
            raise AssertionError(
                f"fedllm: silo {rank} trained on {mesh.devices}, not {chips}")
    return {
        "ran": f"{len(silos)} silos x {len(silos[0])} chips "
               f"(mesh {overrides['mesh_shape']}), {overrides['comm_round']} "
               f"rounds of {overrides['local_steps']} local Cheetah step(s), "
               f"{bundle.param_count(server.manager.global_params) / 1e6:.1f}M"
               f" params, seq {bundle.cfg.max_seq_len}",
        "test_loss": round(float(result["test_loss"]), 4),
        "run_s": round(run_s, 2),
        "wire": wire,
        "host_fallbacks": wire.get("comm.wire.host_fallbacks", 0),
        "peak_bytes_in_use": _peak_bytes(),
    }


# ---------------------------------------------------------------------------
# Which legs, in which order (small to large: see _peak_bytes)
# ---------------------------------------------------------------------------


def _legs(n_devices: int, run_dir: str) -> dict:
    """name -> the leg as a call without arguments. ``fedavg`` and
    ``cheetah`` widen with the host: one chip runs backend sp and a
    one-device mesh, several run the cohort over ``clients:N`` and the
    parameters over ``fsdp:N``."""
    fedavg = dict(FEDAVG) if n_devices == 1 else dict(
        FEDAVG, backend="mesh", mesh_shape=f"clients:{n_devices}")
    return {
        "ring_kernel": partial(leg_ring_kernel, **RING),
        "fedavg": partial(leg_fedavg, fedavg, run_dir),
        "cheetah": partial(leg_cheetah, dict(
            FLAGSHIP, mesh_shape=f"fsdp:{n_devices}"), run_dir),
        # four-chip host only (make_mesh refuses any other device count)
        "cheetah_fsdp2_tensor2": partial(leg_cheetah, dict(
            FLAGSHIP, mesh_shape="fsdp:2,tensor:2"), run_dir),
        # Lb 4096 per chip: the ring takes the kernel path over real ppermute
        "cheetah_seq4_16k": partial(leg_cheetah, dict(
            FLAGSHIP, mesh_shape="sequence:4", seq_len=16384, batch_size=1,
            total_steps=5), run_dir),
        # the dataset owns the token space here: vocab 90, windows of 80.
        # Depth cut to 2: server, both clients' delta stores and silo 1 all
        # keep their vectors on chip 0 in this one-process layout, and at
        # depth 8 (361M params) the server's aggregation ran chip 0 out of
        # HBM (my chip run, PR 21; PERF.md, Findings)
        "fedllm_2x2": partial(leg_fedllm, {
            **{k: FLAGSHIP[k] for k in (
                "dataset", "model_size", "d_model", "n_heads", "n_kv_heads",
                "d_ff", "remat", "remat_policy")},
            "n_layers": 2,
            "training_type": "cross_silo", "model": "cheetah",
            "backend": "LOOPBACK", "mesh_shape": "fsdp:2", "comm_round": 2,
            "local_steps": 1, "batch_size": 8, "learning_rate": 0.05,
            "client_optimizer": "sgd"}, [[0, 1], [2, 3]], run_dir),
    }


DEFAULT_LEGS = ("ring_kernel", "fedavg", "cheetah")


def result(devices: list) -> dict:
    """The last stdout line, reached only when every leg passed: these keys
    and no others, the device as JAX reports it."""
    return {
        "ok": True,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)},
    }


def main(argv: list) -> int:
    _require_repo()
    import jax
    import jaxlib

    _require_tpu()
    from fedml_tpu import native
    from fedml_tpu.core.mlops import telemetry
    from fedml_tpu.device import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    telemetry.install_jax_listeners()  # count compiles from the first jit
    devices = jax.devices()
    run_dir = os.path.join(HERE, "chiprun_out", "chip_smoke")
    legs = _legs(len(devices), run_dir)
    names = argv or list(DEFAULT_LEGS)
    unknown = [n for n in names if n not in legs]
    if unknown:
        sys.stderr.write(f"chip_smoke: unknown leg(s) {unknown}; "
                         f"known: {sorted(legs)}\n")
        return 1
    os.makedirs(run_dir, exist_ok=True)

    t_start = time.perf_counter()
    report = {}
    for name in names:
        sys.stderr.write(f"chip_smoke: leg {name} …\n")
        report[name] = legs[name]()
        sys.stderr.write(f"chip_smoke: leg {name} ok: "
                         f"{json.dumps(report[name])}\n")
        gc.collect()  # drop the leg's device buffers before the next one

    snap = telemetry.registry().snapshot()
    counters = snap["counters"]
    report_line = json.dumps({
        "report": "chip_smoke",
        **result(devices),
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": importlib.metadata.version("libtpu")},
        "native_host_pipeline": native.have_native(),
        "compile_cache": {
            "dir": cache_dir,
            "hits": int(counters.get("jax.compilation_cache.hits", 0)),
            "misses": int(counters.get("jax.compilation_cache.misses", 0)),
            "xla_compiles": int(counters.get("jax.compiles", 0)),
            "compile_s": snap["histograms"].get(
                "jax.compile.seconds", {}).get("sum", 0.0),
        },
        "legs": report,
        "total_s": round(time.perf_counter() - t_start, 1),
        "note": "times are informational, from this device, not records of "
                "speed",
        "claim": None,
    })
    with open(os.path.join(run_dir, "report.json"), "w") as f:
        f.write(report_line + "\n")
    print(report_line)
    print(json.dumps(result(devices)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
