"""Chaos soak harness: prove the crash-recovery plane end-to-end.

reference: none — the reference has no recovery soak of any kind (SURVEY.md
§5). This harness runs the SAME loopback cross-silo federation twice:

1. **reference leg** — fault-free, in-process, to completion;
2. **chaos leg** — a subprocess under a seeded fault matrix (visible loss +
   wire duplication + payload corruption on every client link) that
   SIGTERMs ITSELF after the run ledger commits round ``kill_round``, then
   a second subprocess restarts it with ``--resume auto``;

and asserts the recovered run's final global params are **bitwise equal**
to the fault-free run's, that no client contribution was ever counted
twice (per-round contribution counters from the durable ledger), and that
the combined ledger stream covers every round exactly like the reference
run's. That is the "kill -9 anywhere, restart, converge to the same
params" invariant as an executable check — ``fedml_tpu chaos`` from the
CLI, ``tools/chaos_smoke.sh`` in CI.

Why this catches real bugs: visible loss exercises the at-least-once retry
budget, duplication exercises the receiver dedup window, corruption
exercises the payload checksum + NACK re-send, and the mid-run SIGTERM +
restart exercises the preemption drain, the Orbax round checkpoint, and
ledger-driven resume — all composed, all seeded, all reproducible.
"""

from __future__ import annotations

import json
import logging
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional

import numpy as np

logger = logging.getLogger(__name__)

FINAL_PARAMS_FILE = "final_params.npz"
REPORT_FILE = "chaos_report.json"


def _world_overrides(a) -> Dict:
    over = dict(
        training_type="cross_silo", dataset="synthetic", model="lr",
        client_num_in_total=int(a.clients), client_num_per_round=int(a.clients),
        comm_round=int(a.rounds), epochs=int(a.epochs), batch_size=8,
        learning_rate=0.2, backend="LOOPBACK", frequency_of_the_test=1000,
        random_seed=int(a.seed),
    )
    if _kill_phase(a) or _edge_fault(a) \
            or float(getattr(a, "heartbeat_s", 0.0) or 0.0) > 0:
        # server-kill legs need the client liveness/resync FSM: a fast
        # lease so a dead server is detected within ~a second, and a
        # patient reconnect budget that rides out the restart leg's
        # process spawn + jax import (tens of seconds on a cold host).
        # Edge-fault legs run the same FSM one tier down (client↔edge,
        # edge↔root).
        over.update(
            heartbeat_s=float(getattr(a, "heartbeat_s", 0.0) or 0.3),
            heartbeat_miss_limit=2,
            resync_backoff_s=0.3,
            resync_backoff_max_s=2.0,
            resync_max_attempts=90,
        )
    if _edge_fault(a):
        # a killed edge's orphans must give up on the corpse quickly and
        # re-home to a sibling (docs/robustness.md "Edge tier failure
        # domains") instead of burning the whole resync budget on it
        over.update(rehome_after_attempts=2)
    if _partition_window(a) is not None \
            or _edge_partition_window(a) is not None:
        # a healed partition must cost backoff, not contributions: give
        # the at-least-once layer enough retry budget to outlast the cut
        over.update(comm_retry_max_attempts=10)
    scheme = str(getattr(a, "compression", "") or "")
    if scheme:
        # BOTH legs (reference and chaos) run compressed + delta-shipped:
        # the bitwise verdict then proves dedup, payload digests and the
        # version store survive delta frames under faults. Stateless
        # schemes only — eftopk's client-side residual dies with the
        # killed process and would legitimately diverge the resumed leg.
        if scheme == "eftopk":
            raise ValueError(
                "chaos --compression eftopk cannot hold bitwise parity "
                "across a kill/restart (client residual state is lost); "
                "use topk/quantize/qsgd"
            )
        over.update(compression=scheme,
                    compression_ratio=float(
                        getattr(a, "compression_ratio", 0.1)))
    tdir = str(getattr(a, "trace_dir", "") or "")
    if tdir:
        # traced leg (server-kill chaos runs set this): spans persist
        # through the JSONL sink into the shared trace dir, and the flight
        # recorder's pre-SIGKILL flush lands its post-mortem there too —
        # the orchestrator's verdict reads both
        over.update(enable_tracing=True, trace_sample=1.0, trace_dir=tdir,
                    enable_tracking=True, tracking_dir=tdir)
    return over


def _kill_phase(a) -> str:
    return str(getattr(a, "kill_phase", "") or "")


def _edge_count(a) -> int:
    return int(getattr(a, "edges", 0) or 0)


def _edge_kill_phase(a) -> str:
    return str(getattr(a, "kill_edge", "") or "")


def _edge_partition_window(a):
    """Parse ``--edge-partition START:DURATION`` — the root–edge cut — or
    None when unset."""
    raw = str(getattr(a, "edge_partition", "") or "")
    if not raw:
        return None
    try:
        start_s, dur_s = raw.split(":", 1)
        return float(start_s), float(dur_s)
    except ValueError as e:
        raise ValueError(
            f"--edge-partition wants START:DURATION seconds, got {raw!r}"
        ) from e


def _edge_fault(a) -> bool:
    return bool(_edge_kill_phase(a) or _edge_partition_window(a) is not None)


def _partition_window(a):
    """Parse ``--partition START:DURATION`` (seconds) into a (start,
    duration) tuple, or None when the flag is unset."""
    raw = str(getattr(a, "partition", "") or "")
    if not raw:
        return None
    try:
        start_s, dur_s = raw.split(":", 1)
        return float(start_s), float(dur_s)
    except ValueError as e:
        raise ValueError(
            f"--partition wants START:DURATION seconds, got {raw!r}"
        ) from e


def build_fault_plan(rank: int, seed: int, loss: float, duplicate: float,
                     corrupt: float, partition=None):
    """Seeded per-client fault matrix. Loss is VISIBLE (the sender sees the
    failure and retries — the at-least-once contract under test); rank
    decorrelates the client streams while keeping each reproducible.
    ``partition`` = (start_s, duration_s) cuts this client off from the
    server for the window — bidirectionally, since the server's own plan
    carries the same rule."""
    from .core.distributed.faults import FaultPlan

    plan = FaultPlan()
    if loss > 0:
        plan.loss(loss, seed=seed * 1000 + rank, visible=True)
    if duplicate > 0:
        plan.duplicate(p=duplicate, seed=seed * 2000 + rank)
    if corrupt > 0:
        plan.corrupt(p=corrupt, seed=seed * 3000 + rank)
    if partition is not None:
        plan.partition({0}, start_s=partition[0], duration_s=partition[1])
    return plan


def _resolved_heartbeat_s(a, kill_context: bool) -> float:
    """The heartbeat interval a leg actually runs with: the user's value,
    or the fast-lease default on kill legs (where the FSM is the thing
    under test). Resolving it HERE — once, for every leg — keeps the
    reference, killed, restart and client-process legs on one config."""
    hb = float(getattr(a, "heartbeat_s", 0.0) or 0.0)
    if hb <= 0 and kill_context:
        hb = 0.3
    return hb


def client_proc_cmd(a, rank: int, port: int,
                    kill_phase: str = "") -> List[str]:
    """The ONE spawn command for a real gRPC chaos client process — used
    by both the worker-owned leg (run_world) and the orchestrator-owned
    crash-failover leg, so their fault matrices can never decorrelate."""
    from fedml_tpu.traffic.swarm import python_module_cmd

    hb = _resolved_heartbeat_s(a, bool(kill_phase or _kill_phase(a)))
    cmd = python_module_cmd(
        "fedml_tpu.cli", "chaos", "--client",
        "--client_rank", str(rank), "--port", str(port),
        "--clients", str(a.clients), "--rounds", str(a.rounds),
        "--epochs", str(a.epochs), "--seed", str(a.seed),
        "--loss", str(a.loss), "--duplicate", str(a.duplicate),
        "--corrupt", str(a.corrupt),
        "--partition", str(getattr(a, "partition", "") or ""),
        "--heartbeat_s", str(hb),
        "--compression", str(getattr(a, "compression", "") or ""),
        "--compression_ratio", str(getattr(a, "compression_ratio", 0.1)),
        "--trace_dir", str(getattr(a, "trace_dir", "") or ""),
    )
    if kill_phase:
        # turns the client liveness/resync FSM on (matching the
        # _world_overrides the server legs run with)
        cmd += ["--kill-phase", kill_phase]
    return cmd


def build_server_fault_plan(a):
    """The SERVER side of the fault matrix: the kill switch (SIGKILL at a
    protocol phase) and/or its half of a partition cut. None when the
    server runs fault-free."""
    from .core.distributed.faults import FaultPlan

    plan = None
    kp = _kill_phase(a)
    if kp:
        plan = FaultPlan().kill_server(kp, int(a.kill_round))
    window = _partition_window(a)
    if window is not None:
        plan = plan or FaultPlan()
        plan.partition({0}, start_s=window[0], duration_s=window[1])
    return plan


def run_world(a, run_id: str, checkpoint_dir: str, faulty: bool,
              kill_round: int = -1, server_only: bool = False) -> Dict:
    """One cross-silo federation: server in THIS process; clients either as
    loopback threads (default) or — with ``--transport grpc`` on a faulty
    leg — as REAL client OS processes over multiprocess gRPC, spawned
    through the swarm harness's :class:`ProcSpawner` (ISSUE 7 satellite:
    chaos matrices beyond loopback). ``server_only`` runs JUST the server
    against ``a.port`` — the crash-failover flow, where the orchestrator
    owns long-lived client processes that must survive (and resync across)
    this server process's SIGKILL + restart.

    Returns {"params": leaves, "server": manager, "preempted": bool}. With
    ``kill_round >= 0`` a watcher thread SIGTERMs THIS process as soon as
    the run ledger commits that round — the real preemption path, timed
    deterministically off the durable commit rather than a sleep. With
    ``--kill-phase`` the server's fault plan SIGKILLs instead, at the
    armed protocol phase (faults.FaultPlan.kill_server).
    """
    import fedml_tpu as fedml
    from fedml_tpu import data as data_mod
    from fedml_tpu import models as model_mod
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core import runstate
    from fedml_tpu.cross_silo import FedMLCrossSiloClient, FedMLCrossSiloServer

    from fedml_tpu.parallel.multihost import free_port

    grpc_leg = (faulty and not server_only and str(
        getattr(a, "transport", "loopback")).lower() == "grpc")
    port = free_port() if grpc_leg else int(getattr(a, "port", 0) or 0)
    # the edge tier rides the FAULTY leg only: the reference leg stays a
    # flat fault-free federation, so the bitwise verdict proves a 2-tier
    # chaos run converges to EXACTLY the flat FedBuff params
    tiered = faulty and _edge_count(a) > 0
    if tiered and (grpc_leg or server_only):
        raise ValueError(
            "chaos --edges composes with the loopback transport only")

    def mk(role, rank=0):
        overrides = dict(
            _world_overrides(a), role=role, rank=rank, run_id=run_id,
            checkpoint_dir=checkpoint_dir,
            checkpoint_rounds=int(a.checkpoint_rounds),
        )
        if tiered:
            overrides.update(
                hierarchy_edges=_edge_count(a),
                hierarchy_edge_rank_base=int(a.clients) + 1,
            )
        if grpc_leg or server_only:
            overrides.update(backend="GRPC", comm_port=port,
                             comm_host="127.0.0.1")
        return fedml.init(Arguments(overrides=overrides),
                          should_init_logs=False)

    args_s = mk("server")
    if faulty:
        server_plan = build_server_fault_plan(a)
        if server_plan is not None:
            args_s.fault_plan = server_plan
    ds, od = data_mod.load(args_s)
    bundle = model_mod.create(args_s, od)
    server = FedMLCrossSiloServer(args_s, None, ds, bundle)

    edge_managers: List = []
    if tiered:
        from fedml_tpu.core.distributed.faults import FaultPlan
        from fedml_tpu.hierarchy import EdgeAggregatorManager, Topology

        topo = Topology.from_args(args_s)
        ekill = _edge_kill_phase(a)
        ewin = _edge_partition_window(a)
        for i, er in enumerate(topo.edge_ranks):
            args_e = mk("client", er)
            if i == 0 and (ekill or ewin is not None):
                # the FIRST edge is the designated failure domain: it takes
                # the kill switch (in-process fail-stop at the armed phase,
                # first hit) and/or the root-link cut; its siblings stay
                # healthy so orphaned clients have somewhere to re-home
                plan = FaultPlan()
                if ekill:
                    plan.kill_edge(ekill, -1)
                if ewin is not None:
                    plan.partition({0}, start_s=ewin[0], duration_s=ewin[1])
                args_e.fault_plan = plan
            edge = EdgeAggregatorManager(args_e, rank=er,
                                         size=topo.world_size)
            edge.run_async()
            edge_managers.append(edge)

    partition = _partition_window(a) if faulty else None
    clients = []
    spawner = None
    if server_only:
        pass  # the orchestrator owns the client processes
    elif grpc_leg:
        from fedml_tpu.traffic.swarm import ProcSpawner

        spawner = ProcSpawner()
        for rank in range(1, int(a.clients) + 1):
            spawner.spawn(client_proc_cmd(a, rank, port))
    else:
        for rank in range(1, int(a.clients) + 1):
            args_c = mk("client", rank)
            if faulty:
                args_c.fault_plan = build_fault_plan(
                    rank, int(a.seed), float(a.loss), float(a.duplicate),
                    float(a.corrupt), partition=partition,
                )
            clients.append(FedMLCrossSiloClient(args_c, None, ds, bundle))

    if kill_round >= 0 and _kill_phase(a):
        kill_round = -1  # the phase switch owns the kill; no SIGTERM watcher
    if kill_round >= 0:
        ledger = runstate.RunLedger.for_checkpoint_dir(checkpoint_dir)
        stop_watch = threading.Event()

        def watch():
            while not stop_watch.is_set():
                last = ledger.last_round()
                if last is not None and last >= kill_round:
                    logger.warning(
                        "chaos: round %d committed — SIGTERM self", last
                    )
                    os.kill(os.getpid(), signal.SIGTERM)
                    return
                time.sleep(0.02)

        watcher = threading.Thread(target=watch, daemon=True,
                                   name="chaos-kill-watcher")
        watcher.start()

    threads = [threading.Thread(target=c.run, daemon=True) for c in clients]
    for t in threads:
        t.start()
    time.sleep(0.05)
    try:
        server.run()
    except runstate.PreemptionError:
        pass  # expected under kill_round; reported via the preempted flag
    finally:
        if spawner is not None:
            # a preempted server leaves its client processes blocked on a
            # dead endpoint: reap them (the resumed leg spawns fresh ones,
            # which re-train the resumed round from its re-broadcast INIT)
            if not server.manager.preempted:
                spawner.wait_all(timeout_s=30.0)
            spawner.kill_all()
        # reap the in-process client threads: on a clean FINISH they exit
        # promptly; a preempted leg leaves them parked on a dead endpoint,
        # so the join is deadline-bounded (they are daemons — the process
        # exit that follows a preemption reclaims them)
        deadline = time.monotonic() + 5.0
        for t in threads:
            t.join(timeout=max(deadline - time.monotonic(), 0.05))
        for em in edge_managers:
            # clean FINISH already tore these down via _on_root_finish;
            # a killed edge's world is drained here instead
            em.done.set()
            em.finish()
    if kill_round >= 0:
        stop_watch.set()
        watcher.join(timeout=5.0)
    import jax

    leaves = [np.asarray(l)
              for l in jax.tree.leaves(server.manager.global_params)]
    return {
        "params": leaves,
        "server": server.manager,
        "preempted": bool(server.manager.preempted),
        "edges": edge_managers,
    }


# ---------------------------------------------------------------------------
# worker entry (the subprocess the orchestrator spawns)
# ---------------------------------------------------------------------------


def run_worker(a) -> int:
    """One chaos leg in THIS process: run the faulty world, write the final
    params + report into --out, exit EXIT_PREEMPTED if preempted. A
    ``--kill-phase`` leg never reaches the report: the armed fault plan
    SIGKILLs this process at the protocol phase — the restart leg (same
    checkpoint_dir, no kill) writes them instead."""
    from fedml_tpu.core.runstate import EXIT_PREEMPTED

    os.makedirs(a.out, exist_ok=True)
    result = run_world(
        a, run_id=f"chaos-{os.getpid()}", checkpoint_dir=a.checkpoint_dir,
        faulty=True, kill_round=int(a.kill_round),
        server_only=bool(getattr(a, "server_only", False)),
    )
    report = {
        "preempted": result["preempted"],
        "round_idx": int(result["server"].round_idx),
        "contrib_counts": {
            str(r): {str(k): v for k, v in per.items()}
            for r, per in result["server"].contrib_counts.items()
        },
    }
    if result.get("edges"):
        # the tiered leg's edge verdict half: which edges the fault plan
        # actually fail-stopped, plus the re-homing/dedup counters the
        # orchestrator gates on (everything runs in THIS process under
        # loopback, so the registry sees all tiers)
        from fedml_tpu.core.mlops import telemetry

        counters = telemetry.registry().snapshot()["counters"]
        report["edge_tier"] = {
            "edges": len(result["edges"]),
            "killed_edges": sorted(
                e.rank for e in result["edges"] if e.killed),
            "edge_kill_exercised": any(e.killed for e in result["edges"]),
            "rehomed_clients": counters.get("comm.rehomes", 0.0),
            "root_adoptions": counters.get("edge.root_adoptions", 0.0),
            "edge_rehome_adoptions": counters.get(
                "edge.rehomed_clients", 0.0),
            "resolicited_updates": counters.get(
                "edge.resolicited_updates", 0.0),
            "edge_resyncs": counters.get("comm.edge_resyncs", 0.0),
            "heartbeat_misses": counters.get("comm.heartbeat_misses", 0.0),
            "resync_replays": counters.get("comm.resync_replays", 0.0),
            "replay_dedup_drops": counters.get(
                "traffic.replay_dedup_drops", 0.0),
            "summaries_folded": counters.get("edge.summaries_folded", 0.0),
            "direct_client_updates": counters.get(
                "edge.direct_client_updates", 0.0),
        }
    with open(os.path.join(a.out, REPORT_FILE), "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    if not result["preempted"]:
        np.savez(os.path.join(a.out, FINAL_PARAMS_FILE), *result["params"])
    return EXIT_PREEMPTED if result["preempted"] else 0


# ---------------------------------------------------------------------------
# orchestrator
# ---------------------------------------------------------------------------


def _worker_cmd(a, out: str, ckpt_dir: str, kill_round: int,
                kill_phase: str = "", server_only: bool = False,
                port: int = 0) -> List[str]:
    cmd = [
        sys.executable, "-m", "fedml_tpu.cli", "chaos", "--worker",
        "--out", out, "--checkpoint_dir", ckpt_dir,
        "--clients", str(a.clients), "--rounds", str(a.rounds),
        "--epochs", str(a.epochs), "--seed", str(a.seed),
        "--loss", str(a.loss), "--duplicate", str(a.duplicate),
        "--corrupt", str(a.corrupt),
        "--checkpoint_rounds", str(a.checkpoint_rounds),
        "--kill-round", str(kill_round),
        "--kill-phase", kill_phase,
        "--partition", str(getattr(a, "partition", "") or ""),
        "--edges", str(_edge_count(a)),
        "--kill-edge", _edge_kill_phase(a),
        "--edge-partition", str(getattr(a, "edge_partition", "") or ""),
        "--transport", str(getattr(a, "transport", "loopback")),
        "--compression", str(getattr(a, "compression", "") or ""),
        "--compression_ratio", str(getattr(a, "compression_ratio", 0.1)),
        "--trace_dir", str(getattr(a, "trace_dir", "") or ""),
    ]
    if server_only:
        cmd += ["--server-only", "--port", str(port)]
    # the RESOLVED heartbeat interval reaches every leg — killed AND
    # restart (whose own kill_phase is "") — so parity never compares
    # two different FSM configs
    cmd += ["--heartbeat_s",
            str(_resolved_heartbeat_s(
                a, bool(kill_phase or server_only or _kill_phase(a))))]
    return cmd


SIGKILL_RCS = (-9, 137)  # subprocess returncode forms of a SIGKILL death


def _run_leg(cmd: List[str], timeout_s: float) -> int:
    # legs simulate devices: pinned to the CPU so they never fight the
    # parent for a chip (a chip belongs to one process)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        cmd, timeout=timeout_s, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
    )
    if proc.stdout:
        sys.stderr.write(proc.stdout.decode(errors="replace")[-4000:])
    return proc.returncode


def orchestrate(a) -> int:
    """Reference leg (in-process, fault-free) vs chaos leg (subprocess,
    faults + self-SIGTERM + resumed subprocess); verify bitwise parity and
    exactly-once contribution counting. Returns a process exit code."""
    from fedml_tpu.core.runstate import EXIT_PREEMPTED, RunLedger

    workdir = a.workdir or tempfile.mkdtemp(prefix="fedml_chaos_")
    os.makedirs(workdir, exist_ok=True)
    ref_ckpt = os.path.join(workdir, "ref_ckpt")
    chaos_ckpt = os.path.join(workdir, "chaos_ckpt")
    chaos_out = os.path.join(workdir, "chaos_out")

    logger.info("chaos: reference (fault-free) leg …")
    from fedml_tpu.core import world as world_mod

    threads_before = world_mod.thread_snapshot()
    ref = run_world(a, run_id=f"chaos-ref-{os.getpid()}-{time.time_ns()}",
                    checkpoint_dir=ref_ckpt, faulty=False)
    ref_params = ref["params"]
    # thread-leak witness (graftiso I005's runtime half): the in-process
    # world must not leak a non-daemon thread past its shutdown
    leaked = world_mod.leaked_threads(threads_before)
    if leaked:
        print(json.dumps({"ok": False,
                          "error": f"reference leg leaked threads: "
                                   f"{leaked}"}))
        return 1

    kill_round = int(a.kill_round)
    kill_phase = _kill_phase(a)
    if _edge_fault(a) and not kill_phase:
        # edge-fault legs complete in ONE worker process: the edge dies (or
        # rides out its partition) in-process and the federation must
        # survive it — the default self-SIGTERM would add an unrelated
        # server preemption on top
        kill_round = -1
    if kill_phase:
        # server-kill legs run traced: the pre-SIGKILL flight-recorder
        # flush must leave a post-mortem naming the kill phase, and the
        # killed + restarted legs' spans must merge orphan-free. Resolved
        # onto the namespace so _worker_cmd and client_proc_cmd (both
        # read ``a.trace_dir``) ship the SAME dir to every process. The
        # reference leg ran above, untraced — tracing must never be a
        # parity variable.
        a.trace_dir = (str(getattr(a, "trace_dir", "") or "")
                       or os.path.join(workdir, "trace"))
    grpc_failover = (kill_phase and str(
        getattr(a, "transport", "loopback")).lower() == "grpc")
    client_spawner = None
    port = 0
    if grpc_failover:
        # the crash-failover flow: the ORCHESTRATOR owns the client
        # processes, so they survive the server's SIGKILL and must resync
        # (heartbeat miss -> bounded reconnect -> c2s_resync -> replay)
        # onto the restarted server process at the same port
        from fedml_tpu.parallel.multihost import free_port
        from fedml_tpu.traffic.swarm import ProcSpawner

        port = free_port()
        client_spawner = ProcSpawner()
        for rank in range(1, int(a.clients) + 1):
            client_spawner.spawn(
                client_proc_cmd(a, rank, port, kill_phase=kill_phase))
    if kill_phase:
        logger.info("chaos: faulty leg (loss=%.2f dup=%.2f corrupt=%.2f, "
                    "SIGKILL at %s of round %d) …", a.loss, a.duplicate,
                    a.corrupt, kill_phase, kill_round)
    else:
        logger.info("chaos: faulty leg (loss=%.2f dup=%.2f corrupt=%.2f, "
                    "self-SIGTERM after round %d) …", a.loss, a.duplicate,
                    a.corrupt, kill_round)
    try:
        rc1 = _run_leg(
            _worker_cmd(a, chaos_out, chaos_ckpt, kill_round,
                        kill_phase=kill_phase, server_only=grpc_failover,
                        port=port),
            float(a.timeout))
        killed = rc1 == EXIT_PREEMPTED or (kill_phase
                                           and rc1 in SIGKILL_RCS)
        if not killed and rc1 != 0:
            print(json.dumps({"ok": False,
                              "error": f"chaos leg failed rc={rc1}"}))
            return 1
        if kill_phase and not killed:
            print(json.dumps({
                "ok": False,
                "error": f"kill-phase {kill_phase!r} of round {kill_round} "
                         "never fired (rc=0) — the armed phase was not "
                         "reached"}))
            return 1
        if kill_round >= 0 and not kill_phase and not killed:
            # the federation outran the watcher — still verify parity, but
            # report that preemption wasn't exercised so CI can tighten
            # knobs
            logger.warning("chaos: run completed before the SIGTERM landed")

        if killed:
            logger.info("chaos: killed as planned (rc=%d) — restarting "
                        "with --resume auto …", rc1)
            rc2 = _run_leg(
                _worker_cmd(a, chaos_out, chaos_ckpt, -1,
                            server_only=grpc_failover, port=port),
                float(a.timeout))
            if rc2 != 0:
                print(json.dumps({"ok": False,
                                  "error": f"resume leg failed rc={rc2}"}))
                return 1
        if client_spawner is not None:
            # every surviving client process must have resynced its way to
            # FINISH — a wedged resync FSM shows up here as a nonzero exit
            client_rcs = client_spawner.wait_all(
                timeout_s=float(a.timeout))
            if any(rc != 0 for rc in client_rcs):
                print(json.dumps({
                    "ok": False,
                    "error": f"client processes did not all reach FINISH "
                             f"across the server kill: {client_rcs}"}))
                return 1
    finally:
        if client_spawner is not None:
            client_spawner.kill_all()

    with np.load(os.path.join(chaos_out, FINAL_PARAMS_FILE)) as z:
        chaos_params = [z[k] for k in z.files]

    # -- verdicts -----------------------------------------------------------
    problems: List[str] = []
    if len(chaos_params) != len(ref_params):
        problems.append("param tree arity mismatch")
    else:
        for i, (x, y) in enumerate(zip(ref_params, chaos_params)):
            if x.dtype != y.dtype or x.shape != y.shape \
                    or not np.array_equal(x, y):
                problems.append(f"params leaf {i} not bitwise equal")

    ledger = RunLedger.for_checkpoint_dir(chaos_ckpt)
    rounds_seen: Dict[int, Dict] = {}
    round_counts: Dict[int, int] = {}
    double_counted: List[str] = []
    for e in ledger.rounds():
        rounds_seen[int(e["round"])] = e
        round_counts[int(e["round"])] = round_counts.get(
            int(e["round"]), 0) + 1
        for client, count in (e.get("contrib") or {}).items():
            if int(count) > 1:
                double_counted.append(
                    f"round {e['round']} client {client} counted {count}x"
                )
    if double_counted:
        problems.append("double-counted contributions: "
                        + "; ".join(double_counted))
    expect_rounds = set(range(int(a.rounds)))
    missing = expect_rounds - set(rounds_seen)
    if missing:
        problems.append(f"ledger missing committed rounds: {sorted(missing)}")
    if kill_phase:
        # a SIGKILL never drains, so no crash round is ever committed
        # twice: the combined ledger must hold EXACTLY one entry per round
        dups = sorted(r for r, n in round_counts.items() if n > 1)
        if dups:
            problems.append(
                f"ledger committed rounds more than once: {dups}")
    full_cohort = list(range(1, int(a.clients) + 1))
    bad_cohorts = [r for r, e in sorted(rounds_seen.items())
                   if sorted(e.get("cohort") or []) != full_cohort]
    if bad_cohorts:
        problems.append(f"rounds aggregated a partial cohort: {bad_cohorts}")

    edge_block = None
    if _edge_count(a) > 0:
        # tiered-leg verdict half: the worker's report must show the armed
        # edge fault actually fired AND the orphans found a new home —
        # a leg that never exercised the failure domain proves nothing
        try:
            with open(os.path.join(chaos_out, REPORT_FILE),
                      encoding="utf-8") as f:
                edge_block = (json.load(f) or {}).get("edge_tier")
        except (OSError, ValueError):
            edge_block = None
        if not edge_block:
            problems.append("tiered leg wrote no edge_tier report block")
        else:
            if float(edge_block.get("direct_client_updates", 0) or 0) > 0 \
                    and not _edge_kill_phase(a):
                # direct updates are LEGAL only as the degraded mode an
                # edge death forces; any other leg must stay two-tier
                problems.append("root folded direct client updates in a "
                                "fault-free edge tier")
            if _edge_kill_phase(a):
                if not edge_block.get("edge_kill_exercised"):
                    problems.append(
                        f"edge kill phase {_edge_kill_phase(a)!r} never "
                        "fired — the armed phase was not reached")
                rehomed = (float(edge_block.get("rehomed_clients", 0) or 0)
                           + float(edge_block.get("root_adoptions", 0)
                                   or 0))
                if rehomed <= 0:
                    problems.append(
                        "edge kill leg saw no client re-homing")
            if _edge_partition_window(a) is not None:
                cut_seen = (
                    float(edge_block.get("heartbeat_misses", 0) or 0)
                    + float(edge_block.get("resync_replays", 0) or 0))
                if cut_seen <= 0:
                    problems.append(
                        "root–edge partition leg never exercised the "
                        "edge resync FSM (no heartbeat miss, no replay)")

    flight_verdict = None
    trace_spans = None
    trace_orphans = None
    if kill_phase:
        flight_verdict, trace_spans, trace_orphans = _trace_verdict(
            str(a.trace_dir), kill_phase, kill_round, problems)

    verdict = {
        "ok": not problems,
        "parity": not any("leaf" in p or "arity" in p for p in problems),
        "preemption_exercised": bool(killed),
        "rounds": int(a.rounds),
        "clients": int(a.clients),
        "fault_matrix": {"loss": float(a.loss),
                         "duplicate": float(a.duplicate),
                         "corrupt": float(a.corrupt),
                         "seed": int(a.seed),
                         "kill_phase": kill_phase or None,
                         "partition": str(getattr(a, "partition", "")
                                          or "") or None,
                         "edges": _edge_count(a) or None,
                         "kill_edge": _edge_kill_phase(a) or None,
                         "edge_partition": str(getattr(a, "edge_partition",
                                                       "") or "") or None},
        "edge_tier": edge_block,
        "problems": problems,
        "workdir": workdir,
        "flight_recorder": flight_verdict,
        "trace_spans": trace_spans,
        "trace_orphans": trace_orphans,
    }
    print(json.dumps(verdict, indent=2, sort_keys=True))
    return 0 if verdict["ok"] else 1


def _trace_verdict(trace_dir: str, kill_phase: str, kill_round: int,
                   problems: List[str]):
    """Traced kill-leg verdict half: (a) a pre-SIGKILL flight-recorder
    post-mortem exists and its last phase mark names EXACTLY the armed
    kill phase+round; (b) the killed and restarted legs' spans merge into
    one orphan-free trace (flight rings recover the dead process's tail).
    Appends failures to ``problems``; returns the verdict fields."""
    import glob as glob_mod

    from fedml_tpu.core.mlops import tracing

    flight = None
    for path in sorted(glob_mod.glob(
            os.path.join(trace_dir, "flight_*_rank_0.json"))):
        try:
            with open(path, encoding="utf-8") as f:
                post = json.load(f)
        except (OSError, ValueError):
            continue
        if str(post.get("reason", "")).startswith("kill_server:"):
            flight = post
            break
    flight_verdict: Optional[Dict] = None
    if flight is None:
        problems.append(
            "no pre-SIGKILL flight-recorder post-mortem in trace dir")
    else:
        last = flight.get("last_phase") or {}
        flight_verdict = {"reason": flight.get("reason"),
                          "phase": last.get("phase"),
                          "round": last.get("round"),
                          "open_spans": len(flight.get("open_spans") or [])}
        if last.get("phase") != kill_phase:
            problems.append(
                f"post-mortem names phase {last.get('phase')!r}, "
                f"expected {kill_phase!r}")
        elif int(last.get("round", -1)) != int(kill_round):
            problems.append(
                f"post-mortem names round {last.get('round')}, "
                f"expected {kill_round}")
    spans, clocks = tracing.read_trace(
        tracing.collect_trace_files(trace_dir))
    merged = tracing.merge_trace(spans, clocks)
    if not merged["spans"]:
        problems.append("traced kill leg produced no spans")
    if merged["orphans"]:
        problems.append(
            f"merged trace has orphan spans: {merged['orphans'][:5]}")
    return flight_verdict, len(merged["spans"]), len(merged["orphans"])


def run_client_worker(a) -> int:
    """One REAL cross-silo client as its own OS process — the multiprocess
    gRPC chaos leg's client side, spawned by the chaos worker's
    ProcSpawner. It builds its own fault plan from the matrix flags (the
    same seeding as the loopback leg, so the fault stream per rank is
    transport-independent) and runs the production client FSM to FINISH."""
    import fedml_tpu as fedml
    from fedml_tpu import data as data_mod
    from fedml_tpu import models as model_mod
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.cross_silo import FedMLCrossSiloClient

    rank = int(a.client_rank)
    overrides = dict(
        _world_overrides(a), role="client", rank=rank,
        run_id=f"chaos-grpc-{rank}", backend="GRPC",
        comm_port=int(a.port), comm_host="127.0.0.1",
    )
    args_c = fedml.init(Arguments(overrides=overrides),
                        should_init_logs=False)
    args_c.fault_plan = build_fault_plan(
        rank, int(a.seed), float(a.loss), float(a.duplicate),
        float(a.corrupt), partition=_partition_window(a),
    )
    ds, od = data_mod.load(args_c)
    bundle = model_mod.create(args_c, od)
    client = FedMLCrossSiloClient(args_c, None, ds, bundle)
    client.run()
    return 0 if client.manager.done.is_set() else 1


def main(a) -> int:
    if getattr(a, "client", False):
        return run_client_worker(a)
    if a.worker:
        return run_worker(a)
    return orchestrate(a)
