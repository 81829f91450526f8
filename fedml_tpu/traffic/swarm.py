"""Client-swarm traffic generator: soak the cross-silo server at scale.

reference: none — the reference framework was never load-tested (one server,
a handful of loopback clients; SURVEY §5). ``fedml_tpu swarm`` drives the
REAL server FSM (``FedMLServerManager`` in ``aggregation_mode=async``)
with thousands of concurrent simulated devices:

- each device runs the genuine client-side wire protocol (ONLINE status →
  version-tagged INIT/SYNC → C2S model upload → shed/NACK backoff →
  FINISH) through the real transport (loopback broker or multiprocess
  gRPC), with **seeded processes** for think time (exponential — the
  Poisson-arrival analog per device) and dropout, so a soak is
  reproducible;
- devices are *event-driven*, not thread-per-device: over loopback a
  single pump thread drains every device mailbox and one timer wheel
  schedules the delayed sends, so 2000 devices cost 2 threads, not 2000;
- the report's headline is the **p99 dispatch→ready latency** from the PR 2
  telemetry plane (``traffic.dispatch_ready_s``: server-side admission →
  update folded), next to the backpressure counters (accepted / shed /
  stale-dropped), staleness distribution, achieved server steps, and peak
  RSS — the "bounded memory under overload" evidence.

The :class:`ProcSpawner` here is the one process-launch surface shared with
the chaos harness's multiprocess-gRPC legs (ISSUE 7 satellite).
"""

from __future__ import annotations

import heapq
import json
import logging
import os
import subprocess
import sys
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from .. import constants
from ..core import world as world_mod
from ..core.distributed import FedMLCommManager, Message
from ..core.mlops import telemetry
from ..core.mlops.tracing import NULL_SPAN
from ..cross_silo.message_define import MyMessage

logger = logging.getLogger(__name__)


def rss_peak_mb() -> float:
    """Peak resident set of THIS process (ru_maxrss is KiB on Linux)."""
    try:
        import resource

        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    except Exception:  # pragma: no cover - non-posix
        return 0.0


def rss_now_mb() -> float:
    """CURRENT resident set from /proc/self/status VmRSS (kB). ru_maxrss
    is a high-water mark — useless for a leak slope, which needs the live
    value falling as well as rising."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return float(line.split()[1]) / 1024.0
    except Exception:  # pragma: no cover - non-linux
        pass
    return 0.0


class RssSampler:
    """The graftmem runtime witness's sampler: VmRSS on a fixed cadence
    from a daemon thread, joined by :meth:`stop`.

    :meth:`slope_mb_per_s` fits a least-squares line over the STEADY-STATE
    half of the samples (the second half by time) — the first half is
    warmup (imports, first compiles, buffer fills) and would make every
    healthy soak look like a leak. A retention bug shows as a positive
    slope that persists after warmup: one entry per message/sender/round
    never released is linear growth under constant load by definition.
    """

    def __init__(self, interval_s: float = 0.2):
        self.interval_s = max(float(interval_s), 0.01)
        self._lock = threading.Lock()
        self._samples: List[Tuple[float, float]] = []  # (t_monotonic, MB)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="rss-sampler")

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5.0)

    def samples(self) -> List[Tuple[float, float]]:
        with self._lock:
            return list(self._samples)

    def _loop(self) -> None:
        while not self._stop.is_set():
            with self._lock:
                self._samples.append((time.monotonic(), rss_now_mb()))
            self._stop.wait(self.interval_s)
        with self._lock:
            self._samples.append((time.monotonic(), rss_now_mb()))

    def slope_mb_per_s(self) -> Optional[float]:
        """Least-squares dRSS/dt over the steady-state (second) half; None
        with fewer than 4 steady-state samples (no signal, not a pass)."""
        samples = self.samples()
        if not samples:
            return None
        t_mid = (samples[0][0] + samples[-1][0]) / 2.0
        steady = [(t, m) for (t, m) in samples if t >= t_mid]
        if len(steady) < 4:
            return None
        n = float(len(steady))
        mean_t = sum(t for t, _ in steady) / n
        mean_m = sum(m for _, m in steady) / n
        var_t = sum((t - mean_t) ** 2 for t, _ in steady)
        if var_t <= 0.0:
            return None
        cov = sum((t - mean_t) * (m - mean_m) for t, m in steady)
        return cov / var_t


# ---------------------------------------------------------------------------
# seeded device processes
# ---------------------------------------------------------------------------


class SwarmSchedule:
    """Per-device seeded think-time + dropout process.

    Think times are exponential with mean ``think_s`` — superposed over N
    devices that is a Poisson arrival process at the server. The stream
    depends only on (seed, rank), never on wall-clock or delivery order, so
    a swarm's *schedule* is deterministic (pinned by tests/test_traffic.py).
    """

    def __init__(self, seed: int, rank: int, think_s: float,
                 dropout_p: float):
        self.rank = int(rank)
        self.think_s = float(think_s)
        self.dropout_p = float(dropout_p)
        self._rng = np.random.RandomState(
            (int(seed) * 1_000_003 + int(rank)) % (2**31 - 1))

    def next_think_s(self) -> float:
        if self.think_s <= 0:
            return 0.0
        return float(self._rng.exponential(self.think_s))

    def drops_out(self) -> bool:
        return bool(self._rng.rand() < self.dropout_p)


class TimerWheel:
    """One thread, many delayed callbacks (heapq): the thread-per-Timer
    alternative melts at swarm scale (every backoff would be an OS
    thread)."""

    def __init__(self):
        self._heap: List = []
        self._seq = 0
        self._cv = threading.Condition()
        self._stop = False
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="swarm-timers")
        self._thread.start()

    def call_later(self, delay_s: float, fn: Callable[[], None]) -> None:
        with self._cv:
            self._seq += 1
            heapq.heappush(
                self._heap, (time.monotonic() + max(delay_s, 0.0),
                             self._seq, fn))
            self._cv.notify()

    def stop(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify()
        self._thread.join(timeout=5)

    def _loop(self) -> None:
        while True:
            with self._cv:
                if self._stop:
                    return
                if not self._heap:
                    self._cv.wait(timeout=0.5)
                    continue
                when, _seq, fn = self._heap[0]
                now = time.monotonic()
                if when > now:
                    self._cv.wait(timeout=min(when - now, 0.5))
                    continue
                heapq.heappop(self._heap)
            try:
                fn()
            except Exception:  # a dead server mid-shutdown: keep ticking
                logger.debug("swarm timer callback failed", exc_info=True)


# ---------------------------------------------------------------------------
# the simulated device
# ---------------------------------------------------------------------------


class SwarmClientManager(FedMLCommManager):
    """A lightweight simulated device speaking the full cross-silo client
    protocol. It does not train: after a seeded think time it echoes the
    dispatched model back as its update (num_samples=1), which exercises
    every server-side path (admission, staleness, folding, aggregation)
    with realistic payload bytes at a per-device cost that scales to
    thousands.

    With ``delta_capable=True`` the device also speaks the S2C delta plane
    (docs/delivery.md): it advertises ``delta_capable`` on its C2S
    updates, keeps a small version-indexed base store, and decodes delta
    frames against the global it last held — so a swarm soak exercises the
    server's per-base encode cache and ACK tracking at scale, not just
    full-frame dispatch."""

    def __init__(self, args, schedule: SwarmSchedule, timers: TimerWheel,
                 comm=None, rank: int = 0, size: int = 0,
                 backend: str = constants.COMM_BACKEND_LOOPBACK,
                 delta_capable: bool = False):
        super().__init__(args, comm, rank, size, backend)
        self.schedule = schedule
        self.timers = timers
        self.done = threading.Event()
        # tiered worlds: a device speaks to its home edge aggregator, not
        # the root — the same wire protocol, one hop down
        from ..hierarchy import Topology

        topo = Topology.from_args(args)
        self._server_rank = (topo.home_edge(rank)
                             if topo is not None and topo.is_client(rank)
                             else 0)
        # (_version, _arrays) is a PAIR: the receive thread updates it on
        # dispatch while the timer wheel snapshots it at send time — the
        # lock keeps a delayed send from tagging version v on version
        # v+1's payload, which would corrupt the server's staleness
        # accounting (the orchestrator itself only reads the done Event
        # and the process-wide telemetry counters)
        self._state_lock = threading.Lock()
        self._version = -1
        self._arrays: List[np.ndarray] = []
        # the dispatch's wire trace context, snapshotted WITH the version
        # it arrived under: the ambient context is thread-local to the
        # receive path, and the delayed send runs on the timer-wheel
        # thread — without this hand-off the device's upload would start a
        # fresh trace instead of continuing the server's dispatch span
        self._trace_ctx = None
        self._dropped = False
        self._delta_on = bool(delta_capable)
        self._store = None
        self._leaf_meta: Optional[List] = None
        if self._delta_on:
            from ..delivery import VersionedModelStore, WireCodec

            self._store = VersionedModelStore(
                4, metric_prefix="swarm.delta_store")
            self._wire = WireCodec(getattr(args, "wire_path", "auto"),
                                   scoped=self.world.telemetry)

    def register_message_receive_handlers(self) -> None:
        self.register_message_receive_handler(
            MyMessage.MSG_TYPE_CONNECTION_IS_READY, self._on_ready
        )
        self.register_message_receive_handler(
            MyMessage.MSG_TYPE_S2C_INIT_CONFIG, self._on_dispatch
        )
        self.register_message_receive_handler(
            MyMessage.MSG_TYPE_S2C_SYNC_MODEL_TO_CLIENT, self._on_dispatch
        )
        self.register_message_receive_handler(
            MyMessage.MSG_TYPE_S2C_SHED_NOTICE, self._on_shed
        )
        self.register_message_receive_handler(
            MyMessage.MSG_TYPE_S2C_FINISH, self._on_finish
        )

    def _on_ready(self, msg: Message) -> None:
        self._announce_online()

    def _announce_online(self) -> None:
        """ONLINE announcement — also the delta-base-missing recovery (the
        server clears this device's ACK on receipt, so the next dispatch
        falls back to a full frame)."""
        status = Message(MyMessage.MSG_TYPE_C2S_CLIENT_STATUS, self.rank,
                         self._server_rank)
        status.add(MyMessage.MSG_ARG_KEY_CLIENT_STATUS,
                   MyMessage.CLIENT_STATUS_ONLINE)
        self._send_quiet(status)

    def _decode_frame(self, version: int, arrays,
                      dmeta) -> Optional[List[np.ndarray]]:
        """Delta-plane decode of one dispatch: full frames refresh the base
        store; delta frames decode against the stored base (or trigger the
        ONLINE resync when that base is gone)."""
        from ..delivery import flatten_leaves
        from ..delivery.device_codec import host_view

        if dmeta is None:
            self._leaf_meta = [(np.asarray(a).shape, np.asarray(a).dtype)
                               for a in arrays]
            self._store.put(version, flatten_leaves(arrays))
            return list(arrays)
        on_device = self._wire.path == "device"
        base = (self._store.get_device(int(dmeta["base_version"]))
                if on_device else self._store.get(int(dmeta["base_version"])))
        if base is None or self._leaf_meta is None:
            self.world.telemetry.counter_inc("swarm.delta_base_missing")
            self._announce_online()
            return None
        vec = self._wire.decode(base, arrays, dmeta)
        if isinstance(vec, np.ndarray):
            self._store.put(version, vec)
        else:
            # device decode: keep the device buffer as the next base and
            # slice the per-leaf views off the (dlpack) host view
            dev = vec
            vec = host_view(dev, scoped=self.world.telemetry)
            self._store.put(version, vec, device=dev)
        self.world.telemetry.counter_inc("swarm.delta_decodes")
        out, off = [], 0
        for shape, dtype in self._leaf_meta:
            n = int(np.prod(shape, dtype=np.int64))
            out.append(np.asarray(vec[off:off + n],
                                  dtype=dtype).reshape(shape))
            off += n
        return out

    def _on_dispatch(self, msg: Message) -> None:
        version = int(msg.get(MyMessage.MSG_ARG_KEY_ROUND_IDX, 0))
        with self._state_lock:
            if version <= self._version:
                # replayed/stale dispatch: checked BEFORE the delta decode
                # so a duplicated frame can never pollute the base store,
                # inflate decode counters, or fire the ONLINE resync (which
                # would clear the server's ACK and silently degrade this
                # device to full frames)
                return
        arrays = msg.get_arrays()
        if self._delta_on:
            from ..delivery.delta_codec import DELTA_KEY

            arrays = self._decode_frame(version, arrays, msg.get(DELTA_KEY))
            if arrays is None:
                return  # undecodable delta: resynced via ONLINE instead
        with self._state_lock:
            if version <= self._version:
                return  # a fresher dispatch landed during the decode
            self._version = version
            self._arrays = arrays
            self._trace_ctx = self.world.trace.current_context()
        if self._dropped:
            return  # silent device: receives, never answers
        if self.schedule.drops_out():
            self._dropped = True
            self.world.telemetry.counter_inc("swarm.dropouts")
            return
        self.timers.call_later(
            self.schedule.next_think_s(),
            lambda v=version: self._send_update(v),
        )

    def _send_update(self, version: int) -> None:
        if self.done.is_set():
            return
        with self._state_lock:
            if version != self._version:
                return  # a fresher dispatch superseded this one
            arrays = self._arrays
            ctx = self._trace_ctx
        out = Message(
            MyMessage.MSG_TYPE_C2S_SEND_MODEL_TO_SERVER, self.rank,
            self._server_rank)
        out.add(MyMessage.MSG_ARG_KEY_ROUND_IDX, version)
        out.add(MyMessage.MSG_ARG_KEY_NUM_SAMPLES, 1.0)
        if self._delta_on:
            # ACK: this version becomes the server's S2C delta base for us
            out.add(MyMessage.MSG_ARG_KEY_DELTA_CAPABLE, 1)
        out.set_arrays(arrays)
        # continue the dispatch's trace across the think-time hop: the
        # upload span parents to the server's dispatch span, and
        # send_message stamps ITS context onto the C2S wire — a shed
        # retry is a genuinely new upload attempt, so it gets a new span
        # (transport-level retries inside send stay events, never spans)
        sp = (self.world.trace.span("upload", ctx=ctx, client=self.rank)
              if ctx is not None else NULL_SPAN)
        with sp:
            self.world.telemetry.counter_inc("swarm.updates_sent")
            self._send_quiet(out)

    def _on_shed(self, msg: Message) -> None:
        shed_version = int(msg.get(MyMessage.MSG_ARG_KEY_ROUND_IDX, -1))
        with self._state_lock:
            current = self._version
        if shed_version != current or self._dropped:
            return
        retry_s = max(
            float(msg.get(MyMessage.MSG_ARG_KEY_RETRY_AFTER_S, 0.1)), 0.01)
        self.world.telemetry.counter_inc("swarm.retries")
        self.timers.call_later(
            retry_s, lambda v=shed_version: self._send_update(v))

    def _on_finish(self, msg: Message) -> None:
        self.done.set()
        self.finish()

    def _send_quiet(self, msg: Message) -> None:
        try:
            self.send_message(msg)
        except Exception:
            # the server is gone (soak teardown, chaos kill): a traffic
            # generator must absorb that, not crash the swarm
            self.world.telemetry.counter_inc("swarm.send_failures")


# ---------------------------------------------------------------------------
# loopback pump: 2000 devices on one thread
# ---------------------------------------------------------------------------


class LoopbackPump:
    """Drains every device's loopback mailbox on ONE thread and dispatches
    through the managers' normal ``receive_message`` path (dedup window,
    payload fetch, handlers) — the event-driven replacement for a
    receive-loop thread per device."""

    def __init__(self, world: str):
        from ..core.distributed.loopback import _Broker

        self.broker = _Broker.get(world)
        self.devices: Dict[int, SwarmClientManager] = {}
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="swarm-pump")

    def add(self, device: SwarmClientManager) -> None:
        # setup-phase only: every add() happens before start(), whose
        # Thread.start() publishes the finished dict to the pump thread
        # (the same discipline as FedMLCommManager.register_comm_manager)
        device.register_message_receive_handlers()
        self.devices[device.rank] = device  # graftlint: disable=G005

    def start(self) -> None:
        # synthetic connection-ready per device, exactly like the backend's
        # own receive loop would emit
        for rank, dev in self.devices.items():
            dev.receive_message(
                MyMessage.MSG_TYPE_CONNECTION_IS_READY,
                Message(MyMessage.MSG_TYPE_CONNECTION_IS_READY, rank, rank),
            )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5)

    def _loop(self) -> None:
        from ..core.distributed.delivery import safe_deserialize

        while not self._stop.is_set():
            drained = 0
            for rank, dev in self.devices.items():
                q = self.broker.queue_for(rank)
                for _ in range(32):  # bounded burst per device per sweep
                    try:
                        data = q.get_nowait()
                    except Exception:
                        break
                    msg = safe_deserialize(data, "swarm-pump")
                    if msg is not None:
                        dev.receive_message(msg.get_type(), msg)
                    drained += 1
            if drained == 0:
                time.sleep(0.002)


# ---------------------------------------------------------------------------
# process spawner (shared with the chaos harness's gRPC legs)
# ---------------------------------------------------------------------------


class ProcSpawner:
    """Launch + supervise worker OS processes. One definition serves the
    swarm's multiprocess-gRPC device hosts AND the chaos harness's real
    multiprocess client legs."""

    def __init__(self, cwd: Optional[str] = None):
        self.cwd = cwd or os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        self.procs: List[subprocess.Popen] = []

    def spawn(self, cmd: List[str]) -> subprocess.Popen:
        # workers simulate devices: pinned to the CPU so they never fight
        # the parent for a chip (a chip belongs to one process)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(cmd, cwd=self.cwd, env=env)
        self.procs.append(proc)
        return proc

    def wait_all(self, timeout_s: float) -> List[Optional[int]]:
        deadline = time.monotonic() + timeout_s
        codes: List[Optional[int]] = []
        for p in self.procs:
            left = max(deadline - time.monotonic(), 0.1)
            try:
                codes.append(p.wait(timeout=left))
            except subprocess.TimeoutExpired:
                codes.append(None)
        return codes

    def kill_all(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.terminate()
        for p in self.procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=5)
        self.procs.clear()


def python_module_cmd(module: str, *args: str) -> List[str]:
    return [sys.executable, "-m", module, *args]


# ---------------------------------------------------------------------------
# orchestrator
# ---------------------------------------------------------------------------


def _s2c_delta(a) -> str:
    return str(getattr(a, "s2c_delta", "off") or "off").lower()


def _wire_path(a) -> str:
    return str(getattr(a, "wire_path", "auto") or "auto").lower()


def _trace_on(a) -> bool:
    return bool(getattr(a, "trace", False))


def _trace_sample(a) -> float:
    raw = getattr(a, "trace_sample", None)
    return 1.0 if raw is None else max(0.0, min(1.0, float(raw)))


def _trace_dir(a) -> str:
    """Shared span-sink directory for the soak: every process (server,
    loopback devices, gRPC device hosts) writes here so the merge sees one
    federation. Per-run by default so stale files from earlier soaks can
    never pollute the reconciliation."""
    explicit = str(getattr(a, "trace_dir", "") or "")
    if explicit:
        return explicit
    return os.path.join(".fedml_tpu_runs", f"trace_{a.run_id}")


def _trace_overrides(a) -> Dict:
    """Tracing knobs for a soak participant's Arguments: spans persist
    through the PR 2 JSONL sink, so a traced soak also turns tracking on,
    pointed at the shared trace dir."""
    if not _trace_on(a):
        return {}
    return dict(
        enable_tracing=True,
        trace_sample=_trace_sample(a),
        trace_dir=_trace_dir(a),
        enable_tracking=True,
        tracking_dir=_trace_dir(a),
    )


def _server_overrides(a) -> Dict:
    return dict(
        training_type="cross_silo", dataset="synthetic", model="lr",
        client_num_in_total=int(a.clients),
        client_num_per_round=int(a.clients),
        comm_round=int(a.steps), epochs=1, batch_size=8, learning_rate=0.2,
        random_seed=int(a.seed), role="server", rank=0,
        run_id=str(a.run_id),
        s2c_delta=_s2c_delta(a),
        wire_path=_wire_path(a),
        aggregation_mode="async",
        async_buffer_size=int(a.buffer),
        async_staleness_alpha=float(a.staleness_alpha),
        async_max_staleness=int(a.max_staleness),
        async_flush_s=float(a.flush_s),
        async_admit_rate=float(a.admit_rate),
        async_admit_burst=int(a.admit_burst),
        async_queue_limit=int(a.queue_limit),
        # eval only the final step: the soak measures the traffic plane,
        # not the model
        frequency_of_the_test=10**9,
        **_trace_overrides(a),
    )


def _device_args(a, rank: int, backend: str):
    import fedml_tpu as fedml
    from ..arguments import Arguments

    overrides = dict(
        training_type="cross_silo", dataset="synthetic", model="lr",
        client_num_in_total=int(a.clients),
        client_num_per_round=int(a.clients),
        comm_round=int(a.steps), role="client", rank=int(rank),
        run_id=str(a.run_id), backend=backend,
        random_seed=int(a.seed),
        wire_path=_wire_path(a),
        **_hierarchy_overrides(a, backend),
        **_trace_overrides(a),
    )
    if backend == constants.COMM_BACKEND_GRPC:
        overrides.update(
            comm_port=int(a.port), comm_host="127.0.0.1",
            grpc_ranks_per_port=_ranks_per_port(a),
        )
    return fedml.init(Arguments(overrides=overrides), should_init_logs=False)


def _ranks_per_port(a) -> int:
    """Resolved gRPC rank→port multiplexing for a swarm config: an explicit
    ``--ranks_per_port``, else one port per device-host process (the
    per-process rank-block size) — 2000 devices over 8 processes cost 9
    listening ports instead of 2001. 1 = legacy port-per-rank."""
    explicit = int(getattr(a, "ranks_per_port", 0) or 0)
    if explicit > 0:
        return explicit
    procs = max(int(getattr(a, "procs", 1) or 1), 1)
    return max((int(a.clients) + procs - 1) // procs, 1)


def _edge_count(a) -> int:
    """Edge aggregators for this soak: 0 = flat FedBuff. An explicit
    ``--edges`` wins; a bare ``--tiers 2`` derives roughly one edge per
    100 devices (min 2 so failover always has a sibling, max 64)."""
    explicit = int(getattr(a, "edges", 0) or 0)
    if explicit > 0:
        return explicit
    if int(getattr(a, "tiers", 1) or 1) < 2:
        return 0
    return max(2, min(int(a.clients) // 100, 64))


def _edge_rank_base(a, backend: str) -> int:
    """First edge rank: clients+1, pushed up to the next rank→port block
    boundary under gRPC so the edge ranks (which live in the orchestrator
    process) never share a port group with a device-host process."""
    n = int(a.clients)
    if backend != constants.COMM_BACKEND_GRPC:
        return n + 1
    per = _ranks_per_port(a)
    return ((n + per - 1) // per) * per + 1


def _hierarchy_overrides(a, backend: str) -> Dict:
    """Topology knobs every tiered-soak participant (root, edges, devices)
    must agree on — Topology.from_args keys off these."""
    edges = _edge_count(a)
    if edges <= 0:
        return {}
    return dict(hierarchy_edges=edges,
                hierarchy_edge_rank_base=_edge_rank_base(a, backend))


def _edge_args(a, rank: int, backend: str):
    """Arguments for one in-orchestrator edge aggregator: async mode to
    mirror the root's fold plane, plus the shared topology knobs."""
    import fedml_tpu as fedml
    from ..arguments import Arguments

    overrides = dict(
        training_type="cross_silo", dataset="synthetic", model="lr",
        client_num_in_total=int(a.clients),
        client_num_per_round=int(a.clients),
        comm_round=int(a.steps), role="client", rank=int(rank),
        run_id=str(a.run_id), backend=backend,
        random_seed=int(a.seed),
        wire_path=_wire_path(a),
        aggregation_mode="async",
        async_buffer_size=int(a.buffer),
        **_hierarchy_overrides(a, backend),
        **_trace_overrides(a),
    )
    if backend == constants.COMM_BACKEND_GRPC:
        overrides.update(
            comm_port=int(a.port), comm_host="127.0.0.1",
            grpc_ranks_per_port=_ranks_per_port(a),
        )
    return fedml.init(Arguments(overrides=overrides), should_init_logs=False)


def _percentiles(hist_summary: Optional[dict]) -> Dict:
    if not hist_summary:
        return {"count": 0, "sum": None,
                "p50": None, "p95": None, "p99": None}
    return {k: hist_summary.get(k)
            for k in ("count", "sum", "p50", "p95", "p99")}


def run_swarm(a) -> int:
    """The ``fedml_tpu swarm`` CLI entry: run the soak, print the JSON
    report, return a process exit code."""
    backend = str(a.backend).upper()
    if backend not in (constants.COMM_BACKEND_LOOPBACK,
                       constants.COMM_BACKEND_GRPC):
        print(json.dumps({"ok": False,
                          "error": f"unsupported swarm backend {backend}"}))
        return 2
    report = swarm_soak(a)
    print(json.dumps(report, indent=2, sort_keys=True))
    return 0 if report["ok"] else 1


def swarm_soak(a) -> Dict:
    """The orchestrator: async server + N-device swarm; returns the soak
    report (tests call this directly; the CLI prints it)."""
    import fedml_tpu as fedml
    from .. import data as data_mod
    from .. import models as model_mod
    from ..arguments import Arguments
    from ..cross_silo import FedMLCrossSiloServer

    backend = str(a.backend).upper()
    telemetry.registry().reset()
    # thread-leak witness (graftiso I005's runtime half): every thread the
    # soak starts must be gone — or at least daemonic and world-joined —
    # after world shutdown; a leaked non-daemon thread fails the soak
    threads_before = world_mod.thread_snapshot()
    # memory-leak witness (graftmem's runtime half): VmRSS sampled across
    # the soak; a positive steady-state slope fails it
    sampler: Optional[RssSampler] = None
    if getattr(a, "leak_check", False):
        sampler = RssSampler(float(getattr(a, "leak_interval", 0.2)))
        sampler.start()
    t0 = time.monotonic()

    edges_n = _edge_count(a)
    edge_base = _edge_rank_base(a, backend)
    world_size = (edge_base + edges_n) if edges_n else int(a.clients) + 1

    server_over = dict(_server_overrides(a), backend=backend,
                       **_hierarchy_overrides(a, backend))
    if backend == constants.COMM_BACKEND_GRPC:
        server_over.update(comm_port=int(a.port), comm_host="127.0.0.1",
                           grpc_ranks_per_port=_ranks_per_port(a))
    args_s = fedml.init(Arguments(overrides=server_over),
                        should_init_logs=False)
    ds, od = data_mod.load(args_s)
    bundle = model_mod.create(args_s, od)
    server = FedMLCrossSiloServer(args_s, None, ds, bundle)

    timers = TimerWheel()
    pump: Optional[LoopbackPump] = None
    spawner: Optional[ProcSpawner] = None
    devices: List[SwarmClientManager] = []
    edge_managers: List = []
    server_thread: Optional[threading.Thread] = None
    try:
        if edges_n:
            # the edge tier lives in the orchestrator process: E is small
            # (devices are the thing that scales), and keeping the edges
            # here lets the report read their counters directly. Each edge
            # is a first-class manager with its own receive loop.
            from ..hierarchy import EdgeAggregatorManager

            for er in range(edge_base, edge_base + edges_n):
                eargs = _edge_args(a, er, backend)
                if backend == constants.COMM_BACKEND_LOOPBACK:
                    from ..core.distributed.loopback import (
                        LoopbackCommManager,
                    )

                    edge = EdgeAggregatorManager(
                        eargs,
                        comm=LoopbackCommManager(er, world_size,
                                                 str(a.run_id)),
                        rank=er, size=world_size,
                    )
                else:
                    edge = EdgeAggregatorManager(
                        eargs, rank=er, size=world_size,
                        backend=constants.COMM_BACKEND_GRPC,
                    )
                edge.run_async()
                edge_managers.append(edge)

        if backend == constants.COMM_BACKEND_LOOPBACK:
            from ..core.distributed.loopback import LoopbackCommManager

            pump = LoopbackPump(str(a.run_id))
            n = int(a.clients)
            for rank in range(1, n + 1):
                dev = SwarmClientManager(
                    _device_args(a, rank, backend),
                    SwarmSchedule(int(a.seed), rank, float(a.think_s),
                                  float(a.dropout)),
                    timers,
                    comm=LoopbackCommManager(rank, world_size,
                                             str(a.run_id)),
                    rank=rank, size=world_size,
                    delta_capable=_s2c_delta(a) != "off",
                )
                devices.append(dev)
                pump.add(dev)
        else:
            spawner = ProcSpawner()
            procs = max(int(a.procs), 1)
            base = 1
            per = (int(a.clients) + procs - 1) // procs
            for _ in range(procs):
                count = min(per, int(a.clients) - base + 1)
                if count <= 0:
                    break
                cmd = python_module_cmd(
                    "fedml_tpu.cli", "swarm", "--worker",
                    "--rank_base", str(base), "--count", str(count),
                    "--clients", str(a.clients), "--steps", str(a.steps),
                    "--port", str(a.port), "--seed", str(a.seed),
                    "--think_s", str(a.think_s), "--dropout",
                    str(a.dropout), "--run_id", str(a.run_id),
                    "--timeout", str(a.timeout),
                    "--procs", str(a.procs),
                    "--ranks_per_port", str(_ranks_per_port(a)),
                    "--s2c_delta", _s2c_delta(a),
                    "--wire_path", _wire_path(a),
                )
                if edges_n:
                    # explicit count so worker processes resolve the same
                    # topology (edge count + rank base) as the orchestrator
                    cmd += ["--edges", str(edges_n)]
                if _trace_on(a):
                    # device hosts join the same trace: the resolved dir is
                    # passed explicitly so orchestrator and workers agree
                    cmd += ["--trace",
                            "--trace_sample", str(_trace_sample(a)),
                            "--trace_dir", _trace_dir(a)]
                spawner.spawn(cmd)
                base += count

        server_thread = threading.Thread(target=server.run, daemon=True)
        if pump is not None:
            pump.start()
        server_thread.start()
        completed = server.manager.done.wait(timeout=float(a.timeout))
        # let FINISH drain to the edges, and through them to the devices
        deadline = time.monotonic() + 10.0
        for edge in edge_managers:
            edge.done.wait(timeout=max(deadline - time.monotonic(), 0.05))
        for dev in devices:
            dev.done.wait(timeout=max(deadline - time.monotonic(), 0.05))
        worker_rcs: List[Optional[int]] = []
        if spawner is not None:
            worker_rcs = spawner.wait_all(timeout_s=15.0)
    finally:
        if pump is not None:
            pump.stop()
        timers.stop()
        if spawner is not None:
            spawner.kill_all()
        server.manager.done.set()  # unblock the worker on a timed-out soak
        for edge in edge_managers:
            edge.done.set()
            edge.finish()
        server.manager.finish()
        if server_thread is not None:
            server_thread.join(timeout=10.0)
        if sampler is not None:
            sampler.stop()

    leaked = world_mod.leaked_threads(threads_before)

    wall = time.monotonic() - t0
    snap = telemetry.registry().snapshot()
    counters = snap["counters"]
    hists = snap["histograms"]
    grpc_mode = backend == constants.COMM_BACKEND_GRPC
    report = {
        # grpc mode: every device-host process must ALSO have exited 0
        # (all its devices reached FINISH); a leaked non-daemon thread
        # after world shutdown fails the soak outright
        "ok": (bool(completed) and all(rc == 0 for rc in worker_rcs)
               and not leaked),
        "leaked_threads": leaked,
        "backend": backend,
        "clients": int(a.clients),
        "steps_requested": int(a.steps),
        "steps_completed": int(server.manager.round_idx),
        "buffer_size": server.manager.async_cfg.buffer_size,
        "wall_s": round(wall, 3),
        "accepted_updates": counters.get("traffic.accepted_updates", 0.0),
        "shed_updates": counters.get("traffic.shed_updates", 0.0),
        "shed_rate_limited": counters.get("traffic.shed_rate_limited", 0.0),
        "shed_queue_full": counters.get("traffic.shed_queue_full", 0.0),
        "stale_dropped_updates": counters.get(
            "traffic.stale_dropped_updates", 0.0),
        "server_steps": counters.get("traffic.server_steps", 0.0),
        # recovery plane (docs/robustness.md): a soak that silently
        # survived a server restart / client resyncs / deadline rounds
        # must be visible in the report, not indistinguishable from a
        # clean run
        "server_recoveries": counters.get("run.server_recoveries", 0.0),
        "resyncs": counters.get("comm.resyncs", 0.0),
        "partial_rounds": counters.get("traffic.partial_rounds", 0.0),
        # device-side stats live in the device processes under grpc, not
        # this registry — report None there instead of a misleading 0
        "swarm_dropouts": (None if grpc_mode
                           else counters.get("swarm.dropouts", 0.0)),
        "swarm_updates_sent": (None if grpc_mode else
                               counters.get("swarm.updates_sent", 0.0)),
        "swarm_retries": (None if grpc_mode
                          else counters.get("swarm.retries", 0.0)),
        # delta plane (server side: valid for both backends — the server
        # always runs in the orchestrator process)
        "s2c_delta": _s2c_delta(a),
        "s2c_delta_frames": counters.get("comm.delta.s2c_delta_frames",
                                         0.0),
        "s2c_full_frames": counters.get("comm.delta.s2c_full_frames", 0.0),
        # wire path (docs/delivery.md device-direct): which codec served
        # the server's encodes, and whether the device kernels engaged
        "wire_path": _wire_path(a),
        "wire_device_encodes": counters.get("comm.wire.device_encodes", 0.0),
        "wire_device_decodes": (None if grpc_mode else counters.get(
            "comm.wire.device_decodes", 0.0)),
        "wire_host_fallbacks": counters.get("comm.wire.host_fallbacks", 0.0),
        "swarm_delta_decodes": (None if grpc_mode else
                                counters.get("swarm.delta_decodes", 0.0)),
        "devices_finished": (
            None if grpc_mode
            else sum(1 for d in devices if d.done.is_set())),
        "worker_exit_codes": worker_rcs,
        # the headline: server-side dispatch→ready (admission → folded)
        "dispatch_ready_s": _percentiles(
            hists.get("traffic.dispatch_ready_s")),
        "staleness": _percentiles(hists.get("traffic.staleness")),
        "step_s": _percentiles(hists.get("traffic.step_s")),
        "rss_peak_mb": round(rss_peak_mb(), 1),
    }
    if sampler is not None:
        slope = sampler.slope_mb_per_s()
        rss_samples = sampler.samples()
        limit = float(getattr(a, "leak_slope_mb_s", 1.0))
        # no-signal (too-short soak) fails: a leak gate that silently
        # passes when it measured nothing is not a gate
        mem_ok = slope is not None and slope <= limit
        report["mem"] = {
            "ok": mem_ok,
            "rss_slope_mb_per_s": (None if slope is None
                                   else round(slope, 4)),
            "rss_slope_limit_mb_per_s": limit,
            "rss_start_mb": round(rss_samples[0][1], 1),
            "rss_end_mb": round(rss_samples[-1][1], 1),
            "rss_samples": len(rss_samples),
            # per-container occupancy: every BoundedDict in the serving
            # plane publishes mem.<name>.occupancy/.evictions
            "containers": {
                name[len("mem."):-len(".occupancy")]: {
                    "occupancy": value,
                    "evictions": counters.get(
                        name[:-len(".occupancy")] + ".evictions", 0.0),
                }
                for name, value in sorted(snap["gauges"].items())
                if name.startswith("mem.")
                and name.endswith(".occupancy")
            },
        }
        report["ok"] = bool(report["ok"] and mem_ok)
    else:
        report["mem"] = None
    if edges_n:
        # edge tier block (docs/traffic.md): the root must fold ONLY edge
        # summaries — direct_client_updates > 0 means a device bypassed
        # its home edge, and the swarm smoke gates on it staying 0
        report["edge_tier"] = {
            "edges": edges_n,
            "edge_rank_base": edge_base,
            "edges_finished": sum(
                1 for e in edge_managers if e.done.is_set()),
            "summaries_folded": counters.get("edge.summaries_folded", 0.0),
            "summary_entries": counters.get("edge.summary_entries", 0.0),
            "direct_client_updates": counters.get(
                "edge.direct_client_updates", 0.0),
            "edge_folds": counters.get("edge.folds", 0.0),
            "summaries_sent": counters.get("edge.summaries_sent", 0.0),
            "rehomed_clients": counters.get("edge.rehomed_clients", 0.0),
            "resolicited_updates": counters.get(
                "edge.resolicited_updates", 0.0),
            "summary_decode_errors": counters.get(
                "edge.summary_decode_errors", 0.0),
            "per_edge": server.manager.edge_report(),
        }
    else:
        report["edge_tier"] = None
    report.update(_trace_report(a))
    return report


def _trace_report(a) -> Dict:
    """Merge the soak's per-process span files and attach the trace block:
    span count, per-segment critical-path shares, straggler top-k, and the
    traced dispatch→ready sum the smoke reconciles (within 5%) against the
    ``traffic.dispatch_ready_s`` histogram's measured sum."""
    if not _trace_on(a):
        return {"trace_spans": None, "critical_path_segments": None}
    from ..core import mlops
    from ..core.mlops import tracing

    mlops.flush()  # the orchestrator's own buffered span tail
    files = tracing.collect_trace_files(_trace_dir(a),
                                        run_id=str(a.run_id))
    spans, clocks = tracing.read_trace(files)
    merged = tracing.merge_trace(spans, clocks)
    shares = tracing.critical_path_shares(merged)
    traced_total, traced_folds = tracing.dispatch_ready_from_trace(merged)
    rounds_with_path = sum(
        1 for r in merged["rounds"] if tracing.critical_path(merged, r))
    return {
        "trace_spans": len(merged["spans"]),
        "trace_rounds": len(merged["rounds"]),
        "trace_rounds_with_path": rounds_with_path,
        "trace_orphans": len(merged["orphans"]),
        "critical_path_segments": {
            k: round(v, 6) for k, v in sorted(shares.items())},
        "stragglers": tracing.straggler_attribution(merged, k=5),
        "trace_dispatch_ready_s": round(traced_total, 6),
        "trace_dispatch_ready_folds": traced_folds,
        "trace_dir": _trace_dir(a),
    }


def run_device_worker(a) -> int:
    """One swarm device-host process (gRPC mode): ranks
    [rank_base, rank_base+count) as real gRPC endpoints against the
    orchestrator's server. Spawned via :class:`ProcSpawner`."""
    n = int(a.clients)
    edges_n = _edge_count(a)
    world_size = (_edge_rank_base(a, constants.COMM_BACKEND_GRPC) + edges_n
                  if edges_n else n + 1)
    devices = []
    threads_before = world_mod.thread_snapshot()
    timers = TimerWheel()
    try:
        for rank in range(int(a.rank_base),
                          int(a.rank_base) + int(a.count)):
            dev = SwarmClientManager(
                _device_args(a, rank, constants.COMM_BACKEND_GRPC),
                SwarmSchedule(int(a.seed), rank, float(a.think_s),
                              float(a.dropout)),
                timers,
                rank=rank, size=world_size,
                backend=constants.COMM_BACKEND_GRPC,
                delta_capable=_s2c_delta(a) != "off",
            )
            dev.run_async()
            devices.append(dev)
        deadline = time.monotonic() + float(a.timeout)
        for dev in devices:
            dev.done.wait(timeout=max(deadline - time.monotonic(), 0.1))
    finally:
        timers.stop()
        for dev in devices:
            dev.finish()
    if world_mod.leaked_threads(threads_before):
        return 1
    return 0 if all(d.done.is_set() for d in devices) else 1
