"""Federation-wide distributed tracing: cross-process causal spans, clock
alignment, round critical-path extraction, and a crash flight recorder.

reference: Dapper (Sigelman et al., 2010) for the span/context model and
FedScale (Lai et al., 2022) for per-client latency attribution. The PR 2
telemetry plane answers "how long" (histograms); this module answers
"WHERE" — one round is ONE causal trace spanning the server, every cohort
client, and all swarm worker processes, decomposing the opaque p99
``traffic.dispatch_ready_s`` scalar into admission wait, fold-queue wait,
fold, store lookup, wire encode (server side) and decode, local train,
upload (client side).

Three planes live here:

- **Recording** (:class:`Tracer`): per-``(run_id, rank)`` span recorder,
  owned by :class:`~fedml_tpu.core.world.WorldScope` (``world.trace``) so
  handler code never touches a process singleton without a run
  discriminator (graftiso I002). Spans are emitted as ``trace_span`` JSONL
  records through the PR 2 sink; a W3C-traceparent-style context
  ``(run_id, round, span_id, parent)`` rides ``Message`` headers
  (``Message.MSG_ARG_KEY_TRACE``) so causality survives grpc/mqtt/loopback,
  the retry/dedup layer (retries become span EVENTS, dedup drops become
  annotations — never duplicate spans), and the delta delivery plane.
  Zero-cost when disabled: every entry point is one ``bool`` check that
  returns a shared no-op object; nothing on the fused path ever syncs.
- **Flight recorder**: a bounded ring of the most recent spans/events per
  world, flushed to ``flight_<run>_rank_<rank>.json`` on world shutdown,
  atexit (which covers the preemption-drain exit 75), and explicitly
  before the PR 12 ``kill_server(phase, round)`` fault hook fires — so a
  SIGKILL'd server leaves a post-mortem naming the exact protocol phase it
  died in, and the merge tool can recover the dead process's span tail
  that the write-behind JSONL buffer lost.
- **Analysis** (pure functions; ``fedml_tpu trace`` is the CLI face):
  merge per-process span files, align clocks — NTP-style offset estimation
  from monotonic send/recv timestamp pairs piggybacked on the PR 12
  heartbeat exchange, wall-clock anchoring as the fallback — extract the
  per-round critical path and straggler attribution, and export Chrome
  trace-event JSON loadable in Perfetto.
"""

from __future__ import annotations

import atexit
import glob
import json
import os
import threading
import time
import zlib
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..containers import BoundedDict

TRACE_VERSION = 1

# span-record JSONL kind (rides the PR 2 sink next to round_record et al.)
SPAN_KIND = "trace_span"
CLOCK_KIND = "trace_clock"

FLIGHT_RING_CAPACITY = 256

# inter-span gaps on the critical path below this are float noise, not a
# network/wait segment worth naming
_GAP_EPSILON_S = 1e-6


# ---------------------------------------------------------------------------
# Trace context — the wire-propagated causal identity
# ---------------------------------------------------------------------------


class TraceContext:
    """W3C-traceparent-style context ``(run_id, round, span_id, parent)``.

    Serialized as a compact 4-element JSON list inside the ``Message``
    header params, so it survives every transport (the header rides the
    length-prefixed JSON frame) and the payload-store offload path
    untouched."""

    __slots__ = ("run_id", "round_idx", "span_id", "parent")

    def __init__(self, run_id: str, round_idx: int, span_id: str,
                 parent: Optional[str] = None):
        self.run_id = str(run_id)
        self.round_idx = int(round_idx)
        self.span_id = str(span_id)
        self.parent = parent

    def to_wire(self) -> list:
        return [self.run_id, self.round_idx, self.span_id, self.parent]

    @classmethod
    def from_wire(cls, value) -> Optional["TraceContext"]:
        """Parse a header value; malformed contexts are dropped, never
        raised — a traced world must interoperate with an untraced one."""
        try:
            run_id, round_idx, span_id, parent = value
            return cls(str(run_id), int(round_idx), str(span_id),
                       None if parent is None else str(parent))
        except (TypeError, ValueError):
            return None

    def child(self, span_id: str) -> "TraceContext":
        return TraceContext(self.run_id, self.round_idx, span_id,
                            parent=self.span_id)

    def __repr__(self) -> str:  # pragma: no cover - debug only
        return (f"TraceContext(run={self.run_id}, round={self.round_idx}, "
                f"span={self.span_id}, parent={self.parent})")


# ---------------------------------------------------------------------------
# Null objects — the zero-cost-disabled face
# ---------------------------------------------------------------------------


class _NullSpan:
    """Shared no-op span: one allocation per process, every method a pass."""

    __slots__ = ()
    span_id = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def end(self) -> None:
        pass

    def event(self, name: str, **attrs) -> None:
        pass

    def annotate(self, key: str, value) -> None:
        pass

    def context(self) -> Optional[TraceContext]:
        return None


_NULL_SPAN = _NullSpan()
# the public face for call sites that gate span creation themselves
# (e.g. "only when the incoming message carried a context")
NULL_SPAN = _NULL_SPAN


# ---------------------------------------------------------------------------
# Clock-offset estimation (NTP-style, from heartbeat probe pairs)
# ---------------------------------------------------------------------------


class ClockOffsetEstimator:
    """Estimate the offset between a local and a peer monotonic clock from
    ``(t_send, t_peer_recv, t_peer_send, t_recv)`` probe pairs.

    Per pair (all seconds, sender clock for t_send/t_recv, peer clock for
    the middle two): ``offset = ((t_peer_recv - t_send) +
    (t_peer_send - t_recv)) / 2`` and ``delay = (t_recv - t_send) -
    (t_peer_send - t_peer_recv)``. The estimate keeps the minimum-delay
    pair inside a sliding window — asymmetric queuing inflates high-delay
    pairs, so the tightest round-trip is the most trustworthy sample
    (classic NTP clock filtering). ``uncertainty = delay / 2`` bounds the
    unknowable path asymmetry.
    """

    def __init__(self, window: int = 64):
        self._pairs: deque = deque(maxlen=int(window))
        self._lock = threading.Lock()

    def add_pair(self, t_send: float, t_peer_recv: float,
                 t_peer_send: float, t_recv: float) -> Tuple[float, float]:
        offset = ((t_peer_recv - t_send) + (t_peer_send - t_recv)) / 2.0
        delay = max(0.0, (t_recv - t_send) - (t_peer_send - t_peer_recv))
        with self._lock:
            self._pairs.append((delay, offset))
        return offset, delay

    @property
    def n(self) -> int:
        with self._lock:
            return len(self._pairs)

    def estimate(self) -> Optional[Tuple[float, float]]:
        """``(offset_s, uncertainty_s)`` from the min-delay pair, or None
        before the first probe."""
        with self._lock:
            if not self._pairs:
                return None
            delay, offset = min(self._pairs, key=lambda p: p[0])
        return offset, delay / 2.0


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------


class _Span:
    """An open span. Context-manager or explicit :meth:`end`; emits its
    record exactly once (idempotent end — a with-block around an explicit
    end must not double-emit)."""

    __slots__ = ("tracer", "name", "span_id", "parent", "round_idx",
                 "client", "t0_mono", "ts_wall", "events", "annot",
                 "_done")

    def __init__(self, tracer: "Tracer", name: str, span_id: str,
                 parent: Optional[str], round_idx: int,
                 client: Optional[int]):
        self.tracer = tracer
        self.name = name
        self.span_id = span_id
        self.parent = parent
        self.round_idx = round_idx
        self.client = client
        self.t0_mono = time.monotonic()
        self.ts_wall = time.time()
        self.events: List[Dict[str, Any]] = []
        self.annot: Dict[str, Any] = {}
        self._done = False

    def __enter__(self) -> "_Span":
        return self

    def __exit__(self, *exc) -> bool:
        self.end()
        return False

    def event(self, name: str, **attrs) -> None:
        """A point-in-time event inside this span (e.g. a send retry)."""
        e = {"name": name, "t": time.monotonic() - self.t0_mono}
        if attrs:
            e.update(attrs)
        self.events.append(e)

    def annotate(self, key: str, value) -> None:
        self.annot[key] = value

    def context(self) -> TraceContext:
        """The context a child (possibly across the wire) continues from."""
        return TraceContext(self.tracer.run_id, self.round_idx,
                            self.span_id)

    def end(self) -> None:
        if self._done:
            return
        self._done = True
        dur = time.monotonic() - self.t0_mono
        self.tracer._finish_span(self, dur)


class Tracer:
    """Per-(run_id, rank) span recorder + flight recorder.

    Access from serving-plane code goes through ``world.trace`` — the
    module-level index exists for construction and the pre-SIGKILL flush,
    both keyed by run identity."""

    # process index of tracers — always accessed through the (run_id,
    # rank) discriminator, mirroring telemetry's scope registry
    _tracers: Dict[Tuple[str, int], "Tracer"] = {}
    _tracers_lock = threading.Lock()

    def __init__(self, run_id: str, rank: int):
        self.run_id = str(run_id)
        self.rank = int(rank)
        self.pid = os.getpid()
        self.enabled = False
        self.sample = 1.0
        self.flight_dir = ""
        self._lock = threading.Lock()
        self._seq = 0
        self._tls = threading.local()
        self._ring: deque = deque(maxlen=FLIGHT_RING_CAPACITY)
        self._last_phase: Optional[Dict[str, Any]] = None
        # per-peer clock filters, LRU-bounded (graftmem M001): a root
        # probing 100k clients would otherwise pin one estimator each
        self._estimators: Dict[int, ClockOffsetEstimator] = BoundedDict(
            1024, lru=True, name="trace.clock_estimators")
        self._atexit_armed = False

    # -- configuration -------------------------------------------------------

    def configure(self, args) -> "Tracer":
        """Apply a run's tracing knobs (idempotent; called by WorldScope
        construction so every comm manager wires the same way)."""
        self.enabled = bool(getattr(args, "enable_tracing", False))
        raw_sample = getattr(args, "trace_sample", None)
        self.sample = (1.0 if raw_sample is None
                       else max(0.0, min(1.0, float(raw_sample))))
        self.flight_dir = str(
            getattr(args, "trace_dir", "")
            or getattr(args, "tracking_dir", "")
            or ".fedml_tpu_runs")
        if self.enabled and not self._atexit_armed:
            # atexit covers normal exit AND the preemption-drain exit 75
            # (sys.exit runs atexit hooks); SIGKILL is the flight
            # recorder's explicit pre-kill flush's business
            atexit.register(self.flush_flight, "atexit")
            self._atexit_armed = True
        return self

    def sampled(self, round_idx: int) -> bool:
        """Deterministic per-round sampling decision: a hash of
        ``(run_id, round)`` — no RNG (graftrep D002), and every process
        that asks about the same round agrees without coordination."""
        if not self.enabled:
            return False
        if self.sample >= 1.0:
            return True
        if self.sample <= 0.0:
            return False
        h = zlib.crc32(f"{self.run_id}:{int(round_idx)}".encode("utf-8"))
        return (h / 4294967296.0) < self.sample

    # -- span recording ------------------------------------------------------

    def _next_id(self) -> str:
        with self._lock:
            self._seq += 1
            return f"{self.rank}.{self.pid}.{self._seq}"

    def span(self, name: str, round_idx: Optional[int] = None,
             parent: Optional[str] = None,
             ctx: Optional[TraceContext] = None,
             client: Optional[int] = None):
        """Open a span. ``ctx`` continues a wire-carried context (the new
        span's parent is ``ctx.span_id``); ``parent`` overrides explicitly;
        otherwise the innermost open span on this thread (or an adopted
        context) is the parent."""
        if not self.enabled:
            return _NULL_SPAN
        if ctx is not None:
            parent = ctx.span_id
            if round_idx is None:
                round_idx = ctx.round_idx
        elif parent is None:
            cur = self.current_context()
            if cur is not None:
                parent = cur.span_id
                if round_idx is None:
                    round_idx = cur.round_idx
        s = _Span(self, name, self._next_id(), parent,
                  -1 if round_idx is None else int(round_idx), client)
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        stack.append(s)
        return s

    def record_span(self, name: str, t0_mono: float, dur_s: float,
                    round_idx: Optional[int] = None,
                    parent: Optional[str] = None,
                    ctx: Optional[TraceContext] = None,
                    client: Optional[int] = None,
                    **annot) -> Optional[str]:
        """Emit an already-measured span (e.g. fold-queue wait, computed
        retroactively from the enqueue timestamp). Returns its span id."""
        if not self.enabled:
            return None
        if ctx is not None:
            parent = ctx.span_id
            if round_idx is None:
                round_idx = ctx.round_idx
        now = time.monotonic()
        rec = {
            "kind": SPAN_KIND, "v": TRACE_VERSION, "run": self.run_id,
            "rank": self.rank, "pid": self.pid, "span": self._next_id(),
            "parent": parent, "name": name,
            "round": -1 if round_idx is None else int(round_idx),
            "ts": time.time() - (now - t0_mono), "mono": t0_mono,
            "dur": float(dur_s),
        }
        if client is not None:
            rec["client"] = int(client)
        if annot:
            rec["annot"] = dict(annot)
        self._emit(rec)
        return rec["span"]

    def _finish_span(self, s: _Span, dur: float) -> None:
        stack = getattr(self._tls, "stack", None)
        if stack and s in stack:
            stack.remove(s)
        rec = {
            "kind": SPAN_KIND, "v": TRACE_VERSION, "run": self.run_id,
            "rank": self.rank, "pid": self.pid, "span": s.span_id,
            "parent": s.parent, "name": s.name, "round": s.round_idx,
            "ts": s.ts_wall, "mono": s.t0_mono, "dur": float(dur),
        }
        if s.client is not None:
            rec["client"] = int(s.client)
        if s.events:
            rec["events"] = s.events
        if s.annot:
            rec["annot"] = s.annot
        self._emit(rec)

    # -- ambient context (wire receive path) ---------------------------------

    def adopt(self, ctx: Optional[TraceContext]) -> None:
        """Set the thread's ambient context (the comm manager calls this
        with the incoming message's wire context before dispatching to
        handlers, so spans opened inside — and messages sent from — the
        handler continue the sender's trace)."""
        if not self.enabled:
            return
        self._tls.adopted = ctx

    def current_context(self) -> Optional[TraceContext]:
        """Innermost open span on this thread, else the adopted wire
        context, else None."""
        if not self.enabled:
            return None
        stack = getattr(self._tls, "stack", None)
        if stack:
            return stack[-1].context()
        return getattr(self._tls, "adopted", None)

    def event(self, name: str, **attrs) -> None:
        """A point event: attached to the innermost open span when one
        exists (a send retry inside an upload span), otherwise noted in
        the flight-recorder ring only — never a standalone span, so
        retries/dedup drops can NEVER duplicate spans."""
        if not self.enabled:
            return
        stack = getattr(self._tls, "stack", None)
        if stack:
            stack[-1].event(name, **attrs)
            return
        note = {"kind": "trace_event", "run": self.run_id,
                "rank": self.rank, "name": name,
                "mono": time.monotonic()}
        if attrs:
            note.update(attrs)
        with self._lock:
            self._ring.append(note)

    # -- clock probes --------------------------------------------------------

    def clock_probe(self, peer: int, t_send: float, t_peer_recv: float,
                    t_peer_send: float,
                    t_recv: float) -> Optional[Tuple[float, float]]:
        """Feed one heartbeat probe pair; returns the refreshed
        ``(offset_s, uncertainty_s)`` estimate toward ``peer`` and emits a
        ``trace_clock`` record so the merge tool can align this process's
        monotonic timeline onto the peer's."""
        with self._lock:
            est = self._estimators.get(int(peer))
            if est is None:
                est = self._estimators[int(peer)] = ClockOffsetEstimator()
        est.add_pair(t_send, t_peer_recv, t_peer_send, t_recv)
        out = est.estimate()
        if out is not None and self.enabled:
            self._emit({
                "kind": CLOCK_KIND, "v": TRACE_VERSION, "run": self.run_id,
                "rank": self.rank, "pid": self.pid, "peer": int(peer),
                "offset_s": out[0], "uncertainty_s": out[1], "n": est.n,
            })
        return out

    def clock_offset(self, peer: int) -> Optional[Tuple[float, float]]:
        with self._lock:
            est = self._estimators.get(int(peer))
        return None if est is None else est.estimate()

    # -- flight recorder -----------------------------------------------------

    def note_phase(self, phase: str, round_idx: int) -> None:
        """Mark the protocol phase the world is entering — the post-mortem
        names the LAST mark, which is exactly the phase a no-drain SIGKILL
        died in (pairs with FaultPlan.kill_server)."""
        if not self.enabled:
            return
        mark = {"phase": str(phase), "round": int(round_idx),
                "mono": time.monotonic(), "ts": time.time()}
        with self._lock:
            self._last_phase = mark
            self._ring.append({"kind": "trace_phase", **mark})

    def flush_flight(self, reason: str = "") -> Optional[str]:
        """Write the flight-recorder post-mortem JSON (ring of recent
        spans/events, still-open spans, the last phase mark) and drain the
        write-behind JSONL sink. Safe to call repeatedly; the newest call
        wins the file. Returns the path (None when tracing is off)."""
        if not self.enabled:
            return None
        with self._lock:
            ring = list(self._ring)
            last_phase = dict(self._last_phase) if self._last_phase else None
        open_spans = []
        stack = getattr(self._tls, "stack", None)
        if stack:
            now = time.monotonic()
            for s in stack:
                open_spans.append({
                    "span": s.span_id, "parent": s.parent, "name": s.name,
                    "round": s.round_idx, "ts": s.ts_wall, "mono": s.t0_mono,
                    "dur": now - s.t0_mono, "open": True,
                })
        post = {
            "kind": "flight_recorder", "v": TRACE_VERSION,
            "run": self.run_id, "rank": self.rank, "pid": self.pid,
            "reason": str(reason), "time": time.time(),
            "last_phase": last_phase, "open_spans": open_spans,
            "ring": ring,
        }
        path = flight_path(self.flight_dir, self.run_id, self.rank)
        try:
            os.makedirs(self.flight_dir or ".", exist_ok=True)
            tmp = f"{path}.tmp.{self.pid}"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(post, f)
            os.replace(tmp, path)
        except OSError:  # pragma: no cover - post-mortem must never raise
            return None
        # the main sink's buffered tail must also survive the crash window
        from fedml_tpu.core import mlops

        mlops.flush()
        return path

    # -- emission ------------------------------------------------------------

    def _emit(self, rec: Dict[str, Any]) -> None:
        with self._lock:
            self._ring.append(rec)
        # ride the PR 2 JSONL sink (a no-op when tracking is off — the
        # flight-recorder ring still captures for the post-mortem)
        from fedml_tpu.core import mlops

        mlops._emit(dict(rec))


def tracer_for(run_id: str, rank: int = 0) -> Tracer:
    """The (run_id, rank)-keyed tracer — created disabled on first ask;
    :meth:`Tracer.configure` (via WorldScope construction) arms it."""
    key = (str(run_id), int(rank))
    with Tracer._tracers_lock:
        t = Tracer._tracers.get(key)
        if t is None:
            t = Tracer._tracers[key] = Tracer(key[0], key[1])
        return t


def flight_path(flight_dir: str, run_id: str, rank: int) -> str:
    return os.path.join(flight_dir or ".",
                        f"flight_{run_id}_rank_{int(rank)}.json")


# ---------------------------------------------------------------------------
# Analysis plane — pure functions over span/clock records
# ---------------------------------------------------------------------------


def collect_trace_files(trace_dir: str,
                        run_id: Optional[str] = None) -> List[str]:
    """Every span-bearing file in a directory: run JSONL sinks plus flight
    recorder post-mortems (sorted — merge determinism starts here)."""
    pats = ["run_*.jsonl", "flight_*.json"]
    if run_id:
        pats = [f"run_{run_id}_edge_*.jsonl", f"flight_{run_id}_rank_*.json"]
    out: List[str] = []
    for pat in pats:
        out.extend(glob.glob(os.path.join(trace_dir, pat)))
    return sorted(out)


def read_trace(paths: Sequence[str]) -> Tuple[List[dict], List[dict]]:
    """Load ``(spans, clocks)`` from JSONL sinks and flight-recorder JSON.

    Flight-recorder rings recover the span tail a SIGKILL'd process's
    write-behind buffer lost; spans present in both sources dedupe on
    their globally-unique ``(rank, pid, span)`` id, so merging a crashed
    run never double-counts."""
    spans: Dict[Tuple, dict] = {}
    clocks: List[dict] = []
    loop: List[dict] = []  # this file's loop spans, until its pid is known

    def take(rec: dict) -> None:
        kind = rec.get("kind")
        if kind == SPAN_KIND and "span" in rec:
            spans.setdefault(
                (rec.get("rank"), rec.get("pid"), rec["span"]), rec)
        elif kind == CLOCK_KIND:
            clocks.append(rec)
        elif kind == "round_record":
            loop.extend(_loop_spans(rec))

    def place_loop_spans(wire_before: int) -> None:
        """A RoundRecord names no process and its spans carry the epoch
        clock only: they take the pid and the ``ts - mono`` anchor of the
        wire spans the same process wrote into the same file (0 where there
        are none, which the wall-clock fallback of align_clocks rebases)."""
        wire = list(spans.values())[wire_before:]
        pid = wire[0].get("pid", 0) if wire else 0
        anchors = sorted(float(w["ts"]) - float(w["mono"]) for w in wire
                         if "ts" in w and "mono" in w)
        anchor = anchors[len(anchors) // 2] if anchors else 0.0
        for rec in loop:
            rec["pid"], rec["mono"] = pid, rec["ts"] - anchor
            spans.setdefault((rec["rank"], pid, rec["span"]), rec)
        loop.clear()

    for path in paths:
        wire_before = len(spans)
        try:
            if path.endswith(".jsonl"):
                with open(path, encoding="utf-8") as f:
                    for line in f:
                        line = line.strip()
                        if not line:
                            continue
                        try:
                            take(json.loads(line))
                        except ValueError:
                            continue  # torn tail of a crashed writer
            else:
                with open(path, encoding="utf-8") as f:
                    post = json.load(f)
                for rec in post.get("ring", []):
                    rec = dict(rec)
                    rec.setdefault("rank", post.get("rank"))
                    rec.setdefault("pid", post.get("pid"))
                    take(rec)
                for rec in post.get("open_spans", []):
                    rec = dict(rec, kind=SPAN_KIND, run=post.get("run"),
                               rank=post.get("rank"), pid=post.get("pid"))
                    take(rec)
        except (OSError, ValueError):
            continue
        finally:
            place_loop_spans(wire_before)
    ordered = sorted(spans.values(),
                     key=lambda r: (r.get("rank", 0), r.get("pid", 0),
                                    r.get("mono", 0.0), r.get("span", "")))
    clocks.sort(key=lambda r: (r.get("rank", 0), r.get("pid", 0),
                               r.get("n", 0)))
    return ordered, clocks


def _loop_spans(record: dict) -> List[dict]:
    """A RoundRecord's ``spans`` (the round loop's phases, telemetry.py) as
    span records, so that ``fedml_tpu trace --chrome`` puts the loop and the
    wire on one timeline. They carry ``round`` -1, which keeps them out of
    the critical-path analysis (that follows wire causality); the round or
    step is the annotation ``unit``."""
    rank = int(record.get("edge_id", 0) or 0)
    out = []
    for s in record.get("spans") or ():
        out.append({
            "kind": SPAN_KIND, "v": TRACE_VERSION, "run": record.get("run_id"),
            "rank": rank, "span": f"loop-{s['span']}",
            "parent": (None if s.get("parent") is None
                       else f"loop-{s['parent']}"),
            "name": s["name"], "round": -1, "ts": s["ts_ns"] * 1e-9,
            "dur": s["dur_ns"] * 1e-9,
            "annot": {"unit": record.get("round_idx")},
        })
    return out


def _proc_key(rec: dict) -> Tuple[int, int]:
    return int(rec.get("rank", 0)), int(rec.get("pid", 0))


def align_clocks(spans: Sequence[dict],
                 clocks: Sequence[dict]) -> Dict[Tuple[int, int], float]:
    """Per-process offsets that map each process's monotonic timeline onto
    a shared reference (the server process's monotonic clock).

    Primary source: heartbeat probe estimates (``trace_clock`` records —
    ``offset_s`` maps the recording process's clock onto its peer's, and
    the peer is the server). Fallback for processes that never exchanged a
    probe (swarm sim devices, the server itself): wall-clock anchoring —
    each span carries both ``ts`` (epoch) and ``mono``, so the median of
    ``ts - mono`` per process rebases everything onto the wall clock,
    then onto the server's monotonic frame. Single-host soaks share a wall
    clock, which is exactly the case the fallback serves."""
    procs: Dict[Tuple[int, int], List[float]] = {}
    for rec in spans:
        if "ts" in rec and "mono" in rec:
            procs.setdefault(_proc_key(rec), []).append(
                float(rec["ts"]) - float(rec["mono"]))
    anchors = {k: sorted(v)[len(v) // 2] for k, v in procs.items()}
    if not anchors:
        return {}
    server_proc = min(anchors,
                      key=lambda k: (k[0], -len(procs[k]), k[1]))
    server_anchor = anchors[server_proc]
    # newest probe estimate per process (records are emitted in order)
    probe: Dict[Tuple[int, int], float] = {}
    for rec in clocks:
        probe[_proc_key(rec)] = float(rec.get("offset_s", 0.0))
    offsets: Dict[Tuple[int, int], float] = {}
    for key, anchor in anchors.items():
        if key == server_proc:
            offsets[key] = 0.0
        elif key in probe:
            offsets[key] = probe[key]
        else:
            offsets[key] = anchor - server_anchor
    return offsets


def merge_trace(spans: Sequence[dict],
                clocks: Sequence[dict] = ()) -> Dict[str, Any]:
    """Merge per-process spans into one clock-aligned trace.

    Deterministic: identical inputs produce a byte-identical structure
    (stable sort keys, no wall-clock reads). Spans whose parent is missing
    after flight-recorder recovery are counted as ``orphans`` — a clean
    killed-and-recovered chaos run must merge with zero."""
    offsets = align_clocks(spans, clocks)
    merged: List[dict] = []
    index: Dict[str, dict] = {}
    for rec in spans:
        off = offsets.get(_proc_key(rec), 0.0)
        t0 = float(rec.get("mono", 0.0)) + off
        m = dict(rec)
        m["t0"] = t0
        m["t1"] = t0 + float(rec.get("dur", 0.0))
        merged.append(m)
        index[str(rec.get("span"))] = m
    if merged:
        base = min(m["t0"] for m in merged)
        for m in merged:
            m["t0"] -= base
            m["t1"] -= base
    merged.sort(key=lambda m: (m["t0"], str(m.get("span"))))
    orphans = sorted(str(m.get("span")) for m in merged
                     if m.get("parent") and str(m["parent"]) not in index)
    rounds = sorted({int(m.get("round", -1)) for m in merged
                     if int(m.get("round", -1)) >= 0})
    return {"v": TRACE_VERSION, "spans": merged, "orphans": orphans,
            "rounds": rounds,
            "procs": sorted({_proc_key(m) for m in merged})}


def critical_path(merged: Dict[str, Any],
                  round_idx: int) -> List[Dict[str, Any]]:
    """The round's gating causal chain: walk parent links back from the
    latest-finishing terminal span of the round, emitting one segment per
    span plus ``transit`` segments for inter-span gaps (network + peer
    scheduling). Empty only when the round has no spans at all."""
    spans = [m for m in merged.get("spans", [])
             if int(m.get("round", -1)) == int(round_idx)]
    if not spans:
        return []
    index = {str(m.get("span")): m for m in spans}
    terminal = max(spans, key=lambda m: (m["t1"], str(m.get("span"))))
    chain: List[dict] = []
    cur: Optional[dict] = terminal
    seen = set()
    while cur is not None and str(cur.get("span")) not in seen:
        seen.add(str(cur.get("span")))
        chain.append(cur)
        parent = cur.get("parent")
        cur = index.get(str(parent)) if parent else None
    chain.reverse()
    path: List[Dict[str, Any]] = []
    prev: Optional[dict] = None
    for m in chain:
        if prev is not None:
            gap = m["t0"] - prev["t1"]
            if gap > _GAP_EPSILON_S:
                path.append({"name": "transit", "dur_s": gap,
                             "rank": m.get("rank"),
                             "from": prev.get("name"),
                             "to": m.get("name")})
        seg = {"name": m.get("name"), "dur_s": float(m.get("dur", 0.0)),
               "rank": m.get("rank"), "span": m.get("span")}
        if m.get("client") is not None:
            seg["client"] = m["client"]
        path.append(seg)
        prev = m
    return path


def critical_path_shares(merged: Dict[str, Any]) -> Dict[str, float]:
    """Aggregate critical-path time by segment name over every round —
    the 'where do the gating milliseconds go' distribution."""
    totals: Dict[str, float] = {}
    for r in merged.get("rounds", []):
        for seg in critical_path(merged, r):
            totals[seg["name"]] = (totals.get(seg["name"], 0.0)
                                   + float(seg["dur_s"]))
    return totals


def straggler_attribution(merged: Dict[str, Any],
                          k: int = 5) -> List[Dict[str, Any]]:
    """Top-k clients by attributed wait: per round, a client's chain-end
    lateness relative to the round's fastest client chain (the FedScale
    framing — who gates, not who averages worst), summed over rounds."""
    by_round: Dict[int, Dict[int, float]] = {}
    for m in merged.get("spans", []):
        client = m.get("client")
        r = int(m.get("round", -1))
        if client is None or r < 0:
            continue
        ends = by_round.setdefault(r, {})
        c = int(client)
        ends[c] = max(ends.get(c, 0.0), float(m["t1"]))
    waits: Dict[int, float] = {}
    rounds_gated: Dict[int, int] = {}
    for r, ends in by_round.items():
        if len(ends) < 2:
            continue
        fastest = min(ends.values())
        slowest = max(ends, key=lambda c: ends[c])
        for c, t1 in ends.items():
            waits[c] = waits.get(c, 0.0) + (t1 - fastest)
        rounds_gated[slowest] = rounds_gated.get(slowest, 0) + 1
    top = sorted(waits, key=lambda c: (-waits[c], c))[:int(k)]
    return [{"client": c, "wait_s": waits[c],
             "rounds_gated": rounds_gated.get(c, 0)} for c in top]


def dispatch_ready_from_trace(
        merged: Dict[str, Any]) -> Tuple[float, int]:
    """Sum of traced server-side dispatch→ready segments per folded
    update: the histogram's window opens at the enqueue stamp, and
    ``queue_wait + fold`` cover it additively (the admission span overlaps
    the pre-enqueue part of the receive path), so their sum must reconcile
    with the measured ``traffic.dispatch_ready_s`` total within 5%
    (acceptance gate). Folds the histogram never observed — stale or
    undecodable updates, annotated ``outcome`` — are excluded. Returns
    ``(total_seconds, folds)``."""
    spans = merged.get("spans", [])
    index = {str(m.get("span")): m for m in spans}
    total = 0.0
    folds = 0
    for m in spans:
        if m.get("name") != "fold":
            continue
        if (m.get("annot") or {}).get("outcome") in ("stale",
                                                     "undecodable"):
            continue
        folds += 1
        total += float(m.get("dur", 0.0))
        cur = index.get(str(m.get("parent")))
        if cur is not None and cur.get("name") == "queue_wait":
            total += float(cur.get("dur", 0.0))
    return total, folds


def to_chrome(merged: Dict[str, Any]) -> Dict[str, Any]:
    """Chrome trace-event JSON (Perfetto-loadable): one complete ('X')
    event per span, processes keyed by federation rank."""
    events: List[dict] = []
    for rank, pid in merged.get("procs", []):
        events.append({
            "ph": "M", "name": "process_name", "pid": rank, "tid": 0,
            "args": {"name": (f"server rank {rank}" if rank == 0
                              else f"client rank {rank}") + f" (pid {pid})"},
        })
    for m in merged.get("spans", []):
        args: Dict[str, Any] = {"round": m.get("round"),
                                "span": m.get("span")}
        if m.get("client") is not None:
            args["client"] = m["client"]
        if m.get("annot"):
            args.update(m["annot"])
        ev = {
            "ph": "X", "name": m.get("name"),
            "cat": f"round_{m.get('round')}",
            "pid": int(m.get("rank", 0)), "tid": int(m.get("pid", 0)),
            "ts": round(m["t0"] * 1e6, 3),
            "dur": round(float(m.get("dur", 0.0)) * 1e6, 3),
            "args": args,
        }
        events.append(ev)
        for e in m.get("events", []) or []:
            events.append({
                "ph": "i", "name": e.get("name"), "s": "t",
                "pid": int(m.get("rank", 0)), "tid": int(m.get("pid", 0)),
                "ts": round((m["t0"] + float(e.get("t", 0.0))) * 1e6, 3),
                "args": {k: v for k, v in e.items()
                         if k not in ("name", "t")},
            })
    return {"traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {"run": (merged.get("spans") or [{}])[0].get(
                "run", ""), "format": "fedml_tpu.tracing"}}


def read_postmortem(flight_dir: str, run_id: str,
                    rank: int = 0) -> Optional[Dict[str, Any]]:
    """Load a flight-recorder post-mortem, if one was flushed."""
    path = flight_path(flight_dir, run_id, rank)
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError):
        return None
