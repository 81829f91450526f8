"""``fedml`` CLI.

reference: ``python/fedml/cli/cli.py:29-685`` (click app: version / status /
logs / login / logout / build / register / env). TPU re-grounding: argparse
(no extra deps). ``build`` packages a training dir into a deployable zip
(reference: build — client/server MLOps packages), ``env`` collects the
environment report (reference: cli/env/collect_env.py:6-68), ``logs`` tails a
run's JSONL event log. The deployment surface binds to the directory-queue
agent plane in ``fedml_tpu/agent.py``: ``login``/``logout`` bind/unbind this
host as an edge device (reference: cli/edge_deployment/client_login.py),
``launch`` submits a built package to a job queue, and ``agent`` runs the
edge/server daemon that claims and executes queued jobs (reference:
client_daemon.py / client_runner.py).

Run as ``python -m fedml_tpu.cli <command>``.
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import zipfile


def cmd_version(_args) -> int:
    from . import __version__

    print(f"fedml_tpu version: {__version__}")
    return 0


def cmd_env(_args) -> int:
    """reference: collect_env — fedml/OS/python/torch/device info."""
    from . import __version__

    print(f"fedml_tpu: {__version__}")
    print(f"python: {sys.version.split()[0]}")
    print(f"os: {platform.platform()}")
    try:
        import jax

        print(f"jax: {jax.__version__}")
        devs = jax.devices()
        print(f"devices: {[str(d) for d in devs]}")
        print(f"default backend: {jax.default_backend()}")
    except Exception as e:  # pragma: no cover - env-specific
        print(f"jax: unavailable ({e})")
    for mod in ("flax", "optax", "orbax.checkpoint", "numpy"):
        try:
            import importlib

            m = importlib.import_module(mod)
            print(f"{mod}: {getattr(m, '__version__', '?')}")
        except ImportError:
            print(f"{mod}: not installed")
    return 0


def cmd_status(_args) -> int:
    runs_dir = ".fedml_tpu_runs"
    if not os.path.isdir(runs_dir):
        print("no runs directory; nothing tracked")
        return 0
    for fn in sorted(os.listdir(runs_dir)):
        path = os.path.join(runs_dir, fn)
        with open(path) as f:
            lines = f.readlines()
        last = json.loads(lines[-1]) if lines else {}
        print(f"{fn}: {len(lines)} events, last={last.get('kind', '?')}")
    return 0


def _resolve_run_file(path: str) -> str:
    """Explicit path, else the newest (by mtime — lexicographic order lies
    once run ids pass one digit) ``.jsonl`` in the default runs dir ('')."""
    if path:
        return path
    runs_dir = ".fedml_tpu_runs"
    if not os.path.isdir(runs_dir):
        return ""
    files = [os.path.join(runs_dir, f) for f in os.listdir(runs_dir)
             if f.endswith(".jsonl")]
    return max(files, key=os.path.getmtime) if files else ""


def cmd_logs(args) -> int:
    """Tail a run's event log (reference: fedml logs)."""
    path = _resolve_run_file(args.file)
    if not path:
        print("no logs found")
        return 1
    with open(path) as f:
        lines = f.readlines()
    for line in lines[-args.n:]:
        print(line.rstrip())
    return 0


def _percentile(sorted_vals, q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(int(q * len(sorted_vals)), len(sorted_vals) - 1)
    return sorted_vals[idx]


def cmd_top(args) -> int:
    """Phase-latency breakdown for a finished run's RoundRecords.

    Reads the JSONL event log a tracked run wrote (``--enable_tracking``)
    and prints, per phase, call count / total / mean / p50 / p95 and the
    share of total round wall-clock — the "where does a round's time go"
    table the 2,217-LoC reference MLOps plane never had.
    """
    from .core.mlops import read_events

    path = _resolve_run_file(args.file)
    if not path or not os.path.exists(path):
        print("no run event log found (run with --enable_tracking)")
        return 1
    events = read_events(path)
    records = [e for e in events if e.get("kind") == "round_record"]
    if not records:
        print(f"{path}: {len(events)} events but no round_record entries "
              "(tracked runs emit one per round)")
        return 1

    phases = {}
    # dispatch→loss latency (the loop that waits: Cheetah)
    # overlaps their train/step + loss_sync spans, so it stays OUT of the
    # phase table (whose % wall must not double-count) and is summarised
    # separately below
    dispatch_lat = []
    for r in records:
        for name, dur in (r.get("phases") or {}).items():
            phases.setdefault(name, []).append(float(dur))
        dl = r.get("dispatch_latency_s")
        if dl is not None:
            dispatch_lat.append(float(dl))
    wall = sum(float(r.get("wall_s") or 0.0) for r in records)
    rounds = len(records)

    print(f"run: {path}")
    print(f"rounds: {rounds}   wall: {wall:.3f}s   "
          f"rounds/s: {rounds / wall if wall else float('nan'):.2f}")
    examples = sum(float(r.get("examples") or 0.0) for r in records)
    if examples:
        print(f"examples: {examples:.0f}   examples/s: "
              f"{examples / wall if wall else float('nan'):.0f}")
    compiles = sum(int(r.get("compiles") or 0) for r in records)
    fused = sum(1 for r in records if r.get("fused"))
    hbm_peaks = [r.get("hbm_peak_mb") for r in records
                 if r.get("hbm_peak_mb") is not None]
    print(f"fused rounds: {fused}/{rounds}   compile events: {compiles}"
          + (f"   hbm peak: {max(hbm_peaks):.1f} MB" if hbm_peaks else ""))
    if dispatch_lat:
        ds = sorted(dispatch_lat)
        print(f"dispatch→ready: mean "
              f"{1e3 * sum(ds) / len(ds):.3f}ms   "
              f"p50 {1e3 * _percentile(ds, 0.5):.3f}ms   "
              f"p95 {1e3 * _percentile(ds, 0.95):.3f}ms")
    print()
    header = (f"{'phase':<18} {'calls':>6} {'total s':>9} {'mean ms':>9} "
              f"{'p50 ms':>8} {'p95 ms':>8} {'% wall':>7}")
    print(header)
    print("-" * len(header))
    for name, vals in sorted(phases.items(), key=lambda kv: -sum(kv[1])):
        vs = sorted(vals)
        total = sum(vals)
        pct = 100.0 * total / wall if wall else 0.0
        print(f"{name:<18} {len(vals):>6} {total:>9.3f} "
              f"{1e3 * total / len(vals):>9.3f} "
              f"{1e3 * _percentile(vs, 0.5):>8.3f} "
              f"{1e3 * _percentile(vs, 0.95):>8.3f} {pct:>6.1f}%")
    summary = next((e for e in reversed(events)
                    if e.get("kind") == "telemetry_summary"), None)
    if summary:
        metrics = summary.get("metrics") or {}
        counters = metrics.get("counters", {})
        hits = counters.get("jax.compilation_cache.hits", 0)
        misses = counters.get("jax.compilation_cache.misses", 0)
        if hits or misses:
            print(f"\ncompilation cache: {hits:.0f} hits / "
                  f"{misses:.0f} misses")
        _print_traffic_summary(metrics)
        _print_delta_summary(metrics)
        _print_wire_summary(metrics)
        _print_recovery_summary(metrics)
        _print_edge_summary(metrics)
        _print_mem_summary(metrics)
    _print_trace_summary(events)
    return 0


def _print_trace_summary(events: list) -> None:
    """The distributed-tracing story (docs/tracing.md): where the gating
    milliseconds of each round went (critical-path segment shares) and
    which clients gated rounds (straggler top-k). Reads the ``trace_span``
    records riding the same JSONL file; silent when the run was untraced."""
    from .core.mlops import tracing

    spans = [e for e in events
             if e.get("kind") == tracing.SPAN_KIND and "span" in e]
    if not spans:
        return
    clocks = [e for e in events if e.get("kind") == tracing.CLOCK_KIND]
    merged = tracing.merge_trace(spans, clocks)
    shares = tracing.critical_path_shares(merged)
    total = sum(shares.values())
    print(f"\ntrace (critical path over {len(merged['rounds'])} rounds, "
          f"{len(merged['spans'])} spans):")
    for name, dur in sorted(shares.items(), key=lambda kv: -kv[1]):
        pct = 100.0 * dur / total if total else 0.0
        print(f"  {name:<18} {dur:>9.4f}s {pct:>6.1f}%")
    stragglers = tracing.straggler_attribution(merged, k=5)
    if stragglers:
        print("  stragglers: " + "   ".join(
            f"client {s['client']} (+{s['wait_s']:.3f}s, "
            f"gated {s['rounds_gated']})" for s in stragglers))


def _print_wire_summary(metrics: dict) -> None:
    """The wire-path story (comm.wire.* family, docs/delivery.md
    device-direct): which codec served encodes/decodes, the per-call time
    histograms, and bytes that had to be materialized host-side. Silent
    when no wire codec ever ran."""
    counters = metrics.get("counters", {})
    hists = metrics.get("histograms", {})
    enc = hists.get("comm.wire.encode_s") or {}
    dec = hists.get("comm.wire.decode_s") or {}
    dev_enc = counters.get("comm.wire.device_encodes", 0)
    dev_dec = counters.get("comm.wire.device_decodes", 0)
    fallbacks = counters.get("comm.wire.host_fallbacks", 0)
    if not (enc.get("count") or dec.get("count") or dev_enc or fallbacks):
        return
    print("\nwire path (delta codec kernels):")
    print(f"  encodes: {enc.get('count', 0):.0f} "
          f"({dev_enc:.0f} device)   decodes: {dec.get('count', 0):.0f} "
          f"({dev_dec:.0f} device)   host fallbacks: {fallbacks:.0f}")
    if enc.get("count"):
        print(f"  encode_s p50 {1e3 * (enc.get('p50') or 0):.2f}ms   "
              f"p99 {1e3 * (enc.get('p99') or 0):.2f}ms")
    if dec.get("count"):
        print(f"  decode_s p50 {1e3 * (dec.get('p50') or 0):.2f}ms   "
              f"p99 {1e3 * (dec.get('p99') or 0):.2f}ms")
    copied = counters.get("comm.wire.host_bytes_copied", 0)
    if copied:
        print(f"  host bytes copied: {copied / 1e6:.2f} MB "
              "(non-dlpack transfers)")


def _print_recovery_summary(metrics: dict) -> None:
    """The survivable-serving-plane story (docs/robustness.md): a soak
    that silently survived a server kill, a partition, or straggler
    deadlines must be VISIBLE instead of indistinguishable from a clean
    run. Silent when nothing recovery-shaped happened."""
    counters = metrics.get("counters", {})
    recoveries = counters.get("run.server_recoveries", 0)
    resyncs = counters.get("comm.resyncs", 0)
    reconnects = counters.get("comm.reconnects", 0)
    misses = counters.get("comm.heartbeat_misses", 0)
    partial = counters.get("traffic.partial_rounds", 0)
    late = counters.get("traffic.late_folds", 0)
    if not (recoveries or resyncs or reconnects or misses or partial
            or late):
        return
    print("\nrecovery plane (failover / resync / deadlines):")
    print(f"  server recoveries: {recoveries:.0f}   client resyncs: "
          f"{resyncs:.0f} (replays "
          f"{counters.get('comm.resync_replays', 0):.0f})")
    print(f"  heartbeat misses: {misses:.0f}   reconnect attempts: "
          f"{reconnects:.0f}")
    if partial or late:
        print(f"  partial rounds: {partial:.0f}   late folds: {late:.0f}"
              f"   late superseded: "
              f"{counters.get('traffic.late_superseded', 0):.0f}")


def _print_edge_summary(metrics: dict) -> None:
    """The hierarchical-tier story (edge.* family, docs/traffic.md
    "Hierarchical edge tier"): how many pre-folded summaries the root
    consumed instead of raw client updates, and what the edge failure
    domains absorbed (re-homing, re-solicited replays, degraded-mode
    adoptions). Silent when the run was flat."""
    counters = metrics.get("counters", {})
    folded = counters.get("edge.summaries_folded", 0)
    folds = counters.get("edge.folds", 0)
    if not (folded or folds):
        return
    print("\nedge tier (hierarchical aggregation):")
    print(f"  summaries folded at root: {folded:.0f} "
          f"({counters.get('edge.summary_entries', 0):.0f} client entries)"
          f"   edge folds: {folds:.0f}   direct client updates: "
          f"{counters.get('edge.direct_client_updates', 0):.0f}")
    rehomed = counters.get("comm.rehomes", 0)
    adopted = counters.get("edge.rehomed_clients", 0)
    root_adopt = counters.get("edge.root_adoptions", 0)
    resolicited = counters.get("edge.resolicited_updates", 0)
    dedup = counters.get("edge.buffer_dedup_drops", 0)
    replay_drops = counters.get("traffic.replay_dedup_drops", 0)
    if rehomed or adopted or root_adopt or resolicited or dedup \
            or replay_drops:
        print(f"  re-homed clients: {rehomed:.0f} "
              f"(edge adoptions {adopted:.0f}, root adoptions "
              f"{root_adopt:.0f})   re-solicited replays: "
              f"{resolicited:.0f}")
        print(f"  dedup drops: {dedup:.0f} edge buffer / "
              f"{replay_drops:.0f} root replay")


def _print_mem_summary(metrics: dict) -> None:
    """The retention story (mem.* family, docs/graftmem.md): per-container
    occupancy and eviction counts from the serving plane's BoundedDicts —
    the runtime face of the graftmem static gate. Silent when no bounded
    container published (a run predating the mem.* family)."""
    gauges = metrics.get("gauges", {})
    counters = metrics.get("counters", {})
    rows = []
    for name in sorted(gauges):
        if name.startswith("mem.") and name.endswith(".occupancy"):
            container = name[len("mem."):-len(".occupancy")]
            rows.append((container, gauges[name],
                         counters.get(f"mem.{container}.evictions", 0.0)))
    if not rows:
        return
    print("\nmemory (bounded serving-plane containers):")
    for container, occ, ev in rows:
        print(f"  {container:<28} occupancy {occ:>8.0f}   "
              f"evictions {ev:>6.0f}")


def _print_delta_summary(metrics: dict) -> None:
    """The delta delivery plane's wire story (comm.delta.* family,
    docs/delivery.md): delta hit rate and bytes saved per direction, plus
    the version store's occupancy/eviction health. Silent when the plane
    never engaged (no delta frame, no compressed decode)."""
    counters = metrics.get("counters", {})
    gauges = metrics.get("gauges", {})
    s2c_delta = counters.get("comm.delta.s2c_delta_frames", 0)
    s2c_full = counters.get("comm.delta.s2c_full_frames", 0)
    c2s_decodes = counters.get("comm.delta.c2s_delta_decodes", 0)
    if not (s2c_delta or c2s_decodes):
        return
    print("\ndelivery plane (delta shipping):")
    total = s2c_delta + s2c_full
    rate = s2c_delta / total if total else 0.0
    print(f"  s2c: {s2c_delta:.0f} delta / {s2c_full:.0f} full frames   "
          f"delta hit rate {rate:.2f}   "
          f"saved {counters.get('comm.delta.s2c_bytes_saved', 0) / 1e6:.2f} "
          "MB")
    print(f"  c2s: {c2s_decodes:.0f} delta decodes   saved "
          f"{counters.get('comm.delta.c2s_bytes_saved', 0) / 1e6:.2f} MB   "
          f"base-missing drops "
          f"{counters.get('comm.delta.c2s_base_missing', 0):.0f}")
    occ = gauges.get("comm.delta.server_store.occupancy")
    ev = counters.get("comm.delta.server_store.evictions", 0)
    if occ is not None or ev:
        print(f"  store: occupancy {occ if occ is not None else 0:.0f}   "
              f"evictions {ev:.0f}")


def _print_traffic_summary(metrics: dict) -> None:
    """The async plane's backpressure story (traffic.* family, PR 7) next
    to the phase table: accepted vs shed, staleness actually folded, and
    how close the dispatch buffer ran to its limit."""
    counters = metrics.get("counters", {})
    gauges = metrics.get("gauges", {})
    hists = metrics.get("histograms", {})
    accepted = counters.get("traffic.accepted_updates", 0)
    shed_rate = counters.get("traffic.shed_rate_limited", 0)
    shed_queue = counters.get("traffic.shed_queue_full", 0)
    stale = counters.get("traffic.stale_dropped_updates", 0)
    steps = counters.get("traffic.server_steps", 0)
    if not (accepted or shed_rate or shed_queue or stale or steps):
        return  # sync run: the async plane never engaged
    print("\ntraffic plane (async aggregation):")
    print(f"  accepted: {accepted:.0f}   shed: "
          f"{shed_rate + shed_queue:.0f} "
          f"(rate-limited {shed_rate:.0f}, queue-full {shed_queue:.0f})   "
          f"stale-dropped: {stale:.0f}")
    line = f"  server steps: {steps:.0f}"
    occupancy = gauges.get("traffic.buffer_occupancy")
    if occupancy is not None:
        line += f"   buffer occupancy: {occupancy:.0f}"
    print(line)
    for name, label in (("traffic.staleness", "staleness"),
                        ("traffic.dispatch_ready_s", "dispatch→ready")):
        h = hists.get(name)
        if not h or not h.get("count"):
            continue
        unit = "" if name == "traffic.staleness" else "s"
        print(f"  {label}: p50 {h['p50']:.3f}{unit}   "
              f"p95 {h['p95']:.3f}{unit}   p99 {h['p99']:.3f}{unit} "
              f"(n={h['count']:.0f})")


def cmd_trace(args) -> int:
    """Merge a federation's per-process span files into ONE clock-aligned
    causal trace (docs/tracing.md): collect every run JSONL sink + flight-
    recorder post-mortem in the trace dir, align each process's monotonic
    timeline (heartbeat probe offsets, wall-anchor fallback), and print the
    per-round critical path, segment shares, and straggler attribution —
    or export Chrome trace-event JSON for Perfetto (``--chrome``)."""
    from .core.mlops import tracing

    trace_dir = args.dir or ".fedml_tpu_runs"
    files = tracing.collect_trace_files(trace_dir,
                                        run_id=args.run_id or None)
    if not files:
        print(f"no trace files in {trace_dir} "
              "(run with --enable_tracing + --enable_tracking)")
        return 1
    spans, clocks = tracing.read_trace(files)
    merged = tracing.merge_trace(spans, clocks)
    if not merged["spans"]:
        print(f"{len(files)} files in {trace_dir} but no trace_span "
              "records (was the run traced?)")
        return 1
    if args.chrome:
        with open(args.chrome, "w", encoding="utf-8") as f:
            json.dump(tracing.to_chrome(merged), f)
    shares = tracing.critical_path_shares(merged)
    stragglers = tracing.straggler_attribution(merged, k=args.top)
    round_idx = (args.round if args.round >= 0
                 else (merged["rounds"][-1] if merged["rounds"] else -1))
    path = tracing.critical_path(merged, round_idx) if round_idx >= 0 else []
    if args.json:
        print(json.dumps({
            "files": len(files), "spans": len(merged["spans"]),
            "procs": [list(p) for p in merged["procs"]],
            "rounds": merged["rounds"], "orphans": merged["orphans"],
            "critical_path_round": round_idx,
            "critical_path": path,
            "critical_path_segments": shares,
            "stragglers": stragglers,
        }, indent=2, sort_keys=True))
        return 0
    print(f"trace dir: {trace_dir}   files: {len(files)}")
    print(f"spans: {len(merged['spans'])}   "
          f"processes: {len(merged['procs'])}   "
          f"rounds: {len(merged['rounds'])}   "
          f"orphans: {len(merged['orphans'])}")
    if args.chrome:
        print(f"chrome trace: {args.chrome} "
              "(load in Perfetto or chrome://tracing)")
    if path:
        print(f"\ncritical path (round {round_idx}):")
        for seg in path:
            who = (f"client {seg['client']}" if seg.get("client") is not None
                   else f"rank {seg.get('rank')}")
            label = seg["name"]
            if label == "transit":
                label = f"transit {seg.get('from')}→{seg.get('to')}"
            print(f"  {label:<28} {1e3 * seg['dur_s']:>9.3f}ms  {who}")
    total = sum(shares.values())
    if shares:
        print("\ncritical-path segment shares (all rounds):")
        for name, dur in sorted(shares.items(), key=lambda kv: -kv[1]):
            pct = 100.0 * dur / total if total else 0.0
            print(f"  {name:<18} {dur:>9.4f}s {pct:>6.1f}%")
    if stragglers:
        print("\nstragglers (attributed wait vs the round's fastest "
              "chain):")
        for s in stragglers:
            print(f"  client {s['client']:<4} +{s['wait_s']:.4f}s  "
                  f"gated {s['rounds_gated']} rounds")
    return 0


def cmd_build(args) -> int:
    """Package a training directory into a deployable zip
    (reference: cli.py ``build`` — client/server MLOps packages)."""
    src = os.path.abspath(args.source_folder)
    if not os.path.isdir(src):
        print(f"error: {src} is not a directory")
        return 1
    out = os.path.abspath(args.output or f"{os.path.basename(src)}_package.zip")
    entry = args.entry_point
    if entry and not os.path.exists(os.path.join(src, entry)):
        print(f"error: entry point {entry!r} not found in {src}")
        return 1
    with zipfile.ZipFile(out, "w", zipfile.ZIP_DEFLATED) as z:
        for root, _dirs, files in os.walk(src):
            for fn in files:
                if fn.endswith((".pyc", ".pyo")) or "__pycache__" in root:
                    continue
                full = os.path.join(root, fn)
                z.write(full, os.path.relpath(full, src))
        manifest = {"type": args.type, "entry_point": entry or "main.py"}
        z.writestr("fedml_package.json", json.dumps(manifest, indent=2))
    print(f"built {args.type} package: {out}")
    return 0


def cmd_login(args) -> int:
    """Bind this host as an edge device (reference: fedml login)."""
    from .agent import login

    state = login(args.account_id, role=args.role, state_dir=args.state_dir)
    print(f"bound as {state['role']} device {state['device_id']} "
          f"(account {state['account_id']})")
    return 0


def cmd_logout(args) -> int:
    from .agent import logout

    print("unbound" if logout(state_dir=args.state_dir) else "not bound")
    return 0


def cmd_launch(args) -> int:
    """Submit a built package to a job queue (reference: run-start msg)."""
    from .agent import submit_job

    job_id = submit_job(args.package, args.jobs_dir,
                        run_args=args.run_args or [])
    print(f"submitted {job_id} to {args.jobs_dir}")
    return 0


def cmd_agent(args) -> int:
    """Run the edge/server job daemon (reference: client_daemon.py)."""
    from .agent import Agent, agent_state

    state = agent_state(state_dir=args.state_dir)
    role = args.role or (state or {}).get("role", "client")
    agent = Agent(args.jobs_dir, args.work_dir, role=role)
    if args.once:
        result = agent.run_once()
        print("no pending jobs" if result is None
              else f"{result.job_id}: {result.status}")
        return 0 if result is None or result.status == "FINISHED" else 1
    agent.run_forever(max_jobs=args.max_jobs)
    return 0


def cmd_cache(args) -> int:
    """Inspect / clear the persistent XLA compilation cache.

    The cache is what lets repeat runs skip the compile wall; every run
    that starts through ``fedml_tpu.init`` uses the directory
    ``device.enable_compilation_cache`` decides.
    """
    from .device import enable_compilation_cache

    cache_dir = args.dir or enable_compilation_cache()
    if not os.path.isdir(cache_dir):
        print(f"compilation cache: {cache_dir} (empty — no directory)")
        _report_cache_telemetry(getattr(args, "run_file", ""))
        return 0
    entries, total = [], 0
    for root, _dirs, files in os.walk(cache_dir):
        for fn in files:
            full = os.path.join(root, fn)
            try:
                total += os.path.getsize(full)
                entries.append(full)
            except OSError:
                pass
    if args.clear:
        for full in entries:
            try:
                os.remove(full)
            except OSError:
                pass
        print(f"compilation cache: cleared {len(entries)} entries "
              f"({total / 1e6:.1f} MB) from {cache_dir}")
        return 0
    print(f"compilation cache: {cache_dir}")
    print(f"  entries: {len(entries)}")
    print(f"  size:    {total / 1e6:.1f} MB")
    _report_cache_telemetry(getattr(args, "run_file", ""))
    return 0


def _report_cache_telemetry(run_file: str) -> None:
    """Hit/miss counts from the newest tracked run's telemetry summary, so
    repeat-run compile savings are visible next to the cache's disk state."""
    from .core.mlops import read_events

    path = _resolve_run_file(run_file)
    if not path or not os.path.exists(path):
        return
    summary = next(
        (e for e in reversed(read_events(path))
         if e.get("kind") == "telemetry_summary"), None)
    if summary is None:
        return
    counters = (summary.get("metrics") or {}).get("counters", {})
    hits = counters.get("jax.compilation_cache.hits", 0)
    misses = counters.get("jax.compilation_cache.misses", 0)
    saved = counters.get("jax.compilation_cache.time_saved_s", 0.0)
    compiles = counters.get("jax.compiles", 0)
    if not (hits or misses or compiles):
        return
    print(f"  last tracked run ({os.path.basename(path)}):")
    print(f"    cache hits/misses: {hits:.0f}/{misses:.0f}"
          + (f", ~{saved:.1f}s compile time saved" if saved else ""))
    print(f"    backend compiles:  {compiles:.0f}")


def cmd_lint(args) -> int:
    """Run the static-analysis suites over the tree. Default: graftlint
    (tools/graftlint) — trace-safety (G001), donation (G002), recompile
    (G003), purity (G004) and thread-safety (G005). ``--proto``: graftproto
    (tools/graftproto) — message-flow graph (P001–P003), FSM replay/
    termination (P004/P005), delivery invariants (P006/P007) and lock-order
    analysis (P008/P009). ``--shard``: graftshard (tools/graftshard) —
    partition-rule coverage (S001), spec validity (S002), implicit-reshard
    (S003), host-transfer (S004) and static HBM budgets (S005, via
    ``--model``/``--mesh``). ``--rep``: graftrep (tools/graftrep) —
    determinism discipline (D001 key reuse, D002 seed provenance, D003
    unordered accumulation, D004 dtype drift, D005 run-identity leaks).
    ``--iso``: graftiso (tools/graftiso) — serving-plane state ownership (I001
    module-global state in handlers, I002 unscoped singleton access, I003
    class-level defaults & cross-instance aliasing, I004 ambient config,
    I005 untethered thread lifecycle). ``--mem``: graftmem (tools/graftmem)
    — serving-plane retention (M001 unbounded keyed growth, M002
    capacity-less caches, M003 telemetry cardinality explosion, M004
    undrained parking, M005 payload retention past commit). Shells into
    the same entry points CI uses, anchored at the repo root so results
    are identical from any cwd.

    Exit codes (all suites): 0 clean, 1 findings, 2 the analyzer itself
    crashed (or usage error) — CI failures are diagnosable at a glance."""
    import subprocess

    picked = [flag for flag in ("proto", "shard", "rep", "iso", "mem")
              if getattr(args, flag, False)]
    if len(picked) > 1:
        print(f"fedml_tpu lint: --{picked[0]} and --{picked[1]} are "
              "different suites — pick one (or run all six like "
              "tools/lint_smoke.sh does)")
        return 2
    suite = ("graftproto" if getattr(args, "proto", False)
             else "graftshard" if getattr(args, "shard", False)
             else "graftrep" if getattr(args, "rep", False)
             else "graftiso" if getattr(args, "iso", False)
             else "graftmem" if getattr(args, "mem", False)
             else "graftlint")
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if not os.path.isdir(os.path.join(repo_root, "tools", suite)):
        print(f"fedml_tpu lint: tools/{suite} not found next to the "
              f"package (looked in {repo_root}) — run from a source checkout")
        return 2
    # absolutize user paths: the subprocess runs with cwd=repo_root, which
    # would otherwise re-resolve relative paths against the wrong directory
    paths = [os.path.abspath(p) for p in args.paths] or ["fedml_tpu"]
    cmd = [sys.executable, "-m", f"tools.{suite}", *paths]
    if args.format != "text":
        cmd += ["--format", args.format]
    if args.runtime:
        if suite == "graftproto":
            print("fedml_tpu lint: --runtime is a graftlint/graftshard "
                  "pass; it does not combine with --proto")
            return 2
        if suite == "graftrep":
            print("fedml_tpu lint: --runtime is a graftlint/graftshard "
                  "pass; graftrep is pure AST")
            return 2
        if suite == "graftiso":
            print("fedml_tpu lint: --runtime is a graftlint/graftshard "
                  "pass; graftiso's runtime witness is the swarm/chaos "
                  "thread-leak assertion (fedml_tpu swarm / chaos)")
            return 2
        if suite == "graftmem":
            print("fedml_tpu lint: --runtime is a graftlint/graftshard "
                  "pass; graftmem's runtime witness is the RSS-slope soak "
                  "(fedml_tpu swarm --leak_check)")
            return 2
        cmd.append("--runtime")
    if getattr(args, "model", ""):
        if suite != "graftshard":
            print("fedml_tpu lint: --model is the graftshard HBM "
                  "estimator — add --shard")
            return 2
        cmd += ["--model", args.model]
        if getattr(args, "mesh", ""):
            cmd += ["--mesh", args.mesh]
    elif getattr(args, "mesh", ""):
        print("fedml_tpu lint: --mesh needs --shard --model")
        return 2
    for flag, value in (("--check-rules", getattr(args, "check_rules", "")),
                        ("--check-state-rules",
                         getattr(args, "check_state_rules", ""))):
        if value:
            if suite != "graftshard":
                print(f"fedml_tpu lint: {flag} is a graftshard rule-set "
                      "check — add --shard")
                return 2
            cmd += [flag, value]
    return subprocess.call(cmd, cwd=repo_root)


def cmd_chaos(args) -> int:
    """Chaos soak harness (fedml_tpu/chaos.py): run a loopback cross-silo
    federation under a seeded fault matrix (visible loss + duplication +
    payload corruption + mid-run self-SIGTERM), restart it with
    ``--resume auto``, and verify the recovered run's final global params
    are bitwise-equal to a fault-free reference run with no contribution
    counted twice. CI entry: ``tools/chaos_smoke.sh``."""
    import logging as _logging

    from .chaos import main as chaos_main

    _logging.basicConfig(level=_logging.INFO)
    return chaos_main(args)


def cmd_swarm(args) -> int:
    """Client-swarm traffic soak (fedml_tpu/traffic/swarm.py): drive the
    async cross-silo server (``aggregation_mode=async``, FedBuff-style
    buffered aggregation + admission control) with thousands of concurrent
    simulated devices — seeded think-time/dropout processes over loopback
    or real multiprocess gRPC — and report p99 dispatch→ready latency plus
    the traffic.* backpressure counters as JSON. CI entry:
    ``tools/swarm_smoke.sh``."""
    import logging as _logging

    from .traffic.swarm import run_device_worker, run_swarm

    _logging.basicConfig(
        level=_logging.WARNING if args.worker else _logging.INFO)
    if args.worker:
        return run_device_worker(args)
    return run_swarm(args)


def cmd_multihost(args) -> int:
    """Spawn N coordinated worker processes (analog: mpirun -np N).

    reference: the MPI launch plane; here jax.distributed under one mesh —
    see ``parallel/multihost.py``.
    """
    import sys as _sys

    from .parallel.multihost import spawn

    try:
        results = spawn(
            [args.script, *args.script_args],
            n_processes=args.np, local_device_count=args.local_devices,
            timeout_s=args.timeout,
        )
    except (RuntimeError, TimeoutError) as e:
        print(e)
        return 1
    for r in results:
        _sys.stdout.write(r.stdout)
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="fedml_tpu", description=__doc__)
    sub = parser.add_subparsers(dest="command")

    sub.add_parser("version", help="print version")
    sub.add_parser("env", help="environment report")
    sub.add_parser("status", help="tracked run status")

    p_logs = sub.add_parser("logs", help="show run event logs")
    p_logs.add_argument("--file", default="", help="specific event file")
    p_logs.add_argument("-n", type=int, default=20, help="tail lines")

    p_top = sub.add_parser(
        "top", help="phase-latency breakdown of a tracked run"
    )
    p_top.add_argument("file", nargs="?", default="",
                       help="run JSONL event file (default: newest run)")

    p_trace = sub.add_parser(
        "trace",
        help="merge per-process span files into one clock-aligned trace: "
        "round critical path, segment shares, straggler attribution, "
        "Perfetto export (docs/tracing.md)",
    )
    p_trace.add_argument("dir", nargs="?", default="",
                         help="trace dir holding run_*.jsonl sinks + "
                         "flight_*.json post-mortems "
                         "(default: .fedml_tpu_runs)")
    p_trace.add_argument("--run_id", default="",
                         help="only merge this run's files")
    p_trace.add_argument("--round", type=int, default=-1,
                         help="print the critical path of this round "
                         "(default: the last traced round)")
    p_trace.add_argument("--chrome", default="", metavar="OUT.json",
                         help="also write Chrome trace-event JSON "
                         "(Perfetto / chrome://tracing)")
    p_trace.add_argument("--top", type=int, default=5,
                         help="straggler top-k")
    p_trace.add_argument("--json", action="store_true",
                         help="machine-readable output")

    p_build = sub.add_parser("build", help="package a training dir")
    p_build.add_argument("--type", "-t", choices=("client", "server"),
                         default="client")
    p_build.add_argument("--source_folder", "-sf", required=True)
    p_build.add_argument("--entry_point", "-ep", default="")
    p_build.add_argument("--output", "-o", default="")

    p_login = sub.add_parser("login", help="bind this host as an edge device")
    p_login.add_argument("account_id")
    p_login.add_argument("--role", "-r", choices=("client", "server"),
                         default="client")
    p_login.add_argument("--state_dir", default=".fedml_tpu_agent")

    p_logout = sub.add_parser("logout", help="unbind this host")
    p_logout.add_argument("--state_dir", default=".fedml_tpu_agent")

    p_launch = sub.add_parser(
        "launch", help="submit a package to a job queue",
        usage="%(prog)s [--jobs_dir DIR] package [run_args ...]",
    )
    p_launch.add_argument("--jobs_dir", "-j", default=".fedml_tpu_jobs")
    p_launch.add_argument("package")
    # REMAINDER: everything after the package — flags included — goes to the
    # job's entry point verbatim (launch options must precede the package):
    #   fedml_tpu launch -j /queue pkg.zip --lr 0.1
    p_launch.add_argument("run_args", nargs=argparse.REMAINDER)

    p_agent = sub.add_parser("agent", help="run the edge/server job daemon")
    p_agent.add_argument("--role", choices=("client", "server"), default="")
    p_agent.add_argument("--jobs_dir", "-j", default=".fedml_tpu_jobs")
    p_agent.add_argument("--work_dir", "-w", default=".fedml_tpu_work")
    p_agent.add_argument("--state_dir", default=".fedml_tpu_agent")
    p_agent.add_argument("--once", action="store_true",
                         help="claim and run at most one job, then exit")
    p_agent.add_argument("--max_jobs", type=int, default=None)

    p_cache = sub.add_parser(
        "cache", help="inspect/clear the persistent XLA compilation cache"
    )
    p_cache.add_argument("--dir", default="",
                         help="cache dir (default: $JAX_COMPILATION_CACHE_DIR,"
                         " else <checkout>/.jax_cache)")
    p_cache.add_argument("--clear", action="store_true",
                         help="delete every cache entry")
    p_cache.add_argument("--run_file", default="",
                         help="run JSONL to read hit/miss telemetry from "
                         "(default: newest run)")

    p_lint = sub.add_parser(
        "lint",
        help="run static analysis over the tree (graftlint; --proto for "
        "the comm-plane protocol suite, --shard for the TPU execution "
        "plane's sharding/HBM suite, --rep for the determinism suite)",
    )
    p_lint.add_argument("paths", nargs="*", default=[],
                        help="files/dirs to lint (default: fedml_tpu)")
    p_lint.add_argument("--format", choices=("text", "json"), default="text")
    p_lint.add_argument("--proto", action="store_true",
                        help="run graftproto (message-flow graph, FSM "
                        "replay/termination, delivery invariants, lock "
                        "order) instead of graftlint")
    p_lint.add_argument("--shard", action="store_true",
                        help="run graftshard (partition-rule coverage, "
                        "spec validity, implicit-reshard/host-transfer "
                        "detection, static HBM budgets) instead of "
                        "graftlint")
    p_lint.add_argument("--iso", action="store_true",
                        help="run graftiso (tools/graftiso: state-"
                        "ownership, tenant-isolation & thread-lifecycle "
                        "verification of the serving plane) instead of "
                        "graftlint")
    p_lint.add_argument("--mem", action="store_true",
                        help="run graftmem (tools/graftmem: unbounded-"
                        "state & retention verification of the serving "
                        "plane — bounded containers, drained parking, "
                        "released payloads) instead of graftlint")
    p_lint.add_argument("--rep", action="store_true",
                        help="run graftrep (PRNG-key discipline, seed "
                        "provenance, unordered accumulation, dtype drift, "
                        "run-identity leaks) instead of graftlint")
    p_lint.add_argument("--runtime", action="store_true",
                        help="also run the suite's runtime pass: graftlint "
                        "traces the round engine under jax.make_jaxpr, "
                        "graftshard diffs declared vs inferred shardings "
                        "over a forced multi-device CPU mesh")
    p_lint.add_argument("--model", default="",
                        help="(--shard) run the S005 HBM-budget estimator "
                        "for this model registry entry (e.g. 7b)")
    p_lint.add_argument("--mesh", default="",
                        help="(--shard) mesh rows for --model, e.g. "
                        "'4x4' or 'v5e:2x4,v5p:2x2x2'")
    p_lint.add_argument("--check-rules", default="", dest="check_rules",
                        help="(--shard) validate a --mesh_partition_rules "
                        "string (S001 catch-all + S002 axis validity)")
    p_lint.add_argument("--check-state-rules", default="",
                        dest="check_state_rules",
                        help="(--shard) validate a --mesh_state_rules "
                        "string the same way")

    p_chaos = sub.add_parser(
        "chaos",
        help="chaos soak: faults + kill/restart must reproduce the "
        "fault-free run bitwise",
    )
    p_chaos.add_argument("--clients", type=int, default=2)
    p_chaos.add_argument("--rounds", type=int, default=4)
    p_chaos.add_argument("--epochs", type=int, default=1)
    p_chaos.add_argument("--seed", type=int, default=7)
    p_chaos.add_argument("--loss", type=float, default=0.1,
                         help="visible (retryable) per-message loss prob")
    p_chaos.add_argument("--duplicate", type=float, default=0.2,
                         help="wire-duplication probability")
    p_chaos.add_argument("--corrupt", type=float, default=0.2,
                         help="payload-corruption probability")
    p_chaos.add_argument("--kill-round", type=int, default=1, metavar="R",
                         help="self-SIGTERM once the ledger commits round R "
                         "(-1 disables the kill)")
    p_chaos.add_argument("--compression", default="",
                         choices=("", "topk", "quantize", "qsgd"),
                         help="run BOTH legs with this C2S update "
                         "compression: dedup + digests must survive delta "
                         "frames bitwise (stateless schemes only — eftopk's "
                         "client residual does not survive a restart)")
    p_chaos.add_argument("--compression_ratio", type=float, default=0.1,
                         help="top-k fraction for --compression topk")
    p_chaos.add_argument("--checkpoint_rounds", type=int, default=1)
    p_chaos.add_argument("--workdir", default="",
                         help="scratch dir (default: a fresh temp dir)")
    p_chaos.add_argument("--timeout", type=float, default=240.0,
                         help="per-leg subprocess timeout (seconds)")
    p_chaos.add_argument("--transport", choices=("loopback", "grpc"),
                         default="loopback",
                         help="faulty-leg transport: loopback threads, or "
                         "REAL multiprocess gRPC clients (the reference "
                         "leg stays loopback — parity must hold across "
                         "transports)")
    p_chaos.add_argument("--kill-phase", dest="kill_phase", default="",
                         choices=("", "pre_fold", "mid_fold",
                                  "post_commit"),
                         help="crash-failover soak: SIGKILL the server "
                         "process (no drain) at this protocol phase of "
                         "--kill-round, restart it with --resume auto, and "
                         "require bitwise parity with the fault-free run; "
                         "with --transport grpc the client processes "
                         "SURVIVE the kill and resync onto the restarted "
                         "server (heartbeat miss -> c2s_resync -> replay)")
    p_chaos.add_argument("--edges", type=int, default=0, metavar="E",
                         help="hierarchical edge-aggregation tier for the "
                         "FAULTY leg: E edge aggregators between clients "
                         "and root (the reference leg stays flat — the "
                         "bitwise verdict proves 2-tier ≡ flat). Loopback "
                         "transport only")
    p_chaos.add_argument("--kill-edge", dest="kill_edge", default="",
                         choices=("", "pre_fold", "mid_fold",
                                  "post_commit"),
                         help="fail-stop the FIRST edge aggregator at this "
                         "protocol phase (first hit): its clients must "
                         "detect the death, re-home to a sibling edge (or "
                         "the root), replay their cached updates, and the "
                         "run must still finish bitwise-equal with "
                         "exactly-once contributions. Needs --edges >= 2")
    p_chaos.add_argument("--edge-partition", dest="edge_partition",
                         default="", metavar="START:DURATION",
                         help="cut the FIRST edge off from the root for "
                         "the window (seconds since leg start) — the edge "
                         "rides it out on its resync FSM and re-ships its "
                         "cached summary; dedup + the committed-round "
                         "guard keep contributions exactly-once")
    p_chaos.add_argument("--partition", default="",
                         metavar="START:DURATION",
                         help="cut the server off from every client for "
                         "the window (seconds from world start, both "
                         "directions visible-fail); the at-least-once "
                         "layer must absorb it bitwise")
    p_chaos.add_argument("--heartbeat_s", type=float, default=0.0,
                         help="client heartbeat interval for the soak "
                         "(0 = auto: on for kill legs, off otherwise)")
    p_chaos.add_argument("--trace_dir", default="",
                         help="distributed-tracing span/flight dir for the "
                         "faulty legs (kill-phase legs default to "
                         "WORKDIR/trace and verify the pre-SIGKILL "
                         "post-mortem + orphan-free merge)")
    # internal: run ONE chaos leg in this process (the orchestrator's child)
    p_chaos.add_argument("--worker", action="store_true",
                         help=argparse.SUPPRESS)
    # internal: the crash-failover flow's server-only worker — the
    # orchestrator owns the client processes so they survive the kill
    p_chaos.add_argument("--server-only", dest="server_only",
                         action="store_true", help=argparse.SUPPRESS)
    p_chaos.add_argument("--out", default="", help=argparse.SUPPRESS)
    p_chaos.add_argument("--checkpoint_dir", default="",
                         help=argparse.SUPPRESS)
    # internal: run ONE real gRPC client in this process (spawned by the
    # chaos worker's ProcSpawner for the multiprocess transport leg)
    p_chaos.add_argument("--client", action="store_true",
                         help=argparse.SUPPRESS)
    p_chaos.add_argument("--client_rank", type=int, default=0,
                         help=argparse.SUPPRESS)
    p_chaos.add_argument("--port", type=int, default=0,
                         help=argparse.SUPPRESS)

    p_swarm = sub.add_parser(
        "swarm",
        help="client-swarm traffic soak against the async (FedBuff-style) "
        "server: seeded arrival/dropout, admission control, p99 "
        "dispatch→ready report",
    )
    p_swarm.add_argument("--clients", type=int, default=200,
                         help="concurrent simulated devices")
    p_swarm.add_argument("--steps", type=int, default=20,
                         help="server steps (model versions) to run")
    p_swarm.add_argument("--buffer", type=int, default=0,
                         help="async buffer size K (0 = min(10, clients))")
    p_swarm.add_argument("--staleness_alpha", type=float, default=0.5,
                         help="staleness decay exponent (1+s)^-alpha")
    p_swarm.add_argument("--max_staleness", type=int, default=0,
                         help="drop updates staler than this (0 = never)")
    p_swarm.add_argument("--flush_s", type=float, default=5.0,
                         help="flush a partial buffer after this stall")
    p_swarm.add_argument("--admit_rate", type=float, default=0.0,
                         help="token-bucket admission rate, updates/s "
                         "(0 = unlimited)")
    p_swarm.add_argument("--admit_burst", type=int, default=0,
                         help="token-bucket burst (0 = 2x buffer)")
    p_swarm.add_argument("--queue_limit", type=int, default=0,
                         help="bounded fold-queue depth (0 = 4x buffer)")
    p_swarm.add_argument("--think_s", type=float, default=0.2,
                         help="mean device think time, seconds "
                         "(exponential — Poisson arrivals at the server)")
    p_swarm.add_argument("--dropout", type=float, default=0.0,
                         help="per-dispatch device dropout probability")
    p_swarm.add_argument("--seed", type=int, default=7)
    p_swarm.add_argument("--tiers", type=int, default=1,
                         help="aggregation tiers: 2 inserts an edge-"
                         "aggregator tier between devices and root "
                         "(~1 edge per 100 devices unless --edges is "
                         "given); root then folds E pre-folded summaries "
                         "per bump instead of N raw updates")
    p_swarm.add_argument("--edges", type=int, default=0, metavar="E",
                         help="explicit edge-aggregator count for the "
                         "tiered soak (implies --tiers 2)")
    p_swarm.add_argument("--backend", choices=("loopback", "grpc"),
                         default="loopback")
    p_swarm.add_argument("--procs", type=int, default=2,
                         help="device-host processes (grpc backend)")
    p_swarm.add_argument("--ranks_per_port", type=int, default=0,
                         help="gRPC rank→port multiplexing: N device ranks "
                         "share one port/server (0 = auto: one port per "
                         "device-host process; 1 = legacy port-per-rank)")
    p_swarm.add_argument("--port", type=int, default=18950,
                         help="gRPC base port")
    p_swarm.add_argument("--s2c_delta", choices=("auto", "off"),
                         default="off",
                         help="S2C delta plane for the soak: auto makes "
                         "devices delta-capable (ACK + base store + frame "
                         "decode) so dispatches ship delta frames; off "
                         "keeps the legacy full-frame soak")
    p_swarm.add_argument("--wire_path", choices=("host", "device", "auto"),
                         default="auto",
                         help="delta codec implementation for the soak: "
                         "device forces the jit'd kernels (byte-identical "
                         "frames), host the numpy reference, auto picks "
                         "device only on a real accelerator")
    p_swarm.add_argument("--timeout", type=float, default=300.0)
    p_swarm.add_argument("--run_id", default="swarm")
    p_swarm.add_argument("--trace", action="store_true",
                         help="distributed tracing for the soak: every "
                         "process records causal spans, and the report "
                         "gains trace_spans / critical_path_segments plus "
                         "the traced dispatch→ready sum (reconciles with "
                         "the traffic.dispatch_ready_s histogram)")
    p_swarm.add_argument("--trace_sample", type=float, default=1.0,
                         metavar="P",
                         help="fraction of rounds traced (deterministic "
                         "per-round hash; 1.0 = every round)")
    p_swarm.add_argument("--trace_dir", default="",
                         help="span/flight dir (default: "
                         ".fedml_tpu_runs/trace_RUN_ID)")
    p_swarm.add_argument("--leak_check", action="store_true",
                         help="memory-leak witness (graftmem's runtime "
                         "half): sample VmRSS across the soak, fail on a "
                         "positive steady-state slope, and report the "
                         "mem.* per-container occupancy gauges")
    p_swarm.add_argument("--leak_interval", type=float, default=0.2,
                         metavar="S",
                         help="RSS sampling period in seconds")
    p_swarm.add_argument("--leak_slope_mb_s", type=float, default=1.0,
                         metavar="MB",
                         help="max tolerated steady-state RSS slope "
                         "(MB/s over the soak's second half)")
    # internal: one gRPC device-host process (the orchestrator's child)
    p_swarm.add_argument("--worker", action="store_true",
                         help=argparse.SUPPRESS)
    p_swarm.add_argument("--rank_base", type=int, default=1,
                         help=argparse.SUPPRESS)
    p_swarm.add_argument("--count", type=int, default=0,
                         help=argparse.SUPPRESS)

    p_mh = sub.add_parser(
        "multihost", help="spawn N coordinated worker processes",
        usage="%(prog)s [-np N] [--local_devices D] script [script_args ...]",
    )
    p_mh.add_argument("-np", type=int, default=2,
                      help="number of worker processes")
    p_mh.add_argument("--local_devices", type=int, default=1,
                      help="virtual CPU devices per worker (emulation runs)")
    p_mh.add_argument("--timeout", type=float, default=600.0)
    p_mh.add_argument("script")
    p_mh.add_argument("script_args", nargs=argparse.REMAINDER)

    args = parser.parse_args(argv)
    handlers = {
        "version": cmd_version,
        "env": cmd_env,
        "status": cmd_status,
        "logs": cmd_logs,
        "top": cmd_top,
        "trace": cmd_trace,
        "build": cmd_build,
        "login": cmd_login,
        "logout": cmd_logout,
        "launch": cmd_launch,
        "agent": cmd_agent,
        "cache": cmd_cache,
        "lint": cmd_lint,
        "chaos": cmd_chaos,
        "swarm": cmd_swarm,
        "multihost": cmd_multihost,
    }
    if args.command is None:
        parser.print_help()
        return 1
    return handlers[args.command](args)


if __name__ == "__main__":
    raise SystemExit(main())
