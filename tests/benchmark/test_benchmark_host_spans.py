"""``benchmark/host_spans.py`` and the four readers built on it, on synthetic
records and a synthetic ``Trace`` whose launch, wake, gap and idle seconds can
be worked out by hand."""

import types

import numpy as np
import pytest

from benchmark import harness, host_spans
from benchmark import trace_reduce as tr

ORIGIN_NS = 1_700_000_000_000_000_000  # the profile's start on the epoch clock
STEP_MODULE = "_train_step_raw"

READERS = ["cheetah_runner.launch_wake_s_per_step",
           "cheetah_runner.host_between_s_per_step",
           "device.step_idle_attributed_share",
           "device.round_idle_attributed_share"]


def events(*rows):
    names = list(dict.fromkeys(r[0] for r in rows))
    return tr.Events(names, np.array([names.index(r[0]) for r in rows], int),
                     np.array([r[1] for r in rows], float),
                     np.array([r[2] for r in rows], float))


def span(name, start_s, end_s, parent=None, ident=0):
    return {"name": name, "span": ident, "parent": parent,
            "ts_ns": ORIGIN_NS + round(start_s * 1e9),
            "dur_ns": round((end_s - start_s) * 1e9)}


def op(name):
    return f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p)"


def run_of(records, devices, module=STEP_MODULE, start_epoch_ns=ORIGIN_NS):
    trace = None if devices is None else tr.Trace(devices, start_epoch_ns)
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(root=harness.ROOT), records=records,
        facts={"module": module}, trace=trace, counters={}, peaks={})


def step_run(chips=1, steps=3):
    """Steps of period 1 s. Step k: ``step`` span from k+0.10, the program on
    the device from k+0.13 (launch 0.03) to k+0.80 on chip 0 and k+0.82 on
    the last of four chips, ``loss_sync`` ends at k+0.85 (wake 0.05 on one
    chip, 0.03 on four), the next ``step`` span starts at k+1.10 (between
    0.25). ``h2d`` is nested in nothing; ``record`` closes after the record."""
    records, modules = [], [[] for _ in range(chips)]
    ops = [[] for _ in range(chips)]
    for k in range(steps):
        records.append({"round_idx": k, "spans": [
            span("hooks", k + 0.00, k + 0.02), span("data", k + 0.02, k + 0.08),
            span("h2d", k + 0.08, k + 0.10), span("step", k + 0.10, k + 0.12),
            span("loss_sync", k + 0.12, k + 0.85),
            span("record", k + 0.85, k + 0.95)]})
        for c in range(chips):
            end = k + (0.82 if c == chips - 1 and chips > 1 else 0.80)
            modules[c].append((f"jit_{STEP_MODULE}(1)", k + 0.13, end))
            ops[c].append((op("fusion.1"), k + 0.13, end))
    devices = [tr.DeviceTrace(c, events(*modules[c]), events(*ops[c]), tr.EMPTY)
               for c in range(chips)]
    return run_of(records, devices)


def read(name, run):
    return harness.load_module(harness.ROOT, "layer_metrics", name).read(run)


def test_spans_land_on_the_traces_clock_with_their_unit():
    got = host_spans.spans(step_run(steps=2))
    assert [s.name for s in got[:3]] == ["hooks", "data", "h2d"]
    step = host_spans.named(got, "step")
    assert [(s.unit, round(s.start, 9), round(s.end, 9)) for s in step] == [
        (0, 0.10, 0.12), (1, 1.10, 1.12)]


@pytest.mark.parametrize("chips, wake", [(1, 0.05), (4, 0.03)])
def test_a_known_launch_wake_and_gap_come_back(chips, wake, capsys):
    run = step_run(chips=chips)
    assert host_spans.median_of(run, "launch") == pytest.approx(0.03)
    assert host_spans.median_of(run, "wake") == pytest.approx(wake)
    assert read("cheetah_runner.launch_wake_s_per_step", run) == \
        pytest.approx(0.03 + wake)
    assert read("cheetah_runner.host_between_s_per_step", run) == pytest.approx(0.25)
    times = host_spans.step_times(run)
    assert [t.unit for t in times] == [0, 1, 2] and times[-1].between is None
    # the four quantities tile the step: they sum to the device's period
    assert all(t.launch + t.device + t.wake + t.between == pytest.approx(1.0)
               for t in times[:-1])
    err = capsys.readouterr().err
    assert err.count("steps on one clock") == 1  # the readers share one reduction
    assert "period 1.000000s on the device" in err


def test_an_offset_between_the_clocks_cancels_in_the_sum(capsys):
    """The profiler places the device's timeline on the host's clock to a
    millisecond or two (PERF.md section 7). Shift every device event 0.04 s
    early: launch turns negative and wake grows by as much, every step is
    still paired with its execution, and launch + wake, the device's time
    and the gap between steps read as before."""
    run, shifted = step_run(), step_run()
    for dev in shifted.trace.devices:
        for ev in (dev.modules, dev.ops):
            ev.start -= 0.04
            ev.end -= 0.04
    assert host_spans.median_of(shifted, "launch") == pytest.approx(-0.01)
    assert host_spans.median_of(shifted, "wake") == pytest.approx(0.09)
    assert "launch min -0.010000s" in capsys.readouterr().err
    for name in ("cheetah_runner.launch_wake_s_per_step",
                 "cheetah_runner.host_between_s_per_step"):
        assert read(name, shifted) == pytest.approx(read(name, run))
    assert len(host_spans.step_times(shifted)) == 3


def test_idle_seconds_go_to_the_innermost_span(capsys):
    """Chip 0 is busy 0.13..0.80 of every second. Of the 0.33 s idle between
    two steps, 0.05 is the end of loss_sync, 0.10 record, 0.05 after record
    (no span), 0.02 hooks, 0.06 data, 0.02 h2d, 0.02 step and 0.01 the start
    of the next loss_sync."""
    run = step_run(steps=3)
    table = host_spans.idle_by_span(run)
    want = {"loss_sync": 0.12, "record": 0.20, "": 0.10, "hooks": 0.04,
            "data": 0.12, "h2d": 0.04, "step": 0.04}
    assert set(table) == set(want)
    for name, seconds in want.items():
        assert table[name] == pytest.approx(seconds), name
    share = read("device.step_idle_attributed_share", run)
    assert share == pytest.approx(100 * (0.66 - 0.10) / 0.66)
    assert read("device.round_idle_attributed_share", run) == pytest.approx(share)
    assert "idle seconds of chip 0 by innermost span: record 0.200000" in \
        capsys.readouterr().err


def test_a_nested_span_owns_its_time_and_its_parent_the_rest():
    records = [{"round_idx": 0, "spans": [
        span("sample", 0.0, 1.0, ident=1),
        span("place_params", 0.2, 0.5, parent=1, ident=2)]}]
    dev = tr.DeviceTrace(0, tr.EMPTY, events((op("a"), -1.0, 0.0),
                                             (op("b"), 1.0, 2.0)), tr.EMPTY)
    table = host_spans.idle_by_span(run_of(records, [dev], module="core"))
    assert table["place_params"] == pytest.approx(0.3)
    assert table["sample"] == pytest.approx(0.7)
    assert table[""] == pytest.approx(0.0)


@pytest.mark.parametrize("name", READERS)
def test_records_without_spans_or_no_trace_give_none(name):
    with_spans = step_run()
    bare = [{"round_idx": r["round_idx"], "phases": {"data": 0.06}}
            for r in with_spans.records]
    assert read(name, run_of(bare, with_spans.trace.devices)) is None
    assert read(name, run_of(with_spans.records, None)) is None
    # a trace whose start on the host's clock is unknown places no span
    assert read(name, run_of(with_spans.records, with_spans.trace.devices,
                             start_epoch_ns=None)) is None


# ---------------------------------------------------------------------------
# benchmark/tools/scope_table.py: the name stack cut at vocabulary and module
# ---------------------------------------------------------------------------


def _scope_table():
    import importlib.util
    import os

    path = os.path.join(harness.ROOT, "benchmark", "tools", "scope_table.py")
    spec = importlib.util.spec_from_file_location("scope_table", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("tf_op, want", [
    ("jit(_train_step_raw)/jvp(Transformer)/CheckpointBlock_0/RMSNorm_1/convert_element_type:",
     ("Transformer/CheckpointBlock/RMSNorm", "fwd")),
    ("jit(_train_step_raw)/transpose(jvp(Transformer))/jvp(Transformer)/checkpoint/"
     "CheckpointBlock_0/FeedForward_0/bld,df->blf/dot_general:",
     ("Transformer/CheckpointBlock/FeedForward", "bwd")),
    ("jit(_train_step_raw)/transpose(jvp(Transformer))/jvp(Transformer)/checkpoint/"
     "rematted_computation/CheckpointBlock_0/FeedForward_0/bld,df->blf/dot_general:",
     ("Transformer/CheckpointBlock/FeedForward", "remat")),
    ("jit(_train_step_raw)/transpose(jvp(Transformer))/CheckpointBlock_1/Attention_0/"
     "rope/mul:", ("Transformer/CheckpointBlock/Attention/rope", "bwd")),
    ("jit(_train_step_raw)/transpose(jvp(loss))/while/body/closed_call/dot_general:",
     ("loss", "bwd")),
    ("jit(_train_step_raw)/optimizer/clip/sqrt:", ("optimizer/clip", "fwd")),
    ("jit(_train_step_raw)/jvp(Transformer)/embed/jit(_take)/and:",
     ("Transformer/embed", "fwd")),
    ("jit(core)/local_train/while/body/while/body/jvp(ResNet)/BasicBlock_3/"
     "GroupNorm_0/reduce_sum:", ("local_train/ResNet/BasicBlock/GroupNorm", "fwd")),
    ("jit(core)/local_train/while/body/while/body/optimizer/mul:",
     ("local_train/optimizer", "fwd")),
    ("jit(_train_step_raw)/jvp()/while/body/add:", ("(no name)", "fwd")),
    ("jit(_take)/gather:", ("(no name)", "fwd")),
])
def test_scope_key_cuts_the_stack_at_vocabulary_and_module(tf_op, want):
    st = _scope_table()
    assert st.scope_key(tf_op, st.vocabulary()) == want


def test_scope_table_sums_self_time_flops_and_bytes_by_scope():
    st = _scope_table()
    ev = events((f"%while.1 = f32[8]{{0}} while(f32[8]{{0}} %p)", 0, 10),
                (op("fusion.1"), 1, 4), (op("fusion.2"), 5, 9),
                (op("fusion.3"), 12, 13), (op("fusion.1"), 14, 17))
    stats = [{"tf_op": "jit(core)/local_train/while:", "flops": 99.0},
             {"tf_op": "jit(core)/local_train/while/body/jvp(LR)/Dense_0/dot_general:",
              "flops": 6.0, "bytes_accessed": 2.0},
             {"tf_op": "jit(core)/local_train/while/body/optimizer/sub:",
              "flops": 1.0, "bytes_accessed": 8.0},
             {}]
    rows, by_op, busy = st.table(ev, stats, st.vocabulary(), layers=True)
    assert busy == 14
    got = {(r["scope"], r["pass"]): r for r in rows}
    dense = got[("local_train/LR/Dense_0", "fwd")]
    assert (dense["self_s"], dense["flops"], dense["bytes"]) == (6, 12.0, 4.0)
    assert got[("local_train/optimizer", "fwd")]["self_s"] == 4
    loop = got[("local_train", "fwd")]  # the loop's own time; its count is its body's
    assert (loop["self_s"], loop["flops"]) == (3, 0.0)
    assert got[("(no name)", "fwd")]["share"] == pytest.approx(100 / 14)
    assert by_op["fusion.2"][:2] == ("local_train/optimizer", "fwd")
    assert [r["scope"] for r in rows][0] == "local_train/LR/Dense_0"
