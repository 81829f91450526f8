"""The reduction from trace to numbers, on synthetic intervals whose answers
can be worked out by hand and on a small trace recorded on the chip."""

import os

import numpy as np
import pytest

from benchmark import harness
from benchmark import trace_reduce as tr

FIXTURE = os.path.join(harness.ROOT, "benchmark", "fixtures",
                       "cheetah_step_v5e.xplane.pb")


def events(*rows):
    """rows of (name, start, end) -> Events."""
    names = list(dict.fromkeys(r[0] for r in rows))
    return tr.Events(names, np.array([names.index(r[0]) for r in rows], int),
                     np.array([r[1] for r in rows], float),
                     np.array([r[2] for r in rows], float))


def hlo(name, opcode, extra=""):
    return f"%{name} = f32[8,128]{{1,0:T(8,128)}} {opcode}(f32[8,128]{{1,0}} %p){extra}"


def test_merge_total_subtract_gaps():
    busy = tr.merge([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)])
    assert busy == [(0, 3), (5, 7)]
    assert tr.total(busy) == 5
    assert tr.subtract([(0, 10)], [(2, 3), (4, 6), (9, 12)]) == [(0, 2), (3, 4), (6, 9)]
    assert tr.subtract([(0, 2), (4, 6)], [(1, 5)]) == [(0, 1), (5, 6)]
    assert tr.subtract([(0, 2)], []) == [(0, 2)]
    assert tr.gaps(busy, 0, 8) == [(3, 5), (7, 8)]


def test_nesting_self_time_and_top_level():
    """A while from 0 to 10 holds two fusions of 3 and 4 s; one op follows."""
    ev = events((hlo("while.1", "while"), 0, 10), (hlo("fusion.1", "fusion"), 1, 4),
                (hlo("fusion.2", "fusion"), 5, 9), (hlo("copy.1", "copy"), 12, 13))
    assert tr.self_seconds(ev).tolist() == [3, 3, 4, 1]
    top = tr.top_level(ev)
    assert [tr.op_name(top.names[i]) for i in top.name_id] == ["while.1", "copy.1"]
    dev = tr.DeviceTrace(0, tr.EMPTY, ev, tr.EMPTY)
    assert tr.busy_intervals(dev) == [(0, 10), (12, 13)]
    trace = tr.Trace([dev], None)
    assert tr.device_window(trace) == (0, 13)
    assert tr.idle_share(trace) == pytest.approx(2 / 13)
    # the table is by self time and by XLA's op name, summed over executions
    twice = events(*[(n, s + o, e + o) for o in (0, 20) for n, s, e in
                     zip(ev.names, ev.start, ev.end)])
    assert tr.op_table(twice, top=2) == [("fusion.2", 8.0), ("while.1", 6.0)]


def test_reading_xla_names():
    text = ("%all-gather-start.3 = (f32[4,8]{1,0}, f32[16,8]{1,0:T(8,128)S(1)}) "
            "all-gather-start(f32[4,8]{1,0} %param.1), channel_id=1, dimensions={0}")
    assert tr.op_name(text) == "all-gather-start.3"
    assert tr.opcode(text) == "all-gather-start"
    assert tr.collective_kind(text) == "all-gather"
    assert tr.collective_kind(hlo("reduce-scatter.7", "fusion")) == "reduce-scatter"
    assert tr.collective_kind(hlo("all-reduce.2", "all-reduce")) == "all-reduce"
    assert tr.collective_kind(hlo("fusion.9", "fusion")) is None
    assert tr.collective_kind(hlo("copy-start.2", "copy-start")) is None
    tuple_shaped = ("%while.10 = (s32[]{:T(128)}, bf16[4,256]{1,0:T(4,128)(2,1)S(1)}, "
                    "/*index=5*/f32[4]{0}) while((s32[]{:T(128)}) %tuple.3), "
                    "condition=%cond, body=%body")
    assert tr.opcode(tuple_shaped) == "while" and tr.is_control_flow(tuple_shaped)
    kernel = hlo("splash_mqa_fwd.4", "custom-call",
                 ', custom_call_target="tpu_custom_call", backend_config={}')
    assert tr.is_mosaic_kernel(kernel)
    assert not tr.is_mosaic_kernel(
        hlo("custom-call.54", "custom-call", ', custom_call_target="ConcatBitcast"'))
    assert tr.module_function("jit__train_step_raw(4713677946146403524)") == "_train_step_raw"
    assert tr.module_function("jit_core(19)") == "core"


def test_collective_and_exposed_time():
    """An async all-gather in flight from 0 to 6 with compute from 1 to 4, a
    synchronous all-reduce from 8 to 9 alone, and a reduce-scatter from 10 to
    12 under a fusion from 10 to 13: in flight 6 + 1 + 2, exposed (0..1) +
    (4..6) + (8..9) = 4. The issue and wait of the async op and the while
    around everything do not count as compute."""
    ops = events(
        (hlo("while.1", "while"), 0, 14),
        (hlo("all-gather-start.1", "all-gather-start"), 0, 0.1),
        (hlo("fusion.1", "fusion"), 1, 4),
        (hlo("all-gather-done.1", "all-gather-done"), 5.9, 6),
        (hlo("all-reduce.2", "all-reduce"), 8, 9),
        (hlo("fusion.2", "fusion"), 10, 13),
    )
    async_ops = events((hlo("all-gather-start.1", "all-gather-start"), 0, 6),
                       (hlo("reduce-scatter-start.3", "async-start"), 10, 12),
                       (hlo("copy-start.5", "copy-start"), 6, 8))
    dev = tr.DeviceTrace(0, tr.EMPTY, ops, async_ops)
    assert tr.collective_intervals(dev) == [(0, 6), (8, 9), (10, 12)]
    assert tr.compute_intervals(dev) == [(1, 4), (10, 13)]
    assert tr.exposed_collective_seconds(dev) == pytest.approx(4)


def test_phase_spans_label_the_idle_gaps():
    """A gap is named after the RoundRecord phase its middle falls in."""
    ops = events((hlo("fusion.1", "fusion"), 1.0, 2.0), (hlo("fusion.1", "fusion"), 2.5, 3.5),
                 (hlo("fusion.1", "fusion"), 3.6, 4.0))
    trace = tr.Trace([tr.DeviceTrace(0, tr.EMPTY, ops, tr.EMPTY)],
                     start_epoch_ns=1_000 * 10 ** 9)
    # a record that closed at 1003.6 s after 1.6 s: data 0.4 s from 1002.0
    records = [{"round_idx": 7, "time": 1003.6, "wall_s": 1.6,
                "phases": {"data": 0.4, "step": 1.2}}]
    out = harness.breakdown(trace, records)
    assert out["device_ops"] == [["fusion.1", pytest.approx(2.4)]]
    assert out["idle_gaps"][0] == ["data (unit 7)", pytest.approx(0.5)]
    assert out["idle_gaps"][1][0] == "step (unit 7)"
    assert harness.breakdown(trace, [])["idle_gaps"][0][0] == "unattributed"


@pytest.fixture(scope="module")
def recorded():
    return tr.load(FIXTURE)


def test_recorded_trace_layout(recorded):
    """Cut from one traced run of ``pretrain_mistral7b_1chip`` on a v5e (my
    chip run, PR 22; ``benchmark/tools/cut_xplane.py``): two executions of the
    step program with every op in them."""
    assert [d.ordinal for d in recorded.devices] == [0]
    dev = recorded.devices[0]
    steps = tr.module_events(dev, "_train_step_raw")
    assert len(steps) == 2
    assert len(dev.ops) > 1000 and recorded.start_epoch_ns > 0
    # every op lies inside one of the two step executions
    inside = sum(((dev.ops.start >= s) & (dev.ops.end <= e)).sum()
                 for s, e in tr.as_intervals(steps))
    assert inside == len(dev.ops)


def test_recorded_trace_numbers(recorded):
    """The device is busy for nearly all of a step; the union of op intervals
    cannot exceed the step executions that hold them; the table's self times
    add up to the busy time; the splash kernels are found and are a minority
    of it."""
    dev = recorded.devices[0]
    steps = tr.as_intervals(tr.module_events(dev, "_train_step_raw"))
    busy = tr.total(tr.busy_intervals(dev))
    assert 0.9 * tr.total(steps) < busy <= tr.total(steps) * (1 + 1e-9)
    assert tr.self_seconds(dev.ops).sum() == pytest.approx(busy, rel=1e-6)
    table = tr.op_table(dev.ops, top=10)
    assert len(table) == 10 and table == sorted(table, key=lambda r: -r[1])
    assert sum(s for _, s in table) < busy
    mosaic = np.array([tr.is_mosaic_kernel(n) for n in dev.ops.names])
    share = tr.self_seconds(dev.ops)[mosaic[dev.ops.name_id]].sum() / busy
    assert mosaic.sum() >= 3 and 0.01 < share < 0.5  # fwd, dq, dkv
    assert tr.collective_intervals(dev) == []  # one chip: no collective
    lo, hi = tr.device_window(recorded)
    assert 0 <= tr.idle_share(recorded) < 0.5 and hi - lo >= tr.total(steps)
