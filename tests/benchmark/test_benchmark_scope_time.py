"""``benchmark/scope_time.py`` and the nine readers built on it, on a synthetic
``XLA Ops`` line and a scope map made by hand, whose seconds per scope and
pass can be worked out on paper."""

import types

import numpy as np
import pytest

from benchmark import harness, scope_time
from benchmark import trace_reduce as tr
from fedml_tpu.core import mlops

STEP = "_train_step_raw"
LM_READERS = ["cheetah_step.forward_s_per_step",
              "cheetah_step.backward_s_per_step",
              "cheetah_step.remat_s_per_step",
              "cheetah_step.optimizer_s_per_step",
              "cheetah_step.loss_head_s_per_step",
              "cheetah_step.unnamed_device_share",
              "moe_experts.dispatch_combine_s_per_step"]
ROUND_READERS = ["local_train.backward_s_per_round",
                 "local_train.outside_model_s_per_round"]


def events(*rows):
    names = list(dict.fromkeys(r[0] for r in rows))
    return tr.Events(names, np.array([names.index(r[0]) for r in rows], int),
                     np.array([r[1] for r in rows], float),
                     np.array([r[2] for r in rows], float))


def op(name, opcode="fusion"):
    return f"%{name} = f32[8]{{0}} {opcode}(f32[8]{{0}} %p)"


def scope_map(program, rows):
    """A ``program_scopes`` event: ``rows`` is {op name: (path, pass)}."""
    keys = list(dict.fromkeys(rows.values()))
    return {"kind": "program_scopes", "program": program,
            "module": "jit_" + program, "scopes": [list(k) for k in keys],
            "ops": {name: keys.index(k) for name, k in rows.items()},
            "instructions": len(rows), "unnamed": 0, "stale": []}


def run_of(ops, modules, module=STEP):
    dev = tr.DeviceTrace(0, modules, ops, tr.EMPTY)
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(root=harness.ROOT), records=[],
        facts={"module": module}, trace=tr.Trace([dev], None), counters={},
        peaks={})


def read(name, run):
    return harness.load_module(harness.ROOT, "layer_metrics", name).read(run)


@pytest.fixture
def publish(monkeypatch):
    """What the run's event log holds, in place of a JSONL file."""
    log = []
    monkeypatch.setattr(mlops, "read_events", lambda path=None: list(log))
    return log


# one step of 10 s at offset t: a forward fusion 1 s, a loss `while` of 3 s
# whose body runs a forward op for 1 s and a backward op for 1.5 s (so the
# loop's own time is 0.5 s), a recomputed fusion 1 s, a dispatch gather run in
# the forward (0.5 s) and again recomputed under another name (0.25 s), a
# grouped product with no name stack 0.75 s, the optimizer 2 s; idle between
STEP_MAP = {
    "fusion.1": ("Transformer/Block/FeedForward", "fwd"),
    "while.2": ("loss", "fwd"),
    "fusion.3": ("loss", "fwd"),
    "fusion.4": ("loss", "bwd"),
    "fusion.5": ("Transformer/Block/FeedForward", "remat"),
    "gather.6": ("Transformer/Block/MoEFeedForward/moe_experts", "fwd"),
    "gather.7": ("Transformer/Block/MoEFeedForward/moe_experts", "remat"),
    "ragged-dot.8": ("", "fwd"),
    "fusion.9": ("optimizer/clip", "fwd"),
    "fusion.10": ("optimizer", "fwd"),
}


def step_ops(t, scale=1.0):
    rows = [(op("fusion.1"), 0.0, 1.0), (op("while.2", "while"), 1.0, 4.0),
            (op("fusion.3"), 1.25, 2.25), (op("fusion.4"), 2.25, 3.75),
            (op("fusion.5"), 4.5, 5.5), (op("gather.6"), 5.5, 6.0),
            (op("gather.7"), 6.0, 6.25), (op("ragged-dot.8", "custom-call"), 6.25, 7.0),
            (op("fusion.9"), 7.5, 8.0), (op("fusion.10"), 8.0, 9.5)]
    return [(n, t + scale * a, t + scale * b) for n, a, b in rows]


def step_run():
    """Two executions of the step (the second takes twice as long), with an
    op of another program between them."""
    ops = step_ops(0.0) + [(op("fusion.1"), 10.5, 10.75)] + step_ops(20.0, 2.0)
    modules = events((f"jit_{STEP}(1)", 0.0, 10.0), ("jit_other(7)", 10.4, 10.8),
                     (f"jit_{STEP}(1)", 20.0, 40.0))
    return run_of(events(*ops), modules)


def test_by_scope_sums_self_time_by_path_and_pass(publish, capsys):
    publish.append(scope_map(STEP, {"fusion.1": ("stale", "fwd")}))  # an older program's
    publish.append(scope_map("core", {}))
    publish.append(scope_map(STEP, STEP_MAP))
    run = step_run()
    times = scope_time.by_scope(run)
    assert scope_time.by_scope(run) is times  # the readers share one reduction
    first, second = times.seconds
    assert first == {
        ("Transformer/Block/FeedForward", "fwd"): 1.0,
        ("loss", "fwd"): pytest.approx(0.5 + 1.0),  # the loop's own time and its body's forward
        ("loss", "bwd"): 1.5,
        ("Transformer/Block/FeedForward", "remat"): 1.0,
        ("Transformer/Block/MoEFeedForward/moe_experts", "fwd"): 0.5,
        ("Transformer/Block/MoEFeedForward/moe_experts", "remat"): 0.25,
        ("", "fwd"): 0.75,
        ("optimizer/clip", "fwd"): 0.5,
        ("optimizer", "fwd"): 1.5,
    }
    assert second == {k: pytest.approx(2 * v) for k, v in first.items()}
    assert times.busy == [pytest.approx(8.5), pytest.approx(17.0)]
    err = capsys.readouterr().err
    assert "device ms a unit by scope and pass" in err
    assert "loss:fwd 2250.000" in err and "under no scope 8.82% of busy" in err


def test_the_four_passes_and_the_unnamed_seconds_tile_the_busy_time(publish):
    publish.append(scope_map(STEP, STEP_MAP))
    run = step_run()
    parts = [scope_time.per_execution(run, keep) for keep in (
        scope_time.forward, scope_time.backward, scope_time.remat,
        scope_time.optimizer, scope_time.unnamed)]
    assert [p[0] for p in parts] == [pytest.approx(v) for v in
                                     (3.0, 1.5, 1.25, 2.0, 0.75)]
    for k, busy in enumerate(scope_time.by_scope(run).busy):
        assert sum(p[k] for p in parts) == pytest.approx(busy)


@pytest.mark.parametrize("name, want", zip(LM_READERS, [
    # medians over the two executions: 1.5 times the first one's seconds
    1.5 * 3.0, 1.5 * 1.5, 1.5 * 1.25, 1.5 * 2.0, 1.5 * (1.5 + 1.5),
    100 * 0.75 / 8.5, 1.5 * 0.75]))
def test_step_readers_on_the_synthetic_step(publish, name, want):
    publish.append(scope_map(STEP, STEP_MAP))
    assert read(name, step_run()) == pytest.approx(want)


def test_an_execution_of_another_program_under_the_same_name_is_left_out(publish):
    """fsdp's first step is a program of its own: the map is the steady
    one's, and only the executions of the program that ran most are read."""
    publish.append(scope_map(STEP, STEP_MAP))
    ops = step_ops(0.0) + step_ops(20.0) + step_ops(40.0)
    modules = events((f"jit_{STEP}(5)", 0.0, 10.0), (f"jit_{STEP}(9)", 20.0, 30.0),
                     (f"jit_{STEP}(9)", 40.0, 50.0))
    times = scope_time.by_scope(run_of(events(*ops), modules))
    assert len(times.seconds) == 2 and times.busy == [pytest.approx(8.5)] * 2


def test_a_map_that_lacks_an_op_reports_nothing(publish, capsys):
    lacking = {k: v for k, v in STEP_MAP.items() if k != "gather.7"}
    publish.append(scope_map(STEP, lacking))
    run = step_run()
    assert scope_time.by_scope(run) is None
    err = capsys.readouterr().err
    assert "accounts for 8.250000s of an execution's 8.500000s" in err
    assert "['gather.7']" in err
    for name in LM_READERS:
        assert read(name, run) is None
    # an op that takes under TILES_WITHIN of the busy time may be missing:
    # its seconds then stand under the unnamed share
    tiny = [(op("copy.11", "copy"), 9.6, 9.62)]
    publish[:] = [scope_map(STEP, STEP_MAP)]
    run = run_of(events(*(step_ops(0.0) + tiny)),
                 events((f"jit_{STEP}(1)", 0.0, 10.0)))
    assert read("cheetah_step.unnamed_device_share", run) == pytest.approx(
        100 * (0.75 + 0.02) / 8.52)


@pytest.mark.parametrize("name", LM_READERS + ROUND_READERS)
def test_readers_report_nothing_without_a_map_or_a_trace(publish, name):
    module = STEP if name in LM_READERS else "core"
    run = step_run()
    run.facts["module"] = module
    assert read(name, run) is None  # a program from before the event existed
    publish.append(scope_map("some_other_program", STEP_MAP))
    run = step_run()
    run.facts["module"] = module
    assert read(name, run) is None
    publish.append(scope_map(module, STEP_MAP))
    no_trace = types.SimpleNamespace(
        cell=types.SimpleNamespace(root=harness.ROOT), records=[],
        facts={"module": module}, trace=None, counters={}, peaks={})
    assert read(name, no_trace) is None


# a round of 6 s: under local_train a model forward 1 s and backward 2 s, the
# batch scan (a `while` of 5 s in all, 0.5 s its own), the batch gather 0.5 s,
# the client optimizer 1 s; outside it the aggregation 0.5 s
ROUND_MAP = {
    "while.1": ("local_train", "fwd"),
    "fusion.2": ("local_train/ResNet/BasicBlock/Conv", "fwd"),
    "fusion.3": ("local_train/ResNet/BasicBlock/Conv", "bwd"),
    "gather.4": ("local_train", "fwd"),
    "fusion.5": ("local_train/optimizer", "fwd"),
    "fusion.6": ("local_train/loss", "bwd"),
    "fusion.7": ("aggregate", "fwd"),
}


def test_round_readers_on_a_synthetic_round(publish):
    publish.append(scope_map("core", ROUND_MAP))
    rows = [(op("while.1", "while"), 0.0, 5.25), (op("fusion.2"), 0.25, 1.25),
            (op("fusion.3"), 1.25, 3.25), (op("gather.4"), 3.25, 3.75),
            (op("fusion.5"), 3.75, 4.75), (op("fusion.6"), 4.75, 5.0),
            (op("fusion.7"), 5.5, 6.0)]
    run = run_of(events(*rows), events(("jit_core(3)", 0.0, 6.0)), module="core")
    assert read("local_train.backward_s_per_round", run) == pytest.approx(2.25)
    # the loop's own 0.5 s, the gather, the optimizer and the loss outside the model
    assert read("local_train.outside_model_s_per_round", run) == pytest.approx(
        0.5 + 0.5 + 1.0 + 0.25)
