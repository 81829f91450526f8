"""The reader ``moe_products.kernel_roofline`` on a made trace whose seconds
can be worked out on paper: XLA's ``ragged-dot-*`` calls under no scope and
the repo's ``expert_gmm_*`` calls under ``moe_products`` read as the same
work, a call at its least time reads 100%, and a step without an expert
layer reads nothing."""

import os
import types

import numpy as np
import pytest

from benchmark import harness
from benchmark import trace_reduce as tr
from fedml_tpu.core import mlops

ROOT = harness.ROOT
CONFIG = {"flops": {"module": "ssm_moe"}}
STEP = "_train_step_raw"
PRODUCTS = "Transformer/CheckpointBlock/MoEFeedForward/moe_products"
HELD, K, N = 8, 2688, 1856
ROWS = 4520.0            # assignments held a layer
LAYERS = 4
flops = harness.load_module(ROOT, "flops", "ssm_moe")


def _call(name, held=HELD):
    """A Mosaic call's text with the matrices operand ``[held, K, N]``."""
    return (f"%{name} = bf16[49152,{N}]{{1,0}} custom-call(bf16[49152,{K}]{{1,0}} "
            f"%x, bf16[{held},{K},{N}]{{2,1,0}} %w), "
            'custom_call_target="tpu_custom_call"')


def _metadata(name):
    return (f"%{name} = s32[512]{{0}} custom-call(s32[{HELD}]{{0}} %c), "
            'custom_call_target="tpu_custom_call"')


def _events(*rows):
    names = list(dict.fromkeys(r[0] for r in rows))
    return tr.Events(names, np.array([names.index(r[0]) for r in rows], int),
                     np.array([r[1] for r in rows], float),
                     np.array([r[2] for r in rows], float))


def _run(rows, counters=True):
    """One execution of the step holding ``rows`` (text, start, end)."""
    modules = _events((f"jit_{STEP}(1)", 0.0, max(r[2] for r in rows) + 1.0))
    dev = tr.DeviceTrace(0, modules, _events(*rows), tr.EMPTY)
    records = ([{"counters": {"moe_assignments_held": ROWS * LAYERS}}]
               if counters else [{"counters": {}}])
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(root=ROOT, config=CONFIG), records=records,
        facts={"module": STEP, "expert_layers": LAYERS, "experts_held": HELD},
        trace=tr.Trace([dev], None), peaks=harness.peaks_for("TPU v5 lite"))


def _scope_map(paths):
    keys = list(dict.fromkeys(paths.values()))
    return {"kind": "program_scopes", "program": STEP, "module": "jit_" + STEP,
            "scopes": [[k, "fwd"] for k in keys],
            "ops": {name: keys.index(p) for name, p in paths.items()},
            "instructions": len(paths), "unnamed": 0, "stale": []}


def _read(run):
    return harness.load_module(
        ROOT, "layer_metrics", "moe_products.kernel_roofline").read(run)


@pytest.fixture
def publish(monkeypatch):
    log = []
    monkeypatch.setattr(mlops, "read_events", lambda path=None: list(log))
    return log


def _least():
    return flops.grouped_product_least_seconds(
        ROWS, HELD, K, N, harness.peaks_for("TPU v5 lite"))[0]


def test_xla_calls_and_the_kernels_read_as_the_same_work(publish):
    """Two calls at twice and four times their least time, a metadata call
    beside XLA's: 3 least times of need over 6 + 0.5 of seconds, either way;
    a splash call under no scope and a fusion under the scope are not
    counted."""
    t = _least()
    other = [("%fusion.9 = bf16[8]{0} fusion(bf16[8]{0} %p)", 100.0, 100.0 + t),
             ('%splash_mha_fwd.1 = bf16[8]{0} custom-call(bf16[8]{0} %p), '
              'custom_call_target="tpu_custom_call"', 200.0, 200.0 + t)]
    xla = _run([(_call("ragged-dot-none.1"), 0.0, 2 * t),
                (_metadata("ragged-dot-metadata.2"), 10.0, 10.0 + 0.5 * t),
                (_call("ragged-dot-none.3"), 20.0, 20.0 + 4 * t)] + other)
    publish[:] = [_scope_map({"fusion.9": "", "splash_mha_fwd.1": ""})]
    assert _read(xla) == pytest.approx(100.0 * 2 / 6.5)
    ours = _run([(_call("expert_gmm_fwd.1"), 0.0, 2 * t),
                 (_metadata("fusion.2"), 10.0, 10.0 + 0.5 * t),
                 (_call("expert_gmm_drhs.3"), 20.0, 20.0 + 4 * t)] + other)
    # the metadata's time counts where it is a Mosaic call under the scope
    publish[:] = [_scope_map({"expert_gmm_fwd.1": PRODUCTS, "fusion.2": PRODUCTS,
                              "expert_gmm_drhs.3": PRODUCTS,
                              "fusion.9": PRODUCTS, "splash_mha_fwd.1": ""})]
    assert _read(ours) == pytest.approx(100.0 * 2 / 6.5)
    # the kernels by name where the run published no map
    publish[:] = []
    assert _read(ours) == pytest.approx(100.0 * 2 / 6.5)


def test_a_call_at_its_least_time_reads_100_and_no_more(publish):
    t = _least()
    publish[:] = [_scope_map({"expert_gmm_fwd.1": PRODUCTS})]
    assert _read(_run([(_call("expert_gmm_fwd.1"), 0.0, t)])) == pytest.approx(100.0)
    # a Mosaic call under the scope whose shapes name no held matrices adds
    # seconds and no need
    publish[:] = [_scope_map({"expert_gmm_fwd.1": PRODUCTS,
                              "other.2": PRODUCTS})]
    run = _run([(_call("expert_gmm_fwd.1"), 0.0, t),
                (_call("other.2", held=HELD + 1), 5.0, 5.0 + t)])
    assert _read(run) == pytest.approx(50.0)


def test_nothing_to_read_reads_nothing(publish):
    t = _least()
    publish[:] = [_scope_map({"expert_gmm_fwd.1": PRODUCTS})]
    # a step without an expert layer: no counter
    assert _read(_run([(_call("expert_gmm_fwd.1"), 0.0, t)],
                      counters=False)) is None
    # no product among the calls
    splash = ('%splash_mha_fwd.1 = bf16[8]{0} custom-call(bf16[8]{0} %p), '
              'custom_call_target="tpu_custom_call"')
    assert _read(_run([(splash, 0.0, t)])) is None
    run = _run([(_call("expert_gmm_fwd.1"), 0.0, t)])
    run.trace = None
    assert _read(run) is None


def test_the_metric_is_listed_where_its_reader_finds_products():
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    (entry,) = [m for m in spec["per_layer"]
                if m["name"] == "moe_products.kernel_roofline"]
    assert entry["layer"] == "expert_layer"
    assert entry["moves"] == "tokens_per_s_per_chip"
    experts = {m["name"]: m for m in spec["per_layer"]}[
        "moe_experts.kernel_roofline"]["workloads"]
    assert set(entry["workloads"]) <= set(experts)
