"""The reader ``moe_products.kernel_roofline`` on a made trace whose seconds
can be worked out on paper: XLA's ``ragged-dot-*`` calls under no scope and
the repo's ``expert_gmm_*`` calls under ``moe_products`` read as the same
work, a call at its least time reads 100%, a product of the rows is charged
the matrices of the held experts that received rows and the matrices'
gradient every held matrix, written, and a step without an expert layer
reads nothing."""

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
PEAKS = harness.peaks_for("TPU v5 lite")
flops = harness.load_module(ROOT, "flops", "ssm_moe")


def _call(name, held=HELD, k=K, n=N):
    """A Mosaic call's text with the matrices operand ``[held, k, n]``: the
    forward product."""
    return (f"%{name} = bf16[49152,{n}]{{1,0}} custom-call(bf16[49152,{k}]{{1,0}} "
            f"%x, bf16[{held},{k},{n}]{{2,1,0}} %w), "
            'custom_call_target="tpu_custom_call"')


def _dlhs(name, k=K, n=N):
    """The rows' gradient: ``dy`` [rows, n] against the matrices operand."""
    return (f"%{name} = bf16[49152,{k}]{{1,0}} custom-call(bf16[49152,{n}]{{1,0}} "
            f"%dy, bf16[{HELD},{k},{n}]{{2,1,0}} %w), "
            'custom_call_target="tpu_custom_call"')


def _drhs(name, k=K, n=N):
    """The matrices' gradient: two row arrays in, ``[held, k, n]`` out."""
    return (f"%{name} = bf16[{HELD},{k},{n}]{{2,1,0}} custom-call("
            f"bf16[49152,{k}]{{1,0}} %x, bf16[49152,{n}]{{1,0}} %dy), "
            'custom_call_target="tpu_custom_call"')


def _metadata(name):
    return (f"%{name} = s32[512]{{0}} custom-call(s32[{HELD}]{{0}} %c), "
            'custom_call_target="tpu_custom_call"')


def _events(*rows):
    names = list(dict.fromkeys(r[0] for r in rows))
    return tr.Events(names, np.array([names.index(r[0]) for r in rows], int),
                     np.array([r[1] for r in rows], float),
                     np.array([r[2] for r in rows], float))


def _run(rows, counters=True, with_rows=None, config=CONFIG, layers=LAYERS,
         assigned=ROWS):
    """One execution of the step holding ``rows`` (text, start, end);
    ``with_rows`` held experts a layer that received rows, where the program
    counts them; ``assigned`` rows a layer."""
    modules = _events((f"jit_{STEP}(1)", 0.0, max(r[2] for r in rows) + 1.0))
    dev = tr.DeviceTrace(0, modules, _events(*rows), tr.EMPTY)
    counted = {"moe_assignments_held": assigned * layers}
    if with_rows is not None:
        counted["moe_held_experts_with_rows"] = with_rows * layers
    records = [{"counters": counted if counters else {}}]
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(root=ROOT, config=config), records=records,
        facts={"module": STEP, "expert_layers": layers, "experts_held": HELD},
        trace=tr.Trace([dev], None), peaks=PEAKS)


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


def _least(matrices=HELD, rows=ROWS):
    return flops.grouped_product_least_seconds(rows, matrices, K, N, PEAKS)[0]


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


def test_row_products_are_charged_the_matrices_that_received_rows(publish):
    """Half the held experts get no rows, and 500 rows a layer leave the
    calls bound by bytes: the forward product and the rows' gradient must
    read four matrices of eight, so each at that least time reads 100%. A
    program without the counter is charged all eight, and the same calls
    read more than 100%: the count the counter repairs."""
    rows = 500.0
    half, whole = _least(HELD / 2, rows), _least(HELD, rows)
    calls = [(_call("expert_gmm_fwd.1"), 0.0, half),
             (_dlhs("expert_gmm_dlhs.2"), 10.0, 10.0 + half)]
    publish[:] = [_scope_map({"expert_gmm_fwd.1": PRODUCTS,
                              "expert_gmm_dlhs.2": PRODUCTS})]
    assert _read(_run(calls, with_rows=HELD / 2, assigned=rows)) == \
        pytest.approx(100.0)
    assert whole > 1.5 * half
    assert _read(_run(calls, assigned=rows)) == pytest.approx(
        100.0 * whole / half)


def test_the_matrices_gradient_writes_every_held_matrix_and_reads_none(publish):
    """Its least time is the rows read and all eight matrices written, with
    the counter at half or without it."""
    publish[:] = [_scope_map({"expert_gmm_drhs.1": PRODUCTS})]
    for with_rows in (HELD / 2, None):
        run = _run([(_drhs("expert_gmm_drhs.1"), 0.0, _least())],
                   with_rows=with_rows)
        assert _read(run) == pytest.approx(100.0)


def test_a_ling_shaped_step_at_its_least_time_reads_100_and_no_more(publish):
    """Six expert layers, 8 of 512 experts held, 727 rows a layer that three
    of the eight take: the gate-and-up product ``[8, 2560, 1536]`` and the
    down product ``[8, 768, 2560]``, each forward, rows' gradient and
    matrices' gradient, every call at its least time. Charged every held
    matrix, the same calls read over 100%, as the Ling cell did on a TPU
    v5e (102.35%)."""
    ling = harness.load_module(ROOT, "flops", "kda_mla_moe")
    rows, layers, taken = 727.0, 6, 3
    calls, paths, t = [], {}, 0.0
    for j, (k, n) in enumerate(((2560, 1536), (768, 2560))):
        for kind, text, matrices in (("fwd", _call, taken),
                                     ("dlhs", _dlhs, taken),
                                     ("drhs", _drhs, HELD)):
            name = f"expert_gmm_{kind}.{j}"
            seconds = ling.grouped_product_least_seconds(
                rows, matrices, k, n, PEAKS)[0]
            calls.append((text(name, k=k, n=n), t, t + seconds))
            paths[name] = PRODUCTS
            t += seconds + 1.0
    publish[:] = [_scope_map(paths)]
    shaped = dict(config={"flops": {"module": "kda_mla_moe"}}, layers=layers,
                  assigned=rows)
    got = _read(_run(calls, with_rows=taken, **shaped))
    assert got == pytest.approx(100.0) and got <= 100.0 + 1e-9
    assert _read(_run(calls, **shaped)) > 100.0


def test_the_metric_is_listed_where_its_reader_finds_products():
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    (entry,) = [m for m in spec["per_layer"]
                if m["name"] == "moe_products.kernel_roofline"]
    assert entry["layer"] == "expert_layer"
    assert entry["moves"] == "tokens_per_s_per_chip"
    assert entry["workloads"] == [
        "pretrain_xing4_ep8_1chip", "pretrain_ling3_ep64_1chip",
        "pretrain_sdar_ep8_bd_1chip", "pretrain_nemotron3_ep16_1chip",
        "pretrain_lfm2_ep4_1chip"]
    # the reader of XLA's calls alone, silent since the kernels came, is gone
    assert "moe_experts.kernel_roofline" not in {
        m["name"] for m in spec["per_layer"]}
    assert not os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", "moe_experts.kernel_roofline.py"))
