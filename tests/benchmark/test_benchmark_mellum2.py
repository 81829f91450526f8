"""The benchmark's side of the Mellum2-12B-A2.5B configuration: its file
against the catalog row, its shape functions against hand counts and the
program's own, the job ``pretrain_moe`` end to end on the CPU at a tiny
fixture (``fixture_root_mellum2``), and the two window-attention readers on a
made trace whose seconds can be worked out on paper."""

import json
import math
import os
import types

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark import trace_reduce as tr
from fedml_tpu.core import mlops

ROOT = harness.ROOT
FIXTURE_ROOT = os.path.join(ROOT, "tests", "benchmark", "fixture_root_mellum2")
CELL = "pretrain_mellum2_ep4_1chip"
with open(os.path.join(ROOT, "benchmark", "configs",
                       "mellum2_12b_a2.5b_ep4_l4.json")) as _f:
    CONFIG = json.load(_f)
flops = harness.load_module(ROOT, "flops", "swa_moe")

PERIOD = ["sliding_attention"] * 3 + ["full_attention"]


# the published config.json (the configuration's `source`), its shape keys
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": PERIOD * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True,
}


def test_configuration_keeps_the_published_numbers_and_states_its_cut():
    row = PUBLISHED
    for key, value in row.items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert CONFIG["published"]["layer_types"] == row["layer_types"]
    assert row["layer_types"] == PERIOD * 7 and CONFIG["layer_types"] == PERIOD
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    (entry,) = [c for c in spec["configs"] if c["name"] == CONFIG["name"]]
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"]) == sorted(
        ["num_hidden_layers", "layer_types", "num_experts", "vocab_rows_held"])
    assert entry["source"] == CONFIG["source"]
    assert CONFIG["published"] == dict(num_hidden_layers=28,
                                       layer_types=row["layer_types"],
                                       num_experts=64, vocab_size=98304)
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["router_experts"], CONFIG["vocab_rows_held"]) == (
                4, 16, 64, 24576)
    assert set(row["mlp_layer_types"]) == {"sparse"}
    # the derived keys say what the published ones say
    rope = row["rope_parameters"]
    assert CONFIG["yarn_layer_types"] == [
        kind for kind, r in rope.items() if r["rope_type"] == "yarn"] == [
            "full_attention"]
    assert rope["sliding_attention"]["rope_type"] == "default"
    assert CONFIG["rope_theta"] == rope["full_attention"]["rope_theta"] == \
        rope["sliding_attention"]["rope_theta"]
    assert rope["full_attention"]["attention_factor"] == pytest.approx(
        0.1 * math.log(rope["full_attention"]["factor"]) + 1, rel=1e-15)
    assert "4 chips share each layer" in CONFIG["deployment"]
    assert "28 chips" in CONFIG["deployment"]
    for key in ("qk_norm", "mtp", "router", "initializers", "precision",
                "recipe", "splash_blocks", "data"):
        assert CONFIG["assumed"][key]
    for key in ("loss_abs", "logits_rel_l2", "routing_margin",
                "near_tie_share_max"):
        assert len(CONFIG["tolerances"][key + "_why"]) > 100


def _job(cell):
    return harness.load_module(ROOT, "jobs", cell.job).Job(
        cell, seed=0, tracked=False, work_dir="", log=lambda s: None)


def test_the_cell_resolves_and_builds_the_program_the_file_describes():
    import jax

    from fedml_tpu.arguments import Arguments
    from fedml_tpu.cheetah.runner import config_from_args
    from fedml_tpu.parallel.transformer import Transformer

    cell = harness.load_cell(CELL)
    assert (cell.chips, cell.job) == (1, "pretrain_moe")
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s_per_chip", "peak_hbm_gb", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert {"window_attention.kernel_roofline",
            "window_attention.kernel_device_share", "cheetah_step.mfu",
            "attention_kernels.device_share",
            "moe_experts.dispatch_combine_s_per_step"} <= names
    # the products' roofline keeps its five cells: its list is held as it
    # stands by tests/benchmark/test_benchmark_moe_products.py
    assert not {n for n in names if n.startswith(
        ("kda.", "mla_", "bd_", "collectives.", "ssd.", "short_conv.",
         "moe_products."))}
    assert len(names) == 21  # 17 named lists, the 2 new, the 2 of `entry`
    job = _job(cell)
    assert (job.seq_len, job.batch, job.tokens_per_step()) == (16384, 1, 16384)
    cfg = config_from_args(Arguments(overrides=job.program))
    # every published key the file hands over reaches the program unchanged
    for key, arg in CONFIG["program_argument_of"].items():
        want = CONFIG
        for part in key.split("."):
            want = want[part]
        have = getattr(cfg, arg)
        if isinstance(have, tuple):
            have = list(have)
        assert (float(want) == float(have)
                if isinstance(have, (int, float)) and not isinstance(have, bool)
                else want == have), key
    assert cfg.layer_types == tuple(PERIOD) and cfg.attn_window == 1024
    assert [cfg.takes_yarn(t) for t in PERIOD] == [False] * 3 + [True]
    assert [cfg.attn_mask(16384, t) for t in PERIOD] == [("local", 1024)] * 3 + [
        ("causal",)]
    assert cfg.mixers == ("gqa",) * 4 and cfg.layer_kinds == ("moe",) * 4
    assert (cfg.head_dim, cfg.qk_norm, cfg.n_heads, cfg.n_kv_heads) == (
        128, True, 32, 4)
    assert (cfg.moe_router, cfg.moe_top_k, cfg.experts_held, cfg.shared_d_ff,
            cfg.tie_embeddings) == ("softmax", 8, 16, 0, False)
    assert cfg.max_seq_len == 16384 and cfg.rope_theta == 5e5
    # the parameters, counted from shapes alone: 595.15M
    shapes = jax.eval_shape(
        lambda: Transformer(cfg).init(jax.random.PRNGKey(0),
                                      jnp.zeros((1, 8), jnp.int32)))
    assert sum(int(np.prod(s.shape)) for s in
               jax.tree.leaves(shapes["params"])) == 595_154_176
    layer = (2304 * 5120 + 4096 * 2304 + 2 * 128 + 2304 * 64
             + 16 * 3 * 2304 * 896 + 2 * 2304)
    assert 4 * layer + 2 * 24576 * 2304 + 2304 == 595_154_176


def test_job_refuses_a_program_without_the_arguments(monkeypatch):
    """The PR's parent: its ``TransformerConfig`` lacks the window and the
    rotary per kind of layer, and the job says so at once."""
    import dataclasses

    from fedml_tpu.parallel import transformer

    fields = {f.name: f for f in dataclasses.fields(transformer.TransformerConfig)
              if f.name not in ("attn_window", "rope_yarn_types")}

    @dataclasses.dataclass(frozen=True)
    class Parent:
        pass

    Parent.__dataclass_fields__ = fields
    monkeypatch.setattr(transformer, "TransformerConfig", Parent)
    with pytest.raises(RuntimeError, match=r"cannot build mellum2_12b_a2.5b_ep4_l4"
                       r": its TransformerConfig has no \['attn_window', "
                       r"'rope_yarn_types'\]"):
        _job(harness.load_cell(CELL))


def test_job_refuses_a_file_whose_window_or_rotary_disagrees():
    cell = harness.load_cell(CELL)
    for arg, value, key in (("attn_window", 1023, "sliding_window"),
                            ("rope_factor", 8.0, "factor"),
                            ("rope_yarn_types", ["sliding_attention"],
                             "yarn_layer_types")):
        changed = dict(cell.config, program=dict(cell.config["program"],
                                                 **{arg: value}))
        cell.config, original = changed, cell.config
        with pytest.raises(ValueError, match=f"{key} is .* but the program's "
                           f"argument {arg} is"):
            _job(cell)
        cell.config = original


def test_flops_by_hand_and_the_programs_gauge_agrees():
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.cheetah.runner import config_from_args
    from fedml_tpu.parallel.transformer import train_flops_per_token

    D, L, W = 2304, 16384, 1024
    proj = 2 * D * 128 * (2 * 32 + 2 * 4)
    band = W * (W + 1) // 2 + (L - W) * W
    assert band == 16_253_440 and flops.window_pairs(L, W) == band
    window = proj + 2 * 2 * 32 * 128 * band / L
    full = proj + 2 * 2 * 32 * 128 * (L + 1) / 2
    expert = 2 * D * 64 + 2 * 3 * D * 896 * 8 * 16 / 64
    head = 2 * D * 24576
    forward = 3 * window + full + 4 * expert + head
    assert flops.train_flops_per_token(CONFIG, L) == pytest.approx(3 * forward)
    assert forward == pytest.approx(566.4e6, rel=1e-3)
    # the band's pairs: 1/8.3 of the causal triangle's
    assert L * (L + 1) / 2 / band == pytest.approx(8.26, abs=0.01)
    cfg = config_from_args(Arguments(overrides=_job(harness.load_cell(CELL)).program))
    assert train_flops_per_token(cfg, L) == pytest.approx(
        flops.train_flops_per_token(CONFIG, L), rel=1e-12)


def test_attention_kernel_flops_by_hand():
    band = 16_253_440
    per = flops.attention_kernel_flops(CONFIG, 16384, 1)
    assert per == {"fwd": 2 * 32 * band * 2 * 128,
                   "dq": 2 * 32 * band * 3 * 128,
                   "dkv": 2 * 32 * band * 4 * 128}
    assert flops.attention_kernel_flops(CONFIG, 16384, 2)["fwd"] == 2 * per["fwd"]
    # a window past the sequence is the causal triangle
    assert flops.window_pairs(64, 100) == 64 * 65 // 2


# ---------------------------------------------------------------------------
# the job end to end on the CPU
# ---------------------------------------------------------------------------


def test_run_cell_on_the_tiny_fixture(tmp_path):
    cell = harness.load_cell("tiny_pretrain_mellum2", root=FIXTURE_ROOT)
    logged = []
    result = harness.run_cell(cell, seed=2**31 + 7, seconds=1.0, trace=True,
                              work_dir=str(tmp_path), log=logged.append)
    assert result["correct"] is True and result["failed"] == 0
    assert any("near-tie share" in line for line in logged)
    got = set(result["metrics"])
    assert {"moe.assignments_held_share", "moe.max_expert_load_ratio",
            "entry.compile_s", "cheetah_runner.data_s_per_step"} <= got
    # the device trace's readers find no TPU plane on this CPU
    assert not {"window_attention.kernel_roofline",
                "window_attention.kernel_device_share", "cheetah_step.mfu"} & got
    # the program said what it built
    inits = [e for e in mlops.read_events(os.path.join(
        str(tmp_path), "runs", f"run_{cell.name}_seed{2**31 + 7}_edge_0.jsonl"))
        if e.get("kind") == "cheetah_init"]
    assert inits and all(e["attn_mask"]["layers"] == {"local:32": 3, "causal": 1}
                         for e in inits)


# ---------------------------------------------------------------------------
# the two readers on a made trace
# ---------------------------------------------------------------------------

STEP = "_train_step_raw"
ATTN = "Transformer/CheckpointBlock/Attention"


def _events(*rows):
    names = list(dict.fromkeys(r[0] for r in rows))
    return tr.Events(names, np.array([names.index(r[0]) for r in rows], int),
                     np.array([r[1] for r in rows], float),
                     np.array([r[2] for r in rows], float))


def _op(name):
    return f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p)"


def _kernel(name):
    return (f"%{name} = f32[8]{{0}} custom-call(f32[8]{{0}} %p), "
            'custom_call_target="tpu_custom_call"')


def _scope_map(rows):
    keys = list(dict.fromkeys(rows.values()))
    return {"kind": "program_scopes", "program": STEP, "module": "jit_" + STEP,
            "scopes": [list(k) for k in keys],
            "ops": {name: keys.index(k) for name, k in rows.items()},
            "instructions": len(rows), "unnamed": 0, "stale": []}


# one step of 10 units: a window layer's forward kernel 1, run again under
# remat 1, its dq 1.5 and dkv 2; the full layer's forward kernel 2 (the same
# shapes: only the map tells them apart); an expert layer 1.5; idle 1
SCOPES = {
    "splash_mqa_fwd_residuals.1": (ATTN + "/window_attention", "fwd"),
    "splash_mqa_fwd_residuals.2": (ATTN + "/window_attention", "remat"),
    "splash_mqa_dq_no_residuals.3": (ATTN + "/window_attention", "bwd"),
    "splash_mqa_dkv_no_residuals.4": (ATTN + "/window_attention", "bwd"),
    "splash_mqa_fwd_residuals.5": (ATTN, "fwd"),
    "fusion.6": ("Transformer/CheckpointBlock/MoEFeedForward/moe_experts", "fwd"),
}
ROWS = [("splash_mqa_fwd_residuals.1", 0.0, 1.0),
        ("splash_mqa_fwd_residuals.2", 1.0, 2.0),
        ("splash_mqa_dq_no_residuals.3", 2.0, 3.5),
        ("splash_mqa_dkv_no_residuals.4", 3.5, 5.5),
        ("splash_mqa_fwd_residuals.5", 5.5, 7.5),
        ("fusion.6", 7.5, 9.0)]


def _made_run(unit, scopes=SCOPES, config=CONFIG):
    """Two executions of the step, ``unit`` seconds a unit of the table."""
    ops = []
    for t in (0.0, 20 * unit):
        ops += [(_op(n) if n.startswith("fusion") else _kernel(n),
                 t + unit * a, t + unit * b) for n, a, b in ROWS]
    modules = _events((f"jit_{STEP}(1)", 0.0, 10 * unit),
                      (f"jit_{STEP}(1)", 20 * unit, 30 * unit))
    dev = tr.DeviceTrace(0, modules, _events(*ops), tr.EMPTY)
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(root=ROOT, config=config), records=[],
        facts={"module": STEP, "seq_len": 16384,
               "sequences_per_step_per_chip": 1},
        trace=tr.Trace([dev], None), counters={},
        peaks=harness.peaks_for("TPU v5 lite")), _scope_map(scopes)


def _read(name, run):
    return harness.load_module(ROOT, "layer_metrics", name).read(run)


@pytest.fixture
def publish(monkeypatch):
    """What the run's event log holds, in place of a JSONL file."""
    log = []
    monkeypatch.setattr(mlops, "read_events", lambda path=None: list(log))
    return log


def test_the_readers_take_the_window_layers_calls_alone(publish):
    per = flops.attention_kernel_flops(CONFIG, 16384, 1)
    # two executions: 2 forward calls (one under remat), a dq and a dkv each
    need = 2 * (2 * per["fwd"] + per["dq"] + per["dkv"])
    peak = harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"]
    unit = need / peak / 0.4 / (2 * 5.5)   # 5.5 units of window kernels, 40%
    run, scope_map = _made_run(unit)
    publish.append(scope_map)
    assert _read("window_attention.kernel_roofline", run) == pytest.approx(40.0)
    # 5.5 of the 9 busy units of an execution
    assert _read("window_attention.kernel_device_share", run) == pytest.approx(
        100 * 5.5 / 9)


def test_the_readers_return_nothing_where_there_is_nothing_to_read(publish):
    """No map (a program from before the event), a step without window
    layers (the full layer's call alone), no trace: the line leaves the
    metrics out."""
    names = ("window_attention.kernel_roofline",
             "window_attention.kernel_device_share")
    run, _ = _made_run(1.0)
    for name in names:
        assert _read(name, run) is None                      # no map published
    full = {k: (ATTN, w) for k, (_, w) in SCOPES.items()}
    run, scope_map = _made_run(1.0, scopes=full)
    publish[:] = [scope_map]
    for name in names:
        assert _read(name, run) is None                      # no window layer
    run.trace = None
    for name in names:
        assert _read(name, run) is None                      # no trace
