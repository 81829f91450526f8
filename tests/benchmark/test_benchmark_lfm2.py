"""The benchmark's side of the LFM2-8B-A1B configuration (ISSUE 40): its file
against the catalog row, its shape functions against hand counts and the
program's own, the job ``pretrain_moe`` end to end on the CPU at a tiny
fixture (``fixture_root_lfm2``), and the two new readers on a made trace
whose seconds can be worked out on paper."""

import json
import os
import types

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark import trace_reduce as tr
from fedml_tpu.core import mlops

ROOT = harness.ROOT
FIXTURE_ROOT = os.path.join(ROOT, "tests", "benchmark", "fixture_root_lfm2")
CELL = "pretrain_lfm2_ep4_1chip"
with open(os.path.join(ROOT, "benchmark", "configs",
                       "lfm2_8b_a1b_ep4_l7.json")) as _f:
    CONFIG = json.load(_f)
flops = harness.load_module(ROOT, "flops", "conv_moe")

CUT = ["conv", "full_attention", "conv", "conv", "conv", "full_attention",
       "conv"]


def _catalog_row():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["source_url"] == CONFIG["source"]]
    return row


def test_configuration_keeps_the_published_numbers_and_states_its_cut():
    try:
        row = _catalog_row()["config"]
    except FileNotFoundError:
        pytest.skip("no model catalog on this machine")
    for key, value in row.items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert CONFIG["published"]["layer_types"] == row["layer_types"]
    assert row["layer_types"][1:8] == CONFIG["layer_types"] == CUT
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    (entry,) = [c for c in spec["configs"] if c["name"] == CONFIG["name"]]
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"]) == sorted(
        ["num_hidden_layers", "num_dense_layers", "layer_types", "num_experts",
         "vocab_rows_held"])
    assert entry["source"] == CONFIG["source"]
    assert CONFIG["published"] == dict(
        num_hidden_layers=24, num_dense_layers=2, layer_types=row["layer_types"],
        num_experts=32, vocab_size=65536)
    assert (CONFIG["num_hidden_layers"], CONFIG["num_dense_layers"],
            CONFIG["num_experts"], CONFIG["vocab_rows_held"]) == (7, 1, 8, 16384)
    assert [row["layer_types"].count(k) for k in ("conv", "full_attention")] == [
        18, 6]
    assert CONFIG["vocab_rows_held"] * 4 == CONFIG["vocab_size"]
    assert CONFIG["tie_word_embeddings"] is True
    assert "4 chips share each layer" in CONFIG["deployment"]
    assert "711.39M" in CONFIG["notes"]["parameters"]
    assert "1,024 rows a held expert" in CONFIG["notes"]["tokens_per_expert"]
    for key in ("tie", "moe_bias_rate", "initializers", "dense_width",
                "renormalisation", "splash_blocks", "recipe", "precision",
                "data"):
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
    from fedml_tpu.parallel.transformer import SHORTCONV_TAPS, Transformer

    cell = harness.load_cell(CELL)
    assert (cell.chips, cell.job) == (1, "pretrain_moe")
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s_per_chip", "peak_hbm_gb", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert {"short_conv.mixer_device_share", "short_conv.conv_roofline",
            "moe_experts.kernel_roofline", "cheetah_step.mfu",
            "attention_kernels.device_share"} <= names
    assert not {n for n in names if n.startswith(("kda.", "mla_", "bd_",
                                                  "collectives.", "ssd."))}
    assert len(names) == 22  # the 18 named lists, the 2 new, the 2 of `entry`
    job = _job(cell)
    assert (job.seq_len, job.batch, job.tokens_per_step()) == (8192, 1, 8192)
    cfg = config_from_args(Arguments(overrides=job.program))
    for key, arg in CONFIG["program_argument_of"].items():
        have, want = getattr(cfg, arg), CONFIG[key]
        if isinstance(have, tuple):
            have = list(have)
        assert (float(want) == float(have)
                if isinstance(have, (int, float)) and not isinstance(have, bool)
                else want == have), key
    assert cfg.mixers == ("shortconv", "gqa", "shortconv", "shortconv",
                          "shortconv", "gqa", "shortconv")
    assert cfg.layer_kinds == ("dense",) + ("moe",) * 6
    assert (cfg.head_dim, cfg.qk_norm, cfg.pos_emb, cfg.ffn_act) == (
        64, True, "rope", "swiglu")
    assert (cfg.tie_embeddings, cfg.shared_d_ff, cfg.moe_router,
            cfg.moe_routed_scale) == (True, 0, "sigmoid", 1.0)
    assert cfg.max_seq_len == 8192 and cfg.rope_theta == 1e6
    assert CONFIG["conv_L_cache"] == SHORTCONV_TAPS and not CONFIG["conv_bias"]
    # the parameters, counted from shapes alone: 711.39M, no head of its own
    shapes = jax.eval_shape(
        lambda: Transformer(cfg).init(jax.random.PRNGKey(0),
                                      jnp.zeros((1, 8), jnp.int32)))
    assert "w_lm_head" not in shapes["params"]
    assert sum(int(np.prod(s.shape)) for s in
               jax.tree.leaves(shapes["params"])) == 711_389_440


def test_job_refuses_a_program_without_the_arguments(monkeypatch):
    """The PR's parent: its ``TransformerConfig`` lacks the mixer list and
    the tie, and the job says so at once."""
    import dataclasses

    from fedml_tpu.parallel import transformer

    fields = {f.name: f for f in dataclasses.fields(transformer.TransformerConfig)
              if f.name not in ("layer_types", "tie_embeddings")}

    @dataclasses.dataclass(frozen=True)
    class Parent:
        pass

    Parent.__dataclass_fields__ = fields
    monkeypatch.setattr(transformer, "TransformerConfig", Parent)
    with pytest.raises(RuntimeError, match=r"cannot build lfm2_8b_a1b_ep4_l7: "
                       r"its TransformerConfig has no \['layer_types', "
                       r"'tie_embeddings'\]"):
        _job(harness.load_cell(CELL))


def test_job_refuses_a_file_whose_program_disagrees_with_its_layer_list():
    cell = harness.load_cell(CELL)
    swapped = ["full_attention"] + CUT[1:]
    cell.config = dict(cell.config, program=dict(cell.config["program"],
                                                 layer_types=swapped))
    with pytest.raises(ValueError, match="layer_types is .* but the program's "
                       "argument layer_types is"):
        _job(cell)


def test_flops_by_hand_and_the_programs_gauge_agrees():
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.cheetah.runner import config_from_args
    from fedml_tpu.parallel.transformer import train_flops_per_token

    D, L = 2048, 8192
    conv = 2 * D * 3 * D + 2 * D * D + 2 * 3 * D + 2 * D
    attn = 2 * D * 64 * (64 + 16) + 2 * 2 * 32 * 64 * (L + 1) / 2
    dense = 2 * 3 * D * 7168
    expert = 2 * D * 32 + 2 * 3 * D * (4 * 8 / 32 * 1792)
    head = 2 * D * 16384
    forward = 5 * conv + 2 * attn + dense + 6 * expert + head
    assert flops.train_flops_per_token(CONFIG, L) == pytest.approx(3 * forward)
    assert forward == pytest.approx(565.01e6, rel=1e-4)   # ISSUE 40's table
    assert 0.29 < 5 * conv / forward < 0.31    # the mixers' share of them
    assert 2 * attn / forward == pytest.approx(0.193, abs=0.001)
    cfg = config_from_args(Arguments(overrides=_job(harness.load_cell(CELL)).program))
    assert train_flops_per_token(cfg, L) == pytest.approx(
        flops.train_flops_per_token(CONFIG, L), rel=1e-12)


def test_shortconv_least_seconds_by_hand():
    peaks = harness.peaks_for("TPU v5 lite")
    tokens = 8192
    fwd, bound = flops.shortconv_least_seconds(CONFIG, 8192, 1, "fwd", peaks)
    assert bound == "bytes"
    # B, C, x read and y written in bfloat16: 134 MB a layer
    assert fwd == pytest.approx(tokens * 2 * 4 * 2048 / 819e9)
    assert tokens * 2 * 4 * 2048 == pytest.approx(134.2e6, rel=1e-3)
    bwd, bound = flops.shortconv_least_seconds(CONFIG, 8192, 1, "bwd", peaks)
    assert bound == "bytes"
    assert bwd == pytest.approx(tokens * 2 * 7 * 2048 / 819e9)   # 235 MB
    assert 5 * (fwd + bwd) == pytest.approx(2.3e-3, rel=0.03)
    two, _ = flops.shortconv_least_seconds(CONFIG, 8192, 2, "fwd", peaks)
    assert two == pytest.approx(2 * fwd)
    # at a peak of operations far below the chip's the operations bound
    slow = dict(peaks, bf16_flops_per_s=1e9)
    seconds, bound = flops.shortconv_least_seconds(CONFIG, 8192, 1, "fwd", slow)
    assert bound == "flops" and seconds == pytest.approx(
        tokens * (2 * 3 * 2048 + 2 * 2048) / 1e9)
    with pytest.raises(ValueError, match="fwd|bwd"):
        flops.shortconv_least_seconds(CONFIG, 8192, 1, "remat", peaks)
    # the experts' products: three matrices an expert, [held, 2048, 2 x 1792]
    seconds, bound = flops.grouped_product_least_seconds(
        8192, 8, 2048, 3584, peaks)
    assert bound == "flops" and seconds == pytest.approx(
        2 * 8192 * 2048 * 3584 / 197e12)


# ---------------------------------------------------------------------------
# the job end to end on the CPU
# ---------------------------------------------------------------------------


def test_run_cell_on_the_tiny_fixture(tmp_path):
    cell = harness.load_cell("tiny_pretrain_lfm2", root=FIXTURE_ROOT)
    logged = []
    result = harness.run_cell(cell, seed=2**31 + 7, seconds=1.0, trace=True,
                              work_dir=str(tmp_path), log=logged.append)
    assert result["correct"] is True and result["failed"] == 0
    assert any("near-tie share" in line for line in logged)
    got = set(result["metrics"])
    assert {"moe.assignments_held_share", "moe.max_expert_load_ratio",
            "entry.compile_s", "cheetah_runner.data_s_per_step"} <= got
    # the device trace's readers find no TPU plane on this CPU
    assert not {"short_conv.conv_roofline", "short_conv.mixer_device_share",
                "cheetah_step.mfu"} & got
    share = result["metrics"]["moe.assignments_held_share"]["value"]
    assert 3 < share < 40            # 2 of 16 experts held: 12.5% if balanced
    # the program said what it built
    inits = [e for e in mlops.read_events(os.path.join(
        str(tmp_path), "runs", f"run_{cell.name}_seed{2**31 + 7}_edge_0.jsonl"))
        if e.get("kind") == "cheetah_init"]
    assert inits and all(
        e["mixers"] == "shortconv,gqa,shortconv,shortconv"
        and e["tie_embeddings"] is True for e in inits)


# ---------------------------------------------------------------------------
# the two readers on a made trace
# ---------------------------------------------------------------------------

STEP = "_train_step_raw"
MIXER = "Transformer/CheckpointBlock/ShortConvMixer/shortconv"


def _events(*rows):
    names = list(dict.fromkeys(r[0] for r in rows))
    return tr.Events(names, np.array([names.index(r[0]) for r in rows], int),
                     np.array([r[1] for r in rows], float),
                     np.array([r[2] for r in rows], float))


def _op(name, opcode="fusion"):
    return f"%{name} = f32[8]{{0}} {opcode}(f32[8]{{0}} %p)"


def _kernel(name):
    return (f"%{name} = f32[8]{{0}} custom-call(f32[8]{{0}} %p), "
            'custom_call_target="tpu_custom_call"')


def _scope_map(rows):
    keys = list(dict.fromkeys(rows.values()))
    return {"kind": "program_scopes", "program": STEP, "module": "jit_" + STEP,
            "scopes": [list(k) for k in keys],
            "ops": {name: keys.index(k) for name, k in rows.items()},
            "instructions": len(rows), "unnamed": 0, "stale": []}


# one step of 10 units: the projections 2 (forward) and 1.5 (backward), the
# gates and convolution 1 forward, 0.5 again under remat and 1.5 backward (3
# in all; the backward's while of 1.5 holds 0.5 of its own), an expert layer
# 2.5, idle 1
SCOPES = {
    "fusion.1": (MIXER + "/shortconv_proj", "fwd"),
    "fusion.2": (MIXER + "/shortconv_conv", "fwd"),
    "fusion.3": (MIXER + "/shortconv_conv", "remat"),
    "while.4": (MIXER + "/shortconv_conv", "bwd"),
    "fusion.5": (MIXER + "/shortconv_conv", "bwd"),
    "fusion.6": (MIXER + "/shortconv_proj", "bwd"),
    "fusion.7": ("Transformer/CheckpointBlock/MoEFeedForward/moe_experts", "fwd"),
}


def _step_ops(t, unit, forward):
    rows = [(_op("fusion.1"), 0.0, 2.0), (forward, 2.0, 3.0),
            (_op("fusion.3"), 3.0, 3.5), (_op("while.4", "while"), 3.5, 5.0),
            (_op("fusion.5"), 4.0, 5.0), (_op("fusion.6"), 5.0, 6.5),
            (_op("fusion.7"), 6.5, 9.0)]
    return [(n, t + unit * a, t + unit * b) for n, a, b in rows]


def _made_run(unit, scopes=SCOPES, config=CONFIG, forward=_op("fusion.2")):
    """Two executions of the step, ``unit`` seconds a unit of the table
    above; ``forward`` is the op that runs the gates' forward."""
    ops = _step_ops(0.0, unit, forward) + _step_ops(20 * unit, unit, forward)
    modules = _events((f"jit_{STEP}(1)", 0.0, 10 * unit),
                      (f"jit_{STEP}(1)", 20 * unit, 30 * unit))
    dev = tr.DeviceTrace(0, modules, _events(*ops), tr.EMPTY)
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(root=ROOT, config=config), records=[],
        facts={"module": STEP, "seq_len": 8192,
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


def _need():
    peaks = harness.peaks_for("TPU v5 lite")
    return 5 * sum(flops.shortconv_least_seconds(CONFIG, 8192, 1, kind, peaks)[0]
                   for kind in ("fwd", "bwd"))


def test_the_readers_sum_the_mixers_seconds_over_all_passes(publish):
    unit = _need() / 3 / 0.25         # the gates' 3 units at a quarter
    run, scope_map = _made_run(unit)
    publish.append(scope_map)
    assert _read("short_conv.conv_roofline", run) == pytest.approx(25.0)
    # projections 3.5, gates and convolution 3 of the 9 busy units
    assert _read("short_conv.mixer_device_share", run) == pytest.approx(
        100 * 6.5 / 9)


def test_a_kernel_is_read_whatever_scope_the_map_gives_it(publish):
    """A Mosaic call named ``shortconv_*`` counts once: inside the scope with
    the scope's seconds, and under no scope by its own."""
    unit = _need() / 3 / 0.25
    rest = {k: v for k, v in SCOPES.items() if k != "fusion.2"}
    for path in (MIXER + "/shortconv_conv", ""):
        run, scope_map = _made_run(
            unit, scopes=dict(rest, **{"shortconv_fwd.9": (path, "fwd")}),
            forward=_kernel("shortconv_fwd.9"))
        publish[:] = [scope_map]
        assert _read("short_conv.conv_roofline", run) == pytest.approx(25.0)
    # a kernel of another name under no scope is somebody else's
    run, scope_map = _made_run(
        unit, scopes=dict(rest, **{"ragged-dot.9": ("", "fwd")}),
        forward=_kernel("ragged-dot.9"))
    publish[:] = [scope_map]
    assert _read("short_conv.conv_roofline", run) == pytest.approx(25.0 * 3 / 2)


def test_the_readers_return_nothing_where_there_is_nothing_to_read(publish):
    """No map (a program from before the event), a step without such a layer,
    a flops module without the function: the line leaves the metrics out."""
    names = ("short_conv.conv_roofline", "short_conv.mixer_device_share")
    run, scope_map = _made_run(1.0)
    for name in names:
        assert _read(name, run) is None                      # no map published
    other = {k: ("Transformer/CheckpointBlock/Attention", w)
             for k, (_, w) in SCOPES.items()}
    run, scope_map = _made_run(1.0, scopes=other)
    publish[:] = [scope_map]
    for name in names:
        assert _read(name, run) is None                      # no such layer
    run, scope_map = _made_run(1.0, config=dict(
        CONFIG, flops={"module": "mla_moe", "function": "train_flops_per_token"}))
    publish[:] = [scope_map]
    assert _read("short_conv.conv_roofline", run) is None    # no such function
    run.trace = None
    assert _read("short_conv.mixer_device_share", run) is None  # no trace
