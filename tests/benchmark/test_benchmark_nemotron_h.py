"""The benchmark's side of the NVIDIA-Nemotron-3-Nano-30B-A3B configuration
(ISSUE 38): its file against the catalog row, its shape functions against hand
counts and the program's own, the job ``pretrain_moe`` end to end on the CPU
at a tiny fixture (``fixture_root_nemotron_h``), and the two new readers on a
made trace whose seconds can be worked out on paper."""

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
FIXTURE_ROOT = os.path.join(ROOT, "tests", "benchmark", "fixture_root_nemotron_h")
CELL = "pretrain_nemotron3_ep16_1chip"
with open(os.path.join(ROOT, "benchmark", "configs",
                       "nemotron3_nano_30b_a3b_ep16_l9.json")) as _f:
    CONFIG = json.load(_f)
flops = harness.load_module(ROOT, "flops", "ssm_moe")

# the catalog row's numbers (model-configs guide, architectures.jsonl,
# NVIDIA-Nemotron-3-Nano-30B-A3B-BF16) that are not in ``reduced``: none may
# differ
PUBLISHED = dict(
    chunk_size=128, conv_kernel=4, expand=2, head_dim=128, hidden_size=2688,
    intermediate_size=1856, layer_norm_epsilon=1e-5, mamba_head_dim=64,
    mamba_num_heads=64, max_position_embeddings=262144,
    moe_intermediate_size=1856, moe_shared_expert_intermediate_size=3712,
    n_group=1, n_groups=8, n_shared_experts=1, norm_eps=1e-5,
    num_attention_heads=32, num_experts_per_tok=6, num_key_value_heads=2,
    num_logits_to_keep=1, partial_rotary_factor=1, rope_theta=10000,
    routed_scaling_factor=2.5, ssm_state_size=128, time_step_floor=1e-4,
    time_step_max=0.1, time_step_min=1e-3, topk_group=1, vocab_size=131072)
WHOLE_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def test_configuration_keeps_the_published_numbers_and_states_its_cut():
    for key, value in PUBLISHED.items():
        assert CONFIG[key] == value, key
    assert CONFIG["mlp_hidden_act"] == "relu2" and CONFIG["model_type"] == "nemotron_h"
    assert CONFIG["mamba_hidden_act"] == "silu" and CONFIG["use_conv_bias"] is True
    assert CONFIG["mamba_proj_bias"] is False and CONFIG["attention_bias"] is False
    assert CONFIG["norm_topk_prob"] is True and CONFIG["sliding_window"] is None
    assert CONFIG["tie_word_embeddings"] is False
    # every key of the catalog's config is in the file
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["source_url"] == CONFIG["source"]]
    for key, value in row["config"].items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    (entry,) = [c for c in spec["configs"] if c["name"] == CONFIG["name"]]
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"]) == sorted(
        ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
         "vocab_rows_held"])
    assert entry["source"] == CONFIG["source"]
    assert CONFIG["published"] == dict(
        num_hidden_layers=52, hybrid_override_pattern=WHOLE_PATTERN,
        n_routed_experts=128, vocab_size=131072)
    assert (CONFIG["num_hidden_layers"], CONFIG["hybrid_override_pattern"],
            CONFIG["n_routed_experts"], CONFIG["vocab_rows_held"]) == (
        9, "MEMEMEM*E", 8, 16384)
    # the cut is the published layers 35 to 43, and the kinds' ratio
    assert WHOLE_PATTERN[35:44] == CONFIG["hybrid_override_pattern"]
    assert [WHOLE_PATTERN.count(c) for c in "ME*"] == [23, 23, 6]
    assert CONFIG["vocab_rows_held"] * 8 == CONFIG["vocab_size"]
    assert "16 chips share each layer" in CONFIG["deployment"]
    assert "666.96M" in CONFIG["notes"]["parameters"]
    assert "384 tokens a held expert" in CONFIG["notes"]["tokens_per_expert"]
    for key in ("no_rotary", "dt_clamp", "gate_before_norm", "d_inner",
                "initializers", "moe_bias_rate", "routing", "precision",
                "recipe", "data", "chunk"):
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
    assert {"ssd.mixer_device_share", "ssd.scan_roofline",
            "moe_experts.kernel_roofline", "cheetah_step.mfu",
            "attention_kernels.device_share"} <= names
    assert not {n for n in names if n.startswith(("kda.", "mla_", "bd_",
                                                  "collectives."))}
    assert len(names) == 22  # the 18 named lists, the 2 new, the 2 of `entry`
    job = _job(cell)
    assert (job.seq_len, job.batch, job.tokens_per_step()) == (8192, 1, 8192)
    cfg = config_from_args(Arguments(overrides=job.program))
    for key, arg in CONFIG["program_argument_of"].items():
        have = getattr(cfg, arg)
        want = CONFIG[key]
        assert (float(want) == float(have) if isinstance(have, (int, float))
                else want == have), key
    assert cfg.mixers == ("ssd", "none") * 3 + ("ssd", "gqa", "none")
    assert cfg.layer_kinds == ("none", "moe") * 3 + ("none", "none", "moe")
    assert (cfg.pos_emb, cfg.ffn_act, cfg.hc_mult, cfg.mtp_layers) == (
        "none", "relu2", 1, 0)
    assert (cfg.ssm_inner, cfg.shared_d_ff, cfg.head_dim) == (4096, 3712, 128)
    assert cfg.max_seq_len == 8192
    # the parameters, counted from shapes alone: 666.96M
    shapes = jax.eval_shape(
        lambda: Transformer(cfg).init(jax.random.PRNGKey(0),
                                      jnp.zeros((1, 8), jnp.int32)))
    assert sum(int(np.prod(s.shape)) for s in
               jax.tree.leaves(shapes["params"])) == 666_962_944


def test_job_refuses_a_program_without_the_arguments(monkeypatch):
    """The PR's parent: its ``TransformerConfig`` lacks the layer pattern and
    the state-space sizes, and the job says so at once."""
    import dataclasses

    from fedml_tpu.parallel import transformer

    @dataclasses.dataclass(frozen=True)
    class Old:
        vocab_size: int = 0
        d_model: int = 0

    monkeypatch.setattr(transformer, "TransformerConfig", Old)
    with pytest.raises(RuntimeError,
                       match="cannot build nemotron3_nano_30b_a3b_ep16_l9"):
        _job(harness.load_cell(CELL))


def test_flops_by_hand_and_the_programs_gauge_agrees():
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.cheetah.runner import config_from_args
    from fedml_tpu.parallel.transformer import train_flops_per_token

    D, L = 2688, 8192
    chunked = 8 * 2 * 128 * 128 + 64 * (2 * 128 * 64 + 4 * 64 * 128)
    mamba = 2 * D * (4096 + 6144 + 64) + 2 * 4096 * D + 2 * 4 * 6144 + chunked
    attn = 2 * D * 128 * (64 + 4) + 2 * 2 * 32 * 128 * (L + 1) / 2
    expert = 2 * D * 128 + 4 * D * (3712 + 6 * 8 / 128 * 1856)
    forward = 4 * mamba + attn + 4 * expert + 2 * D * 16384
    assert flops.ssd_chunked_flops_per_token(CONFIG) == chunked
    assert flops.train_flops_per_token(CONFIG, L) == pytest.approx(3 * forward)
    assert 0.70e9 < forward < 0.73e9       # about 0.72 GFLOP a token forward
    assert 0.44 < 4 * mamba / forward < 0.46   # the mixers' share of them
    assert chunked / mamba < 0.05          # of which the recurrence is little
    cfg = config_from_args(Arguments(overrides=_job(harness.load_cell(CELL)).program))
    assert train_flops_per_token(cfg, L) == pytest.approx(
        flops.train_flops_per_token(CONFIG, L), rel=1e-12)


def test_ssd_least_seconds_by_hand():
    peaks = harness.peaks_for("TPU v5 lite")
    tokens = 8192
    operands = 2 * (4096 + 2 * 1024) + 4 * 64       # X, B, C in bf16, dt in f32
    fwd, bound = flops.ssd_least_seconds(CONFIG, 8192, 1, "fwd", peaks)
    assert bound == "bytes"
    assert fwd == pytest.approx(tokens * (operands + 2 * 4096) / 819e9)
    assert tokens * flops.ssd_chunked_flops_per_token(CONFIG) / 197e12 < fwd
    bwd, bound = flops.ssd_least_seconds(CONFIG, 8192, 1, "bwd", peaks)
    assert bound == "bytes"
    assert bwd == pytest.approx(tokens * (2 * operands + 2 * 4096) / 819e9)
    two, _ = flops.ssd_least_seconds(CONFIG, 8192, 2, "fwd", peaks)
    assert two == pytest.approx(2 * fwd)
    with pytest.raises(ValueError, match="fwd|bwd"):
        flops.ssd_least_seconds(CONFIG, 8192, 1, "remat", peaks)
    # the experts' products: two matrices an expert, [held, 2688, 1856]
    seconds, bound = flops.grouped_product_least_seconds(
        3072, 8, 2688, 1856, peaks)
    assert bound == "flops" and seconds == pytest.approx(
        2 * 3072 * 2688 * 1856 / 197e12)
    assert seconds > 2 * (8 * 2688 * 1856 + 3072 * (2688 + 1856)) / 819e9


# ---------------------------------------------------------------------------
# the job end to end on the CPU
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_run_cell_on_the_tiny_fixture(trace, tmp_path):
    cell = harness.load_cell("tiny_pretrain_nemotron_h", root=FIXTURE_ROOT)
    logged = []
    result = harness.run_cell(cell, seed=2**31 + 5, seconds=1.0, trace=trace,
                              work_dir=str(tmp_path), log=logged.append)
    assert result["correct"] is True and result["failed"] == 0
    assert any("near-tie share" in line for line in logged)
    got = set(result["metrics"])
    if not trace:
        assert got == {"tokens_per_s_per_chip", "peak_hbm_gb", "setup_s"}
        return
    assert {"moe.assignments_held_share", "moe.max_expert_load_ratio",
            "entry.compile_s", "cheetah_runner.data_s_per_step"} <= got
    # the device trace's readers find no TPU plane on this CPU
    assert not {"ssd.scan_roofline", "ssd.mixer_device_share",
                "cheetah_step.mfu"} & got
    share = result["metrics"]["moe.assignments_held_share"]["value"]
    assert 3 < share < 40            # 2 of 16 experts held: 12.5% if balanced
    # the program said what it built
    inits = [e for e in mlops.read_events(os.path.join(
        str(tmp_path), "runs", f"run_{cell.name}_seed{2**31 + 5}_edge_0.jsonl"))
        if e.get("kind") == "cheetah_init"]
    assert inits and all(e["layer_pattern"] == "MEMEMEM*E"
                         and e["ssd"]["chunk"] == 32 for e in inits)


# ---------------------------------------------------------------------------
# the two readers on a made trace
# ---------------------------------------------------------------------------

STEP = "_train_step_raw"
MIXER = "Transformer/CheckpointBlock/Mamba2Mixer/mamba"


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


# one step of 10 units: the projections 2 (forward) and the chunked form 1.5
# forward, 1 again under remat and 2.5 backward (5 in all), the norm 0.5, an
# expert layer 2, idle 0.5
SCOPES = {
    "fusion.1": (MIXER + "/ssd_proj", "fwd"),
    "fusion.2": (MIXER + "/ssd_chunk", "fwd"),
    "fusion.3": (MIXER + "/ssd_chunk", "remat"),
    "while.4": (MIXER + "/ssd_chunk", "bwd"),
    "fusion.5": (MIXER + "/ssd_chunk", "bwd"),
    "fusion.6": (MIXER + "/ssd_norm", "fwd"),
    "fusion.7": ("Transformer/CheckpointBlock/MoEFeedForward/moe_experts", "fwd"),
}


def _step_ops(t, unit, forward):
    rows = [(_op("fusion.1"), 0.0, 2.0), (forward, 2.0, 3.5),
            (_op("fusion.3"), 3.5, 4.5), (_op("while.4", "while"), 4.5, 7.0),
            (_op("fusion.5"), 5.0, 7.0), (_op("fusion.6"), 7.0, 7.5),
            (_op("fusion.7"), 7.5, 9.5)]
    return [(n, t + unit * a, t + unit * b) for n, a, b in rows]


def _made_run(unit, scopes=SCOPES, config=CONFIG, forward=_op("fusion.2")):
    """Two executions of the step, ``unit`` seconds a unit of the table
    above; ``forward`` is the op that runs the chunked form's forward."""
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
    return 4 * sum(flops.ssd_least_seconds(CONFIG, 8192, 1, kind, peaks)[0]
                   for kind in ("fwd", "bwd"))


def test_the_readers_sum_the_mixers_seconds_over_all_passes(publish):
    need = _need()
    unit = need / 5 / 0.25            # the chunked form's 5 units at a quarter
    run, scope_map = _made_run(unit)
    publish.append(scope_map)
    assert _read("ssd.scan_roofline", run) == pytest.approx(25.0)
    # projections 2, chunked 5, norm 0.5 of the 9.5 busy units
    assert _read("ssd.mixer_device_share", run) == pytest.approx(100 * 7.5 / 9.5)


def test_a_kernel_is_read_whatever_scope_the_map_gives_it(publish):
    """A Mosaic call named ``ssd_*`` counts once: inside the scope with the
    scope's seconds, and under no scope (as XLA names the grouped products)
    by its own."""
    need = _need()
    unit = need / 5 / 0.25
    rest = {k: v for k, v in SCOPES.items() if k != "fusion.2"}
    # the kernel stands where the forward fusion stood: the same seconds
    for path in (MIXER + "/ssd_chunk", ""):
        run, scope_map = _made_run(
            unit, scopes=dict(rest, **{"ssd_chunk_fwd.9": (path, "fwd")}),
            forward=_kernel("ssd_chunk_fwd.9"))
        publish[:] = [scope_map]
        assert _read("ssd.scan_roofline", run) == pytest.approx(25.0)
    # a kernel of another name under no scope is somebody else's
    run, scope_map = _made_run(
        unit, scopes=dict(rest, **{"ragged-dot.9": ("", "fwd")}),
        forward=_kernel("ragged-dot.9"))
    publish[:] = [scope_map]
    assert _read("ssd.scan_roofline", run) == pytest.approx(25.0 * 5 / 3.5)


def test_the_readers_return_nothing_where_there_is_nothing_to_read(publish):
    """No map (a program from before the event), a step without such a layer,
    a flops module without the function: the line leaves the metrics out."""
    run, scope_map = _made_run(1.0)
    for name in ("ssd.scan_roofline", "ssd.mixer_device_share"):
        assert _read(name, run) is None                      # no map published
    other = {k: ("Transformer/CheckpointBlock/Attention", w)
             for k, (_, w) in SCOPES.items()}
    run, scope_map = _made_run(1.0, scopes=other)
    publish[:] = [scope_map]
    for name in ("ssd.scan_roofline", "ssd.mixer_device_share"):
        assert _read(name, run) is None                      # no such layer
    run, scope_map = _made_run(1.0, config=dict(
        CONFIG, flops={"module": "mla_moe", "function": "train_flops_per_token"}))
    publish[:] = [scope_map]
    assert _read("ssd.scan_roofline", run) is None           # no such function
    run.trace = None
    assert _read("ssd.mixer_device_share", run) is None      # no trace


# ---------------------------------------------------------------------------
# the reference alone
# ---------------------------------------------------------------------------


def test_the_reference_at_a_small_size():
    """The reference alone, no program: the recurrence forgets at the decay's
    rate and reads what was written through ``C``; the convolution is causal
    with its last tap on the current token; the norm is by group after the
    gate."""
    ref = harness.load_module(ROOT, "reference", "nemotron_h")
    X = jnp.zeros((3, 1, 2)).at[0, 0].set(jnp.array([1.0, 2.0]))
    dt = jnp.full((3, 1), 0.5)
    A = jnp.array([-2.0])
    B = jnp.zeros((3, 1, 4)).at[:, 0, 1].set(1.0)
    C = B
    y = ref.ssd_scan(X, dt, A, B, C)
    # written at token 0 with weight dt, decayed by exp(-1) a token after
    want = 0.5 * np.array([1.0, 2.0])
    assert np.allclose(y[0, 0], want)
    assert np.allclose(y[2, 0], want * np.exp(-2.0))
    x = jnp.arange(12.0).reshape(6, 2)
    taps = jnp.array([[0.0, 1.0], [0.0, 0.0], [0.0, 0.0], [1.0, 0.0]])
    bias = jnp.array([0.5, -0.5])
    out = ref.causal_depthwise_conv(x, taps, bias)
    assert np.allclose(out[:, 0], x[:, 0] + 0.5)             # the current token
    assert np.allclose(out[3:, 1], x[:3, 1] - 0.5) and np.allclose(out[:3, 1], -0.5)
    y = jnp.array([[3.0, 4.0, 0.0, 5.0]])
    z = jnp.full((1, 4), 50.0)                               # silu(50) = 50
    normed = ref.gated_group_norm(y, z, jnp.ones((4,)), 2, 0.0)
    assert np.allclose(normed, [[3 / np.sqrt(12.5), 4 / np.sqrt(12.5), 0.0,
                                 np.sqrt(2.0)]], rtol=1e-5)
