"""The benchmark's side of the Ling-3.0-flash configuration (ISSUE 32): its
file against the catalog row, its shape functions against hand counts and the
program's own, the job ``pretrain_moe`` end to end on the CPU at a tiny
fixture (``fixture_root_ling3``), the two new readers on synthetic runs, and
the KDA chunk kernels compiled at the cell's real shapes for a described
v5e."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark import trace_reduce as tr

ROOT = harness.ROOT
FIXTURE_ROOT = os.path.join(ROOT, "tests", "benchmark", "fixture_root_ling3")
CELL = "pretrain_ling3_ep64_1chip"
with open(os.path.join(ROOT, "benchmark", "configs",
                       "ling3.0_flash_ep64_l7.json")) as _f:
    CONFIG = json.load(_f)
flops = harness.load_module(ROOT, "flops", "kda_mla_moe")

# the catalog row's numbers (model-configs guide, architectures.jsonl,
# Ling-3.0-flash-VL) that are not in ``reduced``: none may differ
PUBLISHED = dict(
    hidden_size=2560, intermediate_size=6144, moe_intermediate_size=768,
    moe_shared_expert_intermediate_size=768, num_experts_per_tok=8,
    num_attention_heads=32, num_key_value_heads=32, head_dim=128,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, rotary_dim=64, partial_rotary_factor=0.5,
    rope_theta=6000000, rms_norm_eps=1e-6, vocab_size=157184,
    max_position_embeddings=131072, routed_scaling_factor=2.5, n_group=8,
    topk_group=4, layer_group_size=6, num_kv_heads_for_linear_attn=0,
    group_norm_size=1, short_conv_kernel_size=4, kda_lower_bound=-5)


def test_configuration_keeps_the_published_numbers_and_states_its_cut():
    for key, value in PUBLISHED.items():
        assert CONFIG[key] == value, key
    assert CONFIG["q_lora_rank"] is None and CONFIG["use_qk_norm"] is True
    assert CONFIG["expert_swiglu_limit_list"][:35] == [0] * 35
    assert CONFIG["share_expert_swiglu_limit_list"][:34] == [0] * 34
    assert len(CONFIG["expert_swiglu_limit_list"]) == 42
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    (entry,) = [c for c in spec["configs"] if c["name"] == CONFIG["name"]]
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"]) == sorted(
        ["num_hidden_layers", "first_k_dense_replace", "num_experts",
         "vocab_rows_held"])
    assert entry["source"] == CONFIG["source"]
    assert CONFIG["published"] == dict(num_hidden_layers=42,
                                       first_k_dense_replace=2,
                                       num_experts=512, vocab_size=157184)
    assert (CONFIG["num_hidden_layers"], CONFIG["first_k_dense_replace"],
            CONFIG["num_experts"], CONFIG["vocab_rows_held"]) == (7, 1, 8, 19648)
    assert CONFIG["vocab_rows_held"] * 8 == CONFIG["vocab_size"]
    assert "64 chips share each layer" in CONFIG["deployment"]
    assert "821.95M" in CONFIG["notes"]["parameters"]
    assert "128 tokens a held expert" in CONFIG["notes"]["tokens_per_expert"]
    for key in ("kda_decay_gate", "use_qk_norm", "rotary_pairs",
                "moe_bias_rate", "group_score", "kda_initializers",
                "kda_chunk", "recipe", "data", "left_out"):
        assert CONFIG["assumed"][key]
    for key in ("loss_abs", "logits_rel_l2", "routing_margin",
                "near_tie_share_max"):
        assert len(CONFIG["tolerances"][key + "_why"]) > 100


def test_the_cell_resolves_and_builds_the_program_the_file_describes():
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.cheetah.runner import config_from_args
    from fedml_tpu.parallel import kda

    cell = harness.load_cell(CELL)
    assert (cell.chips, cell.job) == (1, "pretrain_moe")
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s_per_chip", "peak_hbm_gb", "setup_s"}
    assert {"kda.kernel_roofline", "kda.kernel_device_share",
            "mla_attention.kernel_roofline", "moe_products.kernel_roofline",
            "cheetah_step.mfu"} <= {m["name"] for m in cell.per_layer}
    job = harness.load_module(ROOT, "jobs", cell.job).Job(
        cell, seed=0, tracked=False, work_dir="", log=lambda s: None)
    assert (job.seq_len, job.batch, job.tokens_per_step()) == (8192, 1, 8192)
    cfg = config_from_args(Arguments(overrides=job.program))
    for key, arg in CONFIG["program_argument_of"].items():
        have = getattr(cfg, arg)
        want = CONFIG[key]
        assert (float(want) == float(have) if isinstance(have, (int, float))
                else want == have), key
    assert cfg.mixers == ("kda",) * 5 + ("mla", "kda")
    assert cfg.layer_kinds == ("dense",) + ("moe",) * 6
    assert (cfg.q_lora_rank, cfg.hc_mult, cfg.mtp_layers) == (0, 1, 0)
    assert cfg.max_seq_len == 8192 and CONFIG["kda_chunk"] == kda.KDA_CHUNK


def test_job_refuses_a_program_without_the_arguments(monkeypatch):
    """The PR's parent: its ``TransformerConfig`` lacks the mixer per layer,
    and the job says so at once."""
    import dataclasses

    from fedml_tpu.parallel import transformer

    @dataclasses.dataclass(frozen=True)
    class Old:
        vocab_size: int = 0
        d_model: int = 0

    monkeypatch.setattr(transformer, "TransformerConfig", Old)
    cell = harness.load_cell(CELL)
    with pytest.raises(RuntimeError, match="cannot build ling3.0_flash_ep64_l7"):
        harness.load_module(ROOT, "jobs", cell.job).Job(
            cell, seed=0, tracked=False, work_dir="", log=lambda s: None)


def test_flops_by_hand_and_the_programs_gauge_agrees():
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.cheetah.runner import config_from_args
    from fedml_tpu.parallel.transformer import train_flops_per_token

    D, H, hd, C, L = 2560, 32, 128, 64, 8192
    kda_layer = (2 * D * (4 * H * hd + 2 * H) + 2 * H * hd * D
                 + 2 * 4 * 3 * H * hd
                 + H * (2 * C * 5 * hd + 2 * C * C / 3 + 6 * hd * hd))
    mla_layer = (2 * (D * H * 192 + D * 576 + 512 * H * 256 + H * 128 * D)
                 + 2 * H * 320 * (L + 1) / 2)
    expert = 2 * D * 512 + (1 + 8 * 8 / 512) * 6 * D * 768
    forward = (6 * kda_layer + mla_layer + 6 * D * 6144 + 6 * expert
               + 2 * D * 19648)
    assert flops.train_flops_per_token(CONFIG, L) == pytest.approx(3 * forward)
    assert 1.05e9 < forward < 1.15e9      # about 1.1 GFLOP a token forward
    assert 0.55 < 6 * kda_layer / forward < 0.65  # the KDA layers' share
    cell = harness.load_cell(CELL)
    job = harness.load_module(ROOT, "jobs", cell.job).Job(
        cell, seed=0, tracked=False, work_dir="", log=lambda s: None)
    cfg = config_from_args(Arguments(overrides=job.program))
    assert train_flops_per_token(cfg, L) == pytest.approx(
        flops.train_flops_per_token(CONFIG, L), rel=1e-12)


def test_kernel_operations_and_bytes():
    peaks = harness.peaks_for("TPU v5 lite")
    tokens = 8192 * 32
    fwd, bound = flops.kda_kernel_least_seconds(CONFIG, 8192, 1, "fwd", peaks)
    operands = 2 * (4 * 128 + 64) + 4 * 128 / 64
    assert bound == "bytes"
    assert fwd == pytest.approx(tokens * (operands + 2 * 128) / 819e9)
    assert tokens * 2 * (3 * 128 * 128 + 64 * 128) / 197e12 < fwd
    bwd, bound = flops.kda_kernel_least_seconds(CONFIG, 8192, 1, "bwd", peaks)
    assert bound == "bytes"
    assert bwd == pytest.approx(tokens * (2 * operands + 2 * 128) / 819e9)
    with pytest.raises(ValueError, match="fwd|bwd"):
        flops.kda_kernel_least_seconds(CONFIG, 8192, 1, "dq", peaks)
    per_call = flops.attention_kernel_flops(CONFIG, 8192, 1)
    pairs = 32 * 8192 * 8193 / 2
    assert per_call["fwd"] == 2 * pairs * 320
    assert per_call["dkv"] == 2 * pairs * 640
    seconds, bound = flops.grouped_product_least_seconds(
        1024, 8, 2560, 1536, peaks)
    assert bound == "bytes" and seconds == pytest.approx(
        2 * (8 * 2560 * 1536 + 1024 * (2560 + 1536)) / 819e9)


def _program_seeds(monkeypatch, cell_name, seed):
    """The program's ``random_seed`` as the job hands it to the data loader
    (the synthetic corpus) and to the runner (weights and windows)."""
    from fedml_tpu import data as data_mod
    from fedml_tpu import runner as runner_mod

    seen = {}

    class Built(Exception):
        pass

    def load(args):
        seen["corpus"] = int(args.random_seed)
        return None, None

    def runner(args, *rest):
        seen["weights_and_windows"] = int(args.random_seed)
        raise Built

    monkeypatch.setattr(data_mod, "load", load)
    monkeypatch.setattr(runner_mod, "FedMLRunner", runner)
    cell = harness.load_cell(cell_name)
    job = harness.load_module(ROOT, "jobs", cell.job).Job(
        cell, seed=seed, tracked=False, work_dir="", log=lambda s: None)
    with pytest.raises(Built):
        job.setup()
    return seen


def test_the_window_runs_on_the_configurations_seed_whatever_the_seed(
        monkeypatch):
    """The cell's steps run on one draw of weights, corpus and windows for
    every ``--seed`` (``window_seed``); the reference check's draw and batch
    follow ``--seed`` (``_reference_check`` reads ``job.seed``)."""
    pinned = int(CONFIG["window_seed"])
    for seed in (2**31 + 17, 3):
        assert _program_seeds(monkeypatch, CELL, seed) == {
            "corpus": pinned, "weights_and_windows": pinned}


def test_a_configuration_without_a_window_seed_runs_on_the_seed(monkeypatch):
    cell = harness.load_cell("pretrain_xing4_ep8_1chip")
    assert "window_seed" not in cell.config
    assert _program_seeds(monkeypatch, cell.name, 2**31 + 17) == {
        "corpus": 2**31 + 17, "weights_and_windows": 2**31 + 17}


# ---------------------------------------------------------------------------
# the job end to end on the CPU, and the readers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_run_cell_on_the_tiny_fixture(trace, tmp_path):
    cell = harness.load_cell("tiny_pretrain_ling3", root=FIXTURE_ROOT)
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
    assert not {"kda.kernel_roofline", "kda.kernel_device_share",
                "cheetah_step.mfu"} & got
    share = result["metrics"]["moe.assignments_held_share"]["value"]
    assert 3 < share < 40            # 2 of 16 experts held: 12.5% if balanced


def _events(names_and_seconds):
    names, ids, start, end, t = [], [], [], [], 0.0
    for name, seconds in names_and_seconds:
        if name not in names:
            names.append(name)
        ids.append(names.index(name))
        start.append(t)
        t += seconds
        end.append(t)
    return tr.Events(names, np.asarray(ids), np.asarray(start), np.asarray(end))


def _run(ops, facts):
    dev = tr.DeviceTrace(0, tr.EMPTY, _events(ops), tr.EMPTY)
    return harness.TracedRun(
        cell=harness.load_cell(CELL), facts=facts, records=[], counters={},
        peaks=harness.peaks_for("TPU v5 lite"), trace=tr.Trace([dev], None))


KERNEL = ('%{name} = (bf16[1,32,8192,128]{{3,2,1,0}}, f32[1,32,128,128]) '
          'custom-call(%a, %b), custom_call_target="tpu_custom_call"')
FACTS = dict(seq_len=8192, sequences_per_step_per_chip=1)


def test_kda_readers_find_their_kernels_and_no_others():
    peaks = harness.peaks_for("TPU v5 lite")
    fwd = flops.kda_kernel_least_seconds(CONFIG, 8192, 1, "fwd", peaks)[0]
    bwd = flops.kda_kernel_least_seconds(CONFIG, 8192, 1, "bwd", peaks)[0]
    ops = [(KERNEL.format(name="kda_chunk_fwd.3"), fwd / 0.5),   # at half
           (KERNEL.format(name="kda_chunk_fwd.9"), fwd / 0.5),
           (KERNEL.format(name="kda_chunk_bwd.4"), bwd / 0.25),  # a quarter
           (KERNEL.format(name="splash_mha_fwd_residuals.1"), 1.0),
           ("%fusion.1 = f32[8] fusion(%x), kind=kLoop", 2.0)]
    run = _run(ops, FACTS)
    read = lambda name: harness.load_module(ROOT, "layer_metrics", name).read(run)
    spent = 2 * fwd / 0.5 + bwd / 0.25
    assert read("kda.kernel_roofline") == pytest.approx(
        100 * (2 * fwd + bwd) / spent)
    assert read("kda.kernel_device_share") == pytest.approx(
        100 * spent / (spent + 3.0))
    assert 0 < read("kda.kernel_roofline") < 100


def test_kda_readers_return_nothing_where_the_program_has_no_such_kernel():
    """The PR's parent, and every configuration without a KDA layer: the
    line leaves the metrics out."""
    ops = [(KERNEL.format(name="splash_mha_fwd_residuals.1"), 1.0),
           ("%fusion.1 = f32[8] fusion(%x), kind=kLoop", 1.0)]
    for facts in (FACTS, {"tokens_per_step": 8192, "chips": 1}):
        run = _run(ops, facts)
        for name in ("kda.kernel_roofline", "kda.kernel_device_share"):
            assert harness.load_module(ROOT, "layer_metrics", name).read(run) is None
    # and with a flops module that lacks the function (another configuration)
    run = _run([(KERNEL.format(name="kda_chunk_fwd.3"), 1.0)], FACTS)
    run.cell.config = dict(run.cell.config,
                           flops={"module": "mla_moe",
                                  "function": "train_flops_per_token"})
    assert harness.load_module(
        ROOT, "layer_metrics", "kda.kernel_roofline").read(run) is None


def test_the_reference_at_a_small_size():
    """The reference alone, no program: the recurrence forgets at the decay's
    rate, overwrites a key's value when beta is 1, and the loss of a seeded
    model is finite with its margins."""
    ref = harness.load_module(ROOT, "reference", "ling3")
    k = jnp.zeros((3, 1, 4)).at[:, 0, 0].set(1.0)          # the same key
    v = jnp.arange(1.0, 7.0).reshape(3, 1, 2)
    g = jnp.full((3, 1, 4), jnp.log(0.5))
    o, S = ref.delta_rule_recurrence(k, k, v, g, jnp.ones((3, 1)))
    # beta 1: the key's old value is removed and the new one written
    assert np.allclose(o, v) and np.allclose(S[0, 0], v[-1, 0])
    o, S = ref.delta_rule_recurrence(k, k, v, g, jnp.zeros((3, 1)))
    assert np.allclose(o, 0) and np.allclose(S, 0)
    half = jnp.full((3, 1), 0.5)
    o, _ = ref.delta_rule_recurrence(k, k, v, g, half)
    # S_1 = v_1 / 2; S_2 = S_1 / 2 + (v_2 - S_1 / 2) / 2
    s1 = v[0, 0] / 2
    s2 = s1 / 2 + (v[1, 0] - s1 / 2) / 2
    assert np.allclose(o[1, 0], s2)
    x = jnp.arange(12.0).reshape(6, 2)
    taps = jnp.array([[0.0, 1.0], [0.0, 0.0], [0.0, 0.0], [1.0, 0.0]])
    y = ref.causal_depthwise_conv(x, taps)
    assert np.allclose(y[:, 0], x[:, 0])                   # the current token
    assert np.allclose(y[3:, 1], x[:3, 1]) and np.allclose(y[:3, 1], 0)


# ---------------------------------------------------------------------------
# the KDA chunk kernels at the cell's shapes, for a described v5e
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def one_chip():
    """Described inside the fixture, never at import (on-chip-measurement
    guide, section 2); skips where no topology can be described, and where
    another test file's worker holds the TPU's library."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # whatever the plugin raises where it cannot
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def test_kda_chunk_kernels_compile_at_the_cells_shapes(one_chip):
    """Forward (with and without the saved states) and backward at 32 heads
    of 128, one sequence of 8,192 tokens in chunks of ``KDA_CHUNK``."""
    from fedml_tpu.parallel import kda

    B, H, T, hd, C = 1, 32, 8192, 128, kda.KDA_CHUNK
    N = T // C

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    wide = shape(B, H, N, C, hd)
    operands = (wide, wide, wide, wide, shape(B, H, N, C, C),
                shape(B, H, N, hd, dtype=jnp.float32),
                shape(B, H, hd, hd, dtype=jnp.float32))
    for save in (False, True):
        text = jax.jit(lambda *a, save=save: kda.chunk_fwd(
            *a, save_states=save)).lower(*operands).compile().as_text()
        assert "kda_chunk_fwd" in text and "tpu_custom_call" in text
    states = shape(B, H, N, hd, hd, dtype=jnp.float32)
    text = jax.jit(kda.chunk_bwd).lower(
        *operands[:6], states, wide, operands[6]).compile().as_text()
    assert "kda_chunk_bwd" in text and "tpu_custom_call" in text
