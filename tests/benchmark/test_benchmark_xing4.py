"""The benchmark's side of the Xing4.0 configuration (ISSUE 28): its file
against the published widths, its shape functions against hand counts and the
program's own, the job ``pretrain_moe`` end to end on the CPU at a tiny
fixture (``fixture_root_moe``), its readers on synthetic runs, and the two new
kernels compiled at their real shapes for a described v5e."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark import trace_reduce as tr
from benchmark.flops import mla_moe

ROOT = harness.ROOT
FIXTURE_ROOT = os.path.join(ROOT, "tests", "benchmark", "fixture_root_moe")
CELL = "pretrain_xing4_ep8_1chip"
with open(os.path.join(ROOT, "benchmark", "configs",
                       "xing4.0_29b_a4b_ep8_l5.json")) as _f:
    CONFIG = json.load(_f)

# the catalog row's widths (model-configs guide, architectures.jsonl): none
# may differ in the configuration's file
PUBLISHED_WIDTHS = dict(
    hidden_size=3584, num_attention_heads=32, num_key_value_heads=32,
    q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, intermediate_size=9216,
    moe_intermediate_size=1024, num_experts_per_tok=4, n_shared_experts=1,
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
    mhc_h_res_clamp_max=30, rms_norm_eps=1e-6, rope_theta=10000,
    routed_scaling_factor=2, vocab_size=131072, max_position_embeddings=262144,
    n_group=1, topk_group=1, moe_layer_freq=1, ep_size=1)


def test_configuration_keeps_the_published_widths_and_states_its_cut():
    for key, want in PUBLISHED_WIDTHS.items():
        assert CONFIG[key] == want, key
    assert CONFIG["rope_scaling"] == dict(
        beta_fast=32, beta_slow=1, factor=64, mscale=1, mscale_all_dim=1,
        original_max_position_embeddings=4096, type="yarn")
    assert CONFIG["router_experts"] == 64 and CONFIG["scoring_func"] == "sigmoid"
    assert sorted(CONFIG["reduced"]) == sorted([
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "num_nextn_predict_layers", "vocab_rows_held"])
    assert (CONFIG["num_hidden_layers"], CONFIG["first_k_dense_replace"],
            CONFIG["n_routed_experts"], CONFIG["num_nextn_predict_layers"],
            CONFIG["vocab_rows_held"]) == (5, 1, 8, 0, 16384)
    assert CONFIG["published"] == dict(
        num_hidden_layers=40, first_k_dense_replace=2, n_routed_experts=64,
        num_nextn_predict_layers=1, vocab_size=131072)
    # the guide's floors: a whole period and four layers after the dense one,
    # 8 routed experts a layer, an eighth of the vocabulary
    assert CONFIG["num_hidden_layers"] - CONFIG["first_k_dense_replace"] >= 4
    assert CONFIG["n_routed_experts"] >= 8
    assert CONFIG["vocab_rows_held"] * 8 >= CONFIG["vocab_size"]
    for key in ("assumed", "deployment", "tolerances"):
        assert CONFIG[key]
    for name in ("loss_abs", "logits_rel_l2", "routing_margin", "near_tie_share_max"):
        assert CONFIG["tolerances"][name] > 0
        assert len(CONFIG["tolerances"][name + "_why"]) > 40


def test_job_builds_the_program_the_file_describes():
    """Every published key reaches the program's configuration through the
    argument the file pairs it with, and the parameter count is the file's."""
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.cheetah.runner import config_from_args
    from fedml_tpu.parallel.transformer import Transformer

    cell = harness.load_cell(CELL)
    job = harness.load_module(ROOT, "jobs", cell.job).Job(
        cell, seed=0, tracked=False, work_dir="", log=lambda s: None)
    cfg = config_from_args(Arguments(overrides=job.program))
    assert (cfg.d_model, cfg.n_layers, cfg.vocab_size) == (3584, 5, 16384)
    assert cfg.layer_kinds == ("dense", "moe", "moe", "moe", "moe")
    assert (cfg.attn_kind, cfg.moe_router, cfg.moe_experts, cfg.experts_held,
            cfg.moe_top_k, cfg.hc_mult, cfg.mtp_layers) == (
                "mla", "sigmoid", 64, 8, 4, 4, 0)
    assert (cfg.norm_eps, cfg.rope_factor, cfg.moe_capacity_factor) == (1e-6, 64.0, 0.0)
    shapes = jax.eval_shape(
        lambda: Transformer(cfg).init(jax.random.PRNGKey(0),
                                      jnp.zeros((1, 8), jnp.int32)))["params"]
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert n_params == 759_489_550
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        why = {c["name"]: c["why"] for c in json.load(f)["configs"]}
    assert "759M params" in why[CONFIG["name"]]
    assert job.tokens_per_step() == 8192


def test_job_refuses_a_file_whose_arguments_disagree_and_a_program_without_them(
        monkeypatch):
    cell = harness.load_cell(CELL)
    module = harness.load_module(ROOT, "jobs", cell.job)
    wrong = dataclasses.replace(cell, config=dict(
        cell.config, program=dict(cell.config["program"], q_lora_rank=512)))
    with pytest.raises(ValueError, match="q_lora_rank is 768"):
        module.Job(wrong, seed=0, tracked=False, work_dir="", log=print)
    # the PR's parent: its TransformerConfig has none of the new fields
    import fedml_tpu.parallel.transformer as tfm

    @dataclasses.dataclass
    class Old:
        vocab_size: int = 0
        d_model: int = 0

    monkeypatch.setattr(tfm, "TransformerConfig", Old)
    with pytest.raises(RuntimeError, match="cannot build xing4.0"):
        module.Job(cell, seed=0, tracked=False, work_dir="", log=print)


def test_flops_by_hand_and_the_programs_gauge_agrees():
    """Per token forward at 4,096 tokens a sequence. MLA: q_a 3584x768, q_b
    768x(32x192), kv_a 3584x576, kv_b 512x(32x256), o 4096x3584, and causal
    scores and values 32 heads x (192 + 128) x 4097/2. Dense SwiGLU 3 x 3584
    x 9216. Expert layer: router 3584x64, shared 3 x 3584 x 1024, routed 4 x
    8/64 of the same. mHC maps 2 x 14336 x 24. Head 3584 x 16384."""
    mla = 2 * (3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192 + 4096 * 3584) \
        + 2 * 32 * 320 * 4097 / 2
    hyper = 2 * 2 * 14336 * 24
    dense = 2 * 3 * 3584 * 9216
    expert = 2 * 3584 * 64 + (1 + 0.5) * 2 * 3 * 3584 * 1024
    head = 2 * 3584 * 16384
    want = 3 * (5 * (mla + hyper) + dense + 4 * expert + head)
    assert mla_moe.train_flops_per_token(CONFIG, 4096) == want
    assert want == pytest.approx(2.851e9, rel=1e-3)
    # with the MTP module: one more expert block, the 7168 x 3584 projection,
    # the head once more
    with_mtp = mla_moe.train_flops_per_token(
        dict(CONFIG, num_nextn_predict_layers=1), 4096)
    assert with_mtp == want + 3 * (mla + hyper + expert + 2 * 7168 * 3584 + head)

    from fedml_tpu.arguments import Arguments
    from fedml_tpu.cheetah.runner import config_from_args
    from fedml_tpu.parallel.transformer import train_flops_per_token

    cell = harness.load_cell(CELL)
    job = harness.load_module(ROOT, "jobs", cell.job).Job(
        cell, seed=0, tracked=False, work_dir="", log=lambda s: None)
    cfg = config_from_args(Arguments(overrides=job.program))
    assert train_flops_per_token(cfg, 4096) == pytest.approx(want, rel=1e-12)
    assert train_flops_per_token(
        dataclasses.replace(cfg, mtp_layers=1), 4096) == pytest.approx(with_mtp)


def test_kernel_operations_and_bytes():
    per_call = mla_moe.attention_kernel_flops(CONFIG, 4096, 2)
    pairs = 2 * 32 * 4096 * 4097 / 2
    assert per_call == {"fwd": 2 * pairs * 320, "dq": 2 * pairs * 512,
                        "dkv": 2 * pairs * 640}
    peaks = harness.peaks_for("TPU v5 lite")
    # 4,096 rows through 8 matrices of 3584 x 2048: 60 GFLOP against 151 MB
    seconds, bound = mla_moe.grouped_product_least_seconds(4096, 8, 3584, 2048, peaks)
    assert bound == "flops" and seconds == pytest.approx(2 * 4096 * 3584 * 2048 / 197e12)
    # 64 rows in all: the matrices' bytes bound it
    seconds, bound = mla_moe.grouped_product_least_seconds(64, 8, 3584, 2048, peaks)
    assert bound == "bytes"
    assert seconds == pytest.approx(2 * (8 * 3584 * 2048 + 64 * 5632) / 819e9)


# ---------------------------------------------------------------------------
# the job end to end on the CPU, and the readers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_run_cell_on_the_tiny_fixture(trace, tmp_path):
    cell = harness.load_cell("tiny_pretrain_moe", root=FIXTURE_ROOT)
    logged = []
    result = harness.run_cell(cell, seed=2**31 + 5, seconds=1.0, trace=trace,
                              work_dir=str(tmp_path), log=logged.append)
    assert result["correct"] is True and result["failed"] == 0
    assert any("near-tie share" in line for line in logged)
    got = set(result["metrics"])
    if not trace:
        assert got == {"tokens_per_s_per_chip", "peak_hbm_gb", "setup_s"}
        return
    # the counters' readers report; the device trace's find no TPU plane
    assert {"moe.assignments_held_share", "moe.max_expert_load_ratio",
            "entry.compile_s", "cheetah_runner.data_s_per_step"} <= got
    assert not {"mla_attention.kernel_roofline", "moe_experts.kernel_roofline",
                "cheetah_step.mfu"} & got
    share = result["metrics"]["moe.assignments_held_share"]["value"]
    assert 10 < share < 50          # 4 of 16 experts held: 25% if balanced
    assert result["metrics"]["moe.max_expert_load_ratio"]["value"] >= 1.0


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


def _run(ops, records, facts):
    cell = harness.load_cell(CELL)
    dev = tr.DeviceTrace(0, tr.EMPTY, _events(ops), tr.EMPTY)
    return harness.TracedRun(
        cell=cell, facts=facts, records=records, counters={},
        peaks=harness.peaks_for("TPU v5 lite"), trace=tr.Trace([dev], None))


FACTS = dict(seq_len=4096, sequences_per_step_per_chip=2, expert_layers=4,
             experts_held=8, assignments_per_step=8192 * 4 * 4)
CALL = ('%{name} = bf16[{shape}]{{1,0}} custom-call(bf16[32768,3584]{{1,0}} %a, '
        'bf16[8,3584,2048]{{2,1,0}} %w), custom_call_target="tpu_custom_call"')


def test_kernel_readers_tell_the_two_kernels_apart():
    splash = ('%{name} = bf16[2,32,4096,128]{{3,2,1,0}} custom-call(%q), '
              'custom_call_target="tpu_custom_call"')
    per_call = mla_moe.attention_kernel_flops(CONFIG, 4096, 2)
    ops = [(splash.format(name="splash_mha_fwd_residuals.1"),
            per_call["fwd"] / 197e12 / 0.5),            # at half the peak
           (splash.format(name="splash_mha_dkv_no_residuals.1"),
            per_call["dkv"] / 197e12 / 0.5),
           (splash.format(name="splash_mha_dq_no_residuals.1"),
            per_call["dq"] / 197e12 / 0.5),
           (CALL.format(name="ragged-dot-none.3", shape="32768,2048"),
            2 * 4096 * 3584 * 2048 / 197e12 / 0.25),    # at a quarter of it
           (CALL.format(name="ragged-dot-metadata.3", shape="9"), 0.0),
           ("%fusion.1 = f32[8] fusion(%x), kind=kLoop", 1.0)]
    records = [{"counters": {"moe_assignments_held": 4 * 4096.0,
                             "moe_max_expert_load": 768.0}}]
    run = _run(ops, records, FACTS)
    read = lambda name: harness.load_module(ROOT, "layer_metrics", name).read(run)
    assert read("mla_attention.kernel_roofline") == pytest.approx(50.0)
    assert read("moe_experts.kernel_roofline") == pytest.approx(25.0)
    assert read("moe.assignments_held_share") == pytest.approx(12.5)
    assert read("moe.max_expert_load_ratio") == pytest.approx(768 / 512)


def test_new_readers_return_nothing_where_the_program_has_no_counter_or_kernel():
    """The PR's parent, and every configuration without experts or latent
    attention: the line leaves the metric out."""
    ops = [("%fusion.1 = f32[8] fusion(%x), kind=kLoop", 1.0)]
    run = _run(ops, [{"phases": {"data": 0.1}}],
               {"tokens_per_step": 8192, "chips": 1})
    for name in ("moe.assignments_held_share", "moe.max_expert_load_ratio",
                 "mla_attention.kernel_roofline", "moe_experts.kernel_roofline"):
        assert harness.load_module(ROOT, "layer_metrics", name).read(run) is None


# ---------------------------------------------------------------------------
# the new kernels at their real shapes, for a described v5e
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


def test_latent_attention_kernels_compile_at_the_published_head_sizes(one_chip):
    """Forward and backward splash kernels at 32 heads, 4,096 positions, a
    192-wide query/key head and a 128-wide value head."""
    from fedml_tpu.parallel.transformer import splash_attention_tpu

    def loss(q, k, v):
        out = splash_attention_tpu(q, k, v, 512, 512, True, 192 ** -0.5)
        return out.astype(jnp.float32).sum()

    qk = jax.ShapeDtypeStruct((2, 4096, 32, 192), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((2, 4096, 32, 128), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(qk, qk, v).compile()
    text = compiled.as_text()
    for kind in ("_fwd", "_dq", "_dkv"):
        assert f"splash_mha{kind}" in text
    assert "tpu_custom_call" in text


def test_grouped_products_compile_to_mosaic_kernels_named_ragged_dot(one_chip):
    """``jax.lax.ragged_dot`` at the expert layer's shapes, forward and both
    gradients: XLA:TPU's own Mosaic kernels, under the name the reader of
    ``moe_experts.kernel_roofline`` looks for."""
    def loss(x, w, sizes):
        return jax.lax.ragged_dot(x, w, sizes, preferred_element_type=jnp.bfloat16
                                  ).astype(jnp.float32).sum()

    x = jax.ShapeDtypeStruct((32768, 3584), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((8, 3584, 2048), jnp.bfloat16, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, w, sizes).compile().as_text()
    calls = [line for line in text.split("\n")
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert calls and all(tr.op_name(c.strip()).startswith("ragged-dot")
                         for c in calls)
    assert any("bf16[8,3584,2048]" in c.split(" custom-call(")[0] for c in calls)
