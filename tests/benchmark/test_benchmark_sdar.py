"""The benchmark's side of the SDAR-30B-A3B configuration (ISSUE 34): its file
against the catalog row, its shape functions against hand counts and the
program's own, the job ``pretrain_bd`` end to end on the CPU at a tiny fixture
(``fixture_root_sdar``), the two new readers on synthetic runs, and the
reference alone at a small size."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark import trace_reduce as tr

ROOT = harness.ROOT
FIXTURE_ROOT = os.path.join(ROOT, "tests", "benchmark", "fixture_root_sdar")
CELL = "pretrain_sdar_ep8_bd_1chip"
with open(os.path.join(ROOT, "benchmark", "configs",
                       "sdar_30b_a3b_ep8_l6.json")) as _f:
    CONFIG = json.load(_f)
flops = harness.load_module(ROOT, "flops", "bd_gqa_moe")

# the catalog row's config (model-configs guide, architectures.jsonl,
# SDAR-30B-A3B-Chat) but the keys in ``reduced``: none may differ
PUBLISHED = dict(
    attention_bias=False, decoder_sparse_step=1, head_dim=128,
    hidden_act="silu", hidden_size=2048, intermediate_size=6144,
    max_position_embeddings=32768, max_window_layers=48, mlp_only_layers=[],
    model_type="sdar_moe", moe_intermediate_size=768, norm_topk_prob=True,
    num_attention_heads=32, num_experts_per_tok=8, num_key_value_heads=4,
    rms_norm_eps=1e-6, rope_scaling=None, rope_theta=1000000,
    sliding_window=None, tie_word_embeddings=False, use_sliding_window=False,
    vocab_size=151936)


def _job(cell):
    return harness.load_module(ROOT, "jobs", cell.job).Job(
        cell, seed=0, tracked=False, work_dir="", log=lambda s: None)


def test_configuration_keeps_the_published_numbers_and_states_its_cut():
    for key, value in PUBLISHED.items():
        assert CONFIG[key] == value, key
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    (entry,) = [c for c in spec["configs"] if c["name"] == CONFIG["name"]]
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"]) == sorted(
        ["num_hidden_layers", "num_experts", "vocab_rows_held"])
    assert entry["source"] == CONFIG["source"]
    assert CONFIG["published"] == dict(num_hidden_layers=48, num_experts=128,
                                       vocab_size=151936)
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["router_experts"], CONFIG["expert_offset"],
            CONFIG["vocab_rows_held"]) == (6, 16, 128, 0, 18992)
    assert CONFIG["vocab_rows_held"] * 8 == CONFIG["vocab_size"]
    assert CONFIG["mask_token_id"] == CONFIG["vocab_rows_held"] - 1
    assert "Eight chips share each layer" in CONFIG["deployment"]
    assert "645.6M" in CONFIG["notes"]["parameters"]
    assert "512 rows a held expert" in CONFIG["notes"]["tokens_per_expert"]
    for key in ("block_length", "noise_schedule", "no_shift", "qk_norm",
                "router", "mask_token", "rotary_pairs", "initializer",
                "initial_weights", "precision", "recipe", "data"):
        assert CONFIG["assumed"][key]
    # one update, then a rate of zero: the window runs on weights that stand
    # still (assumed.recipe says why)
    import optax
    rate = optax.warmup_cosine_decay_schedule(
        0.0, CONFIG["program"]["learning_rate"], CONFIG["program"]["warmup_steps"],
        CONFIG["schedule_total_steps"])
    assert [float(rate(t)) for t in range(4)] == pytest.approx([0, 1e-4, 0, 0])
    assert "routing_spread" in CONFIG["notes"]["tokens_per_expert"]
    for key in ("loss_abs", "logits_rel_l2", "routing_margin",
                "near_tie_share_max"):
        assert len(CONFIG["tolerances"][key + "_why"]) > 100


def test_the_parameter_count_the_file_states():
    D, H, Hkv, hd, F = 2048, 32, 4, 128, 768
    layer = (D * (H + 2 * Hkv) * hd + H * hd * D + 2 * D + 2 * hd
             + D * 128 + 16 * 3 * D * F)
    total = 6 * layer + 2 * 18992 * D + D
    assert round(layer / 1e6, 2) == 94.64 and round(total / 1e6, 1) == 645.6
    assert 7.74e9 < 12 * total < 7.76e9 and 10.32e9 < 16 * total < 10.34e9


def test_the_cell_resolves_and_builds_the_program_the_file_describes():
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.cheetah.runner import config_from_args

    cell = harness.load_cell(CELL)
    assert (cell.chips, cell.job) == (1, "pretrain_bd")
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s_per_chip", "peak_hbm_gb", "setup_s"}
    listed = {m["name"] for m in cell.per_layer}
    assert {"bd_attention.kernel_roofline", "bd_attention.kernel_device_share",
            "moe_experts.kernel_roofline", "moe.assignments_held_share",
            "moe.max_expert_load_ratio", "cheetah_step.mfu",
            "attention_kernels.device_share", "device.step_idle_share",
            "device.step_idle_attributed_share",
            "cheetah_runner.data_s_per_step",
            "cheetah_runner.launch_wake_s_per_step",
            "cheetah_runner.host_between_s_per_step",
            "cheetah_step.device_s_per_step"} <= listed
    assert not {m for m in listed if m.startswith(("mla_", "kda.", "collectives."))}
    job = _job(cell)
    assert (job.seq_len, job.batch, job.tokens_per_step()) == (4096, 1, 4096)
    cfg = config_from_args(Arguments(overrides=job.program))
    for key, arg in CONFIG["program_argument_of"].items():
        have, want = getattr(cfg, arg), CONFIG[key]
        assert (float(want) == float(have)
                if isinstance(have, (int, float)) and not isinstance(have, bool)
                else want == have), key
    assert cfg.mixers == ("gqa",) * 6 and cfg.layer_kinds == ("moe",) * 6
    assert (cfg.head_dim, cfg.n_heads * cfg.head_dim, cfg.d_model) == (
        128, 4096, 2048)
    assert cfg.attn_mask(8192) == ("block_diffusion", 4096, 4)
    assert cfg.max_seq_len == 4096 and (cfg.hc_mult, cfg.mtp_layers) == (1, 0)


def test_job_refuses_a_program_without_the_arguments(monkeypatch):
    """The PR's parent: its ``TransformerConfig`` lacks the objective, the
    head size and the q/k norms, and the job says so at once."""
    import dataclasses

    from fedml_tpu.parallel import transformer

    @dataclasses.dataclass(frozen=True)
    class Old:
        vocab_size: int = 0
        d_model: int = 0

    monkeypatch.setattr(transformer, "TransformerConfig", Old)
    cell = harness.load_cell(CELL)
    with pytest.raises(RuntimeError, match="cannot build sdar_30b_a3b_ep8_l6"):
        _job(cell)


def test_job_refuses_a_file_whose_key_and_argument_disagree():
    cell = harness.load_cell(CELL)
    cell.config = dict(cell.config, head_dim=64)
    with pytest.raises(ValueError, match="head_dim is 64 but the program's "
                                         "argument attn_head_dim is 128"):
        _job(cell)


def test_flops_by_hand_and_the_programs_gauge_agrees():
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.cheetah.runner import config_from_args
    from fedml_tpu.parallel.transformer import train_flops_per_token

    D, H, Hkv, hd, L, B = 2048, 32, 4, 128, 4096, 4
    row = (2 * D * hd * (2 * H + 2 * Hkv)               # q, k, v, o
           + 2 * D * 128 + (8 * 16 / 128) * 6 * D * 768)  # router, 1 expert
    attention = 2 * 2 * H * hd * (L * L + L * B) / L
    forward = 6 * (2 * row + attention) + 2 * D * 18992
    assert flops.train_flops_per_token(CONFIG, L) == pytest.approx(3 * forward)
    assert 1.04e9 < forward < 1.06e9       # 3.16 GFLOP a data token trained
    assert 0.40 < 6 * attention / (forward - 2 * D * 18992) < 0.42
    assert flops.mask_pairs(L, B) / (2 * L) ** 2 == pytest.approx(0.25, abs=3e-4)
    cfg = config_from_args(Arguments(overrides=_job(harness.load_cell(CELL)).program))
    assert train_flops_per_token(cfg, L) == pytest.approx(
        flops.train_flops_per_token(CONFIG, L), rel=1e-12)


def test_kernel_operations_and_bytes():
    peaks = harness.peaks_for("TPU v5 lite")
    per_call = flops.attention_kernel_flops(CONFIG, 4096, 1)
    pairs = 32 * (4096 * 4096 + 4096 * 4)
    assert per_call == {"fwd": 2 * pairs * 256, "dq": 2 * pairs * 384,
                        "dkv": 2 * pairs * 512}
    assert flops.attention_kernel_flops(CONFIG, 4096, 2)["fwd"] == \
        2 * per_call["fwd"]
    seconds, bound = flops.grouped_product_least_seconds(
        8192, 16, 2048, 1536, peaks)
    assert bound == "flops" and seconds == pytest.approx(
        2 * 8192 * 2048 * 1536 / 197e12)
    seconds, bound = flops.grouped_product_least_seconds(
        512, 16, 2048, 1536, peaks)
    assert bound == "bytes" and seconds == pytest.approx(
        2 * (16 * 2048 * 1536 + 512 * (2048 + 1536)) / 819e9)


def test_the_masked_share_band():
    band = harness.load_module(ROOT, "jobs", "pretrain_bd").masked_share_band
    mean, half = band(4096, 4, 1e-3, 5.0)
    assert mean == pytest.approx(0.5005)
    # a block of 4: 4 E[t(1 - t)] + 16 Var(t) = 0.667 + 1.331; 1,024 blocks
    assert half == pytest.approx(5 * np.sqrt(1024 * 1.998) / 4096, rel=1e-3)
    rng = np.random.default_rng(0)
    t = rng.uniform(1e-3, 1, (4000, 1024, 1))
    shares = (rng.uniform(size=(4000, 1024, 4)) < t).mean((1, 2))
    assert abs(shares.std() - half / 5) < 0.05 * half / 5
    assert (abs(shares - mean) < half).all()


def test_the_rate_is_the_harnesses_own():
    """``tokens_per_s_per_chip`` means here what it means in every LM cell:
    the step's data tokens over the median step program on the device's
    clock. The job brings no rate of its own."""
    import types

    module = harness.load_module(ROOT, "jobs", "pretrain_bd")
    assert "throughput" not in vars(module.Job)
    job = _job(harness.load_cell(CELL))
    durations = np.array([0.47, 0.50, 0.48, 0.51, 0.49, 0.52, 0.49, 0.49])
    starts = np.cumsum(np.r_[0.0, durations[:-1]]) + 0.01 * np.arange(8)
    modules = tr.Events(["jit__train_step_raw(7)"], np.zeros(8, int), starts,
                        starts + durations)
    trace = tr.Trace([tr.DeviceTrace(0, modules, tr.EMPTY, tr.EMPTY)], None)
    job._periods = durations + 0.01
    rates = job.throughput(8, float(job._periods.sum()), trace)
    assert rates["tokens_per_s_per_chip"] == pytest.approx(4096 / 0.49)


def _draw(kind, L=4096, B=4, mask_token=18991, t_min=1e-3, rows=2):
    """A draw of the objective's noise made with numpy, sound or with one
    fault."""
    rng = np.random.default_rng(7)
    x_0 = rng.integers(0, 90, (rows, L))
    lo = 0.5 if kind == "t_clipped" else t_min
    t = np.repeat(rng.uniform(lo, 1, (rows, L // B)), B, -1)
    if kind == "one_t_a_token":
        t = rng.uniform(t_min, 1, (rows, L))
    masked = rng.uniform(size=(rows, L)) < t
    x_t = np.where(masked, mask_token, x_0)
    weight = 1.0 / t
    if kind == "weight_of_the_next_block":
        weight = np.roll(weight, -B, -1)
    if kind == "mask_token_where_not_masked":
        x_t[0, np.flatnonzero(~masked[0])[:3]] = mask_token
    if kind == "token_kept_where_masked":
        x_t[0, np.flatnonzero(masked[0])[:3]] = x_0[0, np.flatnonzero(masked[0])[:3]]
    if kind == "data_holds_the_mask_token":
        x_0[1, 5] = mask_token
        x_t[1, 5] = mask_token
    if kind == "weight_under_one":
        weight = weight * 0.5
    return x_0, x_t, masked, weight.astype(np.float32)


@pytest.mark.parametrize("kind, says", [
    ("sound", None),
    ("one_t_a_token", "one value a block"),
    ("weight_of_the_next_block", "masked counts"),
    ("t_clipped", "of t is"),
    ("mask_token_where_not_masked", "x_t is not"),
    ("token_kept_where_masked", "x_t is not"),
    ("data_holds_the_mask_token", "data holds"),
    ("weight_under_one", "outside [1,"),
])
def test_the_job_holds_the_draw_to_the_objective(kind, says):
    faults = harness.load_module(ROOT, "jobs", "pretrain_bd").draw_faults(
        *_draw(kind), 4, 18991, 1e-3, 5.0)
    if says is None:
        assert faults == []
    else:
        assert any(says in fault for fault in faults), faults


def test_the_programs_draw_is_sound_at_the_timed_shape():
    import jax
    from fedml_tpu.parallel import block_diffusion as bd

    faults = harness.load_module(ROOT, "jobs", "pretrain_bd").draw_faults
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 4096), 0, 90)
    for step in (0, 1, 7):
        x_t, masked, weight = bd.noise(bd.step_key(step), tokens, 4, 18991)
        assert faults(tokens, x_t, masked, weight, 4, 18991, bd.T_MIN, 5.0) == []
    assert float(CONFIG["t_min"]) == bd.T_MIN
    wrong = harness.load_cell(CELL)
    wrong.config = dict(wrong.config, t_min=0.01)
    with pytest.raises(ValueError, match="lowest noise level"):
        _job(wrong)


def test_the_steps_start_from_the_scaled_table_and_the_reference_from_the_draw():
    """The job scales the embedding table of the state the loop starts from
    (inside the stamped init, so before the window opens) and hands the
    reference check the program's own draw."""
    import dataclasses
    import types

    @dataclasses.dataclass
    class State:
        params: dict

        def replace(self, **kw):
            return dataclasses.replace(self, **kw)

    cell = harness.load_cell(CELL)
    scale = float(cell.config["initial_embedding_scale"])
    assert scale > 1
    job = _job(cell)
    job._window = None
    keys = []
    job.trainer = types.SimpleNamespace(
        init_state=lambda rng: keys.append(rng) or State(
            {"embed": np.full(3, 0.02), "w": np.ones(2)}),
        train_step=lambda state, tokens, mask: (state, {
            "loss": 1.0, "moe_dropped": 0, "bd_masked_tokens": 2, "bd_weight_sum": 3.0,
            "moe_max_expert_load": 5}))
    job._instrument()
    job._step_started, job._step_losses, job._clock_from = [], [], None
    started = job.trainer.init_state(None)
    assert started.params["embed"] == pytest.approx(0.02 * scale)
    assert (started.params["w"] == 1).all()
    # one draw for every --seed in the window; the reference's by --seed
    import jax
    assert (keys[-1] == jax.random.PRNGKey(cell.config["window_weights_seed"])).all()
    drawn, _ = job._uninstrumented
    assert drawn("by --seed").params["embed"] == pytest.approx(0.02)
    assert keys[-1] == "by --seed"
    job.trainer.train_step(started, None, None)
    assert (job._step_max_load, job._step_masked, job._step_weight) == (
        [5], [2], [3.0])


# ---------------------------------------------------------------------------
# the job end to end on the CPU, and the readers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_run_cell_on_the_tiny_fixture(trace, tmp_path):
    cell = harness.load_cell("tiny_pretrain_sdar", root=FIXTURE_ROOT)
    logged = []
    result = harness.run_cell(cell, seed=2**31 + 5, seconds=1.0, trace=trace,
                              work_dir=str(tmp_path), log=logged.append)
    assert result["correct"] is True and result["failed"] == 0
    assert any("near-tie share" in line and "tokens masked" in line
               for line in logged)
    assert any("masked share a step" in line for line in logged)
    got = set(result["metrics"])
    if not trace:
        assert got == {"tokens_per_s_per_chip", "peak_hbm_gb", "setup_s"}
        return
    assert {"moe.assignments_held_share", "moe.max_expert_load_ratio",
            "entry.compile_s", "cheetah_runner.data_s_per_step"} <= got
    # the device trace's readers find no TPU plane on this CPU
    assert not {"bd_attention.kernel_roofline", "cheetah_step.mfu",
                "bd_attention.kernel_device_share"} & got
    share = result["metrics"]["moe.assignments_held_share"]["value"]
    assert 10 < share < 50           # 4 of 16 experts held: 25% if balanced
    # the RoundRecords carry the draw's counters beside the routing's
    with open(os.path.join(str(tmp_path), "runs",
                           f"run_{cell.name}_seed{2**31 + 5}_edge_0.jsonl")) as f:
        events = [json.loads(line) for line in f]
    (init,) = [e for e in events if e["kind"] == "cheetah_init"][-1:]
    assert (init["objective"], init["bd_block"], init["head_dim"],
            init["attn_mask"]["kind"]) == ("block_diffusion", 4, 32,
                                           "block_diffusion")
    record = [e for e in events if e["kind"] == "round_record"][-1]
    assert 0 < record["counters"]["bd_masked_tokens"] < 8 * 128
    assert record["counters"]["bd_weight_sum"] > 0


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


def _run(ops, facts, cell=CELL):
    dev = tr.DeviceTrace(0, tr.EMPTY, _events(ops), tr.EMPTY)
    return harness.TracedRun(
        cell=harness.load_cell(cell), facts=facts, records=[], counters={},
        peaks=harness.peaks_for("TPU v5 lite"), trace=tr.Trace([dev], None))


KERNEL = ('%{name} = (bf16[1,4,8,8192,128]{{4,3,2,1,0}}, f32[8]) '
          'custom-call(%a, %b), custom_call_target="tpu_custom_call"')
FACTS = dict(seq_len=4096, sequences_per_step_per_chip=1)


def _read(name, run):
    return harness.load_module(ROOT, "layer_metrics", name).read(run)


def test_bd_readers_find_the_splash_calls_and_no_others():
    per_call = flops.attention_kernel_flops(CONFIG, 4096, 1)
    least = {k: v / 197e12 for k, v in per_call.items()}
    ops = [(KERNEL.format(name="splash_mqa_fwd_residuals.3"), least["fwd"] / 0.5),
           (KERNEL.format(name="splash_mqa_fwd_residuals.7"), least["fwd"] / 0.5),
           (KERNEL.format(name="splash_mqa_dq_no_residuals.4"), least["dq"] / 0.25),
           (KERNEL.format(name="splash_mqa_dkv_no_residuals.5"), least["dkv"] / 0.25),
           (KERNEL.format(name="ragged-dot-none.1"), 1.0),
           ("%fusion.1 = f32[8] fusion(%x), kind=kLoop", 2.0)]
    run = _run(ops, FACTS)
    spent = 2 * least["fwd"] / 0.5 + (least["dq"] + least["dkv"]) / 0.25
    assert _read("bd_attention.kernel_roofline", run) == pytest.approx(
        100 * (2 * least["fwd"] + least["dq"] + least["dkv"]) / spent)
    assert 0 < _read("bd_attention.kernel_roofline", run) < 100
    assert _read("bd_attention.kernel_device_share", run) == pytest.approx(
        100 * spent / (spent + 3.0))


def test_bd_readers_return_nothing_where_there_is_nothing_to_read():
    """A next-token configuration (no ``block_length``), a step without the
    kernels, and a run without a device trace: the line leaves the metrics
    out."""
    splash = [(KERNEL.format(name="splash_mqa_fwd_residuals.1"), 1.0)]
    names = ("bd_attention.kernel_roofline", "bd_attention.kernel_device_share")
    other = _run(splash, FACTS, cell="pretrain_xing4_ep8_1chip")
    none = _run([("%fusion.1 = f32[8] fusion(%x), kind=kLoop", 1.0)], FACTS)
    untraced = _run(splash, FACTS)
    untraced.trace = None
    for run in (other, none, untraced):
        for name in names:
            assert _read(name, run) is None
    assert _read(names[0], _run(splash, {"tokens_per_step": 4096})) is None


def test_the_reference_at_a_small_size():
    """The reference alone, no program: its mask is the three clauses, a
    noised row's output ignores its own clean block, the gates of the chosen
    sum to 1, and the loss weighs the masked positions alone."""
    ref = harness.load_module(ROOT, "reference", "sdar")
    rows = jnp.arange(16)
    see = np.asarray(ref.block_diffusion_mask(rows, rows, 8, 4))
    want = np.kron(np.array([[1, 0, 0, 0], [0, 1, 1, 0],
                             [0, 0, 1, 0], [0, 0, 1, 1]]), np.ones((4, 4)))
    assert (see == want.astype(bool)).all()
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(16, 2, 8)), jnp.float32)
               for _ in range(3))
    out = ref.masked_attention(q, k, v, 8 ** -0.5, 8, 4)
    moved = ref.masked_attention(q, k.at[12:].add(1.0), v.at[12:].add(1.0),
                                 8 ** -0.5, 8, 4)
    # clean block 1 (rows 12 to 15) is seen by itself alone
    assert np.allclose(out[:12], moved[:12]) and not np.allclose(out[12:], moved[12:])
    config = dict(num_experts_per_tok=2, expert_offset=2, num_experts=2,
                  router_experts=8)
    p = {"router": jnp.asarray(rng.normal(size=(4, 8)), jnp.float32)}
    x = jnp.asarray(rng.normal(size=(32, 4)), jnp.float32)
    selected, gates, margin, counts, prob_sum = ref.route(p, x, config)
    assert np.allclose(gates.sum(-1), 1.0) and selected.shape == (32, 2)
    assert float(counts.sum()) == 64 and float(prob_sum.sum()) == pytest.approx(32)
    assert (np.asarray(margin) > 0).all()
    balanced = ref.aux_loss(jnp.full((8,), 8.0), jnp.full((8,), 4.0), 32, config)
    assert float(balanced) == pytest.approx(1.0)
