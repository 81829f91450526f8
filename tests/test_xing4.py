"""Latent attention, the sigmoid-routed expert layer that holds a share of the
experts, hyper-connected residual streams and multi-token prediction (ISSUE
28), at a small size on XLA:CPU: the program against the plain reference
(``benchmark/reference/xing4.py``) on seeded weights, the shares of the expert
layer against the whole, the invariants of routing and of the residual maps,
and the step programs that must still lower, Mistral's unchanged."""

from __future__ import annotations

import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from fedml_tpu.parallel import transformer as tfm
from fedml_tpu.parallel.moe import MoEFeedForward
from fedml_tpu.parallel.sharding import make_mesh, unbox
from fedml_tpu.parallel.train_step import CheetahTrainer
from fedml_tpu.parallel.transformer import Transformer, TransformerConfig

ref = harness.load_module(harness.ROOT, "reference", "xing4")


def xing_tiny(**kw) -> TransformerConfig:
    """Xing4.0's block at width 64: 1 dense + 2 expert layers, 16 routed
    experts of which experts 4 to 7 are held, 4 a token, 4 streams, MTP."""
    base = dict(
        vocab_size=96, d_model=64, n_layers=3, n_heads=4, n_kv_heads=4,
        d_ff=160, max_seq_len=128, remat=False, attn_impl="xla",
        norm_eps=1e-6, dtype=jnp.float32, attn_kind="mla", q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rope_factor=64.0, rope_original_max_pos=32,
        rope_mscale=1.0, rope_mscale_all_dim=1.0, first_k_dense=1,
        moe_experts=16, moe_top_k=4, moe_capacity_factor=0.0,
        moe_router="sigmoid", moe_routed_scale=2.0, moe_d_ff=32,
        moe_shared_experts=1, moe_experts_held=4, moe_expert_offset=4,
        hc_mult=4, hc_sinkhorn_iters=20, mtp_layers=1)
    base.update(kw)
    return TransformerConfig(**base)


def reference_config(cfg: TransformerConfig) -> dict:
    """``cfg`` under the published keys the reference reads."""
    return dict(
        hidden_size=cfg.d_model, num_attention_heads=cfg.n_heads,
        q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        rms_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
        rope_scaling=dict(
            factor=cfg.rope_factor,
            original_max_position_embeddings=cfg.rope_original_max_pos,
            beta_fast=cfg.rope_beta_fast, beta_slow=cfg.rope_beta_slow,
            mscale=cfg.rope_mscale, mscale_all_dim=cfg.rope_mscale_all_dim),
        num_experts_per_tok=cfg.moe_top_k, n_routed_experts=cfg.experts_held,
        expert_offset=cfg.moe_expert_offset, router_experts=cfg.moe_experts,
        routed_scaling_factor=cfg.moe_routed_scale, hc_mult=cfg.hc_mult,
        hc_eps=cfg.hc_eps, hc_sinkhorn_iters=cfg.hc_sinkhorn_iters,
        mhc_h_res_clamp_min=-cfg.hc_clamp, mhc_h_res_clamp_max=cfg.hc_clamp)


def _name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def seeded(cfg: TransformerConfig, tokens, moved: bool = True):
    """The model's initial variables; ``moved`` takes the hyper-connections'
    gains to 1 and perturbs every bias, norm weight and selection bias, so
    that what is inert at initialisation (gains of 0.01, streams that are
    copies of each other, a zero selection bias) is checked too."""
    variables = Transformer(cfg).init(jax.random.PRNGKey(0), tokens)
    params = unbox(variables["params"])
    state = {"router_state": unbox(variables["router_state"])}
    if not moved:
        return params, state

    def move(path, p):
        name = _name(path)
        key = jax.random.PRNGKey(sum(map(ord, name)))
        if name.endswith("/a"):
            return jnp.ones_like(p)
        if name.endswith("/b") or "norm" in name.lower():
            return p + 0.3 * jax.random.normal(key, p.shape)
        return p

    params = jax.tree_util.tree_map_with_path(move, params)
    state = jax.tree.map(
        lambda b: 0.2 * jax.random.normal(jax.random.PRNGKey(7), b.shape), state)
    return params, state


TOKENS = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 96)


# ---------------------------------------------------------------------------
# the program against the reference
# ---------------------------------------------------------------------------

# float32 on both sides: only the order of float32 sums differs (the program
# folds the stream norm's weight into the maps and sorts assignments by
# expert), so 1e-5; both sides choose the same experts because no margin of
# these seeds lies under 1e-5.
@pytest.mark.parametrize("mtp", [0, 1], ids=["no_mtp", "mtp"])
def test_logits_and_mtp_hidden_agree_with_the_reference(mtp):
    cfg = xing_tiny(mtp_layers=mtp)
    config = reference_config(cfg)
    params, state = seeded(cfg, TOKENS)
    logits, mtp_hidden = Transformer(cfg).apply(
        {"params": params, "router_state": state["router_state"]}, TOKENS,
        return_mtp=True)
    plain = ref.reference_params(params, config, state["router_state"])
    assert ("mtp" in plain) == bool(mtp)
    for row in range(2):
        want, _, _, margin = ref.logits_and_losses(plain, TOKENS[row], config)
        assert float(margin.min()) > 1e-5
        err = jnp.linalg.norm(logits[row] - want) / jnp.linalg.norm(want)
        assert float(err) < 1e-5
        if mtp:
            _, want_h, _ = ref.hidden_states(plain, TOKENS[row], config)
            err = (jnp.linalg.norm(mtp_hidden[row, :-1] - want_h[:-1])
                   / jnp.linalg.norm(want_h[:-1]))
            assert float(err) < 1e-5
        else:
            assert mtp_hidden is None


# The step's own loss (chunked cross entropy, MTP term through the same head,
# weight 0.3) and its gradient, every leaf, against jax.grad of the
# reference's loss in the reference's layout: 1e-4 relative to the largest
# leaf-wise norm, float32 both sides. "fused": the streams' one-pass backward
# that a TPU takes (ISSUE 29), its kernels' bodies under Pallas' interpreter,
# at width 128 because the kernels work on whole lanes.
@pytest.mark.parametrize("mhc_backward", ["xla", "fused"])
@pytest.mark.parametrize("mtp", [0, 1], ids=["no_mtp", "mtp"])
def test_loss_and_gradients_agree_with_the_reference(mtp, mhc_backward,
                                                     request):
    cfg, kernels = xing_tiny(mtp_layers=mtp), []
    if mhc_backward == "fused":
        cfg = dataclasses.replace(cfg, d_model=128)
        kernels = request.getfixturevalue("fused_mhc_backward")
    config = reference_config(cfg)
    params, state = seeded(cfg, TOKENS)
    trainer = CheetahTrainer(cfg, make_mesh(None, devices=jax.devices()[:1]),
                             loss_chunk=16)
    mask = jnp.ones_like(TOKENS)
    (got, _), got_grads = jax.value_and_grad(trainer._loss_fn, has_aux=True)(
        params, state, TOKENS, mask)
    # a read and a write in each sublayer of every block, or none
    assert len(kernels) == (4 * (cfg.n_layers + mtp)
                            if mhc_backward == "fused" else 0)

    def reference_loss(plain):
        main = mtp_sum = 0.0
        for row in range(2):
            _, m, t, _ = ref.logits_and_losses(plain, TOKENS[row], config)
            main, mtp_sum = main + m, mtp_sum + t
        loss = main / (2 * 63)
        return loss + (cfg.mtp_weight * mtp_sum / (2 * 62) if mtp else 0.0)

    plain = ref.reference_params(params, config, state["router_state"])
    want, want_grads = jax.value_and_grad(reference_loss)(plain)
    assert abs(float(got) - float(want)) < 1e-5 * float(want)
    # the re-layout is linear (slices and reshapes), so it maps gradients too
    got_plain = ref.reference_params(got_grads, config)
    flat_got = jax.tree_util.tree_leaves_with_path(got_plain)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    scale = max(float(jnp.linalg.norm(g)) for g in flat_want.values())
    checked = 0
    for path, g in flat_got:
        if _name(path).endswith("bias"):
            continue  # the selection bias: state, no gradient
        err = float(jnp.linalg.norm(g - flat_want[path]))
        assert err < 1e-4 * scale, (_name(path), err, scale)
        checked += 1
    assert checked > 60


def test_bfloat16_stays_close_and_float8_does_not():
    """The benchmark's kind of tolerance at this width: the program in
    bfloat16 (its default) against the float32 reference over the positions
    whose routing margin is clear, and the reference with every product's
    inputs rounded to float8 (the nearest precision below) against itself."""
    cfg = xing_tiny(mtp_layers=0, dtype=jnp.bfloat16)
    config = reference_config(cfg)
    params, state = seeded(cfg, TOKENS, moved=False)
    plain = ref.reference_params(params, config, state["router_state"])
    want, _, _, margin = ref.logits_and_losses(plain, TOKENS[0], config)
    clear = np.asarray(margin) >= 0.003
    assert clear.mean() > 0.5

    def err(got):
        d = (np.asarray(got) - np.asarray(want))[clear]
        return float(np.linalg.norm(d) / np.linalg.norm(np.asarray(want)[clear]))

    got = Transformer(cfg).apply({"params": params, **state}, TOKENS)[0]
    assert err(got) < 2e-2
    ref.MATMUL_INPUT_DTYPE = jnp.float8_e4m3fn
    try:
        low, _, _, _ = ref.logits_and_losses(plain, TOKENS[0], config)
    finally:
        ref.MATMUL_INPUT_DTYPE = None
    assert err(low) > 3 * err(got)


@pytest.mark.parametrize("mistake", [
    "no_shared_expert", "unscaled_gate", "unrotated_k_rope", "no_sinkhorn",
    "rows_only_sinkhorn", "plain_rope", "no_selection_bias"])
def test_reference_is_sensitive_to_what_it_checks(mistake, monkeypatch):
    """Each of these mistakes moves the reference's logits by at least ten
    times what the float32 agreement above allows (1e-5), so the comparison
    would catch the program making it."""
    cfg = xing_tiny(mtp_layers=0)
    config = reference_config(cfg)
    params, state = seeded(cfg, TOKENS)
    plain = ref.reference_params(params, config, state["router_state"])
    want, _, _, _ = ref.logits_and_losses(plain, TOKENS[0], config)
    if mistake == "no_shared_expert":
        for layer in plain["layers"]:
            if "moe" in layer:
                layer["moe"]["shared"]["w_down"] *= 0
    elif mistake == "unscaled_gate":
        config = dict(config, routed_scaling_factor=1.0)
    elif mistake == "plain_rope":
        config = dict(config, rope_scaling=dict(config["rope_scaling"], factor=1.0))
    elif mistake == "no_selection_bias":
        for layer in plain["layers"]:
            if "moe" in layer:
                layer["moe"]["bias"] *= 0
    elif mistake == "unrotated_k_rope":
        rotary = ref.rotary
        monkeypatch.setattr(ref, "rotary", lambda x, f, s: (
            x if x.shape[1] == 1 else rotary(x, f, s)))
    elif mistake == "no_sinkhorn":
        monkeypatch.setattr(ref, "sinkhorn", lambda logits, config: jnp.exp(
            jnp.clip(logits, -30, 30)))
    elif mistake == "rows_only_sinkhorn":
        def rows_only(logits, config):
            m = jnp.exp(jnp.clip(logits, -30, 30))
            return m / m.sum(-1, keepdims=True)
        monkeypatch.setattr(ref, "sinkhorn", rows_only)
    got, _, _, _ = ref.logits_and_losses(plain, TOKENS[0], config)
    err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert err > 1e-4, err


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------


def _layer_params(cfg, x, key=3):
    variables = MoEFeedForward(cfg).init(jax.random.PRNGKey(key), x)
    return unbox(variables["params"])


def test_the_shares_of_an_expert_layer_add_up_to_the_whole():
    """Four chips' shares of a 16-expert layer (each holds 4, routes over all
    16), the shared expert counted once, equal the layer that holds every
    expert, and that equals the uncut reference's layer."""
    whole_cfg = xing_tiny(moe_experts_held=0, moe_expert_offset=0)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 64), jnp.float32)
    whole = _layer_params(whole_cfg, x)
    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(5), (16,))
    state = {"router_state": {"bias": bias}}
    y_whole, _ = MoEFeedForward(whole_cfg).apply({"params": whole, **state}, x)
    shared = tfm.FeedForward(whole_cfg, d_ff=32).apply(
        {"params": whole["shared"]}, x)

    total = jnp.zeros_like(y_whole)
    for share in range(4):
        cfg = xing_tiny(moe_experts_held=4, moe_expert_offset=4 * share)
        part = dict(whole, w_gate_up=whole["w_gate_up"][4 * share:4 * share + 4],
                    w_down=whole["w_down"][4 * share:4 * share + 4])
        y, _ = MoEFeedForward(cfg).apply({"params": part, **state}, x)
        total = total + (y - shared)
    assert float(jnp.abs(total + shared - y_whole).max()) < 1e-5

    config = dict(reference_config(whole_cfg), n_routed_experts=16, expert_offset=0)
    half = whole["w_gate_up"].shape[-1] // 2
    plain = {"router": whole["w_router"], "bias": bias,
             "shared": {"w_gate": whole["shared"]["w_gate_up"][:, :32],
                        "w_up": whole["shared"]["w_gate_up"][:, 32:],
                        "w_down": whole["shared"]["w_down"]},
             "experts": {"w_gate": whole["w_gate_up"][..., :half],
                         "w_up": whole["w_gate_up"][..., half:],
                         "w_down": whole["w_down"]}}
    for row in range(2):
        want, _ = ref.expert_layer(plain, x[row], config)
        assert float(jnp.abs(y_whole[row] - want).max()) < 1e-5


def test_no_assignment_is_dropped_when_the_router_is_forced_onto_one_expert():
    """Every token's first choice is expert 5 (held): it gets all T tokens, no
    capacity cuts it, and the layer's output is the dense sum."""
    cfg = xing_tiny()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 64), jnp.float32)
    params = _layer_params(cfg, x)
    bias = jnp.zeros((16,)).at[5].set(10.0)
    (y, _), sown = MoEFeedForward(cfg).apply(
        {"params": params, "router_state": {"bias": bias}}, x,
        mutable=["moe_stats"])
    load = sown["moe_stats"]["load"][0]
    assert int(load[5]) == 64 and int(load.sum()) == 4 * 64
    assert int(sown["moe_stats"]["dropped"][0]) == 0
    config = reference_config(cfg)
    half = params["w_gate_up"].shape[-1] // 2
    plain = {"router": params["w_router"], "bias": bias,
             "shared": {"w_gate": params["shared"]["w_gate_up"][:, :32],
                        "w_up": params["shared"]["w_gate_up"][:, 32:],
                        "w_down": params["shared"]["w_down"]},
             "experts": {"w_gate": params["w_gate_up"][..., :half],
                         "w_up": params["w_gate_up"][..., half:],
                         "w_down": params["w_down"]}}
    for row in range(2):
        want, _ = ref.expert_layer(plain, x[row], config)
        assert float(jnp.abs(y[row] - want).max()) < 1e-5


def test_capacity_rule_drops_and_counts_under_the_sigmoid_router():
    """The two rules are one layer: the sigmoid router under a capacity
    factor drops what exceeds the slots and says how many."""
    cfg = xing_tiny(moe_capacity_factor=0.5)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 64), jnp.float32)
    params = _layer_params(cfg, x)
    bias = jnp.zeros((16,)).at[5].set(10.0)
    (y, _), sown = MoEFeedForward(cfg).apply(
        {"params": params, "router_state": {"bias": bias}}, x,
        mutable=["moe_stats"])
    capacity = int(0.5 * 4 * 64 / 16)
    assert int(sown["moe_stats"]["dropped"][0]) >= 64 - capacity
    assert bool(jnp.isfinite(y).all())


def test_selection_bias_moves_against_the_load_and_outside_the_optimizer():
    cfg = xing_tiny(mtp_layers=0)
    trainer = CheetahTrainer(cfg, make_mesh(None, devices=jax.devices()[:1]))
    state = trainer.init_state(jax.random.PRNGKey(0))
    before = jax.tree.map(np.asarray, state.model_state)
    # no optimizer state mirrors the bias: it is no parameter
    assert not any("bias" in _name(p) for p, _ in
                   jax.tree_util.tree_leaves_with_path(state.opt_state))
    variables = {"params": state.params, **state.model_state}
    _, sown = trainer.model.apply(variables, TOKENS, mutable=["moe_stats"])
    state, metrics = trainer.train_step(state, TOKENS, jnp.ones_like(TOKENS))
    assert int(metrics["moe_dropped"]) == 0
    for block in ("Block_1", "Block_2"):
        load = np.asarray(sown["moe_stats"][block]["MoEFeedForward_0"]["load"][0])
        moved = (np.asarray(state.model_state["router_state"][block]
                            ["MoEFeedForward_0"]["bias"])
                 - before["router_state"][block]["MoEFeedForward_0"]["bias"])
        np.testing.assert_allclose(
            moved, cfg.moe_bias_rate * np.sign(load.mean() - load), atol=1e-9)
        assert (moved[load > load.mean()] < 0).all()
        assert (moved[load < load.mean()] > 0).all()
    held = sum(int(np.asarray(sown["moe_stats"][b]["MoEFeedForward_0"]["load"][0])
                   [4:8].sum()) for b in ("Block_1", "Block_2"))
    assert int(metrics["moe_assignments_held"]) == held


# ---------------------------------------------------------------------------
# hyper-connections and positions
# ---------------------------------------------------------------------------


def test_residual_map_is_doubly_stochastic():
    """To 1e-5 around the bias the maps start from, with the dynamic part the
    0.01 gains give (0.024 in standard deviation at the real width) and four
    times that. Sinkhorn contracts slowly near a permutation, which is why
    the bias starts at 2 I and not nearer the identity: from 5 I twenty
    sweeps leave the rows 7e-4 off (the columns are normalised last and are
    exact either way)."""
    noise = jax.random.normal(jax.random.PRNGKey(4), (2, 16, 4, 4))
    for scale in (0.024, 0.1):
        m = tfm.sinkhorn(tfm.HC_RES_INIT * jnp.eye(4) + scale * noise,
                         20, 1e-6, 30.0)
        assert float(jnp.abs(m.sum(-1) - 1).max()) < 1e-5
        assert float(jnp.abs(m.sum(-2) - 1).max()) < 1e-5
        assert float(m.min()) >= 0
    near_identity = tfm.sinkhorn(5.0 * jnp.eye(4) + 0.024 * noise, 20, 1e-6, 30.0)
    assert float(jnp.abs(near_identity.sum(-2) - 1).max()) < 1e-5
    assert float(jnp.abs(near_identity.sum(-1) - 1).max()) > 1e-4
    # the clamp bounds what exp sees
    big = tfm.sinkhorn(jnp.full((1, 1, 4, 4), 1e4), 20, 1e-6, 30.0)
    assert bool(jnp.isfinite(big).all())
    logits = tfm.HC_RES_INIT * jnp.eye(4) + noise
    want = ref.sinkhorn(logits[0], dict(
        hc_eps=1e-6, hc_sinkhorn_iters=20, mhc_h_res_clamp_min=-30,
        mhc_h_res_clamp_max=30))
    got = tfm.sinkhorn(logits, 20, 1e-6, 30.0)
    assert float(jnp.abs(got[0] - want).max()) < 1e-6


def test_hyper_connection_starts_as_a_plain_residual_over_equal_streams():
    """At initialisation the sublayer reads the streams' mean (H_pre 1/4) and
    writes to every stream alike (H_post 1), up to the 0.01 gains."""
    cfg = xing_tiny()
    X = jnp.broadcast_to(jax.random.normal(jax.random.PRNGKey(3), (2, 1, 8, 64)),
                         (2, 4, 8, 64))
    module = tfm.HyperConnection(cfg)
    variables = module.init(jax.random.PRNGKey(0), X)
    pre, post, res = module.apply(variables, X)
    assert float(jnp.abs(pre - 0.25).max()) < 0.02
    assert float(jnp.abs(post - 1.0).max()) < 0.05
    # each stream keeps most of itself; rows and columns sum to 1
    assert float(jnp.diagonal(res, axis1=-2, axis2=-1).min()) > 0.65
    assert float(jnp.abs(res.sum(-1) - 1).max()) < 1e-5


def test_yarn_frequencies():
    """Dimensions that turn often keep their frequency, those that turn
    seldom have it divided by the factor, a ramp between; the reference
    computes the same; the score scale carries mscale squared."""
    inv = tfm.yarn_inv_freq(64, 10000.0, 64.0, 4096, 32.0, 1.0)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    assert inv[0] == pytest.approx(plain[0]) and inv[-1] == pytest.approx(plain[-1] / 64)
    assert (np.diff(inv / plain) <= 1e-6).all()
    want = ref.yarn_inv_freq(64, 10000.0, dict(
        factor=64, original_max_position_embeddings=4096, beta_fast=32, beta_slow=1))
    np.testing.assert_allclose(inv, np.asarray(want), rtol=1e-6)
    cfg = xing_tiny(qk_nope_head_dim=128, qk_rope_head_dim=64)
    m = 0.1 * np.log(64.0) + 1.0
    assert tfm.attention_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    assert tfm.attention_scale(TransformerConfig.tiny()) is None


# ---------------------------------------------------------------------------
# step programs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [{"fsdp": 4}, {"fsdp": 2, "tensor": 2}],
                         ids=["fsdp4", "fsdp2_tensor2"])
def test_step_lowers_on_a_four_device_mesh(shape):
    cfg = xing_tiny(dtype=jnp.bfloat16, remat=True, n_layers=2,
                    hc_sinkhorn_iters=2)
    trainer = CheetahTrainer(cfg, make_mesh(shape, devices=jax.devices()[:4]))
    state = trainer.init_state(jax.random.PRNGKey(0))
    tokens = jnp.zeros((8, 64), jnp.int32)
    text = trainer.lower_step(state, tokens, jnp.ones_like(tokens)).as_text()
    assert "mhlo.num_partitions = 4" in text and "sdy.sharding" in text
    # every new parameter carries logical axis names: none is left whole on
    # every device but norms, biases, gains and the router's 16 columns
    for path, s in jax.tree_util.tree_leaves_with_path(trainer.param_shardings):
        leaf = _name(path).rsplit("/", 1)[-1]
        if leaf in ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "w", "w_gate_up",
                    "w_down", "w_eh", "embed", "w_lm_head", "w_router"):
            assert any(axis is not None for axis in s.spec), _name(path)


# sha256 of ``lower_step(..).as_text()`` at TransformerConfig.tiny() on the
# commit before this file existed (8398b4f, PR 27), normalised: a
# configuration without the new mechanisms must lower to the program it
# lowered to then. On the four-device meshes the raw texts are equal byte for
# byte. On one device two things differ, neither an op: Adam's two counters
# now arrive under the mesh's sharding like every other argument (PR 28:
# ``_commit_replicated``; they used to come bare, which made the second step
# compile again), which puts ``sdy.sharding`` attributes of extent-1 axes on
# more arguments; and JAX numbers its private helper functions
# (``@_where_351``) by how many it traced before. So the hash is taken
# without the sharding attributes and the helper numbers. A change that means
# to alter the program changes these on purpose.
MISTRAL_SHAPED_STEP = {
    (None, 1): "031f2678d109009b45e747648e91bd391184595b80849ebd34693dfcfc900f86",
    (None, 2): "e682274c4e3a642c2cdaaca4c005e839ce303bab58ddbe15aafad7cf4fc90ea8",
    ("fsdp:4", 1): "903829561ab7550cec7ebfd3c0fd700e71151aef4822c7af47a7d24be78407e5",
    ("fsdp:4", 2): "b667c0d2b9fb891d936d4545eda655f09692837d3ad226914fac84e4fa52a0d3",
    ("fsdp:2,tensor:2", 1): "06ddb25e2d34e23e4cf076f50de0ca073dbb515ce530beb13e357ae9855c5528",
    ("fsdp:2,tensor:2", 2): "4739114c8aaf532b7064ecceb600d80522c23ab5f00d96c07193562f384d8d9c",
}


def _normalised(text: str) -> str:
    text = re.sub(r"sdy\.sharding = #sdy\.sharding<[^>]*>,? ?", "", text)
    return re.sub(r"@(_?[A-Za-z_]+)_\d+", r"@\1", text)


@pytest.mark.parametrize("mesh, accum", sorted(MISTRAL_SHAPED_STEP, key=str))
def test_a_configuration_without_the_new_mechanisms_lowers_as_before(mesh, accum):
    shape = dict((k, int(v)) for k, v in (p.split(":") for p in mesh.split(","))
                 ) if mesh else None
    n = int(np.prod(list(shape.values()))) if shape else 1
    trainer = CheetahTrainer(TransformerConfig.tiny(),
                             make_mesh(shape, devices=jax.devices()[:n]),
                             accum_steps=accum)
    state = trainer.init_state(jax.random.PRNGKey(0))
    assert state.model_state == {}
    tokens = jnp.zeros((8, 32) if accum == 1 else (accum, 8, 32), jnp.int32)
    text = trainer.lower_step(state, tokens, jnp.ones_like(tokens)).as_text()
    assert hashlib.sha256(_normalised(text).encode()).hexdigest() == \
        MISTRAL_SHAPED_STEP[(mesh, accum)]


def test_second_step_on_one_device_compiles_nothing():
    """The state ``init_state`` returns has the abstract type the step
    returns, so the step program is traced and compiled once."""
    trainer = CheetahTrainer(TransformerConfig.tiny(),
                             make_mesh(None, devices=jax.devices()[:1]))
    state = trainer.init_state(jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 32), jnp.int32)
    from fedml_tpu.core.mlops import telemetry

    telemetry.install_jax_listeners()
    compiles = lambda: telemetry.registry().snapshot()["counters"].get(
        "jax.compiles", 0)
    state, _ = trainer.train_step(state, tokens, jnp.ones_like(tokens))
    after_first = compiles()
    for _ in range(2):
        state, _ = trainer.train_step(state, tokens, jnp.ones_like(tokens))
    assert compiles() == after_first


def test_old_switch_routing_is_the_same_layer():
    """softmax top-2 with a capacity factor still builds, trains and reports
    its auxiliary loss; its counters ride along."""
    cfg = TransformerConfig(vocab_size=96, d_model=64, n_layers=2, n_heads=4,
                            n_kv_heads=4, d_ff=128, max_seq_len=64, remat=False,
                            moe_experts=4, moe_top_k=2, moe_capacity_factor=2.0)
    assert cfg.layer_kinds == ("moe", "moe")
    trainer = CheetahTrainer(cfg, make_mesh(None, devices=jax.devices()[:1]))
    state = trainer.init_state(jax.random.PRNGKey(0))
    assert state.model_state == {}
    _, metrics = trainer.train_step(state, TOKENS, jnp.ones_like(TOKENS))
    assert np.isfinite(float(metrics["loss"]))
    assert int(metrics["moe_assignments_held"]) == 2 * 2 * TOKENS.size


def test_arguments_reach_every_new_field():
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.cheetah.runner import config_from_args

    want = xing_tiny(dtype=jnp.bfloat16, remat=True)
    args = {f.name: getattr(want, f.name)
            for f in dataclasses.fields(TransformerConfig)
            if f.name not in ("dtype", "param_dtype", "max_seq_len")}
    args.update(model_size="from_arguments", seq_len=want.max_seq_len,
                training_type="distributed", remat="true")
    assert config_from_args(Arguments(overrides=args)) == want
    # a preset keeps its shape and takes the rest
    tiny = config_from_args(Arguments(overrides=dict(
        training_type="distributed", model_size="tiny", norm_eps=1e-6,
        rope_theta=500000.0)))
    assert (tiny.d_model, tiny.norm_eps, tiny.rope_theta) == (128, 1e-6, 500000.0)


def test_invalid_configurations_are_refused():
    with pytest.raises(ValueError, match="attn_kind"):
        TransformerConfig(attn_kind="latent")
    with pytest.raises(ValueError, match="needs q_lora_rank"):
        TransformerConfig(attn_kind="mla")
    with pytest.raises(ValueError, match="moe_router"):
        TransformerConfig(moe_router="tanh")
    with pytest.raises(ValueError, match="not among"):
        xing_tiny(moe_expert_offset=14)
    with pytest.raises(ValueError, match="objective must be"):
        TransformerConfig(objective="masked_lm")
    # the softmax router takes any number of choices since PR 34
    cfg = xing_tiny(moe_router="softmax")
    MoEFeedForward(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 64)))
