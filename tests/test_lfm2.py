"""LFM2's gated short-convolution mixer in a per-layer mixer list, GQA at a
head size of its own with q/k norms, a head tied to the embedding and the
sigmoid router with a selection bias and no shared expert (ISSUE 40), at a
small size on XLA:CPU: the program against the plain reference
(``benchmark/reference/lfm2_moe.py``) on seeded weights, the mixer against a
per-channel ``numpy.convolve``, the tie's one table and its two gradients,
the shares of the expert layer against the whole, and every refusal by
name."""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from fedml_tpu.parallel import moe
from fedml_tpu.parallel import transformer as tfm
from fedml_tpu.parallel.moe import MoEFeedForward
from fedml_tpu.parallel.sharding import make_mesh, unbox
from fedml_tpu.parallel.train_step import CheetahTrainer
from fedml_tpu.parallel.transformer import (ShortConvMixer, Transformer,
                                            TransformerConfig, output_head)

ref = harness.load_module(harness.ROOT, "reference", "lfm2_moe")

# the cell's seven layers: one leading dense layer, then a period and two
TYPES = ("conv", "full_attention", "conv", "conv", "conv", "full_attention",
         "conv")


def lfm2_tiny(**kw) -> TransformerConfig:
    """The cell's layer list at width 64: 5 short-convolution mixers of 3
    taps, 2 attention layers (4 query and 2 key / value heads of 16, q/k
    norms, rotary at 1e6), one dense layer of 96, six expert layers (16
    routed experts of 24, experts 4 to 7 held, 4 a token, no shared expert),
    the head tied to the embedding."""
    base = dict(
        vocab_size=96, d_model=64, n_layers=len(TYPES), n_heads=4,
        n_kv_heads=2, d_ff=96, max_seq_len=64, remat=False, attn_impl="xla",
        dtype=jnp.float32, qk_norm=True, rope_theta=1e6, layer_types=TYPES,
        first_k_dense=1, tie_embeddings=True, moe_experts=16, moe_top_k=4,
        moe_capacity_factor=0.0, moe_router="sigmoid", moe_routed_scale=1.0,
        moe_d_ff=24, moe_experts_held=4, moe_expert_offset=4)
    base.update(kw)
    return TransformerConfig(**base)


def reference_config(cfg: TransformerConfig) -> dict:
    """``cfg`` under the published keys the reference reads."""
    return dict(
        hidden_size=cfg.d_model, norm_eps=cfg.norm_eps,
        num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_kv_heads,
        rope_theta=cfg.rope_theta, num_experts_per_tok=cfg.moe_top_k,
        num_experts=cfg.experts_held, expert_offset=cfg.moe_expert_offset,
        router_experts=cfg.moe_experts,
        routed_scaling_factor=cfg.moe_routed_scale)


def _name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


@functools.lru_cache(maxsize=None)
def _initial(cfg: TransformerConfig):
    return jax.jit(Transformer(cfg).init)(jax.random.PRNGKey(0),
                                           jnp.zeros((1, 8), jnp.int32))


def seeded(cfg: TransformerConfig, moved: bool = True):
    """The model's initial variables; ``moved`` perturbs every norm weight
    and the selection bias, so that what is 1 or 0 at initialisation is
    checked too."""
    variables = _initial(cfg)
    params = unbox(variables["params"])
    state = {"router_state": unbox(variables["router_state"])}
    if not moved:
        return params, state

    def move(path, p):
        name = _name(path)
        if "norm" in name.lower():
            return p + 0.3 * jax.random.normal(
                jax.random.PRNGKey(sum(map(ord, name))), p.shape)
        return p

    params = jax.tree_util.tree_map_with_path(move, params)
    state = jax.tree.map(
        lambda b: 0.05 * jax.random.normal(jax.random.PRNGKey(7), b.shape),
        state)
    return params, state


TOKENS = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 96)


def reference_logits(plain, tokens, config):
    """The reference's (logits, summed loss, margins) of one sequence, jitted
    (eagerly its loops dispatch one small op at a time)."""
    return jax.jit(lambda p, t: ref.logits_and_loss(p, t, config))(plain, tokens)


# ---------------------------------------------------------------------------
# the layer list
# ---------------------------------------------------------------------------


def test_the_published_names_are_the_mixers_and_the_rules_give_what_they_gave():
    cfg = lfm2_tiny()
    assert cfg.mixers == ("shortconv", "gqa", "shortconv", "shortconv",
                          "shortconv", "gqa", "shortconv")
    assert cfg.layer_kinds == ("dense",) + ("moe",) * 6
    assert cfg.head_dim == 16
    # the list from the arguments (a JSON or YAML list)
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.cheetah.runner import config_from_args

    built = config_from_args(Arguments(overrides=dict(
        model_size="from_config", n_layers=7, layer_types=list(TYPES),
        first_k_dense=1, moe_experts=8, tie_embeddings="true")))
    assert built.layer_types == TYPES and built.tie_embeddings is True
    old = TransformerConfig(n_layers=4, layer_group_size=2, attn_kind="mla",
                            kv_lora_rank=8, qk_nope_head_dim=8,
                            qk_rope_head_dim=8, v_head_dim=8, kda_head_dim=8)
    assert old.mixers == ("kda", "mla", "kda", "mla")
    assert TransformerConfig.tiny().tie_embeddings is False


@pytest.mark.parametrize("change, error, message", [
    (dict(layer_types=TYPES[:-1] + ("mamba",)), ValueError,
     r"layer_types names \['conv', 'full_attention'\], got \['mamba'\]"),
    (dict(n_layers=6), ValueError, "names 7 layers, n_layers is 6"),
    (dict(layer_pattern="-E-E-E-", first_k_dense=0), ValueError,
     "the other spellings"),
    (dict(layer_group_size=2), ValueError, "the other spellings"),
    (dict(attn_kind="kda", kda_head_dim=8), ValueError,
     "full_attention layer runs attn_kind"),
    (dict(objective="block_diffusion", bd_block=4, bd_mask_token=95),
     NotImplementedError, "does not run with a short-convolution mixer"),
], ids=["unknown", "length", "pattern", "group_size", "kda",
        "block_diffusion"])
def test_a_configuration_that_cannot_be_built_says_why(change, error, message):
    with pytest.raises(error, match=message):
        lfm2_tiny(**change)


def test_every_layer_is_a_mixer_and_a_feed_forward_and_the_table_is_one():
    cfg = lfm2_tiny()
    params, _ = seeded(cfg, moved=False)
    assert "w_lm_head" not in params
    blocks = sorted((k for k in params if k.startswith("Block_")),
                    key=lambda k: int(k.rsplit("_", 1)[1]))
    mixer = {"conv": "ShortConvMixer_0", "full_attention": "Attention_0"}
    for i, (name, kind) in enumerate(zip(blocks, TYPES)):
        ffn = "FeedForward_0" if i == 0 else "MoEFeedForward_0"
        assert sorted(params[name]) == sorted(
            ["RMSNorm_0", "RMSNorm_1", mixer[kind], ffn])
    m = params["Block_0"]["ShortConvMixer_0"]
    assert {k: v.shape for k, v in m.items()} == {
        "w_in": (64, 192), "conv": (3, 64), "w_out": (64, 64)}
    # torch's depthwise Conv1d default: U(+-1/sqrt(3))
    assert 0.4 < float(jnp.abs(m["conv"]).max()) <= 3 ** -0.5
    a = params["Block_1"]["Attention_0"]
    assert a["q_norm"]["weight"].shape == a["k_norm"]["weight"].shape == (16,)
    e = params["Block_1"]["MoEFeedForward_0"]
    assert e["w_gate_up"].shape == (4, 64, 48) and "shared" not in e
    assert output_head(cfg, params).shape == (64, 96)
    assert np.array_equal(output_head(cfg, params), params["embed"].T)
    # untied configurations build the head they always built
    untied = dataclasses.replace(cfg, tie_embeddings=False)
    p2, _ = seeded(untied, moved=False)
    assert p2["w_lm_head"].shape == (64, 96)
    assert output_head(untied, p2) is p2["w_lm_head"]


def test_flops_by_hand():
    cfg = lfm2_tiny()
    D, L = 64, 64
    conv = 2 * D * 3 * D + 2 * D * D + 2 * 3 * D + 2 * D
    attn = 2 * D * 16 * (2 * 4 + 2 * 2) + 2 * 2 * 4 * 16 * (L + 1) / 2
    dense = 2 * 3 * D * 96
    expert = 2 * D * 16 + 2 * 3 * D * (4 * 4 / 16 * 24)
    by_hand = 5 * conv + 2 * attn + dense + 6 * expert + 2 * D * 96
    assert tfm.train_flops_per_token(cfg, L) == pytest.approx(3 * by_hand)


# ---------------------------------------------------------------------------
# the mixer
# ---------------------------------------------------------------------------


def test_the_mixer_is_a_gated_per_channel_convolution():
    """The mixer against ``numpy.convolve`` channel by channel: [B | C | x]
    in that order, C * (w * (B x)) with the filter's last tap on the current
    token, then the output projection."""
    cfg = lfm2_tiny()
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 20, 64))
    params = unbox(ShortConvMixer(cfg).init(jax.random.PRNGKey(4), x)["params"])
    got = np.asarray(ShortConvMixer(cfg).apply({"params": params}, x))
    w_in, w_out, taps = (np.asarray(params[k], np.float64)
                         for k in ("w_in", "w_out", "conv"))
    for row in range(2):
        h = np.asarray(x[row], np.float64) @ w_in
        b, c, v = h[:, :64], h[:, 64:128], h[:, 128:]
        u = b * v
        conv = np.stack([np.convolve(u[:, ch], taps[::-1, ch])[:20]
                         for ch in range(64)], axis=1)
        want = (c * conv) @ w_out
        assert np.allclose(got[row], want, atol=1e-4 * np.abs(want).max())


def test_a_change_at_t_moves_nothing_before_t():
    cfg = lfm2_tiny()
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 20, 64))
    params = unbox(ShortConvMixer(cfg).init(jax.random.PRNGKey(4), x)["params"])
    mixer = lambda x: ShortConvMixer(cfg).apply({"params": params}, x)  # noqa: E731
    y, moved = mixer(x), mixer(x.at[:, 11].add(1.0))
    assert np.array_equal(y[:, :11], moved[:, :11])
    assert not np.allclose(y[:, 11:14], moved[:, 11:14])
    # three taps: a change at t reaches t, t + 1 and t + 2 and no further
    assert np.array_equal(y[:, 14:], moved[:, 14:])
    # and the whole stack: attention and the mixers alike
    params, state = seeded(cfg)
    model = jax.jit(lambda t: Transformer(cfg).apply(
        {"params": params, **state}, t))
    logits, changed = model(TOKENS), model(TOKENS.at[:, 40].set(
        (TOKENS[:, 40] + 1) % 96))
    assert np.array_equal(logits[:, :40], changed[:, :40])
    assert not np.allclose(logits[:, 40:], changed[:, 40:])


# ---------------------------------------------------------------------------
# the program against the reference
# ---------------------------------------------------------------------------

# float32 on both sides: only the order of float32 sums differs (the program
# sorts assignments by expert), so 1e-5; both sides choose the same experts
# because no margin of these seeds lies under 1e-5
def test_logits_agree_with_the_reference():
    cfg = lfm2_tiny()
    config = reference_config(cfg)
    params, state = seeded(cfg)
    logits = jax.jit(Transformer(cfg).apply)({"params": params, **state},
                                             TOKENS)
    plain = ref.reference_params(params, config, state["router_state"])
    assert [("conv" in layer, "moe" in layer) for layer in plain["layers"]] == [
        (kind == "conv", i > 0) for i, kind in enumerate(TYPES)]
    for row in range(2):
        want, _, margin = reference_logits(plain, TOKENS[row], config)
        assert float(margin.min()) > 1e-5
        err = jnp.linalg.norm(logits[row] - want) / jnp.linalg.norm(want)
        assert float(err) < 1e-5


# The step's own loss (chunked cross entropy through the tied head) and its
# gradient, every leaf, against jax.grad of the reference's loss in the
# reference's layout: 1e-4 relative to the largest leaf-wise norm, float32
# both sides. The embedding's gradient is the lookup's and the head's. Four
# layers hold every kind: conv + dense, attention, conv and conv + experts.
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_loss_and_gradients_agree_with_the_reference(remat):
    types = TYPES[:4]
    cfg = lfm2_tiny(remat=remat, n_layers=4, layer_types=types)
    config = reference_config(cfg)
    tokens = TOKENS[:, :32]
    params, state = seeded(cfg)
    trainer = CheetahTrainer(cfg, make_mesh(None, devices=jax.devices()[:1]),
                             loss_chunk=16)
    mask = jnp.ones_like(tokens)
    (got, _), got_grads = jax.jit(jax.value_and_grad(
        trainer._loss_fn, has_aux=True))(params, state, tokens, mask)

    def reference_loss(plain):
        return sum(ref.logits_and_loss(plain, tokens[row], config)[1]
                   for row in range(2)) / (2 * 31)

    plain = ref.reference_params(params, config, state["router_state"])
    want, want_grads = jax.jit(jax.value_and_grad(reference_loss))(plain)
    assert abs(float(got) - float(want)) < 1e-5 * float(want)
    got_plain = ref.reference_params(got_grads, config)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    scale = max(float(jnp.linalg.norm(g)) for g in flat_want.values())
    checked = 0
    for path, g in jax.tree_util.tree_leaves_with_path(got_plain):
        if _name(path).endswith("bias") and "moe" in _name(path):
            continue  # the selection bias: state, no gradient
        err = float(jnp.linalg.norm(g - flat_want[path]))
        assert err < 1e-4 * scale, (_name(path), err, scale)
        checked += 1
    # embed + final norm; 2 norms a layer; conv 3 x 3, attention 6;
    # dense 3, experts 4 (router and three matrices) x 3
    assert checked == 2 + 2 * 4 + 9 + 6 + 3 + 12


def test_the_tied_gradient_is_the_lookups_plus_the_heads():
    """The same numbers untied, the head a copy of the embedding's
    transpose: the tied table's gradient is the untied embedding's gradient
    (the lookup's part) plus the untied head's, transposed."""
    cfg = lfm2_tiny()
    params, state = seeded(cfg)
    untied_cfg = dataclasses.replace(cfg, tie_embeddings=False)
    untied = dict(params, w_lm_head=params["embed"].T)
    mask = jnp.ones_like(TOKENS)

    def grads(c, p):
        trainer = CheetahTrainer(c, make_mesh(None, devices=jax.devices()[:1]),
                                 loss_chunk=16)
        return jax.jit(jax.grad(
            lambda p: trainer._loss_fn(p, state, TOKENS, mask)[0]))(p)

    tied, apart = grads(cfg, params), grads(untied_cfg, untied)
    lookup, head = apart["embed"], apart["w_lm_head"].T
    assert float(jnp.abs(lookup).max()) > 0 and float(jnp.abs(head).max()) > 0
    assert np.allclose(tied["embed"], lookup + head, atol=1e-6)
    assert "w_lm_head" not in tied


def test_bfloat16_stays_close_and_float8_does_not():
    """The benchmark's kind of tolerance at this width: the program in
    bfloat16 (its default) against the float32 reference over the positions
    whose routing margin is clear, and the reference with every product's
    inputs rounded to float8 (the nearest precision below) against itself."""
    cfg = lfm2_tiny(dtype=jnp.bfloat16)
    config = reference_config(cfg)
    params, state = seeded(cfg, moved=False)
    plain = ref.reference_params(params, config, state["router_state"])
    want, _, margin = reference_logits(plain, TOKENS[0], config)
    clear = np.asarray(margin) >= 0.003
    assert clear.mean() > 0.3

    def err(got):
        d = (np.asarray(got) - np.asarray(want))[clear]
        return float(np.linalg.norm(d) / np.linalg.norm(np.asarray(want)[clear]))

    got = jax.jit(Transformer(cfg).apply)({"params": params, **state},
                                          TOKENS)[0]
    assert err(got) < 2e-2
    ref.MATMUL_INPUT_DTYPE = jnp.float8_e4m3fn
    try:
        low, _, _ = reference_logits(plain, TOKENS[0], config)
    finally:
        ref.MATMUL_INPUT_DTYPE = None
    assert err(low) > 3 * err(got)


MISTAKES = ["conv_not_causal", "b_c_swapped", "no_qk_norm", "rotary_off",
            "no_renormalisation"]


def make_mistake(mistake, monkeypatch):
    """One mistake in the reference. The benchmark's tolerance study makes
    the same ones at the cell's size (PERF.md)."""
    if mistake == "conv_not_causal":
        # the window centred on the token: one tap looks ahead
        conv = ref.causal_depthwise_conv
        monkeypatch.setattr(ref, "causal_depthwise_conv", lambda x, w: conv(
            jnp.concatenate([x[1:], jnp.zeros_like(x[:1])]), w))
    elif mistake == "b_c_swapped":
        conv = ref.short_conv

        def swapped(p, x, config):
            D, w = int(config["hidden_size"]), p["w_in"]
            return conv(dict(p, w_in=jnp.concatenate(
                [w[:, D:2 * D], w[:, :D], w[:, 2 * D:]], 1)), x, config)
        monkeypatch.setattr(ref, "short_conv", swapped)
    elif mistake == "no_qk_norm":
        monkeypatch.setattr(ref, "qk_norm", lambda x, weight, eps: x)
    elif mistake == "rotary_off":
        monkeypatch.setattr(ref, "rotary", lambda x, theta: x)
    elif mistake == "no_renormalisation":
        monkeypatch.setattr(ref, "renormalise", lambda chosen: chosen)
    else:
        raise ValueError(mistake)


@pytest.mark.parametrize("mistake", MISTAKES)
def test_reference_is_sensitive_to_what_it_checks(mistake, monkeypatch):
    """Each of these mistakes moves the reference's logits by at least ten
    times what the float32 agreement above allows (1e-5), so the comparison
    would catch the program making it."""
    cfg = lfm2_tiny()
    config = reference_config(cfg)
    params, state = seeded(cfg)
    plain = ref.reference_params(params, config, state["router_state"])
    want, _, _ = reference_logits(plain, TOKENS[0], config)
    make_mistake(mistake, monkeypatch)
    got, _, _ = reference_logits(plain, TOKENS[0], config)
    err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert err > 1e-4, err


# ---------------------------------------------------------------------------
# the expert layer: a selection bias, no shared expert
# ---------------------------------------------------------------------------


def test_the_selection_bias_chooses_and_the_scores_weigh():
    """At a nonzero bias the top 4 are taken by ``s + b``: an expert the bias
    lifts is chosen over one whose score alone is higher, and the weights are
    the chosen scores ``s`` renormalised, the bias nowhere in them; the
    reference chooses and weighs the same from the same router output."""
    cfg = lfm2_tiny()
    logits = jnp.array([[2.0, 1.5, 1.0, 0.5, 0.0] + [-3.0] * 11])
    bias = jnp.zeros((16,)).at[10].set(0.97)       # s = 0.047 + 0.97 > s_3
    expert, gate, aux = moe.route(cfg, logits, bias)
    assert sorted(np.asarray(expert[0]).tolist()) == [0, 1, 2, 10]
    unbiased, _, _ = moe.route(cfg, logits)
    assert sorted(np.asarray(unbiased[0]).tolist()) == [0, 1, 2, 3]
    chosen = jax.nn.sigmoid(logits[0])[np.asarray(expert[0])]
    assert np.allclose(gate[0], chosen / chosen.sum(), rtol=1e-6)
    assert float(aux) == 0.0
    # the reference reads the router's output through x W_r: x = e_0
    plain = {"router": jnp.zeros((16, 16)).at[0].set(logits[0]), "bias": bias}
    selected, weights, _ = ref.route(
        plain, jnp.eye(16)[:1], dict(reference_config(cfg), expert_offset=0,
                                     num_experts=16))
    assert np.array_equal(selected[0], expert[0])
    assert np.allclose(weights[0], gate[0], rtol=1e-5)


def test_the_renormalisation_differs_from_the_published_by_its_epsilon():
    """The program divides by ``max(sum, 1e-9)``, the published code by ``sum
    + 1e-6``: for four sigmoid scores the two weights differ by ``1e-6 /
    sum`` relatively, under 1e-6 where the sum is above one. Held here so that
    the difference is known, not hidden."""
    cfg = lfm2_tiny()
    logits = jax.random.normal(jax.random.PRNGKey(5), (256, 16))
    expert, gate, _ = moe.route(cfg, logits)
    chosen = jnp.take_along_axis(jax.nn.sigmoid(logits), expert, axis=-1)
    total = chosen.sum(-1, keepdims=True)
    relative = np.asarray(gate / ref.renormalise(chosen) - 1.0, np.float64)
    assert float(total.min()) > 1.0
    assert 0 < relative.min() and relative.max() < 1e-6
    assert np.allclose(relative, 1e-6 / np.asarray(total), atol=2e-7)


def test_the_four_shares_of_an_expert_layer_add_up_to_the_whole():
    """Four chips' shares of a 32-expert layer (each holds 8, offsets 0, 8,
    16 and 24, routes over all 32, 4 a token, no shared expert) add up to the
    layer that holds every expert, and that equals the uncut reference's
    layer."""
    kw = dict(moe_experts=32, moe_top_k=4)
    whole_cfg = lfm2_tiny(moe_experts_held=0, moe_expert_offset=0, **kw)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 64), jnp.float32)
    whole = unbox(MoEFeedForward(whole_cfg).init(
        jax.random.PRNGKey(3), x)["params"])
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(5), (32,))
    state = {"router_state": {"bias": bias}}
    y_whole, _ = MoEFeedForward(whole_cfg).apply({"params": whole, **state}, x)

    total = jnp.zeros_like(y_whole)
    for offset in (0, 8, 16, 24):
        cfg = lfm2_tiny(moe_experts_held=8, moe_expert_offset=offset, **kw)
        part = dict(whole, w_gate_up=whole["w_gate_up"][offset:offset + 8],
                    w_down=whole["w_down"][offset:offset + 8])
        y, _ = MoEFeedForward(cfg).apply({"params": part, **state}, x)
        total = total + y
    assert float(jnp.abs(total - y_whole).max()) < 1e-5

    config = dict(reference_config(whole_cfg), num_experts=32, expert_offset=0)
    F = whole["w_down"].shape[1]
    plain = {"router": whole["w_router"], "bias": bias,
             "experts": {"w1": whole["w_gate_up"][..., :F],
                         "w3": whole["w_gate_up"][..., F:],
                         "w2": whole["w_down"]}}
    with jax.default_matmul_precision("highest"):
        for row in range(2):
            want, _ = ref.expert_layer(plain, x[row], config)
            assert float(jnp.abs(y_whole[row] - want).max()) < 1e-5 * max(
                float(jnp.abs(want).max()), 1.0)


# ---------------------------------------------------------------------------
# the trainer
# ---------------------------------------------------------------------------


def test_cheetah_init_says_the_mixers_the_tie_and_the_head_size(tmp_path):
    from fedml_tpu.core import mlops

    class Args:
        enable_tracking, tracking_dir, run_id = True, str(tmp_path), "lfm2"
        rank = 0

    mlops.init(Args())
    try:
        trainer = CheetahTrainer(lfm2_tiny(),
                                 make_mesh(None, devices=jax.devices()[:1]))
        trainer.init_state(jax.random.PRNGKey(0))
        (event,) = [e for e in mlops.read_events()
                    if e.get("kind") == "cheetah_init"]
    finally:
        mlops.close()
    assert event["mixers"] == ("shortconv,gqa,shortconv,shortconv,shortconv,"
                               "gqa,shortconv")
    assert event["layers"] == ["dense"] + ["moe"] * 6
    assert event["tie_embeddings"] is True and event["head_dim"] == 16
    assert event["layer_pattern"] == "" and event["ssd"] == {}


def test_training_lowers_the_loss_in_bfloat16_under_remat():
    """The step trains the stack, moves the selection bias and counts the
    routing."""
    cfg = lfm2_tiny(dtype=jnp.bfloat16, remat=True)
    from fedml_tpu.parallel.train_step import make_optimizer

    trainer = CheetahTrainer(
        cfg, make_mesh(None, devices=jax.devices()[:1]),
        optimizer=make_optimizer(learning_rate=3e-3, warmup_steps=2,
                                 total_steps=100))
    state = trainer.init_state(jax.random.PRNGKey(0))
    assert "w_lm_head" not in state.params
    losses = []
    for _ in range(8):
        state, metrics = trainer.train_step(state, TOKENS, jnp.ones_like(TOKENS))
        losses.append(float(metrics["loss"]))
        assert int(metrics["moe_dropped"]) == 0
        assert 0 < int(metrics["moe_assignments_held"]) <= 6 * 4 * 128
    assert all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.05
    biases = jax.tree.leaves(state.model_state)
    assert len(biases) == 6 and all(float(jnp.abs(b).max()) > 0 for b in biases)


def test_the_pipeline_refuses_by_name():
    from fedml_tpu.parallel.pipeline import PipelineCheetah

    pipe = make_mesh({"pipeline": 2}, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="layer_types"):
        PipelineCheetah(lfm2_tiny(), pipe)
    with pytest.raises(NotImplementedError, match="tie_embeddings"):
        PipelineCheetah(dataclasses.replace(TransformerConfig.tiny(),
                                            tie_embeddings=True), pipe)
    # a padding mask: the convolution takes whole sequences
    params, state = seeded(lfm2_tiny(), moved=False)
    with pytest.raises(NotImplementedError, match="no padding mask"):
        Transformer(lfm2_tiny()).apply({"params": params, **state}, TOKENS,
                                       mask=jnp.ones_like(TOKENS))
