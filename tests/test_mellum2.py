"""Window attention beside full layers under the published names, each kind of
layer under its own rotary, and the softmax top-k router over a held share
(Mellum2-12B-A2.5B's block), at a small size on XLA:CPU: the program against
the plain reference (``benchmark/reference/mellum2.py``) on seeded weights,
the band entry by entry in its three forms, the window that reaches back to
the first token as the causal program, YaRN's tables against an independent
computation at the published numbers, the shares of an expert layer against
the whole, and every refusal by name."""

from __future__ import annotations

import dataclasses
import functools
import math

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

ref = harness.load_module(harness.ROOT, "reference", "mellum2")

# one period of the published list
TYPES = ("sliding_attention",) * 3 + ("full_attention",)
L, W = 64, 16


def mellum_tiny(**kw) -> TransformerConfig:
    """The cell's period at width 64: 4 query and 2 key / value heads of 32
    with q/k norms, windows of 16 keys on layers 0 to 2, the full layer 3
    under YaRN (factor 4 over an original 32 positions, so the 64 tokens
    pass it), 8 routed experts of 24, experts 2 to 5 held, 2 a token,
    softmax scores renormalised, the head untied."""
    base = dict(
        vocab_size=96, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2,
        d_ff=96, max_seq_len=L, remat=False, attn_impl="xla",
        dtype=jnp.float32, norm_eps=1e-6, rope_theta=1e4, attn_head_dim=32,
        qk_norm=True, layer_types=TYPES, attn_window=W, rope_factor=4.0,
        rope_original_max_pos=32, rope_yarn_types=("full_attention",),
        moe_experts=8, moe_top_k=2, moe_capacity_factor=0.0,
        moe_router="softmax", moe_aux_weight=0.001, moe_d_ff=24,
        moe_experts_held=4, moe_expert_offset=2)
    base.update(kw)
    return TransformerConfig(**base)


def reference_config(cfg: TransformerConfig) -> dict:
    """``cfg`` under the published keys the reference reads."""
    yarn = {"rope_type": "yarn", "rope_theta": cfg.rope_theta,
            "factor": cfg.rope_factor,
            "original_max_position_embeddings": cfg.rope_original_max_pos,
            "beta_fast": cfg.rope_beta_fast, "beta_slow": cfg.rope_beta_slow,
            "attention_factor": tfm.yarn_mscale(cfg.rope_factor, 1.0)}
    plain = {"rope_type": "default", "rope_theta": cfg.rope_theta}
    return dict(
        hidden_size=cfg.d_model, rms_norm_eps=cfg.norm_eps,
        num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, layer_types=list(cfg.layer_types),
        sliding_window=cfg.attn_window,
        rope_parameters={"full_attention": yarn if cfg.rope_factor > 1 else plain,
                         "sliding_attention": plain},
        num_experts_per_tok=cfg.moe_top_k, num_experts=cfg.experts_held,
        expert_offset=cfg.moe_expert_offset, router_experts=cfg.moe_experts,
        aux_weight=cfg.moe_aux_weight)


def _name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


@functools.lru_cache(maxsize=None)
def seeded(cfg: TransformerConfig):
    """The model's initial parameters with every norm weight moved off 1,
    so that the q/k norms' and the layers' weights are checked too."""
    params = unbox(jax.jit(Transformer(cfg).init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])

    def move(path, p):
        name = _name(path)
        if "norm" in name.lower():
            return p + 0.3 * jax.random.normal(
                jax.random.PRNGKey(sum(map(ord, name))), p.shape)
        return p

    return jax.tree_util.tree_map_with_path(move, params)


TOKENS = jax.random.randint(jax.random.PRNGKey(1), (1, L), 0, 96)


# ---------------------------------------------------------------------------
# (a) the program against the reference
# ---------------------------------------------------------------------------


# float32 on both sides: the program's splash-free XLA path and fused
# projections sum in another order than the reference's blocks, 1e-6 apart
# relatively; 1e-5 on the logits and loss, 1e-4 of the largest gradient's
# norm on each leaf (jax.grad through top-k and two softmaxes) leaves room
# for that and catches a band off by one key (3e-3 here), the kinds' rotary
# swapped (2e-2) or a missing auxiliary term (1e-3 of the loss). Under remat,
# as the cell runs.
def test_loss_logits_and_gradients_agree_with_the_reference():
    cfg = mellum_tiny(remat=True)
    config = reference_config(cfg)
    params = seeded(cfg)
    plain = ref.reference_params(params, config)
    tokens, mask = TOKENS, jnp.ones_like(TOKENS)

    logits = jax.jit(Transformer(cfg).apply)({"params": params}, tokens)[0]
    want_logits, _, margin, _ = jax.jit(
        lambda p: ref.logits_and_loss(p, tokens[0], config))(plain)
    assert float(margin.min()) > 1e-5     # no routing choice near a tie
    err = jnp.linalg.norm(logits - want_logits) / jnp.linalg.norm(want_logits)
    assert float(err) < 1e-5

    trainer = CheetahTrainer(cfg, make_mesh(None, devices=jax.devices()[:1]),
                             loss_chunk=16)
    (got, _), got_grads = jax.jit(jax.value_and_grad(
        trainer._loss_fn, has_aux=True))(params, {}, tokens, mask)

    def reference_loss(p):
        return ref.loss_sum_and_tail_logits(p, tokens[0], config, 1)[0] / (L - 1)

    want, want_grads = jax.jit(jax.value_and_grad(reference_loss))(plain)
    assert abs(float(got) - float(want)) < 1e-5 * float(want)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    scale = max(float(jnp.linalg.norm(g)) for g in flat_want.values())
    checked = 0
    for path, g in jax.tree_util.tree_leaves_with_path(
            ref.reference_params(got_grads, config)):
        err = float(jnp.linalg.norm(g - flat_want[path]))
        assert err < 1e-4 * scale, (_name(path), err, scale)
        checked += 1
    # embed, final norm, head; 2 norms, 6 of attention, 4 of the experts
    assert checked == 3 + 4 * (2 + 6 + 4)


@pytest.mark.parametrize("mistake", ["band_one_short", "kinds_swapped",
                                     "no_aux"])
def test_reference_is_sensitive_to_what_it_checks(mistake, monkeypatch):
    """Each mistake moves the reference by more than the tolerances above."""
    cfg = mellum_tiny()
    config = reference_config(cfg)
    plain = ref.reference_params(seeded(cfg), config)

    def run(c):
        return jax.jit(lambda p: ref.loss_sum_and_tail_logits(
            p, TOKENS[0], c, L))(plain)

    right_loss, _, right, _ = run(config)
    if mistake == "band_one_short":
        config = dict(config, sliding_window=W - 1)
    elif mistake == "kinds_swapped":
        rope = config["rope_parameters"]
        config = dict(config, rope_parameters={
            "full_attention": rope["sliding_attention"],
            "sliding_attention": rope["full_attention"]})
    else:
        config = dict(config, aux_weight=0.0)
    loss, _, logits, _ = run(config)
    moved = float(jnp.linalg.norm(logits - right) / jnp.linalg.norm(right))
    if mistake == "no_aux":
        assert float(right_loss - loss) / float(right_loss) > 1e-4
    else:
        assert moved > 1e-3, moved


# ---------------------------------------------------------------------------
# (b) the band, (c) a window past the sequence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rows, window", [(64, 16), (256, 100), (16, 1)],
                         ids=["16", "100", "self"])
def test_the_band_is_the_same_in_the_kernel_xla_and_the_reference(rows, window):
    """Query ``i`` sees the keys ``(i - W, i]``: the splash ``LocalMask`` the
    program hands the kernel, the dense band the XLA path uses and the
    reference's ``visible``, entry by entry; and the tiles the kernel keeps
    are those the band meets."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as sm,
        splash_attention_mask_info as mi,
    )

    i, j = np.arange(rows)[:, None], np.arange(rows)[None, :]
    by_hand = (j <= i) & (j > i - window)
    assert (by_hand.sum(1) == np.minimum(np.arange(rows) + 1, window)).all()
    kernel = sm.LocalMask((rows, rows), (window - 1, 0), 0)
    assert (np.asarray(kernel[:, :]) == by_hand).all()
    assert (tfm.window_band(rows, window) == by_hand).all()
    assert (np.asarray(ref.visible(jnp.asarray(i), jnp.asarray(j), window))
            == by_hand).all()
    if rows % 128 == 0:
        info, _ = mi.process_mask(sm.MultiHeadMask([kernel] * 2), (128, 128))
        n = rows // 128
        meets = np.array([[by_hand[r * 128:(r + 1) * 128,
                                   c * 128:(c + 1) * 128].any()
                           for c in range(n)] for r in range(n)])
        assert ((np.asarray(info.block_mask)[0] > 0) == meets).all()
        assert not meets.all()        # some tile is skipped


def test_a_window_that_reaches_the_first_token_is_the_causal_program():
    """At ``attn_window >= L`` the window layers are the full layers'
    program: the mask falls back to the model's causal one by name, and the
    logits are the same to the bit; one key shorter than ``L`` they are not."""
    cfg = mellum_tiny(rope_factor=1.0, rope_yarn_types=())
    causal = dataclasses.replace(cfg, layer_types=("full_attention",) * 4,
                                 attn_window=0)
    params = seeded(cfg)
    assert cfg.attn_mask(L, "sliding_attention") == ("local", W)
    out = {}
    for name, c in (("causal", causal), ("at_L", dataclasses.replace(
            cfg, attn_window=L)), ("past_L", dataclasses.replace(
            cfg, attn_window=4 * L)), ("short", dataclasses.replace(
            cfg, attn_window=L - 1))):
        out[name] = np.asarray(jax.jit(Transformer(c).apply)(
            {"params": params}, TOKENS))
    assert dataclasses.replace(cfg, attn_window=L).attn_mask(
        L, "sliding_attention") == ("causal",)
    assert (out["at_L"] == out["causal"]).all()
    assert (out["past_L"] == out["causal"]).all()
    assert not np.allclose(out["short"], out["causal"], atol=1e-4)
    assert (tfm.window_band(L, L) == np.tril(np.ones((L, L), bool))).all()


def test_the_kernel_path_agrees_with_the_band(monkeypatch):
    """``attend`` on the splash path (the kernel under Pallas' interpreter,
    its mask the ``LocalMask``, GQA native) against the dense band through
    ``attention_scores``, forward and backward, at a window that is no
    multiple of the tiles."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
    )

    monkeypatch.setattr(sk, "make_splash_mqa", lambda *a, _f=sk.make_splash_mqa,
                        **kw: _f(*a, interpret=True, **kw))
    rows = 256
    cfg = mellum_tiny(max_seq_len=rows, attn_window=100, attn_block_q=128,
                      attn_block_kv=128)
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (1, rows, 4, 32))
    k = jax.random.normal(keys[1], (1, rows, 2, 32))
    v = jax.random.normal(keys[2], (1, rows, 2, 32))
    kind = cfg.attn_mask(rows, "sliding_attention")

    def out(c):
        return lambda q, k, v: (tfm.attend(c, q, k, v, mask_kind=kind) ** 2).sum()

    dense = jax.value_and_grad(out(cfg), argnums=(0, 1, 2))(q, k, v)
    kernel = jax.value_and_grad(out(dataclasses.replace(cfg, attn_impl="splash")),
                                argnums=(0, 1, 2))(q, k, v)
    assert float(abs(kernel[0] - dense[0])) < 1e-4 * float(dense[0])
    for a, b in zip(kernel[1], dense[1]):
        assert float(jnp.abs(a - b).max()) < 1e-4 * float(jnp.abs(b).max())


# ---------------------------------------------------------------------------
# (d) the rotary of each kind of layer
# ---------------------------------------------------------------------------

# Mellum2's rope_parameters.full_attention, and the head size
HEAD, THETA, FACTOR, ORIGINAL, FAST, SLOW = 128, 5e5, 16.0, 8192, 32.0, 1.0
ATTENTION_FACTOR = 1.2772588722239782


def independent_yarn():
    """YaRN's inverse frequencies at Mellum2's numbers in float64, from the
    published recipe: the correction range by the dimensions that turn
    beta_fast and beta_slow times over the original context, a linear ramp
    over it from the extrapolated to the interpolated frequency."""
    def correction_dim(rotations):
        return (HEAD * math.log(ORIGINAL / (rotations * 2 * math.pi))
                / (2 * math.log(THETA)))

    low = max(math.floor(correction_dim(FAST)), 0)
    high = min(math.ceil(correction_dim(SLOW)), HEAD - 1)
    pos_freqs = THETA ** (np.arange(0, HEAD, 2) / HEAD)
    extrapolation, interpolation = 1 / pos_freqs, 1 / (FACTOR * pos_freqs)
    keep = 1 - np.clip((np.arange(HEAD // 2) - low) / (high - low), 0, 1)
    return interpolation * (1 - keep) + extrapolation * keep


def test_each_kind_of_layer_rotates_by_its_own_tables(monkeypatch):
    """The full layer's inverse frequencies and cos/sin scale equal the
    independent YaRN computation at Mellum2's numbers; the window layers'
    tables are plain rotary at the same theta; each block is handed its
    kind's pair (recorded where Attention applies them), so swapping the two
    kinds fails here."""
    assert tfm.yarn_mscale(FACTOR, 1.0) == pytest.approx(ATTENTION_FACTOR,
                                                         rel=1e-15)
    assert 0.1 * math.log(16) + 1 == pytest.approx(ATTENTION_FACTOR, rel=1e-15)
    yarn = independent_yarn()
    assert np.allclose(tfm.yarn_inv_freq(HEAD, THETA, FACTOR, ORIGINAL, FAST,
                                         SLOW), yarn, rtol=1e-6)
    config = dict(head_dim=HEAD, rope_parameters={
        "full_attention": {"rope_type": "yarn", "rope_theta": THETA,
                           "factor": FACTOR, "beta_fast": FAST,
                           "beta_slow": SLOW, "attention_factor":
                           ATTENTION_FACTOR,
                           "original_max_position_embeddings": ORIGINAL},
        "sliding_attention": {"rope_type": "default", "rope_theta": THETA}})
    inv, factor = ref.inv_freq_and_factor(config, "full_attention")
    assert np.allclose(inv, yarn, rtol=1e-6) and factor == ATTENTION_FACTOR
    inv, factor = ref.inv_freq_and_factor(config, "sliding_attention")
    assert np.allclose(inv, THETA ** -(np.arange(0, HEAD, 2) / HEAD),
                       rtol=1e-6) and factor == 1.0
    assert not np.allclose(yarn[-8:], inv[-8:], rtol=1e-3)

    cfg = mellum_tiny(n_heads=2, n_kv_heads=1, attn_head_dim=HEAD,
                      rope_theta=THETA, rope_factor=FACTOR,
                      rope_original_max_pos=ORIGINAL, max_seq_len=16,
                      moe_experts=0, moe_experts_held=0, moe_expert_offset=0)
    handed = []
    monkeypatch.setattr(tfm, "apply_rotary", lambda x, cos, sin: (
        handed.append((np.asarray(cos), np.asarray(sin))) or x))
    Transformer(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))
    positions = np.arange(16)[:, None]
    want = {"sliding_attention": (inv, 1.0), "full_attention": (yarn,
                                                                ATTENTION_FACTOR)}
    assert len(handed) == 2 * 4          # q and k of each of four layers
    for layer, kind in enumerate(TYPES):
        freqs, scale = want[kind]
        for cos, sin in handed[2 * layer:2 * layer + 2]:
            assert np.allclose(cos[0], scale * np.cos(positions * freqs),
                               atol=2e-6), (layer, kind)
            assert np.allclose(sin[0], scale * np.sin(positions * freqs),
                               atol=2e-6), (layer, kind)
    # without rope_yarn_types every layer takes YaRN, as before
    assert all(mellum_tiny(rope_yarn_types=()).takes_yarn(t) for t in TYPES)


# ---------------------------------------------------------------------------
# (e) the shares of an expert layer
# ---------------------------------------------------------------------------


def test_the_four_shares_of_an_expert_layer_add_up_to_the_whole():
    """Four chips' shares of a 64-expert layer (each holds 16, offsets 0,
    16, 32 and 48, routes over all 64, softmax top 8 renormalised, no shared
    expert) add up to the layer that holds every expert, and that equals the
    uncut reference's layer."""
    kw = dict(moe_experts=64, moe_top_k=8, moe_d_ff=16)
    whole_cfg = mellum_tiny(moe_experts_held=0, moe_expert_offset=0, **kw)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 64), jnp.float32)
    whole = unbox(MoEFeedForward(whole_cfg).init(
        jax.random.PRNGKey(3), x)["params"])
    y_whole, _ = jax.jit(MoEFeedForward(whole_cfg).apply)({"params": whole}, x)
    total = jnp.zeros_like(y_whole)
    for offset in (0, 16, 32, 48):
        cfg = mellum_tiny(moe_experts_held=16, moe_expert_offset=offset, **kw)
        part = dict(whole, w_gate_up=whole["w_gate_up"][offset:offset + 16],
                    w_down=whole["w_down"][offset:offset + 16])
        y, _ = jax.jit(MoEFeedForward(cfg).apply)({"params": part}, x)
        total = total + y
    assert float(jnp.abs(total - y_whole).max()) < 1e-5

    config = dict(reference_config(whole_cfg), num_experts=64, expert_offset=0)
    F = whole["w_down"].shape[1]
    plain = {"router": whole["w_router"],
             "experts": {"w_gate": whole["w_gate_up"][..., :F],
                         "w_up": whole["w_gate_up"][..., F:],
                         "w_down": whole["w_down"]}}
    with jax.default_matmul_precision("highest"):
        for row in range(2):
            want = ref.expert_layer(plain, x[row], config)[0]
            assert float(jnp.abs(y_whole[row] - want).max()) < 1e-5 * max(
                float(jnp.abs(want).max()), 1.0)


# ---------------------------------------------------------------------------
# the trainer: what it counts, reports and refuses
# ---------------------------------------------------------------------------


def test_flops_count_the_band_not_the_triangle():
    cfg = mellum_tiny()
    D, H, Hkv, hd = 64, 4, 2, 32
    proj = 2 * D * hd * (2 * H + 2 * Hkv)
    band = (W * (W + 1) / 2 + (L - W) * W) / L        # keys a query sees
    attn = 3 * (proj + 2 * 2 * H * hd * band) + proj + 2 * 2 * H * hd * (L + 1) / 2
    experts = 4 * (2 * D * 8 + 2 * 3 * D * 24 * 2 * 4 / 8)
    assert tfm.train_flops_per_token(cfg, L) == pytest.approx(
        3 * (attn + experts + 2 * D * 96))
    assert tfm.window_pairs(L, W) == band * L
    assert tfm.window_pairs(L, 4 * L) == L * (L + 1) // 2


def test_cheetah_init_reports_the_mask_of_each_kind_of_layer(monkeypatch):
    from fedml_tpu.core import mlops

    events = []
    monkeypatch.setattr(mlops, "_emit", events.append)
    trainer = CheetahTrainer(mellum_tiny(dtype=jnp.bfloat16, remat=True),
                             make_mesh(None, devices=jax.devices()[:1]))
    state = trainer.init_state(jax.random.PRNGKey(0))
    (event,) = [e for e in events if e["kind"] == "cheetah_init"]
    assert event["attn_mask"] == {"kind": "causal",
                                  "pair_share": (L + 1) / (2 * L),
                                  "layers": {"local:16": 3, "causal": 1}}
    assert event["mixers"] == "gqa,gqa,gqa,gqa" and event["head_dim"] == 32
    losses = []
    for _ in range(3):
        state, metrics = trainer.train_step(state, TOKENS, jnp.ones_like(TOKENS))
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


@pytest.mark.parametrize("change, error, message", [
    (dict(attn_window=0), ValueError, "sliding_attention layer and "
     "attn_window > 0 go together"),
    (dict(layer_types=("full_attention",) * 4, rope_yarn_types=()), ValueError,
     "go together"),
    (dict(attn_window=-4), ValueError, "go together"),
    (dict(causal=False), NotImplementedError, "window is causal"),
    (dict(objective="block_diffusion", bd_block=4, bd_mask_token=95),
     NotImplementedError, "window is causal"),
    (dict(rope_yarn_types=("conv",)), ValueError,
     r"rope_yarn_types \['conv'\] names layers"),
], ids=["no_window", "no_window_layer", "negative", "bidirectional",
        "block_diffusion", "yarn_types"])
def test_a_configuration_that_cannot_be_built_says_why(change, error, message):
    with pytest.raises(error, match=message):
        mellum_tiny(**change)


def test_ring_attention_and_the_pipeline_refuse_a_window_by_name():
    from fedml_tpu.parallel.context import sequence_parallelism
    from fedml_tpu.parallel.pipeline import PipelineCheetah

    cfg = mellum_tiny()
    four = make_mesh({"sequence": 4}, devices=jax.devices()[:4])
    q = jnp.zeros((1, L, 4, 32))
    with sequence_parallelism(four), pytest.raises(
            NotImplementedError, match="the local mask does not run under "
            "sequence parallelism"):
        tfm.attend(cfg, q, q[:, :, :2], q[:, :, :2],
                   mask_kind=cfg.attn_mask(L, "sliding_attention"))
    with pytest.raises(NotImplementedError, match="layer_types"):
        PipelineCheetah(cfg, make_mesh({"pipeline": 2},
                                       devices=jax.devices()[:2]))
