"""A layer list given as a pattern whose layers are one sublayer alone, the
Mamba-2 state-space mixer in its chunked form, squared-ReLU experts with no
gate matrix and attention without positions (ISSUE 38), at a small size on
XLA:CPU: the program against the plain reference
(``benchmark/reference/nemotron_h.py``) on seeded weights, the chunked form
against the recurrence, the shares of the expert layer against the whole, every
refusal by name, and the step programs that must lower as before."""

from __future__ import annotations

import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from fedml_tpu.parallel import moe
from fedml_tpu.parallel import ssd
from fedml_tpu.parallel import transformer as tfm
from fedml_tpu.parallel.moe import MoEFeedForward
from fedml_tpu.parallel.sharding import make_mesh, unbox
from fedml_tpu.parallel.train_step import CheetahTrainer
from fedml_tpu.parallel.transformer import Transformer, TransformerConfig

ref = harness.load_module(harness.ROOT, "reference", "nemotron_h")

PATTERN = "MEMEMEM*E"


def nemotron_tiny(**kw) -> TransformerConfig:
    """The cell's nine layers at width 64: 4 state-space mixers (8 heads of
    12, so ``d_inner`` 96 is not twice the width; 2 groups of state 16; chunks
    of 16), 4 expert layers (16 routed experts of 48, experts 4 to 7 held, 3
    a token, a shared expert of 80), one attention layer (4 query and 2 key /
    value heads of 32) without positions."""
    base = dict(
        vocab_size=96, d_model=64, n_layers=len(PATTERN), n_heads=4,
        n_kv_heads=2, d_ff=48, max_seq_len=64, remat=False, attn_impl="xla",
        dtype=jnp.float32, attn_head_dim=32, pos_emb="none",
        layer_pattern=PATTERN, ssm_heads=8, ssm_head_dim=12, ssm_groups=2,
        ssm_state=16, ssm_conv_size=4, ssm_chunk=16, ffn_act="relu2",
        moe_experts=16, moe_top_k=3, moe_capacity_factor=0.0,
        moe_router="sigmoid", moe_routed_scale=2.5, moe_d_ff=48,
        moe_shared_experts=1, moe_shared_d_ff=80, moe_experts_held=4,
        moe_expert_offset=4)
    base.update(kw)
    return TransformerConfig(**base)


def reference_config(cfg: TransformerConfig) -> dict:
    """``cfg`` under the published keys the reference reads."""
    return dict(
        hidden_size=cfg.d_model, rms_norm_eps=cfg.norm_eps,
        mamba_num_heads=cfg.ssm_heads, mamba_head_dim=cfg.ssm_head_dim,
        n_groups=cfg.ssm_groups, ssm_state_size=cfg.ssm_state,
        conv_kernel=cfg.ssm_conv_size, num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        rope_theta=cfg.rope_theta, num_experts_per_tok=cfg.moe_top_k,
        n_routed_experts=cfg.experts_held, expert_offset=cfg.moe_expert_offset,
        router_experts=cfg.moe_experts,
        routed_scaling_factor=cfg.moe_routed_scale)


def _name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def seeded(cfg: TransformerConfig, tokens, moved: bool = True):
    """The model's initial variables; ``moved`` perturbs every norm weight,
    the convolution's bias, the skip ``D``, ``dt_bias`` and the selection
    bias, so that what is 1 or 0 at initialisation is checked too."""
    variables = Transformer(cfg).init(jax.random.PRNGKey(0), tokens)
    params = unbox(variables["params"])
    state = {"router_state": unbox(variables["router_state"])}
    if not moved:
        return params, state

    def move(path, p):
        name = _name(path)
        if ("norm" in name.lower()
                or name.rsplit("/", 1)[-1] in ("D", "dt_bias", "conv_bias")):
            return p + 0.3 * jax.random.normal(
                jax.random.PRNGKey(sum(map(ord, name))), p.shape)
        return p

    params = jax.tree_util.tree_map_with_path(move, params)
    state = jax.tree.map(
        lambda b: 0.2 * jax.random.normal(jax.random.PRNGKey(7), b.shape), state)
    return params, state


TOKENS = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 96)


# ---------------------------------------------------------------------------
# the layer list
# ---------------------------------------------------------------------------


def test_the_pattern_is_the_layer_list_and_the_two_rules_give_what_they_gave():
    cfg = nemotron_tiny()
    assert cfg.mixers == ("ssd", "none", "ssd", "none", "ssd", "none", "ssd",
                          "gqa", "none")
    assert cfg.layer_kinds == ("none", "moe") * 3 + ("none", "none", "moe")
    dense = nemotron_tiny(layer_pattern="M-*", n_layers=3, moe_experts=0,
                          moe_experts_held=0, moe_expert_offset=0)
    assert dense.mixers == ("ssd", "none", "gqa")
    assert dense.layer_kinds == ("none", "dense", "none")
    old = TransformerConfig(n_layers=7, moe_experts=8, first_k_dense=1,
                            attn_kind="mla", kv_lora_rank=8, qk_nope_head_dim=8,
                            qk_rope_head_dim=8, v_head_dim=8,
                            layer_group_size=3, kda_head_dim=8)
    assert old.layer_kinds == ("dense",) + ("moe",) * 6
    assert old.mixers == ("kda", "kda", "mla", "kda", "kda", "mla", "kda")
    assert set(TransformerConfig.tiny().mixers) == {"gqa"}
    assert set(TransformerConfig.tiny().layer_kinds) == {"dense"}


@pytest.mark.parametrize("change, message", [
    (dict(layer_pattern="MEX"), "layer_pattern is made of"),
    (dict(n_layers=4), "names 9 layers"),
    (dict(first_k_dense=1), "the other spelling"),
    (dict(moe_experts=0), "an E layer needs moe_experts"),
    (dict(ssm_heads=0), "a state-space layer needs"),
    (dict(ssm_groups=3), "a state-space layer needs"),
    (dict(ffn_act="gelu"), "ffn_act must be"),
    (dict(pos_emb="alibi"), "pos_emb must be"),
    (dict(attn_kind="mla", kv_lora_rank=8, qk_nope_head_dim=8,
          qk_rope_head_dim=8, v_head_dim=8), "pos_emb none runs with gqa"),
    # a state-space mixer is a letter of the pattern and no attn_kind, so an
    # MTP module's block (attn_kind and an expert layer) is never one
    (dict(attn_kind="ssd", mtp_layers=1), r"attn_kind must be gqa\|mla\|kda"),
])
def test_a_configuration_that_cannot_be_built_says_why(change, message):
    with pytest.raises(ValueError, match=message):
        nemotron_tiny(**change)


def test_every_layer_is_one_sublayer_and_the_parameters_are_the_equations():
    cfg = nemotron_tiny()
    params, _ = seeded(cfg, TOKENS, moved=False)
    blocks = sorted((k for k in params if k.startswith("Block_")),
                    key=lambda k: int(k.rsplit("_", 1)[1]))
    assert len(blocks) == 9
    module = {"M": "Mamba2Mixer_0", "E": "MoEFeedForward_0", "*": "Attention_0"}
    for name, letter in zip(blocks, PATTERN):
        assert sorted(params[name]) == sorted(["RMSNorm_0", module[letter]])
    m = params["Block_0"]["Mamba2Mixer_0"]
    assert {k: v.shape for k, v in m.items()} == {
        "w_in": (64, 96 + 160 + 8), "conv": (4, 160), "conv_bias": (160,),
        "A_log": (8,), "dt_bias": (8,), "D": (8,), "norm": (96,),
        "w_out": (96, 64)}
    e = params["Block_1"]["MoEFeedForward_0"]
    assert e["w_up"].shape == (4, 64, 48) and e["w_down"].shape == (4, 48, 64)
    assert e["shared"]["w_up"].shape == (64, 80) and "w_gate_up" not in e
    a = params["Block_7"]["Attention_0"]
    assert a["wqkv"].shape == (64, (4 + 2 * 2) * 32)
    # Mamba-2's own initialisers
    dt = jax.nn.softplus(m["dt_bias"])
    assert float(dt.min()) >= 1e-3 - 1e-6 and float(dt.max()) <= 0.1 + 1e-6
    A = jnp.exp(m["A_log"])
    assert float(A.min()) >= 1 and float(A.max()) <= 16
    assert np.all(np.asarray(m["D"]) == 1) and np.all(np.asarray(m["norm"]) == 1)
    assert float(jnp.abs(m["conv"]).max()) <= 0.5


def test_flops_by_hand():
    cfg = nemotron_tiny()
    D, L = 64, 64
    mamba = (2 * D * (2 * 96 + 64 + 8) + 2 * 96 * D + 2 * 4 * 160
             + 2 * 2 * 16 * 16 + 8 * (2 * 16 * 12 + 4 * 12 * 16))
    attn = 2 * D * 32 * (2 * 4 + 2 * 2) + 2 * 2 * 4 * 32 * (L + 1) / 2
    expert = 2 * D * 16 + 2 * 2 * D * (80 + 3 * 4 / 16 * 48)
    by_hand = 4 * mamba + attn + 4 * expert + 2 * D * 96
    assert tfm.train_flops_per_token(cfg, L) == pytest.approx(3 * by_hand)
    # the gated form still counts three matrices
    gated = TransformerConfig.tiny()
    assert tfm.train_flops_per_token(gated, 32) == pytest.approx(3 * (
        2 * (2 * 128 * 32 * 12 + 2 * 2 * 4 * 32 * 33 / 2 + 6 * 128 * 384)
        + 2 * 128 * 256))


# ---------------------------------------------------------------------------
# the program against the reference
# ---------------------------------------------------------------------------

# float32 on both sides: only the order of float32 sums differs (the program
# runs the recurrence in chunks and sorts assignments by expert), so 1e-5;
# both sides choose the same experts because no margin of these seeds lies
# under 1e-5. 50 tokens: three whole chunks of 16 and a padded one.
@pytest.mark.parametrize("length", [64, 50], ids=["whole_chunks", "padded"])
def test_logits_agree_with_the_reference(length):
    cfg = nemotron_tiny()
    config = reference_config(cfg)
    tokens = TOKENS[:, :length]
    params, state = seeded(cfg, tokens)
    logits = Transformer(cfg).apply({"params": params, **state}, tokens)
    plain = ref.reference_params(params, config, state["router_state"])
    assert [sorted(set(layer) - {"norm"})[0] for layer in plain["layers"]] == [
        {"M": "mamba", "E": "moe", "*": "attn"}[c] for c in PATTERN]
    for row in range(2):
        want, _, margin = ref.logits_and_loss(plain, tokens[row], config)
        assert float(margin.min()) > 1e-5
        err = jnp.linalg.norm(logits[row] - want) / jnp.linalg.norm(want)
        assert float(err) < 1e-5


# The step's own loss (chunked cross entropy) and its gradient, every leaf,
# against jax.grad of the reference's loss in the reference's layout: 1e-4
# relative to the largest leaf-wise norm, float32 both sides. The program's
# backward is autodiff's of the chunked form, the reference's of the scan.
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_loss_and_gradients_agree_with_the_reference(remat):
    cfg = nemotron_tiny(remat=remat)
    config = reference_config(cfg)
    params, state = seeded(cfg, TOKENS)
    trainer = CheetahTrainer(cfg, make_mesh(None, devices=jax.devices()[:1]),
                             loss_chunk=16)
    mask = jnp.ones_like(TOKENS)
    (got, _), got_grads = jax.value_and_grad(trainer._loss_fn, has_aux=True)(
        params, state, TOKENS, mask)

    def reference_loss(plain):
        return sum(ref.logits_and_loss(plain, TOKENS[row], config)[1]
                   for row in range(2)) / (2 * 63)

    plain = ref.reference_params(params, config, state["router_state"])
    want, want_grads = jax.value_and_grad(reference_loss)(plain)
    assert abs(float(got) - float(want)) < 1e-5 * float(want)
    # the re-layout is linear (slices), so it maps gradients too
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
    assert checked == 4 * 9 + 4 * 6 + 5 + 3


def test_bfloat16_stays_close_and_float8_does_not():
    """The benchmark's kind of tolerance at this width: the program in
    bfloat16 (its default) against the float32 reference over the positions
    whose routing margin is clear, and the reference with every product's
    inputs rounded to float8 (the nearest precision below) against itself."""
    cfg = nemotron_tiny(dtype=jnp.bfloat16)
    config = reference_config(cfg)
    params, state = seeded(cfg, TOKENS, moved=False)
    plain = ref.reference_params(params, config, state["router_state"])
    want, _, margin = ref.logits_and_loss(plain, TOKENS[0], config)
    clear = np.asarray(margin) >= 0.003
    assert clear.mean() > 0.3

    def err(got):
        d = (np.asarray(got) - np.asarray(want))[clear]
        return float(np.linalg.norm(d) / np.linalg.norm(np.asarray(want)[clear]))

    got = Transformer(cfg).apply({"params": params, **state}, TOKENS)[0]
    assert err(got) < 2e-2
    ref.MATMUL_INPUT_DTYPE = jnp.float8_e4m3fn
    try:
        low, _, _ = ref.logits_and_loss(plain, TOKENS[0], config)
    finally:
        ref.MATMUL_INPUT_DTYPE = None
    assert err(low) > 3 * err(got)


def _scan_without_the_boundary_decay(chunk):
    """The recurrence with the chunked form's likeliest mistake: the state
    that enters a chunk is read decayed inside it, but carried to the next
    chunk without the chunk's decay."""
    def scan(X, dt, A, B, C):
        L, H, P = X.shape
        G, N = B.shape[1:]
        B, C = (jnp.repeat(v, H // G, axis=1) for v in (B, C))
        y, carried = [], jnp.zeros((H, P, N))
        for start in range(0, L, chunk):
            a, own = jnp.zeros((H,)), jnp.zeros((H, P, N))
            for t in range(start, min(start + chunk, L)):
                d = jnp.exp(dt[t] * A)
                a = a + dt[t] * A
                own = d[:, None, None] * own + (
                    dt[t][:, None] * X[t])[:, :, None] * B[t][:, None, :]
                S = jnp.exp(a)[:, None, None] * carried + own
                y.append(jnp.einsum("hpn,hn->hp", S, C[t]))
            carried = carried + own          # should be exp(a) * carried + own
        return jnp.stack(y)
    return scan


MISTAKES = ["no_d_skip", "dt_without_bias", "no_boundary_decay",
            "conv_not_causal", "norm_before_gate", "relu_not_squared",
            "no_shared_expert", "unscaled_gate", "rotary_on",
            "no_selection_bias", "no_conv_bias"]


def make_mistake(mistake, plain, config, monkeypatch, chunk=16):
    """One mistake in the reference (or its parameters); returns the
    configuration to run it with. The benchmark's tolerance study makes the
    same ones at the cell's size."""
    layers = plain["layers"]
    if mistake == "no_d_skip":
        for layer in layers:
            if "mamba" in layer:
                layer["mamba"]["D"] = 0 * layer["mamba"]["D"]
    elif mistake == "dt_without_bias":
        for layer in layers:
            if "mamba" in layer:
                layer["mamba"]["dt_bias"] = 0 * layer["mamba"]["dt_bias"]
    elif mistake == "no_conv_bias":
        for layer in layers:
            if "mamba" in layer:
                layer["mamba"]["conv_bias"] = 0 * layer["mamba"]["conv_bias"]
    elif mistake == "no_boundary_decay":
        monkeypatch.setattr(ref, "ssd_scan",
                            _scan_without_the_boundary_decay(chunk))
    elif mistake == "conv_not_causal":
        # the window centred on the token: two taps look ahead
        conv = ref.causal_depthwise_conv
        monkeypatch.setattr(ref, "causal_depthwise_conv", lambda x, w, b: conv(
            jnp.concatenate([x[2:], jnp.zeros_like(x[:2])]), w, b))
    elif mistake == "norm_before_gate":
        def norm_first(y, z, weight, groups, eps):
            L, C = y.shape
            g = y.reshape(L, groups, C // groups)
            g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
            return g.reshape(L, C) * weight * jax.nn.silu(z)
        monkeypatch.setattr(ref, "gated_group_norm", norm_first)
    elif mistake == "relu_not_squared":
        monkeypatch.setattr(ref, "relu2", lambda x, w_up, w_down: ref._mm(
            jax.nn.relu(ref._mm(x, w_up)), w_down))
    elif mistake == "no_shared_expert":
        for layer in layers:
            if "moe" in layer:
                layer["moe"]["shared"]["w_down"] = (
                    0 * layer["moe"]["shared"]["w_down"])
    elif mistake == "no_selection_bias":
        for layer in layers:
            if "moe" in layer:
                layer["moe"]["bias"] = 0 * layer["moe"]["bias"]
    elif mistake == "unscaled_gate":
        return dict(config, routed_scaling_factor=1.0)
    elif mistake == "rotary_on":
        monkeypatch.setattr(ref, "ROTARY", True)
    else:
        raise ValueError(mistake)
    return config


@pytest.mark.parametrize("mistake", MISTAKES)
def test_reference_is_sensitive_to_what_it_checks(mistake, monkeypatch):
    """Each of these mistakes moves the reference's logits by at least ten
    times what the float32 agreement above allows (1e-5), so the comparison
    would catch the program making it."""
    cfg = nemotron_tiny()
    config = reference_config(cfg)
    params, state = seeded(cfg, TOKENS)
    plain = ref.reference_params(params, config, state["router_state"])
    want, _, _ = ref.logits_and_loss(plain, TOKENS[0], config)
    config = make_mistake(mistake, plain, config, monkeypatch)
    got, _, _ = ref.logits_and_loss(plain, TOKENS[0], config)
    err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert err > 1e-4, err


# ---------------------------------------------------------------------------
# the chunked form against the recurrence
# ---------------------------------------------------------------------------


def _ssd_inputs(L, b=2, H=4, P=8, G=2, N=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (b, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, L, H)) - 2.0)
    A = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.7))
    B = jax.random.normal(k[3], (b, L, G, N))
    C = jax.random.normal(k[4], (b, L, G, N))
    return x, dt, A, B, C


# lengths that are a multiple of the chunk, that are not, shorter than one
# chunk, one chunk exactly, and one token past a boundary
@pytest.mark.parametrize("L, chunk", [(64, 16), (37, 16), (5, 16), (16, 16),
                                      (17, 16), (130, 128)])
def test_chunked_form_is_the_recurrence(L, chunk):
    args = _ssd_inputs(L)
    with jax.default_matmul_precision("highest"):
        want, want_S = ssd.ssd_recurrence(*args)
        got, got_S = ssd.ssd_chunked(*args, chunk)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(jnp.abs(want).max())
    assert float(jnp.abs(got_S - want_S).max()) < 2e-5 * float(
        jnp.abs(want_S).max())


@pytest.mark.parametrize("L, chunk", [(48, 16), (37, 16), (17, 16)])
def test_chunked_forms_gradients_are_the_recurrences(L, chunk):
    args = _ssd_inputs(L, seed=3)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def loss(form):
        def f(*a):
            y, S = form(*a)
            return (y * weight).sum() + (S * S).sum()
        return f

    with jax.default_matmul_precision("highest"):
        want = jax.grad(loss(ssd.ssd_recurrence), argnums=range(5))(*args)
        got = jax.grad(loss(lambda *a: ssd.ssd_chunked(*a, chunk)),
                       argnums=range(5))(*args)
    for name, g, w in zip("x dt A B C".split(), got, want):
        assert bool(jnp.isfinite(g).all()), name
        assert float(jnp.abs(g - w).max()) < 1e-4 * float(jnp.abs(w).max()), name


def test_a_state_crosses_the_chunk_boundary_and_an_initial_state_is_carried():
    """One write at token 3 read at token 20, a chunk later: the chunked form
    carries it over the boundary with the decays of the tokens between; and
    two halves run with the first's state handed on equal the whole."""
    b, L, H, P, N = 1, 32, 1, 2, 4
    x = jnp.zeros((b, L, H, P)).at[0, 3, 0].set(jnp.array([1.0, -2.0]))
    dt = jnp.full((b, L, H), 0.1)
    A = jnp.array([-2.0])
    B = jnp.zeros((b, L, 1, N)).at[0, 3, 0, 1].set(1.0)
    C = jnp.zeros((b, L, 1, N)).at[0, 20, 0, 1].set(1.0)
    y, _ = ssd.ssd_chunked(x, dt, A, B, C, 16)
    want = 0.1 * np.exp(-0.2 * 17) * np.array([1.0, -2.0])
    assert np.allclose(y[0, 20, 0], want, rtol=1e-5)
    assert float(jnp.abs(y).sum()) == pytest.approx(float(np.abs(want).sum()),
                                                    rel=1e-5)
    args = _ssd_inputs(48, seed=5)
    whole, S = ssd.ssd_chunked(*args, 16)
    x, dt, A, B, C = args
    first, S1 = ssd.ssd_chunked(x[:, :20], dt[:, :20], A, B[:, :20], C[:, :20], 16)
    second, S2 = ssd.ssd_chunked(x[:, 20:], dt[:, 20:], A, B[:, 20:], C[:, 20:],
                                 16, S0=S1)
    assert np.allclose(jnp.concatenate([first, second], 1), whole, atol=1e-5)
    assert np.allclose(S2, S, atol=1e-5)


def test_bfloat16_products_keep_float32_decays():
    """bfloat16 inputs to the products, the step sizes, sums, decays and the
    state in float32: within bfloat16's rounding of the float32 form."""
    x, dt, A, B, C = _ssd_inputs(64, seed=7)
    want, _ = ssd.ssd_chunked(x, dt, A, B, C, 16)
    got, S = ssd.ssd_chunked(x.astype(jnp.bfloat16), dt, A,
                             B.astype(jnp.bfloat16), C.astype(jnp.bfloat16), 16)
    assert got.dtype == jnp.float32 and S.dtype == jnp.float32
    err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert 1e-4 < err < 2e-2


def test_the_convolution_is_one_function_for_both_mixers():
    """The program's causal convolution (the KDA mixer's and the state-space
    mixer's) against the reference's own, with and without the bias."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 11, 6))
    taps = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    bias = jax.random.normal(jax.random.PRNGKey(2), (6,))
    got = tfm.causal_depthwise_conv(x, taps, bias)
    for row in range(2):
        assert np.allclose(got[row], ref.causal_depthwise_conv(x[row], taps, bias),
                           atol=1e-6)
    assert np.allclose(tfm.causal_depthwise_conv(x, taps), got - bias, atol=1e-6)
    # causal: moving a later token moves no earlier output
    moved = tfm.causal_depthwise_conv(x.at[:, 7].add(1.0), taps, bias)
    assert np.allclose(moved[:, :7], got[:, :7]) and not np.allclose(
        moved[:, 7], got[:, 7])


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------


def test_the_sixteen_shares_of_an_expert_layer_add_up_to_the_whole():
    """Sixteen chips' shares of a 32-expert layer (each holds 2, routes over
    all 32, 6 a token), the shared expert counted once, equal the layer that
    holds every expert, and that equals the uncut reference's layer."""
    kw = dict(moe_experts=32, moe_top_k=6)
    whole_cfg = nemotron_tiny(moe_experts_held=0, moe_expert_offset=0, **kw)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 64), jnp.float32)
    whole = unbox(MoEFeedForward(whole_cfg).init(
        jax.random.PRNGKey(3), x)["params"])
    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(5), (32,))
    state = {"router_state": {"bias": bias}}
    y_whole, _ = MoEFeedForward(whole_cfg).apply({"params": whole, **state}, x)
    shared = tfm.FeedForward(whole_cfg, d_ff=80).apply(
        {"params": whole["shared"]}, x)

    total = jnp.zeros_like(y_whole)
    for share in range(16):
        cfg = nemotron_tiny(moe_experts_held=2, moe_expert_offset=2 * share, **kw)
        part = dict(whole, w_up=whole["w_up"][2 * share:2 * share + 2],
                    w_down=whole["w_down"][2 * share:2 * share + 2])
        y, _ = MoEFeedForward(cfg).apply({"params": part, **state}, x)
        total = total + (y - shared)
    assert float(jnp.abs(total + shared - y_whole).max()) < 1e-5

    config = dict(reference_config(whole_cfg), n_routed_experts=32,
                  expert_offset=0)
    plain = {"router": whole["w_router"], "bias": bias,
             "shared": {"w_up": whole["shared"]["w_up"],
                        "w_down": whole["shared"]["w_down"]},
             "experts": {"w_up": whole["w_up"], "w_down": whole["w_down"]}}
    for row in range(2):
        want, _ = ref.expert_layer(plain, x[row], config)
        assert float(jnp.abs(y_whole[row] - want).max()) < 1e-5


@pytest.mark.parametrize("capacity", [0.0, 8.0], ids=["grouped", "slotted"])
def test_squared_relu_experts_against_a_loop_over_the_experts(capacity):
    """The two grouped products with ``[held, D, F]`` (no gate matrix) and the
    slotted form under a capacity nothing exceeds, each against every expert
    applied to the rows sorted to it."""
    cfg = nemotron_tiny(moe_capacity_factor=capacity)
    key = jax.random.split(jax.random.PRNGKey(0), 3)
    counts = jnp.array([5, 0, 9, 3], jnp.int32)
    rows = jax.random.normal(key[0], (24, 64))           # 17 arrive, 7 do not
    w_up = jax.random.normal(key[1], (4, 64, 48)) * 0.1
    w_down = jax.random.normal(key[2], (4, 48, 64)) * 0.1
    start = np.concatenate([[0], np.cumsum(counts)])
    want = jnp.concatenate([
        jnp.square(jax.nn.relu(rows[start[e]:start[e + 1]] @ w_up[e])) @ w_down[e]
        for e in range(4)])
    if capacity:
        sorted_local = jnp.repeat(jnp.arange(5), jnp.array([5, 0, 9, 3, 7]))
        kept, slot = moe._capacity_slots(cfg, sorted_local, counts)
        assert bool(kept[:17].all()) and not bool(kept[17:].any())
        got = moe._slotted_experts(cfg, rows, w_up, w_down, slot)
    else:
        got = moe._grouped_experts(cfg, rows, w_up, w_down, counts)
    assert float(jnp.abs(got[:17] - want).max()) < 1e-4
    # the gated form reads the same functions with [held, D, 2F]
    gated = dataclasses.replace(cfg, ffn_act="swiglu")
    w_gate_up = jnp.concatenate([w_up, w_up[..., ::-1]], -1)
    h = moe._grouped_experts(dataclasses.replace(gated, moe_capacity_factor=0.0),
                             rows, w_gate_up, w_down, counts)
    e0 = rows[:5] @ w_gate_up[0]
    assert np.allclose(h[:5], (jax.nn.silu(e0[:, :48]) * e0[:, 48:]) @ w_down[0],
                       atol=1e-4)


@pytest.mark.parametrize("act, F", [
    ("relu2", 200), ("swiglu", 200), ("relu2", 1856), ("relu2", 256),
    ("swiglu", 768), ("relu2", 48)])
def test_grouped_products_at_any_width_are_a_loop_over_the_experts(act, F):
    """The grouped products take the experts' width as it is published, whole
    lanes or not (1,856 is 29 x 64): the result and every gradient, under
    either activation, are those of each expert applied to its own rows."""
    cfg = nemotron_tiny(ffn_act=act, d_model=16)
    halves = 2 if act == "swiglu" else 1
    key = jax.random.split(jax.random.PRNGKey(0), 3)
    counts = jnp.array([5, 0, 9, 3], jnp.int32)
    start = np.concatenate([[0], np.cumsum(counts)])
    rows = jax.random.normal(key[0], (24, 16))           # 17 arrive, 7 do not
    w_up = jax.random.normal(key[1], (4, 16, halves * F)) * 0.1
    w_down = jax.random.normal(key[2], (4, F, 16)) * 0.1

    def loop(rows, w_up, w_down):
        return jnp.concatenate([
            tfm.ffn_activation(act, rows[start[e]:start[e + 1]] @ w_up[e])
            @ w_down[e] for e in range(4)])

    def grouped(rows, w_up, w_down):
        return moe._grouped_experts(cfg, rows, w_up, w_down, counts)[:17]

    def loss(f):
        return lambda *a: (f(*a) ** 2).sum()

    with jax.default_matmul_precision("highest"):
        got, want = grouped(rows, w_up, w_down), loop(rows, w_up, w_down)
        g_got = jax.grad(loss(grouped), argnums=(0, 1, 2))(rows, w_up, w_down)
        g_want = jax.grad(loss(loop), argnums=(0, 1, 2))(rows, w_up, w_down)
    assert got.shape == want.shape == (17, 16)
    assert float(jnp.abs(got - want).max()) < 1e-5 * max(
        float(jnp.abs(want).max()), 1.0)
    for a, b in zip(g_got, g_want):
        assert a.shape == b.shape
        assert float(jnp.abs(a - b).max()) < 1e-4 * float(jnp.abs(b).max())
    assert not np.asarray(g_got[0][17:]).any()  # rows of no group: no gradient


def test_an_expert_layer_at_widths_of_no_whole_tile_is_the_references():
    """A model width and an expert width that are whole tiles of nothing the
    grouped product likes (640 and 200; the published 2,688 is 21 x 128): the
    layer with every expert held and a shared expert of a width of its own
    (328), against the reference's, row by row."""
    cfg = nemotron_tiny(d_model=640, moe_d_ff=200, moe_shared_d_ff=328,
                        moe_experts_held=0, moe_expert_offset=0)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 640), jnp.float32)
    params = unbox(MoEFeedForward(cfg).init(jax.random.PRNGKey(3), x)["params"])
    bias = jax.random.normal(jax.random.PRNGKey(4), (cfg.moe_experts,)) * 0.01
    config = dict(reference_config(cfg), expert_offset=0,
                  n_routed_experts=cfg.moe_experts)
    plain = {"router": params["w_router"], "bias": bias,
             "shared": {"w_up": params["shared"]["w_up"],
                        "w_down": params["shared"]["w_down"]},
             "experts": {"w_up": params["w_up"], "w_down": params["w_down"]}}
    with jax.default_matmul_precision("highest"):
        y, _ = MoEFeedForward(cfg).apply(
            {"params": params, "router_state": {"bias": bias}}, x)
        for row in range(2):
            want, _ = ref.expert_layer(plain, x[row], config)
            assert float(jnp.abs(y[row] - want).max()) < 1e-5 * max(
                float(jnp.abs(want).max()), 1.0)


def test_the_step_moves_the_selection_bias_and_counts_the_routing():
    cfg = nemotron_tiny()
    trainer = CheetahTrainer(cfg, make_mesh(None, devices=jax.devices()[:1]))
    state = trainer.init_state(jax.random.PRNGKey(0))
    state, metrics = trainer.train_step(state, TOKENS, jnp.ones_like(TOKENS))
    assert int(metrics["moe_dropped"]) == 0
    assert 0 < int(metrics["moe_assignments_held"]) <= 4 * 3 * 128
    biases = [b for b in jax.tree.leaves(state.model_state)]
    assert len(biases) == 4 and all(
        float(jnp.abs(b).max()) == pytest.approx(cfg.moe_bias_rate) for b in biases)


# ---------------------------------------------------------------------------
# what does not run with the new layers says so by name
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("change, message", [
    (dict(objective="block_diffusion", bd_block=4, bd_mask_token=95,
          pos_emb="rope"), "block_diffusion does not run with a state-space"),
    (dict(hc_mult=4), "layer_pattern does not run with hyper-connections"),
    (dict(layer_pattern="*E*E*E*E*", hc_mult=4),
     "layer_pattern does not run with hyper-connections"),
], ids=["block_diffusion", "streams", "streams_without_a_mixer"])
def test_the_configuration_refuses_by_name(change, message):
    with pytest.raises(NotImplementedError, match=message):
        nemotron_tiny(**change)


def test_sequence_sharding_and_the_pipeline_refuse_by_name():
    from fedml_tpu.parallel.pipeline import PipelineCheetah

    cfg = nemotron_tiny()
    mesh = make_mesh({"sequence": 2}, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="under sequence sharding"):
        CheetahTrainer(cfg, mesh, seq_sharded=True)
    pipe = make_mesh({"pipeline": 2}, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="layer_pattern"):
        PipelineCheetah(dataclasses.replace(cfg, pos_emb="rope"), pipe)
    # a padding mask: the recurrence takes whole sequences
    params, state = seeded(cfg, TOKENS, moved=False)
    with pytest.raises(NotImplementedError, match="no padding mask"):
        Transformer(cfg).apply({"params": params, **state}, TOKENS,
                               mask=jnp.ones_like(TOKENS))


def test_an_mtp_module_beside_a_pattern_is_attention_and_an_expert_layer():
    """The module's block is the uniform one (``attn_kind`` then a
    feed-forward part), whatever the pattern: it builds and trains."""
    cfg = nemotron_tiny(mtp_layers=1)
    trainer = CheetahTrainer(cfg, make_mesh(None, devices=jax.devices()[:1]))
    state = trainer.init_state(jax.random.PRNGKey(0))
    block = state.params["mtp"]["Block_0"]
    assert {"Attention_0", "MoEFeedForward_0"} <= set(block)
    _, metrics = trainer.train_step(state, TOKENS, jnp.ones_like(TOKENS))
    assert np.isfinite(float(metrics["loss"]))


# ---------------------------------------------------------------------------
# the trainer
# ---------------------------------------------------------------------------


def test_cheetah_init_says_the_pattern_the_mixer_and_the_activation(tmp_path):
    from fedml_tpu.core import mlops

    class Args:
        enable_tracking, tracking_dir, run_id = True, str(tmp_path), "nemo"
        rank = 0

    mlops.init(Args())
    try:
        trainer = CheetahTrainer(nemotron_tiny(),
                                 make_mesh(None, devices=jax.devices()[:1]))
        trainer.init_state(jax.random.PRNGKey(0))
        (event,) = [e for e in mlops.read_events()
                    if e.get("kind") == "cheetah_init"]
    finally:
        mlops.close()
    assert event["layer_pattern"] == PATTERN and event["ffn_act"] == "relu2"
    assert event["ssd"] == {"heads": 8, "head_dim": 12, "groups": 2,
                            "state": 16, "chunk": 16, "path": "xla"}
    assert event["mixers"] == "ssd,none,ssd,none,ssd,none,ssd,gqa,none"
    assert event["layers"] == ["none", "moe"] * 3 + ["none", "none", "moe"]
    assert event["kda_path"] == "" and event["attn_mask"]["kind"] == "causal"


def test_training_lowers_the_loss_in_bfloat16_under_remat():
    cfg = nemotron_tiny(dtype=jnp.bfloat16, remat=True)
    from fedml_tpu.parallel.train_step import make_optimizer

    trainer = CheetahTrainer(
        cfg, make_mesh(None, devices=jax.devices()[:1]),
        optimizer=make_optimizer(learning_rate=3e-3, warmup_steps=2,
                                 total_steps=100))
    state = trainer.init_state(jax.random.PRNGKey(0))
    losses = []
    for _ in range(8):
        state, metrics = trainer.train_step(state, TOKENS, jnp.ones_like(TOKENS))
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.05


# ---------------------------------------------------------------------------
# the other configurations' steps
# ---------------------------------------------------------------------------

# sha256 of ``lower_step(..).as_text()``, normalised as tests/test_xing4.py
# normalises it, at PR 38's parent commit (5cdc529): a configuration with the
# block-diffusion objective over GQA heads of their own size with q/k norms
# and the softmax router's top-4 (SDAR-shaped). The layer pattern, the
# sublayer list, the activation by name and the shared convolution leave such
# a program as it was. (The Mistral-, Xing4-, Ling- and Switch-shaped hashes
# are tests/test_xing4.py's, tests/test_ling3.py's and tests/test_sdar.py's,
# unedited.)
SDAR_SHAPED_STEP = (
    "19797c8ae2593d7842b2f5eaedfe14ae9fa443a219bdb2df25a2009e3fbbb88f")


def _normalised(text: str) -> str:
    text = re.sub(r"sdy\.sharding = #sdy\.sharding<[^>]*>,? ?", "", text)
    return re.sub(r"@(_?[A-Za-z_]+)_\d+", r"@\1", text)


def test_an_sdar_shaped_step_lowers_as_before():
    cfg = TransformerConfig(
        vocab_size=96, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=64, remat=True, attn_impl="xla", norm_eps=1e-6,
        attn_head_dim=32, qk_norm=True, moe_experts=16, moe_top_k=4,
        moe_capacity_factor=0.0, moe_router="softmax", moe_d_ff=32,
        moe_experts_held=4, moe_expert_offset=4, objective="block_diffusion",
        bd_block=4, bd_mask_token=95)
    trainer = CheetahTrainer(cfg, make_mesh(None, devices=jax.devices()[:1]))
    state = trainer.init_state(jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 64), jnp.int32)
    text = trainer.lower_step(state, tokens, jnp.ones_like(tokens)).as_text()
    assert hashlib.sha256(_normalised(text).encode()).hexdigest() == \
        SDAR_SHAPED_STEP
