"""Kimi delta attention beside latent attention in one stack, group-limited
routing and the layer list they need (``parallel/kda.py``,
``parallel/transformer.py``, ``parallel/moe.py``): the chunked form and its
kernels against the token recurrence, the model against its plain reference
(``benchmark/reference/ling3.py``) on seeded weights, the shares of the expert
layer against the whole, and that a configuration without the new fields
builds the program it built. CPU, small sizes."""

import dataclasses
import functools
import hashlib
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from fedml_tpu.core import mlops
from fedml_tpu.parallel import kda
from fedml_tpu.parallel import moe as moe_mod
from fedml_tpu.parallel import transformer as tfm
from fedml_tpu.parallel.context import mesh_context
from fedml_tpu.parallel.sharding import make_mesh, unbox
from fedml_tpu.parallel.train_step import CheetahTrainer
from fedml_tpu.parallel.transformer import Transformer, TransformerConfig

ref = harness.load_module(harness.ROOT, "reference", "ling3")


def ling_tiny(**kw) -> TransformerConfig:
    """Ling-3.0's stack at width 64: 1 dense + 6 expert layers, a period of
    six (layer 5 latent attention, KDA elsewhere), 16 routed experts in 4
    groups of which 2 are kept, 4 a token, experts 0 and 1 held."""
    base = dict(
        vocab_size=96, d_model=64, n_layers=7, n_heads=4, n_kv_heads=4,
        d_ff=160, max_seq_len=64, remat=False, attn_impl="xla",
        norm_eps=1e-6, rope_theta=6e6, dtype=jnp.float32, attn_kind="mla",
        q_lora_rank=0, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, layer_group_size=6,
        kda_head_dim=16, first_k_dense=1, moe_experts=16, moe_top_k=4,
        moe_capacity_factor=0.0, moe_router="sigmoid", moe_n_group=4,
        moe_topk_group=2, moe_routed_scale=2.5, moe_d_ff=32,
        moe_shared_experts=1, moe_experts_held=2, moe_expert_offset=0)
    base.update(kw)
    return TransformerConfig(**base)


def reference_config(cfg: TransformerConfig) -> dict:
    """``cfg`` under the published keys the reference reads."""
    return dict(
        hidden_size=cfg.d_model, num_attention_heads=cfg.n_heads,
        head_dim=cfg.kda_head_dim, q_lora_rank=None,
        kv_lora_rank=cfg.kv_lora_rank, qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        rms_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
        layer_group_size=cfg.layer_group_size,
        short_conv_kernel_size=cfg.kda_conv_size,
        kda_lower_bound=cfg.kda_lower_bound,
        num_experts_per_tok=cfg.moe_top_k, num_experts=cfg.experts_held,
        expert_offset=cfg.moe_expert_offset, router_experts=cfg.moe_experts,
        n_group=cfg.moe_n_group, topk_group=cfg.moe_topk_group,
        routed_scaling_factor=cfg.moe_routed_scale)


def seeded(cfg: TransformerConfig, tokens):
    """The model's initial variables with every norm weight, ``A_log`` and the
    selection bias moved, so that what is inert at initialisation is checked
    too."""
    variables = Transformer(cfg).init(jax.random.PRNGKey(0), tokens)
    params = unbox(variables["params"])
    state = {"router_state": unbox(variables["router_state"])}

    def move(path, p):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        key = jax.random.PRNGKey(sum(map(ord, name)))
        if "norm" in name.lower() or name.endswith("A_log"):
            return p + 0.3 * jax.random.normal(key, p.shape)
        return p

    params = jax.tree_util.tree_map_with_path(move, params)
    state = jax.tree.map(
        lambda b: 0.2 * jax.random.normal(jax.random.PRNGKey(7), b.shape), state)
    return params, state


TOKENS = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 96)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


# ---------------------------------------------------------------------------
# the chunked form against the recurrence
# ---------------------------------------------------------------------------


def kda_inputs(seed, B, T, H, dk, dv, gate="random", beta="random"):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = ref.l2norm(jax.random.normal(ks[0], (B, T, H, dk))) * dk ** -0.5
    k = ref.l2norm(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = (jnp.full((B, T, H, dk), -5.0) if gate == "bound" else
         -5.0 * jax.nn.sigmoid(2 * jax.random.normal(ks[3], (B, T, H, dk))))
    b = {"random": jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H))),
         "near_one": jnp.full((B, T, H), 0.999),
         "near_zero": jnp.full((B, T, H), 1e-3)}[beta]
    return q, k, v, g, b


recurrence = jax.vmap(ref.delta_rule_recurrence)


def _both(fn, weights, *inputs):
    """Outputs and the gradients of a seeded linear functional of them."""
    def functional(*a):
        o, S = fn(*a)
        return jnp.sum(o * weights[0]) + jnp.sum(S * weights[1])

    return fn(*inputs), jax.grad(functional, argnums=(0, 1, 2, 3, 4))(*inputs)


@pytest.mark.parametrize("beta", ["random", "near_one", "near_zero"])
@pytest.mark.parametrize("gate", ["random", "bound"])
@pytest.mark.parametrize("chunk", [16, 64])
def test_chunked_form_is_the_recurrence(chunk, gate, beta):
    """Output, final state and every gradient, with the log decay at its
    bound of -5 over whole chunks (a 64-token chunk's running sum reaches
    -320) and beta near 0 and near 1. The decay's gradient at the bound is a
    sum of terms that nearly cancel, so it gets 1e-3; the rest 3e-5."""
    B, T, H, dk, dv = 2, 128, 2, 32, 16
    inputs = kda_inputs(0, B, T, H, dk, dv, gate, beta)
    weights = (jax.random.normal(jax.random.PRNGKey(9), (B, T, H, dv)),
               jax.random.normal(jax.random.PRNGKey(8), (B, H, dk, dv)))
    (o, S), grads = _both(recurrence, weights, *inputs)
    (o2, S2), grads2 = _both(
        lambda *a: kda.kda_chunked(*a, chunk=chunk), weights, *inputs)
    assert bool(jnp.isfinite(o2).all())
    assert _rel(o2, o) < 1e-5 and _rel(S2, S) < 1e-5
    for name, got, want in zip("q k v g beta".split(), grads2, grads):
        assert bool(jnp.isfinite(got).all()), name
        assert _rel(got, want) < (1e-3 if name == "g" else 3e-5), name


def test_a_sequence_that_is_no_whole_number_of_chunks_is_refused():
    inputs = kda_inputs(0, 1, 96, 2, 32, 16)
    with pytest.raises(ValueError, match="seq_len 96 .* chunk 64"):
        kda.kda_chunked(*inputs, chunk=64)


def test_triangular_inverse_and_its_rule():
    A = jnp.tril(0.2 * jax.random.normal(jax.random.PRNGKey(0), (3, 64, 64)), -1)
    T = kda.unit_lower_inverse(A)
    want = jnp.linalg.inv(jnp.eye(64) + A)
    assert _rel(T, want) < 1e-4
    w = jax.random.normal(jax.random.PRNGKey(1), A.shape)
    got = jax.grad(lambda a: jnp.sum(kda.unit_lower_inverse(a) * w))(A)
    plain = jax.grad(lambda a: jnp.sum(
        jnp.linalg.inv(jnp.eye(64) + jnp.tril(a, -1)) * w))(A)
    assert _rel(got, plain) < 1e-3


@pytest.fixture
def interpreted_kernels():
    """``kda_chunk_fwd`` / ``kda_chunk_bwd`` run by Pallas' interpreter under
    the rule the TPU path has."""
    @jax.custom_vjp
    def scan(*ops):
        return kda.chunk_fwd(*ops, save_states=False, interpret=True)

    def fwd(*ops):
        O, ST, states = kda.chunk_fwd(*ops, save_states=True, interpret=True)
        return (O, ST), (ops[:6], states)

    scan.defvjp(fwd, lambda saved, ct: kda.chunk_bwd(
        *saved[0], saved[1], *ct, interpret=True))
    return scan


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_kernels_are_the_plain_scan_and_the_recurrence(dtype, interpreted_kernels):
    """At head sizes and chunk counts that fill the kernels' blocks: the
    interpreted kernels against the plain scan in the same dtype, forward,
    final state and every gradient; in float32 against the recurrence too."""
    B, T, H, dk, dv = 1, 1024, 4, 128, 128
    inputs = kda_inputs(1, B, T, H, dk, dv)
    weights = (jax.random.normal(jax.random.PRNGKey(9), (B, T, H, dv)),
               jax.random.normal(jax.random.PRNGKey(8), (B, H, dk, dv)))
    (o, S), grads = _both(lambda *a: kda.kda_chunked(
        *a, dtype=dtype, scan=kda._scan_plain), weights, *inputs)
    (o2, S2), grads2 = _both(lambda *a: kda.kda_chunked(
        *a, dtype=dtype, scan=interpreted_kernels), weights, *inputs)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    assert _rel(o2.astype(jnp.float32), o.astype(jnp.float32)) < tol
    assert _rel(S2, S) < tol
    for got, want in zip(grads2, grads):
        assert _rel(got, want) < tol
    if dtype == jnp.float32:
        (o3, S3), grads3 = _both(recurrence, weights, *inputs)
        assert _rel(o2, o3) < 1e-5 and _rel(S2, S3) < 1e-5
        for got, want in zip(grads2, grads3):
            assert _rel(got, want) < 1e-4


# --- the intra-chunk preparation as kernels ---------------------------------

FILLS_THE_BLOCKS = (1, 1024, 4, 128, 128)   # B, T, H, dk, dv
PREPARED = ("Qg", "Kd", "W", "U", "Aqk", "d")
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


@functools.lru_cache(maxsize=None)
def prepared(dtype, gate, beta):
    """The plain preparation and the interpreted kernels on the same seeded
    inputs, and both backward passes at the same seeded cotangents (the
    bfloat16 case also carries the float32 gradients)."""
    dt = DTYPES[dtype]
    inputs = kda_inputs(1, *FILLS_THE_BLOCKS, gate, beta)
    want, vjp = jax.vjp(
        lambda *a: kda.prepare_plain(*a, kda.KDA_CHUNK, dt), *inputs)
    got = kda.intra_fwd(*inputs, kda.KDA_CHUNK, dt, interpret=True)
    cotangents = tuple(
        jax.random.normal(jax.random.PRNGKey(10 + i), w.shape).astype(w.dtype)
        for i, w in enumerate(want))
    grads = kda.intra_bwd(*inputs, *cotangents, kda.KDA_CHUNK, dt,
                          interpret=True)
    exact = None
    if dt != jnp.float32:
        exact = jax.vjp(lambda *a: kda.prepare_plain(
            *a, kda.KDA_CHUNK, jnp.float32), *inputs)[1](
                tuple(c.astype(jnp.float32) for c in cotangents))
    return got, want, grads, vjp(cotangents), exact


CASES = [(d, g, b) for d in DTYPES for g in ("random", "bound")
         for b in ("random", "near_one", "near_zero")]


@pytest.mark.parametrize("dtype,gate,beta", CASES)
def test_intra_fwd_is_the_plain_preparation(dtype, gate, beta):
    """``kda_intra_fwd`` under the interpreter, at sizes that fill its
    blocks, output by output."""
    got, want, _, _, _ = prepared(dtype, gate, beta)
    tol = 1e-5 if dtype == "float32" else 2e-2
    for name, a, b in zip(PREPARED, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert bool(jnp.isfinite(a.astype(jnp.float32)).all()), name
        assert _rel(a.astype(jnp.float32), b.astype(jnp.float32)) < tol, name


@pytest.mark.parametrize("dtype,gate,beta", CASES)
def test_intra_bwd_is_the_plain_preparations_vjp(dtype, gate, beta):
    """``kda_intra_bwd``, written by hand, against autodiff of the plain
    preparation at seeded cotangents of all six outputs. The log decay's
    gradient is a sum of terms that nearly cancel: 1e-3 in float32; in
    bfloat16 the two roundings differ by more than that, so there it is held
    to the float32 gradient, no further from it than autodiff's own bfloat16."""
    _, _, got, want, exact = prepared(dtype, gate, beta)
    tol = 1e-5 if dtype == "float32" else 2e-2
    for i, (name, a, b) in enumerate(zip("q k v g beta".split(), got, want)):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert bool(jnp.isfinite(a).all()), name
        if name != "g":
            assert _rel(a, b) < tol, name
        elif dtype == "float32":
            assert _rel(a, b) < 1e-3
        else:
            assert _rel(a, exact[i]) < 1.1 * _rel(b, exact[i]) + 1e-3


@pytest.fixture
def every_kernel_interpreted(monkeypatch):
    """The path a TPU takes, both pairs of kernels under their own
    ``custom_vjp`` rules, run by Pallas' interpreter on this CPU."""
    monkeypatch.setattr(kda, "scan_path", lambda *a, **k: "fused")
    for name in ("intra_fwd", "intra_bwd", "chunk_fwd", "chunk_bwd"):
        monkeypatch.setattr(kda, name, functools.partial(
            getattr(kda, name), interpret=True))


@pytest.mark.parametrize("gate", ["random", "bound"])
def test_every_kernel_is_the_recurrence(gate, every_kernel_interpreted):
    """``kda_chunked`` as ``scan_path`` = ``fused`` builds it, in float32
    against the token recurrence: output, final state and every gradient."""
    B, T, H, dk, dv = FILLS_THE_BLOCKS
    inputs = kda_inputs(2, B, T, H, dk, dv, gate)
    weights = (jax.random.normal(jax.random.PRNGKey(9), (B, T, H, dv)),
               jax.random.normal(jax.random.PRNGKey(8), (B, H, dk, dv)))
    (o, S), grads = _both(recurrence, weights, *inputs)
    (o2, S2), grads2 = _both(kda.kda_chunked, weights, *inputs)
    assert _rel(o2, o) < 1e-5 and _rel(S2, S) < 1e-5
    for name, got, want in zip("q k v g beta".split(), grads2, grads):
        assert bool(jnp.isfinite(got).all()), name
        assert _rel(got, want) < (1e-3 if name == "g" else 1e-4), name


@pytest.mark.parametrize("size,scale", [(16, 0.2), (64, 0.2), (64, 0.5),
                                        (128, 0.1)])
def test_the_kernels_inverse_is_the_inverse(size, scale):
    """``_inverse_tiles`` inside an interpreted kernel against
    ``jnp.linalg.inv``: tiles of one and of several sub-blocks, and entries
    large enough that the inverse's reach the hundreds."""
    from jax.experimental import pallas as pl

    A = jnp.tril(scale * jax.random.normal(
        jax.random.PRNGKey(0), (3, size, size)), -1)

    def kernel(a_ref, t_ref):
        (t_ref[0],) = kda._inverse_tiles([a_ref[0]])

    spec = pl.BlockSpec((1, size, size), lambda i: (i, 0, 0))
    T = pl.pallas_call(kernel, grid=(3,), in_specs=[spec], out_specs=spec,
                       out_shape=jax.ShapeDtypeStruct(A.shape, A.dtype),
                       interpret=True)(A)
    assert _rel(T, jnp.linalg.inv(jnp.eye(size) + A)) < 1e-4
    assert _rel(T, kda.unit_lower_inverse(A)) < 1e-4


def test_scan_path_reads_its_inputs(monkeypatch):
    one = make_mesh(None, devices=jax.devices()[:1])
    four = make_mesh({"fsdp": 4}, devices=jax.devices()[:4])
    assert kda.scan_path(32, 128, 128, 8192, 64, None) == "xla"  # this CPU
    monkeypatch.setattr(
        jax, "devices", lambda *a: [types.SimpleNamespace(platform="tpu")])
    assert kda.scan_path(32, 128, 128, 8192, 64, None) == "fused"
    assert kda.scan_path(32, 128, 128, 8192, 64, one) == "fused"
    assert kda.scan_path(32, 128, 128, 8192, 64, four) == "xla"
    assert kda.scan_path(32, 128, 128, 8192, 64, one, seq_sharded=True) == "xla"
    assert kda.scan_path(32, 64, 128, 8192, 64, None) == "xla"   # lanes
    assert kda.scan_path(2, 128, 128, 8192, 64, None) == "xla"   # heads a step
    assert kda.scan_path(32, 128, 128, 256, 64, None) == "xla"   # chunks a step


def test_one_decision_takes_both_pairs_of_kernels(monkeypatch):
    """Where ``scan_path`` answers ``fused`` the preparation and the scan are
    the kernels, where ``xla`` neither is: the step lowered for a TPU calls
    all four by name, or none."""
    four = make_mesh({"fsdp": 4}, devices=jax.devices()[:4])
    monkeypatch.setattr(
        jax, "devices", lambda *a: [types.SimpleNamespace(platform="tpu")])
    called = []

    def recorded(name, plain):
        def f(*a):
            called.append(name)
            return plain(*a)
        return f

    monkeypatch.setattr(kda, "prepare_fused",
                        recorded("prepare_fused", kda.prepare_plain))
    monkeypatch.setattr(kda, "_scan_fused",
                        recorded("_scan_fused", kda._scan_plain))
    fills = kda_inputs(0, 1, 512, 4, 128, 128)
    assert kda.scan_path(4, 128, 128, 512, 64, None) == "fused"
    kda.kda_chunked(*fills)
    assert called == ["prepare_fused", "_scan_fused"]
    for inputs, mesh in ((kda_inputs(0, 1, 512, 2, 128, 128), None),
                         (kda_inputs(0, 1, 256, 4, 128, 128), None),
                         (fills, four)):
        del called[:]
        with mesh_context(mesh):
            kda.kda_chunked(*inputs)
        assert called == []
    monkeypatch.undo()
    monkeypatch.setattr(
        jax, "devices", lambda *a: [types.SimpleNamespace(platform="tpu")])

    def loss(*a):
        o, S = kda.kda_chunked(*a, dtype=jnp.bfloat16)
        return jnp.sum(o.astype(jnp.float32)) + jnp.sum(S)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).trace(
        *fills).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    for name in ("kda_intra_fwd", "kda_intra_bwd", "kda_chunk_fwd",
                 "kda_chunk_bwd"):
        assert name in text, name


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------


def test_logits_agree_with_the_reference():
    cfg = ling_tiny()
    params, state = seeded(cfg, TOKENS)
    config = reference_config(cfg)
    got = Transformer(cfg).apply({"params": params, **state}, TOKENS,
                                 mutable=["moe_stats", "losses"])[0]
    plain = ref.reference_params(params, config, state["router_state"])
    for row in range(TOKENS.shape[0]):
        want, _, margin = ref.logits_and_loss(plain, TOKENS[row], config)
        assert float(margin.min()) > 1e-5  # both sides choose alike
        assert _rel(got[row], want) < 2e-5


def test_loss_and_gradients_agree_with_the_reference():
    cfg = ling_tiny()
    trainer = CheetahTrainer(cfg, make_mesh(None, devices=jax.devices()[:1]))
    params, state = seeded(cfg, TOKENS)
    config = reference_config(cfg)
    mask = jnp.ones_like(TOKENS)
    (loss, _), grads = jax.value_and_grad(trainer._loss_fn, has_aux=True)(
        params, state, TOKENS, mask)

    def plain_loss(p):
        plain = ref.reference_params(p, config, state["router_state"])
        total = sum(ref.logits_and_loss(plain, TOKENS[row], config)[1]
                    for row in range(TOKENS.shape[0]))
        return total / (TOKENS.shape[0] * (TOKENS.shape[1] - 1))

    want, want_grads = jax.value_and_grad(plain_loss)(params)
    assert abs(float(loss) - float(want)) < 1e-5
    flat = jax.tree_util.tree_leaves_with_path(grads)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    scale = max(float(jnp.linalg.norm(g)) for _, g in flat)
    for path, g in flat:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        w = flat_want[path]
        assert float(jnp.linalg.norm(g - w)) < 2e-4 * max(
            float(jnp.linalg.norm(w)), 1e-3 * scale), name
    for leaf in ("A_log", "dt_bias", "conv", "wbg", "wf", "o_norm"):
        assert any(str(getattr(p[-1], "key", "")) == leaf
                   and float(jnp.linalg.norm(g)) > 0 for p, g in flat), leaf


MISTAKES = ("decay_gate_at_one", "beta_at_one", "no_convolution",
            "no_output_gate", "no_group_mask", "unscaled_gate")


@pytest.mark.parametrize("mistake", MISTAKES)
def test_one_mistake_in_the_reference_is_seen(mistake, monkeypatch):
    """The comparison sees each mechanism: the reference with one of them
    left out moves the logits by far more than the tolerance above."""
    cfg = ling_tiny()
    params, state = seeded(cfg, TOKENS)
    config = reference_config(cfg)
    got = Transformer(cfg).apply({"params": params, **state}, TOKENS,
                                 mutable=["moe_stats", "losses"])[0][0]
    plain = ref.reference_params(params, config, state["router_state"])
    if mistake == "decay_gate_at_one":
        monkeypatch.setattr(ref, "delta_rule_recurrence",
                            lambda q, k, v, g, b, f=ref.delta_rule_recurrence:
                            f(q, k, v, 0 * g, b))
    elif mistake == "beta_at_one":
        monkeypatch.setattr(ref, "delta_rule_recurrence",
                            lambda q, k, v, g, b, f=ref.delta_rule_recurrence:
                            f(q, k, v, g, 0 * b + 1))
    elif mistake == "no_convolution":
        monkeypatch.setattr(ref, "causal_depthwise_conv", lambda x, taps: x)
    elif mistake == "no_output_gate":
        for layer in plain["layers"]:
            if "wg" in layer["mixer"]:
                layer["mixer"]["wg"] = 0 * layer["mixer"]["wg"]
    elif mistake == "no_group_mask":
        config = dict(config, n_group=1, topk_group=1)
    elif mistake == "unscaled_gate":
        config = dict(config, routed_scaling_factor=1.0)
    want = ref.logits_and_loss(plain, TOKENS[0], config)[0]
    assert _rel(got, want) > 1e-2


# ---------------------------------------------------------------------------
# routing in groups, and the shares of the expert layer
# ---------------------------------------------------------------------------


def test_group_limited_routing_is_the_reference_rule():
    cfg = ling_tiny()
    x = jax.random.normal(jax.random.PRNGKey(3), (200, 16))  # router logits
    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(4), (16,))
    expert, gate, _ = moe_mod.route(cfg, x, bias)
    p = {"router": jnp.eye(16), "bias": bias}
    selected, weights, margin = ref.route(p, x, reference_config(cfg))
    assert float(margin.min()) > 1e-6
    assert (jnp.sort(expert, -1) == jnp.sort(selected, -1)).all()
    order, order_ref = jnp.argsort(expert, -1), jnp.argsort(selected, -1)
    assert _rel(jnp.take_along_axis(gate, order, -1),
                jnp.take_along_axis(weights, order_ref, -1)) < 1e-6
    # every choice lies in one of the two groups kept
    assert (jnp.unique(expert // 4, axis=None).size <= 4
            and all(len(set(np.asarray(row) // 4)) <= 2 for row in expert))
    # and differs from the ungrouped rule for some token
    plain, _, _ = moe_mod.route(
        dataclasses.replace(cfg, moe_n_group=1, moe_topk_group=1), x, bias)
    assert bool((jnp.sort(plain, -1) != jnp.sort(expert, -1)).any())


def test_one_group_routes_as_before_to_the_bit():
    """``moe_n_group`` 1 is the rule the parent had: top k of ``s + b``."""
    cfg = ling_tiny(moe_n_group=1, moe_topk_group=1)
    x = jax.random.normal(jax.random.PRNGKey(3), (200, 16))
    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(4), (16,))
    expert, gate, _ = moe_mod.route(cfg, x, bias)
    s = jax.nn.sigmoid(x)
    _, want = jax.lax.top_k(s + bias, 4)
    chosen = jnp.take_along_axis(s, want, axis=-1)
    want_gate = chosen / jnp.maximum(chosen.sum(-1, keepdims=True), 1e-9) * 2.5
    assert (expert == want).all() and (gate == want_gate).all()


def test_the_routing_margin_sees_a_group_about_to_change():
    """Scores built so that group 0 (where the held experts live) is the
    last group kept by a hair: the margin is that hair."""
    config = reference_config(ling_tiny())
    logits = jnp.full((1, 16), -3.0)
    logits = logits.at[0, 0:2].set(jnp.array([1.0, 0.9]))       # group 0
    logits = logits.at[0, 4:6].set(jnp.array([2.0, 1.9]))       # group 1: kept
    logits = logits.at[0, 8:10].set(jnp.array([1.0, 0.899]))    # group 2: out
    p = {"router": jnp.eye(16), "bias": jnp.zeros(16)}
    selected, _, margin = ref.route(p, logits, config)
    assert set(np.asarray(selected[0])) == {0, 1, 4, 5}
    s = jax.nn.sigmoid(jnp.array([0.9, 0.899]))
    assert abs(float(margin[0]) - float(s[0] - s[1])) < 1e-6


def test_the_shares_of_an_expert_layer_add_up_to_the_whole():
    """Eight chips' shares of a 16-expert layer (each holds 2, routes over
    all 16 in 4 groups of which 2 are kept), the shared expert counted once,
    equal the layer that holds every expert, and the uncut reference's."""
    whole_cfg = ling_tiny(moe_experts_held=0)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 64))
    layer = moe_mod.MoEFeedForward(whole_cfg)
    variables = layer.init(jax.random.PRNGKey(0), x)
    whole = unbox(variables["params"])
    state = {"router_state": {"bias": 0.2 * jax.random.normal(
        jax.random.PRNGKey(7), (16,))}}
    (y_whole, _), _ = layer.apply({"params": whole, **state}, x,
                                  mutable=["moe_stats"])
    shared = tfm.FeedForward(whole_cfg, d_ff=32).apply(
        {"params": whole["shared"]}, x)
    total = jnp.zeros_like(y_whole)
    for share in range(8):
        cfg = ling_tiny(moe_experts_held=2, moe_expert_offset=2 * share)
        part = dict(whole, w_gate_up=whole["w_gate_up"][2 * share:2 * share + 2],
                    w_down=whole["w_down"][2 * share:2 * share + 2])
        (y, _), _ = moe_mod.MoEFeedForward(cfg).apply(
            {"params": part, **state}, x, mutable=["moe_stats"])
        total = total + (y - shared)
    assert float(jnp.abs(total + shared - y_whole).max()) < 1e-5
    # the uncut reference's layer, row by row
    half = whole["w_gate_up"].shape[-1] // 2
    p = {"router": whole["w_router"], "bias": state["router_state"]["bias"],
         "shared": {"w_gate": whole["shared"]["w_gate_up"][:, :32],
                    "w_up": whole["shared"]["w_gate_up"][:, 32:],
                    "w_down": whole["shared"]["w_down"]},
         "experts": {"w_gate": whole["w_gate_up"][..., :half],
                     "w_up": whole["w_gate_up"][..., half:],
                     "w_down": whole["w_down"]}}
    config = dict(reference_config(whole_cfg), num_experts=16, expert_offset=0)
    for row in range(2):
        want, _ = ref.expert_layer(p, x[row], config)
        assert float(jnp.abs(y_whole[row] - want).max()) < 1e-5


# ---------------------------------------------------------------------------
# the layer list and what cannot be built
# ---------------------------------------------------------------------------


def test_the_published_layer_list():
    cfg = TransformerConfig(
        n_layers=42, attn_kind="mla", kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, layer_group_size=6,
        kda_head_dim=128, first_k_dense=2, moe_experts=512, moe_top_k=8,
        moe_router="sigmoid", moe_n_group=8, moe_topk_group=4)
    mla = [i for i, m in enumerate(cfg.mixers) if m == "mla"]
    assert mla == [5, 11, 17, 23, 29, 35, 41]
    assert set(cfg.mixers) == {"mla", "kda"} and cfg.mixers.count("kda") == 35
    assert cfg.layer_kinds == ("dense",) * 2 + ("moe",) * 40
    assert all(ref.mixer_of(i, {"layer_group_size": 6}) == m
               for i, m in enumerate(cfg.mixers))
    # one mixer for the whole stack without the key
    assert set(dataclasses.replace(cfg, layer_group_size=0).mixers) == {"mla"}
    assert set(TransformerConfig.tiny().mixers) == {"gqa"}


def test_latent_attention_without_a_q_lora_builds():
    cfg = ling_tiny(layer_group_size=0, n_layers=2)
    params = unbox(Transformer(cfg).init(jax.random.PRNGKey(0), TOKENS)["params"])
    attn = params["Block_0"]["LatentAttention_0"]
    assert set(attn) == {"wq", "wkv_a", "kv_norm", "wkv_b", "wo"}
    assert attn["wq"].shape == (64, 4 * 24)


def test_what_cannot_be_built_is_refused():
    with pytest.raises(ValueError, match="kda_head_dim"):
        ling_tiny(kda_head_dim=0)
    with pytest.raises(ValueError, match="layer_group_size"):
        ling_tiny(attn_kind="kda")
    with pytest.raises(ValueError, match="group-limited routing"):
        ling_tiny(moe_n_group=3)
    with pytest.raises(ValueError, match="group-limited routing"):
        ling_tiny(moe_topk_group=1, moe_top_k=8)
    with pytest.raises(ValueError, match="sigmoid router"):
        ling_tiny(moe_router="softmax", moe_top_k=2)
    from fedml_tpu.parallel.pipeline import PipelineCheetah

    with pytest.raises(NotImplementedError, match="mixer chosen per layer"):
        PipelineCheetah(ling_tiny(), make_mesh(
            {"pipeline": 2}, devices=jax.devices()[:2]))


def test_a_whole_stack_of_the_linear_mixer_trains():
    cfg = ling_tiny(attn_kind="kda", layer_group_size=0, n_layers=2,
                    moe_experts=0, first_k_dense=0, moe_n_group=1,
                    moe_router="softmax", moe_experts_held=0)
    assert cfg.mixers == ("kda", "kda")
    trainer = CheetahTrainer(cfg, make_mesh(None, devices=jax.devices()[:1]))
    state = trainer.init_state(jax.random.PRNGKey(0))
    losses = []
    for _ in range(3):
        state, metrics = trainer.train_step(state, TOKENS, jnp.ones_like(TOKENS))
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


# ---------------------------------------------------------------------------
# the step: what it reports, and what it leaves as it was
# ---------------------------------------------------------------------------


def test_the_step_trains_and_reports_its_mixers(monkeypatch):
    cfg = ling_tiny(dtype=jnp.bfloat16, remat=True)
    one = make_mesh(None, devices=jax.devices()[:1])
    events = []
    monkeypatch.setattr(mlops, "_emit", events.append)
    trainer = CheetahTrainer(cfg, one)
    state = trainer.init_state(jax.random.PRNGKey(0))
    (event,) = [e for e in events if e["kind"] == "cheetah_init"]
    assert event["mixers"] == "kda,kda,kda,kda,kda,mla,kda"
    assert event["kda_path"] == trainer.kda_path == "xla"  # this CPU
    assert event["kda_chunk"] == kda.KDA_CHUNK
    assert event["layers"] == ["dense"] + ["moe"] * 6
    losses = []
    for _ in range(3):
        state, metrics = trainer.train_step(state, TOKENS, jnp.ones_like(TOKENS))
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    assert int(metrics["moe_dropped"]) == 0
    # 2 held of 16, 4 choices a token, 6 expert layers: what reached them
    assert 0 < int(metrics["moe_assignments_held"]) <= 6 * 4 * TOKENS.size
    text = trainer.lower_step(state, TOKENS, jnp.ones_like(TOKENS)).as_text(
        debug_info=True)
    for scope in ("kda", "kda_conv", "kda_gate", "kda_chunk", "mla",
                  "moe_route"):
        assert f"/{scope}/" in text or f"{scope}\"" in text, scope
    # a trainer without such a layer reports none, and a TPU of several
    # devices the XLA form
    plain = CheetahTrainer(TransformerConfig.tiny(), one)
    assert plain.kda_path == ""
    monkeypatch.setattr(
        jax, "devices", lambda *a: [types.SimpleNamespace(platform="tpu")])
    wide = dataclasses.replace(cfg, kda_head_dim=128, max_seq_len=512)
    assert CheetahTrainer(wide, one).kda_path == "fused"


def test_the_programs_flops_count_the_mixers():
    cfg = ling_tiny()
    by_hand = 0.0
    D, H, hd, C = 64, 4, 16, kda.KDA_CHUNK
    kda_layer = (2 * D * (4 * H * hd + 2 * H) + 2 * H * hd * D
                 + 2 * 4 * 3 * H * hd
                 + H * (2 * C * 5 * hd + 2 * C * C / 3 + 6 * hd * hd))
    mla_layer = (2 * (D * H * 24 + D * (16 + 8) + 16 * H * 32 + H * 16 * D)
                 + 2 * H * (24 + 16) * (64 + 1) / 2)
    expert = 2 * D * 16 + (1 + 4 * 2 / 16) * 6 * D * 32
    by_hand = 6 * kda_layer + mla_layer + 6 * D * 160 + 6 * expert + 2 * D * 96
    assert tfm.train_flops_per_token(cfg, 64) == pytest.approx(3 * by_hand)


# sha256 of ``lower_step(..).as_text()``, normalised as tests/test_xing4.py
# normalises it, at PR 32's parent commit (fbc8b72) for a configuration with
# latent attention behind a q-LoRA, sigmoid routing over one group, a shared
# expert, hyper-connections and an MTP module: the mixer per layer, the
# full-rank query and the group step leave such a program as it was. Taken
# again at PR 37, which rewrote the expert layer's dispatch and combine and
# so every program that holds one (tests/test_moe.py holds that change).
XING_SHAPED_STEP = (
    "60206a61e42da38861558efb61506b6384dca8f1c97077eef545ff3309146666")


def _normalised(text: str) -> str:
    text = re.sub(r"sdy\.sharding = #sdy\.sharding<[^>]*>,? ?", "", text)
    return re.sub(r"@(_?[A-Za-z_]+)_\d+", r"@\1", text)


def xing_shaped() -> TransformerConfig:
    return TransformerConfig(
        vocab_size=96, d_model=64, n_layers=3, n_heads=4, n_kv_heads=4,
        d_ff=160, max_seq_len=128, remat=True, attn_impl="xla",
        norm_eps=1e-6, attn_kind="mla", q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        rope_factor=64.0, rope_original_max_pos=32, rope_mscale=1.0,
        rope_mscale_all_dim=1.0, first_k_dense=1, moe_experts=16, moe_top_k=4,
        moe_capacity_factor=0.0, moe_router="sigmoid", moe_routed_scale=2.0,
        moe_d_ff=32, moe_shared_experts=1, moe_experts_held=4,
        moe_expert_offset=4, hc_mult=4, hc_sinkhorn_iters=2, mtp_layers=1)


def test_a_configuration_without_the_new_fields_lowers_as_before():
    trainer = CheetahTrainer(xing_shaped(),
                             make_mesh(None, devices=jax.devices()[:1]))
    state = trainer.init_state(jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 64), jnp.int32)
    text = trainer.lower_step(state, tokens, jnp.ones_like(tokens)).as_text()
    assert hashlib.sha256(_normalised(text).encode()).hexdigest() == \
        XING_SHAPED_STEP
