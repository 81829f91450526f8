"""Block-diffusion training over a softmax top-k expert stack with per-head
q/k norms and a head size of its own (ISSUE 34: SDAR-30B-A3B-Chat's block), at
a small size on XLA:CPU: the program against the plain reference
(``benchmark/reference/sdar.py``) on seeded weights, the structured mask in
its three forms (the definition, the dense array, the splash kernel's mask
object), the noise, the shares of the expert layer against the whole, and the
step programs that must lower as they did."""

from __future__ import annotations

import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from fedml_tpu.core import mlops
from fedml_tpu.parallel import block_diffusion as bd
from fedml_tpu.parallel import transformer as tfm
from fedml_tpu.parallel.moe import MoEFeedForward, route
from fedml_tpu.parallel.sharding import make_mesh, unbox
from fedml_tpu.parallel.train_step import CheetahTrainer
from fedml_tpu.parallel.transformer import Transformer, TransformerConfig

ref = harness.load_module(harness.ROOT, "reference", "sdar")

L, B, V, MASK = 64, 4, 96, 95


def sdar_tiny(**kw) -> TransformerConfig:
    """SDAR's block at width 64: 2 expert layers, 4 query heads and 2
    key/value heads of 32 (heads x head size = 128, twice the width), q/k
    norms, softmax top-4 of 16 with experts 4 to 7 held, blocks of 4."""
    base = dict(
        vocab_size=V, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=L, remat=False, attn_impl="xla", norm_eps=1e-6,
        rope_theta=1e6, dtype=jnp.float32, attn_head_dim=32, qk_norm=True,
        moe_experts=16, moe_top_k=4, moe_capacity_factor=0.0,
        moe_router="softmax", moe_aux_weight=0.001, moe_d_ff=32,
        moe_experts_held=4, moe_expert_offset=4,
        objective="block_diffusion", bd_block=B, bd_mask_token=MASK)
    base.update(kw)
    return TransformerConfig(**base)


def reference_config(cfg: TransformerConfig) -> dict:
    """``cfg`` under the published keys the reference reads."""
    return dict(
        hidden_size=cfg.d_model, num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        rms_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
        num_experts_per_tok=cfg.moe_top_k, num_experts=cfg.experts_held,
        expert_offset=cfg.moe_expert_offset, router_experts=cfg.moe_experts,
        block_length=cfg.bd_block, aux_weight=cfg.moe_aux_weight)


def _name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def seeded(cfg: TransformerConfig, moved: bool = True):
    """The model's initial parameters; ``moved`` perturbs every norm weight
    (ones at initialisation, where a norm left out of one side would not
    show)."""
    rows = jnp.zeros((1, 2 * L), jnp.int32)
    params = unbox(Transformer(cfg).init(jax.random.PRNGKey(0), rows)["params"])
    if not moved:
        return params

    def move(path, p):
        name = _name(path)
        if "norm" in name.lower():
            key = jax.random.PRNGKey(sum(map(ord, name)))
            return p + 0.3 * jax.random.normal(key, p.shape)
        return p

    return jax.tree_util.tree_map_with_path(move, params)


# data tokens under the mask token's id, and one draw of the noise
X0 = jax.random.randint(jax.random.PRNGKey(1), (2, L), 0, 90)
X_T, MASKED, WEIGHT = bd.noise(bd.step_key(0), X0, B, MASK, 1e-3)


# ---------------------------------------------------------------------------
# the program against the reference
# ---------------------------------------------------------------------------

# float32 on both sides: only the order of float32 sums differs (the program
# sorts assignments by expert and fuses q, k, v), so 1e-5; both sides choose
# the same experts because no margin of these seeds lies under 1e-5.
def test_noised_half_logits_agree_with_the_reference():
    cfg = sdar_tiny()
    config, params = reference_config(cfg), seeded(cfg)
    rows, positions = bd.model_rows(X_T, X0)
    logits = Transformer(cfg).apply({"params": params}, rows,
                                    positions=positions)
    assert logits.shape == (2, L, V)   # the noised half alone
    # the positions default to the repeated ones
    again = Transformer(cfg).apply({"params": params}, rows)
    assert float(jnp.abs(again - logits).max()) == 0.0
    plain = ref.reference_params(params, config)
    for row in range(2):
        want, _, margin, _, _ = ref.logits_and_loss(
            plain, X_T[row], X0[row], MASKED[row], WEIGHT[row], config)
        assert float(margin.min()) > 1e-5
        err = jnp.linalg.norm(logits[row] - want) / jnp.linalg.norm(want)
        assert float(err) < 1e-5


# The step's own loss (the noise drawn from the step's key, the chunk scan
# unshifted, the auxiliary term) and its gradient, every leaf, against
# jax.grad of the reference's loss in the reference's layout: 1e-4 relative
# to the largest leaf-wise norm, float32 both sides.
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_loss_and_gradients_agree_with_the_reference(remat):
    cfg = sdar_tiny(remat=remat)
    config, params = reference_config(cfg), seeded(cfg)
    trainer = CheetahTrainer(cfg, make_mesh(None, devices=jax.devices()[:1]),
                             loss_chunk=16)
    mask = jnp.ones_like(X0)
    (got, stats), got_grads = jax.value_and_grad(
        trainer._loss_fn, has_aux=True)(params, {}, X0, mask, bd.step_key(0))
    assert int(stats["bd"]["masked_tokens"]) == int(MASKED.sum())
    assert float(stats["bd"]["weight_sum"]) == pytest.approx(
        float((MASKED * WEIGHT).sum()), rel=1e-6)

    plain = ref.reference_params(params, config)
    want, want_grads = jax.value_and_grad(ref.training_loss)(
        plain, X_T, X0, MASKED, WEIGHT, config)
    assert abs(float(got) - float(want)) < 1e-5 * float(want)
    # the re-layout is linear (slices and reshapes), so it maps gradients too
    got_plain = ref.reference_params(got_grads, config)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    scale = max(float(jnp.linalg.norm(g)) for g in flat_want.values())
    checked = 0
    for path, g in jax.tree_util.tree_leaves_with_path(got_plain):
        err = float(jnp.linalg.norm(g - flat_want[path]))
        assert err < 1e-4 * scale, (_name(path), err, scale)
        checked += 1
    assert checked == 3 + 2 * 12


def test_bfloat16_stays_close_and_float8_does_not():
    """The benchmark's kind of tolerance at this width: the program in
    bfloat16 (its default) against the float32 reference over the positions
    whose routing margin is clear, within 2e-2 (the Xing4 block at this width
    reads the same band); the reference with every product's inputs rounded
    to float8, the nearest precision below, reads over three times that."""
    cfg = sdar_tiny(dtype=jnp.bfloat16)
    config, params = reference_config(cfg), seeded(cfg, moved=False)
    plain = ref.reference_params(params, config)
    args = (X_T[0], X0[0], MASKED[0], WEIGHT[0], config)
    want, want_loss, margin, _, _ = ref.logits_and_loss(plain, *args)
    clear = np.asarray(margin) >= 0.003
    assert clear.mean() > 0.5

    def err(got):
        d = (np.asarray(got) - np.asarray(want))[clear]
        return float(np.linalg.norm(d) / np.linalg.norm(np.asarray(want)[clear]))

    got = Transformer(cfg).apply({"params": params}, bd.model_rows(X_T, X0)[0])[0]
    assert err(got) < 2e-2
    ref.MATMUL_INPUT_DTYPE = jnp.float8_e4m3fn
    try:
        low, _, _, _, _ = ref.logits_and_loss(plain, *args)
    finally:
        ref.MATMUL_INPUT_DTYPE = None
    assert err(low) > 3 * err(got)


def _wrong_mask(clause):
    def mask(rows, cols, L_, B_):
        i, j = rows[:, None], cols[None, :]
        n_i, n_j = i < L_, j < L_
        blk_i, blk_j = (i % L_) // B_, (j % L_) // B_
        if clause == "causal":
            return j <= i
        if clause == "clean_sees_noised":
            return (ref_mask(rows, cols, L_, B_)
                    | (~n_i & n_j & (blk_j <= blk_i)))
        if clause == "own_clean_block_visible":
            return ((n_i & n_j & (blk_i == blk_j))
                    | (n_i & ~n_j & (blk_j <= blk_i))
                    | (~n_i & ~n_j & (blk_j <= blk_i)))
        raise ValueError(clause)

    ref_mask = ref.block_diffusion_mask
    return mask


@pytest.mark.parametrize("mistake", [
    "causal", "clean_sees_noised", "own_clean_block_visible",
    "positions_not_repeated", "no_qk_norm", "gates_not_renormalised",
    "loss_without_weight", "loss_shifted"])
def test_reference_is_sensitive_to_what_it_checks(mistake, monkeypatch):
    """Each of these mistakes moves the reference's logits (or, for the two
    that touch the loss alone, its loss) by far more than the float32
    agreement above allows (1e-5), so the comparison would catch the program
    making it."""
    cfg = sdar_tiny()
    config, params = reference_config(cfg), seeded(cfg)
    plain = ref.reference_params(params, config)
    args = (X_T[0], X0[0], MASKED[0], WEIGHT[0], config)
    want, want_loss, _, _, _ = ref.logits_and_loss(plain, *args)
    if mistake in ("causal", "clean_sees_noised", "own_clean_block_visible"):
        monkeypatch.setattr(ref, "block_diffusion_mask", _wrong_mask(mistake))
    elif mistake == "positions_not_repeated":
        rotary = ref.rotary
        monkeypatch.setattr(ref, "rotary", lambda x, pos, theta: rotary(
            x, jnp.arange(x.shape[0]), theta))
    elif mistake == "no_qk_norm":
        norm = ref.rms_norm
        monkeypatch.setattr(ref, "rms_norm", lambda x, w, eps: (
            x if x.ndim == 3 else norm(x, w, eps)))
    elif mistake == "gates_not_renormalised":
        route_ = ref.route

        def raw(p, x, config):
            sel, gates, margin, c, s = route_(p, x, config)
            prob = jax.nn.softmax(jnp.matmul(x, p["router"]), axis=-1)
            return sel, jnp.take_along_axis(prob, sel, axis=-1), margin, c, s

        monkeypatch.setattr(ref, "route", raw)
    got, got_loss, _, _, _ = ref.logits_and_loss(plain, *args)
    if mistake == "loss_without_weight":
        got_loss = ref.logits_and_loss(
            plain, X_T[0], X0[0], MASKED[0], jnp.ones_like(WEIGHT[0]), config)[1]
    elif mistake == "loss_shifted":
        got_loss = ref.weighted_nll_sum(got, jnp.roll(X0[0], -1), MASKED[0],
                                        WEIGHT[0])
    if mistake.startswith("loss_"):
        assert abs(float(got_loss - want_loss)) > 1e-3 * float(want_loss)
    else:
        err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        assert err > 1e-4, err


# ---------------------------------------------------------------------------
# the mask: the definition, the dense form, the kernel's mask object
# ---------------------------------------------------------------------------


def test_the_definition_by_hand():
    m = bd.dense_mask(8, 4)
    blocks = np.kron(np.array([[1, 0, 0, 0],     # noised block 0: itself
                               [0, 1, 1, 0],     # noised block 1: itself, clean 0
                               [0, 0, 1, 0],     # clean block 0: itself
                               [0, 0, 1, 1]]),   # clean block 1: clean 0 and 1
                     np.ones((4, 4), int)).astype(bool)
    assert (m == blocks).all()
    for L_, B_ in ((8, 4), (256, 4), (256, 32), (96, 1)):
        dense = bd.dense_mask(L_, B_)
        assert dense.sum() == L_ * L_ + L_ * B_
        assert dense.mean() == pytest.approx(bd.pair_share(L_, B_))
        assert dense.any(axis=1).all()          # no row sees nothing
        rows = np.arange(2 * L_)
        assert (np.asarray(ref.block_diffusion_mask(
            jnp.asarray(rows), jnp.asarray(rows), L_, B_)) == dense).all()
    assert bd.pair_share(4096, 4) == pytest.approx(0.25, abs=3e-4)


@pytest.mark.parametrize("L_, B_", [(256, 4), (256, 32), (96, 3), (120, 8),
                                    (60, 60), (64, 1), (384, 3)])
def test_the_kernel_form_is_the_definition(L_, B_):
    """What the splash kernel is handed (one integer a row and a function of
    it and the column) against ``visible`` entry by entry: whole, and on two
    slices that cut blocks and halves; through numpy as the tile bookkeeping
    calls it and traced on int32 operands as the kernel does."""
    mask = bd.splash_mask(L_, B_)
    rows = np.arange(2 * L_, dtype=np.int32)
    want = np.asarray(bd.visible(rows[:, None], rows[None, :], L_, B_))
    assert mask.q_sequence.dtype == np.int32
    assert (mask.q_sequence == bd.kernel_rows(L_, B_)).all()
    assert (np.asarray(mask[:, :]) == want).all()
    for a, b in ((slice(L_ // 3, L_ + L_ // 2 + 1), slice(L_ // 5, 2 * L_ - 3)),
                 (slice(L_ - 1, 2 * L_), slice(1, L_ + 2))):
        assert (np.asarray(mask[a, b]) == want[a, b]).all()
    r = jnp.broadcast_to(jnp.asarray(mask.q_sequence)[:, None], want.shape)
    j = jnp.broadcast_to(jnp.asarray(rows)[None, :], want.shape)
    traced = jax.jit(mask.mask_function)(r, j)
    assert traced.dtype == jnp.bool_ and (np.asarray(traced) == want).all()


def test_the_kernel_form_divides_nothing():
    """The function the kernel evaluates pair by pair in every tile it keeps:
    a handful of cheap integer primitives on tile-shaped operands (the
    definition traces to sixty, four ``div`` and four ``rem`` among them),
    and a function of ``(L, B)`` alone."""
    tile = jax.ShapeDtypeStruct((128, 128), jnp.int32)

    def primitives(f):
        found = []

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                inner = [v for v in eqn.params.values()
                         if hasattr(v, "jaxpr") or hasattr(v, "eqns")]
                for sub in inner:
                    walk(getattr(sub, "jaxpr", sub))
                if not inner:
                    found.append(str(eqn.primitive))

        walk(jax.make_jaxpr(f)(tile, tile).jaxpr)
        return found

    handed = primitives(bd.splash_mask(4096, 4).mask_function)
    assert not {"div", "rem", "floor", "pow"} & set(handed), handed
    assert len(handed) <= 16, handed
    defined = primitives(lambda i, j: bd.visible(i, j, 4096, 4))
    assert defined.count("div") == 4 and len(defined) > 3 * len(handed)


# the tiles of 128 the kernel's bookkeeping keeps (1: crossed, 2: whole). At
# L 256 the noised half's rows keep their diagonal tile (crossed) and the
# clean tiles up to theirs (the last crossed), the clean half's rows the clean
# tiles up to theirs; the clean half never reads a noised tile: 8 of 16. At
# L 384 in blocks of 3 the tile borders 128 and 256 cut blocks 42 and 85, so
# each half's diagonal spills into the tiles beside it: 21 of 36.
TILES_256 = [[1, 0, 1, 0],
             [0, 1, 2, 1],
             [0, 0, 1, 0],
             [0, 0, 2, 1]]
TILES_384_3 = [[1, 1, 0, 1, 0, 0],
               [1, 1, 1, 1, 1, 0],
               [0, 1, 1, 2, 1, 1],
               [0, 0, 0, 1, 1, 0],
               [0, 0, 0, 2, 1, 1],
               [0, 0, 0, 2, 2, 1]]


@pytest.mark.parametrize("L_, block, want", [
    (256, 4, TILES_256), (256, 32, TILES_256), (384, 3, TILES_384_3)],
    ids=["4", "32", "3"])
def test_the_splash_mask_object_is_the_dense_form(L_, block, want):
    """Entry by entry, and the tiles the kernel's bookkeeping keeps."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as sm,
        splash_attention_mask_info as mi,
    )

    mask, dense = bd.splash_mask(L_, block), bd.dense_mask(L_, block)
    assert mask.shape == (2 * L_, 2 * L_)
    assert (np.asarray(mask[:, :]) == dense).all()
    assert (np.asarray(mask[128:384, 64:448]) == dense[128:384, 64:448]).all()
    assert mask == bd.splash_mask(L_, block) != bd.splash_mask(L_, 2 * block)
    info, fn = mi.process_mask(sm.MultiHeadMask([mask] * 2), (128, 128),
                               shrink_grid=False)
    # computed in the kernel from one integer a row: no tile is stored
    assert fn is not None and info.partial_mask_blocks is None
    assert (info.q_sequence == bd.kernel_rows(L_, block)).all()
    tiles, n = np.asarray(info.block_mask)[0], 2 * L_ // 128
    by_hand = np.array([[dense[r * 128:(r + 1) * 128, c * 128:(c + 1) * 128]
                         .any() for c in range(n)] for r in range(n)])
    assert ((tiles > 0) == by_hand).all()
    assert (tiles == np.array(want)).all()
    assert bd.tile_counts(L_, block, 128, 128) == {
        "kept": int((tiles > 0).sum()), "crossed": int((tiles == 1).sum()),
        "of": n * n}


@pytest.mark.parametrize("L_, block", [(128, 4), (192, 3)], ids=["4", "3"])
def test_the_kernel_path_agrees_with_the_dense_path(monkeypatch, L_, block):
    """``attend`` on the splash path (the kernel under Pallas' interpreter,
    its mask the object above, GQA native) against the dense boolean form
    through ``attention_scores``, forward and backward."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
    )

    for name in ("make_splash_mha", "make_splash_mqa"):
        monkeypatch.setattr(sk, name, lambda *a, _f=getattr(sk, name), **kw:
                            _f(*a, interpret=True, **kw))
    cfg = sdar_tiny(max_seq_len=L_, bd_block=block, attn_block_q=128,
                    attn_block_kv=128)
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (1, 2 * L_, 4, 32))
    k = jax.random.normal(keys[1], (1, 2 * L_, 2, 32))
    v = jax.random.normal(keys[2], (1, 2 * L_, 2, 32))

    def out(cfg_):
        return lambda q, k, v: (tfm.attend(cfg_, q, k, v) ** 2).sum()

    dense = jax.value_and_grad(out(cfg), argnums=(0, 1, 2))(q, k, v)
    kernel = jax.value_and_grad(
        out(dataclasses.replace(cfg, attn_impl="splash")),
        argnums=(0, 1, 2))(q, k, v)
    assert float(abs(kernel[0] - dense[0])) < 1e-4 * float(dense[0])
    for a, b in zip(kernel[1], dense[1]):
        assert float(jnp.abs(a - b).max()) < 1e-4 * float(jnp.abs(b).max())


def test_no_leak():
    """Changing ``x_0`` inside block ``b`` (the noise pattern held) leaves
    the noised half's logits of blocks ``<= b`` as they were (a noised block
    reads its own clean copy nowhere), and moves those of the blocks after
    it, through the clean copy of ``b`` alone. A mask that lets a noised row
    see its own block's clean tokens breaks the first, and the loss then
    collapses: the answer is in the input."""
    cfg = sdar_tiny()
    params = seeded(cfg)
    b = 5
    inside = slice(b * B, (b + 1) * B)
    # the whole block is masked: x_t does not change with x_0 there
    masked = MASKED.at[:, inside].set(True)
    x_t = jnp.where(masked, MASK, X0)
    changed = X0.at[:, inside].set((X0[:, inside] + 7) % 90)
    assert (jnp.where(masked, MASK, changed) == x_t).all()

    def logits(x_0):
        return Transformer(cfg).apply({"params": params},
                                      bd.model_rows(x_t, x_0)[0])

    before, after = logits(X0), logits(changed)
    upto = (b + 1) * B
    assert float(jnp.abs(after[:, :upto] - before[:, :upto]).max()) == 0.0
    assert float(jnp.abs(after[:, upto:] - before[:, upto:]).max()) > 1e-3
    # and the clause a wrong mask breaks: the same change now shows inside b
    leaky = _wrong_mask("own_clean_block_visible")
    rows = jnp.arange(2 * L)
    assert bool((leaky(rows, rows, L, B)
                 != ref.block_diffusion_mask(rows, rows, L, B)).any())


# ---------------------------------------------------------------------------
# the noise
# ---------------------------------------------------------------------------


def test_noise_masks_what_it_says_and_nothing_else():
    tokens = jax.random.randint(jax.random.PRNGKey(3), (8, 4096), 0, 90)
    x_t, masked, weight = bd.noise(bd.step_key(0), tokens, 4, 18991, 1e-3)
    assert x_t.dtype == tokens.dtype and masked.dtype == jnp.bool_
    assert (x_t[~masked] == tokens[~masked]).all()      # unmasked tokens kept
    assert ((x_t == 18991) == masked).all()             # the id nowhere else
    t = 1.0 / weight
    assert float(t.min()) >= 1e-3 and float(t.max()) <= 1.0
    per_block = np.asarray(t).reshape(8, 1024, 4)
    assert (per_block == per_block[..., :1]).all()      # one t a block
    # the masked share within the binomial band of mean(t): 4 sigma
    n = tokens.size
    mean_t = float(t.mean())
    sigma = float(jnp.sqrt((t * (1 - t)).sum())) / n
    assert abs(float(masked.mean()) - mean_t) < 4 * sigma
    assert abs(mean_t - 0.5005) < 4 * (0.2884 / np.sqrt(n / 4))
    # E[masked * weight] = 1: the loss is an unbiased mean over positions
    assert float((masked * weight).mean()) == pytest.approx(1.0, abs=0.1)
    # a pure function of the key; another step draws another pattern
    again = bd.noise(bd.step_key(0), tokens, 4, 18991, 1e-3)
    assert (again[0] == x_t).all() and (again[2] == weight).all()
    other = bd.noise(bd.step_key(1), tokens, 4, 18991, 1e-3)
    assert not (other[1] == masked).all()
    assert not (bd.noise(bd.step_key(0, 1), tokens, 4, 18991, 1e-3)[1]
                == masked).all()
    with pytest.raises(ValueError, match="do not divide"):
        bd.noise(bd.step_key(0), tokens[:, :4094], 4, 18991, 1e-3)


# ---------------------------------------------------------------------------
# the expert layer under the softmax rule
# ---------------------------------------------------------------------------


def _layer_params(cfg, x, key=3):
    return unbox(MoEFeedForward(cfg).init(jax.random.PRNGKey(key), x)["params"])


def test_the_eight_shares_add_up_to_the_whole():
    """Eight chips' shares of a 128-expert layer (each holds 16 from offsets
    0, 16, .., 112 and routes over all 128, 8 a token, renormalised) sum to
    the uncut reference's whole expert layer, and to the program's."""
    base = sdar_tiny(moe_experts=128, moe_top_k=8, moe_experts_held=0,
                     moe_expert_offset=0, objective="next_token")
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 64))
    whole = _layer_params(base, x)
    y_whole, aux_whole = MoEFeedForward(base).apply({"params": whole}, x)
    total = jnp.zeros_like(y_whole)
    for offset in range(0, 128, 16):
        cfg = dataclasses.replace(base, moe_experts_held=16,
                                  moe_expert_offset=offset)
        part = dict(whole, w_gate_up=whole["w_gate_up"][offset:offset + 16],
                    w_down=whole["w_down"][offset:offset + 16])
        y, aux = MoEFeedForward(cfg).apply({"params": part}, x)
        assert float(abs(aux - aux_whole)) < 1e-6   # over all 128 outputs
        total = total + y
    assert float(jnp.abs(total - y_whole).max()) < 1e-5
    config = dict(reference_config(base), num_experts=128, expert_offset=0)
    plain = {"router": whole["w_router"],
             "experts": {"w_gate": whole["w_gate_up"][..., :32],
                         "w_up": whole["w_gate_up"][..., 32:],
                         "w_down": whole["w_down"]}}
    flat = x.reshape(-1, 64)
    want, _, counts, prob_sum = ref.expert_layer(plain, flat, config)
    assert float(jnp.abs(total.reshape(-1, 64) - want).max()) < 1e-5
    assert float(ref.aux_loss(counts, prob_sum, flat.shape[0], config)) == \
        pytest.approx(float(aux_whole), rel=1e-5)


@pytest.mark.parametrize("k", [1, 2, 8])
def test_softmax_routing_by_hand(k):
    """Top-1 keeps its raw probability, more choices renormalise over the
    chosen (top-1 and top-2 as before this PR, any k since), and the
    auxiliary loss is the Switch form over all the router's outputs."""
    cfg = sdar_tiny(moe_experts=16, moe_top_k=k)
    z = jax.random.normal(jax.random.PRNGKey(k), (40, 16))
    expert, gate, aux = route(cfg, z)
    p = np.asarray(jax.nn.softmax(z, axis=-1))
    order = np.argsort(-p, axis=-1)[:, :k]
    assert (np.asarray(expert) == order).all()
    chosen = np.take_along_axis(p, order, axis=-1)
    want = chosen if k == 1 else chosen / chosen.sum(-1, keepdims=True)
    assert np.allclose(np.asarray(gate), want, atol=1e-6)
    frac = np.stack([(order == e).sum() for e in range(16)]) / (40 * k)
    assert float(aux) == pytest.approx(16 * float((frac * p.mean(0)).sum()),
                                       rel=1e-5)


# ---------------------------------------------------------------------------
# the step: what it reports, what it refuses, what it leaves as it was
# ---------------------------------------------------------------------------


def test_the_step_trains_and_reports_its_objective(monkeypatch):
    cfg = sdar_tiny(dtype=jnp.bfloat16, remat=True)
    one = make_mesh(None, devices=jax.devices()[:1])
    events = []
    monkeypatch.setattr(mlops, "_emit", events.append)
    trainer = CheetahTrainer(cfg, one)
    state = trainer.init_state(jax.random.PRNGKey(0))
    (event,) = [e for e in events if e["kind"] == "cheetah_init"]
    assert (event["objective"], event["bd_block"], event["head_dim"]) == (
        "block_diffusion", B, 32)
    # 2L = 128 rows under the kernel's default tiles of 128: one tile
    assert event["attn_mask"] == {"kind": "block_diffusion",
                                  "pair_share": (L * L + L * B) / (4 * L * L),
                                  "layers": {"block_diffusion": 2},
                                  "tiles": {"kept": 1, "crossed": 1, "of": 1}}
    tokens = jnp.tile(jnp.arange(L) % 7, (2, 1)).astype(jnp.int32)
    losses, masked = [], []
    for _ in range(8):
        state, metrics = trainer.train_step(state, tokens, jnp.ones_like(tokens))
        losses.append(float(metrics["loss"]))
        masked.append(int(metrics["bd_masked_tokens"]))
    assert np.isfinite(losses).all() and min(losses[-3:]) < losses[0]
    assert len(set(masked)) > 1          # a new draw every step
    assert all(0 < m < tokens.size for m in masked)
    assert float(metrics["bd_weight_sum"]) > 0
    assert int(metrics["moe_dropped"]) == 0
    # 4 held of 16, 4 choices a row, 2 layers, 2L rows a sequence
    assert 0 < int(metrics["moe_assignments_held"]) <= 2 * 4 * 2 * tokens.size
    text = trainer.lower_step(state, tokens, jnp.ones_like(tokens)).as_text(
        debug_info=True)
    for scope in ("bd_noise", "qk_norm", "moe_route", "moe_experts", "loss"):
        assert re.search(rf'[/"(]{scope}[/")]', text), scope
    # every other trainer reports its mask by name too
    plain = CheetahTrainer(TransformerConfig.tiny(), one)
    assert plain.attn_mask == {"kind": "causal", "pair_share": 129 / 256,
                               "layers": {"causal": 2}}
    assert not plain.bd


def test_gradient_accumulation_draws_a_pattern_a_microbatch():
    cfg = sdar_tiny()
    trainer = CheetahTrainer(cfg, make_mesh(None, devices=jax.devices()[:1]),
                             accum_steps=2)
    state = trainer.init_state(jax.random.PRNGKey(0))
    tokens = jnp.stack([X0, X0])
    _, metrics = trainer.train_step(state, tokens, jnp.ones_like(tokens))
    want = sum(int(bd.noise(bd.step_key(0, i), X0, B, MASK, 1e-3)[1].sum())
               for i in range(2))
    assert int(metrics["bd_masked_tokens"]) == want
    assert np.isfinite(float(metrics["loss"]))


def test_what_the_objective_refuses_it_refuses_by_name():
    for kw, word in ((dict(mtp_layers=1), "MTP module"),
                     (dict(hc_mult=4), "hyper-connections"),
                     (dict(layer_group_size=2, kda_head_dim=16), "kda mixer"),
                     (dict(pos_emb="learned"), "learned positions")):
        with pytest.raises(NotImplementedError, match=word):
            sdar_tiny(**kw)
    with pytest.raises(ValueError, match="bd_block"):
        sdar_tiny(bd_block=0)
    with pytest.raises(ValueError, match="bd_block"):
        sdar_tiny(bd_mask_token=V)
    with pytest.raises(ValueError, match="attn_head_dim"):
        sdar_tiny(attn_head_dim=31)
    cfg = sdar_tiny()
    with pytest.raises(ValueError, match="2L rows"):
        cfg.attn_mask(2 * L + 2)
    assert cfg.attn_mask(2 * L) == ("block_diffusion", L, B)
    with pytest.raises(ValueError, match="blocks that divide the sequence"):
        bd.splash_mask(96, 5)
    assert TransformerConfig.tiny().attn_mask(32) == ("causal",)
    assert dataclasses.replace(TransformerConfig.tiny(),
                               causal=False).attn_mask(32) == ("full",)
    # under a sequence context the structured mask refuses by name, in the
    # trainer and in attend
    four = make_mesh({"sequence": 4}, devices=jax.devices()[:4])
    with pytest.raises(NotImplementedError, match="sequence parallelism"):
        CheetahTrainer(cfg, four, seq_sharded=True)
    from fedml_tpu.parallel.context import sequence_parallelism

    q = jnp.zeros((1, 2 * L, 4, 32))
    with sequence_parallelism(four), pytest.raises(
            NotImplementedError, match="block_diffusion mask"):
        tfm.attend(cfg, q, q[:, :, :2], q[:, :, :2])
    with pytest.raises(ValueError, match="no attention mask named"):
        tfm.splash_attention_tpu(q, q, q, mask_kind=("sliding", 4))


def test_arguments_reach_every_new_field():
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.cheetah.runner import config_from_args

    want = sdar_tiny(dtype=jnp.bfloat16, remat=True)
    args = {f.name: getattr(want, f.name)
            for f in dataclasses.fields(TransformerConfig)
            if f.name not in ("dtype", "param_dtype", "max_seq_len")}
    args.update(model_size="from_arguments", seq_len=want.max_seq_len,
                training_type="distributed", remat="true", qk_norm="true")
    got = config_from_args(Arguments(overrides=args))
    assert got == want
    assert (got.head_dim, got.qk_norm, got.objective, got.bd_block,
            got.bd_mask_token) == (32, True, "block_diffusion", B, MASK)


def test_the_programs_flops_count_a_data_token():
    cfg = sdar_tiny()
    D, H, Hkv, hd = 64, 4, 2, 32
    row = (2 * D * hd * (2 * H + 2 * Hkv)            # q, k, v, o
           + 2 * D * 16 + 4 * 4 / 16 * 6 * D * 32)   # router, 1 expert of 4
    attn = 2 * 2 * H * hd * (L + B)                  # (L^2 + L B) / L pairs
    by_hand = 2 * (2 * row + attn) + 2 * D * V       # the head once
    assert tfm.train_flops_per_token(cfg, L) == pytest.approx(3 * by_hand)
    # the same stack under the next-token objective: one row, causal pairs
    nt = dataclasses.replace(cfg, objective="next_token")
    causal = 2 * (row + 2 * 2 * H * hd * (L + 1) / 2) + 2 * D * V
    assert tfm.train_flops_per_token(nt, L) == pytest.approx(3 * causal)


# sha256 of ``lower_step(..).as_text()``, normalised as tests/test_xing4.py
# normalises it, at PR 34's parent commit (dd8fad1): a configuration with a
# KDA / MLA stack, group-limited sigmoid routing and a shared expert
# (Ling-shaped), and one with the softmax router's top-2 under a capacity
# factor (Switch-shaped): the head size, the q/k norms, the named mask, the
# softmax rule for any k and the objective leave such programs as they were.
# (The Mistral- and Xing4-shaped hashes are tests/test_xing4.py's and
# tests/test_ling3.py's.) Taken again at PR 37, which rewrote the expert
# layer's dispatch and combine and so every program that holds one (forward
# bit for bit and gradients against a dense oracle: tests/test_moe.py).
# Taken again when the router came to obtain its gates and kept mask by
# selects instead of gathers (routing bit for bit: tests/test_moe.py).
PARENT_STEPS = {
    "ling_shaped":
        "edca3804ac509b17e95910cd421277e192c263080574845634a714c6cee2e5a1",
    "switch_shaped":
        "0caa260a0fbde63c4761b6c332de34c8a874494fb301a50cdf60749696b64582",
}


def _normalised(text: str) -> str:
    text = re.sub(r"sdy\.sharding = #sdy\.sharding<[^>]*>,? ?", "", text)
    return re.sub(r"@(_?[A-Za-z_]+)_\d+", r"@\1", text)


def _shaped(name: str) -> TransformerConfig:
    if name == "ling_shaped":
        return TransformerConfig(
            vocab_size=96, d_model=64, n_layers=4, n_heads=4, n_kv_heads=4,
            d_ff=160, max_seq_len=64, remat=True, attn_impl="xla",
            norm_eps=1e-6, attn_kind="mla", q_lora_rank=0, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            layer_group_size=3, kda_head_dim=16, first_k_dense=1,
            moe_experts=16, moe_top_k=4, moe_capacity_factor=0.0,
            moe_router="sigmoid", moe_n_group=4, moe_topk_group=2,
            moe_routed_scale=2.5, moe_d_ff=32, moe_shared_experts=1,
            moe_experts_held=4, moe_expert_offset=0)
    return TransformerConfig(
        vocab_size=96, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=64, remat=True, moe_experts=4, moe_top_k=2,
        moe_capacity_factor=2.0)


@pytest.mark.parametrize("name", sorted(PARENT_STEPS))
def test_a_configuration_without_the_new_fields_lowers_as_before(name):
    trainer = CheetahTrainer(_shaped(name),
                             make_mesh(None, devices=jax.devices()[:1]))
    state = trainer.init_state(jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 64), jnp.int32)
    text = trainer.lower_step(state, tokens, jnp.ones_like(tokens)).as_text()
    assert hashlib.sha256(_normalised(text).encode()).hexdigest() == \
        PARENT_STEPS[name]
