"""MoE / expert parallelism (SURVEY §2.5 component #35, new capability)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from fedml_tpu.parallel.sharding import make_mesh
from fedml_tpu.parallel.train_step import CheetahTrainer, make_optimizer
from fedml_tpu.parallel.transformer import TransformerConfig


def moe_cfg(**kw):
    base = dict(vocab_size=128, d_model=64, n_layers=2, n_heads=4,
                n_kv_heads=4, d_ff=128, max_seq_len=64, remat=False,
                moe_experts=4, moe_capacity_factor=2.0)
    base.update(kw)
    return TransformerConfig(**base)


class TestMoELayer:
    def test_forward_and_aux(self):
        from fedml_tpu.parallel.moe import MoEFeedForward

        cfg = moe_cfg()
        layer = MoEFeedForward(cfg)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 64), jnp.bfloat16)
        variables = layer.init(jax.random.PRNGKey(1), x)
        (y, aux), _ = layer.apply(variables, x, mutable=["intermediates"])
        assert y.shape == x.shape
        assert np.isfinite(float(aux))
        # balanced-uniform routing gives aux ~= 1; collapse gives ~= E
        assert 0.5 < float(aux) < 4.5

    def test_expert_params_stacked(self):
        from fedml_tpu.parallel.moe import MoEFeedForward

        cfg = moe_cfg()
        layer = MoEFeedForward(cfg)
        x = jnp.zeros((1, 8, 64), jnp.bfloat16)
        variables = layer.init(jax.random.PRNGKey(0), x)
        p = jax.tree.map(
            lambda t: t.value if hasattr(t, "value") else t,
            variables["params"],
            is_leaf=lambda t: hasattr(t, "value"),
        )
        assert p["w_gate_up"].shape == (4, 64, 256)
        assert p["w_down"].shape == (4, 128, 64)


class TestTop2Routing:
    def test_top2_matches_dense_oracle_with_ample_capacity(self):
        """With capacity >= T every token reaches both chosen experts, so the
        layer must equal g1*FFN_e1(x) + g2*FFN_e2(x) computed densely."""
        from fedml_tpu.parallel.moe import MoEFeedForward

        cfg = moe_cfg(moe_top_k=2, moe_capacity_factor=float(4))  # C = 2T
        layer = MoEFeedForward(cfg)
        x = jax.random.normal(jax.random.PRNGKey(5), (1, 8, 64), jnp.float32)
        variables = layer.init(jax.random.PRNGKey(6), x)
        (y, _aux), _ = layer.apply(variables, x, mutable=["intermediates"])

        p = jax.tree.map(
            lambda t: t.value if hasattr(t, "value") else t,
            variables["params"], is_leaf=lambda t: hasattr(t, "value"),
        )
        xt = np.asarray(x.reshape(8, 64), np.float32)
        probs = np.asarray(
            jax.nn.softmax(jnp.asarray(xt) @ p["w_router"], axis=-1)
        )
        want = np.zeros_like(xt)
        for t in range(8):
            order = np.argsort(-probs[t])
            e1, e2 = int(order[0]), int(order[1])
            g = probs[t, [e1, e2]] / probs[t, [e1, e2]].sum()
            for gate, e in zip(g, (e1, e2)):
                gu = xt[t] @ np.asarray(p["w_gate_up"][e], np.float32)
                gate_h, up = np.split(gu, 2)
                h = (gate_h / (1 + np.exp(-gate_h))) * up  # silu(gate)*up
                want[t] += gate * (h @ np.asarray(p["w_down"][e], np.float32))
        np.testing.assert_allclose(
            np.asarray(y.reshape(8, 64), np.float32), want,
            rtol=2e-2, atol=2e-3,
        )

    def test_top2_second_choice_respects_leftover_capacity(self):
        """Dropped second choices pass through silently: with tight capacity
        (C = 2 slots/expert for 8 tokens x 2 routes), overflow must not
        corrupt the output."""
        from fedml_tpu.parallel.moe import MoEFeedForward

        cfg = moe_cfg(moe_top_k=2, moe_capacity_factor=float(0.5))  # C=2
        layer = MoEFeedForward(cfg)
        x = jax.random.normal(jax.random.PRNGKey(7), (1, 8, 64), jnp.float32)
        variables = layer.init(jax.random.PRNGKey(8), x)
        (y, aux), _ = layer.apply(variables, x, mutable=["intermediates"])
        assert y.shape == x.shape and np.isfinite(float(aux))
        assert np.isfinite(np.asarray(y, np.float32)).all()

    def test_tight_capacity_matches_priority_oracle(self):
        """At overflowing capacity the kept set follows GShard priority —
        per expert: first choices (in token order), then second choices;
        everything past C drops. Pinned against a python oracle so the r5
        sort-based dispatch provably preserves the r4 cumsum semantics."""
        from fedml_tpu.parallel.moe import MoEFeedForward

        T, E, C = 8, 4, 2
        cfg = moe_cfg(moe_top_k=2, moe_capacity_factor=float(0.5))  # C=2
        layer = MoEFeedForward(cfg)
        x = jax.random.normal(jax.random.PRNGKey(11), (1, T, 64), jnp.float32)
        variables = layer.init(jax.random.PRNGKey(12), x)
        (y, _aux), _ = layer.apply(variables, x, mutable=["intermediates"])

        p = jax.tree.map(
            lambda t: t.value if hasattr(t, "value") else t,
            variables["params"], is_leaf=lambda t: hasattr(t, "value"),
        )
        xt = np.asarray(x.reshape(T, 64), np.float32)
        probs = np.asarray(
            jax.nn.softmax(jnp.asarray(xt) @ p["w_router"], axis=-1)
        )
        e1 = probs.argmax(-1)
        probs2 = probs.copy()
        probs2[np.arange(T), e1] = 0
        e2 = probs2.argmax(-1)
        # assignment priority order: all first choices, then all seconds
        load = {e: 0 for e in range(E)}
        kept = set()
        for j, e in enumerate(np.concatenate([e1, e2])):
            if load[int(e)] < C:
                kept.add(j)
                load[int(e)] += 1
        want = np.zeros_like(xt)
        for t in range(T):
            g1, g2 = probs[t, e1[t]], probs2[t, e2[t]]
            denom = g1 + g2
            for j, (gate, e) in ((t, (g1 / denom, e1[t])),
                                 (T + t, (g2 / denom, e2[t]))):
                if j not in kept:
                    continue
                gu = xt[t] @ np.asarray(p["w_gate_up"][e], np.float32)
                gate_h, up = np.split(gu, 2)
                h = (gate_h / (1 + np.exp(-gate_h))) * up
                want[t] += gate * (h @ np.asarray(p["w_down"][e], np.float32))
        np.testing.assert_allclose(
            np.asarray(y.reshape(T, 64), np.float32), want,
            rtol=2e-2, atol=2e-3,
        )

    def test_top2_trains(self):
        cfg = moe_cfg(moe_top_k=2)
        mesh = make_mesh({"fsdp": 1}, devices=jax.devices()[:1])
        tr = CheetahTrainer(cfg, mesh, optimizer=make_optimizer(
            3e-3, warmup_steps=2, total_steps=50))
        state = tr.init_state(jax.random.PRNGKey(3))
        rng = np.random.RandomState(3)
        tok = jnp.asarray(rng.randint(0, 128, (4, 64)).astype(np.int32))
        m = jnp.ones((4, 64), jnp.int32)
        first = None
        for _ in range(15):
            state, metrics = tr.train_step(state, tok, m)
            if first is None:
                first = float(metrics["loss"])
        assert float(metrics["loss"]) < first - 0.5

    def test_top2_expert_parallel_mesh(self):
        cfg = moe_cfg(moe_top_k=2)
        mesh = make_mesh({"data": 2, "expert": 2, "fsdp": 2})
        tr = CheetahTrainer(cfg, mesh, optimizer=make_optimizer(1e-3))
        state = tr.init_state(jax.random.PRNGKey(4))
        rng = np.random.RandomState(4)
        tok = jnp.asarray(rng.randint(0, 128, (4, 64)).astype(np.int32))
        m = jnp.ones((4, 64), jnp.int32)
        state, metrics = tr.train_step(state, tok, m)
        assert np.isfinite(float(metrics["loss"]))


class TestMoETraining:
    def test_moe_transformer_trains_single_device(self):
        cfg = moe_cfg()
        mesh = make_mesh({"fsdp": 1}, devices=jax.devices()[:1])
        tr = CheetahTrainer(cfg, mesh, optimizer=make_optimizer(
            3e-3, warmup_steps=2, total_steps=50))
        state = tr.init_state(jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        tok = jnp.asarray(rng.randint(0, 128, (4, 64)).astype(np.int32))
        m = jnp.ones((4, 64), jnp.int32)
        first = None
        for _ in range(15):
            state, metrics = tr.train_step(state, tok, m)
            if first is None:
                first = float(metrics["loss"])
        assert float(metrics["loss"]) < first - 0.5

    def test_moe_expert_parallel_mesh(self):
        """Expert weights sharded over the expert axis; one step executes."""
        cfg = moe_cfg()
        mesh = make_mesh({"data": 2, "expert": 2, "fsdp": 2})
        tr = CheetahTrainer(cfg, mesh, optimizer=make_optimizer(1e-3))
        state = tr.init_state(jax.random.PRNGKey(0))
        # expert weights actually sharded over the expert mesh axis
        gu = state.params["Block_0"]["MoEFeedForward_0"]["w_gate_up"]
        spec = gu.sharding.spec
        assert "expert" in str(spec), spec
        rng = np.random.RandomState(1)
        tok = jnp.asarray(rng.randint(0, 128, (4, 64)).astype(np.int32))
        m = jnp.ones((4, 64), jnp.int32)
        state, metrics = tr.train_step(state, tok, m)
        assert np.isfinite(float(metrics["loss"]))


# ---------------------------------------------------------------------------
# dispatch and combine (ISSUE 37): rows travel between token order and expert
# order through two operations with hand-written transposes
# ---------------------------------------------------------------------------

T_ORACLE, D_ORACLE = 32, 16


def oracle_cfg(**kw):
    base = dict(vocab_size=128, d_model=D_ORACLE, n_layers=1, n_heads=4,
                n_kv_heads=4, d_ff=32, max_seq_len=64, remat=False,
                dtype=jnp.float32, moe_experts=16, moe_d_ff=8,
                moe_experts_held=4, moe_expert_offset=4,
                moe_capacity_factor=0.0)
    base.update(kw)
    return TransformerConfig(**base)


def layer_and_params(cfg, key, layer_cls=None):
    import flax

    from fedml_tpu.parallel.moe import MoEFeedForward

    layer = (layer_cls or MoEFeedForward)(cfg)
    x = jax.random.normal(jax.random.fold_in(key, 0),
                          (1, T_ORACLE, cfg.d_model), cfg.dtype)
    variables = flax.core.meta.unbox(layer.init(jax.random.fold_in(key, 1), x))
    params = dict(variables["params"])
    # a router that spreads its choices (the initialiser's 0.02 makes every
    # score 1 / E to three digits)
    params["w_router"] = params["w_router"] * 100.0
    return layer, params, x


def dense_oracle(cfg, params, x, gate_shift):
    """The expert layer with no gather, scatter or sort: every held expert
    computes every token, and one-hot products choose. Softmax rule."""
    E, k = cfg.moe_experts, int(cfg.moe_top_k)
    held, offset = cfg.experts_held, cfg.moe_expert_offset
    T = x.shape[0] * x.shape[1]
    xt = x.reshape(T, -1).astype(jnp.float32)
    scores = jax.nn.softmax(jnp.matmul(
        xt, params["w_router"], precision=jax.lax.Precision.HIGHEST), axis=-1)
    _, expert = jax.lax.top_k(scores, k)                            # [T, k]
    chose = jax.nn.one_hot(expert, E, dtype=jnp.float32)            # [T, k, E]
    gate = jnp.einsum("tke,te->tk", chose, scores)
    if k > 1:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    gate = gate * cfg.moe_routed_scale + gate_shift
    kept = chose[:, :, offset:offset + held]                        # [T, k, held]
    if cfg.moe_capacity_factor > 0:
        capacity = max(int(cfg.moe_capacity_factor * k * T / E), 1)
        # GShard's priority: all first choices in token order, then all second
        flat = kept.transpose(1, 0, 2).reshape(k * T, held)
        position = (jnp.cumsum(flat, 0) - 1).reshape(k, T, held).transpose(1, 0, 2)
        kept = kept * (position < capacity)
    kept = jax.lax.stop_gradient(kept)

    def ffn(w_gate_up, w_down):
        gate_h, up = jnp.split(xt @ w_gate_up, 2, axis=-1)
        return (jax.nn.silu(gate_h) * up) @ w_down

    every = jax.vmap(ffn)(params["w_gate_up"], params["w_down"])    # [held, T, D]
    return jnp.einsum("tk,tkh,htd->td", gate, kept, every).reshape(x.shape)


@pytest.mark.parametrize("case", [
    dict(moe_top_k=1), dict(moe_top_k=2), dict(moe_top_k=8),
    dict(moe_top_k=1, moe_capacity_factor=0.5),
    dict(moe_top_k=2, moe_capacity_factor=0.5),
    dict(moe_top_k=8, moe_capacity_factor=0.25),
    dict(moe_top_k=8, nothing_arrives=True),
], ids=lambda c: "-".join(f"{k.replace('moe_', '')}={v}" for k, v in c.items()))
def test_output_and_gradients_match_a_dense_oracle(case, monkeypatch):
    """Held experts 4 to 7 of 16, so absent experts on both sides of them.
    The gates' gradient is read through a shift added to them on both sides.
    Without a capacity the grouped product's rows past the last group are
    poisoned: the combine has to select them away, a zero gate would not."""
    from fedml_tpu.parallel import moe

    case = dict(case)
    nothing_arrives = case.pop("nothing_arrives", False)
    cfg = oracle_cfg(**case)
    k = int(cfg.moe_top_k)
    layer, params, x = layer_and_params(cfg, jax.random.PRNGKey(k))
    if nothing_arrives:
        # one constant feature and a router that turns every held expert down
        x = x.at[..., 0].set(1.0)
        params["w_router"] = params["w_router"].at[0, 4:8].set(-1e4)

    plain_route, plain_grouped = moe.route, moe._grouped_experts
    shift = {}

    def shifted_route(cfg, scores, bias=None):
        expert, gate, aux = plain_route(cfg, scores, bias)
        return expert, gate + shift["gate"], aux

    def poisoned(cfg, rows, w_gate_up, w_down, counts):
        out = plain_grouped(cfg, rows, w_gate_up, w_down, counts)
        row = jnp.arange(out.shape[0])[:, None]
        return jnp.where(row < counts.sum(), out, jnp.nan)

    monkeypatch.setattr(moe, "route", shifted_route)
    monkeypatch.setattr(moe, "_grouped_experts", poisoned)
    weight = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)

    def system(params, x, gate_shift):
        shift["gate"] = gate_shift
        (y, _aux), stats = layer.apply({"params": params}, x,
                                       mutable=["moe_stats"])
        return (y * weight).sum(), (y, stats["moe_stats"])

    def oracle(params, x, gate_shift):
        y = dense_oracle(cfg, params, x, gate_shift)
        return (y * weight).sum(), y

    zero = jnp.zeros((T_ORACLE, k), jnp.float32)
    (_, (y, stats)), got = jax.value_and_grad(
        system, argnums=(0, 1, 2), has_aux=True)(params, x, zero)
    (_, want_y), want = jax.value_and_grad(
        oracle, argnums=(0, 1, 2), has_aux=True)(params, x, zero)

    load = np.asarray(stats["load"][0])
    dropped = int(stats["dropped"][0])
    assert load.sum() == k * T_ORACLE and load[:4].sum() + load[8:].sum() > 0
    if nothing_arrives:
        assert load[4:8].sum() == 0 and not np.asarray(y).any()
    else:
        assert load[4:8].sum() > 0 and np.asarray(y).any()
    assert (dropped > 0) == (cfg.moe_capacity_factor > 0)

    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-6)
    names = {"w_router", "w_gate_up", "w_down"}
    assert set(got[0]) == names
    for name, g, w in ([(n, got[0][n], want[0][n]) for n in sorted(names)]
                       + [("x", got[1], want[1]), ("gate", got[2], want[2])]):
        g, w = np.asarray(g), np.asarray(w)
        assert np.isfinite(g).all(), name
        assert w.any() != nothing_arrives, name
        scale = max(float(np.abs(w).max()), 1e-30)
        np.testing.assert_allclose(g / scale, w / scale, atol=2e-5, err_msg=name)


def _filled_rows(x, index):
    return jnp.take(x, index, axis=0, mode="fill", fill_value=0)


def parents_dispatch(x, token, src):
    """Dispatch as it stood before ISSUE 37, kept as the definition: a
    ``jnp.take`` that fills past the end."""
    del src
    return _filled_rows(x, token)


def parents_combine(out, gate, gate_row, src, token, order):
    """Combine and the gate weighting as they stood before ISSUE 37, kept as
    the definition: the chosen rows as a filled ``[k, ..., D]`` array and a
    Python sum over the choices."""
    del gate_row, token, order
    chosen = _filled_rows(out, src)
    return sum(gate[..., c, None] * chosen[c].astype(jnp.float32)
               for c in range(src.shape[0])).astype(out.dtype)


@pytest.mark.parametrize("case", [
    dict(moe_top_k=1), dict(moe_top_k=2), dict(moe_top_k=8),
    dict(moe_top_k=2, moe_capacity_factor=0.5),
    dict(moe_top_k=8, moe_capacity_factor=0.25),
    dict(moe_top_k=4, moe_router="sigmoid", moe_shared_experts=1),
], ids=lambda c: "-".join(f"{k.replace('moe_', '')}={v}" for k, v in c.items()))
def test_forward_is_the_definitions_bit_for_bit(case, monkeypatch):
    from fedml_tpu.parallel import moe

    cfg = oracle_cfg(dtype=jnp.bfloat16, **case)
    layer, params, x = layer_and_params(cfg, jax.random.PRNGKey(7))
    variables = {"params": params}
    if cfg.moe_router == "sigmoid":
        variables["router_state"] = {"bias": jnp.zeros((16,), jnp.float32)}

    def forward():
        (y, _aux), _ = layer.apply(variables, x, mutable=["moe_stats"])
        return np.asarray(y.astype(jnp.float32))

    got = forward()
    monkeypatch.setattr(moe, "_dispatch", parents_dispatch)
    monkeypatch.setattr(moe, "_combine", parents_combine)
    want = forward()
    assert want.any() and got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)


def _equations(jaxpr, stack=""):
    """Every equation of a jaxpr and of the jaxprs inside it, with the name
    stack it stands under (an inner jaxpr's stacks are relative to the
    equation that holds it)."""
    for eqn in jaxpr.eqns:
        here = "/".join(s for s in (stack, str(eqn.source_info.name_stack)) if s)
        yield eqn, here
        inner = here + f"/{eqn.params['name']}" if "name" in eqn.params else here
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub, inner)


@pytest.mark.parametrize("capacity", [0.0, 0.5])
def test_rows_move_twice_through_an_assignments_sized_operand(capacity):
    """In the gradient of one rematerialised layer the only gathers that read
    an operand of ``k * T`` rows are the forward combine and the dispatch's
    transpose; the recomputation holds none (the combine's residual is the
    grouped product's result), every other row gather reads an operand with
    one zero row appended, nothing fills an assignments-sized result in a pass
    of its own, and no row is scattered."""
    from flax import linen as nn

    from fedml_tpu.parallel.moe import MoEFeedForward

    k = 2
    cfg = oracle_cfg(dtype=jnp.bfloat16, moe_top_k=k, moe_capacity_factor=capacity)
    layer, params, x = layer_and_params(cfg, jax.random.PRNGKey(3),
                                        nn.remat(MoEFeedForward))
    kT, T, D = k * T_ORACLE, T_ORACLE, D_ORACLE

    def loss(params, x):
        (y, aux), _ = layer.apply({"params": params}, x, mutable=["moe_stats"])
        return jnp.square(y.astype(jnp.float32)).sum() + aux

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x).jaxpr
    row_gathers, filled, scatters = [], [], []
    for eqn, stack in _equations(jaxpr):
        name = eqn.primitive.name
        shapes = [v.aval.shape for v in eqn.outvars]
        if name == "gather" and "moe_experts" in stack and shapes[0][-1:] == (D,):
            row_gathers.append((eqn.invars[0].aval.shape, stack))
        if name == "select_n" and "_take" in stack and any(
                np.prod(shape) == kT * D for shape in shapes):
            filled.append(stack)
        if (name.startswith("scatter") and "moe_experts" in stack
                and shapes[0][1:] == (D,)):
            scatters.append(stack)
    assert not filled and not scatters
    large = [stack for shape, stack in row_gathers if shape == (kT, D)]
    # the forward combine, then the transposes: the dispatch's, and under a
    # capacity the one that brings the rows' cotangent to the slots
    assert len(large) == (2 if capacity == 0 else 3), large
    assert "jvp(" in large[0] and "transpose(" not in large[0]
    assert all("transpose(" in stack for stack in large[1:])
    assert not any("rematted_computation" in stack for stack in large)
    # what is left reads the tokens' rows and one of zeros, or under a
    # capacity the slots' (``held * capacity`` of them) and the rows'
    others = {shape for shape, _ in row_gathers} - {(kT, D)}
    slots = 4 * max(int(capacity * kT / 16), 1)
    assert others == ({(T + 1, D)} if capacity == 0 else
                      {(T + 1, D), (kT + 1, D), (slots + 1, D), (slots, D)})
    recomputed = {shape for shape, s in row_gathers if "rematted_computation" in s}
    assert recomputed == others - {(slots, D)}
