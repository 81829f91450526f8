"""Cheetah parallel-layer tests: transformer math, sharding rules, full
sharded train step on the 8-device virtual mesh, and the driver entry points.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.parallel.sharding import make_mesh, param_shardings, unbox
from fedml_tpu.parallel.train_step import CheetahTrainer, lm_loss, make_optimizer
from fedml_tpu.parallel.transformer import (
    Transformer,
    TransformerConfig,
    apply_rotary,
    rotary_embedding,
)


@pytest.fixture(scope="module")
def tiny_cfg():
    return TransformerConfig.tiny()


class TestTransformer:
    def test_forward_shape_and_dtype(self, tiny_cfg):
        model = Transformer(tiny_cfg)
        toks = jnp.zeros((2, 16), jnp.int32)
        variables = model.init(jax.random.PRNGKey(0), toks)
        logits = model.apply(variables, toks)
        assert logits.shape == (2, 16, tiny_cfg.vocab_size)
        assert logits.dtype == jnp.float32

    def test_causality(self, tiny_cfg):
        """Changing a future token must not change past logits."""
        model = Transformer(tiny_cfg)
        toks = jnp.ones((1, 16), jnp.int32)
        variables = model.init(jax.random.PRNGKey(0), toks)
        a = model.apply(variables, toks)
        toks2 = toks.at[0, 10].set(5)
        b = model.apply(variables, toks2)
        np.testing.assert_allclose(a[0, :10], b[0, :10], atol=2e-2)
        assert not np.allclose(a[0, 10:], b[0, 10:], atol=1e-3)

    def test_rotary_preserves_norm(self):
        pos = jnp.arange(8)[None]
        cos, sin = rotary_embedding(pos, 16, 10000.0)
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 16))
        y = apply_rotary(x, cos, sin)
        np.testing.assert_allclose(
            jnp.linalg.norm(x, axis=-1), jnp.linalg.norm(y, axis=-1), rtol=1e-4
        )

    def test_gqa_fewer_kv_heads(self):
        cfg = TransformerConfig(
            vocab_size=64, d_model=64, n_layers=1, n_heads=8, n_kv_heads=2,
            d_ff=128, max_seq_len=32, remat=False,
        )
        model = Transformer(cfg)
        toks = jnp.zeros((1, 8), jnp.int32)
        variables = model.init(jax.random.PRNGKey(0), toks)
        wqkv = variables["params"]["Block_0"]["Attention_0"]["wqkv"]
        expected = (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.head_dim
        assert unbox(wqkv).shape == (cfg.d_model, expected)

    def test_lm_loss_masking(self):
        logits = jnp.zeros((1, 4, 8), jnp.float32)
        tokens = jnp.zeros((1, 4), jnp.int32)
        full = lm_loss(logits, tokens, jnp.ones((1, 4)))
        none = lm_loss(logits, tokens, jnp.zeros((1, 4)))
        assert float(full) == pytest.approx(np.log(8), rel=1e-4)
        assert float(none) == 0.0


class TestShardedTraining:
    def test_param_shardings_follow_rules(self, tiny_cfg):
        mesh = make_mesh({"fsdp": 4, "tensor": 2})
        model = Transformer(tiny_cfg)
        boxed = jax.eval_shape(
            lambda r: model.init(r, jnp.zeros((1, 8), jnp.int32)),
            jax.random.PRNGKey(0),
        )
        sh = param_shardings(mesh, boxed["params"])
        wqkv_sh = sh["Block_0"]["Attention_0"]["wqkv"]
        assert wqkv_sh.spec == jax.sharding.PartitionSpec("fsdp", "tensor")
        embed_sh = sh["embed"]
        assert embed_sh.spec == jax.sharding.PartitionSpec("tensor", "fsdp")

    def test_train_step_runs_sharded(self, tiny_cfg):
        mesh = make_mesh({"data": 2, "fsdp": 2, "tensor": 2})
        tr = CheetahTrainer(tiny_cfg, mesh,
                            optimizer=make_optimizer(learning_rate=1e-2,
                                                     warmup_steps=1))
        state = tr.init_state(jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        toks = jnp.asarray(rng.randint(0, 255, (8, 32)), jnp.int32)
        mask = jnp.ones((8, 32), jnp.int32)
        losses = []
        for _ in range(4):
            state, m = tr.train_step(state, toks, mask)
            losses.append(float(m["loss"]))
        assert int(state.step) == 4
        assert losses[-1] < losses[0]  # memorizes the fixed batch
        # flagship invariant: params actually sharded over the mesh
        wqkv = state.params["Block_0"]["Attention_0"]["wqkv"]
        assert wqkv.sharding.spec == jax.sharding.PartitionSpec("fsdp", "tensor")

    def test_grad_accumulation_matches_large_batch(self, tiny_cfg):
        mesh = make_mesh({"fsdp": 8})
        opt = make_optimizer(learning_rate=1e-2, warmup_steps=1)
        rng = np.random.RandomState(0)
        toks = jnp.asarray(rng.randint(0, 255, (8, 32)), jnp.int32)
        mask = jnp.ones((8, 32), jnp.int32)

        tr1 = CheetahTrainer(tiny_cfg, mesh, optimizer=opt, accum_steps=1)
        s1 = tr1.init_state(jax.random.PRNGKey(0))
        s1, m1 = tr1.train_step(s1, toks, mask)

        toks2 = jnp.concatenate([toks, toks]).reshape(2, 8, 32)
        mask2 = jnp.concatenate([mask, mask]).reshape(2, 8, 32)
        tr2 = CheetahTrainer(tiny_cfg, mesh, optimizer=opt, accum_steps=2)
        s2 = tr2.init_state(jax.random.PRNGKey(0))
        s2, m2 = tr2.train_step(s2, toks2, mask2)
        assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-3)


# -- the chunked loss under a mesh (train_step.lm_loss_chunked) ---------------

_COLLECTIVE = re.compile(
    r"\s(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_CALLEE = re.compile(
    r"(?:body|condition|to_apply|calls|true_computation|false_computation)"
    r"=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")
_LOSS_SCOPE = re.compile(r'op_name="[^"]*[/(]loss[/)"]')


def _computations(hlo_text):
    """name -> instruction lines of every computation in a compiled HLO."""
    comps, lines = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            lines = comps[head.group(1)] = []
        elif line.rstrip() == "}":
            lines = None
        elif lines is not None:
            lines.append(line)
    return comps


def _reachable(comps, root):
    """Lines of ``root`` and of every computation it calls, transitively."""
    seen, todo, lines = set(), [root], []
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        lines += comps[name]
        for line in comps[name]:
            for one, many in _CALLEE.findall(line):
                todo += [one] if one else [
                    c.strip().lstrip("%") for c in many.split(",")]
    return seen, lines


def _collectives(lines):
    """(opcode, line) of the collectives that cross devices: a group of one
    (shard_map's psum over the mesh axes of extent 1) moves nothing."""
    out = []
    for line in lines:
        op = _COLLECTIVE.search(line)
        groups = re.search(r"replica_groups=(\{\{[^}]*\}(?:,\{[^}]*\})*\}"
                           r"|\[[\d,]+\]<=)", line)
        alone = groups and (
            re.fullmatch(r"\{(\{\d+\},?)+\}", groups.group(1))
            or re.fullmatch(r"\[\d+,1\]<=", groups.group(1)))
        if op and not alone:
            out.append((op.group(1), line))
    return out


def _loss_loop_collectives(hlo_text):
    """(collectives inside each ``while`` body that holds ops of the ``loss``
    scope, collectives outside every ``while`` body)."""
    comps = _computations(hlo_text)
    inside, in_loops = [], set()
    for lines in list(comps.values()):
        for line in lines:
            body = re.search(r"\swhile\(.*body=%?([\w.\-]+)", line)
            if not body:
                continue
            names, ops = _reachable(comps, body.group(1))
            in_loops |= names
            if any(_LOSS_SCOPE.search(op) for op in ops):
                inside.append(_collectives(ops))
    outside = _collectives(
        [l for name, ls in comps.items() if name not in in_loops for l in ls])
    return inside, outside


def _shaped(collectives, opcode, shape):
    return [l for op, l in collectives
            if op == opcode and re.search(r"\[%s\]" % shape, l.split(opcode)[0])]


@pytest.fixture(scope="module")
def fp32_cfg(tiny_cfg):
    return dataclasses.replace(tiny_cfg, dtype=jnp.float32)


def _ragged_batch(accum):
    rng = np.random.RandomState(0)
    toks = rng.randint(0, 255, (8, 33)).astype(np.int32)
    ragged = np.ones((8, 33), np.int32)
    ragged[:, 20:] = 0
    ragged[3, 5:] = 0
    shape = (accum, 8 // accum, 33) if accum > 1 else (8, 33)
    return toks.reshape(shape), ragged.reshape(shape)


def _loss_and_grads(cfg, mesh, accum, ragged):
    # chunks of 8 positions: 4 steps of the loss scan
    tr = CheetahTrainer(cfg, mesh, accum_steps=accum, loss_chunk=8)
    params = tr.init_state(jax.random.PRNGKey(0)).params
    toks, mask = _ragged_batch(accum)
    toks, mask = tr.shard_batch(
        jnp.asarray(toks), jnp.asarray(mask if ragged else np.ones_like(mask)))
    with tr._trace_context():
        loss, grads, _ = jax.jit(tr._loss_and_grads)(params, {}, toks, mask)
    return float(loss), jax.device_get(grads)


@pytest.fixture(scope="module")
def one_device_reference(fp32_cfg):
    """(accum, ragged) -> loss and gradients on a one-device mesh."""
    cache = {}

    def get(accum, ragged):
        if (accum, ragged) not in cache:
            mesh = make_mesh({"fsdp": 1}, devices=jax.devices()[:1])
            cache[accum, ragged] = _loss_and_grads(fp32_cfg, mesh, accum, ragged)
        return cache[accum, ragged]

    return get


class TestChunkedLossUnderMesh:
    @pytest.mark.parametrize("accum,ragged", [(1, False), (1, True), (2, True)],
                             ids=["full_mask", "ragged_mask", "accum2"])
    @pytest.mark.parametrize("mesh_shape", [
        {"fsdp": 4}, {"data": 2, "fsdp": 2},
        {"data": 2, "fsdp": 2, "tensor": 2},
    ], ids=["fsdp4", "data2_fsdp2", "data2_fsdp2_tensor2"])
    def test_matches_one_device(self, fp32_cfg, one_device_reference,
                                mesh_shape, accum, ragged):
        """Per batch shard, head gathered once, vocabulary-parallel under
        ``tensor``: the same loss and the same gradient on every leaf."""
        n = int(np.prod(list(mesh_shape.values())))
        mesh = make_mesh(mesh_shape, devices=jax.devices()[:n])
        loss, grads = _loss_and_grads(fp32_cfg, mesh, accum, ragged)
        want_loss, want = one_device_reference(accum, ragged)
        assert loss == pytest.approx(want_loss, rel=1e-6)
        for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                                jax.tree.leaves(want)):
            rel = np.linalg.norm(g - w) / np.linalg.norm(w)
            assert rel < 1e-5, (jax.tree_util.keystr(path), rel)

    @pytest.mark.parametrize("mesh_shape,gathers", [
        ({"fsdp": 4}, 1), ({"fsdp": 1}, 0),
    ], ids=["fsdp4", "one_device"])
    def test_head_collectives_in_compiled_step(self, tiny_cfg, mesh_shape,
                                               gathers):
        """Under ``fsdp`` the compiled step moves the head once each way and
        has no collective in the loss scan; on one device it has none at all
        and no shard_map; ``loss_head_gathers_per_step`` says which."""
        n = int(np.prod(list(mesh_shape.values())))
        mesh = make_mesh(mesh_shape, devices=jax.devices()[:n])
        tr = CheetahTrainer(tiny_cfg, mesh, loss_chunk=8)
        state = tr.init_state(jax.random.PRNGKey(0))
        toks = jnp.zeros((8, 33), jnp.int32)
        hlo = tr.lower_step(
            state, toks, jnp.ones_like(toks)).compile().as_text()
        inside, outside = _loss_loop_collectives(hlo)
        assert tr.loss_head_gathers_per_step == gathers
        assert len(inside) >= 2  # the forward scan and the backward scan
        assert all(not loop for loop in inside), inside
        D, V = tiny_cfg.d_model, tiny_cfg.vocab_size
        assert len(_shaped(outside, "all-gather", f"{D},{V}")) == gathers
        scattered = (_shaped(outside, "reduce-scatter", f"{D // n},{V}")
                     + _shaped(outside, "all-reduce", f"{D},{V}"))
        assert len(scattered) == gathers
        # op names carry the name stack: .../loss/shard_map/all_gather
        assert ("/shard_map/" in hlo) == bool(gathers)
        if not gathers:
            assert not outside


class TestGraftEntry:
    def test_entry_compiles(self):
        import __graft_entry__ as g

        fn, ex = g.entry()
        out = jax.jit(fn)(*ex)
        assert out.shape[-1] == 2048

    def test_dryrun_multichip(self, capsys):
        import __graft_entry__ as g

        g.dryrun_multichip(8)
        assert "dryrun_multichip ok" in capsys.readouterr().out
