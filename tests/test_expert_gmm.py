"""The experts' grouped-product kernels ``expert_gmm_fwd`` / ``_dlhs`` /
``_drhs`` (``parallel/expert_gmm.py``) on XLA:CPU under Pallas' interpreter,
against ``jax.lax.ragged_dot`` and its autodiff: widths that are no multiple
of 128 lanes, lane tiles with a remainder, an empty group, a group of one row,
rows past the last group that hold NaN; the expert layer on the kernels
against the same layer on ``ragged_dot``; the one decision that takes them,
``expert_gmm.path``, with what the trainer reports of it and of the
dispatch's transpose (``expert_rows.path``); and the kernels, with the row
kernel ``expert_rows_sum``, compiled for a described v5e at the five expert
cells' shapes."""

from __future__ import annotations

import dataclasses
import functools
import logging
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core import mlops
from fedml_tpu.parallel import expert_gmm
from fedml_tpu.parallel.sharding import make_mesh
from fedml_tpu.parallel.train_step import CheetahTrainer
from fedml_tpu.parallel.transformer import TransformerConfig

# bfloat16 operands, float32 accumulation on both sides: the kernels and XLA's
# dot add the same products in another order, so a result rounded to
# bfloat16 may differ by one rounding (2^-8 relative) where XLA:CPU's
# float32 reference differs by none; in float32 they agree to the order of
# summation
TOLERANCE = {"float32": 1e-5, "bfloat16": 1e-2}
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
# (rows, K, N, counts): 2.5 / 1.5 lanes of 128, and 3 x 128 + 64 against
# 2.5 x 128, the Nemotron cell's 2,688 x 1,856 remainder scaled down; groups
# with an empty one, one of a single row, and rows past the last group
CASES = {
    "320x192": (512, 320, 192, (130, 0, 1, 200)),
    "448x320": (384, 448, 320, (0, 127, 129, 1)),
    "boundaries": (384, 192, 320, (128, 128, 0, 100)),
}


def _rel(a, b):
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _operands(rows, K, N, counts, dtype, seed=0):
    """x and the cotangent dy with NaN in every row past the last group,
    matrices scaled to keep the products near 1, and the reference's x with
    those rows zero."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    counts = jnp.asarray(counts, jnp.int32)
    used = int(counts.sum())
    past = jnp.arange(rows)[:, None] >= used
    x = jax.random.normal(k1, (rows, K), jnp.float32).astype(dtype)
    w = (jax.random.normal(k2, (len(counts), K, N)) / np.sqrt(K)).astype(dtype)
    dy = jax.random.normal(k3, (rows, N), jnp.float32).astype(dtype)
    return (jnp.where(past, jnp.nan, x), w, counts, jnp.where(past, jnp.nan, dy),
            jnp.where(past, 0, x), jnp.where(past, 0, dy), used)


def _reference(x, w, counts, dy):
    out, vjp = jax.vjp(lambda a, b: jax.lax.ragged_dot(
        a, b, counts, preferred_element_type=a.dtype), x, w)
    return (out,) + vjp(dy)


def _kernels(x, w, counts, dy):
    out, vjp = jax.vjp(lambda a, b: expert_gmm.grouped_product(
        a, b, counts, interpret=True), x, w)
    return (out,) + vjp(dy)


@pytest.mark.parametrize("dtype", sorted(TOLERANCE))
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernels_are_ragged_dot_and_its_gradients(case, dtype):
    """The product, the rows' gradient and the matrices' gradient on the rows
    that belong to a group; NaN in the rows past the last group, in ``x`` and
    in the cotangent alike, reaches none of them (an empty group's gradient
    is zeros)."""
    rows, K, N, counts = CASES[case]
    x, w, counts, dy, x0, dy0, used = _operands(rows, K, N, counts,
                                                DTYPES[dtype])
    want = _reference(x0, w, counts, dy0)
    got = _kernels(x, w, counts, dy)
    for name, g, r in zip(("out", "dx", "dw"), got, want):
        assert g.dtype == r.dtype and g.shape == r.shape, name
        if name != "dw":
            g, r = g[:used], r[:used]
        assert bool(jnp.isfinite(g.astype(jnp.float32)).all()), name
        assert _rel(g, r) < TOLERANCE[dtype], name
    empty = [i for i, c in enumerate(counts.tolist()) if c == 0]
    assert not bool(jnp.any(got[2][jnp.array(empty, int)])), "empty group"


def test_lane_tiles_with_a_remainder(monkeypatch):
    """Every result cut into tiles of 128 lanes whose last one runs past the
    width (192 as 2 x 128, 320 as 3 x 128): the results stay the
    definition's."""
    monkeypatch.setattr(expert_gmm, "tiles",
                        lambda k, n, itemsize=2: expert_gmm.Tiles(128, 128, 128))
    rows, K, N = 256, 320, 192
    x, w, counts, dy, x0, dy0, used = _operands(rows, K, N, (60, 70, 0, 100),
                                                jnp.float32, seed=3)
    want = _reference(x0, w, counts, dy0)
    got = _kernels(x, w, counts, dy)
    assert _rel(got[0][:used], want[0][:used]) < 1e-5
    assert _rel(got[1][:used], want[1][:used]) < 1e-5
    assert _rel(got[2], want[2]) < 1e-5


def test_the_tiling_rule_reads_the_shapes():
    """Widths whole where two buffers of the blocks fit, else tiles of a
    multiple of 128 lanes that leave the fewest lanes past the width: the
    Nemotron cell's 1,856 is never cut into 64-lane tiles."""
    for k, n in ((2688, 1856), (1856, 2688), (3584, 2048), (1024, 3584),
                 (2560, 1536), (768, 2560), (2048, 1536), (768, 2048),
                 (2048, 3584), (1792, 2048)):
        t = expert_gmm.tiles(k, n)
        assert t is not None
        for lanes, width in zip(t, (n, k, n)):
            assert lanes == width or lanes % 128 == 0, (k, n, t)
    assert expert_gmm.tiles(2688, 1856) == expert_gmm.Tiles(1856, 2688, 640)
    assert expert_gmm.tiles(1 << 16, 1 << 16) is None   # no 128 lanes fit


@pytest.mark.parametrize("budget, lanes", [
    (2000, 1856),  # whole
    (1000, 640),   # 3 x 640 = 1,920: 64 past the width, the fewest tiles
    (500, 384),    # 5 x 384 = 1,920
    (200, 128),    # 15 x 128 = 1,920
    (100, None),   # not even 128 lanes
])
def test_a_width_that_does_not_fit_whole_leaves_the_fewest_lanes_past_it(
        budget, lanes, monkeypatch):
    monkeypatch.setattr(expert_gmm, "_VMEM_BUDGET", budget)
    assert expert_gmm._lane_tile(1856, lambda t: t) == lanes


# ---------------------------------------------------------------------------
# the expert layer on the kernels
# ---------------------------------------------------------------------------


def _layer_cfg(**kw):
    base = dict(vocab_size=128, d_model=32, n_layers=1, n_heads=4,
                n_kv_heads=4, d_ff=64, max_seq_len=64, remat=False,
                dtype=jnp.float32, moe_experts=16, moe_top_k=2, moe_d_ff=24,
                moe_experts_held=4, moe_expert_offset=4,
                moe_capacity_factor=0.0)
    base.update(kw)
    return TransformerConfig(**base)


@pytest.mark.parametrize("act", ["swiglu", "relu2"])
def test_an_expert_layer_on_the_kernels_is_the_layer_on_ragged_dot(
        act, monkeypatch):
    """The layer's output and every parameter's and the input's gradient with
    the grouped products on the interpreted kernels, against the same layer
    on ``ragged_dot``: 2 x 64 = 128 rows, a quarter of them held."""
    import flax

    from fedml_tpu.parallel.moe import MoEFeedForward

    cfg = _layer_cfg(ffn_act=act)
    layer = MoEFeedForward(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 64, cfg.d_model))
    params = dict(flax.core.meta.unbox(
        layer.init(jax.random.PRNGKey(1), x))["params"])
    params["w_router"] = params["w_router"] * 100.0

    def loss(p, x):
        (y, aux), _ = layer.apply({"params": p}, x, mutable=["moe_stats"])
        return jnp.sum(y * jnp.cos(y)) + aux

    want, want_grads = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    called = []
    monkeypatch.setattr(expert_gmm, "layer_path",
                        lambda *a: called.append(a) or "kernel")
    monkeypatch.setattr(expert_gmm, "grouped_product", functools.partial(
        expert_gmm.grouped_product, interpret=True))
    got, got_grads = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    up = 2 * cfg.moe_d_ff if act == "swiglu" else cfg.moe_d_ff
    assert called and called[0][:4] == (128, cfg.d_model, up, cfg.moe_d_ff)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(want_grads)
    for (path, w), g in zip(flat, jax.tree.leaves(got_grads)):
        assert _rel(g, w) < 1e-5, jax.tree_util.keystr(path)


def test_a_lowering_for_a_tpu_calls_the_three_kernels_by_name(monkeypatch):
    """Where the path is ``kernel`` the step's products are the three Mosaic
    calls, and no ``ragged_dot`` is left."""
    from fedml_tpu.parallel.moe import MoEFeedForward

    cfg = _layer_cfg(dtype=jnp.bfloat16)
    layer = MoEFeedForward(cfg)
    x = jnp.zeros((1, 64, cfg.d_model), jnp.bfloat16)
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    monkeypatch.setattr(expert_gmm, "layer_path", lambda *a: "kernel")

    def loss(p, x):
        (y, _), _ = layer.apply({"params": p}, x, mutable=["moe_stats"])
        return jnp.sum(y.astype(jnp.float32))

    text = jax.jit(jax.grad(loss)).trace(params, x).lower(
        lowering_platforms=("tpu",)).as_text()
    for name in ("expert_gmm_fwd", "expert_gmm_dlhs", "expert_gmm_drhs"):
        assert name in text, name
    assert "ragged_dot" not in text


# ---------------------------------------------------------------------------
# the path, and what the trainer says of it
# ---------------------------------------------------------------------------

# the five expert cells' (rows a step, d_model, up width, expert width)
CELLS = {
    "xing4": (4 * 8192, 3584, 2048, 1024),
    "ling3": (8 * 8192, 2560, 1536, 768),
    "sdar": (8 * 8192, 2048, 1536, 768),
    "nemotron3": (6 * 8192, 2688, 1856, 1856),
    "lfm2": (4 * 8192, 2048, 3584, 1792),
}


def _on_a_tpu(monkeypatch):
    monkeypatch.setattr(
        jax, "devices", lambda *a: [types.SimpleNamespace(platform="tpu")])


def test_path_reads_the_back_end_the_mesh_and_the_shapes(monkeypatch):
    one = make_mesh(None, devices=jax.devices()[:1])
    four = make_mesh({"fsdp": 4}, devices=jax.devices()[:4])
    for rows, D, up, F in CELLS.values():
        assert expert_gmm.layer_path(rows, D, up, F, None) == "xla"  # this CPU
    _on_a_tpu(monkeypatch)
    for cell, (rows, D, up, F) in CELLS.items():
        assert expert_gmm.layer_path(rows, D, up, F, None) == "kernel", cell
        assert expert_gmm.layer_path(rows, D, up, F, one) == "kernel", cell
        assert expert_gmm.layer_path(rows, D, up, F, four) == "xla", cell
    assert expert_gmm.path(6 * 8192 + 64, 2688, 1856, None) == "xla"  # rows
    assert expert_gmm.path(1024, 1 << 16, 1 << 16, None) == "xla"     # VMEM


def _init_event(trainer, monkeypatch):
    events = []
    monkeypatch.setattr(mlops, "_emit", events.append)
    trainer.init_state(jax.random.PRNGKey(0))
    (event,) = [e for e in events if e["kind"] == "cheetah_init"]
    return event


def test_the_trainer_reports_the_path_and_tiles_and_warns_on_a_tpu_that_runs_xla(
        monkeypatch, caplog):
    cfg = _layer_cfg(n_layers=2, first_k_dense=1, ffn_act="relu2",
                     max_seq_len=128)
    one = make_mesh(None, devices=jax.devices()[:1])
    two = make_mesh({"fsdp": 2}, devices=jax.devices()[:2])
    with caplog.at_level(logging.WARNING):
        trainer = CheetahTrainer(cfg, one)
        event = _init_event(trainer, monkeypatch)
    tiles = {"up": dict(k=32, n=24, fwd=24, dlhs=32, drhs=24),
             "down": dict(k=24, n=32, fwd=32, dlhs=24, drhs=32)}
    # the dispatch's transpose: rows 32 lanes wide take no kernel, and only
    # the kernel has a token tile
    gathers = dict(gathers="xla", route="select")
    assert event["experts"] == trainer.experts == dict(path="xla", **tiles,
                                                       **gathers)
    assert "parallel/expert_gmm.path" not in caplog.text
    assert "parallel/expert_rows.path" not in caplog.text
    _on_a_tpu(monkeypatch)
    assert CheetahTrainer(cfg, one).experts == dict(path="kernel", **tiles,
                                                    **gathers)
    lanes = dataclasses.replace(cfg, d_model=128)
    assert CheetahTrainer(lanes, one).experts["gathers"] == "kernel"
    assert CheetahTrainer(lanes, one).experts["token_tile"] == 128
    # a program that holds every expert: XLA's gather is the faster form,
    # and nothing is amiss
    every = dataclasses.replace(lanes, moe_experts_held=16, moe_expert_offset=0)
    with caplog.at_level(logging.WARNING):
        held = CheetahTrainer(every, one)
        _init_event(held, monkeypatch)
    assert held.experts["gathers"] == "xla" and "token_tile" not in held.experts
    assert "parallel/expert_rows.path" not in caplog.text
    with caplog.at_level(logging.WARNING):
        wide = CheetahTrainer(cfg, two)
        event = _init_event(wide, monkeypatch)
    assert event["experts"]["path"] == wide.experts["path"] == "xla"
    assert event["experts"]["gathers"] == "xla"
    assert "parallel/expert_gmm.path" in caplog.text
    assert "parallel/expert_rows.path" in caplog.text
    # no expert layer: nothing to report; under a capacity, no grouped
    # products and the router's kept mask by a gather
    assert CheetahTrainer(TransformerConfig.tiny(), one).experts == {}
    capacity = dataclasses.replace(cfg, moe_capacity_factor=1.25)
    assert CheetahTrainer(capacity, one).experts == {"route": "gather"}


# ---------------------------------------------------------------------------
# compiled for a described v5e (nothing runs: the compiler's refusals)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler to describe one
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def uncached():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_kernels_compile_for_a_v5e_at_the_cells_shapes(cell, one_chip,
                                                            uncached):
    rows, D, up, F = CELLS[cell]
    held = 16 if cell == "sdar" else 8
    bf16 = jnp.bfloat16
    for k, n in ((D, up), (F, D)):
        def spec(*shape, dtype=bf16):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        def step(x, w, counts, dy):
            out, vjp = jax.vjp(
                lambda a, b: expert_gmm.grouped_product(a, b, counts), x, w)
            return out, vjp(dy)

        text = jax.jit(step).lower(
            spec(rows, k), spec(held, k, n), spec(held, dtype=jnp.int32),
            spec(rows, n)).compile().as_text()
        for name in ("expert_gmm_fwd", "expert_gmm_dlhs", "expert_gmm_drhs"):
            assert name in text, (cell, k, n, name)


# the five expert cells' dispatch transposes: (choices, tokens, width)
GATHERS = {"xing4": (4, 8192, 3584), "ling3": (8, 8192, 2560),
           "sdar": (8, 8192, 2048), "nemotron3": (6, 8192, 2688),
           "lfm2": (4, 8192, 2048)}


@pytest.mark.parametrize("cell", sorted(GATHERS))
def test_the_row_kernel_compiles_for_a_v5e_at_the_cells_shapes(
        cell, one_chip, uncached):
    """``expert_rows_sum``, the dispatch's transpose, at each cell's ``[k, T,
    D]`` over its ``[kT, D]`` rows, in bfloat16 and, as the float32
    reference's layers run it, in float32."""
    from fedml_tpu.parallel import expert_rows

    k, T, D = GATHERS[cell]
    for dtype in (jnp.bfloat16, jnp.float32):
        rows = jax.ShapeDtypeStruct((k * T, D), dtype, sharding=one_chip)
        src = jax.ShapeDtypeStruct((k, T), jnp.int32, sharding=one_chip)
        text = jax.jit(expert_rows.rows_sum).lower(rows, src).compile().as_text()
        assert "expert_rows_sum" in text, (cell, dtype)
