"""The state-space recurrence's kernels ``ssd_chunk_fwd`` / ``ssd_chunk_bwd``
(ISSUE 39) on XLA:CPU under Pallas' interpreter, at sizes that fill their
blocks: against the recurrence and against the plain chunked form, forward in
both modes and every gradient, in float32 and bfloat16; a step size at which a
factored decay would overflow; and the one decision that takes them,
``ssd.scan_path``, with what the trainer reports of it."""

from __future__ import annotations

import dataclasses
import functools
import logging
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core import mlops
from fedml_tpu.parallel import ssd
from fedml_tpu.parallel.context import mesh_context
from fedml_tpu.parallel.sharding import make_mesh, unbox
from fedml_tpu.parallel.train_step import CheetahTrainer
from fedml_tpu.parallel.transformer import Transformer, TransformerConfig

# heads of 64 side by side in lane tiles, two tiles a group (one in some), state
# 128, chunks of 128: two chunk blocks, so the state crosses a block boundary
CHUNK = 128
LENGTH = 2 * ssd.CHUNKS_PER_STEP * CHUNK
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
OPERANDS = ("x", "dt", "A", "B", "C", "S0", "D")


def _rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def ssd_inputs(L=LENGTH, b=2, H=8, P=64, G=2, N=128, seed=0, dt_shift=-2.0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(k[0], (b, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, L, H)) + dt_shift)
    A = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.7))
    B = jax.random.normal(k[3], (b, L, G, N))
    C = jax.random.normal(k[4], (b, L, G, N))
    S0 = jax.random.normal(k[5], (b, H, P, N))
    D = jax.random.normal(k[6], (H,))
    return x, dt, A, B, C, S0, D


def _both(form, dtype, inputs):
    """(y, the last state) and the gradients of a seeded linear functional of
    them, in every operand. ``form(x, dt, A, B, C, S0, D, dtype)``."""
    weights = (jax.random.normal(jax.random.PRNGKey(9), inputs[0].shape),
               jax.random.normal(jax.random.PRNGKey(8), inputs[5].shape))

    def functional(*a):
        y, S = form(*a, dtype)
        return jnp.sum(y * weights[0]) + jnp.sum(S * weights[1]), (y, S)

    with jax.default_matmul_precision("highest"):
        (_, outs), grads = jax.value_and_grad(
            functional, argnums=range(7), has_aux=True)(*inputs)
    return dict(zip(("y", "S") + tuple("d" + n for n in OPERANDS),
                    (*outs, *grads)))


def plain(x, dt, A, B, C, S0, D, dtype):
    return ssd.ssd_chunked(x, dt, A, B, C, CHUNK, dtype, S0, D)


def kernels(x, dt, A, B, C, S0, D, dtype):
    return ssd.ssd_fused(x, dt, A, B, C, CHUNK, dtype, S0, D, interpret=True)


def recurrence(x, dt, A, B, C, S0, D, dtype):
    y, S = ssd.ssd_recurrence(x, dt, A, B, C, S0)
    return y + D[:, None] * x, S


@functools.lru_cache(maxsize=None)
def computed(dtype):
    """The kernels under their ``custom_vjp``, the plain form in the same
    dtype and, in float32, the recurrence, on the same seeded inputs."""
    dt = DTYPES[dtype]
    inputs = ssd_inputs()
    return (_both(kernels, dt, inputs), _both(plain, dt, inputs),
            _both(recurrence, dt, inputs) if dtype == "float32" else None)


QUANTITIES = ("y", "S") + tuple("d" + n for n in OPERANDS)


@pytest.mark.parametrize("quantity", QUANTITIES)
@pytest.mark.parametrize("dtype", list(DTYPES))
def test_kernels_are_the_plain_form(dtype, quantity):
    """Output, last state and the gradient of each of the five operands, of
    the initial state and of ``D``: the interpreted kernels against the plain
    chunked form in the same dtype. In bfloat16 the forward rounds where the
    plain form does, so it agrees far inside a rounding; the gradients differ
    by the summation order of rounded products."""
    got, want, _ = computed(dtype)
    assert got[quantity].dtype == want[quantity].dtype
    assert got[quantity].shape == want[quantity].shape
    assert bool(jnp.isfinite(got[quantity].astype(jnp.float32)).all())
    forward = quantity in ("y", "S")
    tol = (1e-5 if dtype == "float32" else 1e-5 if forward else 1e-2)
    if quantity == "dA" and dtype == "float32":
        tol = 1e-4  # a sum over every token of differences of large terms
    assert _rel(got[quantity], want[quantity]) < tol


@pytest.mark.parametrize("quantity", QUANTITIES)
def test_kernels_are_the_recurrence(quantity):
    got, _, want = computed("float32")
    assert _rel(got[quantity], want[quantity]) < (
        2e-4 if quantity == "dA" else 2e-5)


def test_bfloat16_gradients_are_no_further_from_float32_than_the_plain_forms():
    """Against the float32 gradients the kernels in bfloat16 are within 1.5
    times what the plain form in bfloat16 is: the hand-written backward
    rounds nowhere that autodiff's does not."""
    got, want, _ = computed("bfloat16")
    exact, _, _ = computed("float32")
    for name in QUANTITIES[2:]:
        assert _rel(got[name], exact[name]) < 1.5 * _rel(
            want[name], exact[name]) + 1e-6, name


@pytest.mark.parametrize("dtype", list(DTYPES))
def test_both_forward_modes_and_the_saved_states(dtype):
    """Without residuals and with them the forward gives the same output and
    last state; the state saved for chunk ``c`` is the one the recurrence
    holds after ``c`` chunks, across the chunk-block boundary too, the first
    the initial state."""
    x, dt, A, B, C, S0, D = ssd_inputs(b=1, H=4)
    b, L, H, P = x.shape
    G, N = B.shape[-2:]
    a = jnp.cumsum((dt * A).reshape(b, L // CHUNK, CHUNK, H), 2).reshape(b, L, H)
    S0T = jnp.transpose(S0.reshape(b, G, H // G, P, N), (0, 1, 4, 2, 3)).reshape(
        b, G, N, H // G * P)
    operands = (x.reshape(b, L, H * P), dt, a, B.reshape(b, L, G * N),
                C.reshape(b, L, G * N),
                jnp.repeat(D, P).reshape(G, 1, H // G * P), S0T)
    kw = dict(heads=H, groups=G, chunk=CHUNK, dtype=jnp.dtype(DTYPES[dtype]),
              interpret=True)
    y, ST = ssd.chunk_fwd(*operands, save_states=False, **kw)
    y2, ST2, states = ssd.chunk_fwd(*operands, save_states=True, **kw)
    assert y.dtype == jnp.float32 and states.dtype == jnp.float32
    assert np.array_equal(y, y2) and np.array_equal(ST, ST2)
    assert states.shape == (b, L // CHUNK, G, N, H // G * P)
    assert np.array_equal(states[:, 0], S0T)
    for c in (1, ssd.CHUNKS_PER_STEP, ssd.CHUNKS_PER_STEP + 1):
        _, S = ssd.ssd_chunked(*(v[:, :c * CHUNK] for v in (x, dt)), A,
                               *(v[:, :c * CHUNK] for v in (B, C)), CHUNK,
                               DTYPES[dtype], S0)
        want = jnp.transpose(S.reshape(b, G, H // G, P, N),
                             (0, 1, 4, 2, 3)).reshape(b, G, N, H // G * P)
        assert _rel(states[:, c], want) < 1e-5, c


def test_step_sizes_at_which_a_factored_decay_overflows():
    """``dt A`` of -1.3 to -2.5 a token: the running sum passes -88 inside
    every chunk, so ``exp(-a_s)`` is infinite in float32 and a decay factored
    as ``exp(a_t) exp(-a_s)`` is ``0 x inf``. The kernels mask the gap before
    the exponential, as the plain form does: everything finite, and the
    recurrence's values."""
    x, dt, A, B, C, S0, D = ssd_inputs(b=1, H=2, G=1, seed=3)
    dt = 0.5 + dt
    A = jnp.array([-2.0, -4.0])
    a = jnp.cumsum((dt * A).reshape(1, -1, CHUNK, 2), axis=2)
    assert float(a.min()) < -150 and not bool(jnp.isfinite(jnp.exp(-a)).all())
    inputs = (x, dt, A, B, C, S0, D)
    got = _both(kernels, jnp.float32, inputs)
    want = _both(recurrence, jnp.float32, inputs)
    for name in QUANTITIES:
        assert bool(jnp.isfinite(got[name]).all()), name
        assert _rel(got[name], want[name]) < 1e-4, name


# ---------------------------------------------------------------------------
# the decision
# ---------------------------------------------------------------------------

CELL = dict(heads=64, head_dim=64, groups=8, state=128, seq_len=8192, chunk=128)


def _on_a_tpu(monkeypatch):
    monkeypatch.setattr(
        jax, "devices", lambda *a: [types.SimpleNamespace(platform="tpu")])


@pytest.mark.parametrize("change, why", [
    ({}, "fused"),
    (dict(seq_len=8192 + 128), "a sequence that is no whole number of blocks"),
    (dict(seq_len=128), "shorter than a block"),
    (dict(head_dim=48), "heads that do not fill lane tiles"),
    (dict(head_dim=128), "a head that fills a tile alone"),
    (dict(head_dim=32, heads=128), "fused"),
    (dict(heads=8, groups=8), "a group's heads fill half a tile"),
    (dict(state=64), "a state off the lanes"),
    (dict(chunk=64, seq_len=8192), "a chunk off the lanes"),
    (dict(groups=5), "heads that do not divide into the groups"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_scan_path_reads_the_sizes(change, why, monkeypatch):
    sizes = dict(CELL, **change)
    assert ssd.scan_path(**sizes, mesh=None) == "xla"  # this CPU
    _on_a_tpu(monkeypatch)
    assert ssd.scan_path(**sizes, mesh=None) == (
        "fused" if why == "fused" else "xla"), why


def test_scan_path_reads_the_mesh(monkeypatch):
    one = make_mesh(None, devices=jax.devices()[:1])
    two = make_mesh({"fsdp": 2}, devices=jax.devices()[:2])
    _on_a_tpu(monkeypatch)
    assert ssd.scan_path(**CELL, mesh=one) == "fused"
    assert ssd.scan_path(**CELL, mesh=two) == "xla"


def test_the_chunked_form_takes_the_path_scan_path_names(monkeypatch):
    """``ssd_chunked`` hands its operands to the kernels where the answer is
    ``fused`` and nowhere else: off the TPU, under the ambient mesh of two
    devices and at a length that fills no block it is the plain form; and
    lowered for a TPU it calls both kernels by name."""
    called = []
    real = ssd.ssd_fused

    def recorded(*a, **k):
        called.append(a[5])
        return real(*a, **k, interpret=True)

    monkeypatch.setattr(ssd, "ssd_fused", recorded)
    x, dt, A, B, C, S0, D = ssd_inputs(L=ssd.CHUNKS_PER_STEP * CHUNK, b=1, H=2, G=1)
    want, _ = ssd.ssd_chunked(x, dt, A, B, C, CHUNK, skip=D)
    assert called == []
    two = make_mesh({"fsdp": 2}, devices=jax.devices()[:2])
    _on_a_tpu(monkeypatch)
    got, _ = ssd.ssd_chunked(x, dt, A, B, C, CHUNK, skip=D)
    assert called == [CHUNK] and _rel(got, want) < 1e-5
    with mesh_context(two):
        ssd.ssd_chunked(x, dt, A, B, C, CHUNK)
    ssd.ssd_chunked(x[:, :CHUNK], dt[:, :CHUNK], A, B[:, :CHUNK], C[:, :CHUNK],
                    CHUNK)
    assert called == [CHUNK]
    monkeypatch.setattr(ssd, "ssd_fused", real)

    def loss(*a):
        y, S = ssd.ssd_chunked(*a, CHUNK, jnp.bfloat16)
        return jnp.sum(y) + jnp.sum(S)

    text = jax.jit(jax.grad(loss, argnums=range(5))).trace(
        x, dt, A, B, C).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    assert "ssd_chunk_fwd" in text and "ssd_chunk_bwd" in text


def kernel_sized(**kw) -> TransformerConfig:
    """Two state-space layers and a dense one at sizes the kernels take: 4
    heads of 64 in 2 groups of state 128, chunks of 128, 512 tokens."""
    base = dict(
        vocab_size=96, d_model=64, n_layers=3, n_heads=4, n_kv_heads=2, d_ff=48,
        max_seq_len=ssd.CHUNKS_PER_STEP * CHUNK, remat=False, attn_impl="xla",
        dtype=jnp.float32, attn_head_dim=32, pos_emb="none", layer_pattern="MM-",
        ssm_heads=4, ssm_head_dim=64, ssm_groups=2, ssm_state=128,
        ssm_conv_size=4, ssm_chunk=CHUNK)
    base.update(kw)
    return TransformerConfig(**base)


def test_a_mixer_on_the_kernels_is_the_mixer_on_the_plain_form(monkeypatch):
    """The model's loss and its parameters' gradients with the layers'
    recurrence on the interpreted kernels (the ``D`` skip inside them) against
    the same model on the plain form."""
    cfg = kernel_sized()
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, cfg.max_seq_len), 0, 96)
    model = Transformer(cfg)
    params = unbox(model.init(jax.random.PRNGKey(0), tokens)["params"])

    def loss(p):
        logits = model.apply({"params": p}, tokens)
        logits = logits[0] if isinstance(logits, tuple) else logits
        return jnp.mean(jax.nn.logsumexp(logits, -1) - logits[..., 0])

    want, want_grads = jax.value_and_grad(loss)(params)
    monkeypatch.setattr(ssd, "scan_path", lambda *a, **k: "fused")
    monkeypatch.setattr(ssd, "ssd_fused",
                        functools.partial(ssd.ssd_fused, interpret=True))
    got, got_grads = jax.value_and_grad(loss)(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(want_grads)
    for (path, w), g in zip(flat, jax.tree.leaves(got_grads)):
        assert _rel(g, w) < 1e-3, jax.tree_util.keystr(path)


def _init_event(trainer, monkeypatch):
    events = []
    monkeypatch.setattr(mlops, "_emit", events.append)
    trainer.init_state(jax.random.PRNGKey(0))
    (event,) = [e for e in events if e["kind"] == "cheetah_init"]
    return event


def test_the_trainer_reports_the_path_and_warns_on_a_tpu_that_runs_xla(
        monkeypatch, caplog):
    cfg = kernel_sized()
    one = make_mesh(None, devices=jax.devices()[:1])
    two = make_mesh({"fsdp": 2}, devices=jax.devices()[:2])
    with caplog.at_level(logging.WARNING):
        trainer = CheetahTrainer(cfg, one)
        event = _init_event(trainer, monkeypatch)
    assert event["ssd"]["path"] == trainer.ssd["path"] == "xla"  # this CPU
    assert "parallel/ssd.scan_path" not in caplog.text
    _on_a_tpu(monkeypatch)
    trainer = CheetahTrainer(cfg, one)
    assert trainer.ssd == {"heads": 4, "head_dim": 64, "groups": 2,
                           "state": 128, "chunk": CHUNK, "path": "fused"}
    short = CheetahTrainer(dataclasses.replace(cfg, max_seq_len=CHUNK), one)
    assert short.ssd["path"] == "xla"
    with caplog.at_level(logging.WARNING):
        wide = CheetahTrainer(cfg, two)
        event = _init_event(wide, monkeypatch)
    assert event["ssd"]["path"] == wide.ssd["path"] == "xla"
    assert "parallel/ssd.scan_path" in caplog.text
    assert CheetahTrainer(TransformerConfig.tiny(), one).ssd == {}
