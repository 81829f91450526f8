"""The one-pass backward of a hyper-connected block's stream read and stream
write (``parallel/mhc_streams.py``, ISSUE 29) on XLA:CPU: the hand-written
VJPs against ``jax.vjp`` of the plain expressions, the kernel bodies under
Pallas' TPU interpret mode, and the choice between the two backwards, which
is read from the shape, the back-end and the mesh and reaches
``cheetah_init``."""

from __future__ import annotations

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core import mlops
from fedml_tpu.parallel import mhc_streams as ms
from fedml_tpu.parallel.sharding import make_mesh
from fedml_tpu.parallel.train_step import CheetahTrainer
from fedml_tpu.parallel.transformer import TransformerConfig


def _inputs(n, L, C, dtype):
    ks = jax.random.split(jax.random.PRNGKey(n * L + C), 7)
    B = 2
    return dict(
        X=jax.random.normal(ks[0], (B, n, L, C)).astype(dtype),
        y=jax.random.normal(ks[1], (B, L, C)).astype(dtype),
        g=jax.random.normal(ks[2], (B, n, L, C)).astype(dtype),
        g_h=jax.random.normal(ks[3], (B, L, C)).astype(dtype),
        pre=jax.nn.sigmoid(jax.random.normal(ks[4], (B, L, n))),
        post=2.0 * jax.nn.sigmoid(jax.random.normal(ks[5], (B, L, n))),
        res=jax.nn.softmax(jax.random.normal(ks[6], (B, L, n, n))))


# L is one whole tile (192 or 256 rows at these widths), or a whole one and
# one that hangs over the end. float32: the same products, sums
# in another order, so 1e-5 of the largest entry. bfloat16 streams: autodiff
# rounds each of the n terms of a stream's gradient to bfloat16 and adds them
# there, the kernel rounds their float32 sum once; the two lie within one
# bfloat16 step (2^-7) at the largest entry.
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("C", [128, 384])
@pytest.mark.parametrize("over", [0, 40], ids=["whole_tile", "overhang"])
@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("op", ["read", "write"])
def test_hand_written_vjp_is_autodiff_of_the_plain_expressions(
        op, n, over, C, dtype, fused_mhc_backward):
    L = ms._tile_rows(n, C, jnp.dtype(dtype).itemsize) + over
    a = _inputs(n, L, C, dtype)
    if op == "read":
        primals, cotangent = (a["X"], a["pre"]), a["g_h"]
        plain, program, names = ms._read_plain, ms.streams_read, ["dX", "dpre"]
    else:
        primals, cotangent = (a["X"], a["y"], a["post"], a["res"]), a["g"]
        plain, program = ms._write_plain, ms.streams_write
        names = ["dX", "dy", "dpost", "dres"]
    want_out, want_vjp = jax.vjp(plain, *primals)
    got_out, got_vjp = jax.vjp(program, *primals)
    np.testing.assert_array_equal(np.asarray(got_out, np.float32),
                                  np.asarray(want_out, np.float32))
    for name, want, got in zip(names, want_vjp(cotangent), got_vjp(cotangent),
                               strict=True):
        assert (got.shape, got.dtype) == (want.shape, want.dtype), name
        want, got = np.asarray(want, np.float32), np.asarray(got, np.float32)
        streams = want.shape[-1] == C and dtype == jnp.bfloat16
        tol = (2.0 ** -7 if streams else 1e-5) * np.abs(want).max()
        off = np.abs(got - want).max()
        assert off <= tol, (name, off)
    assert fused_mhc_backward == [op]
    # the kernel carries its name, inside the scope the forward opened
    (rule,) = [e for e in jax.make_jaxpr(got_vjp)(cotangent).eqns
               if e.primitive.name in ("pjit", "jit")]
    (call,) = [e for e in rule.params["jaxpr"].eqns
               if e.primitive.name == "pallas_call"]
    scopes = [s.name for e in (rule, call)
              for s in e.source_info.name_stack.stack
              if type(s).__name__ == "Scope"]
    assert scopes == ["mhc", f"mhc_streams_{op}_bwd"]


def test_path_falls_back_and_the_choice_reaches_cheetah_init(monkeypatch):
    cfg = TransformerConfig(
        vocab_size=96, d_model=128, n_layers=1, n_heads=4, n_kv_heads=4,
        d_ff=160, max_seq_len=32, remat=False, attn_impl="xla", hc_mult=2,
        hc_sinkhorn_iters=2)
    one = make_mesh(None, devices=jax.devices()[:1])
    four = make_mesh({"fsdp": 4}, devices=jax.devices()[:4])
    events = []
    monkeypatch.setattr(mlops, "_emit", events.append)

    def init_event(trainer):
        del events[:]
        trainer.init_state(jax.random.PRNGKey(0))
        (event,) = [e for e in events if e["kind"] == "cheetah_init"]
        return event["mhc_backward"]

    # this CPU back-end: the plain expressions, whatever the shape
    assert ms.backward_path(4, 3584, None) == "xla"
    trainer = CheetahTrainer(cfg, one)
    assert trainer.mhc_backward == init_event(trainer) == "xla"

    monkeypatch.setattr(
        jax, "devices", lambda *a: [types.SimpleNamespace(platform="tpu")])
    assert ms.backward_path(4, 3584, None) == "fused"
    assert ms.backward_path(4, 3584, one) == "fused"
    assert ms.backward_path(4, 3584 + 64, None) == "xla"  # C % 128 != 0
    assert ms.backward_path(1, 3584, None) == "xla"       # no streams
    assert ms.backward_path(4, 3584, four) == "xla"       # several devices
    trainer = CheetahTrainer(cfg, one)
    assert trainer.mhc_backward == init_event(trainer) == "fused"
    assert CheetahTrainer(cfg, four).mhc_backward == "xla"
    narrow = dataclasses.replace(cfg, d_model=64)
    assert CheetahTrainer(narrow, one).mhc_backward == "xla"
