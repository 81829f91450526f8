"""Ring attention (sequence/context parallelism) tests: exactness vs dense
causal attention, and the full Cheetah train step with the sequence axis
active.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from fedml_tpu.parallel.ring_attention import make_ring_attention
from fedml_tpu.parallel.sharding import compat_shard_map as shard_map
from fedml_tpu.parallel.sharding import make_mesh
from fedml_tpu.parallel.train_step import CheetahTrainer, make_optimizer
from fedml_tpu.parallel.transformer import TransformerConfig, attention_scores


class TestRingAttentionExactness:
    @pytest.mark.parametrize("ring", [2, 4, 8])
    def test_matches_dense_causal(self, ring):
        mesh = make_mesh({"sequence": ring},
                         devices=jax.devices()[:ring])
        B, L, H, D = 2, 32, 4, 16
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
        k = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
        v = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)

        dense = attention_scores(q, k, v, None)

        spec = P(None, "sequence", None, None)
        ring_fn = shard_map(
            make_ring_attention(ring, "sequence"), mesh=mesh,
            in_specs=(spec, spec, spec), out_specs=spec,
        )
        out = jax.jit(ring_fn)(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                                   atol=2e-5, rtol=2e-5)

    def test_non_causal_matches_softmax(self):
        mesh = make_mesh({"sequence": 4}, devices=jax.devices()[:4])
        B, L, H, D = 1, 16, 2, 8
        rng = np.random.RandomState(1)
        q = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
        k = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
        v = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
        logits = jnp.einsum("blhd,bmhd->bhlm", q, k) / np.sqrt(D)
        probs = jax.nn.softmax(logits, -1)
        dense = jnp.einsum("bhlm,bmhd->blhd", probs, v)
        spec = P(None, "sequence", None, None)
        ring_fn = shard_map(
            make_ring_attention(4, "sequence", causal=False), mesh=mesh,
            in_specs=(spec, spec, spec), out_specs=spec,
        )
        out = jax.jit(ring_fn)(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                                   atol=2e-5, rtol=2e-5)


class TestSequenceParallelTraining:
    def test_train_step_with_sequence_axis(self):
        """Full Cheetah step with dp+sp mesh; loss must match the non-sp run."""
        cfg = TransformerConfig(
            vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=128, max_seq_len=64, remat=False,
        )
        rng = np.random.RandomState(0)
        toks = jnp.asarray(rng.randint(0, 127, (4, 64)), jnp.int32)
        mask = jnp.ones((4, 64), jnp.int32)

        mesh_sp = make_mesh({"data": 2, "sequence": 4})
        tr_sp = CheetahTrainer(
            cfg, mesh_sp, optimizer=make_optimizer(learning_rate=1e-2,
                                                   warmup_steps=1),
            seq_sharded=True,
        )
        s_sp = tr_sp.init_state(jax.random.PRNGKey(0))
        s_sp, m_sp = tr_sp.train_step(s_sp, toks, mask)

        mesh_dp = make_mesh({"data": 2, "fsdp": 2, "tensor": 2})
        tr_dp = CheetahTrainer(
            cfg, mesh_dp, optimizer=make_optimizer(learning_rate=1e-2,
                                                   warmup_steps=1),
        )
        s_dp = tr_dp.init_state(jax.random.PRNGKey(0))
        s_dp, m_dp = tr_dp.train_step(s_dp, toks, mask)

        assert float(m_sp["loss"]) == pytest.approx(float(m_dp["loss"]),
                                                    rel=1e-4)
        # two more sp steps: loss decreases (learning through ring attention)
        losses = [float(m_sp["loss"])]
        for _ in range(2):
            s_sp, m_sp = tr_sp.train_step(s_sp, toks, mask)
            losses.append(float(m_sp["loss"]))
        assert losses[-1] < losses[0]


class TestRingBackwardExactness:
    """The hand-written custom-VJP blockwise backward (ring_bwd) must match
    autodiff through dense attention — a dropped scale or mis-rotated dk/dv
    would pass every forward test while corrupting all CP training."""

    @pytest.mark.parametrize("causal", [True, False])
    def test_grads_match_dense(self, causal):
        ring = 4
        mesh = make_mesh({"sequence": ring}, devices=jax.devices()[:ring])
        B, L, H, D = 2, 32, 4, 16
        rng = np.random.RandomState(2)
        q = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
        k = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
        v = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
        # arbitrary non-uniform cotangent via a weighted-sum loss
        w = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)

        def dense_loss(q, k, v):
            if causal:
                out = attention_scores(q, k, v, None)
            else:
                logits = jnp.einsum("blhd,bmhd->bhlm", q, k) / np.sqrt(D)
                probs = jax.nn.softmax(logits, -1)
                out = jnp.einsum("bhlm,bmhd->blhd", probs, v)
            return jnp.sum(out * w)

        spec = P(None, "sequence", None, None)
        ring_fn = shard_map(
            make_ring_attention(ring, "sequence", causal=causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        )

        def ring_loss(q, k, v):
            return jnp.sum(ring_fn(q, k, v) * w)

        want = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
        got = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
        for g, r in zip(want, got):
            np.testing.assert_allclose(np.asarray(r), np.asarray(g),
                                       atol=3e-5, rtol=3e-5)


class TestRingKernelPathInterpret:
    """The splash-kernel ring path (fwd multi-hop LSE merge AND the r5
    kernel backward) executed via Pallas interpret mode on the CPU mesh —
    before this, the S>=2 kernel branch had never run anywhere (r4 ADVICE:
    an index error here would corrupt all causal CP training silently).
    """

    @pytest.mark.parametrize("causal, ring, dtype, block", [
        pytest.param(True, 2, jnp.float32, 128, id="True"),
        pytest.param(False, 2, jnp.float32, 128, id="False"),
        # one device: the ring without a hop, bf16 inputs, and the default
        # (512, 512) blocks fitted to Lb 256
        pytest.param(True, 1, jnp.bfloat16, 0, id="ring1-bf16"),
    ])
    def test_kernel_path_matches_einsum_path_fwd_bwd(self, causal, ring,
                                                      dtype, block):
        mesh = make_mesh({"sequence": ring}, devices=jax.devices()[:ring])
        B, L, H, D = 1, 256, 2, 128  # Lb=128 at ring 2: the minimum tile
        rng = np.random.RandomState(3)
        q = jnp.asarray(rng.randn(B, L, H, D), dtype)
        k = jnp.asarray(rng.randn(B, L, H, D), dtype)
        v = jnp.asarray(rng.randn(B, L, H, D), dtype)
        w = jnp.asarray(rng.randn(B, L, H, D), dtype)
        spec = P(None, "sequence", None, None)

        def make(use_kernel):
            fn = shard_map(
                make_ring_attention(
                    ring, "sequence", causal=causal, use_kernel=use_kernel,
                    block_q=block, block_kv=block, interpret=use_kernel,
                ),
                mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            )

            def loss(q, k, v):
                return jnp.sum(fn(q, k, v) * w)

            return fn, loss

        ein_fn, ein_loss = make(False)
        ker_fn, ker_loss = make(True)

        def assert_close(got, want, tol):
            if dtype == jnp.float32:
                np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                           atol=tol, rtol=tol)
                return
            # bf16: the einsum path rounds its [Lq, Lk] logits to bf16
            # before the fp32 softmax, the kernels keep them fp32, so the two
            # differ by rounding (0.5% relative L2 on the v5e at Lb 4096); a
            # wrong mask, scale or lse merge is O(1)
            got, want = (np.asarray(a, np.float32).ravel()
                         for a in (got, want))
            assert np.isfinite(got).all()
            assert np.linalg.norm(got - want) <= 2e-2 * np.linalg.norm(want)

        out_e = jax.jit(ein_fn)(q, k, v)
        out_k = jax.jit(ker_fn)(q, k, v)
        assert_close(out_k, out_e, 2e-4)

        ge = jax.jit(jax.grad(ein_loss, argnums=(0, 1, 2)))(q, k, v)
        gk = jax.jit(jax.grad(ker_loss, argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(gk, ge):
            assert_close(a, b, 3e-4)
