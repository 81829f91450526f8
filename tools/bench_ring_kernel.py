"""Kernel-level CP-path measurement (VERDICT r3 #6, r4 #3).

A sequence axis of 1-vs-2 on the virtual CPU mesh says nothing about
performance, so this measures what CAN be measured honestly single-chip:

1. The ring-attention INNER engines — fp32 einsum block attend + einsum
   blockwise backward (the r3 path) vs the Pallas splash kernel forward +
   the r5 splash dq/dkv kernel backward — swept over real context-parallel
   block shapes, with a grad-parity check between the two paths.
2. A full CP *train step* (fwd+bwd+AdamW) of the flagship shape at long
   context through ``CheetahTrainer`` with the sequence axis active, plus
   the same step with CP off — the single-chip CP tax, as ``train_step_ms``.

Runs on the one real TPU chip with a 1-device ``sequence`` mesh (the ring
machinery — shard_map, axis_index, ppermute, online merge — is all live;
only the hop count is 1). Writes RING_KERNEL_BENCH.json.

Usage:  python tools/bench_ring_kernel.py [--blocks 2048,4096,8192]
        python tools/bench_ring_kernel.py --smoke   # CPU plumbing check
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def measure_inner(B, Lb, H, D, steps, interpret=False) -> dict:
    """Einsum vs kernel inner engines at one block shape + grad parity."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from fedml_tpu.parallel.ring_attention import make_ring_attention
    from fedml_tpu.parallel.sharding import compat_shard_map

    mesh = Mesh(np.asarray(jax.devices()[:1]), axis_names=("sequence",))
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.standard_normal((B, Lb, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, Lb, H, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, Lb, H, D)), jnp.bfloat16)

    def one(use_kernel: bool):
        ring = make_ring_attention(1, "sequence", use_kernel=use_kernel,
                                   interpret=interpret)
        spec = P(None, "sequence", None, None)
        sm = compat_shard_map(ring, mesh=mesh, in_specs=(spec,) * 3,
                              out_specs=spec)

        @jax.jit
        def fwd(q, k, v):
            return jnp.sum(sm(q, k, v).astype(jnp.float32) ** 2)

        @jax.jit
        def fwd_bwd(q, k, v):
            return jax.value_and_grad(
                lambda q, k, v: jnp.sum(sm(q, k, v).astype(jnp.float32) ** 2),
                argnums=(0, 1, 2),
            )(q, k, v)

        def timeit(f):
            r = jax.block_until_ready(f(q, k, v))
            t0 = time.perf_counter()
            for _ in range(steps):
                r = f(q, k, v)
            jax.block_until_ready(r)
            return (time.perf_counter() - t0) / steps, r

        dt_f, _ = timeit(fwd)
        dt_fb, (l, grads) = timeit(fwd_bwd)
        return {"ms_per_fwd": round(dt_f * 1e3, 2),
                "ms_per_fwd_bwd": round(dt_fb * 1e3, 2),
                "loss": float(l)}, grads

    einsum, g_e = one(False)
    kernel, g_k = one(True)

    import numpy as np

    def rel_l2(a, b):
        a = np.asarray(a, np.float32).ravel()
        b = np.asarray(b, np.float32).ravel()
        return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-9))

    return {
        "einsum_inner": einsum,
        "kernel_inner": kernel,
        "kernel_fwd_speedup": round(
            einsum["ms_per_fwd"] / kernel["ms_per_fwd"], 2
        ),
        "kernel_fwd_bwd_speedup": round(
            einsum["ms_per_fwd_bwd"] / kernel["ms_per_fwd_bwd"], 2
        ),
        # bf16 inputs: agreement to ~1e-2 rel-L2 is bit-level-reasonable;
        # the exact check is tests/test_ring_attention.py (fp32, interpret)
        "grad_rel_l2": {
            n: rel_l2(a, b) for n, a, b in
            (("dq", g_k[0], g_e[0]), ("dk", g_k[1], g_e[1]),
             ("dv", g_k[2], g_e[2]))
        },
        "loss_rel_diff": abs(einsum["loss"] - kernel["loss"])
        / max(abs(einsum["loss"]), 1e-9),
    }


def measure_train_step(seq, batch, steps, smoke=False) -> dict:
    """Full CP train step (fwd+bwd+update) vs the same step with CP off."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from fedml_tpu.parallel.sharding import make_mesh
    from fedml_tpu.parallel.train_step import CheetahTrainer, make_optimizer
    from fedml_tpu.parallel.transformer import TransformerConfig

    if smoke:
        base = dict(vocab_size=512, d_model=128, n_layers=2, n_heads=4,
                    n_kv_heads=2, d_ff=384, max_seq_len=seq)
    else:
        # the bench.py flagship body at long context (attn blocks clamped
        # to the measured (512, 512))
        base = dict(vocab_size=32000, d_model=2048, n_layers=8, n_heads=16,
                    n_kv_heads=4, d_ff=5632, max_seq_len=seq,
                    attn_block_q=512, attn_block_kv=512)
    rng = np.random.RandomState(0)
    tok = jnp.asarray(rng.randint(0, base["vocab_size"], (batch, seq))
                      .astype(np.int32))
    mask = jnp.ones((batch, seq), jnp.int32)

    def one(seq_sharded: bool):
        mesh = make_mesh({"sequence": 1}, devices=jax.devices()[:1])
        last = None
        for rung in (dict(remat=False), dict(remat=True, remat_policy="full")):
            cfg = TransformerConfig(**{**base, **rung})
            tr = CheetahTrainer(
                cfg, mesh,
                optimizer=make_optimizer(learning_rate=3e-4, warmup_steps=5,
                                         total_steps=100,
                                         mu_dtype=jnp.bfloat16),
                seq_sharded=seq_sharded,
            )
            try:
                state = tr.init_state(jax.random.PRNGKey(0))
                state, m = tr.train_step(state, tok, mask)
                jax.block_until_ready(m)
            except Exception as e:
                last = f"{type(e).__name__}: {e}"[:300]
                state = tr = None
                continue
            break
        if state is None:
            return {"error": last}
        n_params = sum(int(p.size) for p in jax.tree.leaves(state.params))
        for _ in range(2):
            state, m = tr.train_step(state, tok, mask)
        jax.block_until_ready(m)
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = tr.train_step(state, tok, mask)
        jax.block_until_ready(m)
        dt = (time.perf_counter() - t0) / steps
        tok_s = batch * seq / dt
        res = {"train_step_ms": round(dt * 1e3, 1),
               "tokens_per_sec": round(tok_s),
               "remat": cfg.remat_policy if cfg.remat else "none",
               "loss": round(float(m["loss"]), 4)}
        from fedml_tpu.core.mlops.telemetry import peak_bf16_flops

        peak = peak_bf16_flops(jax.devices()[0])
        if peak is not None:
            fpt = 6.0 * n_params + 12.0 * seq * cfg.n_layers * cfg.d_model
            res["mfu"] = round(tok_s * fpt / peak, 4)
        return res

    cp = one(True)
    no_cp = one(False)
    out = {"seq": seq, "batch": batch, "cp_on": cp, "cp_off": no_cp}
    if "train_step_ms" in cp and "train_step_ms" in no_cp:
        out["cp_tax"] = round(
            cp["train_step_ms"] / no_cp["train_step_ms"], 3
        )
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--blocks", default="2048,4096,8192")
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--train-seq", type=int, default=4096)
    ap.add_argument("--train-batch", type=int, default=4)
    ap.add_argument("--smoke", action="store_true",
                    help="CPU plumbing check: tiny shapes, interpret kernels")
    ap.add_argument("--out", default=os.path.join(REPO,
                                                  "RING_KERNEL_BENCH.json"))
    a = ap.parse_args()

    import jax

    on_tpu = jax.devices()[0].platform == "tpu"
    if not on_tpu and not a.smoke:
        print(json.dumps({"skipped": "not a tpu host"}))
        return

    if a.smoke:
        blocks, B, H, D, steps = [256], 1, 2, 128, 2
        tseq, tbatch = 128, 2
    else:
        blocks = [int(x) for x in a.blocks.split(",") if x]
        B, H, D, steps = a.batch, a.heads, a.head_dim, a.steps
        tseq, tbatch = a.train_seq, a.train_batch

    out = {
        "shape": {"batch": B, "heads": H, "head_dim": D},
        "blocks": {},
        "device": jax.devices()[0].device_kind,
        "smoke": bool(a.smoke),
    }
    for Lb in blocks:
        out["blocks"][str(Lb)] = measure_inner(
            B, Lb, H, D, steps, interpret=a.smoke and not on_tpu
        )
        print(f"block {Lb}: {json.dumps(out['blocks'][str(Lb)])}",
              file=sys.stderr, flush=True)
    out["train_step"] = measure_train_step(tseq, tbatch, max(steps // 2, 2),
                                           smoke=a.smoke)
    print(json.dumps(out))
    if not a.smoke:
        with open(a.out, "w") as f:
            json.dump(out, f, indent=2)


if __name__ == "__main__":
    main()
