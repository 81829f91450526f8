"""Sweep Cheetah single-chip configs for MFU — each config in a FRESH process.

Dead trainer state from one config must not sit in HBM under the next one's
measurement, and a chip belongs to one process at a time: the parent stays
off jax, spawns one subprocess per config and reads a JSON line back.

Usage:
  python tools/mfu_sweep.py            # run the sweep matrix
  python tools/mfu_sweep.py --one '{"n_heads": 8, ...}'   # child mode
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the shipped bench flagship (bench.py bench_cheetah): d2048 x 8L, GQA
# 16q/4kv — the Llama-standard head_dim 128. Native-GQA splash
# (make_splash_mqa, no K/V repeat) + explicit (512, 512) kernel blocks
# measured 75.7% MFU on the v5e, vs 42% for the same shape through the
# old expand-to-MHA path and 68% for the r2 wide-head (hd512) flagship.
BASE = dict(
    vocab_size=32000, d_model=2048, n_layers=8, n_heads=16, n_kv_heads=4,
    d_ff=5632, max_seq_len=2048, remat=False, remat_policy="full",
    attn_impl="auto", batch=8, seq=2048, steps=15, loss_chunk=256,
    mu_bf16=True, attn_block_q=512, attn_block_kv=512,
)


def run_one(cfg: dict) -> None:
    sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.core.mlops.telemetry import peak_bf16_flops
    from fedml_tpu.parallel.sharding import make_mesh
    from fedml_tpu.parallel.train_step import CheetahTrainer, make_optimizer
    from fedml_tpu.parallel.transformer import TransformerConfig

    B, L, steps = cfg.pop("batch"), cfg.pop("seq"), cfg.pop("steps")
    loss_chunk = cfg.pop("loss_chunk")
    mu_bf16 = cfg.pop("mu_bf16", False)
    if jax.devices()[0].platform != "tpu":
        # the matrix shapes are TPU-sized; grinding them on CPU just burns
        # the caller's timeout (bench.py's hd512 secondary relies on this)
        print(json.dumps({"skipped": "not a tpu host"}))
        return
    tc = TransformerConfig(**cfg)
    mesh = make_mesh()
    tr = CheetahTrainer(
        tc, mesh,
        optimizer=make_optimizer(
            3e-4, warmup_steps=10, total_steps=100,
            mu_dtype=jnp.bfloat16 if mu_bf16 else None,
        ),
        loss_chunk=loss_chunk,
    )
    state = tr.init_state(jax.random.PRNGKey(0))
    n_params = sum(int(p.size) for p in jax.tree.leaves(state.params))
    # MoE: FLOPs follow ACTIVE params — each token visits top_k of E
    # experts, so expert FFN weights count at top_k/E (standard MoE MFU
    # convention); router/attention/embed count fully
    n_active = n_params
    if tc.moe_experts > 1:
        import jax.tree_util as jtu

        expert_params = sum(
            int(leaf.size)
            for path, leaf in jtu.tree_flatten_with_path(state.params)[0]
            if any("MoEFeedForward" in str(getattr(k, "key", k)) for k in path)
            and any(str(getattr(k, "key", k)) in ("w_gate_up", "w_down")
                    for k in path)
        )
        top_k = int(getattr(tc, "moe_top_k", 1))
        n_active = n_params - expert_params \
            + expert_params * top_k // tc.moe_experts
    rng = np.random.RandomState(0)
    tok = jnp.asarray(rng.randint(0, tc.vocab_size, (B, L)).astype(np.int32))
    mask = jnp.ones((B, L), jnp.int32)
    # go through train_step (not _step_jit): it scopes the mesh_context the
    # Pallas kernels need to shard_map themselves on multi-chip meshes
    # >= 2 warmup steps: the FIRST step compiles, and the SECOND
    # recompiles (the donated state comes back with step-output
    # shardings that differ from init_state's) — timing from warmup=1
    # puts that second ~10 s compile inside the measured window and
    # under-reports MFU by 2-3x
    for _ in range(3):
        state, m = tr.train_step(state, tok, mask)
    jax.block_until_ready(m)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = tr.train_step(state, tok, mask)
    jax.block_until_ready(m)
    dt = (time.perf_counter() - t0) / steps
    fpt = 6.0 * n_active + 12.0 * L * tc.n_layers * tc.d_model
    n_chips = jax.device_count()
    tps = B * L / dt / n_chips  # per chip (mesh spans all local devices)
    peak = peak_bf16_flops(jax.devices()[0])  # unknown TPU kind raises
    line = {
        "step_s": round(dt, 3), "tok_s": round(tps),
        "params_m": round(n_params / 1e6, 1),
        "n_chips": n_chips,
        "mfu": round(tps * fpt / peak, 4),
        "device_kind": jax.devices()[0].device_kind,
    }
    if n_active != n_params:
        line["params_active_m"] = round(n_active / 1e6, 1)
    print(json.dumps(line))


def main() -> None:
    if len(sys.argv) > 2 and sys.argv[1] == "--one":
        run_one(json.loads(sys.argv[2]))
        return
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--matrix", default="")
    ns = ap.parse_args()
    if ns.matrix:
        matrix = json.loads(ns.matrix)
    else:
        matrix = [
            dict(),  # the shipped flagship (75.7% MFU measured on v5e)
            # block-size curve for hd128 (the flagship's main lever):
            # kernel-default blocks → 47%, (512,1024) → 75.5%,
            # (512,512) → 75.7%
            dict(attn_block_q=0, attn_block_kv=0),
            dict(attn_block_q=512, attn_block_kv=1024),
            # GQA ratio at hd128: 16/16 (MHA) → 42% via old path;
            # 16/8 → 74%; 16/4 (flagship) → 75.7%
            dict(n_kv_heads=8),
            # the r2 wide-head flagship (4q/2kv hd512): 68%
            dict(n_heads=4, n_kv_heads=2, attn_block_q=0, attn_block_kv=0),
            # memory ladder fallbacks
            dict(remat=True, remat_policy="dots"),
            dict(remat=True, remat_policy="full"),
        ]
    for delta in matrix:
        cfg = {**BASE, **delta}
        tag = json.dumps(delta) if delta else "base"
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        p = subprocess.run(
            [sys.executable, __file__, "--one", json.dumps(cfg)],
            capture_output=True, text=True, timeout=900, env=env,
        )
        line = (p.stdout.strip().splitlines() or ["<no output>"])[-1]
        err = (p.stderr.strip().splitlines() or [""])[-1] if p.returncode else ""
        print(f"{tag:55s} {line} {err[:120]}", flush=True)


if __name__ == "__main__":
    main()
