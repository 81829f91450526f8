"""7B readiness proof, settled by the REAL TPU compiler (VERDICT r3 next #2).

``TransformerConfig.llama2_7b()``'s full fsdp-sharded train step (forward,
backward, AdamW update, splash attention shard_mapped over the mesh) is
AOT-compiled against genuine v5e TPU topologies via
``jax.experimental.topologies`` — no chips needed, the machine's TPU
compiler targets the topology directly. The compiler's own
``memory_analysis()`` is the verdict: per-chip HBM = resident arguments
(params + optimizer + batch) + temp buffers (activations + workspace),
compared against the v5e chip budget. An analytic budget table is printed
alongside and must AGREE with the compiler (the r3 artifact's 383 GiB
XLA:CPU temp figure is gone — the CPU backend's layout/fusion decisions are
meaningless for TPU HBM, which is exactly why the TPU compiler is asked).

Usage:  python tools/check_7b_readiness.py [--rows v5e:8,v5p:32]
                                           [--seq-len 2048]
Needs libtpu (the TPU compiler; no chip). Prints one JSON line
at the end; exit 0 = every compiled config's compiler-reported HBM fits its
chip.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

GiB = 1024**3
CHIP_HBM = {"v5e": 16 * GiB, "v5p": 95 * GiB}
# slice topologies by (chip, count): v5e is 2-D, v5p is 3-D
TOPO = {
    ("v5e", 4): "v5e:2x2", ("v5e", 8): "v5e:2x4",
    ("v5e", 16): "v5e:4x4", ("v5e", 32): "v5e:4x8",
    ("v5p", 4): "v5p:2x2x1", ("v5p", 8): "v5p:2x2x2",
    ("v5p", 16): "v5p:2x4x2", ("v5p", 32): "v5p:2x4x4",
}


def parse_rows(spec: str):
    """"v5e:8,v5p:32" → [("v5e", 8), ...] with a helpful error."""
    rows = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        chip, _, n = part.partition(":")
        try:
            key = (chip, int(n))
        except ValueError:
            key = None
        if key not in TOPO:
            supported = ", ".join(f"{c}:{k}" for c, k in sorted(TOPO))
            raise SystemExit(
                f"unsupported row {part!r}; supported: {supported}"
            )
        rows.append(key)
    if not rows:
        raise SystemExit("no rows requested")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="v5e:8,v5e:16,v5p:32",
                    help="comma list of <chip>:<fsdp> rows to AOT-compile "
                         "(v5p:32 = the BASELINE north-star slice)")
    ap.add_argument("--batch-per-shard", type=int, default=1)
    ap.add_argument("--seq-len", type=int, default=2048)
    # bf16 first moment (make_optimizer docstring: "on a single 16 GiB chip
    # the difference between spilling and staying resident") — the compiler
    # run below proves it IS the difference at fsdp=8 on v5e
    ap.add_argument("--mu-dtype", default="bfloat16",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--out", default=os.path.join(REPO,
                                                  "SEVENB_READINESS.json"))
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from fedml_tpu.parallel.context import mesh_context
    from fedml_tpu.parallel.pipeline import _opt_state_specs
    from fedml_tpu.parallel.sharding import make_mesh
    from fedml_tpu.parallel.train_step import (
        CheetahTrainer,
        TrainState,
        make_optimizer,
    )
    from fedml_tpu.parallel.transformer import TransformerConfig

    cfg = dataclasses.replace(
        TransformerConfig.llama2_7b(), max_seq_len=a.seq_len
    )

    def tree_bytes(tree):
        return sum(
            int(x.size) * jnp.dtype(x.dtype).itemsize
            for x in jax.tree.leaves(tree)
        )

    def compile_for(chip: str, n_chips: int) -> dict:
        """AOT-compile the fsdp=n_chips step against a chip topology and
        return the compiler's per-chip memory verdict."""
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name=TOPO[(chip, n_chips)]
        )
        mesh = make_mesh({"fsdp": n_chips}, devices=list(topo.devices))
        trainer = CheetahTrainer(
            cfg, mesh,
            optimizer=make_optimizer(3e-4, mu_dtype=jnp.dtype(a.mu_dtype)),
        )
        params_abs = jax.eval_shape(
            trainer._init_raw, jax.random.PRNGKey(0)
        )["params"]
        opt_abs = jax.eval_shape(trainer.opt.init, params_abs)
        p_spec = jax.tree.map(
            lambda s: s.spec, trainer.param_shardings,
            is_leaf=lambda x: isinstance(x, NamedSharding),
        )
        o_spec = _opt_state_specs(p_spec, opt_abs)

        def sds(al, spec):
            return jax.ShapeDtypeStruct(
                al.shape, al.dtype, sharding=NamedSharding(mesh, spec)
            )

        state_abs = TrainState(
            step=sds(jax.ShapeDtypeStruct((), jnp.int32), P()),
            params=jax.tree.map(sds, params_abs, p_spec),
            opt_state=jax.tree.map(
                sds, opt_abs, o_spec,
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
            ),
        )
        B = a.batch_per_shard * n_chips
        tok = jax.ShapeDtypeStruct(
            (B, a.seq_len), jnp.int32, sharding=trainer._batch_shard
        )
        t0 = time.time()
        with mesh, mesh_context(mesh):
            compiled = trainer._step_jit.lower(state_abs, tok, tok).compile()
        secs = round(time.time() - t0, 1)
        ma = compiled.memory_analysis()
        args_b = int(ma.argument_size_in_bytes)
        temp_b = int(ma.temp_size_in_bytes)
        out_b = int(ma.output_size_in_bytes)
        alias_b = int(ma.alias_size_in_bytes)
        # peak per-chip HBM: resident inputs + temps + any non-aliased
        # outputs (donated state aliases its argument buffers)
        hbm = args_b + temp_b + max(out_b - alias_b, 0)
        n_params = sum(int(x.size) for x in jax.tree.leaves(params_abs))
        state_bytes = tree_bytes(params_abs) + tree_bytes(opt_abs)
        analytic_args = state_bytes / n_chips \
            + B * a.seq_len * 8 / n_chips  # tokens+mask int32, batch-sharded
        row = {
            "chip": chip,
            "fsdp": n_chips,
            "topology": TOPO[(chip, n_chips)],
            "compile_s": secs,
            "params_b": round(n_params / 1e9, 3),
            "compiler_args_gib": round(args_b / GiB, 2),
            "compiler_temp_gib": round(temp_b / GiB, 2),
            "compiler_hbm_gib_per_chip": round(hbm / GiB, 2),
            "analytic_state_gib_per_chip": round(analytic_args / GiB, 2),
            "agree": abs(args_b - analytic_args) / analytic_args < 0.05,
            "fits": hbm < CHIP_HBM[chip] * 0.95,
        }
        print(json.dumps(row))
        return row

    requested = parse_rows(a.rows)
    rows = []
    for chip, n in requested:
        rows.append(compile_for(chip, n))
        out = {
            "model": "llama2_7b",
            "seq_len": a.seq_len,
            "batch_per_shard": a.batch_per_shard,
            "mu_dtype": a.mu_dtype,
            "remat": cfg.remat,
            "source": "TPU compiler memory_analysis via AOT topologies",
            "rows_requested": [f"{c}:{k}" for c, k in requested],
            # a partial artifact (crash mid-list) must be distinguishable
            # from a complete run: fits/agree only cover finished rows
            "complete": len(rows) == len(requested),
            "rows": rows,
            "fits": all(r["fits"] for r in rows),
            "analytic_agrees_with_compiler": all(r["agree"] for r in rows),
        }
        # write after EVERY row: each costs minutes of TPU AOT compile, and
        # a crash mid-list must not discard finished rows
        with open(a.out, "w") as f:
            json.dump(out, f, indent=2)
    print(json.dumps(out))
    sys.exit(0 if out["fits"] and out["analytic_agrees_with_compiler"]
             else 1)


if __name__ == "__main__":
    main()
