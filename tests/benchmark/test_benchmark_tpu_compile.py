"""The two LM step programs of the benchmark's cells, compiled at their real
widths for a described ``v5e:2x2`` by the TPU compiler that is installed here:
what the chip's compiler would refuse (a kernel it cannot lay out, a program
that does not fit the chip's memory) fails here at no chip time. Nothing runs,
so this says nothing about results or speed.

The topology is described inside a module-scoped fixture, after a test of this
file has started, never at import: only one process at a time may load the
TPU's library, every xdist worker imports every test file, and only the worker
that is given this file may load it (on-chip-measurement guide, section 2).
"""

import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import harness

HBM_BYTES = harness.peaks_for("TPU v5 lite")["hbm_bytes"]
CELLS = ["pretrain_mistral7b_1chip", "pretrain_mistral7b_fsdp4"]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # whatever the plugin raises where it cannot
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def uncached():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _compile_step(cell, devices, moments_sharded):
    """The cell's train step for ``devices``, from shapes alone. The program
    asks ``jax.devices()`` which attention to use and would take XLA's on this
    CPU host, so the test names the kernel path the chip takes."""
    import dataclasses

    from fedml_tpu.arguments import Arguments
    from fedml_tpu.cheetah.runner import config_from_args
    from fedml_tpu.parallel.sharding import make_mesh
    from fedml_tpu.parallel.train_step import (CheetahTrainer, TrainState,
                                               make_optimizer)

    job = harness.load_module(harness.ROOT, "jobs", "pretrain").Job(
        cell, seed=0, tracked=False, work_dir="", log=lambda s: None)
    args = Arguments(overrides=job.program)
    cfg = dataclasses.replace(config_from_args(args), attn_impl="splash")
    mesh = make_mesh(args.parse_mesh_shape(), devices=devices)
    trainer = CheetahTrainer(cfg, mesh, optimizer=make_optimizer(
        learning_rate=float(args.learning_rate),
        warmup_steps=int(args.warmup_steps), total_steps=int(args.total_steps)))
    replicated = NamedSharding(mesh, P())
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(trainer._init_raw, jax.random.PRNGKey(0))["params"],
        trainer.param_shardings)
    by_shape = {s.shape: s.sharding for s in jax.tree.leaves(params)}
    opt_state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype,
            sharding=by_shape.get(s.shape, replicated) if moments_sharded
            else replicated),
        jax.eval_shape(trainer.opt.init, params))
    state = TrainState(step=jax.ShapeDtypeStruct((), jnp.int32, sharding=replicated),
                       params=params, opt_state=opt_state)
    tokens = jax.ShapeDtypeStruct((job.batch, job.seq_len), jnp.int32,
                                  sharding=trainer._batch_shard)
    with trainer._trace_context():
        lowered = trainer._step_jit.lower(state, tokens, tokens)
    n_params = sum(s.size for s in jax.tree.leaves(params))
    return lowered.as_text(), lowered.compile(), n_params


# moments replicated: the first step after init_state (the program's known
# fault, PERF.md); sharded like the parameters: every later step
@pytest.mark.parametrize("moments_sharded", [False, True],
                         ids=["first_step", "steady_step"])
@pytest.mark.parametrize("name", CELLS)
def test_lm_step_compiles_and_fits_the_chip(name, moments_sharded, topo, uncached):
    cell = harness.load_cell(name)
    hlo, compiled, n_params = _compile_step(
        cell, topo.devices[:cell.chips], moments_sharded)
    assert "tpu_custom_call" in hlo  # the splash kernels are in the step
    with open(harness.find_file(harness.ROOT, "BENCHMARK.json")) as f:
        whys = {c["name"]: c["why"] for c in json.load(f)["configs"]}
    millions = round(n_params / 1e6)
    assert f"{millions}M params" in whys[cell.config["name"]]
    m = compiled.memory_analysis()
    on_chip = (m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert on_chip < HBM_BYTES, f"{on_chip / 1e9:.2f} GB a chip"
    assert on_chip > 0.25 * HBM_BYTES  # and the cell is not a toy
    collectives = any(k in compiled.as_text() for k in ("all-gather", "reduce-scatter"))
    assert collectives == (cell.chips > 1)
