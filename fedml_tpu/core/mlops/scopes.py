"""Scope names for the device work that has no flax module around it.

A profiler trace names every device op by the JAX name stack it was traced
under (the ``tf_op`` stat: ``jit(_train_step_raw)/transpose(jvp(Transformer))/
CheckpointBlock_0/FeedForward_0/...``). Flax's module names and JAX's ``jvp``
/ ``transpose(jvp)`` / ``rematted_computation`` already tell the model's
layers and its forward from its backward; the names below cover the rest, one
vocabulary per jitted program. ``benchmark/tools/scope_table.py`` sums device
time by them, and ``tests/test_named_scopes.py`` holds each program's lowered
text to its vocabulary. Names nest: ``local_train/optimizer`` is the clients'
optax update, ``server_update`` the server's.

Adding a scope is adding its name here first: :func:`scope` refuses a name
its vocabulary does not list.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax

# parallel/transformer.py, parallel/moe.py: what a configuration adds to the
# blocks of ``_train_step_raw``; absent from a step whose configuration has
# no latent attention, hyper-connections, experts, MTP module,
# linear-attention layers, q/k norms or block-diffusion objective
TRAIN_STEP_BLOCKS: Tuple[str, ...] = (
    "mla",            # LatentAttention_N: low-rank projections, norms, scores
    "mhc",            # hyper-connection maps, stream reads and writes
    "sinkhorn",       # inside mhc: the Sinkhorn-Knopp sweeps of H_res
    "moe_route",      # router scores, top-k, the sort by expert
    "moe_experts",    # dispatch gather, grouped products, combine
    "shared_expert",  # the expert every token passes (FeedForward "shared")
    "mtp",            # the multi-token-prediction module and its loss
    "kda",            # KimiDeltaAttention_N: the linear-attention mixer
    "kda_conv",       # inside kda: the short causal convolutions of q, k, v
    "kda_gate",       # inside kda: the decay gate, beta and the output gate
    "kda_chunk",      # inside kda: the chunked form (kda_chunk_fwd / _bwd)
    "qk_norm",        # inside Attention_N: the per-head RMS norms of q and k
    "bd_noise",       # block diffusion: the noise draw and the doubled input
)

# parallel/train_step.py: the jitted ``_train_step_raw``
TRAIN_STEP: Tuple[str, ...] = (
    "embed",        # token (and learned position) table gathers
    "rope",         # rotary tables, and their application inside Attention_N
    "loss",         # head matmul + cross entropy (the chunk scan)
    "grad_accum",   # the scan over microbatches and the gradient mean
    "optimizer",    # opt.update + apply_updates
    "clip",         # inside optimizer: make_optimizer's global-norm clip
    "metrics",      # grad_norm and what else the step reports
) + TRAIN_STEP_BLOCKS

# simulation/round_engine.py: the jitted ``core`` and the superround scan
ROUND: Tuple[str, ...] = (
    "select_cohort",   # cohort sampling and the gather of its rows
    "local_train",     # the cohort's vmapped epochs x batches scan
    "loss",            # inside local_train: forward loss (and its backward)
    "optimizer",       # inside local_train: the client's optax update
    "attack",          # where built: data and model attacks
    "defense",         # where built: the robust aggregation rule
    "dp",              # where built: local / central DP clip and noise
    "aggregate",       # weighted average of the stacked client results
    "server_update",   # server optimizer, FedNova step, control variates
    "metrics",         # the round's train_loss and examples counters
)

# ml/evaluate.py: the jitted ``eval_batch``
EVALUATE: Tuple[str, ...] = ("evaluate",)


def scope(vocabulary: Tuple[str, ...], name: str):
    """``jax.named_scope(name)``, for a name of ``vocabulary`` only."""
    if name not in vocabulary:
        raise ValueError(
            f"scope {name!r} is not in the program's vocabulary "
            f"{vocabulary}; add it to fedml_tpu/core/mlops/scopes.py and "
            f"docs/telemetry.md first")
    return jax.named_scope(name)


# what the programs' modules import: ``with train_step_scope("embed"): ...``
train_step_scope = functools.partial(scope, TRAIN_STEP)
round_scope = functools.partial(scope, ROUND)
evaluate_scope = functools.partial(scope, EVALUATE)
