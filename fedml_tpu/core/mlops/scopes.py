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

The same name stack stands in the compiled program's optimized HLO, as the
``op_name`` of every instruction's metadata, under the instruction names a
trace prints. :func:`scope_key` cuts one stack down to its scopes and its
pass, and :func:`program_map` does so for every instruction of a compiled
program: what ``telemetry.record_program_scopes`` publishes and
``benchmark/scope_time.py`` joins to a trace's ``XLA Ops`` line.

Adding a scope is adding its name here first: :func:`scope` refuses a name
its vocabulary does not list.
"""

from __future__ import annotations

import functools
import re
from typing import Any, Collection, Dict, List, Tuple

import jax

# parallel/transformer.py, parallel/moe.py: what a configuration adds to the
# blocks of ``_train_step_raw``; absent from a step whose configuration has
# no latent attention, hyper-connections, experts, MTP module,
# linear-attention, state-space or short-convolution layers, q/k norms,
# window attention or block-diffusion objective
TRAIN_STEP_BLOCKS: Tuple[str, ...] = (
    "mla",            # LatentAttention_N: low-rank projections, norms, scores
    "mhc",            # hyper-connection maps, stream reads and writes
    "sinkhorn",       # inside mhc: the Sinkhorn-Knopp sweeps of H_res
    "moe_route",      # router scores, top-k, the sort by expert
    "moe_experts",    # dispatch gather, the activation, combine
    "moe_products",   # the experts' grouped products (expert_gmm_* kernels)
    "shared_expert",  # the expert every token passes (FeedForward "shared")
    "mtp",            # the multi-token-prediction module and its loss
    "kda",            # KimiDeltaAttention_N: the linear-attention mixer
    "kda_conv",       # inside kda: the short causal convolutions of q, k, v
    "kda_gate",       # inside kda: the decay gate, beta and the output gate
    "kda_chunk",      # inside kda: the chunked form (kda_chunk_fwd / _bwd)
    "qk_norm",        # inside Attention_N: the per-head RMS norms of q and k
    "bd_noise",       # block diffusion: the noise draw and the doubled input
    "mamba",          # Mamba2Mixer_N: the state-space mixer
    "ssd_proj",       # inside mamba: the input and the output projection
    "ssd_conv",       # inside mamba: the short causal convolution of x, B, C
    "ssd_chunk",      # inside mamba: the chunked recurrence, all of it
    "ssd_norm",       # inside mamba: the gate and the grouped RMS norm
    "shortconv",      # ShortConvMixer_N: LFM2's gated short convolution
    "shortconv_proj", # inside shortconv: the input and the output projection
    "shortconv_conv", # inside shortconv: the two gates and the convolution
    "window_attention",  # inside Attention_N of a sliding_attention layer:
                         # the attention over its band (splash or XLA)
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


# ---------------------------------------------------------------------------
# from a name stack to (scope path, pass), and a compiled program's map
# ---------------------------------------------------------------------------

_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
_MODULE = re.compile(r"^[A-Z][A-Za-z0-9]*(_\d+)?$")  # flax's default names
_RECOMPUTE = "rematted_computation"
# elements of a name stack that JAX's own transforms and control flow put
# there; any other plain lower-case element is somebody's ``named_scope``
_JAX_ELEMENTS = frozenset((
    "while", "body", "cond", "checkpoint", _RECOMPUTE, "closed_call",
    "custom_jvp_call", "custom_vjp_call", "pallas_call", "shard_map"))
_SCOPE_LIKE = re.compile(r"^[a-z][a-z0-9_]*$")
_BRANCH = re.compile(r"^branch_\d+_fun$")


def split_stack(op_name: str) -> List[str]:
    """``a/transpose(jvp(B))/c:`` -> ["a", "transpose(jvp(B))", "c"]; a slash
    inside parentheses (an einsum spec has none, a nested jit may) stays."""
    parts, depth, cur = [], 0, ""
    for ch in op_name.rstrip(":"):
        if ch == "/" and depth == 0:
            parts.append(cur)
            cur = ""
            continue
        depth += ch == "("
        depth -= ch == ")"
        cur += ch
    return parts + [cur] if cur else parts


def peel(element: str) -> Tuple[List[str], str]:
    """``transpose(jvp(Transformer))`` -> (["transpose", "jvp"], "Transformer")."""
    wrappers = []
    while True:
        m = _WRAPPED.match(element)
        if not m:
            return wrappers, element
        wrappers.append(m.group(1))
        element = m.group(2)


def scope_key(op_name: str, vocabulary: Collection[str],
              layers: bool = False) -> Tuple[str, str]:
    """(scope path, pass) of one name stack: the elements that are a name of
    ``vocabulary`` or a flax module (its index merged unless ``layers``),
    joined by ``/``, and ``fwd``, ``bwd`` (an element wrapped in
    ``transpose(..)``) or ``remat`` (the forward run again inside the
    backward: a ``rematted_computation`` element). The first element is the
    program (``jit(core)``) and the last the primitive; neither is a scope,
    nor is a nested jit's function name (``jit(_take)``). The path is ``""``
    where the stack holds no scope."""
    kept, backward, recompute = [], False, False
    for element in split_stack(op_name)[1:-1]:
        wrappers, name = peel(element)
        backward |= "transpose" in wrappers
        recompute |= name == _RECOMPUTE
        if "jit" in wrappers or "pjit" in wrappers:
            continue
        if name in vocabulary or _MODULE.match(name):
            name = name if layers else re.sub(r"_\d+$", "", name)
            if not kept or kept[-1] != name:  # transpose(jvp(M))/jvp(M)/..
                kept.append(name)
    # under transpose(..), ``checkpoint/rematted_computation/..`` is the
    # forward run again and ``checkpoint/..`` alone the backward proper
    which = "remat" if recompute else ("bwd" if backward else "fwd")
    return "/".join(kept), which


def stale_elements(op_name: str, vocabulary: Collection[str]) -> List[str]:
    """The plain lower-case elements of one name stack that are neither in
    ``vocabulary`` nor JAX's own: scopes of another tree's vocabulary, which
    :func:`scope_key` drops without a word."""
    parts = split_stack(op_name)
    # a Pallas kernel's own name stands before its ``pallas_call``
    kernel = parts[-2] if parts[-1:] == ["pallas_call"] and len(parts) > 2 else None
    out = []
    for element in parts[1:-1]:
        wrappers, name = peel(element)
        if (not wrappers and _SCOPE_LIKE.match(name) and name != kernel
                and name not in vocabulary and name not in _JAX_ELEMENTS
                and not _BRANCH.match(name)):
            out.append(name)
    return out


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?(\S+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?(\S+) = .*? ([\w\-]+)\(")
# opcodes that name or regroup a value and compute nothing: XLA gives them no
# metadata and a trace no time
_NO_WORK = frozenset(("parameter", "constant", "tuple", "get-tuple-element",
                      "bitcast", "partition-id", "replica-id", "after-all"))
_OPCODE_FUSION = re.compile(r" fusion\(.*calls=%?([^\s,}]+)")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
# the computations a control-flow instruction runs
_CONTROL_FLOW = frozenset(("while", "call", "conditional"))
_CALLED = re.compile(r"(?:condition|body|to_apply|true_computation|"
                     r"false_computation)=%?([^\s,}]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def _callees(text: str) -> List[str]:
    names = _CALLED.findall(text)
    branches = _BRANCHES.search(text)
    if branches:
        names += [b.strip().lstrip("%") for b in branches.group(1).split(",")]
    return names


_MODULE_NAME = re.compile(r"HloModule ([^\s,]+)")


def program_map(hlo_text: str, vocabulary: Collection[str],
                layers: bool = False) -> Dict[str, Any]:
    """A compiled program's optimized HLO (``compiled.as_text()``) as the map
    ``instruction name -> (scope path, pass)``: ``scopes`` lists the
    distinct ``[path, pass]`` pairs and ``ops`` gives every instruction's
    index into it, over every computation of the module (while bodies,
    reducers and the entry alike) but the fused ones, whose instructions run
    inside their ``fusion`` and never show in a trace. ``instructions``
    counts the mapped ones that compute something (not a parameter, constant,
    tuple, element of one or bitcast), ``unnamed`` those among them whose
    stack holds no scope; ``stale`` names what :func:`stale_elements`
    found."""
    lines = hlo_text.splitlines()
    fused = {m.group(1) for m in map(_OPCODE_FUSION.search, lines) if m}
    module = _MODULE_NAME.match(hlo_text)
    # per computation the [name, opcode, text] of its instructions; a Mosaic
    # call's text runs over several lines (its kernel_metadata), the metadata
    # on the last
    computations: List[Tuple[str, List[List[str]]]] = []
    skip, inside = False, False
    for line in lines:
        if not inside:
            m = _COMPUTATION.match(line)
            if m:
                inside, skip = True, m.group(1) in fused
                if not skip:
                    computations.append((m.group(1), []))
        elif line == "}":
            inside = False
        elif not skip:
            m = _INSTRUCTION.match(line)
            if m:
                computations[-1][1].append([m.group(1), m.group(2), line])
            elif computations[-1][1]:
                computations[-1][1][-1][2] += line
    scopes: Dict[Tuple[str, str], int] = {}
    ops: Dict[str, int] = {}
    stale, working, unnamed = set(), 0, 0
    # XLA prints a computation before its callers. Callers first, so that an
    # instruction XLA itself put into a loop's body (the copies of a scan's
    # carry, async slices: no metadata) takes the name stack of the ``while``
    # that runs it, as the profiler's trace names it
    inherited: Dict[str, str] = {}
    for computation, instructions in reversed(computations):
        for name, opcode, text in instructions:
            m = _OP_NAME.search(text)
            stack = m.group(1) if m else inherited.get(computation, "")
            if opcode in _CONTROL_FLOW:
                for callee in _callees(text):
                    inherited.setdefault(callee, stack)
            key = scope_key(stack, vocabulary, layers) if stack else ("", "fwd")
            ops[name] = scopes.setdefault(key, len(scopes))
            if opcode not in _NO_WORK:
                working += 1
                unnamed += not key[0]
            stale.update(stale_elements(stack, vocabulary))
    return {"module": module.group(1) if module else "",
            "scopes": [list(k) for k in scopes], "ops": ops,
            "instructions": working, "unnamed": unnamed,
            "stale": sorted(stale)}
