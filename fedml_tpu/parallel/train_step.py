"""Cheetah training step: sharded init, AdamW, grad accumulation, one jit.

Replaces what the reference delegates to torch DDP + NCCL (SURVEY.md §2.5
"Intra-silo data parallelism") and extends it with TP/SP/FSDP the reference
never had. Everything is one compiled program: forward, backward, gradient
accumulation (``lax.scan`` over microbatches), optimizer update. XLA inserts
the reduce-scatter/all-gather collectives implied by the shardings — no
hand-written NCCL calls to port; the one exception is the LM head in the
chunked loss, which ``lm_loss_chunked`` gathers itself, once a step.
"""

from __future__ import annotations

import collections
import contextlib
import logging
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import mlops
from ..core.mlops import telemetry

# names for the step's device work outside the flax modules
from ..core.mlops.scopes import TRAIN_STEP
from ..core.mlops.scopes import train_step_scope as _scope
from . import block_diffusion, expert_gmm, expert_rows
from .context import get_mesh_context, mesh_context, sequence_parallelism
from .kda import KDA_CHUNK, scan_path
from .mhc_streams import backward_path
from .sharding import (
    FSDP,
    TENSOR,
    batch_mesh_axes,
    batch_sharding,
    compat_shard_map,
    logical_to_mesh_spec,
    param_shardings,
    replicated,
    unbox,
)
from .ssd import scan_path as ssd_scan_path
from .transformer import (EMBED, VOCAB, Transformer, TransformerConfig,
                          output_head, splash_forward_tiles)

logger = logging.getLogger(__name__)

PyTree = Any


def _scoped(name: str, tx: optax.GradientTransformation):
    """``tx`` with its update traced under the scope ``name``: the same
    computation and the same state tree (``optax.named_chain`` would turn the
    chain's state into a dict and orphan every checkpoint)."""

    def update(updates, state, params=None):
        with _scope(name):
            return tx.update(updates, state, params)

    return optax.GradientTransformation(tx.init, update)


@struct.dataclass
class TrainState:
    step: jax.Array
    params: PyTree
    opt_state: PyTree
    # what the model carries from step to step that the optimizer does not
    # own: the expert layers' selection bias (flax collection
    # ``router_state``). Empty for a model that has none
    model_state: PyTree = struct.field(default_factory=dict)


def make_optimizer(
    learning_rate: float = 3e-4,
    weight_decay: float = 0.1,
    b1: float = 0.9,
    b2: float = 0.95,
    grad_clip: float = 1.0,
    warmup_steps: int = 100,
    total_steps: int = 10000,
    mu_dtype=None,
) -> optax.GradientTransformation:
    """``mu_dtype=jnp.bfloat16`` halves the first-moment buffer — on a
    single 16 GB chip the difference between spilling and staying resident."""
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, warmup_steps, max(total_steps, warmup_steps + 1)
    )
    return optax.chain(
        _scoped("clip", optax.clip_by_global_norm(grad_clip)),
        optax.adamw(
            schedule, b1=b1, b2=b2, weight_decay=weight_decay,
            mu_dtype=mu_dtype,
        ),
    )


def lm_loss(logits: jax.Array, tokens: jax.Array, mask: jax.Array) -> jax.Array:
    """Next-token CE. logits [B, L, V] fp32, tokens [B, L], mask [B, L]."""
    targets = tokens[:, 1:]
    m = mask[:, 1:].astype(jnp.float32)
    per = optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], targets
    )
    return (per * m).sum() / jnp.maximum(m.sum(), 1.0)


def lm_loss_chunked(
    hidden: jax.Array,
    w_head: jax.Array,
    tokens: jax.Array,
    mask: jax.Array,
    chunk: int = 256,
) -> jax.Array:
    """Fused head-matmul + next-token CE, chunked over the sequence.

    ``hidden``: [B, L, D] (bf16), ``w_head``: [D, V]. Each lax.scan step
    computes one [B, chunk, V] fp32 logits slice and reduces it to CE sums:
    HBM-bandwidth-bound CE becomes MXU-bound. (The scan still keeps each
    chunk's fp32 exponentials for its backward.)

    Under an ambient mesh (``context.mesh_context``) whose batch axes
    (``data``, ``fsdp``) have a combined extent above 1 the scan runs per
    batch shard, inside a ``shard_map`` that is manual over the whole mesh:
    the head, cast to the compute dtype on its shards, is all-gathered over
    ``fsdp`` ONCE before the scan; every shard scans its own sequences with
    no collective on the head in the loop; numerator and denominator of the
    masked mean are summed over the batch axes once after it. The head's
    gradient therefore leaves the backward scan as one reduce-scatter over
    ``fsdp`` (the transpose of the gather). Left to the SPMD partitioner the
    same scan gathers the 262 MB head and reduce-scatters its gradient in
    every chunk (PERF.md, PR 27).

    Where ``tensor`` shards the vocabulary each shard holds ``V / tensor``
    columns of the gathered head and the body is vocabulary-parallel
    (Megatron's CE): the row maximum, the sum of exponentials and the
    target's logit, picked by the shard's column offset, are reduced over
    ``tensor`` in each chunk ([B, chunk] floats, no head-sized collective),
    and the hidden states' cotangent is summed over ``tensor`` once after
    the scan. That is why the wrap is manual over ``tensor`` too: left to
    the partitioner (``axis_names={data, fsdp}``) it gathers the head over
    ``tensor`` as well and every tensor shard computes the whole vocabulary.
    With batch extent 1 nothing is wrapped and the program is the plain
    scan.
    """
    num, den = chunked_ce_sums(hidden, w_head, tokens, mask, chunk)
    return num / jnp.maximum(den, 1.0)


def chunked_ce_sums(hidden, w_head, tokens, mask, chunk: int = 256,
                    shift: bool = True):
    """What :func:`lm_loss_chunked` divides: (sum of ``mask`` times the cross
    entropy, sum of ``mask``) over the batch, by the same scan under the same
    wrap. ``shift`` True: position ``i`` predicts token ``i + 1``; False:
    its own token, and ``mask`` may be any float weight a position (the
    block-diffusion loss)."""
    mesh = get_mesh_context()  # None on one device
    batch_axes = batch_mesh_axes(mesh) if mesh is not None else ()
    if not batch_axes:
        return _chunked_ce_sums(hidden, w_head, tokens, mask, chunk,
                                shift=shift)

    tensor = TENSOR if int(mesh.shape[TENSOR]) > 1 else None

    def per_shard(h, w, tok, msk):
        if int(mesh.shape[FSDP]) > 1:
            # the gather may not start before the hidden states exist: left
            # free, the scheduler starts it inside the last block's forward
            # and the 262 MB head lies across the step's memory peak (step
            # temporaries 2.83 GB against 2.16 GB with the barrier, and
            # 2.60 GB before this wrap; compiled for v5e:2x2, PR 27)
            w, h = jax.lax.optimization_barrier((w, h))
            w = jax.lax.all_gather(w, FSDP, axis=0, tiled=True)
        num, den = _chunked_ce_sums(h, w, tok, msk, chunk, vocab_axis=tensor,
                                    shift=shift)
        return jax.lax.psum((num, den), batch_axes)

    rows = P(batch_axes)
    return compat_shard_map(
        per_shard, mesh,
        in_specs=(rows, logical_to_mesh_spec((EMBED, VOCAB)), rows, rows),
        out_specs=(P(), P()),
    )(hidden, w_head.astype(hidden.dtype), tokens, mask)


def _chunked_ce_sums(hidden, w, tokens, mask, chunk, vocab_axis=None,
                     shift=True):
    """(sum of masked CE, sum of the mask) over ``hidden``'s rows, scanned in
    ``chunk`` positions at a time; next-token CE, or with ``shift`` False
    each position's own token. ``w`` is the [D, V] head or, inside a
    shard_map that names ``vocab_axis``, this shard's [D, V / extent]
    columns of it."""
    B, L, D = hidden.shape
    if shift:
        h = hidden[:, :-1]
        targets = tokens[:, 1:]
        m = mask[:, 1:].astype(jnp.float32)
        n = L - 1
    else:
        h, targets, m, n = hidden, tokens, mask.astype(jnp.float32), L
    chunk = min(chunk, n)
    pad = (-n) % chunk
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        m = jnp.pad(m, ((0, 0), (0, pad)))
    steps = (n + pad) // chunk
    h = h.reshape(B, steps, chunk, D).swapaxes(0, 1)
    targets = targets.reshape(B, steps, chunk).swapaxes(0, 1)
    m = m.reshape(B, steps, chunk).swapaxes(0, 1)
    w = w.astype(hidden.dtype)

    def ce(logits, tc):
        if vocab_axis is None:
            return optax.softmax_cross_entropy_with_integer_labels(logits, tc)
        # log-sum-exp over the whole vocabulary less the target's logit; the
        # maximum only stabilises, so no gradient flows through it
        top = jax.lax.pmax(
            jax.lax.stop_gradient(logits).max(-1), vocab_axis)
        sum_exp = jax.lax.psum(
            jnp.exp(logits - top[..., None]).sum(-1), vocab_axis)
        local = tc - jax.lax.axis_index(vocab_axis) * logits.shape[-1]
        mine = (local >= 0) & (local < logits.shape[-1])
        picked = jnp.take_along_axis(
            logits, jnp.where(mine, local, 0)[..., None], axis=-1)[..., 0]
        target = jax.lax.psum(jnp.where(mine, picked, 0.0), vocab_axis)
        return jnp.log(sum_exp) + top - target

    def body(acc, xs):
        hc, tc, mc = xs
        logits = (hc @ w).astype(jnp.float32)
        return acc + (ce(logits, tc) * mc).sum(), None

    total, _ = jax.lax.scan(body, jnp.zeros(()), (h, targets, m))
    return total, m.sum()


def _model_state(variables) -> PyTree:
    """The collections of ``model.init``'s variables that the step carries
    and the optimizer does not own, as ``{collection: tree}``."""
    return {k: unbox(v) for k, v in variables.items() if k == "router_state"}


def _sown(tree, name):
    """Every value sown under ``name``, in module order, whatever the depth
    (``sow`` keeps a tuple per call site)."""
    found = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if any(getattr(k, "key", None) == name for k in path):
            found.append(leaf)
    return found


def _routing_metrics(cfg: TransformerConfig, stats) -> dict:
    """What a step reports of its routing: assignments that reached an expert
    held here (of ``moe_top_k x tokens`` a layer, summed over the expert
    layers), the busiest held expert's load in any layer, and held
    assignments dropped over capacity."""
    lo = cfg.moe_expert_offset
    loads = jnp.stack(_sown(stats, "load"))  # [expert layers, E]
    held = loads[:, lo:lo + cfg.experts_held]
    return {"moe_assignments_held": held.sum(),
            "moe_max_expert_load": held.max(),
            "moe_dropped": sum(jnp.sum(d) for d in _sown(stats, "dropped"))}


def _move_selection_bias(cfg: TransformerConfig, model_state, stats):
    """``b_e += rate * sign(mean load - load_e)`` in every expert layer, from
    the step's counts over all routed experts: DeepSeek-V3's auxiliary-loss-
    free balancing. No gradient, no weight decay, no optimizer."""
    if "router_state" not in model_state:
        return model_state

    def move(bias_tree, stats_tree):
        if "bias" in bias_tree and "load" in stats_tree:
            load = jnp.sum(jnp.stack(stats_tree["load"]), 0).astype(jnp.float32)
            return {**bias_tree, "bias": bias_tree["bias"]
                    + cfg.moe_bias_rate * jnp.sign(load.mean() - load)}
        return {k: move(v, stats_tree[k]) if k in stats_tree else v
                for k, v in bias_tree.items()}

    return {**model_state,
            "router_state": move(model_state["router_state"], stats)}


class CheetahTrainer:
    """Builds and owns the sharded init + train step for one config/mesh."""

    def __init__(
        self,
        cfg: TransformerConfig,
        mesh: Mesh,
        optimizer: Optional[optax.GradientTransformation] = None,
        accum_steps: int = 1,
        seq_sharded: bool = False,
        loss_chunk: int = 256,
    ):
        self.cfg = cfg
        self.mesh = mesh
        self.model = Transformer(cfg)
        self.opt = optimizer or make_optimizer()
        self.accum_steps = int(accum_steps)
        self.seq_sharded = seq_sharded
        # chunked CE needs whole-L hidden states per shard; under sequence
        # sharding L is split across devices, so fall back to full logits
        self.loss_chunk = 0 if seq_sharded else int(loss_chunk)
        self._batch_shard = batch_sharding(mesh, seq_sharded)
        self._repl = replicated(mesh)
        # all-gathers of the LM head in one step's chunked loss, decided at
        # trace time from the mesh as lm_loss_chunked decides it: one a
        # microbatch where fsdp shards the head, none where it does not or
        # the step does not run the chunked loss (docs/telemetry.md)
        self.loss_head_gathers_per_step = (
            self.accum_steps
            if self.loss_chunk > 0 and FSDP in batch_mesh_axes(mesh)
            else 0
        )
        # which backward the hyper-connected blocks' stream reads and writes
        # take, as mhc_streams decides it when the step is traced
        self.mhc_backward = backward_path(cfg.hc_mult, cfg.d_model, mesh)
        # which form the chunked part of a kda layer takes, the preparation
        # and the scan over chunks alike, as parallel/kda.py decides it when
        # the step is traced at sequences of cfg.max_seq_len ("" without
        # such a layer)
        self.kda_path = scan_path(
            cfg.n_heads, cfg.kda_head_dim, cfg.kda_head_dim, cfg.max_seq_len,
            KDA_CHUNK, mesh, seq_sharded) if "kda" in cfg.mixers else ""
        # the state-space layers' sizes and the form their recurrence takes,
        # as parallel/ssd.py decides it when the step is traced at sequences
        # of cfg.max_seq_len ({} without such a layer)
        self.ssd = {}
        if "ssd" in cfg.mixers:
            if seq_sharded:
                raise NotImplementedError(
                    "a state-space mixer carries its state along the whole "
                    "sequence: it does not run under sequence sharding yet")
            self.ssd = {"heads": cfg.ssm_heads, "head_dim": cfg.ssm_head_dim,
                        "groups": cfg.ssm_groups, "state": cfg.ssm_state,
                        "chunk": cfg.ssm_chunk, "path": ssd_scan_path(
                            cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                            cfg.ssm_state, cfg.max_seq_len, cfg.ssm_chunk,
                            mesh)}

        # the form the dropless expert layers' grouped products take, as
        # moe._grouped_experts decides it when the step is traced at
        # sequences of cfg.max_seq_len, with the kernels' lane tiles for
        # each of the layer's two matrix shapes, and the form of the
        # dispatch's transpose (moe._rows_sum), with the token tile where it
        # is the kernel, and how the router keeps its assignments ({}
        # without an expert layer)
        self.experts = {}
        if "moe" in cfg.layer_kinds and cfg.moe_capacity_factor <= 0:
            D, F = cfg.d_model, cfg.expert_d_ff
            up = 2 * F if cfg.ffn_act == "swiglu" else F
            k, tokens = int(cfg.moe_top_k), cfg.max_seq_len
            itemsize = jnp.dtype(cfg.dtype).itemsize
            self.experts = expert_gmm.layer_plan(
                k * tokens, D, up, F, mesh, itemsize)
            self.experts["gathers"] = expert_rows.path(
                k * tokens, tokens, k, D, cfg.experts_held / cfg.moe_experts,
                mesh, itemsize)
            if self.experts["gathers"] == "kernel":
                self.experts["token_tile"] = expert_rows.token_tile(
                    tokens, k, D, itemsize)
        if "moe" in cfg.layer_kinds:
            # how the router finds which assignments are kept (moe.index_maps):
            # by a select where nothing is dropped, by a gather under a
            # capacity, whose kept mask depends on the slots
            self.experts["route"] = ("gather" if cfg.moe_capacity_factor > 0
                                     else "select")

        # the model's attention mask at sequences of cfg.max_seq_len, by
        # name, with the share of the [rows, rows] pairs it lets through, and
        # how many attention layers take each mask ("local:W" a window's)
        self.bd = cfg.objective == "block_diffusion"
        if self.bd and self.loss_chunk <= 0:
            raise NotImplementedError(
                "objective block_diffusion takes its loss through the chunk "
                "scan over whole sequences: it does not run under sequence "
                "parallelism yet")
        L = cfg.max_seq_len
        rows = 2 * L if self.bd else L
        kind = cfg.attn_mask(rows)[0]
        self.attn_mask = {
            "kind": kind,
            "pair_share": {"block_diffusion": block_diffusion.pair_share(
                L, cfg.bd_block), "causal": (L + 1) / (2 * L), "full": 1.0}[kind],
        }
        types = cfg.layer_types or (None,) * cfg.n_layers
        masks = [cfg.attn_mask(rows, t) for mixer, t in zip(cfg.mixers, types)
                 if mixer in ("gqa", "mla")]
        self.attn_mask["layers"] = dict(collections.Counter(
            f"local:{m[1]}" if m[0] == "local" else m[0] for m in masks))
        if kind == "block_diffusion":
            # the tiles of the forward kernel that evaluate the mask
            self.attn_mask["tiles"] = block_diffusion.tile_counts(
                L, cfg.bd_block, *splash_forward_tiles(
                    2 * L, cfg.attn_block_q, cfg.attn_block_kv, cfg.head_dim))

        dummy = jnp.zeros((1, 8), jnp.int32)
        boxed_abstract = jax.eval_shape(
            lambda r: self.model.init(r, dummy), jax.random.PRNGKey(0)
        )
        self.param_shardings = jax.tree.map(
            lambda s: s,
            param_shardings(mesh, boxed_abstract["params"]),
            is_leaf=lambda x: isinstance(x, NamedSharding),
        )
        # shapes of the state the optimizer does not own (all zeros at init)
        self._model_state_abstract = _model_state(boxed_abstract)

        self._init_jit = jax.jit(
            self._init_raw,
            out_shardings={"params": self.param_shardings,
                           "model_state": self._repl},
        )
        self._step_jit = jax.jit(self._train_step_raw, donate_argnums=(0,))

    # -- init ---------------------------------------------------------------
    def _init_raw(self, rng):
        dummy = jnp.zeros((1, 8), jnp.int32)
        variables = self.model.init(rng, dummy)
        return {"params": unbox(variables["params"]),
                "model_state": _model_state(variables)}

    def _commit_replicated(self, opt_state):
        """jit(opt.init) leaves scalar state (e.g. adam's count) on a single
        device; commit such leaves to the full mesh (replicated) so the
        train step sees one consistent device set (also post-restore). On a
        one-device mesh too: a scalar that is on the right device but not
        under the mesh's sharding has another abstract type than the step
        returns for it, and the second step would trace and compile again."""
        def stray(x):
            return (len(x.sharding.device_set) < self.mesh.size
                    or not isinstance(x.sharding, NamedSharding))

        return jax.tree.map(
            lambda x: jax.device_put(x, self._repl)
            if isinstance(x, jax.Array) and stray(x) else x,
            opt_state,
        )

    def init_state(self, rng: jax.Array) -> TrainState:
        with self.mesh:
            init = self._init_jit(rng)
            params, model_state = init["params"], init["model_state"]
            opt_state = jax.jit(self.opt.init)(params)
        opt_state = self._commit_replicated(opt_state)
        n_params = sum(int(p.size) for p in jax.tree.leaves(params))
        logger.info(
            "cheetah init: %.1fM params over mesh %s, "
            "loss_head_gathers_per_step %d, mhc_backward %s",
            n_params / 1e6, dict(self.mesh.shape),
            self.loss_head_gathers_per_step, self.mhc_backward,
        )
        if (self.kda_path == "xla"
                and jax.devices()[0].platform == "tpu"):
            logger.warning(
                "cheetah init: the kda layers run the XLA forms of the chunk "
                "preparation and scan, not the Pallas kernels (mesh %s, "
                "sequence sharding %s, %d heads of %d, %d tokens in chunks of "
                "%d): see "
                "parallel/kda.scan_path", dict(self.mesh.shape),
                self.seq_sharded, self.cfg.n_heads, self.cfg.kda_head_dim,
                self.cfg.max_seq_len, KDA_CHUNK)
        if (self.ssd.get("path") == "xla"
                and jax.devices()[0].platform == "tpu"):
            logger.warning(
                "cheetah init: the state-space layers run the XLA form of the "
                "chunked recurrence, not the Pallas kernels (mesh %s, sizes "
                "%s, %d tokens): see parallel/ssd.scan_path",
                dict(self.mesh.shape), self.ssd, self.cfg.max_seq_len)
        if (self.experts.get("path") == "xla"
                and jax.devices()[0].platform == "tpu"):
            logger.warning(
                "cheetah init: the expert layers' grouped products run as "
                "jax.lax.ragged_dot, not the Pallas kernels (mesh %s, "
                "products %s): see parallel/expert_gmm.path",
                dict(self.mesh.shape), self.experts)
        # above the share XLA's gather is the faster form, not a fallback
        held_share = self.cfg.experts_held / max(self.cfg.moe_experts, 1)
        if (self.experts.get("gathers") == "xla"
                and held_share <= expert_rows.MAX_HELD_SHARE
                and jax.devices()[0].platform == "tpu"):
            logger.warning(
                "cheetah init: the expert layers' dispatch transpose "
                "gathers every assignment through XLA, not the Pallas kernel "
                "(mesh %s, width %d, %d choices of %d tokens): see "
                "parallel/expert_rows.path", dict(self.mesh.shape),
                self.cfg.d_model, int(self.cfg.moe_top_k),
                self.cfg.max_seq_len)
        mlops.log_cheetah_init(
            {k: int(v) for k, v in self.mesh.shape.items()},
            self.loss_head_gathers_per_step,
            layers=list(self.cfg.layer_kinds),
            n_routed_experts=int(self.cfg.moe_experts),
            experts_held=int(self.cfg.experts_held),
            mhc_backward=self.mhc_backward,
            mixers=",".join(self.cfg.mixers), kda_path=self.kda_path,
            kda_chunk=KDA_CHUNK if self.kda_path else 0,
            objective=self.cfg.objective, bd_block=int(self.cfg.bd_block),
            attn_mask=self.attn_mask, head_dim=int(self.cfg.head_dim),
            layer_pattern=self.cfg.layer_pattern, ssd=self.ssd,
            ffn_act=self.cfg.ffn_act,
            tie_embeddings=bool(self.cfg.tie_embeddings),
            experts=self.experts,
        )
        # step must be committed to the mesh (replicated) — a default-device
        # scalar breaks jit after checkpoint restore (mixed device sets)
        step = jax.device_put(jnp.zeros((), jnp.int32), self._repl)
        return TrainState(step=step, params=params, opt_state=opt_state,
                          model_state=model_state)

    def state_from_params(self, params: PyTree) -> TrainState:
        """Fresh TrainState around externally-provided params.

        The FedLLM seam (``cross_silo/fedllm.py``): each FL round re-inits
        the local optimizer around the broadcast global params — matching the
        reference's per-round torch optimizer construction in its trainers
        (``ml/trainer/my_model_trainer_classification.py:30-45``). Host
        (numpy) leaves are placed onto the mesh with this trainer's param
        shardings, so a silo's local steps run fsdp/tp/sp-sharded no matter
        where the global model came from.
        """
        def fresh(p, s):
            # train_step donates its state: device_put may ALIAS a
            # caller-owned jax array (same-sharding fast path, and even a
            # host->replicated put can reuse the source buffer as one
            # replica — observed: a replicated [128] norm weight deleted
            # under a silo's second round), and donation then deletes the
            # caller's array. Sharding-equivalence guards are not a reliable
            # aliasing oracle, so jax.Array inputs are always copied; numpy
            # inputs copy on transfer anyway.
            if isinstance(p, jax.Array):
                p = jnp.array(p, copy=True)
            return jax.device_put(jnp.asarray(p), s)

        with self.mesh:
            params = jax.tree.map(fresh, params, self.param_shardings)
            opt_state = jax.jit(self.opt.init)(params)
            # the model's own state starts afresh with the optimizer's
            model_state = jax.tree.map(
                lambda s: jax.device_put(jnp.zeros(s.shape, s.dtype),
                                         self._repl),
                self._model_state_abstract)
        opt_state = self._commit_replicated(opt_state)
        step = jax.device_put(jnp.zeros((), jnp.int32), self._repl)
        return TrainState(step=step, params=params, opt_state=opt_state,
                          model_state=model_state)

    # -- train step ---------------------------------------------------------
    def _loss_fn(self, params, model_state, tokens, mask, noise_key=None):
        """(loss, what the expert layers sowed into ``moe_stats``; under the
        block-diffusion objective also ``bd``: the draw's counters)."""
        cfg = self.cfg
        moe = cfg.moe_experts > 1
        mtp = cfg.mtp_layers > 0
        mutable = ["losses", "moe_stats"] if moe else False
        variables = {"params": params, **model_state}
        if self.bd:
            return self._bd_loss(variables, tokens, mask, noise_key, mutable)
        kwargs = dict(mask=None, mutable=mutable)
        if mtp:
            kwargs["return_mtp"] = True
        if self.loss_chunk > 0:
            out = self.model.apply(variables, tokens, return_hidden=True,
                                   **kwargs)
            out, var_col = out if moe else (out, {})
            hidden, mtp_hidden = out if mtp else (out, None)
            with _scope("loss"):
                loss = lm_loss_chunked(
                    hidden, output_head(cfg, params), tokens, mask,
                    self.loss_chunk
                )
        else:
            out = self.model.apply(variables, tokens, **kwargs)
            out, var_col = out if moe else (out, {})
            logits, mtp_hidden = out if mtp else (out, None)
            with _scope("loss"):
                loss = lm_loss(logits, tokens, mask)
        if mtp:
            # position i of the module predicts token i + 2: the same loss
            # through the same head on the tokens moved up by one, the
            # wrapped last position masked out
            with _scope("mtp"), _scope("loss"):
                nxt = jnp.roll(tokens, -1, axis=1)
                nxt_mask = jnp.roll(mask, -1, axis=1).at[:, -1].set(0)
                if self.loss_chunk > 0:
                    mtp_loss = lm_loss_chunked(
                        mtp_hidden, output_head(cfg, params), nxt, nxt_mask,
                        self.loss_chunk)
                else:
                    mtp_loss = lm_loss(
                        jnp.einsum("bld,dv->blv", mtp_hidden,
                                   output_head(cfg, params).astype(
                                       mtp_hidden.dtype)
                                   ).astype(jnp.float32), nxt, nxt_mask)
                loss = loss + cfg.mtp_weight * mtp_loss
        return self._with_aux(loss, var_col), var_col.get("moe_stats", {})

    def _with_aux(self, loss, var_col):
        """``loss`` plus the softmax router's weighted auxiliary loss."""
        cfg = self.cfg
        if cfg.moe_experts > 1 and cfg.moe_router == "softmax":
            with _scope("loss"):
                aux = sum(
                    jnp.sum(jnp.asarray(v))
                    for v in jax.tree.leaves(var_col.get("losses", {}))
                )
                loss = loss + cfg.moe_aux_weight * aux
        return loss

    def _bd_loss(self, variables, tokens, mask, noise_key, mutable):
        """The block-diffusion objective on one batch [B, L]: the noise from
        ``noise_key``, the model once over the ``2L`` rows ``[x_t ; x_0]``,
        the weighted unshifted loss on the noised half."""
        cfg = self.cfg
        with _scope("bd_noise"):
            x_t, masked, weight = block_diffusion.noise(
                noise_key, tokens, cfg.bd_block, cfg.bd_mask_token)
            rows, positions = block_diffusion.model_rows(x_t, tokens)
            coefficient = masked * weight * mask.astype(jnp.float32)
        out = self.model.apply(variables, rows, positions=positions,
                               return_hidden=True, mutable=mutable)
        hidden, var_col = out if mutable else (out, {})
        with _scope("loss"):
            loss = block_diffusion.loss(
                hidden, output_head(cfg, variables["params"]), tokens,
                coefficient, self.loss_chunk)
        stats = dict(var_col.get("moe_stats", {}))
        stats["bd"] = {"masked_tokens": masked.sum().astype(jnp.int32),
                       "weight_sum": (masked * weight).sum()}
        return self._with_aux(loss, var_col), stats

    def _loss_and_grads(self, params, model_state, tokens, mask, step=None):
        """Loss, gradients and summed ``moe_stats`` of one step's batch: the
        mean over the microbatches where ``accum_steps > 1``. ``step`` numbers
        the step: what the block-diffusion objective draws its noise from
        (no other objective reads it)."""
        grad_fn = jax.value_and_grad(self._loss_fn, has_aux=True)

        def noise_key(micro=0):
            # None for every other objective: no op in their programs
            return block_diffusion.step_key(step, micro) if self.bd else None

        if self.accum_steps == 1:
            (loss, stats), grads = grad_fn(params, model_state, tokens, mask,
                                           noise_key())
            return loss, grads, stats
        xs = (tokens, mask)
        if self.bd:
            xs += (jnp.arange(self.accum_steps),)

        def micro(carry, xs):
            tok, msk, *index = xs
            (loss, stats), grads = grad_fn(params, model_state, tok, msk,
                                           noise_key(*index))
            acc_loss, acc_grads, acc_stats = carry
            return (
                acc_loss + loss,
                jax.tree.map(jnp.add, acc_grads, grads),
                jax.tree.map(jnp.add, acc_stats, stats),
            ), None

        with _scope("grad_accum"):
            zero = jax.tree.map(jnp.zeros_like, params)
            zero_stats = jax.tree.map(
                jnp.zeros_like,
                jax.eval_shape(lambda: self._loss_fn(
                    params, model_state, tokens[0], mask[0], noise_key())[1]))
            (loss_sum, grads, stats), _ = jax.lax.scan(
                micro, (jnp.zeros(()), zero, zero_stats), xs
            )
            loss = loss_sum / self.accum_steps
            grads = jax.tree.map(lambda g: g / self.accum_steps, grads)
        return loss, grads, stats

    def _train_step_raw(self, state: TrainState, tokens, mask):
        """tokens/mask: [accum, micro_batch, L] when accum_steps > 1,
        else [B, L]."""
        loss, grads, stats = self._loss_and_grads(
            state.params, state.model_state, tokens, mask, state.step)
        with _scope("optimizer"):
            updates, opt_state = self.opt.update(
                grads, state.opt_state, state.params
            )
            params = optax.apply_updates(state.params, updates)
        with _scope("metrics"):
            metrics = {"loss": loss, "grad_norm": optax.global_norm(grads)}
            model_state = state.model_state
            stats = dict(stats)
            metrics.update({f"bd_{k}": v
                            for k, v in stats.pop("bd", {}).items()})
            if stats:
                metrics.update(_routing_metrics(self.cfg, stats))
                model_state = _move_selection_bias(
                    self.cfg, state.model_state, stats)
        return (
            TrainState(step=state.step + 1, params=params,
                       opt_state=opt_state, model_state=model_state),
            metrics,
        )

    def shard_batch(self, tokens, mask):
        dp = 1
        for ax in self._batch_shard.spec[0] or ():
            dp *= int(self.mesh.shape[ax])
        b = tokens.shape[1] if self.accum_steps > 1 else tokens.shape[0]
        if b % dp:
            raise ValueError(
                f"batch size {b} must be divisible by the data-parallel "
                f"extent {dp} (mesh {dict(self.mesh.shape)}); raise batch_size "
                f"or shrink the data/fsdp axes"
            )
        if self.accum_steps > 1:
            spec = P(None, *self._batch_shard.spec)
            shard = NamedSharding(self.mesh, spec)
        else:
            shard = self._batch_shard
        return jax.device_put(tokens, shard), jax.device_put(mask, shard)

    @contextlib.contextmanager
    def _trace_context(self):
        """The contexts a step must be traced under: the mesh context lets
        the attention kernels shard_map themselves (Mosaic kernels cannot be
        auto-partitioned by pjit); the sequence context routes attention
        through the ring."""
        with contextlib.ExitStack() as stack:
            stack.enter_context(self.mesh)
            stack.enter_context(mesh_context(self.mesh))
            if self.seq_sharded:
                stack.enter_context(sequence_parallelism(self.mesh))
            yield

    def train_step(self, state: TrainState, tokens, mask) -> Tuple[TrainState, dict]:
        tokens, mask = self.shard_batch(tokens, mask)
        tracked = telemetry.enabled()
        if tracked:
            compiles = telemetry.compiles()
        with self._trace_context():
            out = self._step_jit(state, tokens, mask)
        if tracked and telemetry.compiles() != compiles:
            # the donated state's types only (shape, dtype, sharding), which
            # it still says; its buffers are not read
            self._publish_scopes((state, tokens, mask))  # graftlint: disable=G002
        return out

    def _publish_scopes(self, args) -> None:
        """After a tracked call that compiled (or loaded) its program: which
        scope every instruction of that program belongs to
        (``program_scopes``, docs/telemetry.md). Lowering the types the call
        had finds the call's own executable: nothing compiles here. Under
        fsdp the first step's program is not the later steps' (the moments
        arrive replicated once), so each is published as it appears."""
        with telemetry.phase("program_scopes", record=False):
            with self._trace_context():
                lowered = self._step_jit.lower(*telemetry.abstract_of(args))
            telemetry.record_program_scopes(lowered, TRAIN_STEP)

    def lower_step(self, state: TrainState, tokens, mask):
        """The step lowered (not compiled) exactly as ``train_step`` traces
        it — for inspecting what the program contains (``.as_text()``)."""
        tokens, mask = self.shard_batch(tokens, mask)
        with self._trace_context():
            return self._step_jit.lower(state, tokens, mask)
