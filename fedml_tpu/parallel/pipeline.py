"""GPipe pipeline parallelism over the ``pipeline`` mesh axis.

New-capability work (SURVEY.md §2.5: the reference's only layer-split
precedent is SplitNN, ``simulation/mpi/split_nn/``) — here a TPU-native
schedule:

- the transformer's blocks live as ONE stacked param tree ``[n_layers, ...]``
  reshaped to ``[n_stages, layers_per_stage, ...]`` and sharded over the
  ``pipeline`` mesh axis: each pipeline rank holds its stage's slice only
- the whole schedule is a single ``shard_map`` program: a ``lax.scan`` over
  ``M + S - 1`` ticks; every tick each stage applies its blocks and hands its
  activation to the next stage over ICI with ``lax.ppermute``
- backward needs no hand-written schedule: the transpose of ``ppermute`` is
  the reverse rotation, so ``jax.grad`` through the scan IS the backward
  pipeline (GPipe with rematerialised stages)
- embedding / final norm / LM head are replicated across the pipeline axis
  (stage 0 consumes the embedding, the last stage the head; replication keeps
  the per-device program uniform, which SPMD requires)
- the ``data`` mesh axis composes: microbatches are additionally sharded over
  ``data`` and gradients psum over it — pp x dp in one program

Bubble fraction is the GPipe (S-1)/(M+S-1); raise ``microbatches`` to
amortise.
"""

from __future__ import annotations

import logging
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .sharding import compat_shard_map as shard_map

from .. import constants
from .transformer import (
    Block,
    TransformerConfig,
    rms_norm,
    rotary_embedding,
)

logger = logging.getLogger(__name__)

PyTree = Any

DATA = constants.MESH_AXIS_DATA
PIPELINE = constants.MESH_AXIS_PIPELINE


class PipelineCheetah:
    """Pipeline-parallel trainer for the Cheetah transformer.

    ``mesh`` must carry a ``pipeline`` axis of size S >= 2 and
    ``cfg.n_layers`` must divide evenly into S stages.

    Capabilities (explicit, so nobody infers more than is here):

    - schedule: ``"gpipe"`` (default) — M microbatches through S stages
      over ``M + S - 1`` ticks, backward by autodiff; or ``"1f1b"`` —
      hand-scheduled one-forward-one-backward ticks whose in-flight
      activation memory is O(S) instead of O(M)
      (``_train_step_device_1f1b``; gradient-exact vs gpipe, verified by
      ``tests/test_pipeline.py::test_1f1b_matches_gpipe``). Bubble
      fraction is (S-1)/(M+S-1) for both (non-interleaved); 1F1B's win is
      the memory headroom that lets M grow. No interleaved stages.
    - backward: ``jax.grad`` through the scan (ppermute's transpose is the
      reverse rotation) — exact, rematerialised per stage
    - composes with a ``data`` mesh axis (pp x dp); tensor/sequence axes
      INSIDE a stage are not supported — use ``CheetahTrainer`` for tp/sp
    - embedding/norm/head replicated across stages; every stage computes the
      stage-0 embedding gather each tick (SPMD-uniform program; the waste is
      one [mb, L, D] gather per tick per stage, accepted for uniformity)
    """

    def __init__(
        self,
        cfg: TransformerConfig,
        mesh: Mesh,
        microbatches: int = 4,
        optimizer: Optional[optax.GradientTransformation] = None,
        schedule: str = "gpipe",
    ):
        if schedule not in ("gpipe", "1f1b"):
            raise ValueError(f"schedule must be 'gpipe' or '1f1b', got {schedule!r}")
        if getattr(cfg, "pos_emb", "rope") != "rope":
            # both schedules hard-code rotary; silently dropping a
            # config knob the single-device path honours would train a
            # DIFFERENT model than the same YAML elsewhere
            raise NotImplementedError(
                "PipelineCheetah supports pos_emb='rope' only"
            )
        if cfg.layer_group_size:
            raise NotImplementedError(
                "PipelineCheetah stacks one block: a mixer chosen per layer "
                "(layer_group_size) does not run under it")
        if cfg.layer_pattern:
            raise NotImplementedError(
                "PipelineCheetah stacks one block: a layer list given as a "
                "pattern (layer_pattern), one sublayer a layer, does not run "
                "under it")
        if cfg.layer_types:
            raise NotImplementedError(
                "PipelineCheetah stacks one block: a mixer chosen per layer "
                "(layer_types) does not run under it")
        if cfg.tie_embeddings:
            raise NotImplementedError(
                "PipelineCheetah keeps its own head: a head tied to the "
                "embedding (tie_embeddings) does not run under it")
        self.schedule = schedule
        self.cfg = cfg
        self.mesh = mesh
        self.n_stages = int(mesh.shape[PIPELINE])
        if self.n_stages < 2:
            raise ValueError("pipeline axis must have size >= 2")
        if cfg.n_layers % self.n_stages:
            raise ValueError(
                f"n_layers {cfg.n_layers} not divisible by "
                f"{self.n_stages} stages"
            )
        self.layers_per_stage = cfg.n_layers // self.n_stages
        self.microbatches = int(microbatches)
        self.block = Block(cfg)
        self.opt = optimizer or optax.adamw(3e-4)
        self._step = None
        self._loss_jit = None
        self._blocks_struct = None  # computed once, reused everywhere

    def bubble_fraction(self) -> float:
        """GPipe idle fraction: (S-1)/(M+S-1) of each device's schedule."""
        S, M = self.n_stages, self.microbatches
        return (S - 1) / (M + S - 1)

    # -- params -------------------------------------------------------------
    def init_params(self, rng: jax.Array) -> PyTree:
        """{'embed', 'blocks' (stacked [n_layers, ...]), 'norm_f', 'head'}."""
        cfg = self.cfg
        k_embed, k_blocks, k_head = jax.random.split(rng, 3)
        block_keys = jax.random.split(k_blocks, cfg.n_layers)
        blocks = jax.jit(jax.vmap(self._init_one_block))(block_keys)
        params = {
            "embed": jax.random.normal(
                k_embed, (cfg.vocab_size, cfg.d_model), cfg.param_dtype
            ) * 0.02,
            "blocks": blocks,
            "norm_f": jnp.ones((cfg.d_model,), jnp.float32),
            "head": jax.random.normal(
                k_head, (cfg.d_model, cfg.vocab_size), cfg.param_dtype
            ) * 0.02,
        }
        return jax.device_put(params, self.param_shardings())

    def param_shardings(self) -> PyTree:
        """blocks sharded over pipeline on the layer axis; rest replicated."""
        repl = NamedSharding(self.mesh, P())
        stage = NamedSharding(self.mesh, P(PIPELINE))
        return {
            "embed": repl,
            "blocks": jax.tree.map(lambda _: stage, self._blocks_structure()),
            "norm_f": repl,
            "head": repl,
        }

    def _init_one_block(self, k):
        """Init + unbox one block's params — the single source of the block
        param structure (init_params vmaps it; _blocks_structure shapes it)."""
        cfg = self.cfg
        dummy = jnp.zeros((1, 8, cfg.d_model), cfg.dtype)
        pos = jnp.arange(8)[None, :]
        cos, sin = rotary_embedding(pos, cfg.head_dim, cfg.rope_theta)
        variables = self.block.init(k, dummy, cos, sin)
        return jax.tree.map(
            lambda p: p.value if hasattr(p, "value") else p,
            variables["params"],
            is_leaf=lambda x: hasattr(x, "value"),
        )

    def _blocks_structure(self):
        """Unboxed single-block param shapes (computed once)."""
        if self._blocks_struct is None:
            self._blocks_struct = jax.eval_shape(
                self._init_one_block, jax.random.PRNGKey(0)
            )
        return self._blocks_struct

    # -- the pipelined program ----------------------------------------------
    def _apply_stage(self, stage_blocks, x, cos, sin):
        """Run this stage's layers_per_stage blocks (scan over the slice)."""

        def body(h, layer_params):
            unboxed = jax.tree.map(
                lambda p: p.value if hasattr(p, "value") else p,
                layer_params, is_leaf=lambda q: hasattr(q, "value"),
            )
            h = self.block.apply({"params": unboxed}, h, cos, sin)
            return h, None

        x, _ = jax.lax.scan(body, x, stage_blocks)
        return x

    def _loss_device(self, params, tokens, mask):
        """Per-device GPipe loop. tokens [M, mb_local, L] (local slice)."""
        cfg = self.cfg
        S, M = self.n_stages, self.microbatches
        stage = jax.lax.axis_index(PIPELINE)
        Mb, L = tokens.shape[1], tokens.shape[2]
        pos = jnp.arange(L)[None, :]
        cos, sin = rotary_embedding(pos, cfg.head_dim, cfg.rope_theta)
        # this device's stage slice: [layers_per_stage, ...] — under
        # shard_map the leading n_layers axis arrives already sliced
        stage_blocks = params["blocks"]

        perm = [(i, (i + 1) % S) for i in range(S)]
        T = M + S - 1

        def tick(buf, t):
            # stage 0 embeds microbatch t (junk for t >= M; dropped later)
            mb = jnp.take(
                tokens, jnp.minimum(t, M - 1), axis=0
            )  # [mb_local, L]
            x0 = jnp.take(params["embed"], mb, axis=0).astype(cfg.dtype)
            x_in = jnp.where(stage == 0, x0, buf)
            y = self._apply_stage(stage_blocks, x_in, cos, sin)
            buf_next = jax.lax.ppermute(y, PIPELINE, perm)
            return buf_next, y

        buf0 = jnp.zeros((Mb, L, cfg.d_model), cfg.dtype)
        _, ys = jax.lax.scan(tick, buf0, jnp.arange(T))  # [T, mb, L, D]

        # last stage's ticks S-1 .. T-1 hold microbatches 0..M-1
        outs = jax.lax.dynamic_slice_in_dim(ys, S - 1, M, axis=0)
        h = rms_norm(
            outs, params["norm_f"].astype(jnp.float32), cfg.norm_eps
        )
        logits = jnp.einsum(
            "mbld,dv->mblv", h, params["head"].astype(cfg.dtype)
        ).astype(jnp.float32)
        targets = tokens[:, :, 1:]
        m = mask[:, :, 1:].astype(jnp.float32)
        per = optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :, :-1], targets
        )
        local_sum = (per * m).sum()
        local_cnt = m.sum()
        # only the final stage's logits are meaningful. The returned value is
        # the LOCAL loss over the GLOBAL token count — never psum the
        # numerator inside the differentiated function: psum's transpose is
        # psum, so a psum'd numerator multiplies every gradient by the axis
        # size. Callers psum the scalar afterwards for reporting.
        is_last = (stage == S - 1).astype(jnp.float32)
        cnt = jax.lax.psum(local_cnt * is_last, PIPELINE)
        if DATA in self.mesh.axis_names and self.mesh.shape[DATA] > 1:
            cnt = jax.lax.psum(cnt, DATA)
        return local_sum * is_last / jnp.maximum(cnt, 1.0)

    def _all_reduce_scalar(self, x):
        x = jax.lax.psum(x, PIPELINE)
        if DATA in self.mesh.axis_names and self.mesh.shape[DATA] > 1:
            x = jax.lax.psum(x, DATA)
        return x

    def _train_step_device(self, params, opt_state, tokens, mask):
        loss, grads = jax.value_and_grad(self._loss_device)(
            params, tokens, mask
        )
        loss = self._all_reduce_scalar(loss)  # reporting only
        # cross-stage grad flow rode the ppermute transpose; replicated
        # params (embed/norm/head) need their grads summed across stages,
        # and everything psums over data
        def sync(path_is_blocks, g):
            if not path_is_blocks:
                g = jax.lax.psum(g, PIPELINE)
            if DATA in self.mesh.axis_names and self.mesh.shape[DATA] > 1:
                g = jax.lax.psum(g, DATA)
            return g

        grads = {
            "embed": sync(False, grads["embed"]),
            "blocks": jax.tree.map(partial(sync, True), grads["blocks"]),
            "norm_f": sync(False, grads["norm_f"]),
            "head": sync(False, grads["head"]),
        }
        updates, opt_state = self.opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    # -- 1F1B schedule --------------------------------------------------------
    def _train_step_device_1f1b(self, params, opt_state, tokens, mask):
        """Hand-scheduled one-forward-one-backward pipeline tick loop.

        GPipe-by-autodiff (``_train_step_device``) lets ``jax.grad`` run the
        whole forward scan first, so every tick's stage output — M + S - 1
        activations of [mb, L, D] — is live until its backward. 1F1B
        interleaves: at tick t each stage forwards microbatch ``t - s`` and
        backwards microbatch ``t - 2(S-1) + s`` (the last stage backwards a
        microbatch at the same tick its forward completes), so only a ring
        of 2S in-flight stage INPUTS is ever saved — activation memory
        O(S), independent of M. Bubble fraction is unchanged vs GPipe for
        the non-interleaved schedule — the win is memory, which is what
        lets M grow (and the bubble shrink) without re-enabling remat.

        Gradients are exact: each backward tick recomputes its stage
        forward from the saved input and applies the cotangent arriving
        from the next stage over the reverse ``ppermute``.
        """
        cfg = self.cfg
        S, M = self.n_stages, self.microbatches
        stage = jax.lax.axis_index(PIPELINE)
        Mb, L = tokens.shape[1], tokens.shape[2]
        pos = jnp.arange(L)[None, :]
        cos, sin = rotary_embedding(pos, cfg.head_dim, cfg.rope_theta)
        R = 2 * S  # ring capacity > max in-flight (2(S-1)+1)
        T = M + 2 * (S - 1)
        perm_fwd = [(i, (i + 1) % S) for i in range(S)]
        perm_bwd = [((i + 1) % S, i) for i in range(S)]
        is_last = (stage == S - 1)

        def stage_fwd(p_blocks, p_embed, buf, mb_tokens):
            x0 = jnp.take(p_embed, mb_tokens, axis=0).astype(cfg.dtype)
            x_in = jnp.where(stage == 0, x0, buf)
            return self._apply_stage(p_blocks, x_in, cos, sin)

        def loss_sum_fn(p_norm, p_head, y, mb_tokens, mb_mask):
            h = rms_norm(y, p_norm.astype(jnp.float32), cfg.norm_eps)
            logits = jnp.einsum(
                "bld,dv->blv", h, p_head.astype(cfg.dtype)
            ).astype(jnp.float32)
            per = optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], mb_tokens[:, 1:]
            )
            return (per * mb_mask[:, 1:].astype(jnp.float32)).sum()

        zeros_g = {
            "embed": jnp.zeros_like(params["embed"]),
            "blocks": jax.tree.map(jnp.zeros_like, params["blocks"]),
            "norm_f": jnp.zeros_like(params["norm_f"]),
            "head": jnp.zeros_like(params["head"]),
        }

        def tick(carry, t):
            fwd_buf, bwd_buf, saved, g, loss_sum = carry
            # ---- forward of microbatch m_f = t - stage
            m_f = t - stage
            f_valid = ((m_f >= 0) & (m_f < M)).astype(jnp.float32)
            tok_f = jnp.take(tokens, jnp.clip(m_f, 0, M - 1), axis=0)
            msk_f = jnp.take(mask, jnp.clip(m_f, 0, M - 1), axis=0)
            y = stage_fwd(params["blocks"], params["embed"], fwd_buf, tok_f)
            # save this microbatch's stage INPUT for its backward recompute
            slot_f = jnp.where(m_f >= 0, m_f % R, 0)
            cur = jax.lax.dynamic_index_in_dim(saved, slot_f, 0,
                                               keepdims=False)
            saved = jax.lax.dynamic_update_index_in_dim(
                saved,
                jnp.where(f_valid > 0, fwd_buf, cur),
                slot_f, 0,
            )
            # ---- last stage: loss grads for THIS microbatch, immediately.
            # Gated with lax.cond (r4 ADVICE): ungated, the [mb,L,D]x[D,V]
            # head fwd+bwd ran on EVERY tick of EVERY stage and was masked
            # after the fact — M+2(S-1) head matmul pairs per step per
            # stage vs the M the last stage needs, a real tax at vocab 32k.
            def head_grads(ops):
                p_norm, p_head, y_, tok_, msk_ = ops
                lval, (g_norm, g_head, dy_loss) = jax.value_and_grad(
                    loss_sum_fn, argnums=(0, 1, 2)
                )(p_norm, p_head, y_, tok_, msk_)
                return lval, g_norm, g_head, dy_loss

            def head_skip(ops):
                p_norm, p_head, y_, _tok, _msk = ops
                return (jnp.zeros(()), jnp.zeros_like(p_norm),
                        jnp.zeros_like(p_head), jnp.zeros_like(y_))

            lval, g_norm, g_head, dy_loss = jax.lax.cond(
                is_last & (f_valid > 0), head_grads, head_skip,
                (params["norm_f"], params["head"], y, tok_f, msk_f),
            )
            loss_sum = loss_sum + lval
            g["norm_f"] = g["norm_f"] + g_norm
            g["head"] = g["head"] + g_head
            # ---- backward of microbatch m_b = t - 2(S-1) + stage
            m_b = t - 2 * (S - 1) + stage
            b_valid = ((m_b >= 0) & (m_b < M)).astype(jnp.float32)
            tok_b = jnp.take(tokens, jnp.clip(m_b, 0, M - 1), axis=0)
            slot_b = jnp.where(m_b >= 0, m_b % R, 0)
            x_saved = jax.lax.dynamic_index_in_dim(saved, slot_b, 0,
                                                   keepdims=False)
            # cotangent: the last stage's is its own fresh loss grad
            # (m_b == m_f there); other stages' arrived over the ring
            dy = jnp.where(is_last, dy_loss.astype(cfg.dtype), bwd_buf)
            _, vjp = jax.vjp(
                lambda pb, pe, xb: stage_fwd(pb, pe, xb, tok_b),
                params["blocks"], params["embed"], x_saved,
            )
            d_blocks, d_embed, dx = vjp(dy)
            g["blocks"] = jax.tree.map(
                lambda a, b: a + b * b_valid, g["blocks"], d_blocks
            )
            g["embed"] = g["embed"] + d_embed * b_valid
            # ---- rotate: activations forward, cotangents backward
            fwd_buf = jax.lax.ppermute(y, PIPELINE, perm_fwd)
            bwd_buf = jax.lax.ppermute(
                (dx * b_valid).astype(cfg.dtype), PIPELINE, perm_bwd
            )
            return (fwd_buf, bwd_buf, saved, g, loss_sum), None

        buf0 = jnp.zeros((Mb, L, cfg.d_model), cfg.dtype)
        saved0 = jnp.zeros((R, Mb, L, cfg.d_model), cfg.dtype)
        carry = jax.lax.scan(
            tick, (buf0, buf0, saved0, zeros_g, jnp.zeros(())),
            jnp.arange(T),
        )[0]
        g, loss_sum = carry[3], carry[4]
        # normalize by the GLOBAL token count and sync exactly like GPipe
        cnt = mask[:, :, 1:].astype(jnp.float32).sum()  # replicated over pp
        if DATA in self.mesh.axis_names and self.mesh.shape[DATA] > 1:
            cnt = jax.lax.psum(cnt, DATA)
        cnt = jnp.maximum(cnt, 1.0)

        def sync(path_is_blocks, gr):
            if not path_is_blocks:
                gr = jax.lax.psum(gr, PIPELINE)
            if DATA in self.mesh.axis_names and self.mesh.shape[DATA] > 1:
                gr = jax.lax.psum(gr, DATA)
            return gr / cnt

        grads = {
            "embed": sync(False, g["embed"]),
            "blocks": jax.tree.map(partial(sync, True), g["blocks"]),
            "norm_f": sync(False, g["norm_f"]),
            "head": sync(False, g["head"]),
        }
        # loss_sum is already nonzero only on the last stage (w_last mask)
        loss = self._all_reduce_scalar(loss_sum) / cnt
        updates, opt_state = self.opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    # -- public API ----------------------------------------------------------
    def init_opt_state(self, params: PyTree) -> PyTree:
        with self.mesh:
            return jax.jit(self.opt.init)(params)

    def _specs(self):
        blocks_spec = jax.tree.map(
            lambda _: P(PIPELINE), self._blocks_structure()
        )
        p_spec = {
            "embed": P(), "blocks": blocks_spec, "norm_f": P(), "head": P(),
        }
        d_spec = P(None, DATA) if DATA in self.mesh.axis_names else P(None, None)
        return p_spec, d_spec

    def loss(self, params, tokens, mask) -> jax.Array:
        """tokens/mask: [M, B, L] microbatched global arrays."""
        if self._loss_jit is None:
            p_spec, d_spec = self._specs()

            def full_loss(params, tokens, mask):
                return self._all_reduce_scalar(
                    self._loss_device(params, tokens, mask)
                )

            fn = shard_map(
                full_loss, mesh=self.mesh,
                in_specs=(p_spec, d_spec, d_spec), out_specs=P(),
            )
            self._loss_jit = jax.jit(fn)
        with self.mesh:
            return self._loss_jit(params, tokens, mask)

    def train_step(self, params, opt_state, tokens, mask):
        if self._step is None:
            p_spec, d_spec = self._specs()
            o_spec = _opt_state_specs(p_spec, opt_state)
            device_fn = (
                self._train_step_device_1f1b
                if self.schedule == "1f1b"
                else self._train_step_device
            )
            fn = shard_map(
                device_fn, mesh=self.mesh,
                in_specs=(p_spec, o_spec, d_spec, d_spec),
                out_specs=(p_spec, o_spec, P()),
            )
            self._step = jax.jit(fn)
        with self.mesh:
            return self._step(params, opt_state, tokens, mask)


def _path_keys(path) -> tuple:
    """Normalize a jax key path to plain hashable tokens."""
    out = []
    for k in path:
        for attr in ("key", "name", "idx"):
            if hasattr(k, attr):
                out.append(str(getattr(k, attr)))
                break
        else:
            out.append(str(k))
    return tuple(out)


def _opt_state_specs(p_spec: PyTree, opt_state: PyTree) -> PyTree:
    """PartitionSpecs for an optimizer state mirroring param sharding.

    Optimizer moments (adam mu/nu, momentum buffers, ...) embed the param
    tree inside wrapper structures, so an opt-state leaf's key path ENDS
    with the corresponding param's key path — match by longest path suffix,
    never by leaf shape (two same-shaped params with different shardings
    would collide silently). Scalars like adam's ``count`` match nothing
    and stay replicated.
    """
    import jax.tree_util as jtu

    spec_by_path = {
        _path_keys(path): sp
        for path, sp in jtu.tree_flatten_with_path(
            p_spec, is_leaf=lambda x: isinstance(x, P)
        )[0]
    }

    def one(path, _x):
        keys = _path_keys(path)
        for start in range(len(keys)):  # longest suffix first
            sp = spec_by_path.get(keys[start:])
            if sp is not None:
                return sp
        return P()

    return jtu.tree_map_with_path(one, opt_state)


def microbatch(tokens: np.ndarray, mask: np.ndarray, m: int):
    """[B, L] -> [M, B/M, L]."""
    B, L = tokens.shape
    if B % m:
        raise ValueError(f"batch {B} not divisible by microbatches {m}")
    return (
        tokens.reshape(m, B // m, L),
        mask.reshape(m, B // m, L),
    )
