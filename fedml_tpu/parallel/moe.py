"""Mixture-of-Experts feed-forward with expert parallelism.

New-capability work (SURVEY.md §2.5 "Expert parallelism / MoE" — the
reference has no MoE at all; the ``expert`` mesh axis existed here as a
constant only). One layer, a routing rule and a capacity rule:

- **routing** (``cfg.moe_router``). ``softmax``: scores ``p = softmax(x
  W_r)`` over all experts in float32, the ``cfg.moe_top_k`` largest (Switch
  top-1, GShard / Mixtral top-2, Qwen3-MoE's top-8 of 128), gates ``p_e /
  sum of the chosen p``, and the Switch load-balancing auxiliary loss over
  all the router's outputs; a single choice keeps its raw probability as
  gate. ``sigmoid``
  (DeepSeek-V3's ``noaux_tc``): scores ``s = sigmoid(x W_r)`` in float32,
  the top ``cfg.moe_top_k`` of ``s + b`` selected, where the bias ``b`` is
  state no gradient reaches (the ``router_state`` collection; the trainer
  moves it after each step from the expert loads), gates from ``s`` alone,
  renormalised over the chosen and scaled by ``cfg.moe_routed_scale``; no
  auxiliary loss. With ``cfg.moe_n_group`` > 1 the selection is
  group-limited: the experts are that many contiguous groups, a group scores
  the sum of its two largest ``s + b``, and the top k are taken among the
  ``cfg.moe_topk_group`` best groups. More than one choice renormalises
  under either rule.
- **capacity** (``cfg.moe_capacity_factor``). Above 0: GShard slots,
  ``factor * k * T / E`` an expert in an ``[E, capacity, D]`` buffer,
  over-capacity assignments dropped (they pass through the residual
  unchanged), second choices before first and later tokens before earlier.
  0: no capacity and no drop: the assignments are sorted by expert and the
  experts run as grouped matrix products over the tokens that arrived (on
  one TPU device the kernels of ``parallel/expert_gmm.py``, whose grid visits
  each held expert's row tiles with its matrix in VMEM; elsewhere
  ``jax.lax.ragged_dot``); the buffer is the worst case ``k * T`` rows, the
  work is not.
- **the experts held** (``cfg.moe_experts_held``, ``cfg.moe_expert_offset``):
  expert parallelism seen from one chip. The router keeps its width and
  routes over all ``cfg.moe_experts``; this program holds the weights of
  experts ``[offset, offset + held)`` and adds their part of the result.
  What the absent experts would have added is left out, and nothing stands
  in for their chips or the exchange.
- ``cfg.moe_shared_experts`` experts of the same width that every token
  passes, added to the routed result (``cfg.moe_shared_d_ff``: one of a
  width of its own).
- **the activation** (``cfg.ffn_act``): an expert is the gated SiLU form,
  ``w_gate_up`` ``[held, D, 2F]``, or ``relu(x W_up)^2 W_down`` with no gate
  matrix, ``w_up`` ``[held, D, F]``; the grouped products take either.

Either way dispatch and combine are row gathers over assignments sorted by
expert (stable, so GShard's priority order survives inside each group), and
so are their transposes, written by hand: no row is scattered. Without a
capacity an assignments-sized buffer (``[kT, D]``) is written once, by the
gather that makes it, and read once, by the product or the reduction that
consumes it: a gather that wants zeros for missing rows reads a tokens-sized
operand with one zero row appended (:func:`_padded_rows`), one that reads an
assignments-sized operand drops them inside the sum over the choices
(:func:`_gathered_sum`), and the combine's backward works row by row from
the tokens' cotangent (:func:`_combine`). Under a capacity the rows' way to
their slots appends its zero row to the rows themselves, one copy more.
Expert weights are ONE stacked param tree ``[held, ...]``; the logical
``expert`` axis maps to the ``expert`` mesh axis (sharding.LOGICAL_RULES).

The layer sows what a step reports into the ``moe_stats`` collection:
``load`` (assignments to each of the ``E`` experts, before any drop),
``dropped`` (held assignments over capacity).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..core.mlops.scopes import train_step_scope as _scope
from . import expert_gmm
from .context import get_mesh_context
from .transformer import (EMBED, MLP, FeedForward, TransformerConfig,
                          ffn_activation)

EXPERT_AXIS = "expert_dim"  # logical name for the stacked-expert axis


def route(cfg: TransformerConfig, scores_in: jax.Array, bias=None):
    """Router logits [T, E] float32 -> (expert [T, k] int32, gate [T, k]
    float32, auxiliary loss). Choice ``j`` of every token is column ``j``."""
    E, k = cfg.moe_experts, int(cfg.moe_top_k)
    if cfg.moe_router == "sigmoid":
        scores = jax.nn.sigmoid(scores_in)
        select = scores if bias is None else scores + bias
        if cfg.moe_n_group > 1:
            select = _keep_best_groups(select, cfg.moe_n_group,
                                       cfg.moe_topk_group)
        _, expert = jax.lax.top_k(select, k)
        aux = jnp.zeros((), jnp.float32)
    else:
        scores = jax.nn.softmax(scores_in, axis=-1)
        _, expert = jax.lax.top_k(scores, k)
        # Switch aux loss: E * sum_e frac_e * mean_prob_e. The load fraction
        # is over ALL k assignments (second-choice hot-spotting is visible
        # to the regularizer), normalised by k so a balanced router scores 1
        frac = jax.nn.one_hot(expert, E, dtype=jnp.float32).sum(1).mean(0) / k
        aux = E * jnp.sum(frac * scores.mean(0))
    gate = jnp.take_along_axis(scores, expert, axis=-1)
    if k > 1:  # renormalised over the chosen
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    return expert.astype(jnp.int32), gate * cfg.moe_routed_scale, aux


def _keep_best_groups(select, n_group: int, kept: int):
    """Selection scores [T, E] with every expert outside the ``kept`` best of
    ``n_group`` contiguous groups at minus infinity; a group scores the sum
    of its two largest selection scores (DeepSeek-V3)."""
    T, E = select.shape
    grouped = select.reshape(T, n_group, E // n_group)
    group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)          # [T, n_group]
    _, best = jax.lax.top_k(group_score, kept)                  # [T, kept]
    keep = (best[:, :, None] == jnp.arange(n_group)).any(1)     # [T, n_group]
    return jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(T, E)


class MoEFeedForward(nn.Module):
    """Drop-in replacement for the dense FeedForward in an expert layer.

    Returns ``(y, aux_loss)`` — the caller adds ``aux_loss`` (scaled by
    ``cfg.moe_aux_weight``) to the task loss; under the softmax router the
    router collapses onto one expert without it. Zero under ``sigmoid``.
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x) -> Tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        E, held, offset = cfg.moe_experts, cfg.experts_held, cfg.moe_expert_offset
        D, F = cfg.d_model, cfg.expert_d_ff
        B, L, _ = x.shape
        T = B * L
        k = int(cfg.moe_top_k)
        init = nn.initializers.normal(0.02)

        w_router = self.param(
            "w_router", nn.with_partitioning(init, (EMBED, None)),
            (D, E), jnp.float32,
        )
        gated = cfg.ffn_act == "swiglu"  # gate and up fused, or up alone
        w_up = self.param(
            "w_gate_up" if gated else "w_up",
            nn.with_partitioning(init, (EXPERT_AXIS, EMBED, MLP)),
            (held, D, 2 * F if gated else F), cfg.param_dtype,
        )
        w_down = self.param(
            "w_down",
            nn.with_partitioning(init, (EXPERT_AXIS, MLP, EMBED)),
            (held, F, D), cfg.param_dtype,
        )
        bias = None
        if cfg.moe_router == "sigmoid":
            # the selection bias: state, not a parameter (no gradient, no
            # weight decay; train_step moves it from the loads)
            bias = self.variable(
                "router_state", "bias", jnp.zeros, (E,), jnp.float32).value

        xt = x.reshape(T, D)
        with _scope("moe_route"):
            # routing in fp32 (tiny, numerically sensitive): a default
            # float32 product on a TPU rounds its inputs to bfloat16
            expert, gate, aux_loss = route(
                cfg, jnp.matmul(xt.astype(jnp.float32), w_router,
                                precision=jax.lax.Precision.HIGHEST), bias)
            # the flat assignment order is (all first choices in token
            # order, then all second choices, ...): GShard's priority
            flat_expert = expert.T.reshape(k * T)
            # a compare-and-sum, not a bincount: no scatter on the device
            load = (flat_expert[:, None] == jnp.arange(E)).sum(0)  # [E]
            counts = load[offset:offset + held].astype(jnp.int32)
            # experts this program holds are 0..held-1, the others `held`
            local = flat_expert - offset
            local = jnp.where((local >= 0) & (local < held), local, held)
            # one stable sort brings the keys, the assignments' flat indices
            # and their gates into expert order; the gates ride along for the
            # combine's backward, which works row by row, and carry no
            # gradient here (the combine returns the gates')
            sorted_local, order, gate_row = jax.lax.sort(
                (local, jnp.arange(k * T, dtype=jnp.int32),
                 jax.lax.stop_gradient(gate).T.reshape(k * T)),
                num_keys=1, is_stable=True)                        # [kT] each
            if cfg.moe_capacity_factor > 0:
                kept_sorted, slot = _capacity_slots(cfg, sorted_local, counts)
            else:
                kept_sorted, slot = sorted_local < held, None
            # the two index maps of dispatch and combine; an index one past
            # the end stands for a row of zeros: row -> its token, and
            # (choice, token) -> its row
            token = jnp.where(kept_sorted, order % T, T)
            inv = jnp.argsort(order, stable=True).astype(jnp.int32)
            src = jnp.where(kept_sorted[inv], inv, k * T).reshape(k, T)
        self.sow("moe_stats", "load", load)
        self.sow("moe_stats", "dropped",
                 counts.sum() - kept_sorted.sum().astype(jnp.int32))

        with _scope("moe_experts"):
            rows = _dispatch(xt.astype(cfg.dtype), token, src)       # [kT, D]
            if slot is not None:
                out = _slotted_experts(cfg, rows, w_up, w_down, slot)
        if slot is None:
            # the products beside dispatch and combine, not inside them
            out = _grouped_experts(cfg, rows, w_up, w_down, counts)
        with _scope("moe_experts"):
            # each choice's row back at its token, weighed by its gate;
            # dropped and absent assignments add nothing
            # in the result's own shape: a reshape after the sum over the
            # choices would stand between that sum and the slices it reads
            y = _combine(out, gate.reshape(B, L, k), gate_row,
                         src.reshape(k, B, L), token, order)         # [B, L, D]
        if cfg.moe_shared_experts:
            with _scope("shared_expert"):
                y = y + FeedForward(cfg, d_ff=cfg.shared_d_ff, name="shared")(x)
        return y, aux_loss


def _padded_rows(x, index):
    """``x[index]`` along axis 0 for ``index`` up to ``len(x)``, which reads a
    row of zeros: one zero row appended to ``x`` and a plain in-bounds gather,
    so no pass over the result to fill it. The copy is the operand's size,
    the tokens' where it matters."""
    padded = jnp.concatenate([x, jnp.zeros((1,) + x.shape[1:], x.dtype)])
    return jnp.take(padded, index, axis=0, mode="clip")


def _gathered_sum(rows, src, weight=None):
    """``sum_c weight[c, t] * rows[src[c, t]]`` in float32, [N, D] and [k, ...]
    -> [..., D], leaving out the entries whose ``src`` is ``N`` or more. They
    are selected away, not multiplied by zero (a row no entry names may hold
    anything). The gathered ``[k, ..., D]`` rows are read once: select,
    convert, product and the sum over ``c``, added in the order of ``c``, are
    one fusion."""
    chosen = jnp.take(rows, src, axis=0, mode="clip")
    present = src < rows.shape[0]
    total = 0
    for c in range(src.shape[0]):
        term = jnp.where(present[c, ..., None], chosen[c], 0).astype(jnp.float32)
        total = total + (term if weight is None else weight[c, ..., None] * term)
    return total


@jax.custom_vjp
def _dispatch(x, token, src):
    """Rows [T, D] -> [N, D]: row ``p`` is ``x[token[p]]``, zeros where
    ``token[p]`` is ``T``; ``src`` [k, T] names the rows that read each ``x[t]``
    (``N`` where fewer than ``k`` do). Tokens to the assignments' rows sorted
    by expert, and under a capacity those rows to their slots and back. Its
    transpose sums ``g[src[c, t]]`` over ``c``, so the backward pass gathers
    too: autodiff's own transpose of a gather is a scatter-add, which a TPU
    serialises."""
    del src
    return _padded_rows(x, token)


def _dispatch_fwd(x, token, src):
    return _padded_rows(x, token), src


def _dispatch_bwd(src, g):
    return _gathered_sum(g, src).astype(g.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(out, gate, gate_row, src, token, order):
    """The experts' output rows [kT, D] and the gates [..., k] (the tokens in
    any leading shape, ``src`` [k, ...] in the same) -> [..., D] in the rows'
    type: ``sum_c gate[t, c] * out[src[c, t]]`` in float32. Rows that belong
    to no kept assignment are never read, whatever the kernel left there.

    The backward pass works in row order and never builds a ``[k, T, D]``
    cotangent: row ``p`` reads ``dy[token[p]]`` (a gather from the tokens'
    ``[T, D]``, zeros where ``p`` is no kept assignment), times its gate
    ``gate_row[p]`` it is ``out``'s cotangent, and its product with ``out[p]``
    summed over ``D`` the gate's, which one sort by ``order`` (row -> flat
    ``c * T + t``, a permutation) returns to the gates' shape. The residual is
    ``out`` itself, so a rematerialised block recomputes no combine."""
    del gate_row, token, order
    return _gathered_sum(out, src, jnp.moveaxis(gate, -1, 0)).astype(out.dtype)


def _combine_fwd(out, gate, gate_row, src, token, order):
    y = _gathered_sum(out, src, jnp.moveaxis(gate, -1, 0)).astype(out.dtype)
    return y, (out, gate_row, src.shape, token, order)


def _combine_bwd(residuals, dy):
    out, gate_row, choices, token, order = residuals
    dy = dy.reshape(-1, dy.shape[-1])                                # [T, D]
    g = _padded_rows(dy, token).astype(jnp.float32)                 # [kT, D]
    d_out = (gate_row[:, None] * g).astype(out.dtype)
    d_gate_row = jnp.where(
        token < dy.shape[0], (g * out.astype(jnp.float32)).sum(-1), 0)   # [kT]
    _, d_gate_flat = jax.lax.sort((order, d_gate_row), num_keys=1)
    d_gate = jnp.moveaxis(d_gate_flat.reshape(choices), 0, -1)
    return d_out, d_gate, None, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _capacity_slots(cfg, sorted_local, counts):
    """GShard's capacity: which sorted assignments are kept (their expert is
    held here and they are among its first ``capacity``), and the slot
    ``expert * capacity + position`` of each in the ``[held, capacity]``
    buffer."""
    held, kT = counts.shape[0], sorted_local.shape[0]
    # capacity scales with k (GShard/Mixtral): top-2 makes 2T route
    # assignments, so unscaled capacity would drop most second choices
    # even under a perfectly balanced router
    capacity = max(int(cfg.moe_capacity_factor * kT / cfg.moe_experts), 1)
    group_start = (jnp.cumsum(counts) - counts).astype(jnp.int32)
    group = jnp.minimum(sorted_local, held - 1)
    pos = jnp.arange(kT, dtype=jnp.int32) - group_start[group]
    kept = (sorted_local < held) & (pos < capacity)
    return kept, jnp.where(kept, group * capacity + pos, held * capacity)


def _slotted_experts(cfg, rows, w_up, w_down, slot):
    """Each held expert computes ``capacity`` rows, whatever arrived: the
    sorted rows go to their slots of an ``[held, capacity, D]`` buffer (a
    gather by the slots' inverse: a kept row's slot is unique), through the
    vmapped feed-forward, and back."""
    held = w_up.shape[0]
    kT, D = rows.shape
    capacity = max(int(cfg.moe_capacity_factor * kT / cfg.moe_experts), 1)
    n_slots = held * capacity
    # slot -> the row that fills it (kT where none does)
    filler = jnp.full((n_slots + 1,), kT, jnp.int32).at[slot].set(
        jnp.arange(kT, dtype=jnp.int32), mode="drop")[:n_slots]
    expert_in = _dispatch(rows, filler, slot[None]).reshape(held, capacity, D)

    def ffn(up_w, down_w, h):
        up = jnp.einsum("cd,df->cf", h, up_w.astype(cfg.dtype))
        return jnp.einsum("cf,fd->cd", ffn_activation(cfg.ffn_act, up),
                          down_w.astype(cfg.dtype))

    expert_out = jax.vmap(ffn)(w_up, w_down, expert_in)
    return _dispatch(expert_out.reshape(n_slots, D), slot, filler[None])


def _grouped_experts(cfg, rows, w_up, w_down, counts):
    """No capacity: the sorted rows, held experts first, go through two
    grouped products whose groups are the experts' arrivals. Rows past the
    last group (assignments to experts held elsewhere) belong to no group:
    the products compute nothing for them, and :func:`_combine` never reads
    what they leave there. On one TPU device, at shapes the kernels take
    (``expert_gmm.layer_path``), the products are ``expert_gmm``'s kernels,
    elsewhere ``jax.lax.ragged_dot``. They alone stand under ``moe_products``;
    the weights' casts and the activation between them stay under
    ``moe_experts`` with dispatch and combine."""
    kT, D = rows.shape
    itemsize = jnp.dtype(cfg.dtype).itemsize
    if expert_gmm.layer_path(kT, D, w_up.shape[-1], w_down.shape[1],
                             get_mesh_context(), itemsize) == "kernel":
        product = expert_gmm.grouped_product
    else:
        product = functools.partial(jax.lax.ragged_dot,
                                    preferred_element_type=cfg.dtype)
    with _scope("moe_experts"):
        w_up = w_up.astype(cfg.dtype)
    with _scope("moe_products"):
        h = product(rows, w_up, counts)
    with _scope("moe_experts"):
        a, w_down = ffn_activation(cfg.ffn_act, h), w_down.astype(cfg.dtype)
    with _scope("moe_products"):
        return product(a, w_down, counts)
