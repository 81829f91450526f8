"""Block-diffusion training (BD3-LM, arXiv:2503.09573; SDAR, arXiv:2510.06303):
what the objective needs that had no place yet.

A sequence ``x_0`` of ``L`` tokens is cut into blocks of ``B``. Block ``b``
draws ``t_b``; each of its tokens is replaced by the mask token with
probability ``t_b``, giving ``x_t``. The model reads the ``2L`` rows
``[x_t ; x_0]`` at positions ``[0..L-1 ; 0..L-1]`` under one structured
attention mask (:func:`visible`): a noised block sees itself and the clean
copy of the blocks before it, the clean copy is block-causal and never sees a
noised row. The head reads the noised half; the loss of a sequence is
``(1 / L) sum over masked i of (1 / t_blk(i)) CE(logits_i, x_0[i])``, at the
position itself (no shift), averaged over the batch.

Three pieces, each a pure function: :func:`noise` (of a key),
:func:`visible` (of ``(i, j, L, B)``: the definition, from which the dense
boolean form is built; the splash kernel is handed the same mask in a form
that costs it a compare's worth per pair, :func:`kernel_visible`),
:func:`loss` (through the trainer's chunk scan). ``train_step._loss_fn`` puts
them together.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

# the step draws its noise from this key folded with ``state.step`` (and the
# microbatch's index), so the runner's loop and its batches stay as they are
NOISE_SEED = 0x5DA2
# the lowest noise level a block draws (BD3-LM's clipped uniform): the loss
# weight ``1 / t`` stays under 1,000
T_MIN = 1e-3


def step_key(step, micro=0) -> jax.Array:
    """The key the step numbered ``step`` draws microbatch ``micro``'s noise
    from."""
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(NOISE_SEED), step), micro)


def noise(key, tokens, block: int, mask_token: int, t_min: float = T_MIN):
    """``tokens`` [.., L] -> ``(x_t, masked, weight)``, all [.., L]: one
    ``t`` a block of ``block`` tokens, uniform on ``[t_min, 1]`` (the linear
    schedule: a token is masked with probability ``t``); ``masked`` says
    where ``x_t`` holds ``mask_token`` in place of the token; ``weight`` is
    ``1 / t`` of the token's block, float32."""
    L = tokens.shape[-1]
    if L % block:
        raise ValueError(f"blocks of {block} do not divide {L} tokens")
    k_t, k_u = jax.random.split(key)
    t = jax.random.uniform(k_t, tokens.shape[:-1] + (L // block,),
                           jnp.float32, t_min, 1.0)
    t = jnp.repeat(t, block, axis=-1)
    masked = jax.random.uniform(k_u, tokens.shape, jnp.float32) < t
    x_t = jnp.where(masked, jnp.asarray(mask_token, tokens.dtype), tokens)
    return x_t, masked, 1.0 / t


def repeated_positions(L: int) -> jax.Array:
    """``[0..L-1 ; 0..L-1]`` [1, 2L]: the clean copy of token ``i`` sits at
    position ``i`` like its noised copy."""
    return jnp.tile(jnp.arange(L), 2)[None, :]


def model_rows(x_t, x_0):
    """The ``2L`` rows the model reads, ``[x_t ; x_0]`` [.., 2L], and their
    positions [1, 2L]."""
    return (jnp.concatenate([x_t, x_0], axis=-1),
            repeated_positions(x_0.shape[-1]))


def visible(i, j, L: int, B: int):
    """May row ``i`` of the ``2L`` rows see row ``j``? Rows under ``L`` are
    the noised half; ``blk`` is a row's block in its half::

        (noised i and noised j and blk(i) == blk(j))
        or (noised i and clean j and blk(j) < blk(i))
        or (clean i and clean j and blk(j) <= blk(i))

    ``i`` and ``j`` are integer arrays that broadcast against each other,
    numpy's (the host's tile bookkeeping) or traced (inside the kernel):
    comparisons, ``//`` and ``-`` only."""
    clean_i, clean_j = i // L, j // L            # 0: noised half, 1: clean
    blk_i = (i - clean_i * L) // B
    blk_j = (j - clean_j * L) // B
    noised_i, noised_j = clean_i == 0, clean_j == 0
    return ((noised_i & noised_j & (blk_i == blk_j))
            | (noised_i & ~noised_j & (blk_j < blk_i))
            | (~noised_i & ~noised_j & (blk_j <= blk_i)))


@functools.lru_cache(maxsize=4)
def dense_mask(L: int, B: int) -> np.ndarray:
    """:func:`visible` over all ``2L x 2L`` pairs, a numpy boolean array
    (read-only: every layer of a trace shares it)."""
    rows = np.arange(2 * L, dtype=np.int32)
    mask = visible(rows[:, None], rows[None, :], L, B)
    mask.flags.writeable = False
    return mask


def pair_share(L: int, B: int) -> float:
    """The share of the ``4 L^2`` pairs the mask lets through:
    ``(L^2 + L B) / (4 L^2)``."""
    return (L * L + L * B) / (4.0 * L * L)


# marks a clean row's integer in ``kernel_rows``: above every row index, so
# the own-block test of ``kernel_visible`` never holds for a clean row
_CLEAN = 1 << 30


def kernel_rows(L: int, B: int) -> np.ndarray:
    """One integer a row of the ``2L``, int32 [2L]: all of the row that
    :func:`kernel_visible` needs. With ``lo`` the first row of the row's
    block, a noised row's is ``lo`` (it sees the ``lo`` clean keys before its
    block, and the ``B`` keys from ``lo``); a clean row's is ``_CLEAN`` plus
    the ``lo + B - L`` clean keys it sees."""
    rows = np.arange(2 * L, dtype=np.int32)
    lo = rows - rows % L % B
    return np.where(lo < L, lo, (lo + B - L) | _CLEAN).astype(np.int32)


def _unsigned(x):
    # bit for bit: a negative difference becomes a large one, so one compare
    # tests both ends of a range (numpy wraps; in the kernel it is no
    # operation)
    return x.astype(np.uint32)


def kernel_visible(r, j, L: int, B: int):
    """:func:`visible` ``(i, j)`` from ``r = kernel_rows(L, B)[i]``, for ``B``
    that divides ``L``: the clean keys the row sees are the first
    ``r mod _CLEAN`` of them, and a noised row also sees the ``B`` keys from
    ``r``. Two differences, an ``and``, two unsigned compares and an ``or``
    on tile-shaped operands where :func:`visible` traces to sixty primitives
    with four divisions: the splash kernels evaluate a mask's function pair
    by pair in every tile they keep, whole tiles included. Serves numpy's
    arrays (the tile bookkeeping) and traced ones (the kernel)."""
    clean_keys = _unsigned(j - L) < _unsigned(r & (_CLEAN - 1))
    own_block = _unsigned(j - r) < B
    return clean_keys | own_block


@functools.cache
def _splash_mask_class():
    """The mask's class, built on first use (the splash package is a TPU
    path's import)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as sm,
    )

    class BlockDiffusionMask(sm._ComputableMask):
        def __init__(self, L: int, B: int):
            if L % B or 2 * L >= _CLEAN:
                raise ValueError(
                    f"the block-diffusion mask's kernel form is for blocks "
                    f"that divide the sequence, under {_CLEAN // 2} tokens: "
                    f"got {L} tokens in blocks of {B}")
            self.seq_len, self.block = L, B
            super().__init__(
                shape=(2 * L, 2 * L),
                mask_function=lambda r, kv_ids: kernel_visible(r, kv_ids, L, B))
            # what the kernel is handed a row: not the row's index (which the
            # base class has just put there) but the row's integer
            self.q_sequence = kernel_rows(L, B)

        def __eq__(self, other):
            return (isinstance(other, type(self))
                    and (self.seq_len, self.block)
                    == (other.seq_len, other.block))

        def __hash__(self):
            return hash((type(self), self.seq_len, self.block))

    return BlockDiffusionMask


def splash_mask(L: int, B: int):
    """The mask as an object the splash kernel computes tile by tile (no
    ``[2L, 2L]`` array anywhere): the kernel's own bookkeeping finds the empty
    tiles and skips them, and evaluates :func:`kernel_visible` on
    :func:`kernel_rows` inside every tile it keeps."""
    return _splash_mask_class()(L, B)


def tile_counts(L: int, B: int, block_q: int, block_kv: int) -> Dict[str, int]:
    """Of the ``block_q x block_kv`` tiles of the ``[2L, 2L]`` pairs, how many
    the kernel keeps (some pair visible: it evaluates the mask there) and how
    many of those the mask crosses (some pair not), counted from the object
    the kernel is handed, as its bookkeeping counts them."""
    mask, rows = splash_mask(L, B), 2 * L
    kept = crossed = 0
    for r in range(0, rows, block_q):
        for c in range(0, rows, block_kv):
            tile = mask[r:min(r + block_q, rows), c:min(c + block_kv, rows)]
            if tile.any():
                kept += 1
                crossed += not tile.all()
    return {"kept": kept, "crossed": crossed,
            "of": -(-rows // block_q) * -(-rows // block_kv)}


def loss(hidden, w_head, x_0, coefficient, chunk: int):
    """``sum(coefficient * CE(hidden w_head, x_0)) / L`` a sequence, averaged
    over the batch: ``hidden`` [B, L, D] are the noised half's hidden states,
    ``coefficient`` [B, L] is ``masked * weight`` (times the batch's own
    mask); unshifted, through the trainer's chunk scan so the [B, L, V]
    logits are never whole."""
    from .train_step import chunked_ce_sums

    total, _ = chunked_ce_sums(hidden, w_head, x_0, coefficient, chunk,
                               shift=False)
    return total / (x_0.shape[0] * x_0.shape[1])
