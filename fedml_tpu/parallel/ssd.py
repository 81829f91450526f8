"""The state-space recurrence of a Mamba-2 mixer (SSD, arXiv:2405.21060): a
scalar decay a head, in its chunkwise-parallel form, as plain XLA and, on one
TPU device, as a pair of Pallas kernels.

**The recurrence** (the definition, :func:`ssd_recurrence`;
``benchmark/reference/nemotron_h.py`` scans it token by token in its own
code). Per head ``h`` of ``H``, a state ``S`` in ``R^{P x N}``, zero at the
start of a sequence; for token ``t`` with an input ``x_t`` in ``R^P``, a step
size ``dt_t > 0``, the head's ``A < 0``, and its group's ``B_t``, ``C_t`` in
``R^N`` (``H / G`` consecutive heads read group ``g``)::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t
    y_t = S_t C_t

**The chunked form** (:func:`ssd_chunked`, what a layer runs). Over a chunk of
``Q`` tokens with ``a_t`` the running sum of ``dt A`` inside the chunk
(``a_t <= 0``, falling) and ``S`` the state that enters it::

    Y  = ((C B^T) o exp(a_t - a_s) [s <= t]) (dt x)     inside the chunk
       + exp(a_t) C_t S                                 what came before
    S' = exp(a_Q) S + sum_s exp(a_Q - a_s) dt_s x_s (x) B_s

Every exponent is at most 0: the pairs ``s > t`` are masked before the
exponential, not after it. In the plain form the chunks' own states are one
batched product, the carry over the chunks a ``lax.scan`` of one multiply-add
a chunk. A sequence that is no multiple of ``Q`` is padded with steps of size
0, which neither decay the state nor write to it, and cut. Its backward is
autodiff's. The plain form is the definition of the chunked one, what runs
wherever :func:`scan_path` answers ``xla``, and what the kernels are tested
against.

**The kernels** (``ssd_chunk_fwd`` / ``ssd_chunk_bwd`` under one
``custom_vjp``, :func:`ssd_fused`; where :func:`scan_path` answers ``fused``).
The grid is (batch, group, chunk block), the first two parallel, the chunk
axis sequential; a grid step walks ``CHUNKS_PER_STEP`` chunks of a group's
``R = H / G`` heads. Operands come as the layer has them: ``x`` as
``[b, L, H P]``, blocked by a whole group's lanes (``P`` = 64 is half a lane
tile, so heads stand side by side in tiles of 128 lanes and are never cut
apart), ``B`` and ``C`` as ``[b, L, G N]``; the step sizes and the running sum
``a`` (made outside, so that autodiff takes ``da`` back through the sum to
``dt`` and ``A``) a row a head, ``[b, G, R, L]``, turned into columns by one
transpose of a tile in the kernel. What lies in VMEM and never in HBM: the
gap ``a_t - a_s``, its mask, the decay, ``C B^T`` (one product a group, shared
by its heads), the pairs in the compute dtype, ``dt x`` and its decayed copy.
The carried state of a group's heads is a float32 scratch across the chunk
axis, held transposed (``[N, R P]``), so that the two products against it are
lane-dense over the whole group: the read ``C [Q, N] x S^T [N, R P]``, scaled
by ``exp(a_t)`` a head, and the chunk's own state ``B^T [N, Q] x (dt x o
exp(a_Q - a_s)) [Q, R P]``. The pairs against ``dt x`` are a head's own; the
heads of a tile go through one product of full width (their pair tiles side by
side against ``dt x`` once a head, each copy zero outside its head's lanes).
``y`` leaves in float32 with the mixer's ``D x`` added.

*What the forward saves.* Nothing in its first mode (the primal under
``jax.checkpoint``); in its second the float32 state that enters every chunk,
``[b, L / Q, G, N, R P]`` (134 MB a layer at the Nemotron cell's sizes, alive
while that layer's backward runs). *How the backward walks.* The chunk blocks
and the chunks in them in reverse, the state's cotangent in the scratch; a
chunk's decays and pairs are formed again from the operands and the saved
state, and every gradient is written by hand (:func:`_bwd_kernel` has the
equations): ``dx``, ``ddt`` through ``dt x``, ``dB`` and ``dC`` summed over a
group's heads in float32, ``dD``, and ``da`` through the decays: the row sums
less the column sums of one float32 tile ``dpairs o scores o decay``, and the
``exp(a_t)``, ``exp(a_Q - a_s)`` and ``exp(a_Q)`` factors. *Which exponent is
bounded where.* ``a_t - a_s`` is masked to ``-inf`` for ``s > t`` before its
exponential; ``a_t``, ``a_Q - a_s`` and ``a_Q`` are sums of ``dt A <= 0``; no
``exp(-a_s)`` is ever formed, in either kernel.

Step sizes, running sums, decays, the carried and saved states, ``y`` and
every accumulation in float32; the products' inputs in the dtype the caller
computes in (bfloat16 in training) exactly where the plain form rounds them:
``dt x``, ``B``, ``C``, the pairs, the decayed ``dt x``, the state that is
read; the backward rounds the cotangents that enter a product, as autodiff's
products of the plain form do on a TPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from .context import get_mesh_context, one_tpu_device
from .kda import _grid2, _nn, _nt, _params, _tn

# chunks one grid step of the kernels walks (a block of CHUNKS_PER_STEP x
# chunk tokens of every operand), so that a grid step's overhead is paid once
# in 512 tokens at the configuration's chunks of 128
CHUNKS_PER_STEP = 4
_LANES = 128


def scan_path(heads: int, head_dim: int, groups: int, state: int, seq_len: int,
              chunk: int, mesh: Optional[Mesh]) -> str:
    """``"fused"`` where :func:`ssd_chunked` runs as the Pallas kernels,
    ``"xla"`` where its plain form and autodiff run: another back-end than a
    TPU, a mesh of several devices (Mosaic kernels are not partitioned by
    pjit and no wrapper shards these yet), a sequence that is no whole number
    of the kernels' chunk blocks, heads that do not stand side by side in
    whole lane tiles (``head_dim`` a divisor of 128 below it, a group's heads
    a whole number of tiles: what Mosaic was seen to compile), a state or a
    chunk that is no multiple of 128. ``mesh`` is the ambient one, or the
    trainer's."""
    per_group = heads // max(groups, 1)
    fused = (groups > 0 and heads % groups == 0
             and 0 < head_dim < _LANES and _LANES % head_dim == 0
             and (per_group * head_dim) % _LANES == 0
             and state > 0 and state % _LANES == 0
             and chunk > 0 and chunk % _LANES == 0
             and seq_len > 0 and seq_len % (chunk * CHUNKS_PER_STEP) == 0
             and one_tpu_device(mesh))
    return "fused" if fused else "xla"


def ssd_recurrence(x, dt, A, B, C, S0=None):
    """Token by token, float32. x: [b, L, H, P]; dt: [b, L, H] (after the
    softplus); A: [H]; B, C: [b, L, G, N] -> (y [b, L, H, P], the last state
    [b, H, P, N])."""
    b, L, H, P = x.shape
    G, N = B.shape[-2:]
    f32 = jnp.float32
    x, dt, A, B, C = (v.astype(f32) for v in (x, dt, A, B, C))
    B, C = (jnp.repeat(v, H // G, axis=2) for v in (B, C))       # [b, L, H, N]

    def step(S, inputs):
        x_t, dt_t, B_t, C_t = inputs
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * B_t[..., None, :])
        return S, jnp.einsum("bhpn,bhn->bhp", S, C_t,
                             precision=jax.lax.Precision.HIGHEST)

    S0 = jnp.zeros((b, H, P, N), f32) if S0 is None else S0.astype(f32)
    S, y = jax.lax.scan(step, S0, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1), S


def ssd_chunked(x, dt, A, B, C, chunk: int, dtype=None, S0=None, skip=None):
    """The same in chunks of ``chunk`` tokens. ``dtype``: the products'
    inputs (None: ``x``'s); ``dt`` and ``A`` float32; ``skip`` [H] float32
    (None: none), the mixer's ``D``: ``y + D_h x``. Returns (y [b, L, H, P],
    the last state [b, H, P, N]), both float32 as accumulated. By the path
    :func:`scan_path` names: the kernels, or what follows here."""
    b, L, H, P = x.shape
    G, N = B.shape[-2:]
    if scan_path(H, P, G, N, L, chunk, get_mesh_context()) == "fused":
        return ssd_fused(x, dt, A, B, C, chunk, dtype, S0, skip)
    x_in = x
    f32 = jnp.float32
    dtype = dtype or x.dtype
    Q = min(chunk, L)
    pad = (-L) % Q
    if pad:
        x, dt, B, C = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, B, C))
    n = (L + pad) // Q

    def chunks(v):  # [b, L, ...] -> [b, n, Q, ...]
        return v.reshape((b, n, Q) + v.shape[2:])

    dt = chunks(dt.astype(f32))                                  # [b, n, Q, H]
    a = jnp.cumsum(dt * A.astype(f32), axis=2)                   # <= 0, falling
    # the step size rides on the input, so both products below read dt x
    xs = (chunks(x).astype(f32) * dt[..., None]).astype(dtype)  # [b, n, Q, H, P]
    Bc, Cc = chunks(B).astype(dtype), chunks(C).astype(dtype)    # [b, n, Q, G, N]
    heads = (b, n, Q, G, H // G)

    # inside a chunk: token t reads the tokens s <= t of its chunk
    scores = jnp.einsum("bntgk,bnsgk->bngts", Cc, Bc,
                        preferred_element_type=f32)             # [b, n, G, Q, Q]
    a_h = jnp.moveaxis(a, 2, -1)                                 # [b, n, H, Q]
    gap = a_h[..., :, None] - a_h[..., None, :]                  # a_t - a_s
    seen = jnp.tril(jnp.ones((Q, Q), jnp.bool_))
    decay = jnp.exp(jnp.where(seen, gap, -jnp.inf))              # [b, n, H, Q, Q]
    pairs = (scores[:, :, :, None] * decay.reshape(b, n, G, H // G, Q, Q)
             ).astype(dtype)
    y = jnp.einsum("bngrts,bnsgrp->bntgrp", pairs, xs.reshape(heads + (P,)),
                   preferred_element_type=f32)

    # each chunk's own state at its end, then the carry over the chunks
    to_end = jnp.exp(a[:, :, -1:] - a)                           # [b, n, Q, H]
    own = jnp.einsum(
        "bnsgrp,bnsgk->bngrpk",
        (xs.astype(f32) * to_end[..., None]).astype(dtype).reshape(heads + (P,)),
        Bc, preferred_element_type=f32).reshape(b, n, H, P, N)
    whole = jnp.exp(a[:, :, -1])                                 # [b, n, H]

    def carry(S, inputs):
        d, own_c = inputs
        return d[..., None, None] * S + own_c, S                 # emits S before

    S0 = jnp.zeros((b, H, P, N), f32) if S0 is None else S0.astype(f32)
    S, before = jax.lax.scan(
        carry, S0, (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(own, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                          # [b, n, H, P, N]

    # what came before the chunk, decayed down to token t
    across = jnp.einsum("bntgk,bngrpk->bntgrp", Cc,
                        before.astype(dtype).reshape(b, n, G, H // G, P, N),
                        preferred_element_type=f32)
    y = (y + across * jnp.exp(a).reshape(heads)[..., None]).reshape(
        b, n * Q, H, P)[:, :L]
    if skip is not None:
        y = y + skip[:, None] * x_in.astype(f32)
    return y, S


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------


class _Tiles:
    """How a group's heads lie in lane tiles of 128: ``hp = 128 / P`` heads
    (two or more) side by side in a tile, ``R / hp`` tiles a group."""

    def __init__(self, R, P):
        self.hp, self.count = _LANES // P, R * P // _LANES
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
        self.head = jax.lax.shift_right_logical(lane, P.bit_length() - 1)

    def lanes(self, j):
        return slice(j * _LANES, (j + 1) * _LANES)

    def heads(self, j):
        return range(j * self.hp, (j + 1) * self.hp)

    def spread(self, cols, j):
        """The columns of tile ``j``'s heads (lane ``h`` of ``cols``
        [rows, 128] is head ``h``'s), each over its head's lanes: one gather
        along the lanes (a single row, which the gather does not take, by
        selects)."""
        if cols.shape[0] > 1:
            which = jnp.broadcast_to(self.head + j * self.hp, cols.shape)
            return jnp.take_along_axis(cols, which, axis=1)
        first = j * self.hp
        out = cols[:, first:first + 1]
        for k in range(1, self.hp):
            out = jnp.where(self.head == k, cols[:, first + k:first + k + 1], out)
        return out

    def apart(self, v):
        """``v`` [rows, 128] once a head, one under the other, each copy zero
        outside its head's lanes ([hp rows, 128]): what takes the heads' pair
        tiles, laid side by side, through one product of full width."""
        return jnp.concatenate(
            [jnp.where(self.head == k, v, jnp.zeros_like(v))
             for k in range(self.hp)], axis=0)

    def sums(self, v):
        """The sum over each head's lanes of ``v`` [rows, 128]: one
        [rows, 1] a head."""
        return [jnp.sum(jnp.where(self.head == k, v, 0.0), axis=1, keepdims=True)
                for k in range(self.hp)]


def _columns(rows):
    """``rows`` [R, Q] float32, a row a head, as columns: [Q, 128] whose lane
    ``h`` is head ``h``'s column (the lanes past ``R`` hold zeros). One
    transpose of a whole tile, so that the step sizes and running sums reach
    the kernel lane-dense and no [L, R] array with eight lanes of 128 filled
    lies in HBM."""
    R, Q = rows.shape
    return jnp.concatenate(
        [rows, jnp.zeros((_LANES - R, Q), rows.dtype)], axis=0).T


def _chunk(b_ref, c_ref, dt_ref, a_ref, at, dtype):
    """What both kernels form of one chunk (the tokens ``at``) before its
    heads: ``B``, ``C`` rounded, ``C B^T`` (once a group), the step sizes and
    running sums by column (lane ``h`` of [Q, 128] is head ``h``'s) and the
    sums by row ([R, Q])."""
    Bc, Cc = b_ref[at, :].astype(dtype), c_ref[at, :].astype(dtype)
    a_rows = a_ref[:, at]
    return (Bc, Cc, _nt(Cc, Bc), _columns(dt_ref[:, at]), _columns(a_rows),
            a_rows)


def _decays(tiles, a, j):
    """Of tile ``j``'s heads, each over its lanes: the decays down to each
    token ``exp(a_t)`` and on to the chunk's end ``exp(a_Q - a_s)``
    ([Q, 128]) and over the whole chunk ``exp(a_Q)`` ([1, 128]). Every
    exponent is at most 0."""
    sums = tiles.spread(a, j)
    last = tiles.spread(a[a.shape[0] - 1:, :], j)
    return jnp.exp(sums), jnp.exp(last - sums), jnp.exp(last)


def _pairs(scores, a, a_rows, heads, seen, dtype):
    """Per head of a tile its decay ``exp(a_t - a_s) [s <= t]`` (the mask
    before the exponential) and the pairs under it, rounded."""
    out = []
    for h in heads:
        gap = a[:, h:h + 1] - a_rows[h:h + 1, :]
        decay = jnp.exp(jnp.where(seen, gap, -jnp.inf))
        out.append((decay, (scores * decay).astype(dtype)))
    return out


def _seen(Q):
    row, col = _grid2(Q)
    return row >= col


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, skip_ref, s0_ref,
                y_ref, sT_ref, *rest, chunk, head_dim, dtype, save_states):
    """One (batch, group, chunk block) step. Blocks: ``x``, ``y``
    [cb * Q, R * P] (the layer's [b, L, H * P], a whole group's lanes);
    ``dt``, ``a`` [R, cb * Q], a row a head; ``b``, ``c`` [cb * Q, N];
    ``skip`` [1, R * P], each head's ``D`` over its lanes; ``s0``, ``sT``
    [N, R * P], the state transposed; ``states`` (where saved)
    [cb, N, R * P], the state that enters each chunk. The scratch holds the
    running state of the group's heads."""
    from jax.experimental import pallas as pl

    if save_states:
        states_ref, state = rest
    else:
        (state,) = rest
    Q, f32 = chunk, jnp.float32
    tiles = _Tiles(dt_ref.shape[0], head_dim)
    seen = _seen(Q)

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = s0_ref[...].astype(f32)

    # The loop over a grid step's chunks is traced once and laid out flat
    # (``unroll``): the next chunk's pairs do not wait for this chunk's state,
    # and the scheduler overlaps only what stands in one block (0.52 ms a call
    # against 0.69 as a loop). A Python loop lays out the same schedule and
    # cost every process 7 s more of set-up (PERF.md section 6, PR 39).
    # (The body writes the kernel's output refs: they are the program's
    # results, not a side effect of tracing, whatever graftlint's G004 reads.)
    def one_chunk(c, carry):
        rows = pl.ds(pl.multiple_of(c * Q, Q), Q)
        Bc, Cc, scores, dt, a, a_rows = _chunk(
            b_ref, c_ref, dt_ref, a_ref, rows, dtype)
        if save_states:
            states_ref[c] = state[...]  # graftlint: disable=G004
        for j in range(tiles.count):
            at = tiles.lanes(j)
            St = state[:, at]
            x = x_ref[rows, at].astype(f32)
            xs = (x * tiles.spread(dt, j)).astype(dtype)
            down, to_end, whole = _decays(tiles, a, j)
            pairs = _pairs(scores, a, a_rows, tiles.heads(j), seen, dtype)
            y = _nn(jnp.concatenate([p for _, p in pairs], axis=1),
                    tiles.apart(xs))
            y = y + _nn(Cc, St.astype(dtype)) * down
            y_ref[rows, at] = y + skip_ref[:, at] * x  # graftlint: disable=G004
            xe = (xs.astype(f32) * to_end).astype(dtype)
            state[:, at] = whole * St + _tn(Bc, xe)  # graftlint: disable=G004
        return carry

    jax.lax.fori_loop(0, y_ref.shape[0] // Q, one_chunk, 0, unroll=True)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        sT_ref[...] = state[...]


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, skip_ref, states_ref,
                dy_ref, dsT_ref,
                dx_ref, ddt_ref, da_ref, db_ref, dc_ref, dskip_ref, ds0_ref,
                dstate, cols, *, chunk, head_dim, dtype):
    """The chunk blocks in reverse (the index maps turn the chunk axis
    round); blocks as the forward's, each gradient as its operand. A chunk's
    decays and pairs are formed again from the operands and the saved state
    ``S`` that entered it; ``dS'`` is the carried cotangent of the state that
    left it. With ``xs = dt x``, ``xe = xs o exp(a_Q - a_s)`` and
    ``dA = dY o exp(a_t)``, per head::

        dpairs = dY xs^T                   dscores = sum over the group's heads
                                                     of dpairs o decay
        dxe = B dS'^T                      dxs = pairs^T dY + dxe o exp(a_Q - a_s)
        dC  = dscores B + dA S^T           dB  = dscores^T C + xe dS'
        dS  = exp(a_Q) dS' + dA^T C        dx  = dxs o dt + D dY
        ddt = sum_p dxs o x                dD  = sum_t dY o x (a lane; summed over
                                                 a head's lanes outside)

    and of the running sum, by the factor it enters: the row sums of
    ``dpairs o scores o decay`` less its column sums (one float32 tile, so
    that the two cancel over a chunk as they do under autodiff),
    ``sum_p dA o (C S^T)`` for ``exp(a_t)``, ``- sum_p dxe o xe`` for
    ``exp(a_Q - a_s)``, and at the chunk's last row what ``a_Q`` carries:
    ``sum (dxe o xe) + exp(a_Q) sum (dS' o S)``. What comes a column a head is gathered in the scratch
    ``cols`` ([2, Q, 128]: ``ddt``, ``da``) and leaves as rows by one
    transpose each. All on the transposed state ([N, R * P]), everything
    accumulated in float32."""
    from jax.experimental import pallas as pl

    Q, f32 = chunk, jnp.float32
    R = dt_ref.shape[0]
    tiles = _Tiles(R, head_dim)
    seen = _seen(Q)
    last_row = jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0) == Q - 1

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = dsT_ref[...].astype(f32)
        dskip_ref[...] = jnp.zeros(dskip_ref.shape, f32)
        cols[...] = jnp.zeros(cols.shape, f32)

    steps = dy_ref.shape[0] // Q

    def one_chunk(i, carry):  # laid out flat, as the forward's
        c = steps - 1 - i
        rows = pl.ds(pl.multiple_of(c * Q, Q), Q)
        Bc, Cc, scores, dt, a, a_rows = _chunk(
            b_ref, c_ref, dt_ref, a_ref, rows, dtype)
        dscores = jnp.zeros((Q, Q), f32)
        dB = jnp.zeros(Bc.shape, f32)
        dC = jnp.zeros(Cc.shape, f32)
        for j in range(tiles.count):
            at = tiles.lanes(j)
            St, dSt = states_ref[c, :, at], dstate[:, at]
            s, ds = St.astype(dtype), dSt.astype(dtype)
            x = x_ref[rows, at].astype(f32)
            dts = tiles.spread(dt, j)
            downs, ends, wholes = _decays(tiles, a, j)
            xs = (x * dts).astype(dtype)
            xs32 = xs.astype(f32)
            xe = (xs32 * ends).astype(dtype)
            dy = dy_ref[rows, at].astype(f32)
            dyd = tiles.apart(dy.astype(dtype))
            pairs = _pairs(scores, a, a_rows, tiles.heads(j), seen, dtype)
            dacross = dy * downs
            read = dacross * _nn(Cc, s)                 # dA o (C S^T)
            dacross = dacross.astype(dtype)
            dC = dC + _nt(dacross, s)
            dgaps = []
            for k, (decay, _) in enumerate(pairs):
                dpairs = _nt(dyd[k * Q:(k + 1) * Q], xs) * decay
                dscores = dscores + dpairs
                dgaps.append(dpairs * scores)
            dxs = _tn(jnp.concatenate([p for _, p in pairs], axis=0), dyd)
            pulled = _nn(Bc, ds) * ends                 # dxe o exp(a_Q - a_s)
            dxs = dxs + pulled
            dB = dB + _nt(xe, ds)
            dx_ref[rows, at] = (dxs * dts + skip_ref[:, at] * dy).astype(dx_ref.dtype)  # graftlint: disable=G004
            dskip_ref[:, at] += jnp.sum(dy * x, axis=0, keepdims=True)  # graftlint: disable=G004
            pulled = pulled * xs32                      # dxe o xe
            at_end = (jnp.sum(pulled, axis=0, keepdims=True)
                      + wholes * jnp.sum(dSt * St, axis=0, keepdims=True))
            for h, dgap, ddt_h, da_h, end_h in zip(
                    tiles.heads(j), dgaps, tiles.sums(dxs * x),
                    tiles.sums(read - pulled), tiles.sums(at_end)):
                cols[0, :, h:h + 1] = ddt_h  # graftlint: disable=G004
                cols[1, :, h:h + 1] = (  # graftlint: disable=G004
                    jnp.sum(dgap, axis=1, keepdims=True) + da_h
                    + jnp.where(last_row, end_h, 0.0))
                da_ref[h:h + 1, rows] = -jnp.sum(dgap, axis=0, keepdims=True)  # graftlint: disable=G004
            dstate[:, at] = wholes * dSt + _tn(Cc, dacross)  # graftlint: disable=G004
        ddt_ref[:, rows] = cols[0].T[:R]  # graftlint: disable=G004
        da_ref[:, rows] += cols[1].T[:R]  # graftlint: disable=G004
        dsd = dscores.astype(dtype)
        db_ref[rows, :] = (dB + _tn(dsd, Cc)).astype(db_ref.dtype)  # graftlint: disable=G004
        dc_ref[rows, :] = (dC + _nn(dsd, Bc)).astype(dc_ref.dtype)  # graftlint: disable=G004
        return carry

    jax.lax.fori_loop(0, steps, one_chunk, 0, unroll=True)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        ds0_ref[...] = dstate[...]


def _specs(R, P, N, Q, steps, reverse):
    """Block specs by kind over the grid (b, G, chunk blocks)."""
    from jax.experimental import pallas as pl

    cb = CHUNKS_PER_STEP

    def at(n):
        return steps - 1 - n if reverse else n

    return {
        # the layer's [b, L, H * P] and [b, L, G * N]: a group's lanes
        "heads": pl.BlockSpec((None, cb * Q, R * P), lambda b, g, n: (b, at(n), g)),
        "group": pl.BlockSpec((None, cb * Q, N), lambda b, g, n: (b, at(n), g)),
        # a row a head, [b, G, R, L]
        "rows": pl.BlockSpec((None, None, R, cb * Q),
                             lambda b, g, n: (b, g, 0, at(n))),
        # a value a lane: ``D`` [G, 1, R * P], its gradient [b, G, 1, R * P]
        "skip": pl.BlockSpec((None, 1, R * P), lambda b, g, n: (g, 0, 0)),
        "dskip": pl.BlockSpec((None, None, 1, R * P),
                              lambda b, g, n: (b, g, 0, 0)),
        # [b, G, N, R * P] and, a chunk, [b, n, G, N, R * P]
        "state": pl.BlockSpec((None, None, N, R * P), lambda b, g, n: (b, g, 0, 0)),
        "states": pl.BlockSpec((None, cb, None, N, R * P),
                               lambda b, g, n: (b, at(n), g, 0, 0)),
    }


def _rows(v, G):
    """[b, L, H] with a row a head: [b, G, R, L]."""
    b, L, H = v.shape
    return jnp.transpose(v.reshape(b, L, G, H // G), (0, 2, 3, 1))


def _heads_last(v):
    """Back: [b, G, R, L] -> [b, L, H]."""
    b, G, R, L = v.shape
    return jnp.transpose(v, (0, 3, 1, 2)).reshape(b, L, G * R)


# The kernels' wrappers are jitted, with what is static spelt out: Pallas
# traces a kernel's body at every ``pallas_call``, and under ``jit`` the
# layers of a model share one trace and, in one program, one lowering
# (PERF.md section 6, PR 33).
@functools.partial(jax.jit, static_argnames=(
    "heads", "groups", "chunk", "dtype", "save_states", "interpret"))
def chunk_fwd(x, dt, a, B, C, skip, S0, *, heads, groups, chunk, dtype,
              save_states, interpret=False):
    """The kernel ``ssd_chunk_fwd``. x: [b, L, H * P]; dt and a (its running
    sum times ``A`` inside each chunk): [b, L, H] float32; B, C:
    [b, L, G * N]; skip: [G, 1, R * P] float32, each head's ``D`` over its
    lanes; S0: [b, G, N, R * P] float32, the state transposed. Returns
    (y [b, L, H * P] float32 with ``D x`` added, the last state as ``S0``[,
    the state that enters every chunk, [b, L / chunk, G, N, R * P]
    float32])."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, L, HP = x.shape
    H, G, Q = heads, groups, chunk
    R, P, N = H // G, HP // H, B.shape[-1] // G
    steps = L // (Q * CHUNKS_PER_STEP)
    sp = _specs(R, P, N, Q, steps, reverse=False)
    out_shape = [jax.ShapeDtypeStruct((b, L, HP), jnp.float32),
                 jax.ShapeDtypeStruct((b, G, N, R * P), jnp.float32)]
    out_specs = [sp["heads"], sp["state"]]
    if save_states:
        out_shape.append(
            jax.ShapeDtypeStruct((b, L // Q, G, N, R * P), jnp.float32))
        out_specs.append(sp["states"])
    operands = (x, _rows(dt, G), _rows(a, G), B, C, skip, S0)
    moved = sum(v.size * v.dtype.itemsize for v in (*operands, *out_shape))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=Q, head_dim=P, dtype=dtype,
                          save_states=save_states),
        grid=(b, G, steps),
        in_specs=[sp["heads"], sp["rows"], sp["rows"], sp["group"],
                  sp["group"], sp["skip"], sp["state"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((N, R * P), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=2 * b * L * (G * Q * N + H * P * (Q + 2 * N)),
            bytes_accessed=moved, transcendentals=b * L * H * (Q + 2)),
        name="ssd_chunk_fwd", **_params(interpret),
    )(*operands)


@functools.partial(jax.jit, static_argnames=(
    "heads", "groups", "chunk", "dtype", "interpret"))
def chunk_bwd(x, dt, a, B, C, skip, states, dy, dST, *, heads, groups, chunk,
              dtype, interpret=False):
    """The kernel ``ssd_chunk_bwd``: the cotangents of :func:`chunk_fwd`'s
    seven operands at (dy, dST); those of ``dt`` (through the input ``dt x``),
    ``a`` (through the decays) and ``skip`` float32, the others in their
    operand's dtype."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, L, HP = x.shape
    H, G, Q = heads, groups, chunk
    R, P, N = H // G, HP // H, B.shape[-1] // G
    steps = L // (Q * CHUNKS_PER_STEP)
    sp = _specs(R, P, N, Q, steps, reverse=True)
    operands = (x, _rows(dt, G), _rows(a, G), B, C, skip, states, dy, dST)
    rows = jax.ShapeDtypeStruct((b, G, R, L), jnp.float32)
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype), rows, rows,
                 jax.ShapeDtypeStruct(B.shape, B.dtype),
                 jax.ShapeDtypeStruct(C.shape, C.dtype),
                 jax.ShapeDtypeStruct((b, G, 1, R * P), jnp.float32),
                 jax.ShapeDtypeStruct((b, G, N, R * P), jnp.float32)]
    moved = sum(v.size * v.dtype.itemsize for v in (*operands, *out_shape))
    dx, ddt, da, dB, dC, dskip, dS0 = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=Q, head_dim=P, dtype=dtype),
        grid=(b, G, steps),
        in_specs=[sp["heads"], sp["rows"], sp["rows"], sp["group"],
                  sp["group"], sp["skip"], sp["states"], sp["heads"],
                  sp["state"]],
        out_specs=[sp["heads"], sp["rows"], sp["rows"], sp["group"],
                   sp["group"], sp["dskip"], sp["state"]],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((N, R * P), jnp.float32),
                        pltpu.VMEM((2, Q, _LANES), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=2 * b * L * (3 * G * Q * N + H * P * (3 * Q + 6 * N)),
            bytes_accessed=moved, transcendentals=b * L * H * (Q + 2)),
        name="ssd_chunk_bwd", **_params(interpret),
    )(*operands)
    return (dx, _heads_last(ddt), _heads_last(da), dB, dC, dskip.sum(0),
            dS0)


# The backward rule is traced under the name stack of the forward call, so
# both kernels land in the ``ssd_chunk`` scope that the layer opens.
@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _scan_fused(x, dt, a, B, C, skip, S0, sizes, interpret):
    return chunk_fwd(x, dt, a, B, C, skip, S0, save_states=False,
                     interpret=interpret, **dict(sizes))


def _scan_fused_fwd(x, dt, a, B, C, skip, S0, sizes, interpret):
    y, ST, states = chunk_fwd(x, dt, a, B, C, skip, S0, save_states=True,
                              interpret=interpret, **dict(sizes))
    return (y, ST), (x, dt, a, B, C, skip, states)


def _scan_fused_bwd(sizes, interpret, saved, cotangents):
    return chunk_bwd(*saved, *cotangents, interpret=interpret, **dict(sizes))


_scan_fused.defvjp(_scan_fused_fwd, _scan_fused_bwd)


def ssd_fused(x, dt, A, B, C, chunk: int, dtype=None, S0=None, skip=None,
              interpret=False):
    """:func:`ssd_chunked` as the kernels ``ssd_chunk_fwd`` and, for the
    backward, ``ssd_chunk_bwd``; operands and results as there, sizes as
    :func:`scan_path` asks. The running sum of ``dt A`` inside each chunk is
    made here, so that autodiff takes its gradient back to ``dt`` and ``A``
    and the kernels hold no reduction over heads to ``A``."""
    b, L, H, P = x.shape
    G, N = B.shape[-2:]
    f32 = jnp.float32
    dt = dt.astype(f32)
    a = jnp.cumsum((dt * A.astype(f32)).reshape(b, L // chunk, chunk, H),
                   axis=2).reshape(b, L, H)
    if S0 is None:
        S0 = jnp.zeros((b, H, P, N), f32)
    # the state transposed and a group's heads side by side: [b, G, N, R * P]
    S0 = jnp.transpose(S0.astype(f32).reshape(b, G, H // G, P, N),
                       (0, 1, 4, 2, 3)).reshape(b, G, N, H // G * P)
    skip = jnp.zeros((H,), f32) if skip is None else skip.astype(f32)
    skip = jnp.repeat(skip, P).reshape(G, 1, H // G * P)
    sizes = (("heads", H), ("groups", G), ("chunk", chunk),
             ("dtype", jnp.dtype(dtype or x.dtype)))
    y, ST = _scan_fused(x.reshape(b, L, H * P), dt, a, B.reshape(b, L, G * N),
                        C.reshape(b, L, G * N), skip, S0, sizes, interpret)
    ST = jnp.transpose(ST.reshape(b, G, N, H // G, P), (0, 1, 3, 4, 2))
    return y.reshape(b, L, H, P), ST.reshape(b, H, P, N)
