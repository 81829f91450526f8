"""Kimi delta attention (KDA): a gated delta-rule linear-attention mixer, in
its chunkwise-parallel form, with the part that is sequential over chunks as
a Pallas kernel on a TPU, forward and backward.

**The recurrence** (the definition; ``benchmark/reference/ling3.py`` scans it
token by token). Per head, a state ``S`` in ``R^{dk x dv}``, zero at the start
of a sequence; for token ``t`` with a query ``q_t`` (already scaled), a key
``k_t``, a value ``v_t``, a per-channel log decay ``g_t <= 0`` in ``R^dk``
and a write strength ``beta_t`` in (0, 1)::

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

**The chunked form** (:func:`kda_chunked`, the normal path). Over a chunk of
``C`` tokens with ``G_t`` the running sum of ``g`` inside the chunk and ``S``
the state that enters it::

    A   = tril(beta_t (k_t o e^{G_t}) . (k_i o e^{-G_i}), -1)      [C, C]
    Aqk = tril((q_t o e^{G_t}) . (k_i o e^{-G_i}))                 [C, C]
    T = (I + A)^-1,  W = T (beta k o e^G),  U = T (beta v)
    U~ = U - W S
    O  = (q o e^G) S + Aqk U~
    S' = Diag(e^{G_C}) S + (k o e^{G_C - G})^T U~

``e^{-G_i}`` is never formed: with ``g`` bounded below by -5 a token, ``G``
reaches ``-5 C`` and ``exp(320)`` overflows float32. The decay ratios
``e^{G_t - G_i}``, ``t >= i``, are taken against a reference inside
sub-blocks of ``SUB`` = 16 tokens: with ``R_I`` the running sum at the middle
row of sub-block ``I``, its rows carry ``e^{G_t - R_I}`` and the columns they
see ``e^{R_I - G_i}``, both within ``[e^-35, e^40]`` inside the sub-block
(the columns before it at most 1). The reference sits in the middle and not
at the first row, where the factors would span ``e^-75`` to ``e^75`` and
still fit: a cotangent of 1e-6 times ``e^-75`` is below float32's smallest
normal number, a TPU flushes it, and the gradient of the sub-block's last
rows is lost (measured on the CPU: the log decay's gradient off by four times
its norm with the decay at its bound). Everything outside the sequential part is plain
``jax.numpy`` that autodiff differentiates; the triangular inverse has its
own rule (:func:`unit_lower_inverse`).

**The sequential part** (:func:`chunk_scan`) takes, per head and chunk,
``Qg = q o e^G``, ``Kd = k o e^{G_C - G}``, ``W``, ``U``, ``Aqk`` and
``d = e^{G_C}`` and carries the state over the chunks. Its plain form is a
``lax.scan`` (the definition, and what runs off the TPU or under a mesh of
several devices); on one TPU device it is the kernels ``kda_chunk_fwd`` and
``kda_chunk_bwd`` under a ``custom_vjp``: the state lies in VMEM across the
chunk axis of the grid, transposed (``[dv, dk]``) so that the decay is a row
that broadcasts over sublanes; the forward saves the state at every chunk
boundary in float32 for the backward, which walks the chunks in reverse.
:func:`scan_path` says which runs; nothing else chooses.

State, decay and sums in float32; the products' inputs in the dtype the
caller computes in (bfloat16 in training), accumulated in float32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from .context import get_mesh_context, get_seq_context

# tokens in a chunk, from chip runs at 32, 64 and 128 (PERF.md section 6, PR
# 32): 32 is 6% faster for the layer and doubles the saved states, 128 slower
KDA_CHUNK = 64
# tokens in a sub-block of decay references: 8 steps of a log decay bounded by
# -5 to either side of the middle row stay within exp(+-40), well inside
# float32 (and bfloat16, which has the same exponent range)
SUB = 16
# chunks one grid step of the kernels walks (a block of CHUNKS_PER_STEP x
# chunk tokens of every operand), and heads it walks side by side (their
# recurrences are independent, so their products interleave on the MXUs)
CHUNKS_PER_STEP = 8
HEADS_PER_STEP = 4
_LANES = 128
HIGHEST = jax.lax.Precision.HIGHEST


def scan_path(heads: int, dk: int, dv: int, seq_len: int, chunk: int,
              mesh: Optional[Mesh], seq_sharded: bool = False) -> str:
    """``"fused"`` where the sequential part runs as the Pallas kernels,
    ``"xla"`` where the plain scan and its autodiff run: another back-end
    than a TPU, a mesh of several devices (Mosaic kernels are not partitioned
    by pjit and no wrapper shards these yet), sequence sharding, head sizes
    that do not fill whole lanes, heads or chunks that do not fill the
    kernels' blocks. ``mesh`` is the ambient one, or the trainer's."""
    n_chunks = seq_len // max(chunk, 1)
    fused = (not seq_sharded
             and dk % _LANES == 0 and dv % _LANES == 0
             and chunk % SUB == 0
             and heads % HEADS_PER_STEP == 0
             and n_chunks % CHUNKS_PER_STEP == 0
             and (mesh is None or mesh.size == 1)
             and jax.devices()[0].platform == "tpu")
    return "fused" if fused else "xla"


# ---------------------------------------------------------------------------
# the triangular inverse
# ---------------------------------------------------------------------------


def _blockdiag_inverse(A, sub):
    """The inverses of the ``sub x sub`` diagonal blocks of ``I + A`` (A
    strictly lower triangular, [N, C, C]) as one block-diagonal [N, C, C]:
    forward substitution, row by row, over all blocks at once. The blocks
    are moved batch-minor ([sub, sub, N * C/sub]) so that every step is an
    elementwise pass with the batch in the lanes."""
    N, C, _ = A.shape
    nb = C // sub
    blocks = A.reshape(N, nb, sub, nb, sub)
    diag = jnp.stack([blocks[:, i, :, i, :] for i in range(nb)], axis=1)
    a = jnp.moveaxis(diag.reshape(N * nb, sub, sub), 0, -1)  # [t, i, n]
    eye = jnp.eye(sub, dtype=A.dtype)
    rows = []
    for t in range(sub):
        row = jnp.broadcast_to(eye[t][:, None], (sub, a.shape[-1]))
        for i in range(t):
            row = row - a[t, i] * rows[i]
        rows.append(row)
    inv = jnp.moveaxis(jnp.stack(rows), -1, 0).reshape(N, nb, sub, sub)
    full = inv[:, :, :, None, :] * jnp.eye(nb, dtype=A.dtype)[:, None, :, None]
    return full.reshape(N, C, C)


def _inverse_plain(A):
    """``(I + A)^-1`` for strictly lower triangular ``A`` [..., C, C],
    float32. With ``D`` the inverse of the block diagonal (``SUB`` blocks, by
    substitution) and ``M = D (A - its diagonal blocks)``, strictly block
    lower triangular and so nilpotent of the block count:
    ``(I + A)^-1 = (I + M)^-1 D = (I - M)(I + M^2)(I + M^4) ... D``."""
    shape = A.shape
    C = shape[-1]
    A = A.reshape(-1, C, C).astype(jnp.float32)
    sub = SUB if C % SUB == 0 else C
    D = _blockdiag_inverse(A, sub)
    nb = C // sub
    if nb == 1:
        return D.reshape(shape)
    mm = functools.partial(jnp.matmul, precision=HIGHEST)
    block = jnp.arange(C) // sub
    off = jnp.where(block[:, None] > block[None, :], A, 0.0)
    eye = jnp.eye(C, dtype=jnp.float32)
    M = mm(D, off)
    P = eye - M
    power, span = M, 2
    while span < nb:  # M^span != 0 only while span < nb
        power = mm(power, power)
        P = mm(P, eye + power)
        span *= 2
    return mm(P, D).reshape(shape)


@jax.custom_vjp
def unit_lower_inverse(A):
    """``(I + A)^-1`` for strictly lower triangular ``A`` [..., C, C]. The
    rule: ``dA = -tril(T^T dT T^T, -1)``, two products, whatever computed
    ``T``."""
    return _inverse_plain(A)


def _inverse_fwd(A):
    T = _inverse_plain(A)
    return T, T


def _inverse_bwd(T, dT):
    Tt = jnp.swapaxes(T, -1, -2)
    dA = -jnp.matmul(jnp.matmul(Tt, dT.astype(T.dtype), precision=HIGHEST), Tt,
                     precision=HIGHEST)
    C = T.shape[-1]
    return (jnp.where(jnp.tril(jnp.ones((C, C), bool), -1), dA, 0.0),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


# ---------------------------------------------------------------------------
# the sequential part: plain
# ---------------------------------------------------------------------------


def _scan_plain(Qg, Kd, W, U, Aqk, d, S0):
    """The chunks in turn. Qg, Kd, W: [B, H, N, C, dk]; U: [B, H, N, C, dv];
    Aqk: [B, H, N, C, C]; d: [B, H, N, dk] float32; S0: [B, H, dv, dk]
    float32, the state transposed. Returns (O [B, H, N, C, dv] in U's dtype,
    the final state)."""
    dtype = U.dtype

    def step(St, xs):
        qg, kd, w, u, aqk, dn = xs
        s = St.astype(dtype)
        ut = (u.astype(jnp.float32) - jnp.einsum(
            "bhck,bhvk->bhcv", w, s, preferred_element_type=jnp.float32))
        utd = ut.astype(dtype)
        o = (jnp.einsum("bhck,bhvk->bhcv", qg, s,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhci,bhiv->bhcv", aqk, utd,
                          preferred_element_type=jnp.float32))
        St = dn[:, :, None, :] * St + jnp.einsum(
            "bhcv,bhck->bhvk", utd, kd, preferred_element_type=jnp.float32)
        return St, o.astype(dtype)

    xs = tuple(jnp.moveaxis(a, 2, 0) for a in (Qg, Kd, W, U, Aqk, d))
    St, O = jax.lax.scan(step, S0.astype(jnp.float32), xs)
    return jnp.moveaxis(O, 0, 2), St


# ---------------------------------------------------------------------------
# the sequential part: kernels
# ---------------------------------------------------------------------------


def _nt(a, b):
    """a [m, k] . b [n, k]^T -> [m, n], float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _tn(a, b):
    """a [k, m]^T . b [k, n] -> [m, n], float32."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _nn(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _fwd_kernel(qg_ref, kd_ref, w_ref, u_ref, aqk_ref, d_ref, s0_ref,
                o_ref, sT_ref, *rest, chunk, save_states):
    """One (batch, head block, chunk block) step. Blocks: ``qg``, ``kd``,
    ``w`` [hb, cb * C, dk]; ``u``, ``o`` [hb, cb * C, dv]; ``aqk``
    [hb, cb * C, C]; ``d`` [hb, cb, dk]; ``s0``, ``sT`` [hb, dv, dk];
    ``states`` (where saved) [hb, cb, dv, dk]: the state that enters each
    chunk. The scratch holds the running state of the block's heads."""
    from jax.experimental import pallas as pl

    if save_states:
        states_ref, state = rest
    else:
        (state,) = rest
    hb, cb = d_ref.shape[0], d_ref.shape[1]
    dtype = u_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = s0_ref[...].astype(jnp.float32)

    for c in range(cb):
        rows = pl.ds(c * chunk, chunk)
        for h in range(hb):
            St = state[h]
            if save_states:
                states_ref[h, c] = St
            s = St.astype(dtype)
            ut = u_ref[h, rows, :].astype(jnp.float32) - _nt(w_ref[h, rows, :], s)
            utd = ut.astype(dtype)
            o = _nt(qg_ref[h, rows, :], s) + _nn(aqk_ref[h, rows, :], utd)
            o_ref[h, rows, :] = o.astype(o_ref.dtype)
            state[h] = (d_ref[h, c:c + 1, :] * St
                        + _tn(utd, kd_ref[h, rows, :]))

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        sT_ref[...] = state[...]


def _bwd_kernel(qg_ref, kd_ref, w_ref, u_ref, aqk_ref, d_ref, states_ref,
                do_ref, dsT_ref,
                dqg_ref, dkd_ref, dw_ref, du_ref, daqk_ref, dd_ref, ds0_ref,
                dstate, *, chunk):
    """The chunk blocks in reverse (the index maps turn the chunk axis
    round). With ``S`` the state that entered a chunk (saved), ``dS'`` the
    cotangent of the state that left it (carried) and ``U~ = U - W S``::

        dU~ = Aqk^T dO + Kd dS'        dAqk = dO U~^T
        dQg = dO S                     dKd  = U~ dS'
        dU  = dU~                      dW   = -dU~ S
        dd  = sum_v S o dS'
        dS  = Qg^T dO + d o dS' - W^T dU~

    all on the transposed state ([dv, dk])."""
    from jax.experimental import pallas as pl

    hb, cb = d_ref.shape[0], d_ref.shape[1]
    dtype = u_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = dsT_ref[...].astype(jnp.float32)

    for c in reversed(range(cb)):
        rows = pl.ds(c * chunk, chunk)
        for h in range(hb):
            St = states_ref[h, c]
            s = St.astype(dtype)
            dSt = dstate[h]
            ds = dSt.astype(dtype)
            do = do_ref[h, rows, :]
            w, kd = w_ref[h, rows, :], kd_ref[h, rows, :]
            ut = u_ref[h, rows, :].astype(jnp.float32) - _nt(w, s)
            utd = ut.astype(dtype)
            dut = _tn(aqk_ref[h, rows, :], do) + _nt(kd, ds)
            dutd = dut.astype(dtype)
            du_ref[h, rows, :] = dut.astype(du_ref.dtype)
            daqk_ref[h, rows, :] = _nt(do, utd).astype(daqk_ref.dtype)
            dqg_ref[h, rows, :] = _nn(do, s).astype(dqg_ref.dtype)
            dkd_ref[h, rows, :] = _nn(utd, ds).astype(dkd_ref.dtype)
            dw_ref[h, rows, :] = (-_nn(dutd, s)).astype(dw_ref.dtype)
            dd_ref[h, c:c + 1, :] = jnp.sum(St * dSt, axis=0, keepdims=True)
            dstate[h] = (_tn(do, qg_ref[h, rows, :])
                         + d_ref[h, c:c + 1, :] * dSt - _tn(dutd, w))

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        ds0_ref[...] = dstate[...]


def _specs(B, H, N, C, dk, dv, reverse):
    """Block specs by kind over the grid (B, H / hb, N / cb)."""
    from jax.experimental import pallas as pl

    hb, cb = HEADS_PER_STEP, CHUNKS_PER_STEP
    steps = N // cb

    def at(n):
        return steps - 1 - n if reverse else n

    def rows(width):  # [B, H, N * C, width]
        return pl.BlockSpec((None, hb, cb * C, width),
                            lambda b, h, n: (b, h, at(n), 0))

    return {
        "dk": rows(dk), "dv": rows(dv), "C": rows(C),
        "decay": pl.BlockSpec((None, hb, cb, dk),
                              lambda b, h, n: (b, h, at(n), 0)),
        "state": pl.BlockSpec((None, hb, dv, dk), lambda b, h, n: (b, h, 0, 0)),
        "states": pl.BlockSpec((None, hb, cb, dv, dk),
                               lambda b, h, n: (b, h, at(n), 0, 0)),
    }


def _flat(a):
    """[B, H, N, C, x] -> [B, H, N * C, x]."""
    B, H, N, C, x = a.shape
    return a.reshape(B, H, N * C, x)


def _params(interpret):
    from jax.experimental.pallas import tpu as pltpu

    if interpret:
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=64 << 20)}


def chunk_fwd(Qg, Kd, W, U, Aqk, d, S0, save_states, interpret=False):
    """The kernel ``kda_chunk_fwd``: (O, final state[, the state entering
    every chunk [B, H, N, dv, dk] float32])."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, N, C, dk = Qg.shape
    dv = U.shape[-1]
    sp = _specs(B, H, N, C, dk, dv, reverse=False)
    out_shape = [jax.ShapeDtypeStruct((B, H, N * C, dv), U.dtype),
                 jax.ShapeDtypeStruct((B, H, dv, dk), jnp.float32)]
    out_specs = [sp["dv"], sp["state"]]
    if save_states:
        out_shape.append(jax.ShapeDtypeStruct((B, H, N, dv, dk), jnp.float32))
        out_specs.append(sp["states"])
    operands = tuple(map(_flat, (Qg, Kd, W, U, Aqk))) + (d, S0)
    moved = sum(a.size * a.dtype.itemsize for a in (*operands, *out_shape))
    outs = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=C, save_states=save_states),
        grid=(B, H // HEADS_PER_STEP, N // CHUNKS_PER_STEP),
        in_specs=[sp["dk"], sp["dk"], sp["dk"], sp["dv"], sp["C"],
                  sp["decay"], sp["state"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((HEADS_PER_STEP, dv, dk), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=2 * B * H * N * C * (3 * dk * dv + C * dv),
            bytes_accessed=moved, transcendentals=0),
        name="kda_chunk_fwd", **_params(interpret),
    )(*operands)
    O = outs[0].reshape(B, H, N, C, dv)
    return (O, *outs[1:])


def chunk_bwd(Qg, Kd, W, U, Aqk, d, states, dO, dST, interpret=False):
    """The kernel ``kda_chunk_bwd``: the cotangents of ``chunk_scan``'s seven
    operands at (dO, dST)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, N, C, dk = Qg.shape
    dv = U.shape[-1]
    sp = _specs(B, H, N, C, dk, dv, reverse=True)
    flat = tuple(map(_flat, (Qg, Kd, W, U, Aqk)))
    operands = flat + (d, states, _flat(dO), dST)
    out_shape = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in flat] + [
        jax.ShapeDtypeStruct(d.shape, jnp.float32),
        jax.ShapeDtypeStruct((B, H, dv, dk), jnp.float32)]
    moved = sum(a.size * a.dtype.itemsize for a in (*operands, *out_shape))
    outs = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=C),
        grid=(B, H // HEADS_PER_STEP, N // CHUNKS_PER_STEP),
        in_specs=[sp["dk"], sp["dk"], sp["dk"], sp["dv"], sp["C"],
                  sp["decay"], sp["states"], sp["dv"], sp["state"]],
        out_specs=[sp["dk"], sp["dk"], sp["dk"], sp["dv"], sp["C"],
                   sp["decay"], sp["state"]],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((HEADS_PER_STEP, dv, dk), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=2 * B * H * N * C * (8 * dk * dv + 2 * C * dv),
            bytes_accessed=moved, transcendentals=0),
        name="kda_chunk_bwd", **_params(interpret),
    )(*operands)
    shapes = (Qg, Kd, W, U, Aqk)
    return tuple(o.reshape(a.shape) for o, a in zip(outs[:5], shapes)) + (
        outs[5], outs[6])


# The backward rule is traced under the name stack of the forward call, so
# both kernels land in the ``kda_chunk`` scope that the layer opens.
@jax.custom_vjp
def _scan_fused(Qg, Kd, W, U, Aqk, d, S0):
    O, ST = chunk_fwd(Qg, Kd, W, U, Aqk, d, S0, save_states=False)
    return O, ST


def _scan_fused_fwd(Qg, Kd, W, U, Aqk, d, S0):
    O, ST, states = chunk_fwd(Qg, Kd, W, U, Aqk, d, S0, save_states=True)
    return (O, ST), (Qg, Kd, W, U, Aqk, d, states)


def _scan_fused_bwd(saved, cotangents):
    dO, dST = cotangents
    return chunk_bwd(*saved, dO, dST)


_scan_fused.defvjp(_scan_fused_fwd, _scan_fused_bwd)


def chunk_scan(Qg, Kd, W, U, Aqk, d, S0):
    """The part of the chunked form that is sequential over chunks, by the
    path :func:`scan_path` names. Operands as :func:`_scan_plain`."""
    B, H, N, C, dk = Qg.shape
    path = scan_path(H, dk, U.shape[-1], N * C, C, get_mesh_context(),
                     get_seq_context() is not None)
    return (_scan_fused if path == "fused" else _scan_plain)(
        Qg, Kd, W, U, Aqk, d, S0)


# ---------------------------------------------------------------------------
# the chunked form
# ---------------------------------------------------------------------------


def kda_chunked(q, k, v, g, beta, chunk: int = KDA_CHUNK, dtype=None,
                scan=None):
    """q, k: [B, T, H, dk] (``q`` scaled, both as the layer normalised
    them); v: [B, T, H, dv]; g: [B, T, H, dk] float32 log decay (<= 0);
    beta: [B, T, H] float32. Returns (o [B, T, H, dv] in ``dtype``, the final
    state [B, H, dk, dv] float32). ``dtype`` (default ``v``'s) is what the
    products' inputs are rounded to. ``T`` must be a multiple of ``chunk`` (or
    lie below it), ``chunk`` of its sub-block (``SUB``, or the chunk itself).
    ``scan`` replaces :func:`chunk_scan` (the tests hand in the kernels in
    interpret mode)."""
    B, T, H, dk = k.shape
    dv = v.shape[-1]
    dtype = dtype or v.dtype
    C = min(int(chunk), T)  # a sequence shorter than a chunk is one chunk
    if T % C:
        raise ValueError(
            f"a KDA layer takes sequences that are whole chunks: seq_len {T} "
            f"is no multiple of the chunk {C}")
    sub = SUB if C % SUB == 0 else C
    N, nb = T // C, C // sub
    f32 = jnp.float32

    def chunks(a):  # [B, T, H, x] -> [B, H, N, C, x]
        return jnp.moveaxis(a.reshape(B, N, C, H, -1), 3, 1)

    q32, k32, v32 = (chunks(a).astype(f32) for a in (q, k, v))
    b32 = chunks(beta[..., None].astype(f32))              # [B, H, N, C, 1]
    # the running sum as a product with a triangle of ones, not a cumsum
    # (a reduce-window on a TPU): the layer's chunked form, forward and
    # backward, went from 51.3 to 47.0 ms on the v5e (PERF.md, PR 32)
    G = jnp.einsum("ts,bhnsk->bhntk", jnp.tril(jnp.ones((C, C), f32)),
                   chunks(g.astype(f32)), precision=HIGHEST)   # [B, H, N, C, dk]
    GC = G[:, :, :, -1]                                    # [B, H, N, dk]
    # decay references: the running sum at each sub-block's middle row (no
    # gradient: the ratios e^{G_t - G_i} do not depend on the reference)
    Gb = G.reshape(B, H, N, nb, sub, dk)
    R = jax.lax.stop_gradient(Gb[:, :, :, :, sub // 2])    # [B, H, N, nb, dk]
    row_decay = jnp.exp(Gb - R[:, :, :, :, None])          # in [e^-35, e^40]
    qr = (q32.reshape(Gb.shape) * row_decay).astype(dtype)
    kr = (k32.reshape(Gb.shape) * row_decay).astype(dtype)
    pairs = functools.partial(jnp.einsum, "bhntk,bhnik->bhnti",
                              preferred_element_type=f32)
    aqk_rows, a_rows = [], []
    for i in range(nb):
        # the columns a row of sub-block i sees, up to the block's last row:
        # e^{R_i - G_j}, in [e^-35, e^40] inside the block, at most 1 before
        hi = (i + 1) * sub
        kc = (k32[:, :, :, :hi] * jnp.exp(
            R[:, :, :, i, None] - G[:, :, :, :hi])).astype(dtype)
        pad = ((0, 0),) * 4 + ((0, C - hi),)
        aqk_rows.append(jnp.pad(pairs(qr[:, :, :, i], kc), pad))
        a_rows.append(jnp.pad(pairs(kr[:, :, :, i], kc), pad))
    col = jnp.arange(C)
    lower = col[:, None] >= col[None, :]                   # [C, C], t >= i
    Aqk = jnp.where(lower, jnp.concatenate(aqk_rows, axis=3), 0.0)
    A = jnp.where(lower & (col[:, None] != col[None, :]),
                  jnp.concatenate(a_rows, axis=3), 0.0) * b32
    Tm = unit_lower_inverse(A).astype(dtype)               # [B, H, N, C, C]
    eG = jnp.exp(G)
    W = jnp.einsum("bhnti,bhnik->bhntk", Tm, (b32 * k32 * eG).astype(dtype),
                   preferred_element_type=f32).astype(dtype)
    U = jnp.einsum("bhnti,bhniv->bhntv", Tm, (b32 * v32).astype(dtype),
                   preferred_element_type=f32).astype(dtype)
    Qg = (q32 * eG).astype(dtype)
    Kd = (k32 * jnp.exp(GC[:, :, :, None, :] - G)).astype(dtype)
    d = jnp.exp(GC)
    S0 = jnp.zeros((B, H, dv, dk), f32)  # a sequence starts from no state
    O, ST = (scan or chunk_scan)(Qg, Kd, W, U, Aqk.astype(dtype), d, S0)
    o = jnp.moveaxis(O, 1, 3).reshape(B, T, H, dv)
    return o, jnp.swapaxes(ST, -1, -2)
