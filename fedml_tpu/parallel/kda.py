"""Kimi delta attention (KDA): a gated delta-rule linear-attention mixer, in
its chunkwise-parallel form, on a TPU as four Pallas kernels: the part that is
parallel over chunks and the part that is sequential over them, each forward
and backward.

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
its norm with the decay at its bound).

**The part parallel over chunks** (the preparation: everything between the
layer's tensors and ``Qg = q o e^G``, ``Kd = k o e^{G_C - G}``, ``W``, ``U``,
``Aqk`` and ``d = e^{G_C}``). :func:`prepare_plain` is its definition, plain
``jax.numpy`` that autodiff differentiates, with the triangular inverse under
its own rule (:func:`unit_lower_inverse`). On one TPU device it is the
kernels ``kda_intra_fwd`` and ``kda_intra_bwd`` under a ``custom_vjp``
(:func:`prepare_fused`): a grid step takes ``HEADS_PER_STEP`` heads of
``CHUNKS_PER_STEP`` chunks straight from the layer's ``[B, T, H d]``
tensors, keeps a chunk's running sum, decay factors, pair matrices and
inverse in VMEM, and writes what the scan takes; the backward, written by
hand, keeps nothing but the inputs and forms all of that again. In a kernel
a float32 product is spelt in bfloat16 pieces (:func:`_pieces`): the running
sum in three passes (the triangle of ones is exact), the inverse in 23
(:func:`_inverse_tiles`) where ten products at ``highest`` take 60.

**The sequential part** (:func:`chunk_scan`) carries the state over the
chunks. Its plain form is a ``lax.scan`` (the definition); on one TPU device
it is the kernels ``kda_chunk_fwd`` and ``kda_chunk_bwd`` under a
``custom_vjp``: the state lies in VMEM across the chunk axis of the grid,
transposed (``[dv, dk]``) so that the decay is a row that broadcasts over
sublanes; the forward saves the state at every chunk boundary in float32 for
the backward, which walks the chunks in reverse.

:func:`scan_path` says which form runs, for both parts at once: the kernels
(``fused``) or the plain forms (``xla``: off the TPU, under a mesh of several
devices, sequence sharding, shapes that do not fill the blocks). Nothing else
chooses.

State, decay, sums and the inverse in float32; the products' inputs in the
dtype the caller computes in (bfloat16 in training), accumulated in float32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from .context import get_mesh_context, get_seq_context, one_tpu_device

# tokens in a chunk. 64 is what ``benchmark/configs/ling3.0_flash_ep64_l7.json``
# states and what the cell's FLOP and byte counts are taken at. With the
# preparation in kernels the layer alone is faster at 128 (forward + backward
# 11.2 ms against 13.0 at 64 and 16.8 at 32 on the v5e; PERF.md section 6, PR
# 33): choosing it again takes the ``benchmark`` edit that states it
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
    """``"fused"`` where both parts of the chunked form run as the Pallas
    kernels, ``"xla"`` where the plain forms and their autodiff run: another
    back-end than a TPU, a mesh of several devices (Mosaic kernels are not
    partitioned by pjit and no wrapper shards these yet), sequence sharding,
    head sizes that do not fill whole lanes, heads or chunks that do not fill
    the kernels' blocks. ``mesh`` is the ambient one, or the trainer's."""
    n_chunks = seq_len // max(chunk, 1)
    fused = (not seq_sharded
             and dk % _LANES == 0 and dv % _LANES == 0
             and chunk % SUB == 0
             and heads % HEADS_PER_STEP == 0
             and n_chunks % CHUNKS_PER_STEP == 0
             and one_tpu_device(mesh))
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


def _params(interpret, chunk_axis="arbitrary"):
    """How a kernel's call is built; the chunk axis of the grid carries the
    state in the scan kernels and nothing in the preparation's."""
    from jax.experimental.pallas import tpu as pltpu

    if interpret:
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", chunk_axis),
        vmem_limit_bytes=64 << 20)}


# The kernels' wrappers are jitted: Pallas traces a kernel's body at every
# ``pallas_call``, and a stack of such layers would trace and lower the same
# four bodies once a layer and pass; under ``jit`` the layers share one trace
# and, in one program, one lowering (PERF.md section 6, PR 33).
@functools.partial(jax.jit, static_argnames=("save_states", "interpret"))
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


@functools.partial(jax.jit, static_argnames=("interpret",))
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
# the intra-chunk preparation: plain
# ---------------------------------------------------------------------------


def prepare_plain(q, k, v, g, beta, chunk, dtype):
    """What :func:`chunk_scan` takes, from the layer's tensors: ``Qg``,
    ``Kd``, ``W`` [B, H, N, C, dk], ``U`` [B, H, N, C, dv], ``Aqk``
    [B, H, N, C, C] in ``dtype`` and ``d`` [B, H, N, dk] float32. Plain
    ``jax.numpy`` that autodiff differentiates: the definition, and what the
    kernels ``kda_intra_fwd`` / ``kda_intra_bwd`` are tested against.
    Operands as :func:`kda_chunked`, ``chunk`` a divisor of the sequence."""
    B, T, H, dk = k.shape
    C = chunk
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
    return Qg, Kd, W, U, Aqk.astype(dtype), jnp.exp(GC)


# ---------------------------------------------------------------------------
# the intra-chunk preparation: kernels
# ---------------------------------------------------------------------------


def _grid2(C):
    """Row and column index of a [C, C] tile."""
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    return row, col


def _pieces(x, n=3):
    """A float32 tile as the sum of ``n`` bfloat16 tiles, largest first:
    exact to ``8 n`` bits of mantissa."""
    out = [x.astype(jnp.bfloat16)]
    for _ in range(n - 1):
        x = x - out[-1].astype(jnp.float32)
        out.append(x.astype(jnp.bfloat16))
    return out


def _mm_pieces(a, b, dims=(((1,), (0,)), ((), ()))):
    """The product of two tiles given in pieces, every pair of pieces whose
    ranks sum below the number of pieces: three pieces a side are the six
    passes of a float32 product at ``highest``."""
    total, order = None, max(len(a), len(b))
    for i, x in enumerate(a):
        for j, y in enumerate(b[:order - i]):
            term = jax.lax.dot_general(x, y, dims,
                                       preferred_element_type=jnp.float32)
            total = term if total is None else total + term
    return total


def _inverse_tiles(As):
    """``(I + A)^-1`` of each strictly lower triangular [C, C] float32 tile
    (``C`` a power of two), in a kernel, to float32's last bits. First in
    one bfloat16 pass a product: the inverses of the diagonal blocks of ``s``
    rows give those of ``2 s`` rows, ``[[Xa, 0], [-Xb L Xa, Xb]]`` with ``L``
    the block below the diagonal, all pairs at once as products of
    block-diagonal tiles (``s`` = 1 is the identity, ``s`` = 2 needs no
    product). Then two Newton steps ``X <- X + X (I - (I + A) X)``, which
    square the error (the residual is strictly lower triangular, so they
    end for any tile): the first takes its residual in three passes and adds
    it in one, the second in six and three. 23 passes where ten products at
    ``highest`` take 60. The tiles advance step by step together: their
    chains of products are independent, and side by side they fill the
    matrix units."""
    C = As[0].shape[-1]
    row, col = _grid2(C)
    eye = (row == col).astype(jnp.float32)
    Xs = [eye - jnp.where((row >> 1) == (col >> 1), A, 0.0) for A in As]
    shift = 1
    while (2 << shift) <= C:
        below = ((row >> (shift + 1)) == (col >> (shift + 1))) & (
            (row >> shift) > (col >> shift))
        xs = [_pieces(X, 1) for X in Xs]
        Ys = [_mm_pieces(x, _pieces(jnp.where(below, A, 0.0), 1))
              for x, A in zip(xs, As)]
        Xs = [X - _mm_pieces(_pieces(Y, 1), x) for X, Y, x in zip(Xs, Ys, xs)]
        shift += 1
    pieces = [_pieces(A) for A in As]
    for residual, correction in ((2, 1), (3, 2)):
        xs = [_pieces(X, residual) for X in Xs]
        Rs = [(eye - X) - _mm_pieces(a[:residual], x)
              for X, a, x in zip(Xs, pieces, xs)]
        Xs = [X + _mm_pieces(x[:correction], _pieces(R, correction))
              for X, x, R in zip(Xs, xs, Rs)]
    return Xs


def _chunk_decays(g, k, sub, dtype):
    """What both kernels form of one chunk's log decay ``g`` and keys ``k``
    ([C, dk] float32): the running sum ``G``, the row factors ``e^{G - R_I}``
    and, per sub-block ``I``, the column factors ``e^{R_I - G}`` over the
    rows up to the sub-block's last (float32, [hi, dk]) and the keys they
    scale, rounded, with zeros below ([C, dk])."""
    C, dk = g.shape
    row, col = _grid2(C)
    G = _mm_pieces([(row >= col).astype(jnp.bfloat16)], _pieces(g))
    refs = [G[i * sub + sub // 2:i * sub + sub // 2 + 1] for i in range(C // sub)]
    row_decay = jnp.exp(jnp.concatenate(
        [G[i * sub:(i + 1) * sub] - r for i, r in enumerate(refs)], axis=0))
    col_decays, keys = [], []
    for i, r in enumerate(refs):
        hi = (i + 1) * sub
        e = jnp.exp(r - G[:hi])
        kc = (k[:hi] * e).astype(dtype)
        if hi < C:
            kc = jnp.concatenate([kc, jnp.zeros((C - hi, dk), dtype)], axis=0)
        col_decays.append(e)
        keys.append(kc)
    return G, row_decay, col_decays, keys


def _intra_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref,
                      qg_ref, kd_ref, w_ref, u_ref, aqk_ref, d_ref,
                      *, chunk, sub, dk, dv):
    """One (batch, head block, chunk block) step; no state is carried.
    Blocks: ``q``, ``k``, ``g`` [cb * C, hb * dk] and ``v`` [cb * C, hb * dv]
    (the layer's [B, T, H * d], so no transpose is made); ``beta``
    [cb * C, hb]; out ``qg``, ``kd``, ``w`` [hb, cb * C, dk], ``u``
    [hb, cb * C, dv], ``aqk`` [hb, cb * C, C], ``d`` [hb, cb, dk]."""
    from jax.experimental import pallas as pl

    hb, cb = d_ref.shape[0], d_ref.shape[1]
    dtype = u_ref.dtype
    f32 = jnp.float32
    C, nb = chunk, chunk // sub
    row, col = _grid2(C)

    # (the loop's body writes the kernel's output refs: they are the program's
    # results, not a side effect of tracing, whatever graftlint's G004 reads)
    def one_chunk(c, carry):
        rows = pl.ds(pl.multiple_of(c * C, C), C)
        heads = []
        for h in range(hb):
            at_k, at_v = slice(h * dk, (h + 1) * dk), slice(h * dv, (h + 1) * dv)
            q, k = q_ref[rows, at_k].astype(f32), k_ref[rows, at_k].astype(f32)
            v, g = v_ref[rows, at_v].astype(f32), g_ref[rows, at_k].astype(f32)
            beta = beta_ref[rows, h:h + 1].astype(f32)             # [C, 1]
            G, row_decay, _, keys = _chunk_decays(g, k, sub, dtype)
            qr, kr = (q * row_decay).astype(dtype), (k * row_decay).astype(dtype)
            pq, pk = [], []
            for i in range(nb):
                at = slice(i * sub, (i + 1) * sub)
                both = _nt(jnp.concatenate([qr[at], kr[at]], axis=0), keys[i])
                pq.append(both[:sub])
                pk.append(both[sub:])
            aqk = jnp.where(row >= col, jnp.concatenate(pq, axis=0), 0.0)
            A = jnp.where(row > col, jnp.concatenate(pk, axis=0), 0.0) * beta
            eG, GC = jnp.exp(G), G[C - 1:C]
            qg_ref[h, rows, :] = (q * eG).astype(dtype)  # graftlint: disable=G004
            kd_ref[h, rows, :] = (k * jnp.exp(GC - G)).astype(dtype)  # graftlint: disable=G004
            aqk_ref[h, rows, :] = aqk.astype(dtype)  # graftlint: disable=G004
            d_ref[h, pl.ds(c, 1), :] = jnp.exp(GC)  # graftlint: disable=G004
            heads.append((A, (beta * k * eG).astype(dtype),
                          (beta * v).astype(dtype)))
        Ts = _inverse_tiles([A for A, _, _ in heads])
        for h, (T, (_, Kg, Vb)) in enumerate(zip(Ts, heads)):
            T = T.astype(dtype)
            w_ref[h, rows, :] = _nn(T, Kg).astype(dtype)  # graftlint: disable=G004
            u_ref[h, rows, :] = _nn(T, Vb).astype(dtype)  # graftlint: disable=G004
        return carry

    jax.lax.fori_loop(0, cb, one_chunk, 0)


def _intra_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref,
                      dqg_ref, dkd_ref, dw_ref, du_ref, daqk_ref, dd_ref,
                      dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                      *, chunk, sub, dk, dv):
    """The cotangents of q, k, v, g and beta at those of the forward's six
    outputs; blocks as the forward's. Everything between is formed again in
    VMEM. With ``Kg = beta k e^G``, ``Vb = beta v``, ``P`` the pair matrix of
    k with itself before beta and ``E_ti = e^{G_t - G_i}``::

        dKg = T^T dW      dVb = T^T dU      dT = dW Kg^T + dU Vb^T
        dA = -stril(T^T dT T^T)             dP = beta_t dA
        dbeta_t = sum_i dA_ti P_ti + sum_k dKg o k e^G + sum_v dVb o v
        a pair matrix P_ti = sum_k a_tk b_ik E_tik with cotangent dP, by
        sub-block I (rows I, the columns up to its last row):
            da[I]  = (dP[I] (b o e^{R_I - G})) o e^{G - R_I}
            db    += (dP[I]^T (a o e^{G - R_I})[I]) o e^{R_I - G}
            dG    += a o da - b o db        (Aqk: a = q, b = k; P: a = b = k)
        dq = dQg o e^G + da(Aqk)            dv = beta dVb
        dk = dKg o beta e^G + dKd o e^{GC - G} + da(P) + db(Aqk) + db(P)
        dG += dQg o Qg + dKg o Kg - dKd o Kd
        its last row += sum_t dKd_t o Kd_t + dd o d;  dg = its suffix sums."""
    from jax.experimental import pallas as pl

    hb, cb = dd_ref.shape[0], dd_ref.shape[1]
    dtype = du_ref.dtype
    f32 = jnp.float32
    C, nb = chunk, chunk // sub
    row, col = _grid2(C)
    last = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0) == C - 1
    tn = (((0,), (0,)), ((), ()))
    nt = (((1,), (1,)), ((), ()))

    def one_chunk(c, carry):
        rows = pl.ds(pl.multiple_of(c * C, C), C)
        heads = []
        for h in range(hb):
            at_k = slice(h * dk, (h + 1) * dk)
            q, k = q_ref[rows, at_k].astype(f32), k_ref[rows, at_k].astype(f32)
            g = g_ref[rows, at_k].astype(f32)
            beta = beta_ref[rows, h:h + 1].astype(f32)             # [C, 1]
            G, row_decay, col_decays, keys = _chunk_decays(g, k, sub, dtype)
            qr, kr = (q * row_decay).astype(dtype), (k * row_decay).astype(dtype)
            P = jnp.where(row > col, jnp.concatenate(
                [_nt(kr[i * sub:(i + 1) * sub], keys[i]) for i in range(nb)],
                axis=0), 0.0)
            heads.append((q, k, beta, G, row_decay, col_decays, keys, qr, kr, P))
        Ts = _inverse_tiles([P * beta for _, _, beta, *_, P in heads])
        dTs, between = [], []
        for h, (T, (q, k, beta, G, *_)) in enumerate(zip(Ts, heads)):
            at_v = slice(h * dv, (h + 1) * dv)
            v = v_ref[rows, at_v].astype(f32)
            dW, dU = dw_ref[h, rows, :], du_ref[h, rows, :]
            Td = T.astype(dtype)
            keG = k * jnp.exp(G)
            dKg, dVb = _tn(Td, dW), _tn(Td, dU)
            dTs.append(_nt(dW, (beta * keG).astype(dtype))
                       + _nt(dU, (beta * v).astype(dtype)))
            dv_ref[rows, at_v] = beta * dVb  # graftlint: disable=G004
            between.append((keG, dKg, jnp.sum(dKg * keG, axis=1, keepdims=True)
                            + jnp.sum(dVb * v, axis=1, keepdims=True)))
        # dA = -stril(T^T dT T^T), each product in six passes
        ts = [_pieces(T) for T in Ts]
        Zs = [_mm_pieces(t, _pieces(dT), tn) for t, dT in zip(ts, dTs)]
        dAs = [jnp.where(row > col, -_mm_pieces(_pieces(Z), t, nt), 0.0)
               for Z, t in zip(Zs, ts)]
        for h, (dA, (keG, dKg, dbeta), (q, k, beta, G, row_decay, col_decays,
                                        keys, qr, kr, P)) in enumerate(
                                            zip(dAs, between, heads)):
            at_k = slice(h * dk, (h + 1) * dk)
            dQg = dqg_ref[h, rows, :].astype(f32)
            dKd = dkd_ref[h, rows, :].astype(f32)
            dbeta_ref[rows, h:h + 1] = dbeta + jnp.sum(dA * P, axis=1,  # graftlint: disable=G004
                                                       keepdims=True)
            dPq = jnp.where(row >= col, daqk_ref[h, rows, :].astype(f32), 0.0)
            dPk = dA * beta
            dqr, dkr, dk_cols = [], [], jnp.zeros((C, dk), f32)
            for i in range(nb):
                at, hi = slice(i * sub, (i + 1) * sub), (i + 1) * sub
                dP = jnp.concatenate([dPq[at], dPk[at]], axis=0).astype(dtype)
                da = _nn(dP, keys[i])                              # [2 sub, dk]
                dqr.append(da[:sub])
                dkr.append(da[sub:])
                db = _tn(dP, jnp.concatenate([qr[at], kr[at]], axis=0))[:hi]
                db = db * col_decays[i]
                if hi < C:
                    db = jnp.concatenate([db, jnp.zeros((C - hi, dk), f32)], axis=0)
                dk_cols = dk_cols + db
            dqr = jnp.concatenate(dqr, axis=0) * row_decay
            dkr = jnp.concatenate(dkr, axis=0) * row_decay
            eG, GC = jnp.exp(G), G[C - 1:C]
            tail = jnp.exp(GC - G)
            dq = dQg * eG + dqr
            dq_ref[rows, at_k] = dq  # graftlint: disable=G004
            dk_ref[rows, at_k] = dKg * (beta * eG) + dKd * tail + dkr + dk_cols  # graftlint: disable=G004
            dG = (q * dq + k * (dkr - dk_cols) + dKg * (beta * keG)
                  - dKd * (k * tail))
            dGC = (jnp.sum(dKd * (k * tail), axis=0, keepdims=True)
                   + dd_ref[h, pl.ds(c, 1), :] * jnp.exp(GC))
            dG = dG + jnp.where(last, dGC, 0.0)
            dg_ref[rows, at_k] = _mm_pieces(  # graftlint: disable=G004
                [(row <= col).astype(jnp.bfloat16)], _pieces(dG))
        return carry

    jax.lax.fori_loop(0, cb, one_chunk, 0)


@functools.partial(jax.jit, static_argnames=(
    "kernel", "name", "kinds", "out_kinds", "heads", "chunk", "dtype", "cost",
    "interpret"))
def _intra_call(*operands, kernel, name, kinds, out_kinds, heads, chunk, dtype,
                cost, interpret):
    """One call of a preparation kernel over the grid (B, H / hb, N / cb),
    every axis parallel. ``operands``: q, k, v, g as the layer's
    [B, T, H * d], beta as [B, H / hb, T, hb], then further ones in the scan
    kernels' layout; ``kinds`` / ``out_kinds``: which of the layouts below
    each operand / output has. Jitted, so that the layers of a model share
    one trace and one lowering of the kernel (a kernel's body is traced at
    every ``pallas_call``); the reshapes stay with the caller, where XLA
    fuses them into what produces the operands."""
    from jax.experimental import pallas as pl

    B, T, _ = operands[1].shape
    H, dk, dv = heads, operands[1].shape[-1] // heads, operands[2].shape[-1] // heads
    hb, cb, C = HEADS_PER_STEP, CHUNKS_PER_STEP, chunk
    N = T // C
    f32 = jnp.float32

    def tokens(width):  # the layer's [B, T, H * width]
        return pl.BlockSpec((None, cb * C, hb * width), lambda b, h, n: (b, n, h))

    def rows(width):    # the scan kernels' [B, H, N * C, width]
        return pl.BlockSpec((None, hb, cb * C, width),
                            lambda b, h, n: (b, h, n, 0))

    specs = {
        "tk": tokens(dk), "tv": tokens(dv), "dk": rows(dk), "dv": rows(dv),
        "C": rows(C),
        # beta, a column a head: [B, H / hb, T, hb]
        "beta": pl.BlockSpec((None, None, cb * C, hb),
                             lambda b, h, n: (b, h, n, 0)),
        "decay": pl.BlockSpec((None, hb, cb, dk), lambda b, h, n: (b, h, n, 0)),
    }
    shapes = {
        "tk": ((B, T, H * dk), f32), "tv": ((B, T, H * dv), f32),
        "dk": ((B, H, T, dk), dtype), "dv": ((B, H, T, dv), dtype),
        "C": ((B, H, T, C), dtype), "beta": ((B, H // hb, T, hb), f32),
        "decay": ((B, H, N, dk), f32),
    }
    out_shape = [jax.ShapeDtypeStruct(*shapes[kind]) for kind in out_kinds]
    moved = sum(a.size * a.dtype.itemsize for a in (*operands, *out_shape))
    return pl.pallas_call(
        functools.partial(kernel, chunk=C, sub=SUB, dk=dk, dv=dv),
        grid=(B, H // hb, N // cb),
        in_specs=[specs[kind] for kind in kinds],
        out_specs=[specs[kind] for kind in out_kinds], out_shape=out_shape,
        cost_estimate=pl.CostEstimate(
            flops=cost * B * H * N, bytes_accessed=moved,
            transcendentals=7 * B * H * N * C * dk),
        name=name, **_params(interpret, "parallel"))(*operands)


_LAYER_KINDS = ("tk", "tk", "tv", "tk", "beta")


def _as_the_layer_hands_them(q, k, v, g, beta):
    """q, k, v, g [B, T, H, d] as [B, T, H * d] (no data moves) and beta
    [B, T, H] with a block's heads last, [B, H / hb, T, hb]."""
    B, T, H, _ = k.shape
    hb = HEADS_PER_STEP
    return (*(a.reshape(B, T, -1) for a in (q, k, v, g)),
            jnp.swapaxes(beta.reshape(B, T, H // hb, hb), 1, 2))


def intra_fwd(q, k, v, g, beta, chunk, dtype, interpret=False):
    """The kernel ``kda_intra_fwd``: :func:`prepare_plain`'s six outputs."""
    B, T, H, dk = k.shape
    C, dv = chunk, v.shape[-1]
    outs = _intra_call(
        *_as_the_layer_hands_them(q, k, v, g, beta),
        kernel=_intra_fwd_kernel, name="kda_intra_fwd", kinds=_LAYER_KINDS,
        out_kinds=("dk", "dk", "dk", "dv", "C", "decay"), heads=H, chunk=C,
        dtype=dtype, cost=2 * C * C * (5 * dk + dv + 10 * C),
        interpret=interpret)
    return tuple(o.reshape(B, H, T // C, C, -1) for o in outs[:5]) + (outs[5],)


def intra_bwd(q, k, v, g, beta, dQg, dKd, dW, dU, dAqk, dd, chunk, dtype,
              interpret=False):
    """The kernel ``kda_intra_bwd``: the cotangents of q, k, v, g and beta
    (float32, in their shapes) at those of :func:`intra_fwd`'s outputs."""
    B, T, H, dk = k.shape
    C = chunk
    dq, dk_, dv_, dg, dbeta = _intra_call(
        *_as_the_layer_hands_them(q, k, v, g, beta),
        *map(_flat, (dQg, dKd, dW, dU, dAqk)), dd,
        kernel=_intra_bwd_kernel, name="kda_intra_bwd",
        kinds=_LAYER_KINDS + ("dk", "dk", "dk", "dv", "C", "decay"),
        out_kinds=_LAYER_KINDS, heads=H, chunk=C, dtype=dtype,
        cost=2 * C * C * (11 * dk + 3 * v.shape[-1] + 12 * C),
        interpret=interpret)
    return (dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape),
            dg.reshape(g.shape), jnp.swapaxes(dbeta, 1, 2).reshape(B, T, H))


# As the scan's rule above: both kernels land in the ``kda_chunk`` scope.
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def prepare_fused(q, k, v, g, beta, chunk, dtype):
    """:func:`prepare_plain` as the kernels ``kda_intra_fwd`` and, for the
    backward, ``kda_intra_bwd``, which keeps nothing but the inputs."""
    return intra_fwd(q, k, v, g, beta, chunk, dtype)


def _prepare_fused_fwd(q, k, v, g, beta, chunk, dtype):
    return intra_fwd(q, k, v, g, beta, chunk, dtype), (q, k, v, g, beta)


def _prepare_fused_bwd(chunk, dtype, saved, cotangents):
    grads = intra_bwd(*saved, *cotangents, chunk, dtype)
    return tuple(d.astype(a.dtype) for d, a in zip(grads, saved))


prepare_fused.defvjp(_prepare_fused_fwd, _prepare_fused_bwd)


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
    Where :func:`scan_path` answers ``fused`` the preparation and the scan
    are kernels, elsewhere both are plain. ``scan`` replaces
    :func:`chunk_scan` behind the plain preparation (the tests hand in the
    scan kernels in interpret mode)."""
    B, T, H, dk = k.shape
    dv = v.shape[-1]
    dtype = dtype or v.dtype
    C = min(int(chunk), T)  # a sequence shorter than a chunk is one chunk
    if T % C:
        raise ValueError(
            f"a KDA layer takes sequences that are whole chunks: seq_len {T} "
            f"is no multiple of the chunk {C}")
    fused = scan is None and scan_path(
        H, dk, dv, T, C, get_mesh_context(),
        get_seq_context() is not None) == "fused"
    Qg, Kd, W, U, Aqk, d = (prepare_fused if fused else prepare_plain)(
        q, k, v, g, beta, C, dtype)
    S0 = jnp.zeros((B, H, dv, dk), jnp.float32)  # a sequence starts from no state
    O, ST = (scan or chunk_scan)(Qg, Kd, W, U, Aqk, d, S0)
    o = jnp.moveaxis(O, 1, 3).reshape(B, T, H, dv)
    return o, jnp.swapaxes(ST, -1, -2)
