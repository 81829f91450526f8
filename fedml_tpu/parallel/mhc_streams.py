"""The stream read and the stream write of a hyper-connected block
(``transformer.Block`` where ``cfg.hc_mult`` > 1), with a backward pass that
touches each residual stream once.

The forward of both is a handful of n-term sums that XLA fuses into one
elementwise pass over the streams. Their autodiff is not: every coefficient
gradient (``n^2`` of ``H_res``, ``n`` of ``H_post``, ``n`` of ``H_pre``)
becomes its own reduction over the channel axis, each reading one slice of
the streams and one of the cotangent again. On a TPU the two ``custom_vjp``
rules below replace that by one Pallas kernel each
(``mhc_streams_write_bwd``, ``mhc_streams_read_bwd``): an ``(L-tile, C)``
block of all ``n`` streams and of the cotangent is held in VMEM, and the
stream gradients and the lane reductions for the coefficient gradients come
out of the same pass. The forward stays the XLA expressions, the residuals
are the inputs, and the arithmetic is the plain expressions' (float32
products and sums, results in the primals' dtypes).

:func:`backward_path` says which backward a trace takes; nothing else
chooses.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..core.mlops.scopes import train_step_scope as _scope
from .context import get_mesh_context

_LANES = 128
# bytes of one grid step's blocks (all streams in, all streams out): large
# enough that the step's fixed cost is small beside its DMA, small enough
# that two buffers of it and the float32 working set stay well inside VMEM
_STEP_BYTES = 4 << 20


def backward_path(n: int, channels: int, mesh: Optional[Mesh]) -> str:
    """``"fused"`` where the stream reads and writes of a block with ``n``
    streams of ``channels`` take the one-pass backward, ``"xla"`` where the
    plain expressions' autodiff runs: no streams, another back-end than a
    TPU, channels that do not fill whole lanes, or a mesh of several devices
    (Mosaic kernels are not partitioned by pjit, and no wrapper shards these
    yet). ``mesh`` is the ambient one (``context.get_mesh_context()``), or
    the trainer's."""
    fused = (n > 1 and channels % _LANES == 0
             and (mesh is None or mesh.size == 1)
             and jax.devices()[0].platform == "tpu")
    return "fused" if fused else "xla"


def _read_plain(X, pre):
    return sum(pre[..., i, None] * X[:, i].astype(jnp.float32)
               for i in range(X.shape[1])).astype(X.dtype)


def _write_plain(X, y, post, res):
    n = X.shape[1]
    y32 = y.astype(jnp.float32)
    rows = [sum(res[..., i, j, None] * X[:, j].astype(jnp.float32)
                for j in range(n))
            + post[..., i, None] * y32 for i in range(n)]
    return jnp.stack(rows, axis=1).astype(X.dtype)


def _tile_rows(n: int, channels: int, itemsize: int) -> int:
    """Rows of L in one block: a multiple of 16 (a bfloat16 tile's sublanes)
    that keeps a grid step's blocks near ``_STEP_BYTES``."""
    row_bytes = (3 * n + 2) * channels * itemsize
    return max(16, min(256, _STEP_BYTES // row_bytes // 16 * 16))


def _row_sum(p):
    """[rows, C] -> [rows, 1], float32."""
    return jnp.sum(p, axis=-1, keepdims=True)


def _columns(cols, width: int):
    """``len(cols)`` arrays [rows, 1] side by side as one [rows, width]."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (cols[0].shape[0], width), 1)
    out = jnp.zeros(lane.shape, jnp.float32)
    for k, col in enumerate(cols):
        out = jnp.where(lane == k, col, out)
    return out


def _write_bwd_kernel(coef_ref, x_ref, y_ref, g_ref,
                      dx_ref, dy_ref, dcoef_ref):
    """One (batch, L-tile) block. ``coef``: [rows, n + n*n] float32, ``post``
    then ``res`` row-major; ``x``, ``g``, ``dx``: [n, rows, C]; ``y``, ``dy``:
    [rows, C]; ``dcoef`` like ``coef``."""
    n = x_ref.shape[0]
    coef = coef_ref[0]
    post = [coef[:, i:i + 1] for i in range(n)]
    res = [[coef[:, n + i * n + j:n + i * n + j + 1] for j in range(n)]
           for i in range(n)]
    g = [g_ref[i, 0].astype(jnp.float32) for i in range(n)]
    y = y_ref[0].astype(jnp.float32)
    dy_ref[0] = sum(post[i] * g[i] for i in range(n)).astype(dy_ref.dtype)
    sums = [_row_sum(g[i] * y) for i in range(n)] + [None] * (n * n)
    for j in range(n):
        x = x_ref[j, 0].astype(jnp.float32)
        dx_ref[j, 0] = sum(res[i][j] * g[i]
                           for i in range(n)).astype(dx_ref.dtype)
        for i in range(n):
            sums[n + i * n + j] = _row_sum(g[i] * x)
    dcoef_ref[0] = _columns(sums, dcoef_ref.shape[-1])


def _read_bwd_kernel(pre_ref, x_ref, g_ref, dx_ref, dpre_ref):
    """``pre``, ``dpre``: [rows, n] float32; ``x``, ``dx``: [n, rows, C];
    ``g`` (the cotangent of the sublayer's input): [rows, C]."""
    n = x_ref.shape[0]
    coef = pre_ref[0]
    pre = [coef[:, i:i + 1] for i in range(n)]
    g = g_ref[0].astype(jnp.float32)
    for i in range(n):
        dx_ref[i, 0] = (pre[i] * g).astype(dx_ref.dtype)
    sums = [_row_sum(g * x_ref[i, 0].astype(jnp.float32)) for i in range(n)]
    dpre_ref[0] = _columns(sums, dpre_ref.shape[-1])


def _call(kernel, name, X, in_arrays, in_kinds, out_kinds, terms,
          aliases=None):
    """``pallas_call`` over a (batch, L-tile) grid. A kind is the block's
    layout: ``"streams"`` [B, n, L, C], ``"tokens"`` [B, L, C], or an int m
    for per-token coefficients [B, L, m] float32. The kernel sees the
    streams as [n, B, L, C], which is how XLA lays them out in this program
    (it slices them by stream), so the swap costs nothing; handed over as
    [B, n, L, C] each call paid a copy of the streams to the other layout.
    The last L tile may hang over the end: rows are independent and what
    lies outside is dropped. ``terms``: the coefficients that each multiply
    one [L, C] slice for a stream gradient and take one lane reduction (the
    cost XLA and the profiler are told). ``aliases`` (input index -> output
    index) lets an output take an input's buffer where the tiles divide L (a
    block is read whole before it is written; Pallas' interpreter cannot
    alias an operand it has to pad)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, n, L, C = X.shape
    rows = _tile_rows(n, C, X.dtype.itemsize)

    def spec(kind):
        if kind == "streams":
            return pl.BlockSpec((n, 1, rows, C), lambda b, t: (0, b, t, 0))
        last = C if kind == "tokens" else kind
        return pl.BlockSpec((1, rows, last), lambda b, t: (b, t, 0))

    def shape(kind):
        if kind == "streams":
            return jax.ShapeDtypeStruct((n, B, L, C), X.dtype)
        if kind == "tokens":
            return jax.ShapeDtypeStruct((B, L, C), X.dtype)
        return jax.ShapeDtypeStruct((B, L, kind), jnp.float32)

    def swapped(arrays, kinds):
        return [jnp.swapaxes(a, 0, 1) if k == "streams" else a
                for a, k in zip(arrays, kinds, strict=True)]

    in_arrays = swapped(in_arrays, in_kinds)
    out_shapes = [shape(k) for k in out_kinds]
    moved = sum(a.size * a.dtype.itemsize for a in (*in_arrays, *out_shapes))
    outs = pl.pallas_call(
        kernel,
        grid=(B, pl.cdiv(L, rows)),
        in_specs=[spec(k) for k in in_kinds],
        out_specs=[spec(k) for k in out_kinds],
        out_shape=out_shapes,
        cost_estimate=pl.CostEstimate(
            flops=4 * terms * B * L * C, bytes_accessed=moved,
            transcendentals=0),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=64 << 20),
        input_output_aliases=aliases if aliases and L % rows == 0 else {},
        name=name,
    )(*in_arrays)
    return swapped(outs, out_kinds)


# jitted, both: a step calls each once a sublayer with the same shapes, and a
# nested jit is traced and lowered once (0.1 s a call otherwise, paid by every
# start of the program, warm cache or not)
@jax.jit
def write_bwd(X, y, post, res, g):
    """The cotangents (dX, dy, dpost, dres) of ``_write_plain`` at ``g``."""
    B, n, L, _ = X.shape
    m = n + n * n
    coef = jnp.concatenate(
        [post, res.reshape(B, L, n * n)], axis=-1).astype(jnp.float32)
    # dX takes the cotangent's buffer: the backward of a write runs while
    # everything its sublayer saved is still held, the step's fullest moment
    dX, dy, dcoef = _call(
        _write_bwd_kernel, "mhc_streams_write_bwd", X, (coef, X, y, g),
        (m, "streams", "tokens", "streams"), ("streams", "tokens", m),
        terms=m, aliases={3: 0})
    return (dX, dy.astype(y.dtype), dcoef[..., :n].astype(post.dtype),
            dcoef[..., n:].reshape(res.shape).astype(res.dtype))


@jax.jit
def read_bwd(X, pre, g):
    """The cotangents (dX, dpre) of ``_read_plain`` at ``g``."""
    n = X.shape[1]
    dX, dpre = _call(
        _read_bwd_kernel, "mhc_streams_read_bwd", X,
        (pre.astype(jnp.float32), X, g), (n, "streams", "tokens"),
        ("streams", n), terms=n)
    return dX, dpre.astype(pre.dtype)


# The backward rules are traced under the name stack of the forward call, so
# the kernels land in the ``mhc`` scope that ``streams_read`` and
# ``streams_write`` open.
@jax.custom_vjp
def _read_fused(X, pre):
    return _read_plain(X, pre)


_read_fused.defvjp(lambda X, pre: (_read_plain(X, pre), (X, pre)),
                   lambda saved, g: read_bwd(*saved, g))


@jax.custom_vjp
def _write_fused(X, y, post, res):
    return _write_plain(X, y, post, res)


_write_fused.defvjp(
    lambda X, y, post, res: (_write_plain(X, y, post, res), (X, y, post, res)),
    lambda saved, g: write_bwd(*saved, g))


def _fused(X) -> bool:
    return backward_path(X.shape[1], X.shape[-1],
                         get_mesh_context()) == "fused"


def streams_read(X, pre):
    """``H_pre X``: [B, n, L, C], [B, L, n] -> the sublayer's input
    [B, L, C]. The n-term sums are written out so that they fuse into one
    elementwise pass over the streams (an einsum would be a K = n matmul)."""
    with _scope("mhc"):
        return (_read_fused if _fused(X) else _read_plain)(X, pre)


def streams_write(X, y, post, res):
    """``H_res X + H_post^T (x) y``: the streams after the sublayer."""
    with _scope("mhc"):
        return (_write_fused if _fused(X) else _write_plain)(X, y, post, res)
