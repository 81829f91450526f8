"""The experts' grouped matrix products as Pallas kernels: ``x W`` group by
group, its rows' gradient ``dy W^T`` and its matrices' gradient ``x^T dy``.

**The definition** is ``jax.lax.ragged_dot(x, w, counts,
preferred_element_type=x.dtype)``: ``x`` [M, K] holds rows sorted by group,
``w`` [G, K, N] one matrix a group, ``counts`` [G] how many rows each group
takes, the first group first. Rows past ``sum(counts)`` belong to no group
(the expert layer's assignments to experts held elsewhere): nothing is
computed for them and nothing is promised about what the result holds there.
That form is what runs wherever :func:`path` answers ``xla``, and what the
kernels are tested against.

**The kernels** (``expert_gmm_fwd``, ``expert_gmm_dlhs``, ``expert_gmm_drhs``
under one ``custom_vjp``, :func:`grouped_product`; where :func:`path` answers
``kernel``). Rows come in tiles of ``ROW_TILE`` = 128, small, so that a tile a
group boundary cuts wastes little work. A *visit* is a (group, row tile) pair
that shares a row: a group's tiles in order, the groups one after another, so
a tile that a boundary cuts is visited once by each group and its output block
stays in VMEM between the two. The visits are counted on the device from
``counts`` and that count is the length of the grid's inner axis, so a row
tile past the last group's is never visited. The outer axis walks lane tiles
of the result. Along the inner axis a matrix's block keeps its index for all
its group's visits, so Pallas copies it once: each held matrix is read from
HBM once a lane tile, once a call where the result's width is one tile.

- *forward* ``expert_gmm_fwd``: grid (lane tiles of N, visits), blocks
  ``x`` [128, K], ``w`` [K, tn], out [128, tn]; one product with the whole
  contraction, accumulated in float32, stored in the rows' dtype where the
  rows are the visit's group's (a select, not a product: the other rows may
  hold anything).
- *rows' gradient* ``expert_gmm_dlhs``: the same with ``dy`` [128, N] against
  the block ``w`` [tk, N] transposed in the product, the result ``[M, K]``.
- *matrices' gradient* ``expert_gmm_drhs``: grid (lane tiles of N, visits with
  one more for each empty group), blocks ``x`` [128, K], ``dy`` [128, tn]; the
  rows outside the visit's group selected to zero in both, ``x^T dy`` added to
  a float32 scratch ``[K, tn]`` that starts at zero with its group and is
  written once, in ``w``'s dtype, when the group ends (an empty group's
  matrix is zeros).

*Widths.* The contraction is one block whatever its width; Mosaic pads a width
that is no multiple of 128 lanes in VMEM and keeps the padding out of the
product. A result's width is one block where two buffers of the blocks fit the
VMEM budget, else tiles of a multiple of 128 lanes, the fewest lanes past the
width first (1,856 as 15 x 128 or 5 x 384: both leave 64); a last tile that
runs past the width computes lanes that are never stored (Pallas writes the
part of an edge block that lies inside the array). No width is ever 64 lanes
a tile, and no array is padded in HBM. :func:`tiles` is the rule; no option
sets it.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from .context import one_tpu_device

ROW_TILE = 128
_LANES = 128
_VMEM_LIMIT = 64 << 20
# what two buffers of a kernel's blocks, its scratch and its float32 results
# may take of the limit; the rest is Mosaic's own
_VMEM_BUDGET = 48 << 20


class Tiles(NamedTuple):
    """Lanes of the result a grid step computes, by kernel: the whole width
    or a multiple of 128. The contraction is never cut."""

    fwd: int   # of N, ``x W``
    dlhs: int  # of K, ``dy W^T``
    drhs: int  # of N, ``x^T dy`` (its K rows whole)


def _padded(width: int) -> int:
    return -(-width // _LANES) * _LANES


def _product_bytes(contraction: int, lanes: int, itemsize: int) -> int:
    """VMEM of ``expert_gmm_fwd`` / ``_dlhs`` at ``lanes`` of the result: two
    buffers of the rows, the matrix block and the result block, and the
    float32 product."""
    c = _padded(contraction)
    return (2 * itemsize * (ROW_TILE * c + c * lanes + ROW_TILE * lanes)
            + 4 * ROW_TILE * lanes)


def _drhs_bytes(k: int, lanes: int, itemsize: int) -> int:
    """VMEM of ``expert_gmm_drhs`` at ``lanes`` of N: two buffers of the rows,
    the cotangent and the result block, the float32 scratch and product, and
    the rows' masked float32 copy and its transpose."""
    k = _padded(k)
    return (2 * itemsize * (ROW_TILE * k + ROW_TILE * lanes + k * lanes)
            + 8 * k * lanes + 8 * ROW_TILE * k)


def _lane_tile(width: int, cost) -> Optional[int]:
    """``width`` whole where ``cost`` of it (padded to the lanes) fits the
    budget; else the multiple of 128 that fits and leaves the fewest lanes
    past the width, the fewest tiles among equals; None if not even 128
    fits."""
    if cost(_padded(width)) <= _VMEM_BUDGET:
        return width
    best = None
    for lanes in range(_LANES, _padded(width), _LANES):
        if cost(lanes) > _VMEM_BUDGET:
            break
        n = -(-width // lanes)
        if best is None or (n * lanes, n) < best[0]:
            best = ((n * lanes, n), lanes)
    return best[1] if best else None


def tiles(k: int, n: int, itemsize: int = 2) -> Optional[Tiles]:
    """The kernels' lane tiles for ``[rows, k] x [k, n]`` matrices whose
    elements take ``itemsize`` bytes, from the shapes and the VMEM budget;
    None where some kernel cannot hold its blocks."""
    found = Tiles(
        fwd=_lane_tile(n, lambda t: _product_bytes(k, t, itemsize)),
        dlhs=_lane_tile(k, lambda t: _product_bytes(n, t, itemsize)),
        drhs=_lane_tile(n, lambda t: _drhs_bytes(k, t, itemsize)))
    return None if None in found else found


def path(rows: int, k: int, n: int, mesh: Optional[Mesh],
         itemsize: int = 2) -> str:
    """``"kernel"`` where ``[rows, k] x [held, k, n]`` grouped products run as
    the Pallas kernels, ``"xla"`` where ``jax.lax.ragged_dot`` and its
    autodiff run: another back-end than a TPU, a mesh of several devices
    (``context.one_tpu_device``), rows that are no whole number of row
    tiles, or matrices whose blocks no lane tile fits into VMEM. ``mesh`` is
    the ambient one, or the trainer's."""
    fits = (rows > 0 and rows % ROW_TILE == 0
            and tiles(k, n, itemsize) is not None)
    return "kernel" if fits and one_tpu_device(mesh) else "xla"


def layer_path(rows: int, d_model: int, up_width: int, d_ff: int,
               mesh: Optional[Mesh], itemsize: int = 2) -> str:
    """One answer for an expert layer's two products, ``[rows, d_model] x
    [held, d_model, up_width]`` and ``[rows, d_ff] x [held, d_ff, d_model]``:
    ``kernel`` where both take the kernels."""
    both = (path(rows, d_model, up_width, mesh, itemsize),
            path(rows, d_ff, d_model, mesh, itemsize))
    return "kernel" if both == ("kernel", "kernel") else "xla"


def layer_plan(rows: int, d_model: int, up_width: int, d_ff: int,
               mesh: Optional[Mesh], itemsize: int = 2) -> dict:
    """What a trainer reports of an expert layer's products
    (``cheetah_init.experts``): :func:`layer_path`'s answer and, for each of
    the two matrix shapes, its ``k`` and ``n`` with the kernels' lane tiles
    (none where they cannot hold it)."""
    plan = {"path": layer_path(rows, d_model, up_width, d_ff, mesh, itemsize)}
    for name, (k, n) in (("up", (d_model, up_width)), ("down", (d_ff, d_model))):
        found = tiles(k, n, itemsize)
        plan[name] = dict(k=k, n=n, **(found._asdict() if found else {}))
    return plan


# ---------------------------------------------------------------------------
# the visits
# ---------------------------------------------------------------------------


def _visits(counts, rows: int, empty: bool):
    """The grid's inner axis from the groups' sizes: per visit its group and
    its row tile (arrays of the largest length a visit count can take,
    ``rows / 128 + groups``), the groups' row offsets ``[G + 1]``, and the
    number of visits. ``empty``: an empty group is visited once (its
    matrices' gradient is zeros), else never."""
    G = counts.shape[0]
    n_tiles = rows // ROW_TILE
    ends = jnp.cumsum(counts, dtype=jnp.int32)
    starts = ends - counts
    first = starts // ROW_TILE
    n = jnp.where(counts > 0, (ends + ROW_TILE - 1) // ROW_TILE - first,
                  int(empty)).astype(jnp.int32)
    length = n_tiles + G
    group = jnp.repeat(jnp.arange(G, dtype=jnp.int32), n,
                       total_repeat_length=length)
    before = jnp.cumsum(n) - n
    tile = jnp.clip(first[group] + jnp.arange(length, dtype=jnp.int32)
                    - before[group], 0, n_tiles - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return group, tile, offsets, jnp.sum(n)


def _own_rows(tile, offsets, g, shape):
    """Which rows of a visit's ``shape`` block belong to its group."""
    row = tile * ROW_TILE + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return (row >= offsets[g]) & (row < offsets[g + 1])


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------


def _product_kernel(group, tile, offsets, x_ref, w_ref, out_ref, *,
                    transposed):
    """One visit of ``expert_gmm_fwd`` (``w`` [K, tn]) or ``expert_gmm_dlhs``
    (``w`` [tk, N], transposed in the product)."""
    from jax.experimental import pallas as pl

    v = pl.program_id(1)
    g, t = group[v], tile[v]
    acc = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (1 if transposed else 0,)), ((), ())),
        preferred_element_type=jnp.float32)
    lo = t * ROW_TILE
    whole = (offsets[g] <= lo) & (lo + ROW_TILE <= offsets[g + 1])

    @pl.when(whole)
    def _():
        out_ref[...] = acc.astype(out_ref.dtype)

    @pl.when(jnp.logical_not(whole))
    def _():
        mine = _own_rows(t, offsets, g, acc.shape)
        out_ref[...] = jnp.where(
            mine, acc, out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


def _drhs_kernel(group, tile, offsets, x_ref, dy_ref, out_ref, acc_ref):
    """One visit of ``expert_gmm_drhs``."""
    from jax.experimental import pallas as pl

    v, last = pl.program_id(1), pl.num_programs(1) - 1
    g, t = group[v], tile[v]

    @pl.when((v == 0) | (group[jnp.maximum(v - 1, 0)] != g))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(offsets[g + 1] > offsets[g])
    def _():
        f32 = jnp.float32
        x = jnp.where(_own_rows(t, offsets, g, x_ref.shape),
                      x_ref[...].astype(f32), 0)
        dy = jnp.where(_own_rows(t, offsets, g, dy_ref.shape),
                       dy_ref[...].astype(f32), 0)
        acc_ref[...] += jnp.dot(x.T.astype(x_ref.dtype), dy.astype(dy_ref.dtype),
                                preferred_element_type=f32)

    @pl.when((v == last) | (group[jnp.minimum(v + 1, last)] != g))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _compiler(interpret):
    from jax.experimental.pallas import tpu as pltpu

    if interpret:
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)}


def _rows_by_matrices(x, w, counts, *, width, lanes, transposed, name,
                      interpret):
    """``expert_gmm_fwd`` (``transposed`` False: ``x`` [M, K], result [M, N])
    or ``expert_gmm_dlhs`` (True: ``x`` is the cotangent [M, N], result
    [M, K]); ``width`` the result's, ``lanes`` its tile."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, C = x.shape
    group, tile, offsets, visits = _visits(counts, M, empty=False)
    n_lane_tiles = -(-width // lanes)
    if transposed:
        w_spec = pl.BlockSpec((None, lanes, C),
                              lambda j, v, g, t, o: (g[v], j, 0))
    else:
        w_spec = pl.BlockSpec((None, C, lanes),
                              lambda j, v, g, t, o: (g[v], 0, j))
    item = x.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_product_kernel, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_lane_tiles, visits),
            in_specs=[pl.BlockSpec((ROW_TILE, C),
                                   lambda j, v, g, t, o: (t[v], 0)),
                      w_spec],
            out_specs=pl.BlockSpec((ROW_TILE, lanes),
                                   lambda j, v, g, t, o: (t[v], j))),
        out_shape=jax.ShapeDtypeStruct((M, width), x.dtype),
        cost_estimate=pl.CostEstimate(
            flops=2 * M * C * width, transcendentals=0,
            bytes_accessed=item * (M * C * n_lane_tiles + w.size + M * width)),
        name=name, **_compiler(interpret),
    )(group, tile, offsets, x, w)


# The wrappers are jitted, with what is static spelt out: Pallas traces a
# kernel's body at every ``pallas_call``, and under ``jit`` the layers of a
# model share one trace and, in one program, one lowering.
@functools.partial(jax.jit, static_argnames=("interpret",))
def gmm_fwd(x, w, counts, interpret=False):
    """The kernel ``expert_gmm_fwd``: ``x`` [M, K], ``w`` [G, K, N] ->
    [M, N] in ``x``'s dtype."""
    K, N = w.shape[1:]
    return _rows_by_matrices(
        x, w, counts, width=N, lanes=tiles(K, N, x.dtype.itemsize).fwd,
        transposed=False, name="expert_gmm_fwd", interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gmm_dlhs(dy, w, counts, interpret=False):
    """The kernel ``expert_gmm_dlhs``: ``dy`` [M, N], ``w`` [G, K, N] -> the
    rows' gradient [M, K] in ``dy``'s dtype."""
    K, N = w.shape[1:]
    return _rows_by_matrices(
        dy, w, counts, width=K, lanes=tiles(K, N, dy.dtype.itemsize).dlhs,
        transposed=True, name="expert_gmm_dlhs", interpret=interpret)


@functools.partial(jax.jit, static_argnames=("groups", "dtype", "interpret"))
def gmm_drhs(x, dy, counts, *, groups, dtype, interpret=False):
    """The kernel ``expert_gmm_drhs``: ``x`` [M, K], ``dy`` [M, N] -> the
    matrices' gradient [groups, K, N] in ``dtype``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, K = x.shape
    N = dy.shape[1]
    lanes = tiles(K, N, x.dtype.itemsize).drhs
    n_lane_tiles = -(-N // lanes)
    group, tile, offsets, visits = _visits(counts, M, empty=True)
    item = x.dtype.itemsize
    return pl.pallas_call(
        _drhs_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_lane_tiles, visits),
            in_specs=[pl.BlockSpec((ROW_TILE, K),
                                   lambda j, v, g, t, o: (t[v], 0)),
                      pl.BlockSpec((ROW_TILE, lanes),
                                   lambda j, v, g, t, o: (t[v], j))],
            out_specs=pl.BlockSpec((None, K, lanes),
                                   lambda j, v, g, t, o: (g[v], 0, j)),
            scratch_shapes=[pltpu.VMEM((K, lanes), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((groups, K, N), dtype),
        cost_estimate=pl.CostEstimate(
            flops=2 * M * K * N, transcendentals=0,
            bytes_accessed=item * (M * K * n_lane_tiles + M * N)
            + jnp.dtype(dtype).itemsize * groups * K * N),
        name="expert_gmm_drhs", **_compiler(interpret),
    )(group, tile, offsets, x, dy)


# The backward rule is traced under the name stack of the forward call, so
# all three kernels land in the scope the caller opens (``moe_products``).
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _product(x, w, counts, interpret):
    return gmm_fwd(x, w, counts, interpret=interpret)


def _product_fwd(x, w, counts, interpret):
    return gmm_fwd(x, w, counts, interpret=interpret), (x, w, counts)


def _product_bwd(interpret, saved, dy):
    x, w, counts = saved
    return (gmm_dlhs(dy, w, counts, interpret=interpret),
            gmm_drhs(x, dy, counts, groups=w.shape[0], dtype=w.dtype,
                     interpret=interpret),
            None)


_product.defvjp(_product_fwd, _product_bwd)


def grouped_product(x, w, counts, interpret=False):
    """``jax.lax.ragged_dot(x, w, counts, preferred_element_type=x.dtype)``
    as the kernels ``expert_gmm_fwd`` and, for the backward,
    ``expert_gmm_dlhs`` and ``expert_gmm_drhs``: operands in ``x``'s dtype,
    products accumulated in float32. The residuals are the operands, as
    autodiff of ``ragged_dot`` keeps them."""
    return _product(x, w, counts.astype(jnp.int32), interpret)
