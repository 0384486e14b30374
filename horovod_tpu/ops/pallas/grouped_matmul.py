"""Grouped matrix products for sorted rows: ``lhs[rows of g] @ rhs[g]``.

``lax.ragged_dot`` is a Mosaic kernel of the TPU compiler's own, and the
compiler picks its tile from the widths alone: 512 rows by, in each width,
the largest of 512, 256 and 128 that DIVIDES it. A model whose widths 256
does not divide (2688 = 21 x 128, 1856 = 14.5 x 128) has every grouped
product and both transposes of it run in 512 x 128 x 128 tiles, at a tenth
of a v5e's peak where 512 x 512 x 512 reaches a third; the kernels here
reach 70 % on the same rows, and were a quarter to a third faster at widths
the compiler tiles well too (PERF.md, PR 34). They take their tiles from
the shapes of the call (:func:`pick_tiles`) and need no width to divide by
anything.

Three forms under one ``custom_vjp`` (:func:`grouped_matmul`), after the
pattern of ``jax.experimental.pallas.ops.tpu.megablox``:

- ``lhs @ rhs[g]`` and ``lhs @ rhs[g]^T`` (:func:`_gmm`): the contraction
  is ONE block, so a group's slab of weights stays in VMEM while the row
  tiles of the group pass under it (the pipeline fetches a block again
  only when its index changes), there is no accumulator and no remainder
  of the contraction to mask;
- ``lhs[rows of g]^T @ rhs[rows of g]`` (:func:`_tgmm`): a float32
  accumulator a (group, output tile), the row tiles the reduction.

Rows belong to groups by ``sizes`` alone, group g the ``sizes[g]`` rows
behind group g - 1's; a row tile that two groups share is visited once by
each, under a mask. Rows behind the last group are visited by no step:
the products leave them unwritten (they may hold anything, NaN included,
as ``lax.ragged_dot`` leaves them on the chip) and read none of them.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.pallas.flash_attention import _vma

# Rows a step. Smaller wastes less where a group ends inside a tile (a
# group of 768 rows touches 4 tiles of 256 and 2.5 of 512 on average: 1.33
# and 1.67 times its rows), and larger was no faster on a v5e at any shape
# tried (PERF.md, PR 34).
ROWS = 256
# Widest block of a width that is not contracted.
WIDTH_CAPS = (2048, 1024, 512)
# Bytes of VMEM a call may plan for (both buffers of every block, the
# float32 product and accumulator), under the limit the kernels ask for.
VMEM_BUDGET = 40 * 1024 * 1024
VMEM_LIMIT = 64 * 1024 * 1024


def _interpret():
    return jax.default_backend() != "tpu"


def _width_tile(width, cap):
    """Block of a width that is not contracted: the whole width up to
    ``cap``, else the largest multiple of 128 from 384 to ``cap`` that
    divides it, else an even split in multiples of 128 over a partial last
    block (the pipeline reads and writes the part that exists)."""
    if width <= cap:
        return width
    for tile in range(cap - cap % 128, 383, -128):
        if width % tile == 0:
            return tile
    blocks = -(-width // cap)
    return -(-width // (128 * blocks)) * 128


def _vmem_bytes(tm, tk, tn, k, n, itemsize):
    """What the hungriest of the three kernels keeps in VMEM."""
    def gmm(contracted, tile):
        blocks = tm * contracted + contracted * tile + tm * tile
        return 2 * itemsize * blocks + 4 * tm * tile
    tgmm = 2 * itemsize * (tm * tk + tm * tn + tk * tn) + 8 * tk * tn
    return max(gmm(k, tn), gmm(n, tk), tgmm)


def pick_tiles(m, k, n, itemsize=2):
    """``(tm, tk, tn)`` of the three products of ``(m, k) @ (groups, k,
    n)``, from the shapes alone: ``tm`` rows a step everywhere; the forward
    product writes ``(tm, tn)`` blocks under the whole of k, the product
    for ``lhs``'s gradient ``(tm, tk)`` blocks under the whole of n, the
    one for ``rhs``'s gradient accumulates ``(tk, tn)`` blocks. The widest
    blocks that fit VMEM; None where a slab of the whole contraction has
    no room there at any width (the caller keeps the compiler's
    product)."""
    tm = min(m, ROWS)
    for cap in WIDTH_CAPS:
        tk, tn = _width_tile(k, cap), _width_tile(n, cap)
        if _vmem_bytes(tm, tk, tn, k, n, itemsize) <= VMEM_BUDGET:
            return tm, tk, tn
    return None


def _visits(sizes, m, tm, empty_too):
    """What each step of a sweep over the row tiles works on, for the
    kernels' scalar memory: ``offsets`` (groups + 1: group g is rows
    ``offsets[g]`` to ``offsets[g + 1]``), and a step the ``group`` and row
    ``tile`` it visits, groups in order and a group's tiles in order; with
    them the number of steps to make. A group visits every tile it has a
    row in; an empty one visits one tile if ``empty_too`` (the product
    that owes it a block of zeros) and none otherwise."""
    groups, tiles = sizes.shape[0], pl.cdiv(m, tm)
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    count = jnp.where(sizes > 0, (ends + tm - 1) // tm - starts // tm,
                      int(empty_too))
    done = jnp.cumsum(count)
    step = jnp.arange(tiles + groups - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.sum(step[:, None] >= done[None, :], 1,
                                dtype=jnp.int32), groups - 1)
    tile = (starts // tm)[group] + step - (done - count)[group]
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return (offsets, group, jnp.clip(tile, 0, tiles - 1)), done[-1]


def _rows_of(offsets, group, tile, i, tm):
    """(first row, one past the last) of step i's group, the first row of
    its tile, and whether the tile lies wholly inside the group."""
    g = group[i]
    start, end, row0 = offsets[g], offsets[g + 1], tile[i] * tm
    return start, end, row0, (start <= row0) & (row0 + tm <= end)


def _params(interpret, grid_axes):
    """The row tiles, the last axis of a grid, go in order (a tile two
    groups share is held between their visits); the others may split."""
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (grid_axes - 1) + ("arbitrary",),
        vmem_limit_bytes=VMEM_LIMIT)


# Jitted with every choice static, as the flash kernels are: the layers of
# a model trace, lower and hash each kernel once.
@functools.partial(jax.jit,
                   static_argnames=("transposed", "tm", "tn", "interpret"))
def _gmm(lhs, rhs, sizes, *, transposed, tm, tn, interpret):
    """(m, n) in ``lhs``'s dtype: ``lhs[rows of g] @ rhs[g]`` for rhs
    (groups, k, n), or ``@ rhs[g]^T`` for rhs (groups, n, k) if
    ``transposed``; float32 sums."""
    m, k = lhs.shape
    n = rhs.shape[1] if transposed else rhs.shape[2]
    visits, steps = _visits(sizes, m, tm, False)

    def kernel(offsets, group, tile, lhs, rhs, out):
        start, end, row0, whole = _rows_of(offsets, group, tile,
                                           pl.program_id(1), tm)
        acc = lax.dot_general(
            lhs[...], rhs[...],
            (((1,), (1 if transposed else 0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(whole)
        def _():
            out[...] = acc.astype(out.dtype)

        @pl.when(jnp.logical_not(whole))
        def _():
            rows = row0 + lax.broadcasted_iota(jnp.int32, acc.shape, 0)
            out[...] = jnp.where((rows >= start) & (rows < end), acc,
                                 out[...].astype(jnp.float32)
                                 ).astype(out.dtype)

    def rhs_block(j, i, offsets, group, tile):
        return (group[i], j, 0) if transposed else (group[i], 0, j)
    return pl.pallas_call(
        kernel,
        name=f"hvd_gmm{'_t' if transposed else ''}_{tm}x{k}x{tn}",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(n, tn), steps),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, i, o, g, tile:
                             (tile[i], 0)),
                pl.BlockSpec((None, tn, k) if transposed else (None, k, tn),
                             rhs_block)],
            out_specs=pl.BlockSpec((tm, tn), lambda j, i, o, g, tile:
                                   (tile[i], j))),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype,
                                       vma=_vma(lhs, rhs, sizes)),
        compiler_params=_params(interpret, 2),
        interpret=interpret,
    )(*visits, lhs, rhs)


@functools.partial(jax.jit, static_argnames=("tm", "tk", "tn", "interpret"))
def _tgmm(lhs, rhs, sizes, *, tm, tk, tn, interpret):
    """(groups, k, n) in ``lhs``'s dtype: ``lhs[rows of g]^T @ rhs[rows of
    g]`` for lhs (m, k) and rhs (m, n), float32 sums; zeros for a group of
    no rows."""
    (m, k), n = lhs.shape, rhs.shape[1]
    visits, steps = _visits(sizes, m, tm, True)

    def kernel(offsets, group, tile, lhs, rhs, out, acc):
        i, last = pl.program_id(2), pl.num_programs(2) - 1
        start, end, row0, whole = _rows_of(offsets, group, tile, i, tm)

        @pl.when((i == 0) | (group[jnp.maximum(i - 1, 0)] != group[i]))
        def _():
            acc[...] = jnp.zeros_like(acc)

        def add(a, b):
            acc[...] += lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

        @pl.when(whole)
        def _():
            add(lhs[...], rhs[...])

        @pl.when(jnp.logical_not(whole) & (end > start))
        def _():
            rows = row0 + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
            live = (rows >= start) & (rows < end)

            def masked(x):      # rows of other groups, or of none: NaN too
                return jnp.where(live, x[...].astype(jnp.float32), 0
                                 ).astype(x.dtype)
            add(masked(lhs), masked(rhs))

        @pl.when((i == last) | (group[jnp.minimum(i + 1, last)] != group[i]))
        def _():
            out[...] = acc[...].astype(out.dtype)

    return pl.pallas_call(
        kernel,
        name=f"hvd_tgmm_{tm}x{tk}x{tn}",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(k, tk), pl.cdiv(n, tn), steps),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda a, b, i, o, g, tile:
                             (tile[i], a)),
                pl.BlockSpec((tm, tn), lambda a, b, i, o, g, tile:
                             (tile[i], b))],
            out_specs=pl.BlockSpec((None, tk, tn),
                                   lambda a, b, i, o, group, t:
                                   (group[i], a, b)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((sizes.shape[0], k, n), lhs.dtype,
                                       vma=_vma(lhs, rhs, sizes)),
        compiler_params=_params(interpret, 3),
        interpret=interpret,
    )(*visits, lhs, rhs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped_matmul(lhs, rhs, sizes, tiles):
    tm, _, tn = tiles
    return _gmm(lhs, rhs, sizes, transposed=False, tm=tm, tn=tn,
                interpret=_interpret())


def _grouped_matmul_fwd(lhs, rhs, sizes, tiles):
    return _grouped_matmul(lhs, rhs, sizes, tiles), (lhs, rhs, sizes)


def _grouped_matmul_bwd(tiles, res, g):
    (tm, tk, tn), (lhs, rhs, sizes) = tiles, res
    d_lhs = _gmm(g, rhs, sizes, transposed=True, tm=tm, tn=tk,
                 interpret=_interpret())
    d_rhs = _tgmm(lhs, g, sizes, tm=tm, tk=tk, tn=tn,
                  interpret=_interpret())
    return d_lhs, d_rhs, None


_grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def grouped_matmul(lhs, rhs, sizes, tiles=None):
    """``lhs[rows of g] @ rhs[g]``, (m, n) in ``lhs``'s dtype with float32
    sums, for lhs (m, k), rhs (groups, k, n) of the same dtype and
    ``sizes`` (groups,) whose sum is at most m; differentiable in ``lhs``
    and ``rhs``. Rows behind the last group are not written, here or in
    ``lhs``'s gradient. ``tiles`` default to :func:`pick_tiles`'s."""
    if tiles is None:
        tiles = pick_tiles(lhs.shape[0], *rhs.shape[1:], lhs.dtype.itemsize)
    if tiles is None:
        raise ValueError(f"no tiles for {lhs.shape} @ {rhs.shape} fit VMEM")
    return _grouped_matmul(lhs, rhs, sizes, tuple(tiles))
