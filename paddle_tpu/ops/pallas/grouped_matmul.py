"""Grouped matmul (Pallas, TPU): the expert layer's matmuls over a row
buffer sorted by expert, with tiles chosen from the operands' shapes.

Three forms, over `m` rows cut into E consecutive groups by `sizes`:

* `gmm(x [m, K], w [E, K, N]) -> [m, N]`: row r of group e times w[e];
* the same with `w [E, N, K]` read transposed inside the kernel (the index
  map swaps the block's coordinates, the contraction runs over both
  operands' last dimension): the gradient with respect to a grouped
  matmul's input, with no transposed copy of the weights in HBM;
* `tgmm(x [m, K], g [m, N]) -> [E, K, N]`: x[rows of e]^T g[rows of e],
  the gradient with respect to the weights.

The design is the megablox one (`jax.experimental.pallas.ops.tpu.
megablox`, and XLA's own lowering of `jax.lax.ragged_dot`): the rows are
walked in tiles of `tm`; a tile that straddles a group boundary is visited
once per group it holds rows of, under a row mask. `group_visits` makes the
visit tables from `sizes` once for every kernel that walks the same buffer,
and the kernels take them by scalar prefetch. The grid is STATIC,
`m / tm + E - 1` visits, the most there can be; the visits past the last
real one repeat its block indices (no copy is started) and skip the work.
Operands in their own dtype, float32 accumulation in VMEM, one rounding to
the result dtype.

What XLA's kernel does not do is choose its tiles for the shapes it is
given: it takes the largest power of two up to 512 that divides each
width, so `[65536, 2304] x [2304, 896]` runs under tiles (512, 256, 128)
and reads its row buffer N / 128 = 7 times (PERF.md section 6, PR 31).
`gmm_tiles` / `tgmm_tiles` here keep a WHOLE weight matrix resident
whenever the blocks fit `VMEM_BUDGET`: tn = N, so the row buffer is read
once a kernel, and tk = K, so consecutive tiles of one group name the same
weight block and it is fetched once a group, not once a row tile. Where a
matrix does not fit, tk shrinks first (the largest multiple of 128 that
divides K and fits), then tn. A width that is no multiple of 128 (1856 =
14.5 x 128) has ONE block, itself: a block dimension equal to the array's
is legal for Mosaic whatever it divides, VMEM holds it with its lanes
rounded up to 128 (the resident sums count that), and where the blocks do
not fit it is the other width that shrinks.

Contract: `sizes` sums to `m` (`ops/moe.py` `_whole_buffer`): every row
belongs to a group, so every row of the result is written. A width under
one lane tile (128) or no multiple of the sublane tile (8), or a row count
no row tile divides, is not taken: the tile functions return None and the
caller keeps `jax.lax.ragged_dot`.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode

_LANES = 128
# a width is also the second-minor dimension of a weight block ([tk, tn],
# [tn, tk] read transposed, tgmm's result): 8 rows are a float32 register;
# bf16 packs 16, and a full-width block that ends on half of one is padded
# by Mosaic like its lanes. What 8 keeps out are widths whose last register
# would hold a row or two
_SUBLANES = 8
# rows a visit: the largest power of two up to ROW_TILE that divides m. 256
# and 512 take the same time on a v5e and 1024 more (PERF.md section 6,
# PR 31); Mosaic unrolls a visit's matmul, so the kernel's code, which
# lives in HBM once per call site, grows with the row tile
ROW_TILE = 256
_MIN_ROW_TILE = 16             # bf16 packs 16 rows a register
VMEM_BUDGET = 40 << 20         # bytes the resident blocks may take


class Tiles(NamedTuple):
    tm: int
    tk: int
    tn: int
    resident_bytes: int


class Visits(NamedTuple):
    """The walk of one row buffer under one row tile: `offsets` [E + 1]
    rows at which the groups start; `group_ids`, `tile_ids` [m / tm + E - 1]
    the group and the row tile of each visit; `count` [1] the real visits."""
    offsets: jax.Array
    group_ids: jax.Array
    tile_ids: jax.Array
    count: jax.Array


def _row_tile(m: int):
    tm = ROW_TILE
    while tm >= _MIN_ROW_TILE and m % tm:
        tm //= 2
    return tm if tm >= _MIN_ROW_TILE else None


def _lane_divisors(dim: int):
    """The blocks a width may be cut into, largest first: the multiples of
    128 that divide it, or, for a width that is no multiple of 128, the
    width itself alone."""
    if dim % _LANES:
        return [dim]
    return [t for t in range(dim, 0, -_LANES) if dim % t == 0]


def _in_vmem(width: int):
    """What a block `width` wide occupies: whole lane tiles."""
    return -(-width // _LANES) * _LANES


def _gmm_resident(tm, tk, tn, in_bytes, out_bytes):
    # x and w blocks and the result block double-buffered; four float32
    # values of the result block's size: one visit's product, the
    # accumulator over k tiles, the masked store's old rows and its select
    tk, tn = _in_vmem(tk), _in_vmem(tn)
    return (2 * (tm * tk + tk * tn) * in_bytes + 2 * tm * tn * out_bytes
            + 4 * tm * tn * 4)


def _tgmm_resident(tm, tk, tn, in_bytes, out_bytes):
    # x and g blocks and the result block double-buffered; the float32
    # accumulator and one visit's product beside it; the masked operand
    tk, tn = _in_vmem(tk), _in_vmem(tn)
    return (2 * (tm * tk + tm * tn) * in_bytes + 2 * tk * tn * out_bytes
            + 2 * tk * tn * 4 + tm * min(tk, tn) * in_bytes)


def _pick(resident, m, k, n, in_bytes, out_bytes):
    tm = _row_tile(m)
    if tm is None or min(k, n) < _LANES or k % _SUBLANES or n % _SUBLANES:
        return None
    for tn in _lane_divisors(n):
        for tk in _lane_divisors(k):
            need = resident(tm, tk, tn, in_bytes, out_bytes)
            if need <= VMEM_BUDGET:
                return Tiles(tm, tk, tn, need)
    return None


def gmm_tiles(m, k, n, in_bytes=2, out_bytes=2):
    """Tiles of `gmm` at `[m, k] x [E, k, n]` (either orientation of w), or
    None where the kernel does not take the shape."""
    return _pick(_gmm_resident, m, k, n, in_bytes, out_bytes)


def tgmm_tiles(m, k, n, in_bytes=2, out_bytes=2):
    """Tiles of `tgmm` at `[m, k]^T [m, n]`: (tk, tn) is its result block,
    the rows are the contraction."""
    return _pick(_tgmm_resident, m, k, n, in_bytes, out_bytes)


def group_visits(sizes, m: int, tm: int) -> Visits:
    """The visit tables for `sizes` [E] (summing to m) under row tiles of
    `tm`. A group is visited once for every row tile it holds a row of, an
    empty group once (`tgmm` has its zeros to write; `gmm` skips it), in
    group order, so a tile's visits are consecutive. The visits past the
    last real one repeat it."""
    e = sizes.shape[0]
    tiles_m = m // tm
    n_visits = tiles_m + e - 1
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first_tile = jnp.minimum(starts // tm, tiles_m - 1)
    last_tile = jnp.where(sizes > 0, (ends - 1) // tm, first_tile)
    visits_of = last_tile - first_tile + 1
    upto = jnp.cumsum(visits_of)                       # visits through e
    count = upto[-1]
    visit = jnp.minimum(jnp.arange(n_visits, dtype=jnp.int32), count - 1)
    group_ids = jnp.sum(upto[None, :] <= visit[:, None], axis=1,
                        dtype=jnp.int32)
    # a visit's tile is its group's first tile plus its place among the
    # group's visits. By a one-hot reduce over [visits, E], a few thousand
    # elements: a gather of scalars is priced by the element on a TPU
    mine = group_ids[:, None] == jnp.arange(e, dtype=jnp.int32)[None, :]
    shift = (upto - visits_of - first_tile)[None, :]
    tile_ids = (visit - jnp.sum(jnp.where(mine, shift, 0), axis=1)).astype(
        jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return Visits(offsets, group_ids, tile_ids, count.reshape(1))


def _compiler_params(resident_bytes, semantics):
    """Mosaic's default 16 MiB of scoped VMEM where the blocks fit it with
    room for the compiler's own scratch; else what they need, inside the
    chip's 128 MiB (as `flash_attention._compiler_params`)."""
    need = resident_bytes + (8 << 20)
    extra = {}
    if need > (16 << 20):
        extra["vmem_limit_bytes"] = min(need, 100 << 20)
    return pltpu.CompilerParams(dimension_semantics=semantics, **extra)


def _rows_of_group(offsets, group_ids, tile_ids, v, tm):
    """(first row of the visit's tile, the group's first row, its end)."""
    g = group_ids[v]
    return tile_ids[v] * tm, offsets[g], offsets[g + 1]


def _gmm_kernel(offsets, group_ids, tile_ids, count, x_ref, w_ref, o_ref,
                *acc, tm, k_tiles, transpose_rhs):
    v, ki = pl.program_id(1), pl.program_id(2)
    row0, start, end = _rows_of_group(offsets, group_ids, tile_ids, v, tm)
    dims = (((1,), (1,)), ((), ())) if transpose_rhs \
        else (((1,), (0,)), ((), ()))

    def store(val):
        inside = (row0 >= start) & (row0 + tm <= end)

        @pl.when(inside)
        def _whole():
            o_ref[...] = val.astype(o_ref.dtype)

        @pl.when(jnp.logical_not(inside))
        def _masked():
            rows = row0 + jax.lax.broadcasted_iota(jnp.int32, val.shape, 0)
            keep = (rows >= start) & (rows < end)
            o_ref[...] = jnp.where(
                keep, val, o_ref[...].astype(jnp.float32)).astype(o_ref.dtype)

    @pl.when((v < count[0]) & (end > start))
    def _visit():
        part = jax.lax.dot_general(x_ref[...], w_ref[...], dims,
                                   preferred_element_type=jnp.float32)
        if k_tiles == 1:
            store(part)
            return
        acc_ref, = acc

        @pl.when(ki == 0)
        def _first():
            acc_ref[...] = part

        @pl.when(ki > 0)
        def _rest():
            acc_ref[...] += part

        @pl.when(ki == k_tiles - 1)
        def _last():
            store(acc_ref[...])


def gmm(x, w, visits: Visits, *, tiles: Tiles, transpose_rhs=False,
        out_dtype=None):
    """x [m, K] times, row group by row group, w[e]: `[E, K, N]`, or
    `[E, N, K]` read transposed. `visits` = `group_visits(sizes, m,
    tiles.tm)`. One trace per (shapes, tiles) however many calls a program
    makes: the inner jit's cache."""
    return _gmm(x, w, visits, tiles=tiles, transpose_rhs=transpose_rhs,
                out_dtype=jnp.dtype(out_dtype or x.dtype),
                interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("tiles", "transpose_rhs",
                                             "out_dtype", "interpret"))
def _gmm(x, w, visits, *, tiles, transpose_rhs, out_dtype, interpret):
    m, k = x.shape
    n = w.shape[1] if transpose_rhs else w.shape[2]
    tm, tk, tn, resident = tiles
    k_tiles, n_tiles = k // tk, n // tn
    n_visits = visits.group_ids.shape[0]

    def x_map(ni, v, ki, offsets, group_ids, tile_ids, count):
        return tile_ids[v], ki

    def w_map(ni, v, ki, offsets, group_ids, tile_ids, count):
        return (group_ids[v], ni, ki) if transpose_rhs \
            else (group_ids[v], ki, ni)

    def o_map(ni, v, ki, offsets, group_ids, tile_ids, count):
        return tile_ids[v], ni

    w_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)
    # a matrix that is one block is fetched when the group changes, a k
    # tile with every visit
    weight_elems = n_visits * k * n if k_tiles > 1 else w.size
    call = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, k_tiles=k_tiles,
                          transpose_rhs=transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_tiles, n_visits, k_tiles),
            in_specs=[pl.BlockSpec((tm, tk), x_map),
                      pl.BlockSpec(w_block, w_map)],
            out_specs=pl.BlockSpec((tm, tn), o_map),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]
            if k_tiles > 1 else []),
        compiler_params=_compiler_params(
            resident, ("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k * n_tiles * x.dtype.itemsize
                            + weight_elems * w.dtype.itemsize
                            + m * n * out_dtype.itemsize)),
        interpret=interpret,
        name="ragged-dot-gmm-t" if transpose_rhs else "ragged-dot-gmm",
    )
    return call(*visits, x, w)


def _tgmm_kernel(offsets, group_ids, tile_ids, count, x_ref, g_ref, o_ref,
                 acc_ref, *, tm, mask_x):
    v, last_v = pl.program_id(2), pl.num_programs(2) - 1
    row0, start, end = _rows_of_group(offsets, group_ids, tile_ids, v, tm)
    group = group_ids[v]
    opens = (v == 0) | (group_ids[jnp.maximum(v - 1, 0)] != group)
    closes = (v == last_v) | (group_ids[jnp.minimum(v + 1, last_v)] != group)

    @pl.when(opens)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((v < count[0]) & (end > start))
    def _visit():
        # the other groups' rows of ONE operand (the narrower) as zeros, in
        # every visit: a select over one block where a second copy of the
        # matmul for the unmasked case would double the kernel's code
        ref = x_ref if mask_x else g_ref
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, ref.shape, 0)
        cut = jnp.where((rows >= start) & (rows < end), ref[...],
                        jnp.zeros_like(ref))
        x, g = (cut, g_ref[...]) if mask_x else (x_ref[...], cut)
        acc_ref[...] += jax.lax.dot_general(
            x, g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(closes)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def tgmm(x, g, visits: Visits, *, tiles: Tiles, out_dtype=None):
    """[E, K, N]: x[rows of e]^T g[rows of e] for x [m, K], g [m, N]; zeros
    for an empty group. `visits` = `group_visits(sizes, m, tiles.tm)`."""
    return _tgmm(x, g, visits, tiles=tiles,
                 out_dtype=jnp.dtype(out_dtype or g.dtype),
                 interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("tiles", "out_dtype",
                                             "interpret"))
def _tgmm(x, g, visits, *, tiles, out_dtype, interpret):
    m, k = x.shape
    n = g.shape[1]
    tm, tk, tn, resident = tiles
    e = visits.offsets.shape[0] - 1
    n_visits = visits.group_ids.shape[0]

    def x_map(ni, ki, v, offsets, group_ids, tile_ids, count):
        return tile_ids[v], ki

    def g_map(ni, ki, v, offsets, group_ids, tile_ids, count):
        return tile_ids[v], ni

    def o_map(ni, ki, v, offsets, group_ids, tile_ids, count):
        return group_ids[v], ki, ni

    call = pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm, mask_x=tk <= tn),
        out_shape=jax.ShapeDtypeStruct((e, k, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, k // tk, n_visits),
            in_specs=[pl.BlockSpec((tm, tk), x_map),
                      pl.BlockSpec((tm, tn), g_map)],
            out_specs=pl.BlockSpec((None, tk, tn), o_map),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=_compiler_params(
            resident, ("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k * (n // tn) * x.dtype.itemsize
                            + m * n * (k // tk) * g.dtype.itemsize
                            + e * k * n * out_dtype.itemsize)),
        interpret=interpret,
        name="ragged-dot-tgmm",
    )
    return call(*visits, x, g)
