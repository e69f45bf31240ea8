"""The indexer's scores (Pallas, TPU): `ops/sparse_index.py`'s `_index_fwd`
score and `_scores_bwd` as two kernels in which a (query block, key block)
tile's products, their relu and the sum over the heads live in VMEM.

Grid (batch, query block, key block), the key block last. `QI` [B, H, S, D]
is read in blocks `[H, bq, D]` (fetched once a query block: the block index
does not move with the key block), `KI` [B, S, D] in blocks `[bk, D]`, `W`
[B, S, H] float32 in blocks `[bq, H]`. A tile wholly above the diagonal
does no product (its key block's index is clamped to the diagonal's, so
nothing is fetched for it either); a tile the diagonal crosses masks
inside.

Inside a tile the queries go `sub` rows at a time, the rows of all H heads
stacked into ONE product `[H * sub, D] x [D, bk]` (the key block stays in
the matrix unit for all of them), float32:

* forward: `sum_j W[:, j] relu(p_j)` head after head into a `[sub, bk]`
  float32 value, `+ 0.0`, `-inf` above the diagonal, stored once: the score
  tile of `Scores` [B, S, S];
* backward of `sum(I * ds)`: `dW[:, j] += sum_s ds relu(p_j)`, `g_j =
  where(p_j > 0, ds, 0) * W[:, j]` rounded to the operands' dtype into a
  `[H * sub, bk]` scratch, then two products of the stacked `g`: `dQI +=
  g KI` (a `[H, bq, D]` float32 scratch, written when the query block
  closes) and `dKI^T += QI^T g`. `KI` is one head, so its gradient of a
  whole row, `[D, S]` float32 with the positions along the lanes, IS the
  output block, resident from a batch row's first tile to its last: one
  kernel and three products a tile, where flash's pair of kernels would
  make the tile's products twice. `dW` `[bq, H]` float32 is accumulated in
  its output block likewise.

`ops/sparse_index.py`'s `jax.numpy` form is the specification: operands in
their own dtype, every product accumulated in float32, relu, weighting and
the sums in float32, `g` rounded where `_scores_bwd` rounds it. The heads
are summed first to last; XLA's order is its own, so a score may differ in
the last place.

`plan` is the shape rule: a head width that is a multiple of 64, a row that
a block of 128 or more divides, bf16 or float32 operands, the resident
blocks inside `VMEM_BUDGET`. What it leaves keeps the `jax.numpy` form.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode
from .grouped_matmul import _compiler_params
from .kda_chunk import _HI, _iota, _nn, _nt, _tn

_F32 = jnp.float32
_LANES = 128
VMEM_BUDGET = 40 << 20         # bytes the resident blocks may take
# queries (keys) a tile, largest first: the first that divides the row and
# fits
_BLOCKS = (512, 256, 128)
# query rows an inner step, largest first: the first whose stacked products
# fit. With all the heads' rows stacked an inner step is one product, and
# the longer it is the less the matrix unit waits (on a v5e at the cell's
# size 16, 32, 64, 128 rows read 1.73, 1.35, 1.20, 1.12 ms a forward launch:
# `PERF.md` section 6, PR 44)
_SUBS = (128, 64, 32)
# (batch, query block, key block): the backward carries dKI over both
_SEMANTICS = ("parallel", "arbitrary", "arbitrary")


class Plan(NamedTuple):
    """Queries and keys a tile, the query rows an inner step of it takes,
    and what a grid step keeps in VMEM."""
    block_q: int
    block_k: int
    sub: int
    resident_bytes: int


def plan(q_shape, dtype=jnp.bfloat16, blocks=None):
    """The kernels' plan for QI [B, H, S, D], or None where they do not take
    the shape. `blocks` (block_q, block_k, sub) in place of the rule's own
    is the handle of `chip_smoke.py`'s sweep."""
    _, h, s, d = q_shape
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(_F32)):
        return None
    if d % 64:
        return None
    size = dtype.itemsize

    def padded(n):
        return -(-n // _LANES) * _LANES

    def resident(bq, bk, sub):
        """The backward's blocks, double-buffered: QI, KI, W, ds in; dQI,
        dKI^T, dW out; beside dQI's scratch, the stacked products, g and
        what the heads' elementwise work keeps. None where the blocks do
        not tile the row or do not fit."""
        if s % bq or s % bk or bq % sub or bk % _LANES or sub % 16:
            return None
        q_block = h * bq * padded(d)
        w_block = bq * padded(h) * 4
        need = (2 * (q_block * size + bk * padded(d) * size + w_block
                     + bq * bk * 4)
                + 2 * (q_block * size + d * s * 4 + w_block)
                + q_block * 4
                + 2 * h * sub * bk * (4 + size))
        return need if need <= VMEM_BUDGET else None

    if blocks is None:
        blocks = next(((n, n, sub) for n in _BLOCKS for sub in _SUBS
                       if resident(n, n, sub)), None)
    need = blocks and resident(*blocks)
    return Plan(*blocks, need) if need else None


def _last_key_block(i, bq, bk):
    """The last key block a query block i reaches at or under the
    diagonal."""
    return (i * bq + bq - 1) // bk


def _causal(i, j, r, sub, bq, bk):
    """[sub, bk]: the key is no later than the query, for rows r * sub ..
    of tile (i, j)."""
    row = i * bq + r * sub + _iota((sub, bk), 0)
    return row >= j * bk + _iota((sub, bk), 1)


def _stacked(q_ref, at):
    """Rows `at` of every head, one under the other: [H * sub, D]."""
    h, _, d = q_ref.shape
    return q_ref[:, at, :].reshape(h * at.size, d)


def _fwd_kernel(q_ref, k_ref, w_ref, o_ref, *, sub):
    i, j = pl.program_id(1), pl.program_id(2)
    h, bq, _ = q_ref.shape
    bk = k_ref.shape[0]
    reached = j <= _last_key_block(i, bq, bk)

    @pl.when(jnp.logical_not(reached))
    def _above():
        o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, _F32)

    @pl.when(reached)
    def _scored():
        k = k_ref[...]
        hi = _HI if k.dtype == _F32 else None

        def rows(r, carry):
            at = pl.ds(pl.multiple_of(r * sub, sub), sub)
            p = _nt(_stacked(q_ref, at), k, hi)               # [H * sub, bk]
            w = w_ref[at, :]
            acc = jnp.zeros((sub, bk), _F32)
            for n in range(h):
                acc = acc + w[:, n:n + 1] * jnp.maximum(
                    p[n * sub:(n + 1) * sub], 0.0)
            # + 0.0: a sum of -0.0 terms is -0.0 (`_index_fwd`)
            o_ref[at, :] = jnp.where(_causal(i, j, r, sub, bq, bk),
                                     acc + 0.0, -jnp.inf)
            return carry

        jax.lax.fori_loop(0, bq // sub, rows, 0)


def _bwd_kernel(q_ref, k_ref, w_ref, ds_ref, dq_ref, dkt_ref, dw_ref,
                dq_acc, g_ref, *, sub):
    i, j = pl.program_id(1), pl.program_id(2)
    h, bq, d = q_ref.shape
    bk = k_ref.shape[0]

    @pl.when((i == 0) & (j == 0))
    def _open_row():
        dkt_ref[...] = jnp.zeros_like(dkt_ref)

    @pl.when(j == 0)
    def _open_block():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(j <= _last_key_block(i, bq, bk))
    def _tile():
        k = k_ref[...]
        hi = _HI if k.dtype == _F32 else None
        cols = pl.ds(pl.multiple_of(j * bk, bk), bk)
        head = _iota((sub, h), 1)

        def rows(r, carry):
            at = pl.ds(pl.multiple_of(r * sub, sub), sub)
            q = _stacked(q_ref, at)
            p = _nt(q, k, hi)                                 # [H * sub, bk]
            ds = jnp.where(_causal(i, j, r, sub, bq, bk), ds_ref[at, :], 0.0)
            w = w_ref[at, :]
            dw = jnp.zeros((sub, h), _F32)
            for n in range(h):
                pn = p[n * sub:(n + 1) * sub]
                dw = jnp.where(head == n, jnp.sum(
                    ds * jnp.maximum(pn, 0.0), axis=1, keepdims=True), dw)
                g_ref[n * sub:(n + 1) * sub, :] = (
                    jnp.where(pn > 0, ds, 0.0) * w[:, n:n + 1]).astype(
                        g_ref.dtype)
            g = g_ref[...]
            dq_acc[:, at, :] += _nn(g, k, hi).reshape(h, sub, d)
            dkt_ref[:, cols] += _tn(q, g, hi)                 # [D, bk]
            dw_ref[at, :] += dw
            return carry

        jax.lax.fori_loop(0, bq // sub, rows, 0)

    @pl.when(j == pl.num_programs(2) - 1)
    def _close_block():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _specs(plan_, h, d):
    """The blocks both kernels read, in the order of their leading
    arguments (QI, KI, W), and the map of a [bq, bk] tile of an [S, S]
    array, the key block clamped to the diagonal's."""
    bq, bk, _, _ = plan_

    def reached(i, j):
        return jnp.minimum(j, _last_key_block(i, bq, bk))

    q = pl.BlockSpec((None, h, bq, d), lambda b, i, j: (b, 0, i, 0))
    k = pl.BlockSpec((None, bk, d), lambda b, i, j: (b, reached(i, j), 0))
    w = pl.BlockSpec((None, bq, h), lambda b, i, j: (b, i, 0))
    tile = pl.BlockSpec((None, bq, bk), lambda b, i, j: (b, i, reached(i, j)))
    return q, k, w, tile


def _causal_pairs(s):
    return s * (s + 1) // 2


def scores_fwd(plan_, q, k, w):
    """`ops/sparse_index.py`'s scores under `plan_`: I [B, S, S] float32,
    -inf above the diagonal, from QI [B, H, S, D], KI [B, S, D] in QI's
    dtype and W [B, S, H]. One trace per (shapes, plan) however many layers
    call it: the inner jit's cache."""
    return _scores_fwd(plan_, q, k, w.astype(_F32),
                       interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("plan_", "interpret"))
def _scores_fwd(plan_, q, k, w, *, interpret):
    b, h, s, d = q.shape
    bq, bk, sub, resident = plan_
    q_block, k_block, w_block, _ = _specs(plan_, h, d)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, sub=sub),
        out_shape=jax.ShapeDtypeStruct((b, s, s), _F32),
        grid=(b, s // bq, s // bk),
        in_specs=[q_block, k_block, w_block],
        out_specs=pl.BlockSpec((None, bq, bk), lambda b, i, j: (b, i, j)),
        compiler_params=_compiler_params(resident, _SEMANTICS),
        cost_estimate=pl.CostEstimate(
            flops=b * h * _causal_pairs(s) * (2 * d + 3), transcendentals=0,
            bytes_accessed=(q.nbytes + k.nbytes * (s // bq) // 2 + w.nbytes
                            + 4 * b * s * s)),
        interpret=interpret,
        name="index-scores-fwd",
    )(q, k, w)


def scores_bwd(plan_, q, k, w, ds):
    """`ops/sparse_index.py` `_scores_bwd` under `plan_`: (dQI, dKI, dW) of
    sum(I * ds), in the dtypes of QI, KI, W."""
    dq, dkt, dw = _scores_bwd(plan_, q, k, w.astype(_F32), ds.astype(_F32),
                              interpret=interpret_mode())
    return dq, dkt.transpose(0, 2, 1).astype(k.dtype), dw.astype(w.dtype)


@functools.partial(jax.jit, static_argnames=("plan_", "interpret"))
def _scores_bwd(plan_, q, k, w, ds, *, interpret):
    b, h, s, d = q.shape
    bq, bk, sub, resident = plan_
    q_block, k_block, w_block, tile = _specs(plan_, h, d)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, sub=sub),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, d, s), _F32),
                   jax.ShapeDtypeStruct((b, s, h), _F32)),
        grid=(b, s // bq, s // bk),
        in_specs=[q_block, k_block, w_block, tile],
        out_specs=(q_block,
                   pl.BlockSpec((None, d, s), lambda b, i, j: (b, 0, 0)),
                   w_block),
        scratch_shapes=[pltpu.VMEM((h, bq, d), _F32),
                        pltpu.VMEM((h * sub, bk), q.dtype)],
        compiler_params=_compiler_params(resident, _SEMANTICS),
        cost_estimate=pl.CostEstimate(
            flops=b * h * _causal_pairs(s) * (6 * d + 8), transcendentals=0,
            bytes_accessed=(2 * q.nbytes + k.nbytes * (s // bq) // 2
                            + 2 * w.nbytes + 4 * b * _causal_pairs(s)
                            + 4 * b * d * s)),
        interpret=interpret,
        name="index-scores-bwd",
    )(q, k, w, ds)
