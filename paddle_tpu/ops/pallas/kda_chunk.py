"""The gated delta rule's chunks (Pallas, TPU): `ops/kda.py`'s `_kda_fwd`
and `_kda_bwd` as two kernels in which everything a chunk makes (the
running sums, the decayed rows, the `[L, L]` products, the solve, `U`, `W`)
lives in VMEM and the state is carried in scratch.

Grid (batch, head block, chunk), the chunk axis last and sequential; one
grid step handles `heads` heads, each a 128-lane column block of the
projection's own `[B, S, H * 128]` view, so q, k, v, `G`, `Y` and the
gradients are neither cut nor copied in front of a call. `Beta` `[B, S, H]`
float32 is 4 bytes a head and token and is wanted down the sublanes (a
position's factor for its row): it is cut outside the kernel into
`[B, H / heads, S, heads]`, and its gradient comes back the same way.
`States` `[B, S / L, H * K, V]` float32 in blocks `(heads * K, V)` is the
state the chunk STARTS from, copied out of the scratch before the update.

The backward walks the chunks in reverse in the same grid, `lam` (the
closing state's cotangent) in scratch where the forward has the state, and
makes a chunk's matrices and its solve again from the rows. `_kda_bwd` is
its specification, `_kda_fwd` the forward's: the same values are rounded to
q's dtype at the same places, everything else is float32.

Inside a chunk (`_Chunk`), per head, with `G` the running sum of g (a
log-step scan down the sublanes):

* the decayed products `K+ K-^T`, `Q+ K-^T` a row block of `_SUB` = 16
  positions at a time, around the running sum `ref_a` at the block's start:
  the block's rows `x exp(G - ref_a)` (at most 1) against ONE decayed copy
  of K, `k exp(ref_a - G_m)` for the positions m of this block and the ones
  before it (at most `exp(-16 min g)`), the k and q rows of a block stacked
  into one `[32, K] x [K, L]` product: four products a chunk;
* the solve as an explicit `T = (I + A)^-1`, float32 at
  `Precision.HIGHEST` (`_inverses`): the `_SUB` x `_SUB` diagonal blocks by
  substitution over their rows, all of a chunk's blocks packed side by side
  along the lanes (15 steps, each a multiply and a sum down the sublanes),
  then the off-diagonal blocks by products, `T <- T - T A_off T`, doubling
  the block until it is the chunk. A Neumann doubling over the whole
  `[L, L]` cancels (powers of a strictly lower matrix of ones reach 1e17 at
  L = 64 where the inverse's entries are 1) and is not used. `[U | W] = T
  [beta V | beta K+]` is then one product, and the backward's `T^T d[U |
  W]` and `dA` are products too. The solve is a long chain of dependent
  steps, so the heads of a grid step go through it together, step by step
  (on a v5e at the cell's size a forward launch takes 2.1 ms that way and
  4.0 head after head; 0.67 with the solve left out: `PERF.md` section 6,
  PR 40).

`plan` is the shape rule: K and V one lane tile each (128), the chunk 32,
64 or 128 (blocks of `_SUB` that double to it, whole sublane tiles of
either dtype), the row a whole number of chunks, bf16 or float32 operands,
the blocks inside `VMEM_BUDGET`. What it leaves keeps `ops/kda.py`'s
`jax.numpy` form.
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

_F32 = jnp.float32
_LANES = 128
_SUB = 16                      # `ops/kda.py` `_SUB`: a row block's positions
_HI = jax.lax.Precision.HIGHEST
VMEM_BUDGET = 40 << 20         # bytes the resident blocks may take
# (batch, head block, chunk): the chunk axis carries the state
_SEMANTICS = ("parallel", "parallel", "arbitrary")


class Plan(NamedTuple):
    """What the kernels are built from: `heads` a grid step handles, of `d`
    features each (K and V); `chunk` L; `steps` the grid's middle axis
    (H / heads); `chunks` its last; and what a step keeps in VMEM."""
    heads: int
    d: int
    chunk: int
    steps: int
    chunks: int
    resident_bytes: int
    exact: bool = False


def plan(q_shape, v_shape, chunk, dtype=jnp.bfloat16, heads=None,
         exact=False):
    """The kernels' plan for q, k [B, S, H, K], v [B, S, H, V] in chunks of
    `chunk`, or None where they do not take the shape. `heads` a grid step
    (a divisor of H; by default the most that divides H, up to 4: the
    sweep reads 4, 8 and 16 alike and 2 and 1 slower, `PERF.md` section 6,
    PR 40) is the handle of `chip_smoke.py`'s sweep. `exact`: g has no
    bound, and a block against itself is made level by level (`_Chunk`)."""
    _, s, h, d = q_shape
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(_F32)):
        return None
    if d != _LANES or v_shape[3] != _LANES or chunk not in (32, 64, 128) \
            or s % chunk:
        return None
    if heads is None:
        heads = max(j for j in (1, 2, 4) if h % j == 0)
    if h % heads:
        return None
    size = dtype.itemsize
    # the backward's blocks, double-buffered: q, k, v, do and dq, dk, dv; g
    # and dg; States; beside the scratch and some forty [L, d] and a dozen
    # [L, L] float32 values of a head's work, twice over (the heads of a
    # step share the solve's steps; the rest of a head's values die with it)
    rows = chunk * heads * d
    resident = (2 * (7 * rows * size + 2 * rows * 4 + heads * d * d * 4)
                + heads * d * d * 4
                + 2 * (40 * chunk * d + 12 * chunk * chunk) * 4)
    if exact:
        # the levels' sums [4 L, L], their decays and decayed rows [4 L, d]
        resident += 4 * len(_halves(chunk)) * chunk * (chunk + 3 * d)
    if resident > VMEM_BUDGET:
        return None
    return Plan(heads, d, chunk, h // heads, s // chunk, resident,
                bool(exact))


def _dot(a, b, contract, precision=None):
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=precision,
                               preferred_element_type=_F32)


def _nn(a, b, precision=None):
    """a [m, k] b [k, n]"""
    return _dot(a, b, ((1,), (0,)), precision)


def _nt(a, b, precision=None):
    """a [m, k] b [n, k]^T"""
    return _dot(a, b, ((1,), (1,)), precision)


def _tn(a, b, precision=None):
    """a [k, m]^T b [k, n]"""
    return _dot(a, b, ((0,), (0,)), precision)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _block(i):
    """The `_SUB` block a position lies in."""
    return jax.lax.shift_right_logical(i, 4)


def _running_sum(x, reverse=False):
    """The running sum down the sublanes of x [L, d] (from the last row up
    if `reverse`), float32: log2(L) steps of a rotation and an add."""
    n = x.shape[0]
    row = _iota((n, 1), 0)
    shift = 1
    while shift < n:
        if reverse:
            moved = jnp.where(row < n - shift, pltpu.roll(x, n - shift, 0),
                              0.0)
        else:
            moved = jnp.where(row >= shift, pltpu.roll(x, shift, 0), 0.0)
        x = x + moved
        shift *= 2
    return x


def _down_rows(row_vec, like):
    """row_vec [1, n] along the lanes -> [n, m] as `like`, entry k of it in
    every column of row k: a factor a row of `like` has. One transpose of
    the broadcast rows (a sum along the lanes of a masked square read the
    same time on the chip and is more code)."""
    n, m = like.shape
    return jnp.broadcast_to(row_vec, (m, n)).T


def _row_sums_along_lanes(x):
    """sum_v x[k, v] as a row [1, n] along the lanes, for x [n, m]: the
    sum down the sublanes of the transpose."""
    return jnp.sum(x.T, axis=0, keepdims=True)


def _inverses(mats):
    """(I + a)^-1 for each a [L, L] strictly lower of `mats`, float32:
    lower, ones on the diagonal. The `_SUB` x `_SUB` diagonal blocks by
    substitution over the rows of the transpose, last row first (`U_b[i] =
    e_i - sum_{j > i} a_b[j, i] U_b[j]`: a column of `a` is a lane slice),
    the L / 16 blocks side by side along the lanes of one [16, L] value;
    then `T <- T - T a_off T` with `a_off` the blocks right under the
    diagonal of twice the size, until the block is the chunk. Every step
    runs over all the matrices before the next one starts: a step's chain
    of dependent operations is long and one matrix's leaves the units
    idle."""
    n = mats[0].shape[0]
    nb = n // _SUB
    lane_block = _block(_iota((_SUB, n), 1))
    row = _iota((_SUB, n), 0)
    within = _iota((_SUB, n), 1) - lane_block * _SUB
    # packed[j, 16 b + i] = a[16 b + j, 16 b + i]
    packed = []
    for a in mats:
        p = a[:_SUB, :]
        for b in range(1, nb):
            p = jnp.where(lane_block == b, a[b * _SUB:(b + 1) * _SUB, :], p)
        packed.append(p)
    us = [jnp.where(row == within, 1.0, 0.0)] * len(mats)
    for i in range(_SUB - 2, -1, -1):
        unit = jnp.where(within == i, 1.0, 0.0)
        for h, p in enumerate(packed):
            # column i of every block, over its block's lanes
            factor = p[:, i:i + 1]
            for b in range(1, nb):
                at = b * _SUB + i
                factor = jnp.where(lane_block == b, p[:, at:at + 1], factor)
            below = jnp.sum(factor * us[h], axis=0, keepdims=True)
            us[h] = jnp.where(row == i, unit - below, us[h])
    # block b's [16, 16] back onto the diagonal of [L, L], transposed
    ts = [jnp.concatenate([jnp.where(lane_block == b, u, 0.0)
                           for b in range(nb)], axis=0).T for u in us]
    r, c = _iota((n, n), 0), _iota((n, n), 1)
    size = _SUB
    while size < n:
        bits = size.bit_length()
        under = ((jax.lax.shift_right_logical(r, bits)
                  == jax.lax.shift_right_logical(c, bits))
                 & (jax.lax.shift_right_logical(r, bits - 1)
                    > jax.lax.shift_right_logical(c, bits - 1)))
        xs = [_nn(jnp.where(under, a, 0.0), t, _HI) for a, t in zip(mats, ts)]
        ts = [t - _nn(t, x, _HI) for t, x in zip(ts, xs)]
        size *= 2
    return ts


def _halves(chunk):
    """`ops/kda.py` `_halves`: 32, 16, 8, 4, 2, 1 at a chunk of 64."""
    return [1 << i for i in reversed(range((chunk - 1).bit_length()))]


def _levels(chunk):
    """`ops/kda.py` `_level` for every half of a chunk, made from iotas
    once a grid step: (sums [n L, L] bf16, 0 / 1: row l of level i adds up
    the g's `D_l` is made of; the levels' pairs, n masks [L, L])."""
    r, c = _iota((chunk, chunk), 0), _iota((chunk, chunk), 1)
    sums, pairs = [], []
    for half in _halves(chunk):
        block = ~(2 * half - 1)
        middle = jnp.bitwise_and(r, block) + half
        upper = r >= middle
        adds = ((upper & (c >= middle) & (c <= r))
                | (~upper & (c > r) & (c < middle)))
        sums.append(jnp.where(adds, 1.0, 0.0))
        pairs.append((jnp.bitwise_and(c, block) + half == middle) & upper
                     & (c < middle))
    return jnp.concatenate(sums, axis=0).astype(jnp.bfloat16), pairs


def _thirds(x):
    """x float32 as three bf16 values that add up to it: a product with a
    matrix of 0s and 1s (exact in bf16) is then three passes, not the six
    of `Precision.HIGHEST`."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(_F32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(_F32)).astype(jnp.bfloat16)


def _shift(x, down):
    """x [L, d] moved one row down (row 0 reads 0) or up (the last does)."""
    n = x.shape[0]
    row = _iota((n, 1), 0)
    if down:
        return jnp.where(row >= 1, pltpu.roll(x, 1, 0), 0.0)
    return jnp.where(row < n - 1, pltpu.roll(x, n - 1, 0), 0.0)


class _Chunk:
    """What a chunk makes of one head's rows (`ops/kda.py` `_local`): q, k,
    v [L, d] in the compute dtype, g [L, d] and beta [L, 1] float32.
    `levels` (`_levels`): g has no bound, and the decayed products are made
    level by level; None: around the running sums at the blocks' starts."""

    def __init__(self, q, k, v, g, beta, levels=None):
        chunk, d = k.shape
        cdt = self.cdt = k.dtype
        self.hi = _HI if cdt == _F32 else None
        self.qf, self.kf, self.vf = (t.astype(_F32) for t in (q, k, v))
        self.beta = beta
        cum = self.cum = _running_sum(g)
        self.from_start = jnp.exp(cum)
        r, c = _iota((chunk, chunk), 0), _iota((chunk, chunk), 1)
        self.strict, self.lower = r > c, r >= c
        self.total = cum[chunk - 1:chunk, :]                  # [1, d]
        if levels is None:
            mqk = self._products_around_block_starts()
            self.to_end = jnp.exp(self.total - cum)
        else:
            mqk = self._products_by_levels(g, levels, r == c)
            # the g's after a position, summed from the chunk's end
            self.to_end = jnp.exp(_running_sum(_shift(g, down=False),
                                               reverse=True))
        self.pqkc = jnp.where(self.lower, mqk, 0.0).astype(cdt)
        self.kplus = self.kf * self.from_start
        self.a = jnp.where(self.strict, beta * self.mkk, 0.0)
        self.kend = self.kf * self.to_end
        self.kendc = self.kend.astype(cdt)
        self.qplus = self.qf * self.from_start
        self.qpc = self.qplus.astype(cdt)
        self.decay = jnp.exp(self.total)                      # [1, d]

    def _products_around_block_starts(self):
        """g above -88 / 16 a token: sets `mkk` and what the backward reads
        (`rows`, `back`, `k_back`, `to_row`), returns `Q+ K-^T` unmasked."""
        cum, cdt = self.cum, self.cdt
        chunk, d = cum.shape
        nb = chunk // _SUB
        row_block = self.row_block = _block(_iota((chunk, 1), 0))
        # the running sum before a block's first position, and a row's own
        self.refs = [jnp.zeros((1, d), _F32)] + [
            cum[a * _SUB - 1:a * _SUB, :] for a in range(1, nb)]
        ref_rows = jnp.concatenate(
            [jnp.broadcast_to(ref, (_SUB, d)) for ref in self.refs], axis=0)
        self.to_row = jnp.exp(cum - ref_rows)                 # <= 1
        xk = (self.kf * self.to_row).astype(cdt)
        xq = (self.qf * self.to_row).astype(cdt)
        # block a's rows, k over q [32, d]; the decay of K towards block a's
        # start [L, d] (1 on the later blocks' rows, which the masks drop)
        # and K under it
        self.rows, self.back, self.k_back = [], [], []
        mkk, mqk = [], []
        for a in range(nb):
            at = slice(a * _SUB, (a + 1) * _SUB)
            rows = jnp.concatenate([xk[at], xq[at]], axis=0)
            back = jnp.exp(jnp.where(row_block <= a, self.refs[a] - cum,
                                     0.0))
            k_back = (self.kf * back).astype(cdt)
            both = _nt(rows, k_back, self.hi)                 # [32, L]
            mkk.append(both[:_SUB])
            mqk.append(both[_SUB:])
            self.rows.append(rows)
            self.back.append(back)
            self.k_back.append(k_back)
        self.mkk = jnp.concatenate(mkk, axis=0)
        return jnp.concatenate(mqk, axis=0)

    def _products_by_levels(self, g, levels, diagonal):
        """Any g <= 0 (`ops/kda.py` `_decayed_products_exact`): sets `mkk`
        and what the backward reads (a level's decay `level_decay`, its
        rows k over q [2 L, d] `level_rows`, `sums`, `pairs`), returns
        `Q+ K-^T` unmasked. A level is one `[2 L, d] x [d, L]` product."""
        self.sums, self.pairs = levels
        chunk = g.shape[0]
        # D [n L, d]: every level's sums of g in three bf16 passes
        decays = jnp.exp(sum(_nn(self.sums, part) for part in _thirds(g)))
        self.level_decay, self.level_rows = [], []
        mkk = mqk = 0.0
        for i, pair in enumerate(self.pairs):
            decay = decays[i * chunk:(i + 1) * chunk]
            rows = jnp.concatenate([(self.kf * decay).astype(self.cdt),
                                    (self.qf * decay).astype(self.cdt)],
                                   axis=0)
            both = _nt(rows, rows[:chunk], self.hi)           # [2 L, L]
            mkk = mkk + jnp.where(pair, both[:chunk], 0.0)
            mqk = mqk + jnp.where(pair, both[chunk:], 0.0)
            self.level_decay.append(decay)
            self.level_rows.append(rows)
        self.mkk = mkk
        own = jnp.sum(self.qf * self.kf, axis=1, keepdims=True)
        return mqk + jnp.where(diagonal, own, 0.0)

    def solved(self, t):
        """[U | W] = T [beta V | beta K+] for T = (I + A)^-1 (None: the
        sweep's control, the solve left out, a wrong answer)."""
        self.t = t
        self.u, self.w = self.beta * self.vf, self.beta * self.kplus
        if t is not None:
            self.u, self.w = _nn(t, self.u, _HI), _nn(t, self.w, _HI)
        self.wc = self.w.astype(self.cdt)

    def u_tilde(self, sc):
        """U - W S, rounded."""
        return (self.u - _nn(self.wc, sc, self.hi)).astype(self.cdt)


def _chunks(q_ref, k_ref, v_ref, g_ref, beta, heads, exact, solve=True):
    """A grid step's heads, each with its solve: the heads' inverses are
    made together (`_inverses`)."""
    lanes = [slice(h * _LANES, (h + 1) * _LANES) for h in range(heads)]
    levels = _levels(q_ref.shape[0]) if exact else None
    chunks = [_Chunk(q_ref[:, at], k_ref[:, at], v_ref[:, at], g_ref[:, at],
                     beta[:, h:h + 1], levels)
              for h, at in enumerate(lanes)]
    inverses = _inverses([ch.a for ch in chunks]) if solve \
        else [None] * heads
    for ch, t in zip(chunks, inverses):
        ch.solved(t)
    return lanes, chunks


def _products_around_block_starts_bwd(ch, dmkk, dmqk):
    """(dq, dk, dG [L, d], None) of `_Chunk._products_around_block_starts`
    at the cotangents of `K+ K-^T` and `Q+ K-^T` [L, L], masked and rounded:
    the decays there are differences of the running sum G."""
    chunk = dmkk.shape[0]
    nb = chunk // _SUB
    hi = ch.hi
    row = _iota((chunk, 1), 0)
    dkf = jnp.zeros_like(ch.kf)
    dcum = jnp.zeros_like(ch.cum)
    drows_k, drows_q = [], []
    for a in range(nb):
        blk = slice(a * _SUB, (a + 1) * _SUB)
        both = jnp.concatenate([dmkk[blk], dmqk[blk]], axis=0)  # [32, L]
        drows = _nn(both, ch.k_back[a], hi)                   # [32, d]
        drows_k.append(drows[:_SUB])
        drows_q.append(drows[_SUB:])
        # k_back = k exp(ref_a - G_m): rows of later blocks read zeros
        back = _tn(both, ch.rows[a], hi) * ch.back[a]         # [L, d]
        dkf = dkf + back
        moved = back * ch.kf
        dcum = dcum - moved
        if a:
            dcum = dcum + jnp.where(
                row == a * _SUB - 1,
                jnp.sum(moved, axis=0, keepdims=True), 0.0)
    dxk = jnp.concatenate(drows_k, axis=0)
    dxq = jnp.concatenate(drows_q, axis=0)
    dkf = dkf + dxk * ch.to_row
    dqf = dxq * ch.to_row
    moved = (dxk * ch.kf + dxq * ch.qf) * ch.to_row           # d to_row's
    dcum = dcum + moved
    for a in range(1, nb):
        dcum = dcum - jnp.where(
            row == a * _SUB - 1,
            jnp.sum(jnp.where(ch.row_block == a, moved, 0.0), axis=0,
                    keepdims=True), 0.0)
    return dqf, dkf, dcum, None


def _products_by_levels_bwd(ch, dmkk, dmqk):
    """(dq, dk, dG, dg [L, d]) of `_Chunk._products_by_levels`: the levels'
    decays are sums of g itself, so their part goes to dg and none of it
    to the running sum's cotangent."""
    chunk = dmkk.shape[0]
    hi = ch.hi
    r, c = _iota((chunk, chunk), 0), _iota((chunk, chunk), 1)
    own = jnp.sum(jnp.where(r == c, dmqk.astype(_F32), 0.0), axis=1,
                  keepdims=True)
    dqf, dkf = own * ch.kf, own * ch.qf
    dsums = []
    for pair, decay, rows in zip(ch.pairs, ch.level_decay, ch.level_rows):
        both = jnp.concatenate([jnp.where(pair, dmkk, 0.0),
                                jnp.where(pair, dmqk, 0.0)], axis=0)
        drows = _nn(both, rows[:chunk], hi)                   # [2 L, d]
        dkc = drows[:chunk] + _tn(both, rows, hi)
        dqc = drows[chunk:]
        dkf = dkf + dkc * decay
        dqf = dqf + dqc * decay
        dsums.append((dkc * ch.kf + dqc * ch.qf) * decay)
    dsums = jnp.concatenate(dsums, axis=0)                    # [n L, d]
    dg = sum(_tn(ch.sums, part) for part in _thirds(dsums))
    return dqf, dkf, jnp.zeros_like(ch.cum), dg


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, y_ref, states_ref,
                s_ref, *, heads, exact, solve):
    @pl.when(pl.program_id(2) == 0)
    def _open():
        s_ref[...] = jnp.zeros_like(s_ref)

    states_ref[...] = s_ref[...]
    lanes, chunks = _chunks(q_ref, k_ref, v_ref, g_ref, beta_ref[...], heads,
                            exact, solve)
    for at, ch in zip(lanes, chunks):
        s = s_ref[at, :]                                      # [K, V]
        sc = s.astype(ch.cdt)
        utc = ch.u_tilde(sc)
        y = _nn(ch.qpc, sc, ch.hi) + _nn(ch.pqkc, utc, ch.hi)
        y_ref[:, at] = y.astype(y_ref.dtype)
        s_ref[at, :] = _down_rows(ch.decay, s) * s + _tn(ch.kendc, utc, ch.hi)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, lam_ref, *, heads,
                exact):
    @pl.when(pl.program_id(2) == 0)
    def _open():
        lam_ref[...] = jnp.zeros_like(lam_ref)

    chunk = q_ref.shape[0]
    head = _iota((1, heads), 1)
    row = _iota((chunk, 1), 0)
    dbeta_all = jnp.zeros((chunk, heads), _F32)
    lanes, chunks = _chunks(q_ref, k_ref, v_ref, g_ref, beta_ref[...], heads,
                            exact)
    for h, (at, ch) in enumerate(zip(lanes, chunks)):
        cdt, hi, beta = ch.cdt, ch.hi, ch.beta
        s, lam = states_ref[at, :], lam_ref[at, :]            # [K, V] float32
        sc, lamc = s.astype(cdt), lam.astype(cdt)
        doc = do_ref[:, at].astype(cdt)
        utc = ch.u_tilde(sc)
        # o = Q+ S + lower(Q+ K-^T) U~ ; S' = decay S + kend^T U~
        dut = _tn(ch.pqkc, doc, hi) + _nn(ch.kendc, lamc, hi)
        dutc = dut.astype(cdt)
        dpqk = _nt(doc, utc, hi)                              # [L, L]
        dqplus = _nt(doc, sc, hi)                             # [L, K]
        dkend = _nt(utc, lamc, hi)                            # [L, K]
        # U~ = U - W S
        dw = -_nt(dutc, sc, hi)                               # [L, K]
        lam_ref[at, :] = (_tn(ch.qpc, doc, hi) + _down_rows(ch.decay, lam) * lam
                          - _tn(ch.wc, dutc, hi))
        # [U | W] = T [beta V | beta K+], T = (I + A)^-1
        tt = ch.t.T
        drhs_v = _nn(tt, dut, _HI)                            # T^T dU
        drhs_k = _nn(tt, dw, _HI)
        da = jnp.where(ch.strict, -(_nt(drhs_v, ch.u, _HI)
                                    + _nt(drhs_k, ch.w, _HI)), 0.0)
        dv = beta * drhs_v
        dkplus = beta * drhs_k
        dbeta = (jnp.sum(drhs_v * ch.vf + drhs_k * ch.kplus, axis=1,
                         keepdims=True)
                 + jnp.sum(da * ch.mkk, axis=1, keepdims=True))
        dbeta_all = jnp.where(head == h, dbeta, dbeta_all)
        dmkk = (da * beta).astype(cdt)
        dmqk = jnp.where(ch.lower, dpqk, 0.0).astype(cdt)
        products = (_products_by_levels_bwd if exact
                    else _products_around_block_starts_bwd)
        dqf, dkf, dcum, dg = products(ch, dmkk, dmqk)
        # kend = k to_end, decay = exp(G_C), Q+ = q exp(G), K+
        dkf = dkf + dkend * ch.to_end + dkplus * ch.from_start
        left = dkend * ch.kend
        dqf = dqf + dqplus * ch.from_start
        dcum = dcum + dqplus * ch.qplus + dkplus * ch.kplus
        # d decay[k] = sum_v lam[k, v] S[k, v]
        dtotal = _row_sums_along_lanes(lam * s) * ch.decay
        if exact:
            # to_end = exp(the g's after a position)
            dg = dg + _running_sum(_shift(left, down=True))
        else:
            # to_end = exp(G_C - G)
            dcum = dcum - left
            dtotal = dtotal + jnp.sum(left, axis=0, keepdims=True)
        dcum = dcum + jnp.where(row == chunk - 1, dtotal, 0.0)
        dq_ref[:, at] = dqf.astype(dq_ref.dtype)
        dk_ref[:, at] = dkf.astype(dk_ref.dtype)
        dv_ref[:, at] = dv.astype(dv_ref.dtype)
        dg_own = _running_sum(dcum, reverse=True)
        dg_ref[:, at] = dg_own + dg if exact else dg_own
    dbeta_ref[...] = dbeta_all


def _specs(plan_, reverse):
    """The blocks both kernels read, in the order of their leading
    arguments (q, k, v, g: one rows block; beta down the sublanes), and the
    map of a States block. `reverse`: the chunks last to first."""
    heads, d, chunk, chunks = (plan_.heads, plan_.d, plan_.chunk,
                               plan_.chunks)

    def at(c):
        return chunks - 1 - c if reverse else c

    rows = pl.BlockSpec((None, chunk, heads * d),
                        lambda b, j, c: (b, at(c), j))
    beta = pl.BlockSpec((None, None, chunk, heads),
                        lambda b, j, c: (b, j, at(c), 0))
    states = pl.BlockSpec((None, None, heads * d, d),
                          lambda b, j, c: (b, at(c), j, 0))
    return rows, beta, states


def _operands(plan_, q, k, v, g, beta):
    b, s, h, d = q.shape
    return (q.reshape(b, s, h * d), k.reshape(b, s, h * d),
            v.reshape(b, s, h * d), g.reshape(b, s, h * d),
            beta.reshape(b, s, plan_.steps, plan_.heads).transpose(0, 2, 1, 3))


def _level_flops(chunk, d):
    """What the levels add to a position's forward where g has no bound,
    over `_decayed_products`' four products a chunk: the sums of g (three
    passes over [n L, L]) and n - 1 products [2 L, d] x [d, L] more."""
    n = len(_halves(chunk))
    return 2 * (3 * n * chunk * d + (n - 1) * 2 * chunk * d)


def kda_fwd(plan_, q, k, v, g, beta, solve=True):
    """`ops/kda.py` `_kda_fwd` under `plan_`: (o [B, S, H, V] in q's dtype,
    the state each chunk starts from [B, S / L, H, K, V] float32). One
    trace per (shapes, plan) however many layers call it: the inner jit's
    cache. `solve` False leaves the solve out (a wrong answer: the sweep's
    control, `chip_smoke.kda_scan_forms`)."""
    return _kda_fwd(plan_, q, k, v, g, beta, solve=solve,
                    interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("plan_", "solve", "interpret"))
def _kda_fwd(plan_, q, k, v, g, beta, *, solve, interpret):
    b, s, h, d = q.shape
    heads, _, chunk, steps, chunks, resident, exact = plan_
    rows, beta_block, states = _specs(plan_, reverse=False)
    size = q.dtype.itemsize
    levels = _level_flops(chunk, d) if exact else 0
    y, sprev = pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, exact=exact, solve=solve),
        out_shape=(jax.ShapeDtypeStruct((b, s, h * d), q.dtype),
                   jax.ShapeDtypeStruct((b, chunks, h * d, d), _F32)),
        grid=(b, steps, chunks),
        in_specs=[rows, rows, rows, rows, beta_block],
        out_specs=(rows, states),
        scratch_shapes=[pltpu.VMEM((heads * d, d), _F32)],
        compiler_params=_compiler_params(resident, _SEMANTICS),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * s * h * (5 * chunk * d + 4 * d * d
                                   + 7 * chunk * chunk) + b * s * h * levels,
            transcendentals=b * s * h * d * (4 + chunk // _SUB),
            bytes_accessed=(b * s * h * (4 * d * size + 4 * d + 4)
                            + 4 * b * chunks * h * d * d)),
        interpret=interpret,
        name="kda-chunk-fwd",
    )(*_operands(plan_, q, k, v, g, beta))
    return y.reshape(v.shape), sprev.reshape(b, chunks, h, d, d)


def kda_bwd(plan_, q, k, v, g, beta, states, do):
    """`ops/kda.py` `_kda_bwd` under `plan_`: the gradients of (q, k, v, g,
    beta) at do on the chunk states the forward wrote."""
    return _kda_bwd(plan_, q, k, v, g, beta, states, do,
                    interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("plan_", "interpret"))
def _kda_bwd(plan_, q, k, v, g, beta, states, do, *, interpret):
    b, s, h, d = q.shape
    heads, _, chunk, steps, chunks, resident, exact = plan_
    levels = _level_flops(chunk, d) if exact else 0
    rows, beta_block, states_block = _specs(plan_, reverse=True)
    size = q.dtype.itemsize
    like_rows = jax.ShapeDtypeStruct((b, s, h * d), q.dtype)
    dq, dk, dv, dg, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, exact=exact),
        out_shape=(like_rows, like_rows, like_rows,
                   jax.ShapeDtypeStruct((b, s, h * d), _F32),
                   jax.ShapeDtypeStruct((b, steps, s, heads), _F32)),
        grid=(b, steps, chunks),
        in_specs=[rows, rows, rows, rows, beta_block, states_block, rows],
        out_specs=(rows, rows, rows, rows, beta_block),
        scratch_shapes=[pltpu.VMEM((heads * d, d), _F32)],
        compiler_params=_compiler_params(resident, _SEMANTICS),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * s * h * (14 * chunk * d + 10 * d * d
                                   + 9 * chunk * chunk)
            + 3 * b * s * h * levels,
            transcendentals=b * s * h * d * (4 + chunk // _SUB),
            bytes_accessed=(b * s * h * (8 * d * size + 8 * d + 8)
                            + 4 * b * chunks * h * d * d)),
        interpret=interpret,
        name="kda-chunk-bwd",
    )(*_operands(plan_, q, k, v, g, beta),
      states.reshape(b, chunks, h * d, d), do.reshape(b, s, h * d))
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg.reshape(g.shape),
            dbeta.transpose(0, 2, 1, 3).reshape(beta.shape))
