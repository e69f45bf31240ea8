"""The selective scan's chunks (Pallas, TPU): `ops/ssm.py`'s `_ssd_fwd` and
`_ssd_bwd` as two kernels in which a chunk's `[L, L]` score, decay and
mixing matrices live in VMEM and the state is carried in scratch.

Grid (batch, group, chunk), the chunk axis last and sequential; one grid
step handles the `J = H / G` heads of a group, whose `C . B` scores are
made once. HBM sees the per-token rows in and `Y`, `States` out (backward:
the rows, `States` and `dY` in, the rows' gradients out), in the layouts
the op's callers hold them, so nothing is copied in front of a call:

* `x`, `Y`, `dY`, `dx` as `[B, S, H * P]` in blocks `(L, J * P)`;
* `B`, `C` and their gradients as `[B, S, G * N]` in blocks `(L, N)`;
* `States` `[B, S / L, H * P, N]` float32 in blocks `(J * P, N)`: the state
  the chunk STARTS from, copied out of the scratch before the update
  `H <- exp(s_L) H + S_c`;
* the float32 rows `dt`, `cum` `[B, S, H]` are 16 bytes a head and token
  and are wanted both down the sublanes (a position's factor for its row
  of `x`) and along the lanes (the source position of an `[L, L]`
  matrix): they are cut outside the kernel into `[B, G, S, J]` and
  `[B, G, J, S]`, and their gradients come back the same way.

The backward walks the chunks in reverse in the same grid, `lam` (the
closing state's cotangent) in scratch where the forward has the state, and
makes a chunk's matrices again from the rows. `_ssd_bwd` is its
specification line for line, `_ssd_fwd` the forward's: the same values are
rounded to `x`'s dtype at the same places, everything else is float32.

Inside a grid step nothing is cut within a lane tile. A head of 64
features is half a tile, so the x-shaped blocks are worked a `_Unit` at a
time (the heads that share a tile, or one wider head): a factor a position
and head has is spread over its head's lanes, and a product that must not
mix two heads (`[L, L] x [L, 128]` by source position, `[L, 128] x
[128, L]` over features) gets one operand with the other head's lanes at
zero, which costs the MXU nothing: 64 columns would fill half of it. The
products that do not care (C against the state, B against `lam`, the
closing state, dB, dC) run a unit or a group wide. Sums over a head's
features come out a column a head and are gathered into `[L, J]` values,
on which the rows' arithmetic (`ddt`, `dcum`) runs once for the group.
On a v5e at the hybrid cell's size (PERF.md section 6, PR 37) a forward
grid step takes 1.6 us and a backward one 4.6, against 2.9 and 8.7 for a
first version that walked the heads one by one on `[L, 64]` slices.

`plan` is the shape rule: state width and `J * P` whole lane tiles (128),
a head's features a divisor or a multiple of 128, the chunk a multiple of
128 (it is the lane dimension of the `[L, L]` matrices and of the row-wise
`cum` block), the blocks inside `VMEM_BUDGET`. What it leaves keeps
`ops/ssm.py`'s `jax.numpy` form.
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
VMEM_BUDGET = 40 << 20         # bytes the resident blocks may take
# (batch, group, chunk): the chunk axis carries the state
_SEMANTICS = ("parallel", "parallel", "arbitrary")


class Plan(NamedTuple):
    """What the kernels are built from: `heads` a grid step handles, of `p`
    features each; `n` the state's features; `chunk` L; `steps` the grid's
    middle axis (H / heads), `share` consecutive ones of which read one
    group's B and C; `chunks` its last; and what a step keeps in VMEM."""
    heads: int
    p: int
    n: int
    chunk: int
    steps: int
    share: int
    chunks: int
    resident_bytes: int


def plan(x_shape, bm_shape, chunk, itemsize=2, heads=None):
    """The kernels' plan for x [B, S, H, P] with B, C [B, S, G, N] in
    chunks of `chunk`, or None where they do not take the shape. A grid
    step handles a group's H / G heads; `heads` (a divisor of that) is the
    handle of `chip_smoke.py`'s sweep."""
    _, s, h, p = x_shape
    g, n = bm_shape[2:]
    if h % g or s % chunk:
        return None
    heads = heads or h // g
    q = heads * p
    if (h // g) % heads or n % _LANES or q % _LANES or chunk % _LANES \
            or (p % _LANES and _LANES % p) or p % 8:
        return None
    # the backward's blocks, double-buffered: x, dy, dx; B, C and their
    # gradients; States; beside the lam scratch, a dozen [L, L] and as many
    # [L, Q]-sized float32 values of one head's or one group's work
    resident = (2 * (3 * chunk * q + 4 * chunk * n) * itemsize
                + 3 * q * n * 4 + 12 * chunk * chunk * 4 + 12 * chunk * q * 4)
    if resident > VMEM_BUDGET:
        return None
    return Plan(heads, p, n, chunk, h // heads, h // g // heads, s // chunk,
                resident)


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=_F32)


def _nn(a, b):
    """a [m, k] b [k, n]"""
    return _dot(a, b, ((1,), (0,)))


def _nt(a, b):
    """a [m, k] b [n, k]^T"""
    return _dot(a, b, ((1,), (1,)))


def _tn(a, b):
    """a [k, m]^T b [k, n]"""
    return _dot(a, b, ((0,), (0,)))


def _seen(chunk):
    """[L, L]: source position m is no later than output position l."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    return cols <= rows


def _total(s_end, state):
    """exp(s_L), a whole chunk's decay, as a row of `state`'s width. The
    [1, 1] sum goes along the lanes BEFORE the exp: Mosaic broadcasts along
    the lanes or down the sublanes, not both at once, and would merge two
    broadcasts that follow each other."""
    return jnp.exp(jnp.broadcast_to(s_end, (1, state.shape[1])))


class _Unit:
    """A run of `width` lanes of the x-shaped blocks that is whole lane
    tiles and whole heads: one head of 128 features or more, or the 128 / p
    heads that share a lane tile. Everything x-shaped is worked on a unit
    at a time, so no value is cut inside a lane tile; a factor a position
    and head has is spread over its head's lanes, and a product that must
    not mix heads gets one operand with the other heads' lanes at zero."""

    def __init__(self, p):
        self.p = p
        self.width = max(p, _LANES)
        self.r = self.width // p
        if self.r > 1:
            self.lane = jax.lax.broadcasted_iota(jnp.int32, (1, self.width), 1)
            self.row = jax.lax.broadcasted_iota(jnp.int32, (self.width, 1), 0)
            self.mine = [(self.lane >= i * p) & (self.lane < (i + 1) * p)
                         for i in range(self.r)]

    def spread(self, cols, first):
        """cols [rows, J], columns first .. first + r: [rows, width], head
        i's column over its p lanes."""
        out = cols[:, first:first + 1]
        for i in range(1, self.r):
            out = jnp.where(self.lane >= i * self.p,
                            cols[:, first + i:first + i + 1], out)
        return out

    def down(self, rows_of):
        """rows_of(i) [1, n] for each head of the unit: [width, n] (or the
        one row), head i's row down its p sublanes."""
        out = rows_of(0)
        for i in range(1, self.r):
            out = jnp.where(self.row >= i * self.p, rows_of(i), out)
        return out

    def only(self, i, v):
        """v [rows, width] with the lanes of the unit's other heads zero."""
        return v if self.r == 1 else jnp.where(self.mine[i], v, 0.0)


def _decay(seen, cum, cumr, j):
    """exp(s_t - s_r) where r <= t, else 0, for head j: [L, L]."""
    return jnp.exp(jnp.where(seen, cum[:, j:j + 1] - cumr[j:j + 1, :],
                             -jnp.inf))


def _fwd_kernel(x_ref, bm_ref, cm_ref, dt_ref, cum_ref, cumr_ref, d_ref,
                y_ref, states_ref, h_ref, *, heads, p):
    chunk, cdt = x_ref.shape[0], x_ref.dtype
    unit = _Unit(p)

    @pl.when(pl.program_id(2) == 0)
    def _open():
        h_ref[...] = jnp.zeros_like(h_ref)

    h = h_ref[...]                                        # [Q, N]
    states_ref[...] = h
    bm, cm = bm_ref[...], cm_ref[...]
    dt, cum, cumr, d = dt_ref[...], cum_ref[...], cumr_ref[...], d_ref[...]
    # exp(s_L - s_r): what is left of position r's input at the chunk's
    # end; exp(s_t): of the chunk's opening state at t
    s_end = cum[chunk - 1:chunk, :]
    w = jnp.exp(s_end - cum) * dt
    from_start = jnp.exp(cum)
    scores = _nt(cm, bm)                                  # [L, L] C_t . B_r
    inter = _nt(cm, h.astype(cdt))                        # [L, Q] C_t . H
    seen = _seen(chunk)
    for u in range(heads // unit.r):
        first, at = u * unit.r, slice(u * unit.width, (u + 1) * unit.width)
        xf = x_ref[:, at].astype(_F32)
        stepped = xf * unit.spread(dt, first)
        y = inter[:, at] * unit.spread(from_start, first)
        for i in range(unit.r):
            mixing = (scores * _decay(seen, cum, cumr, first + i)).astype(cdt)
            y = y + _nn(mixing, unit.only(i, stepped).astype(cdt))
        y = y + xf * unit.spread(d, first)
        y_ref[:, at] = y.astype(cdt)
        closing = _tn((xf * unit.spread(w, first)).astype(cdt), bm)  # S_c
        total = unit.down(lambda i: _total(s_end[:, first + i:first + i + 1],
                                           h))
        h_ref[at, :] = total * h[at, :] + closing


def _bwd_kernel(x_ref, bm_ref, cm_ref, dt_ref, cum_ref, cumr_ref, d_ref,
                states_ref, dy_ref, dx_ref, dbm_ref, dcm_ref, ddt_ref,
                dcum_ref, dcumr_ref, dd_ref, lam_ref, *, heads, p):
    chunk, cdt = x_ref.shape[0], x_ref.dtype
    unit = _Unit(p)

    @pl.when(pl.program_id(2) == 0)
    def _open():
        lam_ref[...] = jnp.zeros_like(lam_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    h, lam = states_ref[...], lam_ref[...]                # [Q, N] float32
    hq, lamc = h.astype(cdt), lam.astype(cdt)
    bm, cm = bm_ref[...], cm_ref[...]
    dt, cum, cumr, d = dt_ref[...], cum_ref[...], cumr_ref[...], d_ref[...]
    s_end = cum[chunk - 1:chunk, :]
    to_end = jnp.exp(s_end - cum)
    from_start = jnp.exp(cum)
    w = to_end * dt
    scores = _nt(cm, bm)
    read = _nt(cm, hq)                                    # [L, Q] C_t . H
    dxw_all = _nt(bm, lamc)                               # [L, Q] B_r . lam
    seen = _seen(chunk)
    dbm = jnp.zeros(bm.shape, _F32)
    dcm = jnp.zeros(cm.shape, _F32)
    dscores = jnp.zeros((chunk, chunk), _F32)
    # what is a sum over a head's features or over source positions, a
    # column a head: gathered into [L, J] (and [1, J]) values, so that the
    # rows' arithmetic below runs once for the step's heads
    head = jax.lax.broadcasted_iota(jnp.int32, (1, heads), 1)
    zeros = jnp.zeros((chunk, heads), _F32)
    s_read, s_left, s_steps, s_seg = zeros, zeros, zeros, zeros
    s_carried, s_skipped = zeros[:1], zeros[:1]
    for u in range(heads // unit.r):
        first, at = u * unit.r, slice(u * unit.width, (u + 1) * unit.width)
        xf = x_ref[:, at].astype(_F32)
        dyc = dy_ref[:, at].astype(cdt)
        dyf = dyc.astype(_F32)
        steps, left = unit.spread(dt, first), unit.spread(w, first)
        # y_inter = from_start * (C . H_prev)
        d_read = dyf * read[:, at]
        dhc = (dyf * unit.spread(from_start, first)).astype(cdt)
        dcm = dcm + _nn(dhc, hq[at, :])
        # H_c = total H_{c-1} + S_c: lam_{c-1} = dhprev_c + total_c lam_c
        total = unit.down(lambda i: _total(s_end[:, first + i:first + i + 1],
                                           lam))
        lam_ref[at, :] = _tn(dhc, cm) + total * lam[at, :]
        carried = lam[at, :] * h[at, :]                   # d total's terms
        # S_c = sum_r (to_end dt x)_r (x) B_r
        dbm = dbm + _nn((xf * left).astype(cdt), lamc[at, :])
        dxw = dxw_all[:, at]
        dx = dxw * left
        d_left = dxw * xf
        # y_intra[t] = sum_r M[t, r] (dt x)_r, M = round(scores * decay)
        stepped = (xf * steps).astype(cdt)
        ddtx = None
        for i in range(unit.r):
            j = first + i
            mine = unit.only(i, dyf).astype(cdt)
            decay = _decay(seen, cum, cumr, j)
            dm = _nt(mine, stepped)                       # [L, L]
            part = _tn((scores * decay).astype(cdt), mine)  # by source
            ddtx = part if ddtx is None else ddtx + part
            dseg = dm * scores * decay
            s_seg = jnp.where(head == j,
                              jnp.sum(dseg, axis=1, keepdims=True), s_seg)
            dcumr_ref[j:j + 1, :] = jnp.sum(dseg, axis=0, keepdims=True)
            dscores = dscores + dm * decay
        dx = dx + ddtx * steps
        d_steps = ddtx * xf
        skipped = dyf * xf
        dx = dx + dyf * unit.spread(d, first)
        dx_ref[:, at] = dx.astype(dx_ref.dtype)
        for i in range(unit.r):
            mine = head == first + i
            rows = slice(i * unit.p, (i + 1) * unit.p) if unit.r > 1 \
                else slice(None)

            def over_features(v):
                return jnp.sum(unit.only(i, v), axis=1, keepdims=True)

            s_read = jnp.where(mine, over_features(d_read), s_read)
            s_left = jnp.where(mine, over_features(d_left), s_left)
            s_steps = jnp.where(mine, over_features(d_steps), s_steps)
            s_carried = jnp.where(
                mine, jnp.sum(carried[rows, :], keepdims=True), s_carried)
            s_skipped = jnp.where(
                mine, jnp.sum(unit.only(i, skipped), keepdims=True),
                s_skipped)
    last = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
    dcum = s_read * from_start
    # d total = <lam_c, H_{c-1}>, total = exp(s_L)
    dcum = dcum + jnp.where(last, s_carried * from_start[chunk - 1:chunk, :],
                            0.0)
    ddt = s_left * to_end
    dto_end = s_left * dt * to_end
    dcum = dcum - dto_end
    dcum = dcum + jnp.where(last, jnp.sum(dto_end, axis=0, keepdims=True),
                            0.0)
    ddt_ref[...] = ddt + s_steps
    dcum_ref[...] = dcum + s_seg
    dd_ref[...] += s_skipped
    dscores = dscores.astype(cdt)
    dcm_ref[...] = (dcm + _nn(dscores, bm)).astype(dcm_ref.dtype)
    dbm_ref[...] = (dbm + _tn(dscores, cm)).astype(dbm_ref.dtype)


def _from_rows(t):
    """A float32 row tensor as the kernels hold it, [B, H / J, S, J], as
    the op does: [B, S, H]."""
    b, steps, s, j = t.shape
    return t.transpose(0, 2, 1, 3).reshape(b, s, steps * j)


def _specs(plan_, reverse):
    """The blocks both kernels read, in the order of their leading
    arguments: x, B, C, dt and cum down the sublanes, cum along the lanes,
    D; and the map of a States block. `reverse`: the chunks last to
    first."""
    heads, p, n, chunk, _, share, chunks, _ = plan_
    q = heads * p

    def at(c):
        return chunks - 1 - c if reverse else c

    ins = [
        pl.BlockSpec((None, chunk, q), lambda b, g, c: (b, at(c), g)),
        pl.BlockSpec((None, chunk, n), lambda b, g, c: (b, at(c), g // share)),
        pl.BlockSpec((None, chunk, n), lambda b, g, c: (b, at(c), g // share)),
        pl.BlockSpec((None, None, chunk, heads),
                     lambda b, g, c: (b, g, at(c), 0)),
        pl.BlockSpec((None, None, chunk, heads),
                     lambda b, g, c: (b, g, at(c), 0)),
        pl.BlockSpec((None, None, heads, chunk),
                     lambda b, g, c: (b, g, 0, at(c))),
        pl.BlockSpec((None, 1, heads), lambda b, g, c: (g, 0, 0)),
    ]
    states = pl.BlockSpec((None, None, q, n),
                          lambda b, g, c: (b, at(c), g, 0))
    return ins, states


def _operands(plan_, x, bm, cm, dt, cum, d):
    b, s, h, p = x.shape
    cut = (b, s, plan_.steps, plan_.heads)
    return (x.reshape(b, s, h * p), bm.reshape(b, s, -1),
            cm.reshape(b, s, -1), dt.reshape(cut).transpose(0, 2, 1, 3),
            cum.reshape(cut).transpose(0, 2, 1, 3),
            cum.reshape(cut).transpose(0, 2, 3, 1),
            d.astype(_F32).reshape(plan_.steps, 1, plan_.heads))


def ssd_fwd(plan_, x, bm, cm, dt, cum, d):
    """`ops/ssm.py` `_ssd_fwd` under `plan_`: (y [B, S, H, P] in x's dtype,
    the state each chunk starts from [B, S / L, H, P, N] float32). One
    trace per (shapes, plan) however many layers call it: the inner jit's
    cache."""
    return _ssd_fwd(plan_, x, bm, cm, dt, cum, d, interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("plan_", "interpret"))
def _ssd_fwd(plan_, x, bm, cm, dt, cum, d, *, interpret):
    b, s, h, p = x.shape
    heads, _, n, chunk, steps, _, chunks, resident = plan_
    q, groups = heads * p, bm.shape[2]
    ins, states = _specs(plan_, reverse=False)
    size = x.dtype.itemsize
    y, hprev = pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, p=p),
        out_shape=(jax.ShapeDtypeStruct((b, s, h * p), x.dtype),
                   jax.ShapeDtypeStruct((b, chunks, h * p, n), _F32)),
        grid=(b, steps, chunks),
        in_specs=ins,
        out_specs=(ins[0], states),
        scratch_shapes=[pltpu.VMEM((q, n), _F32)],
        compiler_params=_compiler_params(resident, _SEMANTICS),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * s * (steps * chunk * n + h * p * (2 * n + chunk)),
            transcendentals=b * s * h * (chunk + 2),
            bytes_accessed=(2 * b * s * (h * p + groups * n) * size
                            + 12 * b * s * h + 4 * b * chunks * h * p * n)),
        interpret=interpret,
        name="ssm-chunk-fwd",
    )(*_operands(plan_, x, bm, cm, dt, cum, d))
    return y.reshape(x.shape), hprev.reshape(b, chunks, h, p, n)


def ssd_bwd(plan_, x, bm, cm, dt, cum, d, hprev, dy):
    """`ops/ssm.py` `_ssd_bwd` under `plan_`: the gradients of (x, bm, cm,
    dt, cum, d) at dy on the chunk states the forward wrote."""
    return _ssd_bwd(plan_, x, bm, cm, dt, cum, d, hprev, dy,
                    interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("plan_", "interpret"))
def _ssd_bwd(plan_, x, bm, cm, dt, cum, d, hprev, dy, *, interpret):
    b, s, h, p = x.shape
    heads, _, n, chunk, steps, share, chunks, resident = plan_
    q, groups = heads * p, bm.shape[2]
    ins, states = _specs(plan_, reverse=True)
    x_block, _, _, rows_block, _, lanes_block, _ = ins
    size = x.dtype.itemsize
    # a step's part of dB and dC: where `share` steps read one group's rows
    # (the sweep's plans) each writes its own float32 part, summed below
    part = bm.dtype if share == 1 else _F32
    part_block = pl.BlockSpec((None, chunk, n),
                              lambda b, g, c: (b, chunks - 1 - c, g))
    rows = jax.ShapeDtypeStruct((b, steps, s, heads), _F32)
    dx, dbm, dcm, ddt, dcum, dcumr, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, p=p),
        out_shape=(jax.ShapeDtypeStruct((b, s, h * p), x.dtype),
                   jax.ShapeDtypeStruct((b, s, steps * n), part),
                   jax.ShapeDtypeStruct((b, s, steps * n), part),
                   rows, rows,
                   jax.ShapeDtypeStruct((b, steps, heads, s), _F32),
                   jax.ShapeDtypeStruct((b, steps, 1, heads), _F32)),
        grid=(b, steps, chunks),
        in_specs=ins + [states, x_block],
        out_specs=(x_block, part_block, part_block, rows_block, rows_block,
                   lanes_block,
                   pl.BlockSpec((None, None, 1, heads),
                                lambda b, g, c: (b, g, 0, 0))),
        scratch_shapes=[pltpu.VMEM((q, n), _F32)],
        compiler_params=_compiler_params(resident, _SEMANTICS),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * s * (3 * steps * chunk * n
                               + h * p * (4 * n + 2 * chunk)),
            transcendentals=b * s * h * (chunk + 2),
            bytes_accessed=(3 * b * s * (h * p + groups * n) * size
                            + 24 * b * s * h + 4 * b * chunks * h * p * n)),
        interpret=interpret,
        name="ssm-chunk-bwd",
    )(*_operands(plan_, x, bm, cm, dt, cum, d),
      hprev.reshape(b, chunks, h * p, n), dy.reshape(b, s, h * p))
    # a source position's part of dcum comes back along the lanes
    dcum = _from_rows(dcum - dcumr.transpose(0, 1, 3, 2))
    if share > 1:
        dbm, dcm = (jnp.sum(t.reshape(b, s, groups, share, n), axis=3)
                    for t in (dbm, dcm))
    return (dx.reshape(x.shape), dbm.reshape(bm.shape).astype(bm.dtype),
            dcm.reshape(cm.shape).astype(cm.dtype), _from_rows(ddt), dcum,
            jnp.sum(dd, axis=(0, 2)).reshape(-1).astype(d.dtype))
