"""The state-space mixer of hybrid decoder LMs (Mamba-2: Dao & Gu 2024,
"Transformers are SSMs"), as three ops a builder puts between an input and
an output projection:

* `causal_conv1d`: a depthwise convolution along the sequence that sees
  the current and the `K - 1` earlier positions, with an optional bias and
  activation. Float32 inside, the input's dtype out.
* `ssm_scan`: the selective scan. Per head h (state `[P, N]`, float32)
  `h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t`, `y_t = h_t C_t + D x_t`,
  with `dt = softplus(Dt + DtBias)`, `A = -exp(ALog)`, and head h reading
  the `B`, `C` of group `h // (H / G)`. Computed in chunks of `chunk_size`
  positions (the state-space dual form): inside a chunk everything is
  matmuls, and only the chunk's closing state is carried on, by a scan over
  the chunks. With `a_t = dt_t A` and `s_t` its running sum inside the
  chunk:
      Y_intra[t] = sum_{r<=t} exp(s_t - s_r) (C_t . B_r) dt_r x_r
      S_c        = sum_r exp(s_L - s_r) dt_r x_r (x) B_r
      H_c        = exp(s_L) H_{c-1} + S_c
      Y_inter[t] = exp(s_t) C_t . H_{c-1}
  The decays, their running sums and the states are float32; the matmul
  operands (`X`, `B`, `C`, the decayed products, the state read by `C`) are
  in `X`'s dtype, every dot accumulating float32. The sequence must be a
  whole number of chunks.
* `gated_group_rms_norm`: `GroupRMSNorm(x * silu(gate)) * scale`, the
  statistics over each of `groups` equal slices of the last axis.

And the whole mixer of a gated short-convolution layer, which has no state
to scan: `gated_short_conv`, `C * conv(B * u)` of one projection `[B | C |
u]`, the convolution `causal_conv1d`'s without bias or activation. One op
and not `elementwise_mul`, `causal_conv1d`, `elementwise_mul` over a
`split`: its backward (a `jax.custom_vjp`, so a segment differentiated as a
whole takes it too) keeps the projection alone, makes the first gate and
the convolution again and writes `[dB | dC | du]` once. The products are in
the projection's dtype, the taps and their sum float32.

`ssm_scan` declares a grad rule (docs/custom_ops.md). Its forward writes
what is narrow: `States` `[B, S / L, H, P, N]` float32, the state each
chunk starts from, and the per-token rows `DtSoft`, `CumA` `[B, S, H]`
(the step after softplus, the running sums). The backward recomputes the
`[L, L]` decay and score matrices of a chunk from them, as the flash
kernels recompute their probabilities, and runs the chunks' chain once, in
reverse, for the states' cotangents; never a `[S, H, P, N]` tensor in
either direction. The core is a `jax.custom_vjp` (`_ssd`) whose backward
is the rule's own function, so a segment differentiated as a whole
(recompute, layer scan) gets the same gradients with the forward lowered
once more (`ssm.bwd_recomputed` counts those, `ssm.bwd_residual` the
rule's).

Both directions of the core have two lowerings, chosen from the operands'
shapes alone (`_route`): where the state and a group's heads x features
are whole lane tiles (128) and the chunk a multiple of 128, the two Pallas
kernels of `ops/pallas/ssm_chunk.py`, a grid over (batch, group, chunk)
with the state (backward: its cotangent) in VMEM scratch, in which a
chunk's `[L, L]` matrices never reach HBM; everywhere else (every tiny
configuration) the `jax.numpy` form below, `_ssd_fwd` / `_ssd_bwd`, which
is also the kernels' specification and their oracle in the tests. Same
operands, same results, same residuals either way; `ssm.scan_pallas` /
`ssm.scan_xla` count each lowering that stays in the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .registry import register

_F32 = jnp.float32

# what `ssm_scan`'s forward writes for its grad rule
_RESIDUALS = ("States", "DtSoft", "CumA")


@register("causal_conv1d")
def _causal_conv1d(ctx, ins, attrs):
    """X [B, S, C], W [K, C], Bias [C]: out[t] = sum_j W[j] x[t - (K-1) + j]
    (+ Bias), positions before the row's start read as zeros."""
    x, w = ins["X"][0], ins["W"][0]
    k, s = w.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(_F32), ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(padded[:, j:j + s] * w[j].astype(_F32) for j in range(k))
    if ins.get("Bias"):
        y = y + ins["Bias"][0].astype(_F32)
    act = attrs.get("activation") or ""
    if act == "silu":
        y = jax.nn.silu(y)
    elif act:
        raise ValueError(f"causal_conv1d: unknown activation {act!r}")
    return {"Out": [y.astype(x.dtype)]}


# ---------------------------------------------------------------------------
# gated_short_conv
# ---------------------------------------------------------------------------

def _padded(x, k, reverse=False):
    """x [B, S, C] with k - 1 rows of zeros before the row's start
    (`reverse`: past its end), in x's own dtype: the zeros go on before the
    widening, so what XLA writes between two fusions is as narrow as x and
    the first gate's product is rounded to x's dtype, as the builders state
    it."""
    pad = (0, k - 1) if reverse else (k - 1, 0)
    return jnp.pad(x, ((0, 0), pad, (0, 0)))


def _taps(x, w, reverse=False):
    """sum_j w[j] x[t - (K-1) + j] in float32, zeros before the row's start;
    `reverse`: its transpose, sum_j w[j] x[t + (K-1) - j], zeros past the
    row's end. x [B, S, C], w [K, C]."""
    k, s = w.shape[0], x.shape[1]
    padded = _padded(x, k, reverse)
    taps = w[::-1] if reverse else w
    return sum(padded[:, j:j + s].astype(_F32) * taps[j].astype(_F32)
               for j in range(k))


def _thirds(bcx):
    c = bcx.shape[-1] // 3
    return bcx[..., :c], bcx[..., c:2 * c], bcx[..., 2 * c:]


def _mix(bcx, w):
    """C * conv(B * u) of bcx = [B | C | u] [B, S, 3C]: both gates'
    products in bcx's dtype, the taps and their sum in float32."""
    b, c, u = _thirds(bcx)
    return c * _taps(b * u, w).astype(bcx.dtype)


@jax.custom_vjp
def _gated_conv(bcx, w):
    return _mix(bcx, w)


def _gated_conv_fwd(bcx, w):
    return _mix(bcx, w), (bcx, w)


def _gated_conv_bwd(res, dy):
    """From the projection alone: the first gate and the convolution are
    made again, dC = dy * conv, the convolution's cotangent dy * C goes
    back through the taps in reverse, and [dB | dC | du] is written once."""
    bcx, w = res
    b, c, u = _thirds(bcx)
    k, s = w.shape[0], bcx.shape[1]
    dy = dy.astype(bcx.dtype)
    g = b * u
    dconv = dy * c
    dg = _taps(dconv, w, reverse=True).astype(bcx.dtype)
    dc = dy * _taps(g, w).astype(bcx.dtype)
    padded = _padded(g, k)
    dw = jnp.stack([jnp.sum(padded[:, j:j + s].astype(_F32)
                            * dconv.astype(_F32), axis=(0, 1))
                    for j in range(k)])
    return (jnp.concatenate([dg * u, dc, dg * b], axis=-1),
            dw.astype(w.dtype))


_gated_conv.defvjp(_gated_conv_fwd, _gated_conv_bwd)


@register("gated_short_conv")
def _gated_short_conv(ctx, ins, attrs):
    """X [B, S, 3C] = [B | C | u], W [K, C]: Out = C * conv(B * u), conv
    `causal_conv1d`'s index rule without bias or activation."""
    x, w = ins["X"][0], ins["W"][0]
    if x.shape[-1] != 3 * w.shape[1]:
        raise ValueError(f"gated_short_conv: {x.shape[-1]} features are "
                         f"not three streams of {w.shape[1]}")
    if not ctx.is_eval_shape and not ctx.in_vjp:
        from ..observability import metrics
        metrics.inc("conv.layers_lowered")
    return {"Out": [_gated_conv(x, w)]}


@register("gated_group_rms_norm")
def _gated_group_rms_norm(ctx, ins, attrs):
    x, gate = ins["X"][0], ins["Gate"][0]
    groups = int(attrs.get("groups", 1))
    y = x.astype(_F32) * jax.nn.silu(gate.astype(_F32))
    g = y.reshape(y.shape[:-1] + (groups, y.shape[-1] // groups))
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                          + attrs.get("epsilon", 1e-5))
    y = g.reshape(y.shape)
    if ins.get("Scale"):
        y = y * ins["Scale"][0].astype(_F32)
    return {"Y": [y.astype(x.dtype)]}


# ---------------------------------------------------------------------------
# ssm_scan
# ---------------------------------------------------------------------------

def _decays(dt_raw, dt_bias, a_log, chunk):
    """(dt, cum) [B, S, H] float32: the step after softplus and the running
    sum of dt * A inside each chunk."""
    dt = jax.nn.softplus(dt_raw.astype(_F32) + dt_bias.astype(_F32))
    a = dt * -jnp.exp(a_log.astype(_F32))
    b, s, h = a.shape
    cum = jnp.cumsum(a.reshape(b, s // chunk, chunk, h), axis=2)
    return dt, cum.reshape(b, s, h)


def _dot(spec, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=_F32)


class _Chunks:
    """One call's operands cut into chunks, with what both directions make
    of them. Axes: b batch, c chunk, l / m position in the chunk (output /
    source), g group, j head in the group, p head feature, n state
    feature; q = (j, p) merged."""

    def __init__(self, x, bm, cm, dt, cum, chunk):
        b, s, h, p = x.shape
        g, n = bm.shape[2:]
        c, j = s // chunk, h // g
        self.shape = (b, s, h, p)
        self.dims = b, c, chunk, g, j, p, n
        self.cdt = x.dtype
        self.x = x.reshape(b, c, chunk, g, j, p)
        self.xf = self.x.astype(_F32)
        self.bm = bm.reshape(b, c, chunk, g, n)
        self.cm = cm.reshape(b, c, chunk, g, n)
        self.dt = dt.reshape(b, c, chunk, g, j)
        self.cum = cum.reshape(b, c, chunk, g, j)
        # exp(s_L - s_r): what is left of position r's input at the
        # chunk's end; exp(s_t): of the chunk's opening state at t
        self.to_end = jnp.exp(self.cum[:, :, -1:] - self.cum)
        self.from_start = jnp.exp(self.cum)

    def stepped(self):
        """dt_r x_r [b, c, l, g, j, p], rounded: a position's input."""
        return (self.xf * self.dt[..., None]).astype(self.cdt)

    def stepped_to_end(self):
        """exp(s_L - s_r) dt_r x_r [b, c, l, g, q], rounded: what is left
        of a position's input at the chunk's end."""
        return self.merged((self.xf * (self.to_end * self.dt)[..., None]
                            ).astype(self.cdt))

    def mixing(self, scores, decay):
        """(C_t . B_r) exp(s_t - s_r) [b, c, g, j, l, m], rounded."""
        return (scores[:, :, :, None] * decay).astype(self.cdt)

    def total(self):
        """exp(s_L) [b, c, h]: a whole chunk's decay."""
        return self.from_start[:, :, -1].reshape(self.dims[:2] + (-1,))

    def decay(self):
        """exp(s_t - s_r) where r <= t, else 0: [b, c, g, j, l, m]."""
        cum = jnp.moveaxis(self.cum, 2, -1)               # [b, c, g, j, l]
        seg = cum[..., :, None] - cum[..., None, :]
        seen = jnp.tril(jnp.ones((self.dims[2],) * 2, bool))
        return jnp.exp(jnp.where(seen, seg, -jnp.inf))

    def scores(self):
        """C_t . B_r [b, c, g, l, m] float32."""
        return _dot("bclgn,bcmgn->bcglm", self.cm, self.bm)

    def merged(self, t):
        """[b, c, l, g, j, p] -> [b, c, l, g, q]"""
        return t.reshape(t.shape[:4] + (-1,))

    def heads(self, t):
        """[b, c, l, g, q] -> [b, c, l, g, j, p]"""
        return t.reshape(t.shape[:4] + self.dims[4:6])

    def by_group(self, t):
        """a state [b, c, h, p, n] -> [b, c, g, q, n]"""
        b, c, _, g, j, p, n = self.dims
        return t.reshape(b, c, g, j * p, n)

    def by_head(self, t):
        """a state [b, c, g, q, n] -> [b, c, h, p, n]"""
        b, c, _, g, j, p, n = self.dims
        return t.reshape(b, c, g * j, p, n)


def _carry(states, total):
    """H_c = total_c H_{c-1} + S_c over the chunks of a row, from zeros:
    the state each chunk STARTS from. states [b, c, h, p, n], total
    [b, c, h]."""
    def step(h, inp):
        s_c, e_c = inp
        return e_c[..., None, None] * h + s_c, h

    _, hprev = jax.lax.scan(step, jnp.zeros_like(states[:, 0]),
                            (jnp.moveaxis(states, 1, 0),
                             jnp.moveaxis(total, 1, 0)))
    return jnp.moveaxis(hprev, 0, 1)


def _ssd_fwd(chunk, x, bm, cm, dt, cum, d):
    """(y [B, S, H, P] in x's dtype, the state each chunk starts from
    [B, S / chunk, H, P, N] float32)."""
    ch = _Chunks(x, bm, cm, dt, cum, chunk)
    g, j = ch.dims[3:5]
    with jax.named_scope("ssm.scan.states"):
        states = _dot("bclgq,bclgn->bcgqn", ch.stepped_to_end(), ch.bm)
    with jax.named_scope("ssm.scan.carry"):
        hprev = _carry(ch.by_head(states), ch.total())
    with jax.named_scope("ssm.scan.inter"):
        y = ch.heads(_dot("bclgn,bcgqn->bclgq", ch.cm,
                          ch.by_group(hprev).astype(ch.cdt))
                     ) * ch.from_start[..., None]
    with jax.named_scope("ssm.scan.intra"):
        y = y + _dot("bcgjlm,bcmgjp->bclgjp",
                     ch.mixing(ch.scores(), ch.decay()), ch.stepped())
    y = y + ch.xf * d.astype(_F32).reshape(g, j, 1)
    return y.reshape(ch.shape).astype(ch.cdt), hprev


def _ssd_bwd(chunk, x, bm, cm, dt, cum, d, hprev, dy):
    """The transpose of `_ssd_fwd` at dy, on the chunk states it wrote: the
    gradients of (x, bm, cm, dt, cum, d). The chunks' chain runs once, in
    reverse; a chunk's [L, L] matrices are made again from the per-token
    rows."""
    ch = _Chunks(x, bm, cm, dt, cum, chunk)
    b, c, _, g, j = ch.dims[:5]
    cdt, xf = ch.cdt, ch.xf
    dyc = dy.astype(cdt).reshape(ch.x.shape)
    dyf = dyc.astype(_F32)
    hq = ch.by_group(hprev).astype(cdt)                   # [b, c, g, q, n]

    with jax.named_scope("ssm.scan.inter"):
        # y_inter = from_start * (C . H_prev)
        hc = ch.heads(_dot("bclgn,bcgqn->bclgq", ch.cm, hq))
        dcum = jnp.sum(dyf * hc, axis=-1) * ch.from_start
        dhc = ch.merged((dyf * ch.from_start[..., None]).astype(cdt))
        dcm = _dot("bcgqn,bclgq->bclgn", hq, dhc)
        dhprev = _dot("bclgq,bclgn->bcgqn", dhc, ch.cm)
    with jax.named_scope("ssm.scan.carry"):
        # lam_c = dL/dH_c: lam_{c-1} = dhprev_c + total_c lam_c, from zeros
        def step(lam, inp):
            g_c, e_c = inp
            return g_c + e_c[..., None, None] * lam, lam

        dhprev = ch.by_head(dhprev)
        _, lam = jax.lax.scan(step, jnp.zeros_like(dhprev[:, 0]),
                              (jnp.moveaxis(dhprev, 1, 0),
                               jnp.moveaxis(ch.total(), 1, 0)), reverse=True)
        lam = jnp.moveaxis(lam, 0, 1)                     # [b, c, h, p, n]
        # d total_c = <lam_c, H_{c-1}>, total_c = exp(s_L)
        dtotal = jnp.sum(lam * hprev, axis=(-1, -2)) * ch.total()
        dcum = dcum.at[:, :, -1].add(dtotal.reshape(b, c, g, j))
    with jax.named_scope("ssm.scan.states"):
        # S_c = sum_r (to_end dt x)_r (x) B_r
        lamc = ch.by_group(lam).astype(cdt)
        w = ch.to_end * ch.dt
        dbm = _dot("bcgqn,bclgq->bclgn", lamc, ch.stepped_to_end())
        dxw = ch.heads(_dot("bclgn,bcgqn->bclgq", ch.bm, lamc))
        dx = dxw * w[..., None]
        dw = jnp.sum(dxw * xf, axis=-1)
        ddt = dw * ch.to_end
        dto_end = dw * ch.dt * ch.to_end
        dcum = dcum - dto_end
        dcum = dcum.at[:, :, -1].add(jnp.sum(dto_end, axis=2))
    with jax.named_scope("ssm.scan.intra"):
        # y_intra[t] = sum_r M[t, r] (dt x)_r, M = round(scores * decay)
        scores, decay = ch.scores(), ch.decay()
        dm = _dot("bclgjp,bcmgjp->bcgjlm", dyc, ch.stepped())
        ddtx = _dot("bcgjlm,bclgjp->bcmgjp", ch.mixing(scores, decay), dyc)
        dx = dx + ddtx * ch.dt[..., None]
        ddt = ddt + jnp.sum(ddtx * xf, axis=-1)
        dseg = dm * scores[:, :, :, None] * decay          # [b,c,g,j,l,m]
        dcum = dcum + jnp.moveaxis(
            jnp.sum(dseg, axis=-1) - jnp.sum(dseg, axis=-2), -1, 2)
        dscores = jnp.sum(dm * decay, axis=3).astype(cdt)  # [b,c,g,l,m]
        dcm = dcm + _dot("bcglm,bcmgn->bclgn", dscores, ch.bm)
        dbm = dbm + _dot("bcglm,bclgn->bcmgn", dscores, ch.cm)
    dd = jnp.sum(dyf * xf, axis=(0, 1, 2, 5)).reshape(-1)
    dx = dx + dyf * d.astype(_F32).reshape(g, j, 1)
    return (dx.reshape(ch.shape).astype(x.dtype),
            dbm.reshape(bm.shape).astype(bm.dtype),
            dcm.reshape(cm.shape).astype(cm.dtype),
            ddt.reshape(dt.shape), dcum.reshape(cum.shape),
            dd.astype(d.dtype))


def _route(chunk, count, x, bm):
    """The Pallas kernels' plan where their shape rule takes the operands
    (`ops/pallas/ssm_chunk.py` `plan`), else None: the `jax.numpy` form
    above. `count`: whether this trace's call counts, `ssm.scan_pallas` /
    `ssm.scan_xla`, once per forward or backward lowered."""
    from .pallas import ssm_chunk
    plan = ssm_chunk.plan(x.shape, bm.shape, chunk, x.dtype.itemsize)
    if count:
        from ..observability import metrics
        metrics.inc("ssm.scan_xla" if plan is None else "ssm.scan_pallas")
    return plan


def _scan_fwd(chunk, count, x, bm, cm, dt, cum, d):
    plan = _route(chunk, count, x, bm)
    if plan is None:
        return _ssd_fwd(chunk, x, bm, cm, dt, cum, d)
    from .pallas import ssm_chunk
    return ssm_chunk.ssd_fwd(plan, x, bm, cm, dt, cum, d)


def _scan_bwd(chunk, count, x, bm, cm, dt, cum, d, hprev, dy):
    plan = _route(chunk, count, x, bm)
    if plan is None:
        return _ssd_bwd(chunk, x, bm, cm, dt, cum, d, hprev, dy)
    from .pallas import ssm_chunk
    return ssm_chunk.ssd_bwd(plan, x, bm, cm, dt, cum, d, hprev, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _ssd(chunk, count, relowered, x, bm, cm, dt, cum, d):
    # `relowered` (the generic `__vjp__` differentiates a whole segment):
    # JAX traces this body to a jaxpr it then replaces by the two rules
    # below, so what is traced here is in no program and does not count
    return _scan_fwd(chunk, count and not relowered, x, bm, cm, dt, cum, d)


def _ssd_vjp_fwd(chunk, count, relowered, *args):
    y, hprev = _scan_fwd(chunk, count, *args)
    return (y, hprev), args + (hprev,)


def _ssd_vjp_bwd(chunk, count, relowered, res, cts):
    return _scan_bwd(chunk, count, *res, cts[0])


_ssd.defvjp(_ssd_vjp_fwd, _ssd_vjp_bwd)


def _chunk_size(x, attrs):
    chunk = int(attrs["chunk_size"])
    if x.shape[1] % chunk:
        raise ValueError(
            f"ssm_scan: a row of {x.shape[1]} positions is no whole number "
            f"of chunks of {chunk}")
    return chunk


def _ssm_scan_grad(ctx, ins, attrs, outs, ogs):
    """Grad rule: the backward on what the forward wrote (`_RESIDUALS`).
    Declines when a residual is absent, and the generic `__vjp__`
    differentiates the forward lowering."""
    dy = (ogs.get("Y") or [None])[0]
    if dy is None or not all(outs.get(s) for s in _RESIDUALS):
        return None
    x, bm, cm = (ins[s][0] for s in ("X", "B", "C"))
    dt_raw, dt_bias, a_log, d = (ins[s][0] for s in ("Dt", "DtBias", "ALog",
                                                     "D"))
    hprev, dt, cum = (outs[s][0] for s in _RESIDUALS)
    chunk = _chunk_size(x, attrs)
    dx, dbm, dcm, ddt, dcum, dd = _scan_bwd(
        chunk, not ctx.is_eval_shape, x, bm, cm, dt, cum, d, hprev, dy)
    _, decays_vjp = jax.vjp(
        lambda *a: _decays(*a, chunk), dt_raw, dt_bias, a_log)
    ddt_raw, ddt_bias, da_log = decays_vjp((ddt, dcum))
    if not ctx.is_eval_shape:
        from ..observability import metrics
        metrics.inc("ssm.bwd_residual")
    return {"X": [dx], "B": [dbm], "C": [dcm], "Dt": [ddt_raw],
            "DtBias": [ddt_bias], "ALog": [da_log], "D": [dd]}


@register("ssm_scan", grad=_ssm_scan_grad, residual_slots=_RESIDUALS)
def _ssm_scan(ctx, ins, attrs):
    x, bm, cm = (ins[s][0] for s in ("X", "B", "C"))
    h, g = x.shape[2], bm.shape[2]
    if h % g or cm.shape != bm.shape:
        raise ValueError(f"ssm_scan: {h} heads on B {bm.shape}, C {cm.shape}")
    chunk = _chunk_size(x, attrs)
    dt, cum = _decays(ins["Dt"][0], ins["DtBias"][0], ins["ALog"][0], chunk)
    y, hprev = _ssd(chunk, not ctx.is_eval_shape, ctx.in_vjp, x, bm, cm, dt,
                    cum, ins["D"][0])
    if not ctx.is_eval_shape:
        from ..observability import metrics
        metrics.inc("ssm.bwd_recomputed" if ctx.in_vjp
                    else "ssm.layers_lowered")
    return {"Y": [y], "States": [hprev], "DtSoft": [dt], "CumA": [cum]}
