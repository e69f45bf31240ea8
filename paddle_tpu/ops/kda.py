"""The gated delta rule of linear-attention decoder LMs (Kimi-delta
attention: Yang et al. 2024, "Gated Delta Networks"; Kimi Linear 2025, the
decay a vector a head), as two ops a builder puts between its projections:

* `kda_gate`: the log decay, one number a channel, float32 inside and out.
  With a `lower_bound` the bounded form, `g = lower_bound * sigmoid(
  exp(ALog_h) * (x + DtBias))` in `(lower_bound, 0)`; without one the
  original gate, `g = -exp(ALog_h) * softplus(x + DtBias)`, any g <= 0.
* `kda_scan`: the recurrence. Per head h (state `S` `[K, V]`, float32, zero
  at a row's start), with `alpha_t = exp(g_t)` `[K]` and `beta_t =
  beta_scale * sigmoid(Beta_t)` (`beta_scale` 2 lets `I - beta k k^T` have
  eigenvalues in (-1, 1]):
      S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t
  Computed in chunks of `chunk_size` positions: inside a chunk everything
  is matmuls and ONE triangular solve, and only the chunk's closing state
  is carried on, by a scan over the chunks. With `G_t` the running sum of g
  inside the chunk, `K+ = K * exp(G)`, `K- = K * exp(-G)`, `Q+ = Q *
  exp(G)`:
      A   = strict_lower(Diag(beta) K+ K-^T)
      [U | W] = (I + A)^-1 Diag(beta) [V | K+]
      U~  = U - W S_0
      O   = Q+ S_0 + lower(Q+ K-^T) U~
      S_C = Diag(exp(G_C)) S_0 + (K- * exp(G_C))^T U~
  `exp(-G)` overflows float32 once G passes -88, so the products `K+ K-^T`
  and `Q+ K-^T` are made block by block of `_SUB` = 16 positions, each pair
  of blocks around the running sum at the row block's start: both factors
  of a pair of different blocks are then at most 1, and a block against
  itself reaches `exp(-16 min g)`, which is why `g` must stay above -88 /
  16 = -5.5 a token (the family's `kda_lower_bound` is -5). The op learns
  the bound from the builder (`lower_bound`); with one of -5.5 or above it
  keeps that form. Without one (any g <= 0) no factor passes 1
  (`_decayed_products_exact`): a chunk's pairs (l, m) are split by the
  highest bit in which l and m differ, halves of 32, 16, 8, 4, 2 and 1
  positions at a chunk of 64, and a level's pairs are ONE product of the
  rows `x exp(D)` with `k exp(D)`, `D_l` the sum of g from the half's middle
  to l (upper half) or from l to the middle (lower half): sums of one sign
  taken from g itself and no difference of running sums, so one decay of
  -40 costs the pairs beside it no digit; the diagonal is the plain dot.
  The gate, beta, the running sums, the solve and the states are float32;
  the matmul operands (`Q`, `K`, `V`, the decayed products, the state as a
  factor) are in `Q`'s dtype, every dot accumulating float32. The sequence
  must be a whole number of chunks.

`kda_scan` declares a grad rule (docs/custom_ops.md). Its forward writes
`States` `[B, S / L, H, K, V]` float32, the state each chunk starts from;
the per-token rows the backward needs are the op's own inputs. The backward
makes a chunk's `[L, L]` matrices and its solve again from them, as the
flash kernels recompute their probabilities, and runs the chunks' chain
once, in reverse, for the states' cotangents; never a `[S, H, K, V]` tensor
in either direction. The core is a `jax.custom_vjp` (`_kda`) whose backward
is the rule's own function, so a segment differentiated as a whole
(recompute, layer scan) gets the same gradients with the forward lowered
once more (`kda.bwd_recomputed` counts those, `kda.bwd_residual` the
rule's).

Both directions of the core have two lowerings, chosen from the operands'
shapes and dtype alone (`_route`): where K and V are one lane tile (128),
the chunk 32, 64 or 128 and the rows bf16 or float32, the two Pallas
kernels of `ops/pallas/kda_chunk.py`, a grid over (batch, head block,
chunk) with the state (backward: its cotangent) in VMEM scratch, in which
everything a chunk makes (running sums, decayed rows, the `[L, L]`
products, the solve as an explicit float32 inverse, `U`, `W`) never
reaches HBM; everywhere else (every tiny configuration, a head of 64 or
96, float16) the `jax.numpy` form below, `_kda_fwd` / `_kda_bwd`, which is
also the kernels' specification and their oracle in the tests. Same
operands, same results, same residual either way; `kda.scan_pallas` /
`kda.scan_xla` count each lowering that stays in the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .registry import register

_F32 = jnp.float32
# positions between two restarts of the running sum inside a chunk's
# decayed products
_SUB = 16
# the least bound a token's g may have for a block against itself to stay
# finite as one product around the block's first running sum
_LEAST_BOUND = -88.0 / _SUB

# what `kda_scan`'s forward writes for its grad rule
_RESIDUALS = ("States",)


@register("kda_gate")
def _kda_gate(ctx, ins, attrs):
    """X [B, S, H * K], ALog [H], DtBias [H * K] -> G [B, S, H, K] float32,
    the log of a channel's decay: in (lower_bound, 0), or with no
    `lower_bound` -exp(ALog) softplus(X + DtBias), any number <= 0."""
    x, a_log, dt_bias = ins["X"][0], ins["ALog"][0], ins["DtBias"][0]
    h = a_log.shape[0]
    per_head = x.shape[:-1] + (h, x.shape[-1] // h)
    pre = (x.astype(_F32) + dt_bias.astype(_F32)).reshape(per_head)
    rate = jnp.exp(a_log.astype(_F32))[:, None]
    if attrs.get("lower_bound") is None:
        return {"G": [-rate * jax.nn.softplus(pre)]}
    g = float(attrs["lower_bound"]) * jax.nn.sigmoid(rate * pre)
    return {"G": [g]}


def _dot(spec, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=_F32)


def _sub_block(chunk):
    if chunk % _SUB == 0:
        return _SUB
    if chunk < _SUB:
        return chunk
    raise ValueError(f"kda_scan: a chunk of {chunk} positions is no whole "
                     f"number of blocks of {_SUB}")


def _halves(chunk):
    """The halves a chunk's pairs are split by where g has no bound: 32,
    16, 8, 4, 2, 1 at a chunk of 64."""
    return [1 << i for i in reversed(range((chunk - 1).bit_length()))]


def _level(chunk, half):
    """One level of `_decayed_products_exact`, as constants: (sums [l, j]
    0 / 1, the positions `D_l` adds up; pair [l, m], the pairs the level
    holds). Blocks of 2 `half` positions; a pair (l, m) belongs to the
    level where l lies in a block's upper half and m in its lower one, the
    level of the highest bit in which l and m differ. `D_l` is the sum of g
    over middle .. l for an upper l and over l + 1 .. middle - 1 for a lower
    one, `middle` the upper half's first position."""
    pos = np.arange(chunk)
    middle = pos // (2 * half) * (2 * half) + half
    upper = pos >= middle
    j = pos[None, :]
    sums = np.where(upper[:, None],
                    (j >= middle[:, None]) & (j <= pos[:, None]),
                    (j > pos[:, None]) & (j < middle[:, None]))
    pair = ((middle[:, None] == middle[None, :]) & upper[:, None]
            & ~upper[None, :])
    return sums, pair


def _decayed_products_exact(q, k, g, cdt):
    """`_decayed_products` for any g <= 0, no factor above 1: the pairs
    (l, m), m < l, level by level (`_level`), a level ONE product of the
    rows `x exp(D)` with `k exp(D)`: `exp(D_l) exp(D_m) = exp(G_l - G_m)`
    there, each `D` a sum of g's of one sign and no difference of running
    sums, so a decay of -40 at one position costs the pairs beside it no
    digit; the diagonal is the plain dot. q, k, g [b, c, l, h, d] float32,
    g the log decays themselves."""
    chunk = k.shape[2]
    mkk = mqk = 0.0
    for half in _halves(chunk):
        sums, pair = _level(chunk, half)
        decay = jnp.exp(jnp.einsum(
            "lj,bcjhd->bclhd", jnp.asarray(sums, _F32), g,
            precision=jax.lax.Precision.HIGHEST))
        kc, qc = (k * decay).astype(cdt), (q * decay).astype(cdt)
        mkk = mkk + jnp.where(pair, _dot("bclhd,bcmhd->bchlm", kc, kc), 0.0)
        mqk = mqk + jnp.where(pair, _dot("bclhd,bcmhd->bchlm", qc, kc), 0.0)
    kc = k.astype(cdt).astype(_F32)
    eye = jnp.eye(chunk, dtype=_F32)

    def with_diagonal(m, x):
        own = jnp.sum(x.astype(cdt).astype(_F32) * kc, axis=-1)
        return m + eye * jnp.moveaxis(own, 2, 3)[..., None]

    return with_diagonal(mkk, k), with_diagonal(mqk, q)


def _decayed_products(q, k, cum, cdt):
    """(sum_d k_l k_m exp(G_l - G_m), the same with q_l) [b, c, h, l, m]
    float32, right where m <= l (elsewhere finite and meaningless: the
    caller masks). q, k, cum [b, c, l, h, d] float32; the operands are
    rounded to `cdt`."""
    b, c, chunk, h, d = k.shape
    sub = _sub_block(chunk)
    nb = chunk // sub
    blocks = (b, c, nb, sub, h, d)
    cumb = cum.reshape(blocks)
    # the running sum before a block's first position
    ref = jnp.concatenate([jnp.zeros_like(cumb[:, :, :1, -1]),
                           cumb[:, :, :-1, -1]], axis=2)   # [b, c, a, h, d]
    to_row = jnp.exp(cumb - ref[:, :, :, None])            # <= 1
    kb = k.reshape(blocks)
    # a block against itself: exp(ref - G_m) reaches exp(-sub * min g)
    k_own = (kb * jnp.exp(ref[:, :, :, None] - cumb)).astype(cdt)
    # a block a against the positions of the blocks before it: <= 1 there,
    # clamped where m lies in block a or later (masked below)
    k_before = (k[:, :, None] * jnp.exp(jnp.minimum(
        ref[:, :, :, None] - cum[:, :, None], 0.0))).astype(cdt)
    blk = jnp.arange(chunk) // sub
    before = blk[:, None] > blk[None, :]
    own = jnp.eye(nb, dtype=_F32)[:, None, :, None]        # [a, 1, a', 1]

    def products(x):
        xr = (x.reshape(blocks) * to_row).astype(cdt)
        m_before = _dot("bcarhd,bcamhd->bcharm", xr, k_before).reshape(
            b, c, h, chunk, chunk)
        m_own = (_dot("bcarhd,bcaihd->bchari", xr, k_own)[..., None, :]
                 * own).reshape(b, c, h, chunk, chunk)
        return jnp.where(before, m_before, m_own)

    return products(k), products(q)


def _local(chunk, exact, q, k, v, g, beta):
    """What a chunk makes of its own positions, every chunk at once:
    (U, W [b, c, h, l, .], K- exp(G_C) [b, c, h, l, K], exp(G_C)
    [b, c, h, K], Q+ [b, c, h, l, K], lower(Q+ K-^T) [b, c, h, l, m]), all
    float32. q, k, v [B, S, H, D] in the compute dtype, g [B, S, H, K] and
    beta [B, S, H] float32. `exact`: g has no bound (`_own_block_exact`)."""
    b, s, h, dk = k.shape
    c, cdt = s // chunk, q.dtype
    rows = (b, c, chunk, h)
    qf, kf, vf = (t.astype(_F32).reshape(rows + (-1,)) for t in (q, k, v))
    beta = beta.reshape(rows)
    g = g.reshape(rows + (dk,))
    cum = jnp.cumsum(g, axis=2)
    with jax.named_scope("kda.scan.intra"):
        mkk, mqk = (_decayed_products_exact(qf, kf, g, cdt) if exact
                    else _decayed_products(qf, kf, cum, cdt))
        a = jnp.tril(jnp.moveaxis(beta, 2, -1)[..., None] * mkk, -1)
        pqk = jnp.tril(mqk)
    with jax.named_scope("kda.scan.solve"):
        rhs = beta[..., None] * jnp.concatenate([vf, kf * jnp.exp(cum)], -1)
        uw = jax.lax.linalg.triangular_solve(
            a, jnp.moveaxis(rhs, 2, 3), left_side=True, lower=True,
            unit_diagonal=True)
        u, w = uw[..., :vf.shape[-1]], uw[..., vf.shape[-1]:]
    total = cum[:, :, -1]                                  # [b, c, h, K]
    if exact:
        # the g's after a position, summed from the chunk's end: no
        # difference of running sums
        to_end = jnp.flip(jnp.cumsum(jnp.flip(jnp.pad(
            g[:, :, 1:], ((0, 0), (0, 0), (0, 1), (0, 0), (0, 0))), 2), 2), 2)
    else:
        to_end = total[:, :, None] - cum
    kend = jnp.moveaxis(kf * jnp.exp(to_end), 2, 3)
    qplus = jnp.moveaxis(qf * jnp.exp(cum), 2, 3)
    return u, w, kend, jnp.exp(total), qplus, pqk


def _chunks_first(*ts):
    return tuple(jnp.moveaxis(t, 1, 0) for t in ts)


def _carry(u, wc, kendc, decay):
    """S_{c+1} = Diag(decay_c) S_c + kend_c^T (u_c - w_c S_c) over the
    chunks of a row, from zeros: the state each chunk STARTS from
    [b, c, h, K, V] float32. u float32, wc and kendc [b, c, h, l, .] in the
    compute dtype, decay [b, c, h, K]."""
    cdt = wc.dtype

    def step(s, inp):
        u_c, w_c, kend_c, decay_c = inp
        ut = u_c - _dot("bhlk,bhkv->bhlv", w_c, s.astype(cdt))
        return (decay_c[..., None] * s
                + _dot("bhlk,bhlv->bhkv", kend_c, ut.astype(cdt))), s

    b, _, h, _, dk = wc.shape
    _, states = jax.lax.scan(step, jnp.zeros((b, h, dk, u.shape[-1]), _F32),
                             _chunks_first(u, wc, kendc, decay))
    return jnp.moveaxis(states, 0, 1)


def _kda_fwd(chunk, q, k, v, g, beta, exact=False):
    """(o [B, S, H, V] in q's dtype, the state each chunk starts from
    [B, S / chunk, H, K, V] float32)."""
    cdt = q.dtype
    u, w, kend, decay, qplus, pqk = _local(chunk, exact, q, k, v, g, beta)
    wc = w.astype(cdt)
    with jax.named_scope("kda.scan.carry"):
        states = _carry(u, wc, kend.astype(cdt), decay)
    sc = states.astype(cdt)
    with jax.named_scope("kda.scan.inter"):
        ut = (u - _dot("bchlk,bchkv->bchlv", wc, sc)).astype(cdt)
        o = _dot("bchlk,bchkv->bchlv", qplus.astype(cdt), sc)
    with jax.named_scope("kda.scan.intra"):
        o = o + _dot("bchlm,bchmv->bchlv", pqk.astype(cdt), ut)
    return jnp.moveaxis(o, 2, 3).reshape(v.shape).astype(cdt), states


def _kda_bwd(chunk, q, k, v, g, beta, states, do, exact=False):
    """The transpose of `_kda_fwd` at do, on the chunk states it wrote: the
    gradients of (q, k, v, g, beta). A chunk's matrices and its solve are
    made again from the per-token rows and differentiated where they are
    made (`_local`); the chunks' chain runs once, in reverse."""
    cdt = q.dtype
    (u, w, kend, decay, qplus, pqk), local_vjp = jax.vjp(
        functools.partial(_local, chunk, exact), q, k, v, g, beta)
    b, c, h, _, dv = u.shape
    wc, kendc, sc = w.astype(cdt), kend.astype(cdt), states.astype(cdt)
    doc = jnp.moveaxis(do.astype(cdt).reshape(b, c, chunk, h, dv), 2, 3)
    with jax.named_scope("kda.scan.inter"):
        ut = (u - _dot("bchlk,bchkv->bchlv", wc, sc)).astype(cdt)
        dqplus = _dot("bchlv,bchkv->bchlk", doc, sc)
        dstates = _dot("bchlk,bchlv->bchkv", qplus.astype(cdt), doc)
    with jax.named_scope("kda.scan.intra"):
        dpqk = _dot("bchlv,bchmv->bchlm", doc, ut)
        dut = _dot("bchlm,bchlv->bchmv", pqk.astype(cdt), doc)
    with jax.named_scope("kda.scan.carry"):
        # lam = dL/dS_{c+1}; S_{c+1} = decay S_c + kend^T (u - w S_c)
        def step(lam, inp):
            ds_c, dut_c, w_c, kend_c, decay_c, ut_c, s_c = inp
            lamc = lam.astype(cdt)
            dut_c = dut_c + _dot("bhlk,bhkv->bhlv", kend_c, lamc)
            dutc = dut_c.astype(cdt)
            dkend = _dot("bhlv,bhkv->bhlk", ut_c, lamc)
            ddecay = jnp.sum(lam * s_c, axis=-1)
            dw = -_dot("bhlv,bhkv->bhlk", dutc, s_c.astype(cdt))
            lam = (ds_c + decay_c[..., None] * lam
                   - _dot("bhlk,bhlv->bhkv", w_c, dutc))
            return lam, (dut_c, dw, dkend, ddecay)

        _, grads = jax.lax.scan(
            step, jnp.zeros_like(states[:, 0]),
            _chunks_first(dstates, dut, wc, kendc, decay, ut, states),
            reverse=True)
        du, dw, dkend, ddecay = (jnp.moveaxis(t, 0, 1) for t in grads)
    return local_vjp((du, dw, dkend, ddecay, dqplus, dpqk))


def _route(form, count, q, v):
    """The Pallas kernels' plan where their shape rule takes the operands
    (`ops/pallas/kda_chunk.py` `plan`), else None: the `jax.numpy` form
    above. `form` (chunk, exact). `count`: whether this trace's call
    counts, `kda.scan_pallas` / `kda.scan_xla` and `kda.scan_exact` /
    `kda.scan_bounded`, once per forward or backward lowered."""
    from .pallas import kda_chunk
    chunk, exact = form
    plan = kda_chunk.plan(q.shape, v.shape, chunk, q.dtype, exact=exact) \
        if v.dtype == q.dtype else None
    if count:
        from ..observability import metrics
        metrics.inc("kda.scan_xla" if plan is None else "kda.scan_pallas")
        metrics.inc("kda.scan_exact" if exact else "kda.scan_bounded")
    return plan


def _scan_fwd(form, count, q, k, v, g, beta):
    plan = _route(form, count, q, v)
    if plan is None:
        return _kda_fwd(form[0], q, k, v, g, beta, exact=form[1])
    from .pallas import kda_chunk
    return kda_chunk.kda_fwd(plan, q, k, v, g, beta)


def _scan_bwd(form, count, q, k, v, g, beta, states, do):
    plan = _route(form, count, q, v)
    if plan is None:
        return _kda_bwd(form[0], q, k, v, g, beta, states, do, exact=form[1])
    from .pallas import kda_chunk
    return kda_chunk.kda_bwd(plan, q, k, v, g, beta, states, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _kda(form, count, relowered, q, k, v, g, beta):
    # `relowered` (the generic `__vjp__` differentiates a whole segment):
    # JAX traces this body to a jaxpr it then replaces by the two rules
    # below, so what is traced here is in no program and does not count
    return _scan_fwd(form, count and not relowered, q, k, v, g, beta)


def _kda_vjp_fwd(form, count, relowered, *args):
    o, states = _scan_fwd(form, count, *args)
    return (o, states), args + (states,)


def _kda_vjp_bwd(form, count, relowered, res, cts):
    return _scan_bwd(form, count, *res, cts[0])


_kda.defvjp(_kda_vjp_fwd, _kda_vjp_bwd)


def _form(q, attrs):
    """(chunk, exact): the chunk's length, and whether a block against
    itself is made for any g <= 0 (no `lower_bound`, or one under -88 / 16
    a token) or around one running sum, as the bound allows."""
    chunk = int(attrs["chunk_size"])
    if q.shape[1] % chunk:
        raise ValueError(
            f"kda_scan: a row of {q.shape[1]} positions is no whole number "
            f"of chunks of {chunk}")
    _sub_block(chunk)
    bound = attrs.get("lower_bound")
    return chunk, bound is None or float(bound) < _LEAST_BOUND


def _beta(raw, scale=1.0):
    beta = jax.nn.sigmoid(raw.astype(_F32))
    return beta if scale == 1.0 else scale * beta


def _beta_of(attrs):
    return functools.partial(_beta, scale=float(attrs.get("beta_scale", 1.0)))


def _kda_scan_grad(ctx, ins, attrs, outs, ogs):
    """Grad rule: the backward on what the forward wrote (`_RESIDUALS`).
    Declines when the residual is absent, and the generic `__vjp__`
    differentiates the forward lowering."""
    do = (ogs.get("Y") or [None])[0]
    if do is None or not all(outs.get(s) for s in _RESIDUALS):
        return None
    q, k, v, g, raw = (ins[s][0] for s in ("Q", "K", "V", "G", "Beta"))
    beta, beta_vjp = jax.vjp(_beta_of(attrs), raw)
    dq, dk, dv, dg, dbeta = _scan_bwd(
        _form(q, attrs), not ctx.is_eval_shape, q, k, v,
        g.astype(_F32), beta, outs["States"][0], do)
    if not ctx.is_eval_shape:
        from ..observability import metrics
        metrics.inc("kda.bwd_residual")
    return {"Q": [dq], "K": [dk], "V": [dv], "G": [dg.astype(g.dtype)],
            "Beta": [beta_vjp(dbeta)[0]]}


@register("kda_scan", grad=_kda_scan_grad, residual_slots=_RESIDUALS)
def _kda_scan(ctx, ins, attrs):
    """Q, K [B, S, H, K], V [B, S, H, V], G [B, S, H, K] (a channel's log
    decay: above `lower_bound` a token where the builder gives one, any
    number <= 0 where it gives none), Beta [B, S, H] before its sigmoid
    (times `beta_scale`, 1 by default) -> Y [B, S, H, V] in Q's dtype,
    States."""
    q, k, v, g, raw = (ins[s][0] for s in ("Q", "K", "V", "G", "Beta"))
    if k.shape != q.shape or g.shape != k.shape or raw.shape != q.shape[:3]:
        raise ValueError(f"kda_scan: Q {q.shape}, K {k.shape}, G {g.shape}, "
                         f"Beta {raw.shape}")
    y, states = _kda(_form(q, attrs), not ctx.is_eval_shape,
                     ctx.in_vjp, q, k, v, g.astype(_F32),
                     _beta_of(attrs)(raw))
    if not ctx.is_eval_shape:
        from ..observability import metrics
        metrics.inc("kda.bwd_recomputed" if ctx.in_vjp
                    else "kda.layers_lowered")
    return {"Y": [y], "States": [states]}
