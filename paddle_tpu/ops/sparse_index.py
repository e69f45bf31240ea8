"""The learned indexer of sparse attention (DeepSeek-V3.2-Exp's "lightning
indexer"): which keys a query attends is data, not a static window.

`sparse_index`: from the indexer's own queries `QI` [B, H, S, D] (H small
heads), its one key head `KI` [B, S, D] and the per-head weights `W`
[B, S, H], the scores of every causal pair

    I[t, s] = sum_j W[t, j] relu(QI[t, j] . KI[s]),   s <= t,

and the selection `Select` [B, S, S] int8: for query t the min(t + 1, topk)
keys s <= t with the largest I[t, s], ties to the lower s: the set
`jax.lax.top_k` picks. The selection is stored ONCE A ROW as a mask of one
byte a pair (all query heads of the main attention share it; the flash
kernels read it tile by tile, `ops/pallas/flash_attention.py`), never as a
float mask and never per head. `PairsPerQuery` [1] is the mean of |S_t|.

How the set is found. No sort: the k-th largest score of a row is built bit
by bit over the scores' order-preserving uint32 keys (32 passes of a compare
and a row sum), then `score > kth`, and of the pairs AT the k-th score the
first `topk - count(score > kth)` by position (a running count). The same
set as a sort's, at a fraction of a TPU sort's time. Scores and selection
are made a block of `Q_BLOCK` queries at a time (`_index_fwd`): the
[B, H, block, S] products, the keys and the running count exist for one
block only; what reaches HBM whole is the float32 score and the byte of
selection a pair.

`sparse_index_loss`: the indexer's own objective, mean over queries of
KL(p_t || softmax_{s in S_t} I[t, s]), p the main attention's head-summed
probabilities on the selected pairs (`fused_attention`'s `Target`), a target
no gradient passes. Its gradient reaches `Scores` on the selected pairs
only, and from there `QI`, `KI`, `W` by `sparse_index`'s grad rule
(`_scores_bwd`: the products once more, a block of queries at a time, no
[B, H, S, S] array). Nothing reaches `Select`.

The scores and their grad rule have two lowerings, chosen from the
operands' shapes and dtype alone (`_route`): where the indexer's heads are
a multiple of 64 wide, a block of 128 or more divides the row and the
operands are bf16 or float32, the two Pallas kernels of
`ops/pallas/index_scores.py`, a grid over (batch, query block, key block)
in which a tile's products of all H heads, their relu and the sum over the
heads never leave VMEM and the tiles above the diagonal are skipped (the
selection then reads the finished scores, still a block of `Q_BLOCK`
queries at a time); everywhere else (every tiny configuration: heads 8
wide, rows of 40) the `jax.numpy` form, `_index_fwd` / `_scores_bwd`, which
is also the kernels' specification and their oracle in the tests. Same
operands, same results either way; `attn.index_pallas` / `attn.index_xla`
count each lowering that stays in the program.

Dtypes: `QI`, `KI` arrive in the AMP compute dtype like any matmul's
operands (the op is white-listed, `W` kept float32); every product
accumulates float32; relu, weighting, the sum over heads, the selection and
the loss are float32. Device work names itself `attn.index.score`,
`attn.index.select`, `attn.index.loss` (docs/observability.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .registry import keep_under_recompute, register

# queries a block of the score products: the [B, H, block, S] float32
# products of one block are the largest array the scores ever hold
Q_BLOCK = 512


def _q_blocks(s: int) -> int:
    """Queries a block: the largest divisor of `s` up to `Q_BLOCK`."""
    bq = min(Q_BLOCK, s)
    while s % bq:
        bq -= 1
    return bq


def _products(q, k):
    """q [B, H, bq, D] . k [B, S, D] -> [B, H, bq, S] float32."""
    return jnp.einsum("bhtd,bsd->bhts", q, k,
                      preferred_element_type=jnp.float32)


def _in_blocks(q, w):
    """q [B, H, S, D], w [B, S, H] -> ([n, B, H, bq, D], [n, B, H, bq])."""
    b, h, s, d = q.shape
    bq = _q_blocks(s)
    qb = q.reshape(b, h, s // bq, bq, d).transpose(2, 0, 1, 3, 4)
    wb = w.astype(jnp.float32).reshape(b, s // bq, bq, h).transpose(
        1, 0, 3, 2)
    return qb, wb


def _causal(rows: int, s: int, first_row=0):
    """[rows, s]: key s is no later than query first_row + row."""
    return (first_row + jnp.arange(rows))[:, None] >= jnp.arange(s)[None, :]


def _index_fwd(q, k, w, topk):
    """(scores [B, S, S] float32, select [B, S, S] int8), a block of queries
    at a time: the block's products, its scores and its selection never
    exist for more than `Q_BLOCK` queries at once."""
    b, _, s, _ = q.shape
    bq = _q_blocks(s)

    def one(block):
        qi, wi, lo = block
        with jax.named_scope("attn.index.score"):
            # + 0.0: a sum of -0.0 terms is -0.0, which a key order would
            # put under +0.0; ties at zero are ties
            scores = jnp.where(_causal(bq, s, lo)[None], jnp.sum(
                wi[..., None] * jax.nn.relu(_products(qi, k)), axis=1) + 0.0,
                -jnp.inf)
        with jax.named_scope("attn.index.select"):
            return scores, select_topk(scores, topk, lo)

    scores, select = jax.lax.map(
        one, _in_blocks(q, w) + (jnp.arange(0, s, bq),))  # [n, B, bq, S]
    return (scores.transpose(1, 0, 2, 3).reshape(b, s, s),
            select.transpose(1, 0, 2, 3).reshape(b, s, s))


def _scores_bwd(q, k, w, ds):
    """(dQI, dKI, dW) of sum(I * ds): the products of each block of queries
    once more; `ds` is zero off the selection, so only selected pairs
    contribute."""
    b, h, s, d = q.shape
    bq = _q_blocks(s)
    ds = jnp.where(_causal(s, s)[None], ds.astype(jnp.float32), 0.0)
    dsb = ds.reshape(b, s // bq, bq, s).transpose(1, 0, 2, 3)

    @jax.named_scope("attn.index.score")
    def one(dk, block):
        qi, wi, dsi = block
        p = _products(qi, k)
        dwi = jnp.sum(dsi[:, None] * jax.nn.relu(p), axis=-1)   # [B, H, bq]
        g = (jnp.where(p > 0, dsi[:, None], 0.0)
             * wi[..., None]).astype(q.dtype)
        dqi = jnp.einsum("bhts,bsd->bhtd", g, k,
                         preferred_element_type=jnp.float32)
        return dk + jnp.einsum("bhts,bhtd->bsd", g, qi,
                               preferred_element_type=jnp.float32), (dqi,
                                                                      dwi)

    dk, (dq, dw) = jax.lax.scan(one, jnp.zeros((b, s, d), jnp.float32),
                                _in_blocks(q, w) + (dsb,))
    dq = dq.transpose(1, 2, 0, 3, 4).reshape(b, h, s, d)
    dw = dw.transpose(1, 0, 3, 2).reshape(b, s, h)
    return dq.astype(q.dtype), dk.astype(k.dtype), dw.astype(w.dtype)


def _route(count, q):
    """The score kernels' plan where their shape rule takes the operands
    (`ops/pallas/index_scores.py` `plan`), else None: the `jax.numpy` form
    above. `count`: whether this trace's call counts, `attn.index_pallas` /
    `attn.index_xla`, once per forward or backward lowered."""
    from .pallas import index_scores
    plan = index_scores.plan(q.shape, q.dtype)
    if count:
        from ..observability import metrics
        metrics.inc("attn.index_xla" if plan is None else "attn.index_pallas")
    return plan


def _routed_fwd(count, q, k, w, topk):
    """`_index_fwd` by the route the plan chose: the kernel writes the
    scores whole, the selection reads them a block of `Q_BLOCK` queries at
    a time."""
    plan = _route(count, q)
    if plan is None:
        return _index_fwd(q, k, w, topk)
    from .pallas import index_scores
    b, _, s, _ = q.shape
    bq = _q_blocks(s)
    with jax.named_scope("attn.index.score"):
        scores = index_scores.scores_fwd(plan, q, k, w)
    with jax.named_scope("attn.index.select"):
        select = jax.lax.map(
            lambda lo: select_topk(jax.lax.dynamic_slice_in_dim(
                scores, lo, bq, axis=1), topk, lo), jnp.arange(0, s, bq))
    return scores, select.transpose(1, 0, 2, 3).reshape(b, s, s)


def _routed_bwd(count, q, k, w, ds):
    plan = _route(count, q)
    if plan is None:
        return _scores_bwd(q, k, w, ds)
    from .pallas import index_scores
    with jax.named_scope("attn.index.score"):
        return index_scores.scores_bwd(plan, q, k, w, ds)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def index_scores_and_select(q, k, w, topk, count=False, relowered=False):
    """(I [B, S, S] float32, -inf above the diagonal; the selection [B, S,
    S] int8 of `topk` keys a query). A gradient passes through I alone.
    `count`: the route counts (`_route`). `relowered` (the generic
    `__vjp__` differentiates a whole segment): JAX traces this body to a
    jaxpr it then replaces by the two rules below, so what is traced here
    is in no program and does not count."""
    return _routed_fwd(count and not relowered, q, k, w, topk)


index_scores_and_select.defvjp(
    lambda q, k, w, topk, count, relowered: (
        _routed_fwd(count, q, k, w, topk), (q, k, w)),
    lambda topk, count, relowered, res, cts: _routed_bwd(count, *res, cts[0]))


def index_scores(q, k, w):
    """I [B, S, S] float32 alone."""
    return index_scores_and_select(q, k, w, 1)[0]


def _order_keys(scores, first_row):
    """uint32 keys in the order of the float32 scores (-0.0 as +0.0), 0
    above the diagonal (every score's key, -inf's too, is above 0)."""
    bits = jax.lax.bitcast_convert_type(scores + 0.0, jnp.uint32)
    keys = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    return jnp.where(_causal(*scores.shape[-2:], first_row)[None], keys,
                     jnp.uint32(0))


def select_topk(scores, topk: int, first_row=0):
    """[B, R, S] int8 of the scores of queries first_row .. first_row + R:
    1 on the min(t + 1, topk) keys s <= t of query t with the largest
    scores, ties to the lower s."""
    keys = _order_keys(jax.lax.stop_gradient(scores), first_row)

    def grow(i, kth):
        # the largest key that `topk` keys of the row reach, bit by bit
        cand = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        reach = jnp.sum(keys >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(reach >= topk, cand, kth)

    kth = jax.lax.fori_loop(0, 32, grow,
                            jnp.zeros(keys.shape[:2], jnp.uint32))[..., None]
    above = keys > kth
    # kth == 0: the row has fewer than topk keys, all of them above
    at = (keys == kth) & (kth > 0)
    room = topk - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    first = jnp.cumsum(at, axis=-1, dtype=jnp.int32) <= room
    return (above | (at & first)).astype(jnp.int8)


def index_loss(scores, select, target):
    """mean_t KL(target_t || softmax over the selected of scores_t)."""
    sel = select != 0
    scores = jnp.where(sel, scores.astype(jnp.float32), 0.0)
    logq = scores - jax.nn.logsumexp(jnp.where(sel, scores, -jnp.inf),
                                     axis=-1, keepdims=True)
    p = jax.lax.stop_gradient(target.astype(jnp.float32))
    on = sel & (p > 0)
    kl = jnp.where(on, p * (jnp.log(jnp.where(on, p, 1.0)) - logq), 0.0)
    return jnp.mean(jnp.sum(kl, axis=-1))


def _unpack(ins):
    q, k, w = ins["QI"][0], ins["KI"][0], ins["W"][0]
    return q, k.astype(q.dtype), w


def _sparse_index_grad(ctx, ins, attrs, outs, ogs):
    """Grad rule: the cotangent of `Scores` (the loss's, zero off the
    selection) to QI, KI, W; `Select` and `PairsPerQuery` pass none."""
    ds = (ogs.get("Scores") or [None])[0]
    if ds is None:
        return None
    q, k, w = _unpack(ins)
    dq, dk, dw = _routed_bwd(not ctx.is_eval_shape, q, k, w, ds)
    return {"QI": [dq], "KI": [dk.astype(ins["KI"][0].dtype)], "W": [dw]}


@register("sparse_index", grad=_sparse_index_grad)
def _sparse_index(ctx, ins, attrs):
    from ..observability import metrics
    q, k, w = _unpack(ins)
    topk = int(attrs["topk"])
    if not (ctx.is_eval_shape or ctx.in_vjp):
        metrics.inc("attn.sparse_layers_lowered")
    scores, select = index_scores_and_select(
        q, k, w, topk, not ctx.is_eval_shape, ctx.in_vjp)
    # one byte a pair against 32 passes over the scores and a running count:
    # a recomputed segment reads the selection it made (the scores it makes
    # again, for the loss's backward)
    select = keep_under_recompute(select)
    with jax.named_scope("attn.index.select"):
        pairs = jnp.sum(select, dtype=jnp.float32).reshape(1) / (
            select.shape[0] * select.shape[1])
    return {"Scores": [scores], "Select": [select], "PairsPerQuery": [pairs]}


@register("sparse_index_loss", nondiff_slots=("Select", "Target"))
def _sparse_index_loss(ctx, ins, attrs):
    with jax.named_scope("attn.index.loss"):
        return {"Loss": [index_loss(ins["Scores"][0], ins["Select"][0],
                                    ins["Target"][0])]}


@register("detach")
def _detach(ctx, ins, attrs):
    """X with no gradient behind it, also where a recomputed segment
    differentiates the ops' lowerings and reads no variable's flag."""
    return {"Out": [jax.lax.stop_gradient(ins["X"][0])]}
