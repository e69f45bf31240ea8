"""Vocab-chunked LM-head cross-entropy over the labelled rows
(`fused_lm_head_ce`).

Reference counterpart (what it replaces, not how it works): the
`matmul(seq, wte^T)` / `fc` + `softmax_with_cross_entropy` pair every LM
builds (reference fluid/layers/loss.py:1080 softmax_with_cross_entropy
over the full logits tensor; the fused-op family in operators/fused/
exists for exactly this class of HBM-bound epilogues).

Why: at real LM scale the `[B, S, V]` logits tensor IS the memory peak —
GPT-2's V=50257 at B=32, S=512 is 3.3 GB in f32 before the softmax's own
intermediates, and BERT's V=30522 at B=128, S=128 (one chip of the
four-chip bench cell, which takes this op since PR 50) is 2.0 GB. The
TPU-native fix is streaming: an online logsumexp over vocab chunks
(flash-attention's trick applied to the classifier), so at most one
`[R, C]` block of logits is ever live, and a `jax.custom_vjp` recomputes
each block in the backward pass instead of saving it (same FLOPs trade as
activation remat: one extra head matmul per chunk in exchange for never
materializing the logits).

Only the rows that carry a label are computed (PR 50). A row labelled
`ignore_index` has zero loss and zero gradients by the op's contract, and
a masked LM labels a seventh of its positions: the rows are flattened to
`[N, H]`, a stable sort puts the labelled ones first, and both directions
walk `ceil(n / R)` blocks of `ROW_BLOCK` = R rows, `n` the count of
labels — a loop whose trip count is data (both directions are this file's
own code under the one `custom_vjp`, so JAX differentiates no loop).
Vocab chunks are the outer loop and row blocks the inner one, so what is
carried between blocks is the packed rows' `[N]` statistics forward and
one chunk's `[C, H]` weight gradient backward, never the `[V, H]` one.
Work is proportional to `n` in steps of one block, for every `n` from 0
to N; a causal LM labels every row and walks them all. The sort is over
the rows the op is given: under a dp `shard_map` those are a device's
own, and no collective is added.

Both matmuls per block stay MXU-shaped ([R, H] x [H, C]) and accumulate
f32 (`preferred_element_type`), so bf16 AMP inputs lose no loss precision
(the op is AMP white-listed). The label's logit rides the same walk
(gathered from the chunk that contains it); padded tail rows of a ragged
final chunk are masked to -inf so they never enter the logsumexp.
Supports both weight layouts — `[V, H]` (GPT's tied embedding) and
`[H, V]` (BERT's fc head) — plus an optional `[V]` bias.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .registry import register

DEFAULT_CHUNK = 8192
# Rows a block of the walk computes. From the sweep on the chip at
# [16384, 768] x [768, 30522] and kept shares 0.1125 / 0.1484 / 1.0
# (scripts/head_rows_sweep.py; PERF.md section 6, PR 50).
ROW_BLOCK = 512


def _pad_w(w, b, chunk):
    """w: [V, H]; b: [V]. Pad the vocab dim to a chunk multiple and
    reshape into per-chunk leaves for the scan."""
    v, h = w.shape
    n_chunks = -(-v // chunk)
    pad = n_chunks * chunk - v
    if pad:
        w = jnp.pad(w, ((0, pad), (0, 0)))
        b = jnp.pad(b, (0, pad))
    return (w.reshape(n_chunks, chunk, h),
            b.reshape(n_chunks, chunk), n_chunks, v)


def _chunk_logits(x, w_c, b_c, c0, chunk, v):
    """f32 logits of one row block for one chunk, padded-vocab tail masked
    to -inf. x: [R, H]; w_c: [C, H]; b_c: [C] -> [R, C]."""
    l_c = jnp.einsum("rh,ch->rc", x, w_c,
                     preferred_element_type=jnp.float32)
    l_c = l_c + b_c.astype(jnp.float32)[None, :]
    valid = (c0 + jnp.arange(chunk)) < v
    return jnp.where(valid[None, :], l_c, -jnp.inf)


def _token_grade(labels, v, ignore_index):
    """(ignored, valid): ignore_index tokens are dropped from the loss
    (zero loss AND zero grads — reference softmax_with_cross_entropy
    ignore_index semantics); other out-of-range labels stay loud NaN."""
    ignored = labels == ignore_index
    valid = (labels >= 0) & (labels < v) & ~ignored
    return ignored, valid


def _row_block(n_rows):
    """Rows a block: `ROW_BLOCK`, or all of them where there are fewer."""
    return max(1, min(ROW_BLOCK, n_rows))


def _blocks_walked(labels, ignore_index, rows):
    """Row blocks the labelled rows fill: ceil(n / rows), int32."""
    kept = jnp.sum((labels != ignore_index).astype(jnp.int32))
    return (kept + rows - 1) // rows


def _row_plan(labels, ignore_index, rows):
    """labels: [N] -> (order [Np], packed labels [Np], blocks), Np = N up
    to a multiple of `rows`. `order` lists the labelled rows first, in
    their own order, then the others, then the Np - N places past the end
    (a gather fills those, a scatter drops them); a label outside [0, V)
    that is not `ignore_index` counts as labelled, and stays loud."""
    n_rows = labels.shape[0]
    pad = -n_rows % rows
    drop = (labels == ignore_index).astype(jnp.int32)
    _, order, packed = jax.lax.sort(
        (drop, jnp.arange(n_rows, dtype=jnp.int32), labels),
        num_keys=1, is_stable=True)
    if pad:
        order = jnp.concatenate(
            [order, jnp.arange(n_rows, n_rows + pad, dtype=jnp.int32)])
        packed = jnp.concatenate(
            [packed, jnp.full((pad,), ignore_index, labels.dtype)])
    return order, packed, _blocks_walked(labels, ignore_index, rows)


def _cut(a, i, rows):
    """Block `i` of `a`'s leading axis."""
    return jax.lax.dynamic_slice_in_dim(a, i * rows, rows, axis=0)


def _put(a, blk, i, rows):
    """`a` with block `i` of its leading axis replaced by `blk`."""
    return jax.lax.dynamic_update_slice_in_dim(a, blk, i * rows, axis=0)


def _pack(vals, order, blocks, rows):
    """vals: [N, ...] -> [Np, ...] whose first `blocks` blocks hold vals'
    rows in `order`; a block never walked holds zeros."""
    def body(i, out):
        blk = vals.at[_cut(order, i, rows)].get(mode="fill", fill_value=0)
        return _put(out, blk, i, rows)

    return jax.lax.fori_loop(
        0, blocks, body,
        jnp.zeros(order.shape[:1] + vals.shape[1:], vals.dtype))


def _unpack(packed, order, blocks, rows, n_rows, dtype):
    """The inverse of `_pack`: the walked blocks' rows back at their own
    places of [N, ...] in `dtype`, zeros at every place not visited."""
    def body(i, out):
        return out.at[_cut(order, i, rows)].set(
            _cut(packed, i, rows).astype(dtype), mode="drop",
            unique_indices=True)

    return jax.lax.fori_loop(
        0, blocks, body, jnp.zeros((n_rows,) + packed.shape[1:], dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _chunked_lm_ce(x, w, b, labels, chunk, ignore_index):
    loss, _ = _fwd_walk(x, w, b, labels, chunk, ignore_index)
    return loss


def _fwd_walk(x, w, b, labels, chunk, ignore_index):
    """x: [..., H]; labels: [...] -> (loss [..., 1] f32, what the backward
    walks again: the row plan and the packed rows' logsumexp)."""
    w_chunks, b_chunks, n_chunks, v = _pad_w(w, b, chunk)
    xf = x.reshape(-1, x.shape[-1])
    n_rows = xf.shape[0]
    rows = _row_block(n_rows)
    order, lab_p, blocks = _row_plan(labels.reshape(-1), ignore_index, rows)
    xp = _pack(xf, order, blocks, rows)
    cols = jnp.arange(chunk)

    def chunk_body(carry, leaves):
        w_c, b_c, idx = leaves
        c0 = idx * chunk

        def block_body(i, carry):
            m, ssum, lab = carry
            l_c = _chunk_logits(_cut(xp, i, rows), w_c, b_c, c0, chunk, v)
            m_old = _cut(m, i, rows)
            m_new = jnp.maximum(m_old, jnp.max(l_c, axis=-1))
            s_new = _cut(ssum, i, rows) * jnp.exp(m_old - m_new) + jnp.sum(
                jnp.exp(l_c - m_new[:, None]), axis=-1)
            off = _cut(lab_p, i, rows) - c0
            in_chunk = (off >= 0) & (off < chunk)
            picked = jnp.sum(
                jnp.where(cols[None, :] == off[:, None], l_c, 0.0), axis=-1)
            lab_new = jnp.where(in_chunk, picked, _cut(lab, i, rows))
            return (_put(m, m_new, i, rows), _put(ssum, s_new, i, rows),
                    _put(lab, lab_new, i, rows))

        return jax.lax.fori_loop(0, blocks, block_body, carry), None

    n_pad = order.shape[0]
    init = (jnp.full((n_pad,), -jnp.inf, jnp.float32),
            jnp.zeros((n_pad,), jnp.float32),
            jnp.zeros((n_pad,), jnp.float32))
    (m, ssum, lab), _ = jax.lax.scan(
        chunk_body, init, (w_chunks, b_chunks, jnp.arange(n_chunks)))
    lse_p = m + jnp.log(ssum)
    # Label contract: `ignore_index` tokens (default -100, the reference
    # convention) contribute ZERO loss and zero grads. Any OTHER label
    # outside [0, V) yields NaN for that token — loud and deterministic,
    # where the dense pair's out-of-bounds gather is backend-defined
    # garbage.
    ignored, valid = _token_grade(lab_p, v, ignore_index)
    loss_p = jnp.where(valid, lse_p - lab, jnp.nan)
    loss_p = jnp.where(ignored, 0.0, loss_p)
    loss = _unpack(loss_p, order, blocks, rows, n_rows, jnp.float32)
    return loss.reshape(labels.shape + (1,)), (order, lab_p, blocks, lse_p)


def _ce_fwd(x, w, b, labels, chunk, ignore_index):
    loss, plan = _fwd_walk(x, w, b, labels, chunk, ignore_index)
    return loss, (x, w, b, plan)


def _ce_bwd(chunk, ignore_index, res, g):
    x, w, b, (order, lab_p, blocks, lse_p) = res
    w_chunks, b_chunks, n_chunks, v = _pad_w(w, b, chunk)
    h = x.shape[-1]
    xf = x.reshape(-1, h)
    n_rows = xf.shape[0]
    rows = _row_block(n_rows)
    xp = _pack(xf, order, blocks, rows)
    gp = _pack(g.reshape(-1).astype(jnp.float32), order, blocks, rows)
    # ignored tokens drop out of every gradient term; remaining
    # out-of-range labels NaN the forward loss, so make the gradients
    # loud too (an all-zero one_hot would otherwise emit a finite,
    # label-term-free gradient that silently corrupts training)
    ignored, valid = _token_grade(lab_p, v, ignore_index)
    gp = jnp.where(valid, gp, jnp.nan)
    gp = jnp.where(ignored, 0.0, gp)

    def chunk_body(dxp, leaves):
        w_c, b_c, idx = leaves
        c0 = idx * chunk
        w_f = w_c.astype(jnp.float32)

        def block_body(i, carry):
            dxp, dw_c, db_c = carry
            x_b = _cut(xp, i, rows)
            l_c = _chunk_logits(x_b, w_c, b_c, c0, chunk, v)
            p_c = jnp.exp(l_c - _cut(lse_p, i, rows)[:, None])  # -inf -> 0
            off = _cut(lab_p, i, rows) - c0          # out-of-range -> all-0
            onehot = jax.nn.one_hot(off, chunk, dtype=jnp.float32)
            dl = (p_c - onehot) * _cut(gp, i, rows)[:, None]   # [R, C] f32
            dx_b = _cut(dxp, i, rows) + jnp.einsum("rc,ch->rh", dl, w_f)
            dw_c = dw_c + jnp.einsum("rc,rh->ch", dl,
                                     x_b.astype(jnp.float32))
            return _put(dxp, dx_b, i, rows), dw_c, db_c + jnp.sum(dl, axis=0)

        # the carry between row blocks is this chunk's [C, H], not [V, H]
        dxp, dw_c, db_c = jax.lax.fori_loop(
            0, blocks, block_body,
            (dxp, jnp.zeros((chunk, h), jnp.float32),
             jnp.zeros((chunk,), jnp.float32)))
        return dxp, (dw_c, db_c)

    dxp, (dw_stack, db_stack) = jax.lax.scan(
        chunk_body, jnp.zeros(order.shape + (h,), jnp.float32),
        (w_chunks, b_chunks, jnp.arange(n_chunks)))
    dx = _unpack(dxp, order, blocks, rows, n_rows, x.dtype).reshape(x.shape)
    dw = dw_stack.reshape(n_chunks * chunk, h)[:v]
    db = db_stack.reshape(n_chunks * chunk)[:v]
    return dx, dw.astype(w.dtype), db.astype(b.dtype), None


_chunked_lm_ce.defvjp(_ce_fwd, _ce_bwd)


def rows_computed(labels, ignore_index=-100):
    """The rows `fused_lm_head_ce` computes for `labels` ([...], any int
    dtype): the labelled count up to whole blocks, int32 — the op's `Rows`
    output."""
    rows = _row_block(labels.size)
    return _blocks_walked(labels.reshape(-1), ignore_index, rows) * rows


def record_rows_share(rows, total) -> float:
    """Set gauge `head.rows_computed_share` from a fetched `Rows` value and
    the op's row count; called wherever the host reads that output."""
    from ..observability import metrics
    share = float(rows) / float(total)
    metrics.set_gauge("head.rows_computed_share", share)
    return share


@register("fused_lm_head_ce", nondiff_slots=("Label",))
def _fused_lm_head_ce(ctx, ins, attrs):
    x, w, label = ins["X"][0], ins["W"][0], ins["Label"][0]
    bias = (ins.get("Bias") or [None])[0]
    if attrs.get("w_layout", "vh") == "hv":          # fc-style [H, V]
        w = w.T                                      # XLA folds into the dot
    chunk = int(attrs.get("chunk") or DEFAULT_CHUNK)
    labels = label.astype(jnp.int32)
    if labels.ndim == x.ndim:                        # [B, S, 1] -> [B, S]
        labels = labels[..., 0]
    chunk = min(chunk, max(int(w.shape[0]), 1))
    if bias is None:
        bias = jnp.zeros((w.shape[0],), x.dtype)
    ignore_index = int(attrs.get("ignore_index", -100))
    loss = _chunked_lm_ce(x, w, bias, labels, chunk, ignore_index)
    return {"Loss": [loss.astype(jnp.float32)],
            "Rows": [rows_computed(labels, ignore_index).reshape(1)]}
