"""What the tests of the causal flash kernels share
(tests/test_flash_attention.py at one width, 64;
tests/test_attention_two_widths.py at latent attention's two, 192 and 128):
a plain masked softmax that gives 0 where a row has no key, and one
comparison of the four kernels with it, forward and all three gradients.
"""
import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import flash_attention as fa

# S with block_q / block_k: the last q block of 128 / 128 has several
# blocks no position masks, of 128 / 256 one, the first of 256 / 128 none
GEOMETRIES = [(512, 128, 128), (512, 128, 256), (512, 256, 128)]
# none; smaller than a block; no multiple of either block; three blocks
WINDOWS = [None, 64, 300, 384]


def kept(s, window=None, select=None):
    """[1 or B, S, S] bool: the pairs a causal layer attends."""
    pos = np.arange(s)
    keep = np.asarray(fa._visible(pos[:, None], pos[None, :], window))[None]
    return keep if select is None else keep & (np.asarray(select) != 0)


def masked_softmax_attention(q, k, v, keep, scale):
    """(out, heads' mean of probs) over the pairs of `keep` [B, S, S], K
    and V repeated to the query heads; a row that keeps no key gives 0."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    keep = jnp.asarray(keep)[:, None]
    scores = jnp.where(keep, jnp.einsum("bnqd,bnkd->bnqk", q, k) * scale,
                       -jnp.inf)
    top = jnp.max(scores, -1, keepdims=True)
    p = jnp.where(keep, jnp.exp(scores - jnp.where(keep.any(-1, keepdims=True),
                                                   top, 0.0)), 0.0)
    probs = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    return jnp.einsum("bnqk,bnkd->bnqd", probs, v), probs.mean(1)


def check_causal_kernels(s, block_q, block_k, window=None, nh=4, nkv=4,
                         dqk=64, dv=64, select=None, tol=2e-5):
    """Forward, dq, dk, dv (and with `select` the heads' mean of the
    probabilities) of the kernels under the interpreter against the plain
    masked softmax, float32: they differ by the order of the online
    softmax's sums and of the sum over a group's query heads."""
    rng = np.random.RandomState(s + block_q + 2 * block_k + (window or 0))
    q = jnp.asarray(rng.randn(1, nh, s, dqk), jnp.float32)
    k = jnp.asarray(rng.randn(1, nkv, s, dqk), jnp.float32)
    v = jnp.asarray(rng.randn(1, nkv, s, dv), jnp.float32)
    cot = jnp.asarray(rng.randn(1, nh, s, dv), jnp.float32)
    scale = dqk ** -0.5
    kw = dict(scale=scale, causal=True, window=window, select=select,
              block_q=block_q, block_k=block_k)
    out, lse = fa.flash_attention(q, k, v, return_lse=True, **kw)
    grads = fa.flash_attention_bwd(q, k, v, out, lse, cot, **kw)
    keep = kept(s, window, select)
    (want, want_target), vjp = jax.vjp(
        lambda q, k, v: masked_softmax_attention(q, k, v, keep, scale),
        q, k, v)
    assert out.shape == (1, nh, s, dv) and grads[0].shape == q.shape
    assert grads[1].shape == k.shape and grads[2].shape == v.shape
    for name, got, ref in zip(
            ("out", "dq", "dk", "dv"), (out,) + tuple(grads),
            (want,) + vjp((cot, jnp.zeros_like(want_target)))):
        assert np.isfinite(np.asarray(got)).all(), name
        err = float(jnp.abs(got - ref).max() / jnp.abs(ref).max())
        assert err < tol, (name, err)
    empty = ~keep.any(-1)                                   # [1 or B, S]
    assert (np.isneginf(np.asarray(lse).reshape(1, nh, s))
            == empty[:, None]).all()
    if select is None:
        return
    target = fa.selected_probs_sum(q, k, lse, select, scale=scale,
                                   block_q=block_q, block_k=block_k)
    np.testing.assert_allclose(target, want_target, rtol=tol, atol=1e-7)
    assert not np.asarray(target)[~np.broadcast_to(keep, target.shape)].any()
    np.testing.assert_allclose(np.asarray(target).sum(-1), 1.0 - empty,
                               rtol=1e-5)


def selection_with_empty_rows(s, block_q, block_k, seed=0):
    """[1, S, S] int8, causal, about a third of the pairs, and in the last
    q block, whose loop passes blocks no position masks: a row that keeps
    its own key alone (empty in every such block: the running maximum
    reaches the diagonal's block still -inf), one that keeps the first key
    alone (empty in all that follow) and one that keeps nothing."""
    rng = np.random.RandomState(seed)
    sel = (rng.rand(1, s, s) < 0.3) & np.tril(np.ones((s, s), bool))
    sel[0, np.arange(s), np.arange(s)] = True
    row = s - block_q
    assert row // block_k > 0, "the last q block needs a block before it"
    sel[0, row + 1:row + 4] = False
    sel[0, row + 1, row + 1] = True
    sel[0, row + 2, 0] = True
    return jnp.asarray(sel.astype(np.int8))


def padding_mask(b, s, seed=0):
    """Additive key-padding mask [B, 1, 1, S] float32: every row keeps at
    least half its keys."""
    lens = np.random.RandomState(seed).randint(s // 2, s + 1, size=b)
    return jnp.asarray(np.where(np.arange(s)[None] < lens[:, None], 0.0,
                                -1e9)[:, None, None, :], jnp.float32)


def check_rows_layout(b, s, nh, nkv, hd, *, causal=False, window=None,
                      dropout=0.0, padded=False, block_q=256, block_k=512,
                      select=None, tol=2e-6):
    """The launches in layout "bshd" (q [B, S, nh, hd], k and v
    [B, S, nkv, hd]: a projection's rows as they lie) against the launches
    in layout "bhsd" on the same operands transposed: the forward, its lse
    and all three gradients, float32. A head's scores, softmax and dropout
    pattern are the same numbers in the same order in both, so `out`, `lse`
    and dq are held BIT FOR BIT (one kept probability that differed would
    show); dk and dv of a 64-wide pair sum over the pair's stacked rows in
    another order and are held to `tol`. With `select` (causal, 128 wide)
    the selection rides along and `selected_probs_sum` is held too."""
    rng = np.random.RandomState(b + s + nh + 3 * nkv + hd)
    q, cot = (jnp.asarray(rng.randn(b, s, nh, hd), jnp.float32)
              for _ in range(2))
    k, v = (jnp.asarray(rng.randn(b, s, nkv, hd), jnp.float32)
            for _ in range(2))
    kw = dict(scale=hd ** -0.5, causal=causal, window=window,
              dropout=dropout, seed=11 if dropout else None,
              mask=padding_mask(b, s) if padded else None,
              block_q=block_q, block_k=block_k, select=select)

    def launches(layout, *ops):
        q, k, v, cot = ops
        out, lse = fa.flash_attention(q, k, v, return_lse=True,
                                      layout=layout, **kw)
        target = ()
        if select is not None:
            target = (fa.selected_probs_sum(
                q, k, lse, select, scale=kw["scale"], block_q=block_q,
                block_k=block_k, layout=layout),)
        return (out, lse) + tuple(fa.flash_attention_bwd(
            q, k, v, out, lse, cot, layout=layout, **kw)) + target

    def swap(t):
        return jnp.swapaxes(t, 1, 2)

    rows = launches("bshd", q, k, v, cot)
    heads = launches("bhsd", *(swap(t) for t in (q, k, v, cot)))
    assert rows[0].shape == (b, s, nh, hd) and rows[1].shape == (b * nh, s)
    assert rows[2].shape == q.shape
    assert rows[3].shape == k.shape and rows[4].shape == v.shape
    heads = ((swap(heads[0]), heads[1]) + tuple(swap(t) for t in heads[2:5])
             + heads[5:])
    for name, got, want in zip(("out", "lse", "dq", "dk", "dv", "target"),
                               rows, heads):
        got, want = np.asarray(got), np.asarray(want)
        # a row with no key has lse -inf, in both
        assert name == "lse" or np.isfinite(got).all(), name
        if name in ("out", "lse", "dq") or hd % 128 == 0:
            assert np.array_equal(got, want), name
        else:
            err = np.abs(got - want).max() / np.abs(want).max()
            assert err < tol, (name, err)
