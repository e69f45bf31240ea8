"""Flash attention kernel numerics: forward and backward vs dense reference.

Runs the pallas kernels in interpreter mode on CPU (the same code path
compiles via Mosaic on real TPU; bench.py exercises that). Mirrors the
reference's fused-attention tests (test_fused_multihead_matmul_op.py
pattern: dense numpy reference, tight tolerances).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import flash_harness
from flash_harness import GEOMETRIES, WINDOWS


def _dense_ref(q, k, v, scale, causal):
    s = jnp.einsum("bnqd,bnkd->bnqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        sl = q.shape[2]
        mask = jnp.tril(jnp.ones((sl, sl), bool))[None, None]
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bnqk,bnkd->bnqd", p, v.astype(jnp.float32))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("seq,block_q,block_k", [
    (256, 128, 128),   # multiple blocks both ways
    (128, 256, 512),   # blocks clamped to seq
    (512, 256, 128),   # k blocks < q blocks and vice versa
])
def test_flash_fwd_bwd_matches_dense(causal, seq, block_q, block_k):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    rng = np.random.RandomState(0)
    b, nh, hd = 2, 2, 64
    q = jnp.asarray(rng.randn(b, nh, seq, hd).astype(np.float32))
    k = jnp.asarray(rng.randn(b, nh, seq, hd).astype(np.float32))
    v = jnp.asarray(rng.randn(b, nh, seq, hd).astype(np.float32))
    do = jnp.asarray(rng.randn(b, nh, seq, hd).astype(np.float32))
    scale = 1.0 / np.sqrt(hd)

    out = flash_attention(q, k, v, scale, causal, block_q, block_k)
    ref = _dense_ref(q, k, v, scale, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

    def loss_flash(q, k, v):
        return jnp.vdot(flash_attention(q, k, v, scale, causal,
                                        block_q, block_k), do)

    def loss_ref(q, k, v):
        return jnp.vdot(_dense_ref(q, k, v, scale, causal), do)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4,
            err_msg=f"d{name} mismatch (causal={causal}, seq={seq})")


def test_flash_dropout_mask_semantics():
    """v = I recovers the dropped prob matrix: check drop rate, upscale
    factor, determinism per seed, and dropout=0 == plain path."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    rng = np.random.RandomState(0)
    S = hd = 128
    rate = 0.1
    q = jnp.asarray(rng.randn(1, 2, S, hd).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(1, 2, S, hd).astype(np.float32)) * 0.3
    v_eye = jnp.broadcast_to(jnp.eye(S, dtype=jnp.float32), (1, 2, S, S))

    out = flash_attention(q, k, v_eye, 1.0, False, 128, 128,
                          dropout=rate, seed=42)
    pd = np.asarray(out)
    probs = np.asarray(jax.nn.softmax(
        jnp.einsum("bnqd,bnkd->bnqk", q, k), axis=-1))
    mask = pd != 0
    assert abs((1 - mask.mean()) - rate) < 0.02, "drop fraction off"
    ratio = pd[mask] / probs[mask]
    np.testing.assert_allclose(ratio, 1.0 / (1 - rate), rtol=1e-5)

    out2 = flash_attention(q, k, v_eye, 1.0, False, 128, 128,
                           dropout=rate, seed=42)
    assert bool(jnp.all(out == out2)), "same seed must reproduce"
    out3 = flash_attention(q, k, v_eye, 1.0, False, 128, 128,
                           dropout=rate, seed=43)
    assert bool(jnp.any(out != out3)), "different seed must differ"
    plain = flash_attention(q, k, v_eye, 1.0, False, 128, 128)
    zero = flash_attention(q, k, v_eye, 1.0, False, 128, 128,
                           dropout=0.0, seed=7)
    assert bool(jnp.all(plain == zero))


def test_flash_dropout_grads_match_dense_with_same_mask():
    """The in-kernel mask depends only on (seed, head, positions), so recover
    it via uniform probs + v=I, then check fwd and all three grads against a
    dense implementation using that exact mask."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    rng = np.random.RandomState(1)
    S = hd = 128
    rate, seed = 0.15, 7
    q = jnp.asarray(rng.randn(2, 2, S, hd).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(2, 2, S, hd).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(2, 2, S, hd).astype(np.float32))
    v_eye = jnp.broadcast_to(jnp.eye(S, dtype=jnp.float32), (2, 2, S, S))

    pd = flash_attention(jnp.zeros_like(q), jnp.zeros_like(k), v_eye,
                         1.0, False, 128, 128, dropout=rate, seed=seed)
    keep = jnp.asarray(np.asarray(pd) != 0)

    def dense(q, k, v):
        p = jax.nn.softmax(
            jnp.einsum("bnqd,bnkd->bnqk", q, k) * (hd ** -0.5), axis=-1)
        return jnp.einsum("bnqk,bnkd->bnqd",
                          jnp.where(keep, p / (1 - rate), 0.0), v)

    def flash(q, k, v):
        return flash_attention(q, k, v, None, False, 128, 128,
                               dropout=rate, seed=seed)

    cot = jnp.asarray(rng.randn(2, 2, S, hd).astype(np.float32))
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(dense(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    gf = jax.grad(lambda *a: jnp.vdot(flash(*a), cot), (0, 1, 2))(q, k, v)
    gd = jax.grad(lambda *a: jnp.vdot(dense(*a), cot), (0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("mask_shape", [
    (1, 1, 1, 256),   # shared key bias
    (2, 1, 1, 256),   # per-batch key padding (the padded-BERT case)
    (1, 2, 256, 256), # per-head full bias (ALiBi-style), batch-broadcast
    (2, 2, 256, 256), # distinct per (batch, head)
])
def test_flash_masked_matches_dense(mask_shape):
    """Additive mask applied in-kernel across fwd + both bwd kernels, for
    every head→mask broadcast layout the normalizer distinguishes."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    rng = np.random.RandomState(3)
    b, nh, seq, hd = 2, 2, 256, 64
    q = jnp.asarray(rng.randn(b, nh, seq, hd).astype(np.float32))
    k = jnp.asarray(rng.randn(b, nh, seq, hd).astype(np.float32))
    v = jnp.asarray(rng.randn(b, nh, seq, hd).astype(np.float32))
    do = jnp.asarray(rng.randn(b, nh, seq, hd).astype(np.float32))
    scale = 1.0 / np.sqrt(hd)
    # mix of -1e9 "pad" entries and small finite biases
    bias = rng.randn(*mask_shape).astype(np.float32)
    pad = (rng.rand(*mask_shape) < 0.25) * -1e9
    mask = jnp.asarray(bias + pad.astype(np.float32))

    def dense(q, k, v):
        s = jnp.einsum("bnqd,bnkd->bnqk", q, k) * scale + mask
        return jnp.einsum("bnqk,bnkd->bnqd", jax.nn.softmax(s, -1), v)

    def flash(q, k, v):
        return flash_attention(q, k, v, scale, False, 128, 128, mask=mask)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(dense(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    gf = jax.grad(lambda *a: jnp.vdot(flash(*a), do), (0, 1, 2))(q, k, v)
    gd = jax.grad(lambda *a: jnp.vdot(dense(*a), do), (0, 1, 2))(q, k, v)
    for a, bb, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} ({mask_shape})")


def test_flash_mask_dropout_causal_combined():
    """The round-4 target path: padding mask + dropout + causal, all
    in-kernel at once. Recover the dropout keep-mask via v=I then compare
    against a dense implementation using mask, causal triangle and that
    exact keep pattern."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    rng = np.random.RandomState(5)
    b, nh, S, hd = 2, 2, 128, 128
    rate, seed = 0.1, 11
    q = jnp.asarray(rng.randn(b, nh, S, hd).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(b, nh, S, hd).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(b, nh, S, hd).astype(np.float32))
    v_eye = jnp.broadcast_to(jnp.eye(S, dtype=jnp.float32), (b, nh, S, S))
    # pad out the last 32 keys of example 1
    pad = np.zeros((b, 1, 1, S), np.float32)
    pad[1, :, :, S - 32:] = -1e9
    mask = jnp.asarray(pad)

    pd = flash_attention(jnp.zeros_like(q), jnp.zeros_like(k), v_eye,
                         1.0, False, 128, 128, dropout=rate, seed=seed)
    keep = jnp.asarray(np.asarray(pd) != 0)

    tri = jnp.tril(jnp.ones((S, S), bool))[None, None]

    def dense(q, k, v):
        s = jnp.einsum("bnqd,bnkd->bnqk", q, k) * (hd ** -0.5) + mask
        s = jnp.where(tri, s, -jnp.inf)
        p = jax.nn.softmax(s, -1)
        return jnp.einsum("bnqk,bnkd->bnqd",
                          jnp.where(keep, p / (1 - rate), 0.0), v)

    def flash(q, k, v):
        return flash_attention(q, k, v, None, True, 128, 128,
                               dropout=rate, seed=seed, mask=mask)

    cot = jnp.asarray(rng.randn(b, nh, S, hd).astype(np.float32))
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(dense(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    gf = jax.grad(lambda *a: jnp.vdot(flash(*a), cot), (0, 1, 2))(q, k, v)
    gd = jax.grad(lambda *a: jnp.vdot(dense(*a), cot), (0, 1, 2))(q, k, v)
    for a, bb, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name} mismatch")


def test_flash_int_mask_is_cast():
    """An int additive mask must not poison the bwd cotangent pytree."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(1, 1, 128, 64).astype(np.float32))
    mask = jnp.asarray(
        (rng.rand(1, 1, 1, 128) < 0.3) * np.int32(-10 ** 9))
    out = flash_attention(q, q, q, None, False, 128, 128, mask=mask)
    g = jax.grad(lambda a: jnp.sum(flash_attention(
        a, a, a, None, False, 128, 128, mask=mask)))(q)
    assert np.isfinite(np.asarray(out)).all()
    assert np.isfinite(np.asarray(g)).all()


def test_flash_bf16_grads_finite():
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 2, 256, 64)).astype(jnp.bfloat16)
    k = jnp.asarray(rng.randn(1, 2, 256, 64)).astype(jnp.bfloat16)
    v = jnp.asarray(rng.randn(1, 2, 256, 64)).astype(jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, None, True, 128, 128)
                       .astype(jnp.float32))

    gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g in (gq, gk, gv):
        assert np.isfinite(np.asarray(g, dtype=np.float32)).all()
        assert g.dtype == jnp.bfloat16


def test_flash_bf16_matches_f32_dense_reference():
    """The MXU dots run in the INPUT dtype (bf16 under AMP) with f32
    accumulation — outputs and grads must stay close to the f32 dense
    oracle within bf16 tolerance."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    rng = np.random.RandomState(3)
    qf = rng.randn(1, 2, 256, 64).astype(np.float32)
    kf = rng.randn(1, 2, 256, 64).astype(np.float32)
    vf = rng.randn(1, 2, 256, 64).astype(np.float32)
    scale = 1.0 / np.sqrt(64.0)

    def dense(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)

    ref = dense(jnp.asarray(qf), jnp.asarray(kf), jnp.asarray(vf))
    gref = jax.grad(lambda a, b, c: jnp.sum(dense(a, b, c) ** 2),
                    argnums=(0, 1, 2))(
        jnp.asarray(qf), jnp.asarray(kf), jnp.asarray(vf))

    qb = jnp.asarray(qf).astype(jnp.bfloat16)
    kb = jnp.asarray(kf).astype(jnp.bfloat16)
    vb = jnp.asarray(vf).astype(jnp.bfloat16)
    out = flash_attention(qb, kb, vb, scale, False, 128, 128)
    gb = jax.grad(lambda a, b, c: jnp.sum(
        flash_attention(a, b, c, scale, False, 128, 128)
        .astype(jnp.float32) ** 2), argnums=(0, 1, 2))(qb, kb, vb)

    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=0.05, atol=0.05)
    for g, gr in zip(gb, gref):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(gr), rtol=0.1, atol=0.25)


# ---------------------------------------------------------------------------
# the causal kernels' three loops: the blocks a mask can change (the
# diagonal's, a window's trailing edge's) take the whole loop body, the
# blocks between them one with no position compare and no row guard
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nkv", [4, 1])
@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("s, block_q, block_k", GEOMETRIES)
def test_causal_kernels_match_a_plain_masked_softmax(s, block_q, block_k,
                                                     window, nkv):
    flash_harness.check_causal_kernels(s, block_q, block_k, window, nkv=nkv)


@pytest.mark.parametrize("nkv", [4, 1])
@pytest.mark.parametrize("s, block_q, block_k", GEOMETRIES)
def test_selected_kernels_take_rows_empty_inside_a_bare_block(
        s, block_q, block_k, nkv):
    """A selection can empty a row of a block no position masks, so there
    the guards stay: rows that keep one key, or none, read 0 elsewhere and
    no NaN, in the three kernels and in `selected_probs_sum`."""
    flash_harness.check_causal_kernels(
        s, block_q, block_k, nkv=nkv,
        select=flash_harness.selection_with_empty_rows(s, block_q, block_k))


@pytest.mark.parametrize("nkv", [4, 1])
def test_a_row_a_windows_edge_empties_meets_bare_blocks_next(nkv):
    """S 1024 at 256 / 128 under a window of 700: the last q block's loop
    starts with three blocks the window's trailing edge crosses; its last
    row sees nothing of the first two, so its running maximum is still
    -inf when the three bare blocks come, whose `exp(-inf - m)` has to be
    the 0 the guard gave."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    s, block_q, block_k, window = 1024, 256, 128, 700
    ranges = fa._loop_ranges(np.int64(3), block_q, block_k, s, True, window)
    assert ranges == [(0, 3, True, None), (3, 6, False, None),
                      (6, 8, True, 2)]
    rows = np.arange(768, 1024)[:, None]
    assert not fa._visible(rows, np.arange(256)[None], window)[-1].any()
    flash_harness.check_causal_kernels(s, block_q, block_k, window, nkv=nkv)


def _old_range(idx, block_q, block_k, s, window, over_q):
    """The one loop the kernels ran before the split."""
    if over_q:
        stop = s // block_q if window is None else min(
            s // block_q,
            ((idx + 1) * block_k - 1 + window - 1) // block_q + 1)
        return idx * block_k // block_q, stop
    first = 0 if window is None else max(
        idx * block_q - (window - 1), 0) // block_k
    return first, min(s // block_k,
                      ((idx + 1) * block_q + block_k - 1) // block_k)


@pytest.mark.parametrize("over_q", [False, True])
@pytest.mark.parametrize("window", WINDOWS + [700, 1024])
@pytest.mark.parametrize("s, block_q, block_k", GEOMETRIES + [
    (1024, 256, 128), (2048, 256, 512)])
def test_loop_ranges_tile_the_old_loop_by_what_a_mask_can_do(
        s, block_q, block_k, window, over_q):
    """From `_visible` alone, for every block of the grid: the three
    ranges tile the one range the kernels walked before; a block called
    interior has no masked pair; an edge block has both kinds; a block in
    no range has no visible pair; a range that states its length has it
    under every grid index, and the diagonal's states it where the blocks
    nest."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    own, other = (block_k, block_q) if over_q else (block_q, block_k)
    for idx in range(s // own):
        ranges = fa._loop_ranges(np.int64(idx), block_q, block_k, s, True,
                                 window, over_q)
        assert len(ranges) == (2 if window is None else 3)
        assert [edge for _, _, edge, _ in ranges] == {
            (False, 2): [False, True], (True, 2): [True, False]}.get(
                (over_q, len(ranges)), [True, False, True])
        bounds = [int(ranges[0][0])] + [int(r[1]) for r in ranges]
        assert bounds == sorted(bounds)
        assert all(int(a[1]) == int(b[0])
                   for a, b in zip(ranges, ranges[1:]))
        stated = [r[3] for r in ranges]
        assert all(n is None or n == int(r[1]) - int(r[0])
                   for r, n in zip(ranges, stated))
        assert stated[0 if over_q else -1] == max(
            (block_k // block_q) if over_q else (block_q // block_k), 1)
        assert (bounds[0], bounds[-1]) == _old_range(
            idx, block_q, block_k, s, window, over_q)
        kind = {}
        for start, stop, edge, _ in ranges:
            kind.update({blk: edge for blk in range(int(start), int(stop))})
        for blk in range(s // other):
            qb, kb = (blk, idx) if over_q else (idx, blk)
            seen = fa._visible(
                qb * block_q + np.arange(block_q)[:, None],
                kb * block_k + np.arange(block_k)[None, :], window)
            if blk not in kind:
                assert not seen.any(), (idx, blk)
            elif kind[blk]:
                assert seen.any() and not seen.all(), (idx, blk)
            else:
                assert seen.all(), (idx, blk)


def test_loop_ranges_without_causal_are_one_whole_loop():
    from paddle_tpu.ops.pallas import flash_attention as fa
    assert fa._loop_ranges(np.int64(1), 128, 256, 512, False, None) \
        == [(0, 2, True, 2)]
    assert fa._loop_ranges(np.int64(1), 128, 256, 512, False, None, True) \
        == [(0, 4, True, 4)]


@pytest.mark.parametrize("s, window, blocks, want", [
    (4096, None, (None, None), (56, 16)),     # a latent-attention layer
    (8192, None, (None, None), (240, 32)),    # a full layer at 8,192
    (8192, 1024, (None, None), (30, 60)),     # a sliding one
    (512, None, (None, None), (0, 2)),
    (512, None, (128, 128), (6, 4)),
    (512, 300, (128, 128), (3, 7))])
def test_causal_block_counts(s, window, blocks, want):
    """The pairs a head's forward visits by kind, at the default blocks
    (256 / 512) and at others, against a count from `_visible`."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    assert fa.causal_block_counts(s, window, *blocks) == want
    bq = fa._pick_block(s, blocks[0] or fa.DEFAULT_BLOCK_Q)
    bk = fa._pick_block(s, blocks[1] or fa.DEFAULT_BLOCK_K)
    pos = np.arange(s)
    tiles = fa._visible(pos[:, None], pos[None, :], window).reshape(
        s // bq, bq, s // bk, bk)
    some, every = tiles.any((1, 3)), tiles.all((1, 3))
    assert (int(every.sum()), int((some & ~every).sum())) == want


# ---------------------------------------------------------------------------
# layout "bshd": the kernels read and write a projection's own rows (PR 52)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", [
    # (i) BERT's: 12 heads of 64 in pairs, dropout 0.1, key-padding mask
    dict(b=2, s=256, nh=12, nkv=12, hd=64, dropout=0.1, padded=True,
         block_q=128, block_k=128),
    # the pair's other loops: causal, a window no block size divides, k
    # blocks under two q blocks, a padded batch and dropout together
    dict(b=1, s=512, nh=2, nkv=2, hd=64, causal=True, window=300,
         block_q=128, block_k=256),
    dict(b=2, s=512, nh=4, nkv=4, hd=64, causal=True, dropout=0.1,
         padded=True, block_q=256, block_k=128),
    # (ii) mellum's: 32-on-4 heads of 128, causal, a window of 1,024 and none
    dict(b=1, s=2048, nh=32, nkv=4, hd=128, causal=True, window=1024),
    dict(b=1, s=2048, nh=32, nkv=4, hd=128, causal=True),
    # (iii) solar's: 8-on-1 of 128, causal (no rotary turn before it)
    dict(b=1, s=1024, nh=8, nkv=1, hd=128, causal=True),
    # equal head counts at 128 and a width of 256
    dict(b=2, s=256, nh=2, nkv=2, hd=128, dropout=0.1, padded=True,
         block_q=128, block_k=128),
    dict(b=1, s=256, nh=2, nkv=1, hd=256, causal=True, block_q=128,
         block_k=128),
], ids=["bert-pairs", "pairs-causal-window", "pairs-causal-dropout-mask",
        "32on4-window", "32on4-full", "8on1", "equal-128", "256-wide"])
def test_rows_layout_launches_match_the_head_layout_launches(case):
    """The three kernels through "bshd" index maps (a head is a lane block
    of [B, S, heads * hd]; two 64-wide heads one block) against the same
    kernels on transposed operands."""
    flash_harness.check_rows_layout(**case)


def test_rows_layout_launches_take_a_selection():
    """keye's: the selection's int8 tiles beside a "bshd" launch's score
    tiles, empty rows among them, and `selected_probs_sum` reading q and k
    through the same index maps."""
    s, block_q, block_k = 512, 128, 256
    flash_harness.check_rows_layout(
        1, s, 4, 2, 128, causal=True, block_q=block_q, block_k=block_k,
        select=flash_harness.selection_with_empty_rows(s, block_q, block_k))


def test_a_pairs_dropout_pattern_is_each_heads_own():
    """Uniform probabilities (q = k = 0) and v = one row of ones a key:
    `out` is then the kept share of a query's keys times 1 / (1 - rate),
    per head. In layout "bshd" at 64 wide (pairs) it is bit for bit what
    each head draws alone in layout "bhsd", and two heads of a pair draw
    different patterns."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    b, s, nh, hd = 1, 256, 4, 64
    zeros = jnp.zeros((b, s, nh, hd), jnp.float32)
    v = jnp.asarray(np.random.RandomState(0).randn(b, s, nh, hd),
                    jnp.float32)
    kw = dict(scale=1.0, dropout=0.25, seed=3, block_q=128, block_k=128)
    rows = flash_attention(zeros, zeros, v, layout="bshd", **kw)
    heads = flash_attention(*(jnp.swapaxes(t, 1, 2)
                              for t in (zeros, zeros, v)), **kw)
    assert np.array_equal(np.asarray(rows),
                          np.asarray(jnp.swapaxes(heads, 1, 2)))
    same_v = jnp.broadcast_to(v[:, :, :1], v.shape)
    drawn = np.asarray(flash_attention(zeros, zeros, same_v, layout="bshd",
                                       **kw))
    assert not np.array_equal(drawn[:, :, 0], drawn[:, :, 1])


@pytest.mark.parametrize("shape, why", [
    (dict(nh=3, nkv=3, hd=64), "an odd head count"),
    (dict(nh=4, nkv=2, hd=64), "64-wide heads on grouped KV heads"),
    (dict(nh=2, nkv=2, hd=192, hdv=128), "a width of 192"),
])
def test_rows_layout_refuses_what_the_kernels_cannot_index(shape, why):
    """`rows_layout_fits` is the static fact the op's route reads; the
    launch itself raises for a shape it does not admit (the op never asks:
    it transposes inside itself there, tests/test_attention_grad_rule.py)."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    nh, nkv, hd = shape["nh"], shape["nkv"], shape["hd"]
    hdv = shape.get("hdv", hd)
    assert not fa.rows_layout_fits(hd, hdv, nh, nkv)
    q = jnp.zeros((1, 128, nh, hd), jnp.float32)
    k = jnp.zeros((1, 128, nkv, hd), jnp.float32)
    v = jnp.zeros((1, 128, nkv, hdv), jnp.float32)
    with pytest.raises(ValueError, match="bshd"):
        fa.flash_attention(q, k, v, layout="bshd")
    assert fa.rows_layout_fits(64, 64, 12, 12)
    assert fa.rows_layout_fits(128, 128, 32, 4)
    assert not fa.rows_layout_fits(64, 64, 12, 12,
                                   mask_shape=(1, 12, 128, 128))
    assert fa.rows_layout_fits(64, 64, 12, 12, mask_shape=(32, 1, 1, 128))
    assert not fa.rows_layout_fits(64, 64, 12, 12, select=True)
    assert fa.rows_layout_fits(128, 128, 32, 4, select=True)
