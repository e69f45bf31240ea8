"""The learned selection of sparse attention, op by op on the CPU
(`ops/sparse_index.py`, the `Select` input and `Target` output of
`fused_attention`, the flash kernels' selection tile under the Pallas
interpreter, `rotary_embedding`'s position streams): the selection against
`jax.lax.top_k` (rows shorter than `topk`, planted ties), the scores and
their grad rule against the plain reference's equations
(`benchmark/reference/keye_vl2.py`), the selected attention against a plain
masked softmax forward and backward, which gradients exist and which do not;
the indexer's two score kernels (`ops/pallas/index_scores.py`) under the
interpreter against the `jax.numpy` form, and the shape rule that routes to
them. The model that uses them is held in `tests/test_keye.py`.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import causal_lm_harness as harness
from causal_lm_harness import counter_rise, run_op

from paddle_tpu.ops import attention, llm_ops, registry, sparse_index
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import index_scores
from benchmark.reference import keye_vl2 as ref


def _indexer_inputs(seed, b, h, s, d):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(b, h, s, d), jnp.float32),
            jnp.asarray(rng.randn(b, s, d), jnp.float32),
            jnp.asarray(rng.randn(b, s, h), jnp.float32))


def _top_k_mask(scores, topk):
    """The set `jax.lax.top_k` picks over the causal pairs, as a mask."""
    return np.asarray(ref.select_keys(jnp.asarray(scores), topk))


# ---------------------------------------------------------------------------
# the selection
# ---------------------------------------------------------------------------

def _scores(kind, s):
    rng = np.random.RandomState(7)
    scores = rng.randn(2, s, s).astype(np.float32)
    if kind == "ties":
        # a few distinct values: every row is full of ties, at the k-th
        # score too; zeros of both signs are one value
        scores = np.round(scores * 2) / 2 + 0.0
        scores[0, s // 2] = 0.0
        scores[1, s - 1, ::2] = -0.0
    causal = np.tril(np.ones((s, s), bool))
    return np.where(causal, scores, -np.inf).astype(np.float32)


@pytest.mark.parametrize("kind, s, topk", [
    ("distinct", 48, 12),       # early rows shorter than topk, late ones cut
    ("distinct", 48, 48),       # topk = S: every causal pair
    ("distinct", 40, 64),       # topk > S
    ("ties", 48, 12),
    ("ties", 64, 1)])
def test_the_selection_is_top_ks_set(kind, s, topk):
    scores = _scores(kind, s)
    got = np.asarray(sparse_index.select_topk(jnp.asarray(scores), topk))
    assert got.dtype == np.int8
    want = _top_k_mask(scores + 0.0, min(topk, s))
    np.testing.assert_array_equal(got != 0, want)
    # min(t + 1, topk) keys a query, none above the diagonal
    np.testing.assert_array_equal(
        got.sum(-1), np.minimum(np.arange(s) + 1, topk)[None].repeat(2, 0))
    assert not np.triu(got[0], 1).any()
    if kind == "ties":
        # of the pairs at the k-th score the first by position are taken
        row = s // 2 if topk > 1 else s - 1
        kept = np.flatnonzero(got[0, row])
        at_kth = scores[0, row, kept].min()
        tied = np.flatnonzero(scores[0, row, :row + 1] == at_kth)
        taken = np.intersect1d(kept, tied)
        np.testing.assert_array_equal(taken, tied[:len(taken)])


def test_the_op_scores_selects_and_counts():
    q, k, w = _indexer_inputs(0, 2, 3, 40, 8)
    (scores, select, pairs), rise = counter_rise(
        lambda: run_op("sparse_index", {"QI": q, "KI": k, "W": w},
                       ["Scores", "Select", "PairsPerQuery"], {"topk": 16}),
        ["attn.sparse_layers_lowered"])
    assert rise == (1,)
    want = np.asarray(ref.index_scores(q, k, w, 0))
    np.testing.assert_allclose(scores, want, rtol=1e-5, atol=1e-5)
    assert np.isneginf(scores[0][np.triu_indices(40, 1)]).all()
    np.testing.assert_array_equal(select != 0, _top_k_mask(scores, 16))
    assert pairs[0] == pytest.approx(
        np.minimum(np.arange(40) + 1, 16).mean())


@pytest.mark.parametrize("block", [512, 16], ids=["one_block", "blocks"])
def test_the_scores_grad_rule_is_the_equations_gradient(block, monkeypatch):
    """dQI, dKI, dW of sum(Scores * cot) by the op's rule (the products
    once more, a block of queries at a time) against jax.grad of the
    reference's scores; the cotangent is nonzero on selected pairs only, as
    the loss's is."""
    monkeypatch.setattr(sparse_index, "Q_BLOCK", block)
    q, k, w = _indexer_inputs(1, 2, 3, 48, 8)
    select = sparse_index.select_topk(sparse_index.index_scores(q, k, w), 12)
    cot = jnp.where(select != 0, jnp.asarray(
        np.random.RandomState(2).randn(2, 48, 48), jnp.float32), 0.0)
    opdef = registry.get("sparse_index")
    ctx = registry.LowerCtx(rng_key=None)
    got = opdef.grad(ctx, {"QI": [q], "KI": [k], "W": [w]}, {"topk": 12},
                     {}, {"Scores": [cot]})
    want = jax.grad(lambda q, k, w: jnp.sum(jnp.where(
        select != 0, ref.index_scores(q, k, w, 0), 0.0) * cot),
        argnums=(0, 1, 2))(q, k, w)
    by_jax = jax.grad(lambda q, k, w: jnp.sum(jnp.where(
        select != 0, sparse_index.index_scores(q, k, w), 0.0) * cot),
        argnums=(0, 1, 2))(q, k, w)
    for slot, b, c in zip(("QI", "KI", "W"), want, by_jax):
        a = got[slot][0]
        assert harness.rel_gap(a, b) < 2e-5 and np.linalg.norm(b) > 0, slot
        np.testing.assert_allclose(a, c, rtol=1e-6, atol=1e-6)


def test_the_loss_is_the_kl_to_the_target_and_moves_the_scores_alone():
    q, k, w = _indexer_inputs(3, 2, 3, 40, 8)
    scores = sparse_index.index_scores(q, k, w)
    select = sparse_index.select_topk(scores, 10)
    rng = np.random.RandomState(4)
    target = jnp.where(select != 0, jnp.asarray(
        rng.rand(2, 40, 40), jnp.float32), 0.0)
    target = target / target.sum(-1, keepdims=True)
    loss, = run_op("sparse_index_loss",
                   {"Scores": scores, "Select": select, "Target": target},
                   ["Loss"], {})
    sel, p, i = np.asarray(select) != 0, np.asarray(target), np.asarray(scores)
    want = 0.0
    for b in range(2):
        for t in range(40):
            on = sel[b, t]
            logq = i[b, t, on] - np.log(np.exp(i[b, t, on]).sum())
            want += (p[b, t, on] * (np.log(p[b, t, on]) - logq)).sum()
    assert loss == pytest.approx(want / 80, rel=1e-5)
    # softmax over the selected minus the target, a query's share each;
    # nothing off the selection, nothing into the target
    ds, dt = jax.grad(lambda s, t: sparse_index.index_loss(s, select, t),
                      argnums=(0, 1))(scores, target)
    soft = np.where(sel, np.exp(np.where(sel, i, -np.inf)), 0.0)
    soft = soft / soft.sum(-1, keepdims=True)
    np.testing.assert_allclose(ds, (soft - p) / 80, rtol=1e-5, atol=1e-8)
    assert not np.asarray(dt).any()
    opdef = registry.get("sparse_index_loss")
    assert {"Select", "Target"} <= opdef.nondiff_slots


# ---------------------------------------------------------------------------
# the score kernels, interpreted, against the `jax.numpy` form
# ---------------------------------------------------------------------------

_KERNEL_SHAPES = {
    # (b, h, s, d), dtype, (queries a tile, keys a tile, rows an inner step)
    "batch_2_float32": ((2, 3, 256, 64), jnp.float32, (128, 128, 32)),
    # 384 = 3 x 128: the rule's own blocks
    "three_blocks_bf16": ((1, 2, 384, 64), jnp.bfloat16, None),
    # a key tile twice a query tile: the second query block's rows end
    # inside the tile the first one's ended in
    "wide_key_tile_bf16": ((1, 2, 512, 128), jnp.bfloat16, (128, 256, 32)),
    "wide_query_tile_float32": ((1, 4, 512, 64), jnp.float32, (256, 128, 64)),
}


def _kernel_case(name):
    shape, dtype, blocks = _KERNEL_SHAPES[name]
    q, k, w = _indexer_inputs(8, *shape)
    q, k = q.astype(dtype), k.astype(dtype)
    plan = index_scores.plan(shape, dtype, blocks)
    assert plan is not None and shape[2] // plan.block_q > 1 \
        and shape[2] // plan.block_k > 1
    return plan, q, k, w, int(0.3 * shape[2])


@pytest.mark.parametrize("case", list(_KERNEL_SHAPES))
def test_the_forward_kernel_scores_as_the_form_does(case):
    """`Scores` of the kernel against `_index_fwd`'s: -inf above the
    diagonal and nowhere else, the finite ones equal to 1e-6 of the largest
    (the heads are summed in another order), and the selection made from
    them the same set."""
    plan, q, k, w, topk = _kernel_case(case)
    want, select = sparse_index._index_fwd(q, k, w, topk)
    got = index_scores.scores_fwd(plan, q, k, w)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    got, want = np.asarray(got), np.asarray(want)
    s = got.shape[-1]
    above = np.triu(np.ones((s, s), bool), 1)
    assert np.isneginf(got[:, above]).all()
    assert np.isfinite(got[:, ~above]).all()
    np.testing.assert_allclose(got[:, ~above], want[:, ~above], rtol=0,
                               atol=1e-6 * np.abs(want[:, ~above]).max())
    np.testing.assert_array_equal(
        sparse_index.select_topk(jnp.asarray(got), topk), select)


@pytest.mark.parametrize("case", list(_KERNEL_SHAPES))
def test_the_backward_kernel_is_the_forms_grad_rule(case):
    """dQI, dKI, dW of the kernel against `_scores_bwd`'s at a cotangent
    that is zero off a selection (and, planted, nonzero above the diagonal,
    which both drop), in the operands' dtypes."""
    plan, q, k, w, topk = _kernel_case(case)
    select = sparse_index.select_topk(sparse_index.index_scores(q, k, w),
                                      topk)
    cot = np.where(np.asarray(select) != 0, np.random.RandomState(9).randn(
        *select.shape), 0.0).astype(np.float32)
    cot[:, 0, -1] = 1.0
    cot = jnp.asarray(cot)
    got = index_scores.scores_bwd(plan, q, k, w, cot)
    want = sparse_index._scores_bwd(q, k, w, cot)
    tol = 2e-6 if q.dtype == jnp.float32 else 4e-3    # a bf16 last place
    for name, a, b in zip(("dQI", "dKI", "dW"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert harness.rel_gap(a, b) < tol and np.linalg.norm(
            np.asarray(b, np.float32)) > 0, name


@pytest.mark.parametrize("shape, dtype, takes", [
    ((1, 16, 8192, 64), jnp.bfloat16, True),        # the cell's
    ((2, 4, 1024, 128), jnp.float32, True),
    ((1, 2, 40, 8), jnp.float32, False),            # a head 8 wide
    ((1, 2, 200, 64), jnp.bfloat16, False),         # no block divides 200
    ((1, 2, 256, 96), jnp.bfloat16, False),         # a head no multiple of 64
    ((1, 2, 256, 64), jnp.float16, False),
    ((1, 64, 8192, 512), jnp.bfloat16, False)],     # more than VMEM holds
    ids=["cell", "float32", "head_8", "row_200", "head_96", "float16",
         "vmem"])
def test_the_plan_takes_what_the_kernels_can_tile(shape, dtype, takes):
    plan = index_scores.plan(shape, dtype)
    assert (plan is not None) == takes
    if takes:
        assert shape[2] % plan.block_q == 0 and plan.block_q >= 128
        assert plan.resident_bytes <= index_scores.VMEM_BUDGET


@pytest.mark.parametrize("shape, route", [
    ((1, 2, 256, 64), "attn.index_pallas"),
    ((1, 2, 40, 8), "attn.index_xla"),
    ((1, 2, 200, 64), "attn.index_xla")],
    ids=["taken", "head_8", "row_200"])
def test_the_op_counts_the_route_the_plan_chose(shape, route):
    """Forward and grad rule each count once, by the plan's choice; the
    other route's counter stays; either route gives the form's results."""
    q, k, w = _indexer_inputs(10, *shape)
    counters = ["attn.index_pallas", "attn.index_xla"]
    want = [int(route == c) for c in counters]
    (scores, select), rise = counter_rise(
        lambda: run_op("sparse_index", {"QI": q, "KI": k, "W": w},
                       ["Scores", "Select"], {"topk": 16}), counters)
    assert list(rise) == want
    form, form_select = sparse_index._index_fwd(q, k, w, 16)
    on = np.isfinite(np.asarray(form))
    np.testing.assert_allclose(scores[on], np.asarray(form)[on], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(select, form_select)
    cot = jnp.where(jnp.asarray(select) != 0, 1.0, 0.0)
    opdef = registry.get("sparse_index")
    got, rise = counter_rise(
        lambda: opdef.grad(registry.LowerCtx(rng_key=None),
                           {"QI": [q], "KI": [k], "W": [w]}, {"topk": 16},
                           {}, {"Scores": [cot]}), counters)
    assert list(rise) == want
    for slot, ref_val in zip(("QI", "KI", "W"),
                             sparse_index._scores_bwd(q, k, w, cot)):
        assert harness.rel_gap(got[slot][0], ref_val) < 2e-6, slot
    # differentiated by JAX (a recomputed segment): the forward's rule and
    # the backward's count once each, the discarded primal trace does not
    _, rise = counter_rise(
        lambda: jax.grad(lambda q: jnp.sum(jnp.where(
            select != 0, sparse_index.index_scores_and_select(
                q, k, w, 16, count=True, relowered=True)[0], 0.0)))(q),
        counters)
    assert list(rise) == [2 * n for n in want]


# ---------------------------------------------------------------------------
# attention over a selection: the flash kernels, interpreted, against a
# plain masked softmax
# ---------------------------------------------------------------------------

def _masked_softmax_attention(q, k, v, sel, scale):
    """(out, heads' mean of probs): K and V repeated to the query heads."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    scores = jnp.einsum("bnqd,bnkd->bnqk", q, k) * scale
    probs = jax.nn.softmax(jnp.where(sel[:, None], scores, -jnp.inf), -1)
    return jnp.einsum("bnqk,bnkd->bnqd", probs, v), probs.mean(1)


@pytest.mark.parametrize("s, nh, nkv, topk", [
    (256, 4, 2, 100),       # topk no multiple of a block: a tile holds
                            # queries that take every key and ones that cut
    (256, 2, 2, 300),       # topk > S: the causal triangle, group of one
    (384, 8, 2, 40)])       # blocks of 128, most of every tile masked
def test_selected_flash_kernels_match_a_plain_masked_softmax(s, nh, nkv,
                                                             topk):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, nh, s, 64), jnp.float32)
    k, v = (jnp.asarray(rng.randn(2, nkv, s, 64), jnp.float32)
            for _ in range(2))
    cot = jnp.asarray(rng.randn(2, nh, s, 64), jnp.float32)
    qi, ki, w = _indexer_inputs(5, 2, 2, s, 8)
    select = sparse_index.select_topk(sparse_index.index_scores(qi, ki, w),
                                      topk)
    sel = select != 0
    kw = dict(scale=0.125, causal=True, select=select, block_q=128,
              block_k=128)
    out, lse = fa.flash_attention(q, k, v, return_lse=True, **kw)
    (want, want_target), vjp = jax.vjp(
        lambda q, k, v: _masked_softmax_attention(q, k, v, sel, 0.125),
        q, k, v)
    grads = fa.flash_attention_bwd(q, k, v, out, lse, cot, **kw)
    assert grads[1].shape == grads[2].shape == (2, nkv, s, 64)
    for name, got, ref_val in zip(
            ("out", "dq", "dk", "dv"), (out,) + tuple(grads),
            (want,) + vjp((cot, jnp.zeros_like(want_target)))):
        err = float(jnp.abs(got - ref_val).max() / jnp.abs(ref_val).max())
        assert err < 2e-5, (name, err)
    target = fa.selected_probs_sum(q, k, lse, select, scale=0.125,
                                   block_q=128, block_k=128)
    np.testing.assert_allclose(target, want_target, rtol=2e-5, atol=1e-7)
    assert not np.asarray(target)[~np.asarray(sel)].any()
    np.testing.assert_allclose(np.asarray(target).sum(-1), 1.0, rtol=1e-5)
    # differentiated by JAX: the same kernels, and nothing into the choice
    by_jax = jax.grad(lambda k: jnp.sum(fa.flash_attention(q, k, v, **kw)
                                        * cot))(k)
    np.testing.assert_allclose(by_jax, grads[1], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("route", ["dense", "flash"])
def test_the_op_takes_a_selection_and_gives_the_target(route, monkeypatch):
    """`fused_attention` with `Select` on either route: Out and Target
    against the plain masked softmax; by the generic route (what a
    recomputed segment differentiates) a gradient reaches Q, K, V from Out
    and none from Target; the flash route's grad rule gives the same."""
    if route == "flash":
        monkeypatch.setattr(attention, "_use_pallas",
                            lambda q: q.shape[2] % 128 == 0)
    rng = np.random.RandomState(1)
    s = 128
    q = jnp.asarray(rng.randn(1, 4, s, 64), jnp.float32)
    k, v = (jnp.asarray(rng.randn(1, 2, s, 64), jnp.float32)
            for _ in range(2))
    qi, ki, w = _indexer_inputs(6, 1, 2, s, 8)
    select = sparse_index.select_topk(sparse_index.index_scores(qi, ki, w),
                                      50)
    attrs = {"causal": True, "scale": 0.125, "return_target": True}
    opdef = registry.get("fused_attention")
    ctx = registry.LowerCtx(rng_key=jax.random.key(0))
    counter = "attn.sparse_pallas" if route == "flash" else "attn.sparse_xla"

    def lower(q, k, v):
        outs = opdef.lower(ctx, {"Q": [q], "K": [k], "V": [v],
                                 "Select": [select]}, attrs)
        return outs["Out"][0], outs["Target"][0], outs["Lse"][0]

    (out, target, lse), rise = counter_rise(lambda: lower(q, k, v),
                                            [counter])
    assert rise == (1,)
    want, want_target = _masked_softmax_attention(q, k, v, select != 0, 0.125)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(target, want_target, rtol=2e-5, atol=1e-7)
    cot = jnp.asarray(rng.randn(*out.shape), jnp.float32)
    tcot = jnp.asarray(rng.randn(*target.shape), jnp.float32)
    from_out = jax.grad(lambda *a: jnp.sum(lower(*a)[0] * cot),
                        argnums=(0, 1, 2))(q, k, v)
    from_target = jax.grad(lambda *a: jnp.sum(lower(*a)[1] * tcot),
                           argnums=(0, 1, 2))(q, k, v)
    want_grads = jax.grad(lambda *a: jnp.sum(_masked_softmax_attention(
        *a, select != 0, 0.125)[0] * cot), argnums=(0, 1, 2))(q, k, v)
    for got, zero, ref_val in zip(from_out, from_target, want_grads):
        assert harness.rel_gap(got, ref_val) < 2e-5
        assert not np.asarray(zero).any()
    assert "Select" in opdef.nondiff_slots
    by_rule = opdef.grad(
        ctx, {"Q": [q], "K": [k], "V": [v], "Select": [select]}, attrs,
        {"Out": [out], "Lse": [lse]}, {"Out": [cot], "Target": [tcot]})
    if route == "dense":
        assert by_rule is None          # the generic route differentiates
    else:
        for slot, ref_val in zip(("Q", "K", "V"), want_grads):
            assert harness.rel_gap(by_rule[slot][0], ref_val) < 2e-5


def test_a_selection_goes_with_causal_attention_alone():
    q = jnp.zeros((1, 2, 8, 4))
    select = jnp.ones((1, 8, 8), jnp.int8)
    opdef = registry.get("fused_attention")
    ctx = registry.LowerCtx(rng_key=jax.random.key(0))
    for attrs in ({"causal": False}, {"causal": True, "window": 4},
                  {"causal": True, "dropout": 0.1}):
        with pytest.raises(ValueError, match="Select goes with causal"):
            opdef.lower(ctx, {"Q": [q], "K": [q], "V": [q],
                              "Select": [select]}, attrs)


# ---------------------------------------------------------------------------
# rotary positions in several streams
# ---------------------------------------------------------------------------

_ROPE_CFG = {"rope_theta": 1e7, "rope_scaling": {"mrope_section": [2, 2, 4]}}


@pytest.mark.parametrize("shape", [(2, 3, 9, 16), (2, 9, 16), (2, 9, 8)],
                         ids=["heads_of_16", "one_head_of_16", "one_of_8"])
def test_rotary_embedding_turns_each_section_by_its_stream(shape):
    """Three unequal streams: pair j turns by the position of the section
    it falls in ([2, 2, 4] of 8 pairs; a head of half the width has the
    sections halved) against the reference and against complex numbers; the
    streams equal to the row's own positions change nothing."""
    rng = np.random.RandomState(1)
    x = rng.randn(*shape).astype(np.float32)
    d, s = shape[-1], shape[-2]
    pos = rng.randint(0, 50, (3, 2, s))
    sections = ref.sections_for(dict(_ROPE_CFG, head_dim=16), d)
    assert sections == ([2, 2, 4] if d == 16 else [1, 1, 2])
    attrs = {"theta": 1e7, "rotary_dim": d, "layout": "half",
             "sections": sections}
    out, = run_op("rotary_embedding", {"X": x, "Positions": pos}, ["Out"],
                  attrs)
    np.testing.assert_allclose(
        out, np.asarray(ref.rope(jnp.asarray(x), _ROPE_CFG, pos)),
        rtol=1e-5, atol=1e-5)
    stream = np.repeat(np.arange(3), sections)
    freq = 1e7 ** (-2.0 * np.arange(d // 2) / d)
    ang = np.moveaxis(pos, 0, -1)[..., stream] * freq          # [B, S, D/2]
    if len(shape) == 4:
        ang = ang[:, None]
    z = (x[..., :d // 2] + 1j * x[..., d // 2:]) * np.exp(1j * ang)
    np.testing.assert_allclose(out[..., :d // 2], z.real, rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(out[..., d // 2:], z.imag, rtol=1e-4,
                               atol=1e-4)
    own = np.broadcast_to(np.arange(s), (3, 2, s))
    same, = run_op("rotary_embedding", {"X": x, "Positions": own}, ["Out"],
                   attrs)
    plain, = run_op("rotary_embedding", {"X": x}, ["Out"],
                    {k: v for k, v in attrs.items() if k != "sections"})
    np.testing.assert_allclose(same, plain, rtol=1e-6, atol=1e-6)


def test_rotary_embedding_without_streams_traces_as_before():
    """No `Positions`: the op's jaxpr is the one of the tree before position
    streams (commit bf4a1d0, jax 0.9.0; the digest was made there), with or
    without a `sections` attribute."""
    opdef = registry.get("rotary_embedding")
    attrs = {"theta": 1e7, "rotary_dim": 16, "layout": "half"}

    def text(attrs):
        return harness.cut_source_lines(str(jax.make_jaxpr(
            lambda x: opdef.lower(registry.LowerCtx(rng_key=None),
                                  {"X": [x]}, attrs)["Out"][0])(
            jax.ShapeDtypeStruct((2, 3, 9, 16), jnp.float32))))

    assert harness.sha256(text(attrs)) == _ROTARY_DIGEST
    assert text(dict(attrs, sections=[2, 2, 4])) == text(attrs)
    assert llm_ops.stream_angles(jnp.zeros((3, 2, 9)), np.ones(8), [2, 2, 4],
                                 4).shape == (2, 1, 9, 8)
    with pytest.raises(ValueError, match="sections"):
        llm_ops.stream_angles(jnp.zeros((3, 2, 9)), np.ones(8), [2, 2], 4)


_ROTARY_DIGEST = (
    "21d8b3cc57e33210f343bd42064ae83993c565f8f656c749a18513c704d8e3e5")


def test_new_ops_have_specs_and_amp_placement():
    from paddle_tpu.amp.auto_cast import (black_list, keep_f32_slots,
                                          white_list)
    from paddle_tpu.analysis import op_specs  # noqa: F401
    for op in ("sparse_index", "sparse_index_loss", "detach"):
        assert registry.get_spec(op) is not None, op
        assert op not in black_list
    assert "sparse_index" in white_list
    assert keep_f32_slots["sparse_index"] >= {"W", "Scores"}
    assert registry.get("sparse_index").grad is not None
    spec = registry.get_spec("fused_attention")
    assert "Select" in spec.inputs and "Target" in spec.outputs
