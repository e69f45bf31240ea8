"""The Pallas grouped matmul (`ops/pallas/grouped_matmul.py`) under the
interpreter, in float32 against `jax.lax.ragged_dot` /
`ragged_dot_general`: its three forms on uneven groups (an empty group, a
boundary inside a row tile, a boundary on a tile's edge, a last group
swollen to the buffer's end), at widths that are odd multiples of 128 and
at widths of a lane tile or more that are no multiple of 128 (one
full-width block); the visit tables; the tile rule as a function of shapes; which way `ops/moe.py`
sends a shape, by the counters; and `routed_moe`'s gradients through the
kernels against the generic route and the plain float32 reference layer.
The kernels compiled for a described v5e at the benchmark's sizes are in
tests/test_attention_two_widths.py (one file holds every such compile).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import moe
from paddle_tpu.ops.pallas import grouped_matmul as gm

from causal_lm_harness import counter_rise as _counter_rise
from test_deepseek_v3 import (_GRAD_NAMES, _GROUPED_COUNTERS,
                              _reference_share_gradients, _share_gradients,
                              _uncut_layer)

ROWS = 96                        # row tile 32: three tiles
# held assignments (the rest of the buffer goes to the last group)
SIZES = {
    "boundary_in_tile_and_on_edge": [20, 12, 0, 7, 9],   # 20 | 32 | 32 | 39
    "every_boundary_on_an_edge": [32, 0, 32, 0, 0],
    "one_tile_five_groups": [3, 1, 0, 2, 5],
    "first_and_last_empty": [0, 50, 46, 0, 0],
}
FORMS = ("gmm", "gmm-t", "tgmm")


def _rise(fn):
    """(Pallas kernels, `ragged_dot`s) that `fn`'s traces counted."""
    return _counter_rise(fn, _GROUPED_COUNTERS)


def _operands(k, n, experts, seed=3):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(ROWS, k), jnp.float32),
            jnp.asarray(rng.randn(ROWS, n), jnp.float32),
            jnp.asarray(rng.randn(experts, k, n), jnp.float32))


def _xla(form, x, g, w, sizes):
    if form == "tgmm":
        return jax.lax.ragged_dot_general(x, g, sizes, moe._DW_DIMS)
    return jax.lax.ragged_dot(x, w, sizes)


def _through_moe(form, x, g, w, groups):
    if form == "tgmm":
        return moe._grouped_dw(x, g, groups)
    if form == "gmm-t":
        return moe._grouped(x, jnp.swapaxes(w, 1, 2), groups, transposed=True)
    return moe._grouped(x, w, groups)


@pytest.mark.parametrize("case", SIZES)
@pytest.mark.parametrize("k, n", [(384, 640), (640, 384)])
@pytest.mark.parametrize("form", FORMS)
def test_form_follows_ragged_dot(form, k, n, case):
    """384 and 640 stand for 2304 and 896: 3 x 128 and 5 x 128, widths no
    power of two above 128 divides. Through `ops/moe.py`'s entry points,
    which swell the last group as `_whole_buffer` does."""
    held = jnp.asarray(SIZES[case], jnp.int32)
    x, g, w = _operands(k, n, held.shape[0])
    groups = moe._RowGroups(held, ROWS, True)
    assert int(groups.sizes.sum()) == ROWS
    got, rise = _rise(lambda: _through_moe(form, x, g, w, groups))
    assert rise == (1, 0)
    want = _xla(form, x, g, w, groups.sizes)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize("case", SIZES)
@pytest.mark.parametrize("k, n", [(232, 384), (384, 232), (232, 168)])
@pytest.mark.parametrize("form", FORMS)
def test_a_width_that_is_no_multiple_of_128_is_one_block(form, k, n, case):
    """232 and 168 stand for 1856 = 14.5 x 128: a lane tile or more, a
    multiple of 8, no multiple of 128, as the contraction, as the result's
    width and as both. Its block is the whole width."""
    held = jnp.asarray(SIZES[case], jnp.int32)
    x, g, w = _operands(k, n, held.shape[0])
    groups = moe._RowGroups(held, ROWS, True)
    fn = jax.jit(lambda x, g, w: _through_moe(form, x, g, w, groups))
    jaxpr, rise = _rise(lambda: str(jax.make_jaxpr(fn)(x, g, w)))
    assert rise == (1, 0)
    assert "pallas_call" in jaxpr and "ragged_dot" not in jaxpr
    rule = gm.tgmm_tiles if form == "tgmm" else gm.gmm_tiles
    assert rule(ROWS, k, n, 4, 4)[:3] == (32, k, n)
    got = _through_moe(form, x, g, w, moe._RowGroups(held, ROWS, False))
    want = _xla(form, x, g, w, groups.sizes)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize("form", FORMS)
def test_tiles_smaller_than_the_widths(form):
    """The grid's k and n axes: a budget that holds no whole matrix."""
    held = jnp.asarray(SIZES["boundary_in_tile_and_on_edge"], jnp.int32)
    sizes = moe._whole_buffer(held, ROWS)
    x, g, w = _operands(384, 640, 5)
    tiles = gm.Tiles(16, 128, 128, 0)
    visits = gm.group_visits(sizes, ROWS, 16)
    if form == "tgmm":
        got = gm.tgmm(x, g, visits, tiles=tiles)
    else:
        got = gm.gmm(x, jnp.swapaxes(w, 1, 2) if form == "gmm-t" else w,
                     visits, tiles=tiles, transpose_rhs=form == "gmm-t")
    np.testing.assert_allclose(got, _xla(form, x, g, w, sizes), rtol=2e-5,
                               atol=2e-4)


@pytest.mark.parametrize("k, n", [(384, 128), (232, 168)])
@pytest.mark.parametrize("form", FORMS)
def test_bf16_operands_accumulate_in_float32(form, k, n):
    held = jnp.asarray(SIZES["boundary_in_tile_and_on_edge"], jnp.int32)
    x, g, w = (a.astype(jnp.bfloat16) for a in _operands(k, n, 5))
    groups = moe._RowGroups(held, ROWS, False)
    got, rise = _rise(lambda: _through_moe(form, x, g, w, groups))
    assert rise == (0, 0)                # a trace that does not count
    assert got.dtype == jnp.bfloat16
    exact = _xla(form, *(a.astype(jnp.float32) for a in (x, g, w)),
                 groups.sizes)
    # one rounding of a float32 sum: within a bf16 ulp of the exact result
    # (7 to 384 products a sum; bf16 partial sums would be off by several)
    np.testing.assert_allclose(np.asarray(got, np.float32), exact,
                               rtol=2 ** -8, atol=2 ** -8)


@pytest.mark.parametrize("k, n", [(384, 96), (100, 128), (32, 16)])
@pytest.mark.parametrize("form", FORMS)
def test_a_width_under_one_lane_tile_keeps_ragged_dot(form, k, n):
    held = jnp.asarray(SIZES["boundary_in_tile_and_on_edge"], jnp.int32)
    x, g, w = _operands(k, n, 5)
    groups = moe._RowGroups(held, ROWS, True)
    fn = jax.jit(lambda x, g, w: _through_moe(form, x, g, w, groups))
    jaxpr, rise = _rise(lambda: str(jax.make_jaxpr(fn)(x, g, w)))
    assert rise == (0, 1)
    assert "ragged_dot_general" in jaxpr and "pallas_call" not in jaxpr
    np.testing.assert_allclose(
        _through_moe(form, x, g, w, moe._RowGroups(held, ROWS, False)),
        _xla(form, x, g, w, groups.sizes), rtol=1e-6, atol=1e-6)


def test_a_width_no_sublane_tile_divides_keeps_ragged_dot():
    """132 = 16.5 x 8: the last register of a weight block's second-minor
    dimension would hold four rows."""
    for rule in (gm.gmm_tiles, gm.tgmm_tiles):
        assert rule(96, 132, 256) is None and rule(96, 256, 132) is None
        assert rule(96, 136, 256)[1:3] == (136, 256)


def test_rows_no_row_tile_divides_keep_ragged_dot():
    assert gm.gmm_tiles(100, 384, 640) is None       # 100 = 4 x 25
    assert gm.gmm_tiles(96, 384, 640).tm == 32
    assert gm.gmm_tiles(49152, 2048, 768).tm == 256


@pytest.mark.parametrize("case", SIZES)
@pytest.mark.parametrize("tm", [16, 32, 96])
def test_visit_tables_walk_every_group_in_order(case, tm):
    """Each group once per row tile it holds a row of (an empty one once),
    tiles never going back; the padding repeats the last real visit."""
    sizes = np.asarray(moe._whole_buffer(jnp.asarray(SIZES[case]), ROWS))
    offsets, groups, tiles, count = (np.asarray(a) for a in gm.group_visits(
        jnp.asarray(sizes), ROWS, tm))
    assert offsets.tolist() == [0] + np.cumsum(sizes).tolist()
    assert len(groups) == len(tiles) == ROWS // tm + len(sizes) - 1
    want = []
    for e, (lo, hi) in enumerate(zip(offsets[:-1], offsets[1:])):
        if hi == lo:
            want.append((e, min(lo // tm, ROWS // tm - 1)))
        want += [(e, t) for t in range(lo // tm, -(-hi // tm)) if hi > lo]
    n = int(count[0])
    assert n == len(want)
    assert list(zip(groups[:n], tiles[:n])) == want
    assert (np.diff(tiles[:n]) >= 0).all()
    assert (groups[n:] == groups[n - 1]).all()
    assert (tiles[n:] == tiles[n - 1]).all()


# (K, N) of the two sparse cells' expert matmuls, and what one kernel keeps
# in VMEM there under a row tile of 256 in bf16
@pytest.mark.parametrize("k, n, gmm_bytes, tgmm_bytes", [
    (2304, 896, 15_204_352, 28_508_160),
    (896, 2304, 20_971_520, 28_508_160),
    (2048, 768, 12_320_768, 22_151_168),
    (768, 2048, 17_563_648, 22_151_168)])
def test_tile_rule_keeps_a_whole_matrix_resident(k, n, gmm_bytes, tgmm_bytes):
    """tn = N: the row buffer is read once; tk = K: the weights' block
    changes with the group only."""
    for m in (65536, 49152):
        assert gm.gmm_tiles(m, k, n) == gm.Tiles(256, k, n, gmm_bytes)
        assert gm.tgmm_tiles(m, k, n) == gm.Tiles(256, k, n, tgmm_bytes)
    assert gmm_bytes == (2 * (256 * k + k * n) * 2 + 2 * 256 * n * 2
                         + 4 * 256 * n * 4)
    assert max(gmm_bytes, tgmm_bytes) <= gm.VMEM_BUDGET


# the hybrid cell's expert matmuls, [49152, 2688] x [8, 2688, 1856] and
# back: 1856 = 14.5 x 128 is one block wherever it stands, and VMEM holds it
# as 1920 lanes
@pytest.mark.parametrize("k, n, gmm_bytes, tgmm_tiles, tgmm_bytes", [
    (2688, 1856, 33_226_752, (896, 1856), 23_986_176),
    (1856, 2688, 36_372_480, (1856, 896), 23_986_176)])
def test_tile_rule_takes_an_unaligned_width_whole(k, n, gmm_bytes,
                                                  tgmm_tiles, tgmm_bytes):
    """`gmm`: a whole weight matrix resident, as at the aligned cells.
    `tgmm`: a 1856 x 2688 float32 accumulator does not fit, and the width
    that can be cut is: 2688 = 3 x 896."""
    assert gm.gmm_tiles(49152, k, n) == gm.Tiles(256, k, n, gmm_bytes)
    assert gm.tgmm_tiles(49152, k, n) == gm.Tiles(256, *tgmm_tiles,
                                                  tgmm_bytes)
    held = {1856: 1920}                  # lanes rounded up to 128
    pk, pn = (held.get(w, w) for w in (k, n))
    assert gmm_bytes == (2 * (256 * pk + pk * pn) * 2 + 2 * 256 * pn * 2
                         + 4 * 256 * pn * 4)
    tk, tn = (held.get(w, w) for w in tgmm_tiles)
    assert tgmm_bytes == (2 * (256 * tk + 256 * tn) * 2 + 2 * tk * tn * 2
                          + 2 * tk * tn * 4 + 256 * min(tk, tn) * 2)
    assert max(gmm_bytes, tgmm_bytes) <= gm.VMEM_BUDGET \
        < gm._tgmm_resident(256, k, n, 2, 2)


def test_tile_rule_cuts_k_before_n_when_a_matrix_does_not_fit():
    got = gm.gmm_tiles(65536, 7168, 2048)            # 29 MB of bf16 weights
    assert (got.tn, got.tk) == (2048, 1792)
    assert got.resident_bytes <= gm.VMEM_BUDGET \
        < gm._gmm_resident(256, 3584, 2048, 2, 2)
    assert gm.gmm_tiles(65536, 7168, 2048, 4, 4)[1:3] == (1024, 2048)
    wide = gm.tgmm_tiles(65536, 7168, 7168)          # 205 MB in float32
    assert (wide.tk, wide.tn) == (256, 7168)
    assert wide.resident_bytes <= gm.VMEM_BUDGET


def test_one_trace_per_signature(monkeypatch):
    """Calls that share shapes and tiles share the inner jit's trace."""
    held = jnp.asarray(SIZES["boundary_in_tile_and_on_edge"], jnp.int32)
    x, _, w = _operands(256, 128, 5, seed=4)
    kernels = []
    pallas_call = gm.pl.pallas_call
    monkeypatch.setattr(gm.pl, "pallas_call", lambda *a, **kw: (
        kernels.append(kw["name"]), pallas_call(*a, **kw))[1])

    def layer(x, w):
        groups = moe._RowGroups(held, ROWS, False)
        return (moe._grouped(x, w, groups) + moe._grouped(x, 2 * w, groups)
                + moe._grouped(x, 3 * w, groups))

    jaxpr = str(jax.make_jaxpr(layer)(x, w))
    assert kernels == ["ragged-dot-gmm"]
    assert jaxpr.count("name=_gmm") == 3


# ---------------------------------------------------------------------------
# routed_moe's gradients through the kernels: widths the tile rule takes
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def _kernel_case():
    x, params, _ = _uncut_layer(seed=2, skew=5, n=64, d=256, f=128)
    cot = np.random.RandomState(7).randn(*x.shape).astype(np.float32)
    (got, moe_rise), rise = _rise(
        lambda: _share_gradients(x, params, cot, 4, 4, 16))
    want = _reference_share_gradients(x, params, cot, 4, 4, 16)
    return got, moe_rise, rise, want, (x, params, cot)


@pytest.mark.parametrize("leaf", range(5), ids=_GRAD_NAMES)
def test_grad_rule_through_the_kernels_follows_the_reference(leaf,
                                                             _kernel_case):
    got, moe_rise, rise, want, _ = _kernel_case
    assert moe_rise == (1, 0)            # by the rule, on residuals
    assert rise == (9, 0)                # 3 forward, 6 backward, all Pallas
    err = np.linalg.norm(got[leaf] - want[leaf]) / np.linalg.norm(want[leaf])
    assert err < 2e-5, (_GRAD_NAMES[leaf], err)
    assert np.linalg.norm(want[leaf]) > 0


def test_generic_route_through_the_kernels_agrees(_kernel_case):
    by_rule, _, _, _, inputs = _kernel_case
    (generic, moe_rise), rise = _rise(
        lambda: _share_gradients(*inputs, 4, 4, 16, withhold=True))
    assert moe_rise == (0, 1)
    assert rise == (12, 0)               # the forward's three again
    for name, a, b in zip(_GRAD_NAMES, by_rule, generic):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6, err_msg=name)
