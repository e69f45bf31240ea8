"""Flash attention where q and k are wider than v (latent attention: 192
and 128): the three kernels under the Pallas interpreter against the dense
route, forward and through the op's grad rule, causal; and the same
kernels compiled for a described v5e at the size the benchmark's cell runs
them (S = 4096, where the dkdv kernel needs more than Mosaic's default
16 MiB of scoped VMEM). This file holds EVERY compile for a described
chip (one worker loads the TPU's library): the expert layer's grouped
matmuls (`ops/pallas/grouped_matmul.py`; tests/test_grouped_matmul.py has
their numerics) at the four sparse cells' sizes are at its end, and after
them the gated delta rule (`ops/kda.py`; tests/test_ling.py has its
numerics) at the linear-attention cell's and the selective scan's chunk
kernels (`ops/pallas/ssm_chunk.py`; tests/test_nemotron_h.py has their
numerics) at the hybrid cell's, and the indexer's score kernels
(`ops/pallas/index_scores.py`; tests/test_sparse_index.py has their
numerics) at the learned-selection cell's.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout

import flash_harness
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.observability import metrics
from paddle_tpu.ops import attention, kda, moe, registry
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import grouped_matmul as gm
from paddle_tpu.ops.pallas import index_scores, ssm_chunk
from paddle_tpu.testing import reset_programs

B, NH, DQK, DV = 1, 2, 192, 128


def _grads(s, dtype, flash, monkeypatch):
    """Out and dq, dk, dv of sum(Out * W) through a one-op program."""
    if flash:
        monkeypatch.setattr(
            attention, "_use_pallas",
            lambda q: q.shape[2] % 128 == 0 and q.shape[3] == DQK)
    else:
        monkeypatch.setattr(attention, "_use_pallas", lambda q: False)
    reset_programs(0)
    rng = np.random.RandomState(11)
    widths = {"q": DQK, "k": DQK, "v": DV, "w": DV}
    feed = {n: rng.randn(B, NH, s, d).astype(np.float32)
            for n, d in widths.items()}
    qkv = []
    for n in ("q", "k", "v"):
        var = layers.data(name=n, shape=[NH, s, widths[n]], dtype="float32")
        var.stop_gradient = False
        qkv.append(var)
    w = layers.data(name="w", shape=[NH, s, DV], dtype="float32")
    out = layers.fused_attention(*qkv, causal=True, scale=DQK ** -0.5)
    assert tuple(out.shape)[1:] == (NH, s, DV)
    loss = layers.reduce_sum(layers.elementwise_mul(out, w))
    grads = fluid.gradients(loss, qkv)
    prog = fluid.default_main_program()
    if dtype == "bfloat16":
        prog._amp = True
    before = metrics.get("attention.flash_bwd_residual")
    vals = fluid.Executor().run(prog, feed=feed, fetch_list=[out] + grads)
    rose = metrics.get("attention.flash_bwd_residual") - before
    return [np.asarray(v, np.float32) for v in vals], rose


@pytest.mark.parametrize("s", [128, 384])
@pytest.mark.parametrize("dtype, tol", [("float32", 2e-5), ("bfloat16", 3e-2)])
def test_two_widths_flash_matches_dense(s, dtype, tol, monkeypatch):
    """tol: float32 differs by the order of the online softmax's sums;
    bf16 by the rounding of p and ds to 8 bits before their matmuls."""
    flash, rose = _grads(s, dtype, True, monkeypatch)
    dense, none = _grads(s, dtype, False, monkeypatch)
    assert rose == 1 and none == 0      # the grad rule ran, on residuals
    assert flash[0].shape == (B, NH, s, DV)
    assert flash[1].shape == flash[2].shape == (B, NH, s, DQK)
    assert flash[3].shape == (B, NH, s, DV)
    for got, want, name in zip(flash, dense, ("out", "dq", "dk", "dv")):
        err = np.abs(got - want).max() / np.abs(want).max()
        assert err < tol, (name, err)


@pytest.mark.parametrize("nkv", [4, 1])
@pytest.mark.parametrize("window", flash_harness.WINDOWS)
@pytest.mark.parametrize("s, block_q, block_k", flash_harness.GEOMETRIES)
def test_two_widths_causal_kernels_match_a_plain_masked_softmax(
        s, block_q, block_k, window, nkv):
    """The kernels' three loops (tests/test_flash_attention.py has them at
    one width) with q and k 192 wide, v and the output 128."""
    flash_harness.check_causal_kernels(s, block_q, block_k, window, nkv=nkv,
                                       dqk=DQK, dv=DV)


@pytest.mark.parametrize("nkv", [4, 1])
@pytest.mark.parametrize("s, block_q, block_k", flash_harness.GEOMETRIES)
def test_two_widths_selected_kernels_take_empty_rows(s, block_q, block_k,
                                                     nkv):
    flash_harness.check_causal_kernels(
        s, block_q, block_k, nkv=nkv, dqk=DQK, dv=DV,
        select=flash_harness.selection_with_empty_rows(s, block_q, block_k))


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("dqk, dv, s, rows, dt", [
    (192, 128, 4096, 2, jnp.bfloat16),
    # BERT's cell under AMP, and the same shape as a program without AMP
    # hands it over
    (64, 64, 512, 32, jnp.bfloat16),
    (64, 64, 512, 32, jnp.float32)])
def test_kernels_compile_for_a_v5e_at_the_cells_sizes(v5e, dqk, dv, s, rows,
                                                      dt, monkeypatch):
    monkeypatch.setattr(fa, "interpret_mode", lambda: False)
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    heads = 32 if dqk == 192 else 12

    def sd(width, dtype=dt, shape=None):
        return jax.ShapeDtypeStruct(shape or (rows, heads, s, width), dtype,
                                    sharding=v5e)

    def step(q, k, v, do):
        o, lse = fa.flash_attention(q, k, v, causal=dqk == 192,
                                    return_lse=True)
        return fa.flash_attention_bwd(q, k, v, o, lse, do,
                                      causal=dqk == 192)

    try:
        text = jax.jit(step).trace(sd(dqk), sd(dqk), sd(dv), sd(dv)).lower(
            lowering_platforms=("tpu",)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dq",
                   "flash_attention_bwd_dkdv"):
        assert text.count(f'{kernel}"') or text.count(kernel), kernel
    assert text.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("window", [1024, None])
def test_grouped_kernels_compile_for_a_v5e_at_the_cells_size(v5e, window,
                                                             monkeypatch):
    """The sliding and the full layer of the window / full cell (32 query
    heads on 4 KV heads of 128, one row of 8,192, bf16): Mosaic takes the
    three loops of each kernel, the grouped dkdv grid among them."""
    monkeypatch.setattr(fa, "interpret_mode", lambda: False)
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)

    def sd(heads):
        return jax.ShapeDtypeStruct((1, heads, 8192, 128), jnp.bfloat16,
                                    sharding=v5e)

    def step(q, k, v, do):
        o, lse = fa.flash_attention(q, k, v, causal=True, window=window,
                                    return_lse=True)
        return fa.flash_attention_bwd(q, k, v, o, lse, do, causal=True,
                                      window=window)

    try:
        text = jax.jit(step).trace(sd(32), sd(4), sd(4), sd(32)).lower(
            lowering_platforms=("tpu",)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dq",
                   "flash_attention_bwd_dkdv"):
        assert text.count(kernel), kernel
    assert text.count("tpu_custom_call") >= 3


def _rows_launches_text(v5e, shapes, **kw):
    """The compiled text of the three launches in layout "bshd" from
    [B, S, heads * hd] projections (`shapes`: heads of q and of k, v)."""
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    (b, s, hd), (nh, nkv) = shapes

    def sd(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    def cut(t, heads):
        return t.reshape(b, s, heads, hd)

    def step(q, k, v, do, *extra):
        kws = dict(kw, **dict(zip(("mask", "seed"), extra)))
        q, k, v, do = cut(q, nh), cut(k, nkv), cut(v, nkv), cut(do, nh)
        o, lse = fa.flash_attention(q, k, v, return_lse=True, layout="bshd",
                                    **kws)
        return [t.reshape(b, s, -1) for t in (o,) + fa.flash_attention_bwd(
            q, k, v, o, lse, do, layout="bshd", **kws)]

    extra = ()
    if kw.get("dropout"):
        extra = (sd((b, 1, 1, s), jnp.float32), sd((), jnp.int32))
    try:
        return jax.jit(step).trace(
            sd((b, s, nh * hd)), sd((b, s, nkv * hd)), sd((b, s, nkv * hd)),
            sd((b, s, nh * hd)), *extra).lower(
            lowering_platforms=("tpu",)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)


def _head_moves(text):
    """The copy and transpose instructions of a compiled step whose result
    is a bf16 [B, S, heads * hd] or its heads cut out, either way round."""
    return re.findall(
        r"= (bf16\[(?:32,512,768|32,12,512,64|32,512,12,64)\]\S*) "
        r"(copy|transpose)\(", text)


def test_pair_kernels_compile_for_a_v5e_at_berts_size(v5e, monkeypatch):
    """BERT's [32, 512, 12 x 64] in layout "bshd": two 64-wide heads a
    128-lane block (`_stack_pair`), dropout and the key-padding mask in
    the kernels. Mosaic takes all three inside its default 16 MiB of
    scoped VMEM (the launches ask for no more), and from [32, 512, 768]
    operands the compiled step moves nothing around them."""
    monkeypatch.setattr(fa, "interpret_mode", lambda: False)
    asked = []
    params = fa._compiler_params
    monkeypatch.setattr(fa, "_compiler_params", lambda *a, **k: asked.append(
        params(*a, **k)) or asked[-1])
    text = _rows_launches_text(v5e, ((32, 512, 64), (12, 12)), dropout=0.1)
    assert len(asked) == 3
    assert all(p.vmem_limit_bytes is None for p in asked)
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dq",
                   "flash_attention_bwd_dkdv"):
        assert text.count(kernel), kernel
    assert text.count("tpu_custom_call") == 3
    assert not _head_moves(text)


@pytest.mark.parametrize("window", [1024, None])
def test_rows_kernels_compile_for_a_v5e_at_the_grouped_cells_size(
        v5e, window, monkeypatch):
    """The window / full cell's [1, 8192, 32 x 128] on 4 KV heads in layout
    "bshd": only the index maps differ from the head-layout launches above
    (a head is a lane block; K / V rows of 256 B at a stride of 1 KiB), and
    the VMEM each asks for is what it asks there."""
    monkeypatch.setattr(fa, "interpret_mode", lambda: False)
    text = _rows_launches_text(v5e, ((1, 8192, 128), (32, 4)), causal=True,
                               window=window)
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dq",
                   "flash_attention_bwd_dkdv"):
        assert text.count(kernel), kernel
    assert text.count("tpu_custom_call") == 3
    assert not re.search(r"bf16\[1,(?:32|4),8192,128\]", text)


def test_a_compiled_bert_layer_moves_no_heads(v5e, monkeypatch):
    """One encoder layer of the s512 cell (rows 32 x 512, 12 heads of 64,
    AMP, dropout 0.1, a padded batch), its train step traced as the
    executor traces it and compiled for the described chip: no copy and
    no transpose of a bf16 [32, 512, 768] or of its heads, forward or
    backward. Until PR 52 there were 16 a layer (q, k, v and `Out` to
    [32, 12, 512, 64], dq, dk, dv and dOut back, each in two passes
    through an S-minor layout: `PERF.md` section 5). The barrier in
    `_widen` passes dOut as rows for this count's sake."""
    import jax.extend
    import paddle_tpu as paddle
    from paddle_tpu.models import bert
    monkeypatch.setattr(fa, "interpret_mode", lambda: False)
    monkeypatch.setattr(attention, "_use_pallas",
                        lambda q: q.shape[2] >= 512 and q.shape[3] == 64)
    b, s = 32, 512
    reset_programs(0)
    cfg = bert.BertConfig(vocab_size=1024, hidden_size=768, num_layers=1,
                          num_heads=12, intermediate_size=3072,
                          max_position=s, seq_len=s, hidden_dropout=0.1,
                          attention_dropout=0.1)
    _, _, loss = bert.build_pretrain_program(cfg, use_input_mask=True)
    # one device's step (through fleet the test process's eight virtual
    # devices would each take four rows)
    paddle.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    fluid.default_main_program()._amp = True
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    feed = {"input_ids": np.zeros((b, s), np.int64),
            "mlm_labels": np.zeros((b, s, 1), np.int64),
            "input_mask": np.ones((b, s), np.float32)}
    before = [metrics.get("attention.flash_layout_" + n)
              for n in ("rows", "heads")]
    step = exe.step_jaxpr(feed, [loss])
    assert [metrics.get("attention.flash_layout_" + n) - was for n, was
            in zip(("rows", "heads"), before)] == [1, 0]
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(jax.extend.core.jaxpr_as_fun(step)).trace(*(
            jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e)
            for a in step.in_avals)).lower(
            lowering_platforms=("tpu",)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    assert text.count("tpu_custom_call") == 3
    assert "bf16[32,512,768]" in text
    assert not _head_moves(text)


def test_selection_kernels_compile_for_a_v5e_at_the_cells_size(v5e,
                                                               monkeypatch):
    """The learned-selection cell's attention (32 query heads on 4 KV heads
    of 128, one row of 8,192, bf16): the three kernels with the selection's
    int8 tile beside each score tile, and `selected_probs_sum`. Mosaic has
    to take the int8 tiles (`[256, 8192]` rows of a q block, `[8192, 512]`
    columns of a k block) and their VMEM."""
    monkeypatch.setattr(fa, "interpret_mode", lambda: False)
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    s = 8192

    def sd(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    def step(q, k, v, do, select):
        o, lse = fa.flash_attention(q, k, v, causal=True, return_lse=True,
                                    select=select)
        return fa.flash_attention_bwd(q, k, v, o, lse, do, causal=True,
                                      select=select), fa.selected_probs_sum(
            q, k, lse, select)

    try:
        text = jax.jit(step).trace(
            sd((1, 32, s, 128)), sd((1, 4, s, 128)), sd((1, 4, s, 128)),
            sd((1, 32, s, 128)), sd((1, s, s), jnp.int8)).lower(
            lowering_platforms=("tpu",)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dq",
                   "flash_attention_bwd_dkdv", "selected_probs_sum"):
        assert text.count(kernel), kernel
    assert text.count("tpu_custom_call") >= 4
    assert "s8[1,8192,8192]" in text and "[1,32,8192,8192]" not in text


# rows a layer, d, f, held experts of the benchmark's three sparse cells, and
# whether the experts have a gate (3 + 6 grouped matmuls a layer, or 2 + 4)
_EXPERT_SHAPES = {
    "mellum2_12b_ep4_s8192": (65536, 2304, 896, 16, True),
    "kanana2_30b_a3b_ep8_s4096": (49152, 2048, 768, 16, True),
    "nemotron_twotower_30b_a3b_ep16_s8192": (49152, 2688, 1856, 8, False),
    "ling3_flash_vl_ep64_tp2_s8192": (65536, 2560, 768, 8, True)}


@pytest.mark.parametrize("cell", _EXPERT_SHAPES)
def test_an_expert_layers_grouped_matmuls_compile_for_a_v5e(
        v5e, cell, monkeypatch):
    """`ops/moe.py`'s forward and backward bodies at a cell's shapes in
    bf16: every grouped matmul a Mosaic kernel whose instruction name
    holds `ragged-dot` (what the benchmark's readers find them by) under
    the `moe.experts` scope, each within the VMEM its tiles state, and no
    copy or transpose of a weight operand in front of the dx forms. The
    hybrid cell's expert width, 1856 = 14.5 x 128, is one full-width block
    of every kernel that meets it."""
    monkeypatch.setattr(gm, "interpret_mode", lambda: False)
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    rows, d, f, e, gated = _EXPERT_SHAPES[cell]
    n, per_form = 8192, 3 if gated else 2

    # the weights and their gradients row-major, as a step's loop carries
    # them. Left to itself the compiler stores an array whose last width
    # is no multiple of 128 with the other width innermost, and a program
    # that is one layer would open and close on a change of layout
    row_major = Format(Layout(major_to_minor=(0, 1, 2)), v5e)

    def sd(shape, dtype=jnp.bfloat16, where=v5e):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=where)

    def layer(xt, w_sorted, order, inv, sizes, eg, eu, ed, g):
        out, h, u = moe._experts_fwd(True, xt, w_sorted, order, inv, sizes,
                                     eg, eu, ed)
        return out, moe._experts_bwd(True, xt, w_sorted, order, inv, sizes,
                                     eg, eu, ed, h, u, g)

    args = (sd((n, d)), sd((rows,), jnp.float32), sd((rows,), jnp.int32),
            sd((rows,), jnp.int32), sd((e,), jnp.int32),
            sd((e, d, f), where=row_major) if gated else None,
            sd((e, d, f), where=row_major), sd((e, f, d), where=row_major),
            sd((n, d)))
    grads = (None, None, row_major if gated else None, row_major, row_major)
    counters = ("moe.grouped_pallas", "moe.grouped_xla")
    before = [metrics.get(c) for c in counters]
    try:
        traced = jax.jit(layer, out_shardings=(None, grads)).trace(*args)
        text = traced.lower(lowering_platforms=("tpu",)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    assert [metrics.get(c) - b for c, b in zip(counters, before)] \
        == [3 * per_form, 0]
    assert "ragged_dot" not in str(traced.jaxpr)
    kernels = re.findall(
        r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"[^\n]*'
        r'op_name="([^"]*)"', text)
    assert len(kernels) == 3 * per_form, kernels
    for name, op_name in kernels:
        assert "ragged-dot" in name and "moe.experts" in op_name, (
            name, op_name)
    kinds = sorted(name.rsplit(".", 1)[0] for name, _ in kernels)
    assert kinds == (["ragged-dot-gmm"] * per_form
                     + ["ragged-dot-gmm-t"] * per_form
                     + ["ragged-dot-tgmm"] * per_form)
    # an [E, ., .] operand reaches its kernel as the parameter it is
    assert not re.search(rf"= bf16\[{e},\d+,\d+\][^ ]* (copy|transpose)\(",
                         text)
    for k, w in ((d, f), (f, d)):
        whole, cut = gm.gmm_tiles(rows, k, w), gm.tgmm_tiles(rows, k, w)
        assert (whole.tk, whole.tn) == (k, w)
        # where the float32 accumulator does not fit, a multiple of 128 is
        # cut; a width that is none stays whole
        assert (cut.tk, cut.tn) == (k, w) if f % 128 == 0 \
            else f in (cut.tk, cut.tn)
        for tiles in (whole, cut):
            assert tiles.resident_bytes + (8 << 20) <= 100 << 20


def test_the_gated_delta_rule_compiles_for_a_v5e_at_the_cells_size(v5e):
    """`ops/kda.py`'s forward and its grad rule's backward at one layer of
    the linear-attention cell (1 x 8,192 positions, 16 heads of 128, bf16
    operands, chunks of 64): XLA takes the triangular solve and the blocked
    decayed products, the chunk states are the only `[.., 128, 128]` value a
    position-free axis carries (128 chunks, never 8,192 positions), and the
    layer's temporaries stay under 3 GB."""
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    s, h, d, chunk = 8192, 16, 128, 64

    def sd(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    def layer(q, k, v, g, beta, do):
        o, states = kda._kda_fwd(chunk, q, k, v, g, beta)
        return o, states, kda._kda_bwd(chunk, q, k, v, g, beta, states, do)

    rows = (1, s, h, d)
    try:
        compiled = jax.jit(layer).trace(
            sd(rows), sd(rows), sd(rows), sd(rows, jnp.float32),
            sd(rows[:3], jnp.float32), sd(rows)).lower(
                lowering_platforms=("tpu",)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    text = compiled.as_text()
    assert f"f32[1,{s // chunk},{h},{d},{d}]" in text
    assert not re.search(rf"\[1,{s},{h},{d},{d}\]|\[1,{h},{s},{d},{d}\]",
                         text)
    assert "kda.scan.solve" in text and "kda.scan.carry" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 3e9


def test_the_selective_scan_compiles_for_a_v5e_at_the_cells_size(
        v5e, monkeypatch):
    """`ssm_scan`'s forward and its grad rule's backward at one layer of
    the hybrid cell (1 x 8,192 positions, 64 heads of 64 in 8 groups, state
    128, chunks of 128, bf16 rows as the mixer's projection and conv hand
    them over, `[1, 8192, 4096]` and `[1, 8192, 1024]`): Mosaic takes both
    kernels; the chunk states are the one `[.., 64, 128]` float32 value
    with the 64 chunks in front; no float32 `[128, 128]` matrix a chunk
    and head reaches HBM; x, B, C and dy reach the calls as the parameters
    they are and Y, dx, dB, dC leave them as the results they are, no copy
    or transpose of a `[1, 8192, ..]` bf16 value; and the layer's
    temporaries stay under 128 MB (the `jax.numpy` form's `[L, L]` matrices
    alone were 0.4 GB forward and 1.5 GB backward)."""
    monkeypatch.setattr(ssm_chunk, "interpret_mode", lambda: False)
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    b, s, h, p, g, n, chunk = 1, 8192, 64, 64, 8, 128, 128
    opdef = registry.get("ssm_scan")

    def sd(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    def layer(x, bm, cm, dt, dt_bias, a_log, d, dy):
        ctx = registry.LowerCtx(rng_key=None)
        ins = {"X": [x.reshape(b, s, h, p)], "B": [bm.reshape(b, s, g, n)],
               "C": [cm.reshape(b, s, g, n)], "Dt": [dt],
               "DtBias": [dt_bias], "ALog": [a_log], "D": [d]}
        attrs = {"chunk_size": chunk}
        outs = opdef.lower(ctx, ins, attrs)
        grads = opdef.grad(ctx, ins, attrs,
                           {k: outs[k] for k in opdef.residual_slots},
                           {"Y": [dy.reshape(b, s, h, p)]})
        wide, narrow = (b, s, h * p), (b, s, g * n)
        return (outs["Y"][0].reshape(wide), outs["States"][0],
                grads["X"][0].reshape(wide), grads["B"][0].reshape(narrow),
                grads["C"][0].reshape(narrow),
                [grads[k][0] for k in ("Dt", "DtBias", "ALog", "D")])

    per_head = sd((h,), jnp.float32)
    counters = ("ssm.scan_pallas", "ssm.scan_xla")
    before = [metrics.get(c) for c in counters]
    try:
        compiled = jax.jit(layer).trace(
            sd((b, s, h * p)), sd((b, s, g * n)), sd((b, s, g * n)),
            sd((b, s, h), jnp.float32), per_head, per_head, per_head,
            sd((b, s, h * p))).lower(lowering_platforms=("tpu",)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    assert [metrics.get(c) - v for c, v in zip(counters, before)] == [2, 0]
    text = compiled.as_text()
    kernels = re.findall(
        r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    assert sorted(k.rsplit(".", 1)[0] for k in kernels) \
        == ["ssm-chunk-bwd", "ssm-chunk-fwd"], kernels
    assert f"f32[{b},{s // chunk},{h},{p},{n}]" in text
    assert not re.search(rf"f32\[[\d,]*{chunk},{chunk}\]", text)
    assert not re.search(rf"= bf16\[{b},{s},\d+\][^ ]* (copy|transpose)\(",
                         text)
    assert compiled.memory_analysis().temp_size_in_bytes < 128e6


def test_the_indexers_scores_compile_for_a_v5e_at_the_cells_size(
        v5e, monkeypatch):
    """`sparse_index`'s forward and its grad rule's backward at one layer
    of the learned-selection cell (16 indexer heads of 64 over 1 x 8,192
    positions, bf16 operands, float32 weights, 2,048 keys a query): Mosaic
    takes both score kernels; the heads' products of a block of queries
    reach HBM in neither direction, nor does the backward's `g`; what the
    layer keeps beside its `[S, S]` results stays under the 268 MB that the
    `jax.numpy` form's block of products alone took."""
    monkeypatch.setattr(index_scores, "interpret_mode", lambda: False)
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    b, h, s, d, topk = 1, 16, 8192, 64, 2048
    opdef = registry.get("sparse_index")

    def sd(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    def layer(q, k, w, ds):
        ctx = registry.LowerCtx(rng_key=None)
        ins = {"QI": [q], "KI": [k], "W": [w]}
        outs = opdef.lower(ctx, ins, {"topk": topk})
        grads = opdef.grad(ctx, ins, {"topk": topk}, {}, {"Scores": [ds]})
        return (outs["Scores"][0], outs["Select"][0],
                [grads[slot][0] for slot in ("QI", "KI", "W")])

    counters = ("attn.index_pallas", "attn.index_xla")
    before = [metrics.get(c) for c in counters]
    try:
        compiled = jax.jit(layer).trace(
            sd((b, h, s, d)), sd((b, s, d)), sd((b, s, h), jnp.float32),
            sd((b, s, s), jnp.float32)).lower(
                lowering_platforms=("tpu",)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    assert [metrics.get(c) - v for c, v in zip(counters, before)] == [2, 0]
    text = compiled.as_text()
    kernels = re.findall(
        r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    assert sorted(k.rsplit(".", 1)[0] for k in kernels) \
        == ["index-scores-bwd", "index-scores-fwd"], kernels
    assert not re.search(rf"\[({b},)?{h},\d+,{s}\]", text)
    assert f"s8[{b},{s},{s}]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 256e6


def test_the_delta_rule_for_any_decay_compiles_for_a_v5e_at_the_cells_size(
        v5e, monkeypatch):
    """`kda_scan` WITHOUT a bound on the decay, forward and its grad rule's
    backward at one layer of the unbounded-decay cell (1 x 4,096 positions, 8
    heads of 128, bf16 operands, chunks of 64): Mosaic takes both kernels
    with the level-by-level products (the 0 / 1 matrix of a chunk's sums
    from iotas, three bf16 passes over it, six `[2 L, d] x [d, L]` products
    a chunk and head), four heads a grid step inside the VMEM budget. The
    chunk states are the one `[.., 128, 128]` float32 value the layer
    writes."""
    from paddle_tpu.ops.pallas import kda_chunk
    monkeypatch.setattr(kda_chunk, "interpret_mode", lambda: False)
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    s, h, d, chunk = 4096, 8, 128, 64
    opdef = registry.get("kda_scan")
    attrs = {"chunk_size": chunk, "beta_scale": 2.0}

    def sd(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    def layer(q, k, v, g, beta, do):
        ctx = registry.LowerCtx(rng_key=None)
        ins = {"Q": [q], "K": [k], "V": [v], "G": [g], "Beta": [beta]}
        outs = opdef.lower(ctx, ins, attrs)
        grads = opdef.grad(ctx, ins, attrs, {"States": outs["States"]},
                           {"Y": [do]})
        return outs["Y"][0], [grads[slot][0] for slot in ins]

    rows = (1, s, h, d)
    plan = kda_chunk.plan(rows, rows, chunk, jnp.bfloat16, exact=True)
    assert plan.exact and plan.heads == 4
    assert plan.resident_bytes + (8 << 20) < 16 << 20
    counters = ("kda.scan_pallas", "kda.scan_exact", "kda.scan_bounded")
    before = [metrics.get(c) for c in counters]
    args = (sd(rows), sd(rows), sd(rows), sd(rows, jnp.float32),
            sd(rows[:3], jnp.float32))
    try:
        compiled = jax.jit(layer).trace(*args, sd(rows)).lower(
            lowering_platforms=("tpu",)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    assert [metrics.get(c) - v for c, v in zip(counters, before)] == [2, 2, 0]
    text = compiled.as_text()
    kernels = re.findall(
        r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    assert sorted(k.rsplit(".", 1)[0] for k in kernels) \
        == ["kda-chunk-bwd", "kda-chunk-fwd"], kernels
    assert f"f32[1,{s // chunk},{h * d},{d}]" in text
    assert "triangular_solve" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64e6
