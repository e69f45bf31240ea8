"""fused_lm_head_ce: vocab-chunked streaming LM-head cross-entropy.

Parity: forward loss and BOTH gradients (hidden states and weight) must
match the dense matmul+softmax_with_cross_entropy pair to float
tolerance, with a chunk size that forces multiple scan steps AND a
ragged final chunk. Memory: the fused program's largest live tensor
must stay chunk-sized where the dense one materializes [B, S, V]
logits (asserted on optimized HLO — no hardware needed). Rows: only the
rows that carry a label are computed, in whole blocks of `ROW_BLOCK`
(parity at every kept count against the dense float32 pair, the row
counts of every product in the jaxpr, the op's `Rows` output)."""
import functools

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.testing import reset_programs


def _build_ce(fused, b, s, h, v, chunk=None, seed=0):
    """Tiny LM-ish program: trainable x-projection + head table, CE loss.
    Returns (exe, feed, loss, names of grads to fetch)."""
    reset_programs(seed=seed)
    feat = layers.data(name="feat", shape=[s, h], dtype="float32")
    label = layers.data(name="label", shape=[s, 1], dtype="int64")
    proj = layers.create_parameter([h, h], "float32", name="proj")
    w = layers.create_parameter([v, h], "float32", name="head_w")
    x = layers.matmul(feat, proj)
    if fused:
        loss_tok = layers.fused_lm_head_ce(x, w, label,
                                           chunk=chunk or 8192)
    else:
        logits = layers.matmul(x, w, transpose_y=True)
        loss_tok = layers.softmax_with_cross_entropy(logits, label)
    loss = layers.mean(loss_tok)
    paddle.optimizer.SGD(learning_rate=0.0).minimize(loss)  # grads only
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(7)
    feed = {"feat": rng.randn(b, s, h).astype(np.float32) * 0.3,
            "label": rng.randint(0, v, (b, s, 1)).astype(np.int64)}
    return exe, feed, loss


def _loss_and_grads(fused, chunk=None, v=37):
    exe, feed, loss = _build_ce(fused, b=2, s=5, h=16, v=v, chunk=chunk)
    gb = fluid.default_main_program().global_block()
    fetches = [loss.name, "proj@GRAD", "head_w@GRAD"]
    fetches = [f for f in fetches if gb.has_var(f)]
    return exe.run(feed=feed, fetch_list=fetches)


def test_fused_ce_matches_dense_loss_and_grads():
    # chunk 8 over v=37: 5 scan steps with a ragged 5-row final chunk
    dense = _loss_and_grads(fused=False)
    fused = _loss_and_grads(fused=True, chunk=8)
    assert len(dense) == len(fused) == 3
    for d, f in zip(dense, fused):
        np.testing.assert_allclose(np.asarray(f), np.asarray(d),
                                   rtol=2e-5, atol=2e-6)


def test_fused_ce_single_chunk_matches():
    dense = _loss_and_grads(fused=False)
    fused = _loss_and_grads(fused=True, chunk=64)   # one chunk covers all
    np.testing.assert_allclose(np.asarray(fused[0]), np.asarray(dense[0]),
                               rtol=2e-5)


def test_gpt_auto_selects_fused_head():
    from paddle_tpu.models import gpt
    reset_programs(seed=0)
    cfg = gpt.GPTConfig(vocab_size=20000, hidden_size=32, num_layers=1,
                        num_heads=4, intermediate_size=64, max_position=16,
                        seq_len=16)
    gpt.build_lm_program(cfg)
    ops = [op.type for op in fluid.default_main_program()
           .global_block().ops]
    assert "fused_lm_head_ce" in ops
    reset_programs(seed=0)
    cfg.vocab_size = 512
    gpt.build_lm_program(cfg)
    ops = [op.type for op in fluid.default_main_program()
           .global_block().ops]
    assert "fused_lm_head_ce" not in ops
    assert "softmax_with_cross_entropy" in ops


def test_fused_ce_largest_live_tensor_is_bounded():
    """Compile both variants at a vocab where [B,S,V] logits dominate and
    compare the LARGEST tensor in the optimized HLO (memory_analysis
    reports no temp bytes on the CPU backend, so assert on structure:
    the fused program must never materialize a vocab-sized tensor)."""
    import re

    DT = {"f32": 4, "f64": 8, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
          "s32": 4, "u32": 4, "pred": 1, "s8": 1, "u8": 1}

    def largest_tensor_bytes(fused):
        exe, feed, loss = _build_ce(fused, b=4, s=64, h=64, v=16384,
                                    chunk=2048, seed=0)
        # the public compile-stats surface; no executor internals
        txt = exe.compiled_hlo(feed, [loss])
        biggest = 0
        for m in re.finditer(r"= (\w+)\[([\d,]+)\]", txt):
            dt, shape = m.groups()
            n = 1
            for d in shape.split(","):
                n *= int(d)
            biggest = max(biggest, n * DT.get(dt, 4))
        return biggest

    dense = largest_tensor_bytes(False)
    fused = largest_tensor_bytes(True)
    # dense materializes f32[4,64,16384] = 16.8 MB logits; the fused
    # program's biggest tensor is a [4,64,2048] chunk (2 MB) or the
    # [16384,64] weight (4.2 MB). A surviving vocab-x-seq-sized tensor
    # means the streaming structure broke.
    assert dense >= 4 * 64 * 16384 * 4, dense       # sanity: logits seen
    assert fused * 3 < dense, (dense, fused)


def test_fused_ce_under_amp_bf16():
    """fused_lm_head_ce is AMP white-listed (amp/auto_cast.py): bf16-cast
    operands with f32 einsum accumulation must track the f32 loss within
    bf16 tolerance — the GPT bench row runs exactly this combination."""
    exe, feed, loss = _build_ce(True, b=2, s=5, h=16, v=37, chunk=8)
    ref, = exe.run(feed=feed, fetch_list=[loss])
    exe2, feed2, loss2 = _build_ce(True, b=2, s=5, h=16, v=37, chunk=8)
    fluid.default_main_program()._amp = True        # what strategy.amp sets
    amp, = exe2.run(feed=feed2, fetch_list=[loss2])
    np.testing.assert_allclose(np.asarray(amp), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def test_fused_ce_hv_layout_with_bias_matches_fc():
    """BERT-head shape: fc([H,V] weight + [V] bias) + CE vs the fused op
    with w_layout='hv' — loss and all three grads must match."""
    def build(fused):
        reset_programs(seed=3)
        b, s, h, v = 2, 5, 16, 37
        feat = layers.data(name="feat", shape=[s, h], dtype="float32")
        label = layers.data(name="label", shape=[s, 1], dtype="int64")
        w = layers.create_parameter([h, v], "float32", name="head_hv")
        bia = layers.create_parameter([v], "float32", name="head_b",
                                      is_bias=True)
        if fused:
            loss_tok = layers.fused_lm_head_ce(feat, w, label, chunk=8,
                                               bias=bia, w_layout="hv")
        else:
            logits = layers.elementwise_add(layers.matmul(feat, w), bia)
            loss_tok = layers.softmax_with_cross_entropy(logits, label)
        loss = layers.mean(loss_tok)
        paddle.optimizer.SGD(learning_rate=0.0).minimize(loss)
        exe = fluid.Executor()
        exe.run(fluid.default_startup_program())
        rng = np.random.RandomState(5)
        feed = {"feat": rng.randn(b, s, h).astype(np.float32) * 0.3,
                "label": rng.randint(0, v, (b, s, 1)).astype(np.int64)}
        # bias init is 0: nudge it so its grad path is actually exercised
        from paddle_tpu.framework.scope import global_scope
        import jax.numpy as jnp
        global_scope().set("head_b", jnp.asarray(
            rng.randn(v).astype(np.float32) * 0.1))
        return exe.run(feed=feed, fetch_list=[
            loss.name, "head_hv@GRAD", "head_b@GRAD"])

    dense = build(False)
    fused = build(True)
    # tolerance note: when this file is run directly under the TPU
    # plugin preload (not through ci.py's sanitized CPU-mesh env), it
    # executes on the real chip, where f32 matmuls default to bf16-grade
    # MXU passes — measured 1.2e-5 abs / ~1% rel deviation between the
    # chunked and dense groupings, vs 1.5e-8 on CPU. Real math bugs
    # produce O(1) relative errors, so 5% rel still catches them on
    # either backend.
    for d, f in zip(dense, fused):
        np.testing.assert_allclose(np.asarray(f), np.asarray(d),
                                   rtol=5e-2, atol=1e-4)


def test_bert_auto_selects_fused_head():
    """BERT auto rule: the fused MLM head at a real vocab whatever the
    sequence length (the op computes the labelled rows only, so the short
    geometry gains as the long one does); a vocab under 2x the chunk keeps
    the dense pair; an explicit `fused_mlm_head` wins either way."""
    from paddle_tpu.models import bert
    from paddle_tpu.ops.fused_ce import DEFAULT_CHUNK

    def head_ops(cfg):
        reset_programs(seed=0)
        bert.build_pretrain_program(cfg)
        return [op.type for op in fluid.default_main_program()
                .global_block().ops]

    def cfg_at(vocab, seq):
        return bert.BertConfig(vocab_size=vocab, hidden_size=32,
                               num_layers=1, num_heads=4,
                               intermediate_size=64, max_position=seq,
                               seq_len=seq)

    for seq in (16, 128, 512):
        assert "fused_lm_head_ce" in head_ops(cfg_at(20000, seq)), seq
    small = cfg_at(2 * DEFAULT_CHUNK - 1, 512)
    ops = head_ops(small)
    assert "fused_lm_head_ce" not in ops
    assert "softmax_with_cross_entropy" in ops
    small.fused_mlm_head = True             # explicit force wins
    assert "fused_lm_head_ce" in head_ops(small)
    real = cfg_at(20000, 16)
    real.fused_mlm_head = False
    assert "fused_lm_head_ce" not in head_ops(real)


def test_bert_builder_takes_the_op_at_a_real_vocab_and_seq_128():
    """The four-chip cell's geometry: at a real vocab and seq_len 128 the
    program holds ONE fused_lm_head_ce op, no [B, S, V] variable, and the
    dense head's parameter names and shapes (a checkpoint, and the
    benchmark's weights, load on either path); the tiny preset keeps the
    dense pair."""
    from paddle_tpu.models import bert

    def build(cfg):
        reset_programs(seed=0)
        bert.build_pretrain_program(cfg)
        return fluid.default_main_program().global_block()

    cfg = bert.BertConfig(vocab_size=30522, hidden_size=32, num_layers=1,
                          num_heads=4, intermediate_size=64,
                          max_position=128, seq_len=128)
    gb = build(cfg)
    types = [op.type for op in gb.ops]
    assert types.count("fused_lm_head_ce") == 1
    assert "softmax_with_cross_entropy" not in types
    wide = [v.name for v in gb.vars.values()
            if len(v.shape) == 3 and v.shape[-1] == cfg.vocab_size]
    assert not wide, wide
    assert tuple(gb.var("mlm_head_w").shape) == (32, 30522)
    assert tuple(gb.var("mlm_head_b").shape) == (30522,)
    op = gb.ops[types.index("fused_lm_head_ce")]
    assert op.inputs["W"] == ["mlm_head_w"]
    assert op.inputs["Bias"] == ["mlm_head_b"]
    assert op.attrs["w_layout"] == "hv"

    tiny = build(bert.BertConfig.tiny())
    types = [op.type for op in tiny.ops]
    assert "fused_lm_head_ce" not in types
    assert "softmax_with_cross_entropy" in types
    assert tuple(tiny.var("mlm_head_w").shape) == (64, 1024)


def test_gpt_tiny_step_through_the_op_gives_the_loss_it_gave():
    """GPT-2's tiny step with the head forced through the op labels every
    shifted position: every block is walked, and three Adam steps read the
    losses they read before the op looked at its labels (the parent's
    values, float32 on the CPU; the sums run block by block now)."""
    from paddle_tpu.models import gpt
    reset_programs(seed=0)
    cfg = gpt.GPTConfig.tiny()
    tokens, loss = gpt.build_lm_program(cfg, fused_head=True)
    paddle.optimizer.Adam(learning_rate=2e-3).minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    feed = {"tokens": rng.randint(0, cfg.vocab_size,
                                  (8, cfg.seq_len)).astype(np.int64)}
    got = [float(np.asarray(exe.run(feed=feed, fetch_list=[loss])[0])
                 .reshape(-1)[0]) for _ in range(3)]
    np.testing.assert_allclose(
        got, [6.251549243927002, 6.009985446929932, 5.827591419219971],
        rtol=2e-6)


def test_fused_ce_out_of_range_label_is_nan():
    """Labels outside [0, V) have no implemented ignore semantics: the op
    yields NaN for that token (loud), per the documented contract."""
    exe, feed, loss = _build_ce(True, b=2, s=5, h=16, v=37, chunk=8)
    feed = dict(feed)
    bad = feed["label"].copy()
    bad[0, 0, 0] = -1
    bad[1, 2, 0] = 37
    feed["label"] = bad
    gb = fluid.default_main_program().global_block()
    fetches = [loss.name] + [f for f in ("proj@GRAD", "head_w@GRAD")
                             if gb.has_var(f)]
    vals = exe.run(feed=feed, fetch_list=fetches)
    assert np.isnan(np.asarray(vals[0])), \
        "out-of-range label must surface NaN loss"
    # the custom-VJP backward must be loud too: a finite gradient with
    # the label term silently missing would corrupt training
    for name, g in zip(fetches[1:], vals[1:]):
        assert np.isnan(np.asarray(g)).any(), \
            f"{name} must carry NaN for the invalid token"


def _ignore_ce_build(fused, ignore_index=-1):
    """Dense-vs-fused builder whose labels include ignore_index tokens."""
    reset_programs(seed=11)
    b, s, h, v = 2, 5, 16, 37
    feat = layers.data(name="feat", shape=[s, h], dtype="float32")
    label = layers.data(name="label", shape=[s, 1], dtype="int64")
    proj = layers.create_parameter([h, h], "float32", name="proj")
    w = layers.create_parameter([v, h], "float32", name="head_w")
    x = layers.matmul(feat, proj)
    if fused:
        loss_tok = layers.fused_lm_head_ce(x, w, label, chunk=8,
                                           ignore_index=ignore_index)
    else:
        logits = layers.matmul(x, w, transpose_y=True)
        loss_tok = layers.softmax_with_cross_entropy(
            logits, label, ignore_index=ignore_index)
    loss = layers.mean(loss_tok)
    paddle.optimizer.SGD(learning_rate=0.0).minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(13)
    lab = rng.randint(0, v, (b, s, 1)).astype(np.int64)
    lab[0, :3, 0] = ignore_index                 # ignored tokens
    feed = {"feat": rng.randn(b, s, h).astype(np.float32) * 0.3,
            "label": lab}
    return exe, feed, loss_tok


def test_sce_ignore_index_zeroes_loss_and_grads():
    """softmax_with_cross_entropy honors ignore_index (it used to accept
    and silently drop the kwarg): ignored tokens get zero loss and
    contribute nothing to the gradients."""
    exe, feed, loss_tok = _ignore_ce_build(fused=False)
    lt, gp, gw = exe.run(feed=feed,
                         fetch_list=[loss_tok.name, "proj@GRAD",
                                     "head_w@GRAD"])
    assert np.all(np.asarray(lt)[0, :3] == 0.0)
    assert np.all(np.asarray(lt)[0, 3:] > 0.0)
    assert np.isfinite(np.asarray(gp)).all()
    assert np.isfinite(np.asarray(gw)).all()
    # zero-grad check: an all-ignored batch must produce exactly zero
    feed_all = dict(feed)
    feed_all["label"] = np.full_like(feed["label"], -1)
    lt2, gp2, gw2 = exe.run(feed=feed_all,
                            fetch_list=[loss_tok.name, "proj@GRAD",
                                        "head_w@GRAD"])
    assert np.all(np.asarray(lt2) == 0.0)
    np.testing.assert_array_equal(np.asarray(gp2), 0.0)
    np.testing.assert_array_equal(np.asarray(gw2), 0.0)


def test_fused_ce_ignore_index_matches_dense():
    """The dense/fused auto-switch must not change ignore-label behavior
    (ADVICE #1): with the SAME ignore_index, per-token losses and both
    gradients match to float tolerance."""
    dense_exe, feed, dense_tok = _ignore_ce_build(fused=False)
    d = dense_exe.run(feed=feed, fetch_list=[dense_tok.name, "proj@GRAD",
                                             "head_w@GRAD"])
    fused_exe, feed_f, fused_tok = _ignore_ce_build(fused=True)
    f = fused_exe.run(feed=feed_f, fetch_list=[fused_tok.name, "proj@GRAD",
                                               "head_w@GRAD"])
    for dv, fv in zip(d, f):
        np.testing.assert_allclose(np.asarray(fv), np.asarray(dv),
                                   rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# only the labelled rows are computed
# ---------------------------------------------------------------------------
_ROWS = dict(b=2, s=600, h=16, v=37, chunk=16)      # N = 1200 > ROW_BLOCK


def _lowering(layout, bias):
    """(x, w, b, labels, cot) -> (loss . cot, Rows) through the op's own
    lowering, as a program's walk calls it."""
    import jax.numpy as jnp
    from paddle_tpu.ops import fused_ce

    def f(x, w, b, labels, cot):
        ins = {"X": [x], "W": [w], "Label": [labels]}
        if bias:
            ins["Bias"] = [b]
        outs = fused_ce._fused_lm_head_ce(
            None, ins, {"w_layout": layout, "chunk": _ROWS["chunk"]})
        return jnp.sum(outs["Loss"][0][..., 0] * cot), outs["Rows"][0]
    return f


@functools.lru_cache(maxsize=None)
def _rows_pair(layout, bias):
    """Jitted (op, dense float32 reference), each -> ((value, aux), the
    gradients of x, w and b): one compile a (layout, bias), whatever the
    labels."""
    import jax
    import jax.numpy as jnp

    def dense(x, w, b, labels, cot):
        logits = jnp.einsum("bsh,vh->bsv", x, w if layout == "vh" else w.T,
                            precision="highest")
        if bias:
            logits = logits + b
        lab = labels[..., 0]
        lse = jax.nn.logsumexp(logits, axis=-1)
        got = jnp.take_along_axis(logits, jnp.maximum(lab, 0)[..., None],
                                  axis=-1)[..., 0]
        return jnp.sum(jnp.where(lab == -100, 0.0, lse - got) * cot), 0

    grad = functools.partial(jax.value_and_grad, argnums=(0, 1, 2),
                             has_aux=True)
    return jax.jit(grad(_lowering(layout, bias))), jax.jit(grad(dense))


def _rows_case(kept, placement, layout, seed=3):
    from paddle_tpu.ops.fused_ce import ROW_BLOCK
    b, s, h, v = (_ROWS[k] for k in "bshv")
    n_rows = b * s
    n = {"0": 0, "1": 1, "R-1": ROW_BLOCK - 1, "R": ROW_BLOCK,
         "R+1": ROW_BLOCK + 1, "11%": round(0.11 * n_rows),
         "N": n_rows}[kept]
    rng = np.random.RandomState(seed)
    if placement == "scattered":
        at = rng.permutation(n_rows)[:n]
    else:               # one run of positions from the second row's start:
        at = (s + np.arange(n)) % n_rows     # up to S of them lie in one row
    lab = np.full((n_rows,), -100, np.int32)
    lab[at] = rng.randint(0, v, n)
    w_shape = (v, h) if layout == "vh" else (h, v)
    return n, (rng.randn(b, s, h).astype(np.float32) * 0.3,
               rng.randn(*w_shape).astype(np.float32) * 0.3,
               rng.randn(v).astype(np.float32) * 0.1,
               lab.reshape(b, s, 1),
               rng.rand(b, s).astype(np.float32) + 0.5)


@pytest.mark.parametrize("bias", [False, True], ids=["nobias", "bias"])
@pytest.mark.parametrize("layout", ["vh", "hv"])
@pytest.mark.parametrize("placement", ["scattered", "one_row"])
@pytest.mark.parametrize("kept", ["0", "1", "R-1", "R", "R+1", "11%", "N"])
def test_labelled_rows_only_match_dense_float32(kept, placement, layout,
                                                bias):
    """Loss and the gradients of x, w and b against the dense float32
    pair at every kept count where the walk's trip count or a block's
    filling changes (none, one, a block less one, a block, a block and
    one, a masked LM's share, every row), both weight layouts, with and
    without bias, the labels scattered and in one run; and `Rows` reads
    the labelled count up to whole blocks."""
    from paddle_tpu.ops.fused_ce import ROW_BLOCK
    n, args = _rows_case(kept, placement, layout)
    fused, dense = _rows_pair(layout, bias)
    (got, rows), got_g = fused(*args)
    (want, _), want_g = dense(*args)
    assert int(rows[0]) == -(-n // ROW_BLOCK) * ROW_BLOCK
    np.testing.assert_allclose(float(got), float(want), rtol=2e-5,
                               atol=2e-6)
    for name, g, wg in zip("xwb", got_g, want_g):
        if name == "b" and not bias:
            continue
        np.testing.assert_allclose(np.asarray(g), np.asarray(wg),
                                   rtol=2e-4, atol=2e-6, err_msg=name)
    if n == 0:          # nothing labelled: nothing walked, exact zeros
        assert float(got) == 0.0
        assert not np.asarray(got_g[0]).any()
        assert not np.asarray(got_g[1]).any()


def test_every_product_of_the_op_has_a_block_of_rows():
    """The jaxpr of the op's forward and backward at N = 1200 > ROW_BLOCK:
    every dot_general has ROW_BLOCK rows, none has N (nor N padded to
    whole blocks), and the loops over row blocks are `while`s whose bound
    is data."""
    import jax
    from paddle_tpu.ops.fused_ce import ROW_BLOCK
    _, args = _rows_case("11%", "scattered", "hv")
    n_rows = _ROWS["b"] * _ROWS["s"]
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: _lowering("hv", True)(*a)[0], argnums=(0, 1, 2)))(*args)
    dots, whiles = [], 0

    def walk(jp):
        nonlocal whiles
        for eqn in jp.eqns:
            if eqn.primitive.name == "dot_general":
                dots.append([tuple(v.aval.shape) for v in eqn.invars])
            whiles += eqn.primitive.name == "while"
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert len(dots) == 4, dots         # logits forward; logits, dx, dw back
    whole = {n_rows, -(-n_rows // ROW_BLOCK) * ROW_BLOCK}
    for shapes in dots:
        dims = {d for shape in shapes for d in shape}
        assert ROW_BLOCK in dims and not dims & whole, shapes
    assert whiles >= 2


def test_rows_output_is_fetched_and_feeds_the_gauge():
    """`return_rows=True` wires the op's second output into the program:
    a fetch reads ceil(n / ROW_BLOCK) * ROW_BLOCK for each batch's own
    label count from ONE compiled step, and `record_rows_share` sets gauge
    `head.rows_computed_share` from it."""
    from paddle_tpu.observability import metrics
    from paddle_tpu.ops.fused_ce import ROW_BLOCK, record_rows_share
    reset_programs(seed=0)
    b, s, h, v = (_ROWS[k] for k in "bshv")
    feat = layers.data(name="feat", shape=[s, h], dtype="float32")
    label = layers.data(name="label", shape=[s, 1], dtype="int64")
    w = layers.create_parameter([v, h], "float32", name="head_w")
    loss_tok, rows = layers.fused_lm_head_ce(feat, w, label, chunk=16,
                                             return_rows=True)
    loss = layers.mean(loss_tok)
    paddle.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    misses = None
    for kept in ("0", "11%", "R+1", "N"):
        n, (x, _, _, lab, _) = _rows_case(kept, "scattered", "vh")
        lv, rv = exe.run(feed={"feat": x, "label": lab.astype(np.int64)},
                         fetch_list=[loss, rows])
        assert np.isfinite(np.asarray(lv)).all()
        want = -(-n // ROW_BLOCK) * ROW_BLOCK
        assert int(np.asarray(rv).reshape(-1)[0]) == want, kept
        share = record_rows_share(np.asarray(rv).reshape(-1)[0], b * s)
        assert metrics.get("head.rows_computed_share") == share \
            == want / (b * s)
        if misses is None:
            misses = metrics.get("executor.compile_cache_misses")
    # a trip count that is data compiles once
    assert metrics.get("executor.compile_cache_misses") == misses


def test_bert_fused_auto_select_gated_off_under_tp_vocab_sharding():
    """With an active tp>1 mesh (whose rules vocab-shard mlm_head_w,
    bert.tp_sharding_rules P(None,'tp')), the fused-MLM-head AUTO-select
    stays dense — the chunked scan would force GSPMD to regather the
    sharded weight per chunk (ADVICE #2). Forcing fused_mlm_head=True
    still wins; a dp-only mesh leaves the auto-select on."""
    import jax
    from paddle_tpu.models import bert
    from paddle_tpu.parallel.mesh import build_mesh, get_mesh, set_mesh

    def head_ops(cfg):
        reset_programs(seed=0)
        bert.build_pretrain_program(cfg)
        return [op.type for op in fluid.default_main_program()
                .global_block().ops]

    cfg = bert.BertConfig(vocab_size=16384, hidden_size=16, num_layers=1,
                          num_heads=2, intermediate_size=32,
                          max_position=512, seq_len=512,
                          hidden_dropout=0.0, attention_dropout=0.0)
    old = get_mesh()
    try:
        set_mesh(build_mesh(tp=2, devices=jax.devices()[:2]))
        ops = head_ops(cfg)
        assert "fused_lm_head_ce" not in ops
        assert "softmax_with_cross_entropy" in ops
        cfg.fused_mlm_head = True               # explicit force wins
        assert "fused_lm_head_ce" in head_ops(cfg)
        cfg.fused_mlm_head = None
        set_mesh(build_mesh(dp=2, devices=jax.devices()[:2]))
        assert "fused_lm_head_ce" in head_ops(cfg)
    finally:
        set_mesh(old)


def test_tp_fused_head_build_then_init_warns():
    """The auto-gate reads the mesh at BUILD time, so the canonical
    build-then-fleet.init order slips past it; minimize must then warn
    that the auto-selected fused head will be regathered under the tp
    vocab-sharding rules (a user-FORCED fused head stays silent)."""
    import warnings as _warnings

    from paddle_tpu.distributed import fleet
    from paddle_tpu.models import bert
    from paddle_tpu.parallel.mesh import get_mesh, set_mesh

    old = get_mesh()
    try:
        set_mesh(None)                      # build BEFORE any mesh exists
        cfg = bert.BertConfig(vocab_size=16384, hidden_size=16,
                              num_layers=1, num_heads=2,
                              intermediate_size=32, max_position=512,
                              seq_len=512, hidden_dropout=0.0,
                              attention_dropout=0.0)

        def minimize(forced):
            reset_programs(seed=0)
            cfg.fused_mlm_head = True if forced else None
            ids, labels, loss = bert.build_pretrain_program(cfg)
            ops = [op.type for op in fluid.default_main_program()
                   .global_block().ops]
            assert "fused_lm_head_ce" in ops    # gate missed: no mesh yet
            fleet.init(is_collective=True)
            s = fleet.DistributedStrategy(
                tensor_parallel_degree=2,
                tensor_parallel_rules=bert.tp_sharding_rules())
            opt = fleet.distributed_optimizer(
                paddle.optimizer.SGD(learning_rate=0.1), s)
            with _warnings.catch_warnings(record=True) as caught:
                _warnings.simplefilter("always")
                opt.minimize(loss)
            return [w for w in caught
                    if "regather" in str(w.message)]

        assert minimize(forced=False), "auto-selected head must warn"
        assert not minimize(forced=True), "forced head must stay silent"
    finally:
        set_mesh(old)


@pytest.mark.slow
def test_tp_fused_head_collective_audit():
    """The collective evidence behind the tp auto-gate (ADVICE #2): with a
    vocab-sharded head weight (P(None,'tp')) and a MULTI-chunk fused head
    (chunk < V/shards), GSPMD regathers weight-sized data — all-gather
    bytes at least the full head weight — while the dense vocab-parallel
    head needs NO all-gather of the weight at all (small activation
    all-reduces only). Audited on optimized HLO through the public
    Executor.compiled_hlo. (At a single-chunk geometry, chunk >= V, the
    scan degenerates and GSPMD keeps the weight sharded — the auto-select
    thresholds guarantee >= 2 chunks, so the gate targets exactly the
    regathering regime.)"""
    import re

    import jax
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.parallel import DistConfig, attach, build_mesh
    from paddle_tpu.parallel.mesh import ShardingRules

    DT = {"f32": 4, "f64": 8, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
          "s32": 4, "u32": 4, "pred": 1, "s8": 1, "u8": 1}

    def all_gather_bytes(txt):
        total = 0
        for line in txt.splitlines():
            m = re.search(r"%\S+ = (.*?) all-gather(?:-start)?\(", line)
            if not m:
                continue
            for dm in re.finditer(r"(\w+)\[([\d,]*)\]", m.group(1)):
                dt, shape = dm.groups()
                n = 1
                for d in shape.split(","):
                    if d:
                        n *= int(d)
                total += n * DT.get(dt, 4)
        return total

    b, s, h, v = 4, 32, 32, 4096

    def compile_head(fused):
        reset_programs(seed=0)
        feat = layers.data(name="feat", shape=[s, h], dtype="float32")
        label = layers.data(name="label", shape=[s, 1], dtype="int64")
        w = layers.create_parameter([h, v], "float32", name="mlm_head_w")
        if fused:
            loss_tok = layers.fused_lm_head_ce(feat, w, label, chunk=512,
                                               w_layout="hv")
        else:
            logits = layers.matmul(feat, w)
            loss_tok = layers.softmax_with_cross_entropy(logits, label)
        loss = layers.mean(loss_tok)
        paddle.optimizer.SGD(learning_rate=0.1).minimize(loss)
        mesh = build_mesh(tp=2, devices=jax.devices()[:2])
        attach(fluid.default_main_program(),
               DistConfig(mesh=mesh, param_rules=ShardingRules(
                   [(r"^mlm_head_w$", P(None, "tp"))])))
        exe = fluid.Executor()
        exe.run(fluid.default_startup_program())
        feed = {"feat": np.zeros((b, s, h), np.float32),
                "label": np.zeros((b, s, 1), np.int64)}
        return exe.compiled_hlo(feed, [loss])

    w_bytes = h * v * 4
    fused_ag = all_gather_bytes(compile_head(True))
    dense_ag = all_gather_bytes(compile_head(False))
    assert fused_ag >= w_bytes, (fused_ag, w_bytes)     # the regather
    assert dense_ag < w_bytes, (dense_ag, w_bytes)      # the gated path
