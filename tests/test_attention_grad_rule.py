"""The attention backward on the flash route takes the forward's residuals.

`fused_attention` declares a grad rule (ops/attention.py): where the forward
launched the flash kernel, the `__vjp__` op reads the `Out` and `Lse` that
launch wrote and runs the two backward kernels alone, instead of lowering
the forward a second time. Pinned here, on the CPU with the kernels under
the Pallas interpreter and the static gate patched open: one forward kernel
per layer in the traced step, the same dq/dk/dv bit for bit as the generic
route, and the routes that must not change (dense, recompute, layer scan).
"""
import collections
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.observability import metrics
from paddle_tpu.ops import attention, registry
from paddle_tpu.testing import reset_programs

B, NH, S, HD = 2, 2, 128, 64
COUNTERS = ("attention.flash_bwd_residual", "attention.flash_bwd_recomputed")


@pytest.fixture
def open_gate(monkeypatch):
    """The flash route on a CPU backend: the gate's shape test alone."""
    monkeypatch.setattr(
        attention, "_use_pallas",
        lambda q: q.shape[2] % 128 == 0 and q.shape[3] in (64, 128, 256))


def kernel_calls(jaxpr):
    """{kernel name: count} over every pallas_call of a jaxpr, nested
    jaxprs (scan bodies, remat, custom_vjp calls) included."""
    counts = collections.Counter()

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                counts[str(eqn.params["name"])] += 1
            for val in eqn.params.values():
                for sub in (val if isinstance(val, (list, tuple)) else [val]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jaxpr.jaxpr)
    return dict(counts)


def counter_rise(fn):
    before = [metrics.get(n) for n in COUNTERS]
    out = fn()
    return out, tuple(int(metrics.get(n) - b)
                      for n, b in zip(COUNTERS, before))


def attention_grads(mask, dropout, causal, dtype, use_rule):
    """dq, dk, dv of sum(Out * W) through a one-op program, by the grad
    rule or (`use_rule` False) by the generic `__vjp__` route."""
    reset_programs(0)
    rng = np.random.RandomState(7)
    feed = {n: rng.randn(B, NH, S, HD).astype(np.float32)
            for n in ("q", "k", "v", "w")}
    qkv = []
    for n in ("q", "k", "v"):
        var = layers.data(name=n, shape=[NH, S, HD], dtype="float32")
        var.stop_gradient = False
        qkv.append(var)
    w = layers.data(name="w", shape=[NH, S, HD], dtype="float32")
    mask_var = None
    if mask:
        lengths = np.array([S, S - 37])
        feed["mask"] = np.where(np.arange(S)[None] < lengths[:, None],
                                0.0, -1e9).astype(
                                    np.float32)[:, None, None, :]
        mask_var = layers.data(name="mask", shape=[1, 1, S], dtype="float32")
    out = layers.fused_attention(*qkv, mask=mask_var, dropout=dropout,
                                 causal=causal)
    loss = layers.reduce_sum(layers.elementwise_mul(out, w))
    grads = fluid.gradients(loss, qkv)
    prog = fluid.default_main_program()
    vjp = [op for op in prog.global_block().ops
           if op.type == "__vjp__"
           and op.attrs["fwd_type"] == "fused_attention"]
    assert len(vjp) == 1 and set(vjp[0].inputs) >= {"FO:Out", "FO:Lse"}
    if dtype != "float32":
        # AMP (the op is white-listed): the executor casts its inputs, and
        # its grad op's, to bfloat16 (layers.cast would stop the gradient)
        prog._amp = True
    opdef = registry.get("fused_attention")
    rule = opdef.grad
    seen = {}

    def spy(ctx, ins, attrs, outs, ogs):
        seen.update(lse=outs["Lse"][0].dtype, out=outs["Out"][0].dtype,
                    q=ins["Q"][0].dtype)
        return rule(ctx, ins, attrs, outs, ogs)

    opdef.grad = spy if use_rule else None
    try:
        exe = fluid.Executor()
        vals, rise = counter_rise(
            lambda: exe.run(prog, feed=feed, fetch_list=grads))
    finally:
        opdef.grad = rule
    return [np.asarray(v) for v in vals], rise, seen


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dropout", [0.0, 0.1])
@pytest.mark.parametrize("mask", [False, True], ids=["nomask", "keypad"])
def test_rule_gives_the_generic_routes_grads_bit_for_bit(
        open_gate, mask, dropout, causal, dtype):
    got, rise, seen = attention_grads(mask, dropout, causal, dtype, True)
    want, rise_generic, _ = attention_grads(mask, dropout, causal, dtype,
                                            False)
    assert rise == (1, 0) and rise_generic == (0, 1)
    assert seen["lse"] == jnp.float32
    assert seen["out"] == seen["q"] == jnp.dtype(dtype)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert np.isfinite(a).all() and np.abs(a).max() > 0, name
        np.testing.assert_array_equal(a, b, err_msg=name)


def bert_trainer(seq_len, strategy=None):
    """A 2-layer BERT train program through fleet, padded batch and dropout
    0.1 as the benchmark builds it; returns (exe, loss, feed)."""
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models import bert
    reset_programs(0)
    cfg = bert.BertConfig(vocab_size=128, hidden_size=NH * HD,
                          num_layers=2, num_heads=NH,
                          intermediate_size=64, max_position=seq_len,
                          seq_len=seq_len, hidden_dropout=0.1,
                          attention_dropout=0.1)
    _, _, loss = bert.build_pretrain_program(cfg, use_input_mask=True)
    fleet.init(is_collective=True)
    s = fleet.DistributedStrategy()
    s.amp = True
    for key, val in (strategy or {}).items():
        setattr(s, key, val(loss) if callable(val) else val)
    fleet.distributed_optimizer(
        paddle.optimizer.Adam(learning_rate=1e-3), s).minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(3)
    feed = {"input_ids": rng.randint(0, cfg.vocab_size,
                                     (B, seq_len)).astype(np.int64),
            "mlm_labels": rng.randint(0, cfg.vocab_size,
                                      (B, seq_len, 1)).astype(np.int64),
            "input_mask": (np.arange(seq_len)[None]
                           < np.array([[seq_len], [seq_len - 19]])
                           ).astype(np.float32)}
    return exe, loss, feed


def test_train_step_holds_one_forward_kernel_per_layer(open_gate):
    exe, loss, feed = bert_trainer(S)
    jaxpr, rise = counter_rise(lambda: exe.step_jaxpr(feed, [loss]))
    assert kernel_calls(jaxpr) == {"flash_attention_fwd": 2,
                                   "flash_attention_bwd_dq": 2,
                                   "flash_attention_bwd_dkdv": 2}
    assert rise == (2, 0)
    # and the generic route, for the same program: the forward twice a layer
    opdef = registry.get("fused_attention")
    rule, opdef.grad = opdef.grad, None
    try:
        exe.close()
        jaxpr, rise = counter_rise(lambda: exe.step_jaxpr(feed, [loss]))
    finally:
        opdef.grad = rule
    assert kernel_calls(jaxpr)["flash_attention_fwd"] == 4
    assert rise == (0, 2)


def _hlo_shapes(hlo_text):
    return set(re.findall(r"\b(?:f32|bf16)\[[\d,]*\]", hlo_text))


def test_dense_route_is_the_program_it_was():
    """The bypass bypasses: at a shape the gate refuses (and on any backend
    but a TPU) the rule declines, the step is the generic route's step
    equation for equation, no counter moves, and the empty `Lse`
    placeholder leaves nothing in the compiled program."""
    exe, loss, feed = bert_trainer(32)
    jaxpr, rise = counter_rise(lambda: exe.step_jaxpr(feed, [loss]))
    hlo = exe.compiled_hlo(feed, [loss])
    assert rise == (0, 0) and kernel_calls(jaxpr) == {}
    opdef = registry.get("fused_attention")
    rule, opdef.grad = opdef.grad, None
    try:
        exe.close()
        generic = exe.step_jaxpr(feed, [loss])
    finally:
        opdef.grad = rule
    assert str(jaxpr) == str(generic)
    # the empty placeholder (and anything lane-broadcast) is gone after DCE;
    # a [B, nh, S] float32 is no sign of a residual here, the dense softmax
    # has row statistics of that shape
    lse_like = {f"f32[{B},{NH},0]", f"f32[{B},{NH},32,128]",
                f"f32[{B * NH},32,128]"}
    assert not (lse_like & _hlo_shapes(hlo))
    out, = exe.run(feed=feed, fetch_list=[loss])
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("knob", ["recompute", "layer_scan"])
def test_segment_lowerings_keep_recomputing_and_say_so(open_gate, knob):
    """A step that differentiates a whole segment with JAX still trains.
    A layer scan is lowered a second time by the generic `__vjp__`, and
    `attention.flash_bwd_recomputed` shows it; a recomputed `__segment__`
    is lowered once (it differentiates itself there and keeps the forward
    kernel's `Out` and `Lse`: tests/test_recompute_keep.py), so neither
    counter moves."""
    strategy = {"recompute": True,
                "recompute_configs": lambda loss: {
                    "checkpoints": list(loss._layer_checkpoints)}}
    if knob == "layer_scan":
        strategy = {"layer_scan": True}
    exe, loss, feed = bert_trainer(S, strategy=strategy)

    def two_steps():
        return [float(np.asarray(exe.run(feed=feed, fetch_list=[loss])[0]))
                for _ in range(2)]

    losses, rise = counter_rise(two_steps)
    assert np.isfinite(losses).all() and losses[1] < losses[0]
    assert rise[0] == 0 and (rise[1] >= 1) == (knob == "layer_scan")


# ---------------------------------------------------------------------------
# the attr `layout` (PR 52): a "bshd" op hands the flash kernels its inputs
# as they lie where they can index them, and transposes inside itself
# everywhere else
# ---------------------------------------------------------------------------

LAYOUT_COUNTERS = COUNTERS + ("attention.flash_layout_rows",
                              "attention.flash_layout_heads")


def layout_grads(nh, nkv, dqk, dv, layout, use_rule=True, causal=True,
                 dropout=0.0):
    """(out, dq, dk, dv) head-major and the rise of `LAYOUT_COUNTERS`, of
    sum(Out * W) through a one-op program whose inputs arrive in `layout`
    (the same numbers either way: fed head-major arrays are transposed on
    the host for "bshd")."""
    reset_programs(0)
    rng = np.random.RandomState(5)
    widths = {"q": (nh, dqk), "k": (nkv, dqk), "v": (nkv, dv),
              "w": (nh, dv)}
    rows = layout == "bshd"

    def lay(a):
        return np.ascontiguousarray(a.swapaxes(1, 2)) if rows else a

    feed, qkv = {}, []
    for n, (heads, width) in widths.items():
        feed[n] = lay(rng.randn(B, heads, S, width).astype(np.float32))
        var = layers.data(name=n, shape=list(feed[n].shape[1:]),
                          dtype="float32")
        var.stop_gradient = n == "w"
        qkv.append(var)
    w = qkv.pop()
    out = layers.fused_attention(*qkv, causal=causal, dropout=dropout,
                                 scale=dqk ** -0.5, layout=layout)
    assert tuple(out.shape)[1:] == feed["w"].shape[1:]
    loss = layers.reduce_sum(layers.elementwise_mul(out, w))
    grads = fluid.gradients(loss, qkv)
    opdef = registry.get("fused_attention")
    rule = opdef.grad
    opdef.grad = rule if use_rule else None
    before = [metrics.get(n) for n in LAYOUT_COUNTERS]
    try:
        vals = fluid.Executor().run(fluid.default_main_program(), feed=feed,
                                    fetch_list=[out] + grads)
    finally:
        opdef.grad = rule
    rise = tuple(int(metrics.get(n) - b)
                 for n, b in zip(LAYOUT_COUNTERS, before))
    return [lay(np.asarray(v)) for v in vals], rise


@pytest.fixture
def wide_gate(monkeypatch):
    monkeypatch.setattr(
        attention, "_use_pallas",
        lambda q: q.shape[2] % 128 == 0 and q.shape[3] in (64, 128, 192))


@pytest.mark.parametrize("nh, nkv, dqk, dv, reads_rows", [
    (4, 4, 64, 64, True),       # BERT's kind: 64 wide, in pairs
    (4, 2, 128, 128, True),     # grouped KV heads of 128
    (2, 1, 128, 128, True),
    # (iv) what the kernels cannot index falls back INSIDE the op
    (3, 3, 64, 64, False),      # an odd head count
    (4, 2, 64, 64, False),      # 64 wide on grouped KV heads
    (2, 2, 192, 128, False),    # latent attention's two widths
], ids=["pairs", "4on2-128", "2on1-128", "odd-heads", "4on2-64", "192-128"])
@pytest.mark.parametrize("dropout", [0.0, 0.1])
def test_a_bshd_op_takes_the_forwards_route_in_its_grad_rule(
        wide_gate, nh, nkv, dqk, dv, reads_rows, dropout):
    """The forward and the grad rule read ONE static decision: where the
    kernels take the rows, both launch them in layout "bshd" (counter
    `flash_layout_rows`, the backward on the forward's residuals), else
    both run what a "bhsd" op runs behind transposes of the op's own
    (`flash_layout_heads`) and give its numbers bit for bit. Either way
    the rule's gradients are the generic route's, which differentiates
    the forward's own lowering."""
    want, rise_heads = layout_grads(nh, nkv, dqk, dv, "bhsd",
                                    dropout=dropout)
    got, rise = layout_grads(nh, nkv, dqk, dv, "bshd", dropout=dropout)
    generic, rise_generic = layout_grads(nh, nkv, dqk, dv, "bshd",
                                         use_rule=False, dropout=dropout)
    assert rise_heads == (1, 0, 0, 1)
    assert rise == ((1, 0, 1, 0) if reads_rows else (1, 0, 0, 1))
    # the generic route lowers the forward a second time to differentiate it
    assert rise_generic == ((0, 1, 2, 0) if reads_rows else (0, 1, 0, 2))
    pair = reads_rows and dqk == 64
    for name, a, b, c in zip(("out", "dq", "dk", "dv"), got, want, generic):
        assert np.isfinite(a).all() and np.abs(a).max() > 0, name
        np.testing.assert_array_equal(a, c, err_msg=name)
        if pair and name in ("dk", "dv"):
            # a pair sums its stacked rows in another order
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5,
                                       err_msg=name)
        else:
            np.testing.assert_array_equal(a, b, err_msg=name)


def test_a_bshd_op_on_the_dense_route_gives_the_bhsd_ops_numbers():
    """Off the flash route (here: any backend but a TPU) a "bshd" op is
    the dense lowering behind its own transposes: no kernel, no counter,
    the rule declines, and the numbers are the "bhsd" op's."""
    want, rise_heads = layout_grads(4, 2, 64, 64, "bhsd")
    got, rise = layout_grads(4, 2, 64, 64, "bshd")
    assert rise == rise_heads == (0, 0, 0, 0)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6, err_msg=name)


def test_the_layout_counters_of_a_bert_trace(open_gate):
    """One count a flash forward lowered, by what the kernels read: BERT's
    encoder passes "bshd" and its 64-wide heads go in pairs (+layers / +0,
    +12 / +0 in the s512 cell's trace); a builder that transposes itself
    (latent attention; kanana's +5 flash layers) counts under heads
    (tests/test_ling.py holds that builder's trace where it was)."""
    names = LAYOUT_COUNTERS[2:]
    before = [metrics.get(n) for n in names]
    exe, loss, feed = bert_trainer(S)
    exe.step_jaxpr(feed, [loss])
    assert [int(metrics.get(n) - b) for n, b in zip(names, before)] == [2, 0]
    # the same trace at a sequence the gate refuses: the dense route, none
    before = [metrics.get(n) for n in names]
    exe, loss, feed = bert_trainer(32)
    exe.step_jaxpr(feed, [loss])
    assert [int(metrics.get(n) - b) for n, b in zip(names, before)] == [0, 0]


def test_the_layout_counters_of_a_latent_attention_trace(monkeypatch):
    """kanana's builder (`deepseek_v3.latent_attention`: q and k wider than
    v) keeps its own transposes and passes the default layout: 0 rows / +n
    heads for its n flash layers, whatever the widths."""
    import dataclasses
    import causal_lm_harness as harness
    from paddle_tpu.models import deepseek_v3
    monkeypatch.setattr(attention, "_use_pallas",
                        lambda q: q.shape[2] % 128 == 0)
    cfg = dataclasses.replace(deepseek_v3.DeepseekV3Config.tiny(),
                              seq_len=128, qk_nope_head_dim=120,
                              v_head_dim=64)        # 128 and 64 wide
    exe, loss, ids = harness.amp_step(deepseek_v3, cfg)
    names = LAYOUT_COUNTERS[2:]
    before = [metrics.get(n) for n in names]
    exe.step_jaxpr({"tokens": ids}, [loss], k=2)
    assert [int(metrics.get(n) - b) for n, b in zip(names, before)] == [
        0, cfg.num_hidden_layers]
