"""Attention multiplies in the AMP compute dtype.

`fused_attention` is on the AMP white list (amp/auto_cast.py): under
`prog._amp` the executor casts the op's matmul operands `Q`, `K`, `V` and,
for its grad op, the cotangent `OG:Out` to bfloat16 at the op's boundary, on
every route, and leaves `Mask` and the residual `FO:Lse` float32
(`keep_f32_slots`). Pinned here with the shipped lists, on the CPU: what the
forward lowering and the `__vjp__` op see on the flash route (gate patched
open, kernels under the Pallas interpreter) and on the dense one, the
counter that says which dtype reached the kernels, the dense route's AMP
result against its float32 one, and the same lists under the dygraph tracer.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.observability import metrics
from paddle_tpu.ops import attention, registry
from paddle_tpu.testing import reset_programs

B, NH, S, HD = 2, 2, 128, 64
OPERANDS = ("attention.flash_operands_bf16", "attention.flash_operands_f32")


def _set_route(monkeypatch, route):
    monkeypatch.setattr(
        attention, "_use_pallas",
        (lambda q: q.shape[2] % 128 == 0) if route == "flash"
        else (lambda q: False))


def _one_op_program(mask, dropout=0.0):
    """Out and dq, dk, dv of sum(Out * W) through a one-op program fed
    float32; returns (program, feed, fetch list)."""
    reset_programs(0)
    rng = np.random.RandomState(5)
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
        lengths = np.array([S, S - 41])
        feed["mask"] = np.where(np.arange(S)[None] < lengths[:, None],
                                0.0, -1e9).astype(
                                    np.float32)[:, None, None, :]
        mask_var = layers.data(name="mask", shape=[1, 1, S], dtype="float32")
    out = layers.fused_attention(*qkv, mask=mask_var, dropout=dropout)
    loss = layers.reduce_sum(layers.elementwise_mul(out, w))
    grads = fluid.gradients(loss, qkv)
    return fluid.default_main_program(), feed, [out] + grads


def _spy_on_the_op(monkeypatch):
    """Record the dtype of every input slot the forward lowering and the
    `__vjp__` op of `fused_attention` are handed (after the AMP cast)."""
    seen = {"fwd": {}, "grad": {}}
    fwd, vjp = registry.get("fused_attention"), registry.get("__vjp__")
    fwd_lower, vjp_lower = fwd.lower, vjp.lower

    def dtypes(ins):
        return {slot: vals[0].dtype for slot, vals in ins.items()
                if vals and vals[0] is not None}

    def spy_fwd(ctx, ins, attrs):
        if not ctx.in_vjp and not ctx.is_eval_shape:
            seen["fwd"].update(dtypes(ins))
        return fwd_lower(ctx, ins, attrs)

    def spy_vjp(ctx, ins, attrs):
        if attrs["fwd_type"] == "fused_attention":
            seen["grad"].update(dtypes(ins))
        return vjp_lower(ctx, ins, attrs)

    monkeypatch.setattr(fwd, "lower", spy_fwd)
    monkeypatch.setattr(vjp, "lower", spy_vjp)
    return seen


@pytest.mark.parametrize("mask", [False, True], ids=["nomask", "keypad"])
@pytest.mark.parametrize("route", ["flash", "dense"])
def test_amp_casts_the_matmul_operands_and_nothing_else(monkeypatch, route,
                                                        mask):
    _set_route(monkeypatch, route)
    seen = _spy_on_the_op(monkeypatch)
    prog, feed, fetch = _one_op_program(mask, dropout=0.1)
    prog._amp = True
    before = [metrics.get(n) for n in OPERANDS]
    vals = fluid.Executor().run(prog, feed=feed, fetch_list=fetch)
    rise = tuple(int(metrics.get(n) - b) for n, b in zip(OPERANDS, before))
    bf16, f32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)
    want_fwd = {"Q": bf16, "K": bf16, "V": bf16}
    want_grad = dict(want_fwd, **{"OG:Out": bf16, "FO:Out": bf16,
                                  "FO:Lse": f32})
    if mask:
        want_fwd["Mask"] = want_grad["Mask"] = f32
    assert seen["fwd"] == want_fwd
    assert seen["grad"] == want_grad
    # once per flash forward lowered, by the dtype q arrived in
    assert rise == ((1, 0) if route == "flash" else (0, 0))
    for v in vals:
        v = np.asarray(v, np.float32)
        assert np.isfinite(v).all() and v.any()


def test_without_amp_the_operands_stay_float32(monkeypatch):
    _set_route(monkeypatch, "flash")
    seen = _spy_on_the_op(monkeypatch)
    prog, feed, fetch = _one_op_program(True)
    before = [metrics.get(n) for n in OPERANDS]
    fluid.Executor().run(prog, feed=feed, fetch_list=fetch)
    assert tuple(int(metrics.get(n) - b)
                 for n, b in zip(OPERANDS, before)) == (0, 1)
    assert set(seen["fwd"].values()) == {jnp.dtype(jnp.float32)}
    assert set(seen["grad"].values()) == {jnp.dtype(jnp.float32)}


def test_list_placement():
    from paddle_tpu.amp.auto_cast import (black_list, keep_f32_slots,
                                          white_list)
    assert "fused_attention" in white_list
    assert "fused_attention" not in black_list
    assert keep_f32_slots["fused_attention"] == {"Lse", "Mask"}
    opdef = registry.get("fused_attention")
    assert opdef.residual_slots == ("Out", "Lse")
    assert "Mask" in opdef.nondiff_slots


@pytest.mark.parametrize("mask", [False, True], ids=["nomask", "keypad"])
def test_dense_route_under_amp_is_the_float32_result_to_bf16(monkeypatch,
                                                             mask):
    """One rounding of q, k, v, Out and dOut to 8 bits of mantissa: XLA's
    default precision on a TPU multiplied float32 q, k in one bf16 pass
    already, the CPU did not, so the tolerance here covers more than the
    chip sees."""
    _set_route(monkeypatch, "dense")
    results = []
    for amp in (False, True):
        prog, feed, fetch = _one_op_program(mask)
        prog._amp = amp
        results.append([np.asarray(v, np.float32) for v in
                        fluid.Executor().run(prog, feed=feed,
                                             fetch_list=fetch)])
    for want, got, name in zip(*results, ("out", "dq", "dk", "dv")):
        err = np.abs(got - want).max() / np.abs(want).max()
        assert 0 < err < 3e-2, (name, err)


def test_dygraph_autocast_follows_the_same_lists():
    from paddle_tpu.amp.auto_cast import maybe_autocast_inputs
    from paddle_tpu.dygraph.tracer import Tensor
    paddle.disable_static()
    try:
        t = {slot: [Tensor(jnp.ones((1, 1, 8, 8), jnp.float32),
                           stop_gradient=slot == "Mask")]
             for slot in ("Q", "K", "V", "Mask")}
        cast = maybe_autocast_inputs("fused_attention", t, jnp.bfloat16)
    finally:
        paddle.enable_static()
    assert {s: v[0].value.dtype for s, v in cast.items()} == {
        "Q": jnp.bfloat16, "K": jnp.bfloat16, "V": jnp.bfloat16,
        "Mask": jnp.float32}
    assert cast["Mask"][0] is t["Mask"][0]
