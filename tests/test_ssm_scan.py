"""The chunked selective scan (`ops/ssm.py` `ssm_scan`, the Mamba-2 mixer's
core) and the ops beside it in a state-space layer (`causal_conv1d`,
`gated_group_rms_norm`, `relu2`), on the CPU: the chunks against the
token-by-token recurrence of the plain reference
(`benchmark/reference/nemotron_h.py`), the grad rule on the chunk states, the
Pallas kernels of `ops/pallas/ssm_chunk.py` under the interpreter beside the
`jax.numpy` form, and the shape rule that sends a call to one or the other.
The model that runs them is `tests/test_nemotron_h.py`'s.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from causal_lm_harness import cut_source_lines, run_op as _run_op, sha256

from paddle_tpu.observability import metrics
from paddle_tpu.ops import registry, ssm
from benchmark.reference import nemotron_h as ref

# what the reference's scan reads of a configuration
_REF_CFG = {"reference_scan_tokens_per_block": 8, "assumed": {}}


def _scan_operands(seed, b=2, s=32, h=4, p=8, g=2, n=16):
    rng = np.random.RandomState(seed)
    return {"X": rng.randn(b, s, h, p), "B": rng.randn(b, s, g, n),
            "C": rng.randn(b, s, g, n), "Dt": rng.randn(b, s, h) - 1.0,
            "DtBias": 0.5 * rng.randn(h),
            "ALog": np.log(rng.uniform(1, 16, h)), "D": rng.randn(h)}


def _recurrence(ins):
    """The reference's token-by-token scan on the op's operands."""
    x, bm, cm, dt, dt_bias, a_log, d = (
        jnp.asarray(ins[k], jnp.float32)
        for k in ("X", "B", "C", "Dt", "DtBias", "ALog", "D"))
    return ref.selective_scan(
        x, bm, cm, jax.nn.softplus(dt + dt_bias), -jnp.exp(a_log), d,
        _REF_CFG)


# widths the Pallas kernels' shape rule takes (`ops/pallas/ssm_chunk.py`
# `plan`: state 128, a group's heads x features whole lane tiles, chunks of
# 128): three chunks, so that the carry and the reverse chain run, in two
# groups of two and of four heads. Here under the Pallas interpreter
_KERNEL_SHAPES = {"kernel-2x2": dict(b=2, s=384, h=4, p=64, g=2, n=128),
                  "kernel-2x4": dict(b=1, s=384, h=8, p=64, g=2, n=128)}
_ROUTES = ("ssm.scan_pallas", "ssm.scan_xla")


@pytest.mark.parametrize("chunk, shape", [
    (4, {}), (8, {}), (32, {})] + [(128, v) for v in _KERNEL_SHAPES.values()],
    ids=["chunk4", "chunk8", "chunk32"] + list(_KERNEL_SHAPES))
def test_chunked_scan_is_the_recurrence_forward_and_backward(chunk, shape):
    """`ssm_scan` in chunks of 4, 8 and the whole row (the `jax.numpy`
    form) and at widths the Pallas kernels take, against the plain
    recurrence: the output, and the gradient of every operand by the op's
    grad rule on the forward's residuals (float32: the order of the sums).
    Each lowering counts its route, forward and backward."""
    ins = {k: jnp.asarray(v, jnp.float32)
           for k, v in _scan_operands(chunk, **shape).items()}
    b, s, h, p = ins["X"].shape
    opdef = registry.get("ssm_scan")
    ctx = registry.LowerCtx(rng_key=jax.random.key(0))
    attrs = {"chunk_size": chunk}
    routes = [metrics.get(c) for c in _ROUTES]
    with jax.default_matmul_precision("highest"):
        outs = opdef.lower(ctx, {k: [v] for k, v in ins.items()}, attrs)
        want, vjp = jax.vjp(lambda t: _recurrence(t), ins)
        cot = jnp.asarray(np.random.RandomState(9).randn(*want.shape),
                          jnp.float32)
        before = metrics.get("ssm.bwd_residual")
        grads = opdef.grad(ctx, {k: [v] for k, v in ins.items()}, attrs,
                           {s: outs[s] for s in opdef.residual_slots},
                           {"Y": [cot]})
        assert metrics.get("ssm.bwd_residual") == before + 1
    assert [metrics.get(c) - r for c, r in zip(_ROUTES, routes)] \
        == ([2, 0] if shape else [0, 2])
    y = outs["Y"][0]
    assert outs["States"][0].shape == (b, s // chunk, h, p, ins["B"].shape[3])
    # a chunk of 128 positions: running sums down to -600, and the
    # `jax.numpy` form itself reads 7.4e-6 on these operands
    assert float(jnp.abs(y - want).max() / jnp.abs(want).max()) \
        < (1e-5 if shape else 2e-6)
    for name, ref_grad in vjp(cot)[0].items():
        got = grads[name][0]
        err = float(jnp.linalg.norm(got - ref_grad)
                    / jnp.linalg.norm(ref_grad))
        # A_log's gradient sums differences of running sums as long as the
        # chunk: float32 noise of 4e-5 at a chunk of 32, 6e-5 at 128
        assert err < 1e-4, (name, err)
    if shape:
        _kernels_follow_the_form(chunk, ins, outs, cot, grads)
    # and differentiated by JAX (a segment under recompute): the same, by
    # the same two lowerings
    by_jax = jax.grad(lambda x: jnp.sum(opdef.lower(
        ctx, {**{k: [v] for k, v in ins.items()}, "X": [x]},
        attrs)["Y"][0] * cot))(ins["X"])
    np.testing.assert_allclose(by_jax, grads["X"][0], rtol=1e-5, atol=1e-6)
    assert [metrics.get(c) - r for c, r in zip(_ROUTES, routes)] \
        == ([4, 0] if shape else [0, 4])


def _kernels_follow_the_form(chunk, ins, outs, cot, grads):
    """The kernels' results beside the `jax.numpy` form's on the same
    operands, which the kernels follow line for line: float32's last
    digits, the order of a sum over heads or positions."""
    x, bm, cm, d = (ins[k] for k in ("X", "B", "C", "D"))
    dt, cum = outs["DtSoft"][0], outs["CumA"][0]
    with jax.default_matmul_precision("highest"):
        y, states = ssm._ssd_fwd(chunk, x, bm, cm, dt, cum, d)
        form = ssm._ssd_bwd(chunk, x, bm, cm, dt, cum, d, states, cot)
        _, decays_vjp = jax.vjp(lambda *a: ssm._decays(*a, chunk),
                                ins["Dt"], ins["DtBias"], ins["ALog"])
        form = dict(zip(("X", "B", "C", "D", "Dt", "DtBias", "ALog"),
                        form[:3] + form[5:] + decays_vjp(form[3:5])))
    np.testing.assert_allclose(outs["States"][0], states, rtol=1e-6,
                               atol=1e-6 * float(jnp.abs(states).max()))
    assert float(jnp.abs(outs["Y"][0] - y).max() / jnp.abs(y).max()) < 5e-7
    for name, want in form.items():
        err = float(jnp.linalg.norm(grads[name][0] - want)
                    / jnp.linalg.norm(want))
        assert err < 2e-5, (name, err)


def test_a_row_that_is_no_whole_number_of_chunks_is_refused():
    ins = _scan_operands(0, s=30)
    with pytest.raises(ValueError, match="whole number of chunks"):
        _run_op("ssm_scan", ins, ["Y"], {"chunk_size": 8})
    with pytest.raises(ValueError, match="heads"):
        _run_op("ssm_scan", dict(_scan_operands(0), B=ins["B"][:, :, :1]
                                 .repeat(3, 2)[:, :30]), ["Y"],
                {"chunk_size": 8})


@pytest.mark.parametrize("chunk, shape", [
    (8, {}), (128, _KERNEL_SHAPES["kernel-2x2"])], ids=["form", "kernel"])
def test_scan_in_bf16_keeps_decays_and_states_float32(chunk, shape):
    """Under AMP the operands X, B, C arrive in bf16: the output is bf16 and
    within bf16's rounding of the float32 result; what the forward writes
    for the backward stays float32. By the `jax.numpy` form and by the
    Pallas kernels, which round the same values at the same places: beside
    the form on the same operands the kernel's output differs by a last bf16
    digit here and there, its states by float32's."""
    ins = _scan_operands(3, **shape)
    low = {k: jnp.asarray(v, jnp.bfloat16 if k in "XBC" else jnp.float32)
           for k, v in ins.items()}
    ctx = registry.LowerCtx(rng_key=jax.random.key(0))
    routes = [metrics.get(c) for c in _ROUTES]
    outs = registry.get("ssm_scan").lower(
        ctx, {k: [v] for k, v in low.items()}, {"chunk_size": chunk})
    assert [metrics.get(c) - r for c, r in zip(_ROUTES, routes)] \
        == ([1, 0] if shape else [0, 1])
    want = _recurrence(ins)
    assert outs["Y"][0].dtype == jnp.bfloat16
    assert all(outs[s][0].dtype == jnp.float32
               for s in ("States", "DtSoft", "CumA"))
    err = float(jnp.abs(outs["Y"][0].astype(jnp.float32) - want).max()
                / jnp.abs(want).max())
    assert err < 2e-2, err
    if shape:
        y, states = ssm._ssd_fwd(chunk, low["X"], low["B"], low["C"],
                                 outs["DtSoft"][0], outs["CumA"][0], low["D"])
        top = float(jnp.abs(y.astype(jnp.float32)).max())
        gap = jnp.abs(outs["Y"][0].astype(jnp.float32)
                      - y.astype(jnp.float32))
        assert float(gap.max()) <= 2 ** -7 * top
        assert float(jnp.mean(gap > 0)) < 0.01
        np.testing.assert_allclose(outs["States"][0], states, rtol=1e-5,
                                   atol=1e-6 * float(jnp.abs(states).max()))


def test_the_kernels_shape_rule_and_the_form_it_leaves():
    """`ops/pallas/ssm_chunk.py` `plan` reads the route from the operands'
    shapes and nothing else: state width and a group's heads x features
    whole lane tiles, chunks a multiple of 128. What it leaves counts
    `ssm.scan_xla` and lowers to the `jax.numpy` form as the tree before
    the kernels traced it (commit d302be6, jax 0.9.0: the digest was made
    there, source lines cut)."""
    from paddle_tpu.ops.pallas import ssm_chunk
    cell = ssm_chunk.plan((1, 8192, 64, 64), (1, 8192, 8, 128), 128)
    assert cell[:7] == (8, 64, 128, 128, 8, 1, 64)
    assert cell.resident_bytes + (8 << 20) < 16 << 20
    for x, bm, chunk in (((2, 384, 4, 64), (2, 384, 2, 128), 128),
                         ((1, 512, 2, 128), (1, 512, 1, 256), 256),
                         ((1, 256, 32, 16), (1, 256, 4, 128), 128)):
        assert ssm_chunk.plan(x, bm, chunk, 4) is not None, (x, bm, chunk)
    for x, bm, chunk, why in (
            ((2, 32, 4, 8), (2, 32, 2, 16), 8, "the tiny preset"),
            ((1, 384, 4, 64), (1, 384, 2, 64), 128, "state under a tile"),
            ((1, 384, 4, 64), (1, 384, 2, 192), 128, "state 1.5 tiles"),
            ((1, 384, 4, 48), (1, 384, 2, 128), 128, "96 features a group"),
            ((1, 384, 4, 64), (1, 384, 2, 128), 64, "chunks of 64"),
            ((1, 384, 4, 64), (1, 384, 2, 128), 192, "chunks of 192"),
            ((1, 16384, 64, 512), (1, 16384, 1, 128), 128, "VMEM")):
        assert ssm_chunk.plan(x, bm, chunk) is None, why

    opdef = registry.get("ssm_scan")

    def step(x, bm, cm, dt, dt_bias, a_log, d, dy):
        ctx = registry.LowerCtx(rng_key=None)
        ins = {"X": [x], "B": [bm], "C": [cm], "Dt": [dt],
               "DtBias": [dt_bias], "ALog": [a_log], "D": [d]}
        attrs = {"chunk_size": 64}
        outs = opdef.lower(ctx, ins, attrs)
        grads = opdef.grad(ctx, ins, attrs,
                           {s: outs[s] for s in opdef.residual_slots},
                           {"Y": [dy]})
        return outs["Y"][0], [grads[s][0] for s in ins]

    def sd(*shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt)

    bf = jnp.bfloat16
    routes = [metrics.get(c) for c in _ROUTES]
    text = str(jax.make_jaxpr(step)(
        sd(1, 256, 4, 64, dt=bf), sd(1, 256, 2, 128, dt=bf),
        sd(1, 256, 2, 128, dt=bf), sd(1, 256, 4), sd(4), sd(4), sd(4),
        sd(1, 256, 4, 64, dt=bf)))
    assert [metrics.get(c) - r for c, r in zip(_ROUTES, routes)] == [0, 2]
    assert "pallas_call" not in text
    assert sha256(cut_source_lines(text, "ssm")) == _FORM_DIGEST


_FORM_DIGEST = (
    "1375f04793a9e6b096efdf47f28b6b2e03140aa247f07b6da2aa50ae34df3e7a")


def test_causal_conv_and_gated_group_norm_ops():
    rng = np.random.RandomState(2)
    x = rng.randn(2, 9, 6).astype(np.float32)
    w, b = rng.randn(4, 6).astype(np.float32), rng.randn(6).astype(np.float32)
    out, = _run_op("causal_conv1d", {"X": x, "W": w, "Bias": b}, ["Out"],
                   {"activation": "silu"})
    want = np.zeros_like(x)
    for t in range(9):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += w[j] * x[:, t - 3 + j]
    want = want + b
    want = want / (1 + np.exp(-want))
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        out, np.asarray(jax.nn.silu(ref.causal_conv(x, w, b))), rtol=1e-5,
        atol=1e-6)
    with pytest.raises(ValueError, match="activation"):
        _run_op("causal_conv1d", {"X": x, "W": w}, ["Out"],
                {"activation": "gelu"})
    gate, scale = rng.randn(2, 9, 6).astype(np.float32), rng.rand(6) + 0.5
    y, = _run_op("gated_group_rms_norm",
                 {"X": x, "Gate": gate, "Scale": scale.astype(np.float32)},
                 ["Y"], {"groups": 3, "epsilon": 1e-5})
    v = (x * gate / (1 + np.exp(-gate))).reshape(2, 9, 3, 2)
    v = v / np.sqrt((v ** 2).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(y, v.reshape(2, 9, 6) * scale, rtol=1e-5,
                               atol=1e-6)
    half, = _run_op("gated_group_rms_norm",
                    {"X": x.astype(jnp.bfloat16),
                     "Gate": gate.astype(jnp.bfloat16)}, ["Y"],
                    {"groups": 3})
    assert half.dtype == jnp.bfloat16
    r, = _run_op("relu2", {"X": x}, ["Out"], {})
    np.testing.assert_allclose(r, np.maximum(x, 0) ** 2, rtol=1e-6)


