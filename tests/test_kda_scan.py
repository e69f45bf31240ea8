"""The chunked gated delta rule (`ops/kda.py` `kda_scan`, the Kimi-delta
layer's core) and the ops beside it (`kda_gate`, `l2_norm`, `head_gate`), on
the CPU: the chunks against the token-by-token recurrence of the plain
reference (`benchmark/reference/ling3.py`), the grad rule on the chunk
states, the Pallas kernels of `ops/pallas/kda_chunk.py` under the interpreter
beside the `jax.numpy` form at every count of heads a grid step, in bf16 and
under stress of the solve and the decay bound, and the shape rule that sends
a call to one or the other. The model that runs them is
`tests/test_ling.py`'s.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from causal_lm_harness import cut_source_lines, run_op as _run_op, sha256

from paddle_tpu.observability import metrics
from paddle_tpu.ops import registry
from benchmark.reference import ling3 as ref

# what the reference's delta rule reads of a configuration
_REF_CFG = {"reference_scan_tokens_per_block": 8, "assumed": {}}


def _delta_operands(seed, b=2, s=128, h=3, dk=16, dv=16, power=0.3):
    """q, k L2-normed as the builder norms them; g in (-5, 0), most of it
    near the bound (`power` < 1 pushes the uniform draw towards 1)."""
    rng = np.random.RandomState(seed)
    unit = lambda t: t / np.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    return {"Q": unit(rng.randn(b, s, h, dk)) * dk ** -0.5,
            "K": unit(rng.randn(b, s, h, dk)), "V": rng.randn(b, s, h, dv),
            "G": -5.0 * rng.uniform(0, 1, (b, s, h, dk)) ** power,
            "Beta": rng.randn(b, s, h)}


def _recurrence(ins):
    """The reference's token-by-token delta rule on the op's operands."""
    q, k, v, g, raw = (jnp.asarray(ins[n], jnp.float32)
                       for n in ("Q", "K", "V", "G", "Beta"))
    return ref.delta_rule(q, k, v, g, jax.nn.sigmoid(raw),
                          _REF_CFG)


# widths the Pallas kernels' shape rule takes (`ops/pallas/kda_chunk.py`
# `plan`: heads of 128 x 128, chunks of 32, 64 or 128), three chunks, so
# that the carry and the reverse chain run. Here under the Pallas interpreter
_KERNEL_SHAPE = dict(b=1, s=192, h=2, dk=128, dv=128)
_ROUTES = ("kda.scan_pallas", "kda.scan_xla")


@pytest.mark.parametrize("chunk, shape", [
    (8, {}), (16, {}), (64, {}), (128, {}), (64, _KERNEL_SHAPE)],
    ids=["chunk8", "chunk16", "chunk64", "chunk128", "kernel"])
def test_chunked_delta_rule_is_the_recurrence_forward_and_backward(chunk,
                                                                   shape):
    """`kda_scan` in chunks of 8 (one block), 16, 64 (four blocks of 16, the
    cell's) and the whole row (the `jax.numpy` form) and at widths the
    Pallas kernels take, against the plain recurrence, the decays drawn down
    to the bound of -5 (the running sum reaches -300 inside a chunk of 64: a
    form that takes exp(-G) over a whole chunk reads inf): the output, and
    the gradient of every operand by the op's grad rule on the forward's
    residual (float32: the order of the sums). Each lowering counts its
    route, forward and backward."""
    ins = {k: jnp.asarray(v, jnp.float32)
           for k, v in _delta_operands(chunk, **shape).items()}
    assert float(ins["G"].min()) < -4.99
    opdef = registry.get("kda_scan")
    ctx = registry.LowerCtx(rng_key=jax.random.key(0))
    attrs = {"chunk_size": chunk, "lower_bound": -5.0}
    routes = [metrics.get(c) for c in _ROUTES]
    with jax.default_matmul_precision("highest"):
        outs = opdef.lower(ctx, {k: [v] for k, v in ins.items()}, attrs)
        want, vjp = jax.vjp(lambda t: _recurrence(t), ins)
        cot = jnp.asarray(np.random.RandomState(9).randn(*want.shape),
                          jnp.float32)
        before = metrics.get("kda.bwd_residual")
        grads = opdef.grad(ctx, {k: [v] for k, v in ins.items()}, attrs,
                           {s: outs[s] for s in opdef.residual_slots},
                           {"Y": [cot]})
        assert metrics.get("kda.bwd_residual") == before + 1
        # and differentiated by JAX (a segment under recompute): the same
        by_jax = jax.grad(lambda k: jnp.sum(opdef.lower(
            ctx, {**{n: [v] for n, v in ins.items()}, "K": [k]},
            attrs)["Y"][0] * cot))(ins["K"])
    assert [metrics.get(c) - r for c, r in zip(_ROUTES, routes)] \
        == ([4, 0] if shape else [0, 4])
    y = outs["Y"][0]
    b, s, h, dk = ins["Q"].shape
    assert outs["States"][0].shape == (b, s // chunk, h, dk, dk)
    assert bool(jnp.isfinite(y).all())
    assert float(jnp.abs(y - want).max() / jnp.abs(want).max()) < 5e-6
    for name, ref_grad in vjp(cot)[0].items():
        err = float(jnp.linalg.norm(grads[name][0] - ref_grad)
                    / jnp.linalg.norm(ref_grad))
        # the decay's gradient sums differences of running sums as long as
        # the chunk: float32 noise of 2e-5 at a chunk of 128
        assert err < 1e-4, (name, err)
    np.testing.assert_allclose(by_jax, grads["K"][0], rtol=1e-5, atol=1e-6)


def test_a_row_or_a_chunk_of_the_wrong_length_is_refused():
    ins = {k: [jnp.asarray(v, jnp.float32)]
           for k, v in _delta_operands(0, s=48).items()}
    opdef = registry.get("kda_scan")
    ctx = registry.LowerCtx(rng_key=jax.random.key(0))
    with pytest.raises(ValueError, match="whole number of chunks"):
        opdef.lower(ctx, ins, {"chunk_size": 32})
    with pytest.raises(ValueError, match="blocks of 16"):
        opdef.lower(ctx, ins, {"chunk_size": 24})
    with pytest.raises(ValueError, match="Beta"):
        opdef.lower(ctx, dict(ins, Beta=[ins["Beta"][0][:, :, :2]]),
                    {"chunk_size": 16})


@pytest.mark.parametrize("shape", [{}, _KERNEL_SHAPE], ids=["form", "kernel"])
def test_delta_rule_in_bf16_keeps_decay_and_states_float32(shape):
    """Under AMP q, k, v arrive in bf16: the output is bf16 and within
    bf16's rounding of the float32 result; the chunk states stay float32.
    By the `jax.numpy` form and by the Pallas kernels."""
    ins = _delta_operands(3, power=2.0, **shape)
    low = {k: jnp.asarray(v, jnp.bfloat16 if k in "QKV" else jnp.float32)
           for k, v in ins.items()}
    ctx = registry.LowerCtx(rng_key=jax.random.key(0))
    routes = [metrics.get(c) for c in _ROUTES]
    outs = registry.get("kda_scan").lower(
        ctx, {k: [v] for k, v in low.items()},
        {"chunk_size": 64, "lower_bound": -5.0})
    assert [metrics.get(c) - r for c, r in zip(_ROUTES, routes)] \
        == ([1, 0] if shape else [0, 1])
    want = _recurrence(ins)
    assert outs["Y"][0].dtype == jnp.bfloat16
    assert outs["States"][0].dtype == jnp.float32
    err = float(jnp.abs(outs["Y"][0].astype(jnp.float32) - want).max()
                / jnp.abs(want).max())
    assert err < 3e-2, err


def _kernel_operands(seed, chunks, h=4, dtype=jnp.float32, **changed):
    """(q, k, v, g, beta) as the kernels take them: one row of `chunks`
    chunks of 64, `h` heads of 128 x 128."""
    ins = dict(_delta_operands(seed, b=1, s=64 * chunks, h=h, dk=128, dv=128),
               **changed)
    return tuple(jnp.asarray(ins[n], dtype if n in "QKV" else jnp.float32)
                 for n in ("Q", "K", "V", "G")) \
        + (jax.nn.sigmoid(jnp.asarray(ins["Beta"], jnp.float32)),)


def _gaps(got, want):
    return [float(jnp.linalg.norm((g - w).astype(jnp.float32))
                  / jnp.linalg.norm(w.astype(jnp.float32)))
            for g, w in zip(got, want)]


_OUTPUTS = ("Y", "States", "dQ", "dK", "dV", "dG", "dBeta")


@pytest.mark.parametrize("chunks", [2, 3], ids=lambda c: f"{c}chunks")
@pytest.mark.parametrize("heads", [1, 2, 4], ids=lambda j: f"{j}heads")
def test_the_kernels_follow_the_form_at_every_count_of_heads_a_step(heads,
                                                                    chunks):
    """`ops/pallas/kda_chunk.py` at 1, 2 and all 4 heads a grid step, over
    2 and 3 chunks, beside the `jax.numpy` form on the same operands in
    float32: `Y`, `States` and the five gradients to float32's last digits
    (the order of a sum; the decay's gradient sums differences of running
    sums as long as the chunk, 2e-5 in either lowering)."""
    from paddle_tpu.ops import kda
    from paddle_tpu.ops.pallas import kda_chunk
    ops = _kernel_operands(10 * heads + chunks, chunks)
    plan = kda_chunk.plan(ops[0].shape, ops[2].shape, 64, jnp.float32,
                          heads=heads)
    assert plan[:5] == (heads, 128, 64, 4 // heads, chunks)
    cot = jnp.asarray(np.random.RandomState(9).randn(*ops[2].shape),
                      jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, states = kda._kda_fwd(64, *ops)
        want = (y, states) + kda._kda_bwd(64, *ops, states, cot)
        y, states = kda_chunk.kda_fwd(plan, *ops)
        got = (y, states) + kda_chunk.kda_bwd(plan, *ops, states, cot)
    for name, gap in zip(_OUTPUTS, _gaps(got, want)):
        assert gap < (1e-4 if name == "dG" else 5e-6), (name, gap)


@pytest.mark.parametrize("heads", [1, 4], ids=lambda j: f"{j}heads")
def test_the_kernels_in_bf16_stay_inside_the_forms_own_gap(heads):
    """bf16 rows: both lowerings round the same values to bf16 (the matmul
    operands) and keep the rest float32, so beside the float32 result on
    the same rounded rows the kernels' gap is the form's own, output by
    output (the decay's gradient, a difference of long sums, reads a tenth
    in either)."""
    from paddle_tpu.ops import kda
    from paddle_tpu.ops.pallas import kda_chunk
    low = _kernel_operands(5, 2, dtype=jnp.bfloat16)
    exact = tuple(t.astype(jnp.float32) for t in low)
    plan = kda_chunk.plan(low[0].shape, low[2].shape, 64, jnp.bfloat16,
                          heads=heads)
    cot = jnp.asarray(np.random.RandomState(9).randn(*low[2].shape),
                      jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        y, states = kda._kda_fwd(64, *exact)
        want = (y, states) + kda._kda_bwd(64, *exact, states,
                                          cot.astype(jnp.float32))
        y, states = kda._kda_fwd(64, *low)
        form = (y, states) + kda._kda_bwd(64, *low, states, cot)
        y, states = kda_chunk.kda_fwd(plan, *low)
        got = (y, states) + kda_chunk.kda_bwd(plan, *low, states, cot)
    assert got[0].dtype == jnp.bfloat16 and got[1].dtype == jnp.float32
    assert got[5].dtype == jnp.float32 and got[2].dtype == jnp.bfloat16
    for name, mine, its in zip(_OUTPUTS, _gaps(got, want), _gaps(form, want)):
        assert mine < 1.25 * its + 1e-4, (name, mine, its)
        assert mine < (0.3 if name == "dG" else 1e-2), (name, mine)


def _stress(case):
    """Operands made to stress the solve and the decay bound."""
    ins = _delta_operands(21, b=1, s=128, h=2, dk=128, dv=128)
    if case in ("equal_k", "beta_0.999"):
        # every k of a chunk equal and no decay: A is `beta` in every
        # entry under the diagonal, and (I + A)^-1 cancels powers of it
        ins["K"] = np.repeat(ins["K"][:, ::64], 64, axis=1)
        ins["G"] = np.zeros_like(ins["G"])
    if case == "beta_0.999":
        ins["Beta"] = np.full_like(ins["Beta"], np.log(0.999 / 0.001))
    if case == "g_floor":
        ins["G"] = np.full_like(ins["G"], -5.0)
    if case == "g_-4.5":
        ins["G"] = np.full_like(ins["G"], -4.5)
    if case == "g_zero":
        ins["G"] = np.zeros_like(ins["G"])
    return {k: jnp.asarray(v, jnp.float32) for k, v in ins.items()}


@pytest.mark.parametrize("case", ["equal_k", "beta_0.999", "g_floor",
                                  "g_-4.5", "g_zero"])
def test_the_kernels_solve_and_decay_bound_under_stress(case):
    """The in-kernel solve (substitution over the 16 x 16 diagonal blocks,
    the rest by products) where `A` is as far from small as it gets (every
    k of a chunk equal, beta 0.999: a plain Neumann doubling reads 1e10
    there), and the block-wise decayed products at the lower bound of g on
    every channel (`exp(80)` inside a block) and at no decay: the op at the
    kernels' widths against the token-by-token recurrence at the form's
    tolerance, output and gradients. At -5 on EVERY channel the last rows
    of a block are `x exp(-80)`, 1e-37 and under, where float32 runs out of
    exponent: the form itself reads 5.3e-3 against the recurrence there
    (4e-7 at -4.5), so that case holds the kernels to the form's digits
    (output, dQ, dK, dV) and the recurrence to its percent."""
    from paddle_tpu.ops import kda
    ins = _stress(case)
    opdef = registry.get("kda_scan")
    ctx = registry.LowerCtx(rng_key=jax.random.key(0))
    attrs = {"chunk_size": 64, "lower_bound": -5.0}
    routes = [metrics.get(c) for c in _ROUTES]
    with jax.default_matmul_precision("highest"):
        outs = opdef.lower(ctx, {k: [v] for k, v in ins.items()}, attrs)
        want, vjp = jax.vjp(lambda t: _recurrence(t), ins)
        cot = jnp.asarray(np.random.RandomState(9).randn(*want.shape),
                          jnp.float32)
        grads = opdef.grad(ctx, {k: [v] for k, v in ins.items()}, attrs,
                           {s: outs[s] for s in opdef.residual_slots},
                           {"Y": [cot]})
        want_grads = vjp(cot)[0]
        if case == "g_floor":
            y = outs["Y"][0]
            assert float(jnp.abs(y - want).max() / jnp.abs(want).max()) < 1e-2
            ops = tuple(ins[n] for n in ("Q", "K", "V", "G"))
            beta = jax.nn.sigmoid(ins["Beta"])
            want, states = kda._kda_fwd(64, *ops, beta)
            # the decay's own gradient there is float32 noise in either
            # lowering (the form's lies seven norms off the recurrence's)
            assert bool(jnp.isfinite(grads["G"][0]).all())
            want_grads = dict(zip(("Q", "K", "V"), kda._kda_bwd(
                64, *ops, beta, states, cot)))
    assert [metrics.get(c) - r for c, r in zip(_ROUTES, routes)] == [2, 0]
    y = outs["Y"][0]
    assert bool(jnp.isfinite(y).all())
    assert float(jnp.abs(y - want).max() / jnp.abs(want).max()) < 5e-6
    for name, ref_grad in want_grads.items():
        scale = float(jnp.linalg.norm(ref_grad))
        if scale == 0:
            assert float(jnp.abs(grads[name][0]).max()) == 0, name
            continue
        err = float(jnp.linalg.norm(grads[name][0] - ref_grad)) / scale
        assert err < 1e-4, (name, err)


def test_the_kernels_shape_rule_and_the_form_it_leaves(monkeypatch):
    """`ops/pallas/kda_chunk.py` `plan` reads the route from the operands'
    shapes and dtype and nothing else: K and V one lane tile, chunks of 32,
    64 or 128, bf16 or float32, the blocks inside the VMEM budget. What it
    leaves counts `kda.scan_xla` and lowers to the `jax.numpy` form as the
    tree before the kernels traced it (commit fa2014b, jax 0.9.0: the
    digest was made there, source lines cut)."""
    from paddle_tpu.ops.pallas import kda_chunk
    cell = kda_chunk.plan((1, 8192, 16, 128), (1, 8192, 16, 128), 64)
    assert cell[:5] == (4, 128, 64, 4, 128)
    assert cell.resident_bytes + (8 << 20) < 16 << 20
    for shape, chunk, dtype in (((2, 128, 3, 128), 64, jnp.float32),
                                ((1, 256, 2, 128), 128, jnp.bfloat16),
                                ((1, 96, 5, 128), 32, jnp.bfloat16)):
        assert kda_chunk.plan(shape, shape, chunk, dtype) is not None
    for q, v, chunk, dtype, why in (
            ((2, 32, 4, 16), (2, 32, 4, 16), 16, jnp.float32, "tiny preset"),
            ((1, 128, 2, 64), (1, 128, 2, 64), 64, jnp.bfloat16, "half tile"),
            ((1, 128, 2, 96), (1, 128, 2, 96), 64, jnp.bfloat16, "96 wide"),
            ((1, 128, 2, 128), (1, 128, 2, 64), 64, jnp.bfloat16, "V 64"),
            ((1, 128, 2, 128), (1, 128, 2, 128), 16, jnp.bfloat16,
             "chunks of 16"),
            ((1, 192, 2, 128), (1, 192, 2, 128), 48, jnp.bfloat16,
             "chunks of 48"),
            ((1, 128, 2, 128), (1, 128, 2, 128), 64, jnp.float16,
             "float16")):
        assert kda_chunk.plan(q, v, chunk, dtype) is None, why
    monkeypatch.setattr(kda_chunk, "VMEM_BUDGET", 1 << 20)
    assert kda_chunk.plan((1, 8192, 16, 128), (1, 8192, 16, 128), 64) is None

    opdef = registry.get("kda_scan")

    def step(q, k, v, g, beta, do):
        ctx = registry.LowerCtx(rng_key=None)
        ins = {"Q": [q], "K": [k], "V": [v], "G": [g], "Beta": [beta]}
        attrs = {"chunk_size": 64, "lower_bound": -5.0}
        outs = opdef.lower(ctx, ins, attrs)
        grads = opdef.grad(ctx, ins, attrs,
                           {s: outs[s] for s in opdef.residual_slots},
                           {"Y": [do]})
        return outs["Y"][0], [grads[s][0] for s in ins]

    def traced(width, dtype):
        rows = jax.ShapeDtypeStruct((1, 256, 2, width), dtype)
        routes = [metrics.get(c) for c in _ROUTES]
        # a new function each time: JAX keeps a traced one by its avals
        text = str(jax.make_jaxpr(lambda *a: step(*a))(
            rows, rows, rows,
            jax.ShapeDtypeStruct((1, 256, 2, width), jnp.float32),
            jax.ShapeDtypeStruct((1, 256, 2), jnp.float32), rows))
        return text, [metrics.get(c) - r for c, r in zip(_ROUTES, routes)]

    # over the budget (still patched), float16, and a head of 64
    for width, dtype in ((128, jnp.bfloat16), (128, jnp.float16),
                         (64, jnp.bfloat16)):
        text, rise = traced(width, dtype)
        assert rise == [0, 2], (width, dtype)
        assert "pallas_call" not in text and "triangular_solve" in text
    assert sha256(cut_source_lines(text, "kda")) == _FORM_DIGEST
    monkeypatch.undo()
    text, rise = traced(128, jnp.bfloat16)
    assert rise == [2, 0]
    assert "triangular_solve" not in text
    for name in ("kda-chunk-fwd", "kda-chunk-bwd"):
        assert re.search(rf"name={name}\s", text), name


_FORM_DIGEST = (
    "96cc656f1231dad9df940ed0fdce2b88748c3f45290b6aee7f3436e89b4e56b5")


def test_decay_gate_l2_norm_and_head_gate_ops():
    rng = np.random.RandomState(2)
    x = rng.randn(2, 5, 3 * 4).astype(np.float32) * 3
    a_log = np.log(rng.uniform(1, 16, 3)).astype(np.float32)
    dt_bias = rng.randn(12).astype(np.float32)
    g, = _run_op("kda_gate", {"X": x, "ALog": a_log, "DtBias": dt_bias},
                 ["G"], {"lower_bound": -5.0})
    pre = (x + dt_bias).reshape(2, 5, 3, 4) * np.exp(a_log)[:, None]
    np.testing.assert_allclose(g, -2.5 * (1 + np.tanh(pre / 2)), rtol=1e-5,
                               atol=1e-6)
    assert g.shape == (2, 5, 3, 4) and g.min() >= -5 and g.max() <= 0
    half, = _run_op("kda_gate", {"X": x.astype(jnp.bfloat16), "ALog": a_log,
                                 "DtBias": dt_bias}, ["G"],
                    {"lower_bound": -5.0})
    assert half.dtype == np.float32
    h = rng.randn(2, 5, 3, 4).astype(np.float32)
    y, = _run_op("l2_norm", {"X": h}, ["Out"], {"scale": 0.5})
    np.testing.assert_allclose(
        y, 0.5 * h / np.sqrt((h ** 2).sum(-1, keepdims=True) + 1e-6),
        rtol=1e-5)
    np.testing.assert_allclose(y, 0.5 * np.asarray(ref.l2_norm(h)), rtol=1e-6)
    gate = rng.randn(2, 5, 3).astype(np.float32)
    z, = _run_op("head_gate", {"X": h, "Gate": gate}, ["Out"], {})
    np.testing.assert_allclose(z, h / (1 + np.exp(-gate))[..., None],
                               rtol=1e-5)
    low, = _run_op("head_gate", {"X": h.astype(jnp.bfloat16), "Gate": gate},
                   ["Out"], {})
    assert low.dtype == jnp.bfloat16


