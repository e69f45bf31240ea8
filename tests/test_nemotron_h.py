"""The Nemotron-H-family hybrid LM (`models/nemotron_h.py`: a Mamba-2
state-space mixer, ungated relu^2 experts with a shared one, or attention on
grouped KV heads without rotary positions a layer, by a pattern string; one
expert-parallel rank's share) against its plain float32 reference
(`benchmark/reference/nemotron_h.py`), on the CPU at tiny widths with seeded
weights; and what the model forced on the ops: the chunked selective scan
(`ops/ssm.py`) against the token-by-token recurrence, its grad rule on the
chunk states, the causal conv and the gated grouped norm, and `routed_moe`
with experts that have no gate.
"""
import hashlib
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.fluid as fluid  # noqa: E402
from paddle_tpu.distributed import fleet  # noqa: E402
from paddle_tpu.fluid import layers  # noqa: E402
from paddle_tpu.models import nemotron_h  # noqa: E402
from paddle_tpu.observability import metrics  # noqa: E402
from paddle_tpu.ops import moe, registry, ssm  # noqa: E402
from paddle_tpu.testing import reset_programs  # noqa: E402
from benchmark.reference import nemotron_h as ref  # noqa: E402

S, B = 32, 4
CFG = dict(hidden_size=64, hybrid_override_pattern="MEMEM*EME", layers=9,
           num_hidden_layers=52, rescale_prenorm_residual=True,
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           mamba_num_heads=8, mamba_head_dim=8, n_groups=2,
           ssm_state_size=16, conv_kernel=4, chunk_size=8,
           moe_intermediate_size=32, moe_shared_expert_intermediate_size=64,
           n_routed_experts=4, experts_total=8, expert_offset=2,
           num_experts_per_tok=2, routed_scaling_factor=2.5,
           norm_topk_prob=True, layer_norm_epsilon=1e-5,
           time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4,
           vocab=256, reference_scan_tokens_per_block=8,
           assumed={"initializer_std": 0.02, "select_bias_std": 0.03})
SHARED = ("hidden_size", "hybrid_override_pattern", "num_attention_heads",
          "num_key_value_heads", "head_dim", "mamba_num_heads",
          "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel",
          "chunk_size", "moe_intermediate_size",
          "moe_shared_expert_intermediate_size", "num_experts_per_tok",
          "routed_scaling_factor", "norm_topk_prob", "layer_norm_epsilon")


def model_config(cfg):
    return nemotron_h.NemotronHConfig(
        vocab_size=cfg["vocab"], num_hidden_layers=cfg["layers"],
        n_routed_experts=cfg["experts_total"],
        experts_held=cfg["n_routed_experts"],
        expert_offset=cfg["expert_offset"], seq_len=S,
        **{k: cfg[k] for k in SHARED})


def batches(k, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, CFG["vocab"], (k, B, S)).astype(np.int64)
    labels = np.concatenate([ids[:, :, 1:], np.full((k, B, 1), -100)], 2)
    return ids, labels


def trained_program(amp, k, ids):
    """The program's losses, first routed choice and scope after `k` steps
    of `run_steps` from the reference's seeded weights."""
    reset_programs(0)
    _, loss, routed = nemotron_h.build_causal_lm_program(model_config(CFG))
    fleet.init(is_collective=True)
    strategy = fleet.DistributedStrategy()
    strategy.amp = amp
    fleet.distributed_optimizer(
        paddle.optimizer.Adam(learning_rate=ref.ADAM["lr"]),
        strategy).minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    for name, value in ref.init_params(CFG, jax.random.key(3)).items():
        assert tuple(scope.find(name).shape) == tuple(value.shape), name
        scope.set(name, value)
    out = exe.run_steps(k, feed={"tokens": ids[:k]},
                        fetch_list=[loss, routed[0][0]])
    return np.asarray(out[0]).reshape(-1), np.asarray(out[1]), scope


def reference_states(k, ids, labels):
    """[(loss, grads, params, m, v) after each of k reference steps]."""
    params, buffers = ref.split_state(
        CFG, ref.init_params(CFG, jax.random.key(3)))
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    key = ref._cfg_key(CFG)
    states, first_idx = [], None
    for t in range(k):
        val, idx, grads = ref._block_grad(params, buffers, ids[t], labels[t],
                                          key, None)
        n = float((labels[t] != -100).sum())
        grads = jax.tree.map(lambda g: g / n, grads)
        first_idx = idx if first_idx is None else first_idx
        copy = jax.tree.map(jnp.array, (params, m, v))
        params, m, v = ref._adam(*copy, grads, float(t + 1))
        states.append((float(val) / n, grads, params, m, v))
    return states, np.asarray(first_idx)


DATA_SEED = 1


# Tolerances, as in test_deepseek_v3.py and test_mellum.py. float32: the
# program and the reference differ in the order of their float32 sums (the
# chunked scan against the recurrence among them), 1e-6 relative on a leaf.
# AMP: every matmul operand is rounded to bf16 (2^-9 = 0.2 % an operand);
# over a leaf's gradient the roundings average to a few per cent of the
# leaf's norm, and Adam's first two steps move each weight by at most lr a
# step whatever the gradient's size, so a weight differs by at most 4 lr
# where a tiny gradient changed sign in both steps; in float32 an element
# whose gradient is the rounding noise of a cancelling sum (a few of an
# embedding row's) moves by a fraction of lr differently, which the norm
# over the leaf does not see.
@pytest.mark.parametrize("amp, grad_tol, loss_tol", [
    (False, 5e-5, 1e-6), (True, 2e-2, 2e-4)], ids=["float32", "amp"])
def test_program_follows_the_reference(amp, grad_tol, loss_tol):
    # the data seed is one at which no token sits at a near-tie of two
    # experts' scores in any of the four expert layers under bf16 rounding
    # (two seeds in four at this size): one token of the 128 going to
    # another expert is 5 to 10 % of a leaf's gradient here, a comparison of
    # routings and not of arithmetic (on the chip `route_mismatch_share` is
    # that comparison)
    ids, labels = batches(2, seed=DATA_SEED)
    states, ref_idx = reference_states(2, ids, labels)
    before = [metrics.get(c) for c in ("ssm.bwd_residual",
                                       "ssm.bwd_recomputed")]

    losses, idx, scope = trained_program(amp, 1, ids)
    # the four scans' backward took the rule, on the forward's residuals
    assert [metrics.get(c) - b for c, b in zip(
        ("ssm.bwd_residual", "ssm.bwd_recomputed"), before)] == [4, 0]
    loss1, grads1 = states[0][0], states[0][1]
    assert abs(losses[0] - loss1) / loss1 < loss_tol
    for name, want in grads1.items():
        got = np.asarray(scope.find(name + "_moment1_0"),
                         np.float32) / (1 - ref.ADAM["beta1"])
        err = np.linalg.norm(got - np.asarray(want)) / max(
            np.linalg.norm(np.asarray(want)), 1e-12)
        assert err < grad_tol, (name, err)
    mismatch = (np.sort(idx[0].reshape(ref_idx.shape), 1)
                != np.sort(ref_idx, 1)).mean()
    assert mismatch == 0
    losses, _, scope = trained_program(amp, 2, ids)
    for t in range(2):
        assert abs(losses[t] - states[t][0]) / states[t][0] < loss_tol
    _, _, params, m, v = states[1]
    lr = ref.ADAM["lr"]
    p0 = ref.init_params(CFG, jax.random.key(3))
    for name in params:
        got = np.asarray(scope.find(name), np.float32)
        want = np.asarray(params[name])
        assert np.abs(got - want).max() <= (4.1 if amp else 0.5) * lr, name
        moved = np.linalg.norm(want - np.asarray(p0[name]))
        assert np.linalg.norm(got - want) <= (0.3 if amp
                                              else 1e-3) * moved, name
        for acc, want in (("_moment1_0", m), ("_moment2_0", v)):
            got = np.asarray(scope.find(name + acc), np.float32)
            err = np.linalg.norm(got - np.asarray(want[name])) / max(
                np.linalg.norm(np.asarray(want[name])), 1e-20)
            assert err < 2 * grad_tol, (name, acc, err)


@pytest.mark.parametrize("fault, moved, least", [
    (dict(scan_state_dtype="bfloat16"), "the scan's states in bf16", 0.01),
    (dict(ssm_heads_kept=6), "a quarter of the scan's heads left out", 0.5),
    (dict(float32_parts="bfloat16"), "bf16 where the file says float32",
     0.02)], ids=lambda v: v if isinstance(v, str) else "")
def test_the_reference_tells_each_fault_apart(fault, moved, least):
    """What the new mechanisms admit going wrong each moves the reference's
    own gradients by far more than the float32 tolerance above: the
    roundings by a per cent or more of a leaf at this size (32 tokens; a
    state rounded at every token drifts further over a row of 8,192, the
    chip's `calibrate` has the readings), a quarter of the scan's output
    left out by more than the leaf's own norm."""
    ids, labels = batches(1, seed=DATA_SEED)
    params, buffers = ref.split_state(
        CFG, ref.init_params(CFG, jax.random.key(3)))
    _, _, want = ref._block_grad(params, buffers, ids[0], labels[0],
                                 ref._cfg_key(CFG), None)
    bad_cfg = dict(CFG, assumed=dict(CFG["assumed"], **fault))
    _, _, got = ref._block_grad(params, buffers, ids[0], labels[0],
                                ref._cfg_key(bad_cfg), None)
    worst = max(float(jnp.linalg.norm(got[n] - want[n])
                      / jnp.linalg.norm(want[n])) for n in want)
    assert worst > least, (moved, worst)


# ---------------------------------------------------------------------------
# the selective scan: chunks against the recurrence
# ---------------------------------------------------------------------------

def _run_op(op_type, inputs, outputs, attrs):
    ctx = registry.LowerCtx(rng_key=jax.random.key(0))
    got = registry.get(op_type).lower(
        ctx, {k: [jnp.asarray(v)] for k, v in inputs.items()}, attrs)
    return [np.asarray(got[o][0]) for o in outputs]


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
        dict(CFG, reference_scan_tokens_per_block=8))


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
    text = re.sub(r"ssm\.py:\d+", "ssm.py:N", text)
    assert hashlib.sha256(text.encode()).hexdigest() == _FORM_DIGEST


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


def test_new_ops_have_specs_and_amp_placement():
    from paddle_tpu.amp.auto_cast import (black_list, keep_f32_slots,
                                          white_list)
    from paddle_tpu.analysis import op_specs  # noqa: F401
    for op in ("causal_conv1d", "ssm_scan", "gated_group_rms_norm", "relu2"):
        assert registry.get_spec(op) is not None, op
        assert op not in black_list
    assert "ssm_scan" in white_list
    assert keep_f32_slots["ssm_scan"] >= {"Dt", "DtBias", "ALog", "D",
                                          "States", "DtSoft", "CumA"}
    opdef = registry.get("ssm_scan")
    assert opdef.grad is not None
    assert opdef.residual_slots == ("States", "DtSoft", "CumA")


# ---------------------------------------------------------------------------
# the expert layer without a gate
# ---------------------------------------------------------------------------

def _uncut_layer(seed=0, n=96, d=32, f=16, fs=24, total=32):
    rng = np.random.RandomState(seed)
    params = {"router_w": rng.randn(d, total).astype(np.float32) * 0.3,
              "router_bias": rng.randn(total).astype(np.float32) * 0.05,
              "experts_up_w": rng.randn(total, d, f).astype(np.float32) * .2,
              "experts_down_w": rng.randn(total, f, d).astype(np.float32) * .2,
              "shared_up_w": rng.randn(d, fs).astype(np.float32) * .2,
              "shared_down_w": rng.randn(fs, d).astype(np.float32) * .2}
    return rng.randn(n, d).astype(np.float32), params


def _ref_cfg(held, total, offset, top_k=3):
    return dict(n_routed_experts=held, experts_total=total,
                expert_offset=offset, num_experts_per_tok=top_k,
                norm_topk_prob=True, routed_scaling_factor=2.5, assumed={})


def _share_program(x, params, offset, held, total, top_k=3, withhold=False,
                   cot=None):
    """One share's `routed_moe` without `ExpertGate` (sigmoid scoring, a
    selection bias) through a Program: [Out, TopIdx, ExpertLoad], or with
    `cot` the gradients of sum(Out * cot) with respect to (x, GateW,
    ExpertUp, ExpertDown)."""
    reset_programs(0)
    n, d = x.shape
    xv = layers.data(name="x", shape=[d], dtype="float32")
    xv.stop_gradient = False
    sl = slice(offset, offset + held)
    arrays = {"gate_w": params["router_w"], "eu": params["experts_up_w"][sl],
              "ed": params["experts_down_w"][sl]}
    var = {k: layers.create_parameter(list(v.shape), "float32", name=k)
           for k, v in arrays.items()}
    bias = layers.create_parameter([total], "float32", name="bias")
    bias.stop_gradient = True
    out, idx, load = layers.routed_moe(
        xv, var["gate_w"], None, var["eu"], var["ed"], top_k=top_k,
        select_bias=bias, routed_scaling=2.5, experts_total=total,
        expert_offset=offset)
    feed, fetch = {"x": x}, [out, idx, load]
    if cot is not None:
        cv = layers.data(name="cot", shape=[d], dtype="float32")
        loss = layers.reduce_sum(layers.elementwise_mul(out, cv))
        if withhold:
            for op in fluid.default_main_program().global_block().ops:
                if op.type == "routed_moe":
                    for slot in ("U", "SortedW", "Order", "Inv"):
                        op.outputs.pop(slot)
        fetch = fluid.gradients(loss, [xv] + [var[k] for k in arrays])
        feed["cot"] = cot
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    for k, v in dict(arrays, bias=params["router_bias"]).items():
        fluid.global_scope().set(k, jnp.asarray(v))
    return [np.asarray(g) for g in exe.run(feed=feed, fetch_list=fetch)]


def _reference_routed(x, params, cfg):
    off, held = cfg["expert_offset"], cfg["n_routed_experts"]
    p = {"l_" + k: jnp.asarray(v if not k.startswith("experts")
                               else v[off:off + held])
         for k, v in params.items()}
    out, idx = ref.routed_experts(jnp.asarray(x), p, "l_", cfg)
    return np.asarray(out), np.asarray(idx)


def test_the_sixteen_ranks_parts_and_the_shared_expert_add_up():
    """32 experts cut into 16 shares of 2, as the configuration cuts 128
    into 16 of 8: the routed parts all shares give, plus the shared expert
    that every rank computes alike counted ONCE, are the uncut reference's
    expert layer; every share's TopIdx is the reference's choice and no
    share's op carries a gate projection."""
    x, params = _uncut_layer()
    p = {"l_" + k: jnp.asarray(v) for k, v in params.items()}
    whole, want_idx = ref.expert_layer(jnp.asarray(x)[None], p, "l_",
                                       _ref_cfg(32, 32, 0))
    want_idx = np.asarray(want_idx)
    total, loads = 0.0, []
    for offset in range(0, 32, 2):
        out, idx, load = _share_program(x, params, offset, 2, 32)
        part, _ = _reference_routed(x, params, _ref_cfg(2, 32, offset))
        np.testing.assert_allclose(out, part, rtol=2e-5, atol=2e-6)
        total = total + out
        loads.append(load)
        assert (idx == want_idx).all()
    ops = [op for op in fluid.default_main_program().global_block().ops
           if op.type == "routed_moe"]
    assert "ExpertGate" not in ops[0].inputs and "H" not in ops[0].outputs
    shared = np.asarray(ref.relu2_ffn(jnp.asarray(x), p["l_shared_up_w"],
                                      p["l_shared_down_w"]))
    np.testing.assert_allclose(total + shared, np.asarray(whole)[0],
                               rtol=2e-5, atol=2e-6)
    assert (np.concatenate(loads) == np.bincount(
        want_idx.reshape(-1), minlength=32)).all()


_GRAD_NAMES = ("X", "GateW", "ExpertUp", "ExpertDown")


def test_ungated_experts_grad_rule_against_generic_route_and_reference():
    """A share of 4 of 32 experts: the rule's gradients (on the forward's
    residuals, four grouped matmuls) are the generic route's (the forward
    lowered again) and `jax.grad`'s of the plain float32 reference layer."""
    x, params = _uncut_layer(seed=2)
    cot = np.random.RandomState(7).randn(*x.shape).astype(np.float32)
    counters = ("moe.bwd_residual", "moe.bwd_recomputed")
    grouped = ("moe.grouped_pallas", "moe.grouped_xla")
    rises = []
    for withhold in (False, True):
        before = [metrics.get(c) for c in counters + grouped]
        got = _share_program(x, params, 4, 4, 32, withhold=withhold, cot=cot)
        rise = [int(metrics.get(c) - b)
                for c, b in zip(counters + grouped, before)]
        rises.append(tuple(rise[:2]))
        if withhold:
            for name, a, b in zip(_GRAD_NAMES, by_rule, got):
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7,
                                           err_msg=name)
        else:
            by_rule = got
            assert sum(rise[2:]) == 6       # 2 forward + 4 backward
    assert rises == [(1, 0), (0, 1)]
    cfg = _ref_cfg(4, 32, 4)

    def loss(x, router_w, eu, ed):
        p = {"l_router_w": router_w,
             "l_router_bias": jnp.asarray(params["router_bias"]),
             "l_experts_up_w": eu, "l_experts_down_w": ed}
        return jnp.sum(ref.routed_experts(x, p, "l_", cfg)[0] * cot)

    want = jax.grad(loss, argnums=(0, 1, 2, 3))(
        jnp.asarray(x), jnp.asarray(params["router_w"]),
        jnp.asarray(params["experts_up_w"][4:8]),
        jnp.asarray(params["experts_down_w"][4:8]))
    for name, a, b in zip(_GRAD_NAMES, by_rule, want):
        err = np.linalg.norm(a - np.asarray(b)) / np.linalg.norm(b)
        assert err < 2e-5 and np.linalg.norm(b) > 0, (name, err)


def _gated_jaxpr(scoring, bias, monkeypatch):
    from paddle_tpu.ops.pallas import grouped_matmul
    monkeypatch.setattr(grouped_matmul, "interpret_mode", lambda: False)

    def sd(*shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt)

    n, d, f, held, total = 512, 128, 256, 4, 16
    attrs = {"top_k": 2, "routed_scaling": 2.5, "norm_topk": True,
             "experts_total": total, "expert_offset": 4, "scoring": scoring}
    opdef = registry.get("routed_moe")

    def step(x, wg, sb, eg, eu, ed, g):
        ctx = registry.LowerCtx(rng_key=None)
        ins = {"X": [x], "GateW": [wg], "ExpertGate": [eg],
               "ExpertUp": [eu], "ExpertDown": [ed]}
        if bias:
            ins["SelectBias"] = [sb]
        outs = opdef.lower(ctx, ins, attrs)
        grads = opdef.grad(ctx, ins, attrs,
                           {s: outs[s] for s in opdef.residual_slots},
                           {"Out": [g]})
        return outs["Out"][0], [grads[s][0] for s in (
            "X", "GateW", "ExpertGate", "ExpertUp", "ExpertDown")]

    bf = jnp.bfloat16
    text = str(jax.make_jaxpr(step)(
        sd(n, d), sd(d, total), sd(total), sd(held, d, f, dt=bf),
        sd(held, d, f, dt=bf), sd(held, f, d, dt=bf), sd(n, d, dt=bf)))
    return re.sub(r"(moe|grouped_matmul)\.py:\d+", r"\1.py:N", text)


@pytest.mark.parametrize("scoring, bias, digest", [
    ("sigmoid", True,
     "756425e5b9df58c88211a24260d81274489de5600b84480885065b2d97d77f9f"),
    ("softmax", False,
     "5ddf58c49db0d1315192a97be0bcc0e83840cdca364ea69ef75fc61e8fb0d287")],
    ids=["sigmoid-bias", "softmax"])
def test_with_a_gate_routed_moe_traces_as_before(scoring, bias, digest,
                                                 monkeypatch):
    """`ExpertGate` present: the op's forward and its grad rule trace to
    the jaxpr of the tree before experts without a gate (commit 44019f7,
    jax 0.9.0; the digests were made there, source lines cut). The two
    sparse cells that run gated experts must not pay for the other form. A
    deliberate change to `routed_moe` changes the digests with it: PR 41's
    route without scalar gathers did (`tests/test_moe_route.py`)."""
    text = _gated_jaxpr(scoring, bias, monkeypatch)
    assert text.count("pallas_call") == 6
    assert hashlib.sha256(text.encode()).hexdigest() == digest


# ---------------------------------------------------------------------------
# the builder
# ---------------------------------------------------------------------------

_COUNTERS = ("ssm.layers_lowered", "ssm.bwd_residual", "ssm.bwd_recomputed",
             "moe.layers_lowered", "moe.bwd_residual", "moe.bwd_recomputed",
             "attention.flash_full", "attention.flash_kv_grouped",
             "attention.flash_bwd_residual",
             "attention.flash_bwd_recomputed") + _ROUTES


def test_builder_names_scopes_and_checkpoints_and_verifies():
    from paddle_tpu.analysis import verifier
    from paddle_tpu.observability import trace
    reset_programs(0)
    trace.clear()
    cfg = nemotron_h.NemotronHConfig.tiny()
    _, loss, routed = nemotron_h.build_causal_lm_program(cfg)
    built = [e for e in trace.events() if e["name"] == "program.build"]
    assert built and built[-1]["args"]["model"] == "nemotron_h"
    prog = fluid.default_main_program()
    ops = prog.global_block().ops
    # one mixer OR one feed-forward part a layer, by the pattern's letters
    kinds = {"ssm_scan": "M", "routed_moe": "E", "fused_attention": "*"}
    assert "".join(kinds[op.type] for op in ops
                   if op.type in kinds) == "MEMEM*EME"
    assert [op.type for op in ops].count("rms_norm") == 9 + 1
    assert not any(op.type == "rotary_embedding" for op in ops)
    scopes = {op.attrs.get("name_scope") for op in ops}
    assert {"ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.gate_norm",
            "ssm.out_proj", "attn.proj", "attn.attend.full",
            "moe.shared"} <= scopes
    scan = next(op for op in ops if op.type == "ssm_scan")
    assert scan.attrs["chunk_size"] == 8
    assert scan.attrs["name_scope"] == "ssm.scan"
    assert {"States", "DtSoft", "CumA"} <= set(scan.outputs)
    moe_ops = [op for op in ops if op.type == "routed_moe"]
    assert all("ExpertGate" not in op.inputs and "SelectBias" in op.inputs
               and op.attrs["scoring"] == "sigmoid"
               and op.attrs["routed_scaling"] == 2.5 for op in moe_ops)
    assert len(loss._layer_checkpoints) == 9 and len(routed) == 4
    paddle.optimizer.Adam(1e-4).minimize(loss)
    errors = [f for f in verifier.verify_program(prog)
              if f.severity == "error"]
    assert not errors, errors
    rules = nemotron_h.sharding_rules()
    assert tuple(rules.spec_for("l1_experts_up_w")) == ("ep",)
    assert tuple(rules.spec_for("l5_k_proj_w")) == (None, "tp")
    assert tuple(rules.spec_for("l3_shared_down_w")) == ("tp", None)
    assert tuple(rules.spec_for("l0_in_proj_w")) == ()
    with pytest.raises(ValueError, match="no layer kind"):
        reset_programs(0)
        cfg.hybrid_override_pattern = "MEMEM-EME"
        nemotron_h.build_causal_lm_program(cfg)


def _amp_step(recompute, **changed):
    """(executor, loss, ids [2, 1, 128]) of the tiny preset at 128 tokens
    in chunks of 32 with `changed` set, its AMP train step built through
    fleet, with a checkpoint at every layer boundary if `recompute`."""
    reset_programs(0)
    cfg = nemotron_h.NemotronHConfig.tiny()
    cfg.seq_len, cfg.chunk_size = 128, 32
    for key, value in changed.items():
        setattr(cfg, key, value)
    _, loss, _ = nemotron_h.build_causal_lm_program(cfg)
    fleet.init(is_collective=True)
    strategy = fleet.DistributedStrategy()
    strategy.amp = True
    if recompute:
        strategy.recompute = True
        strategy.recompute_configs = {
            "checkpoints": list(loss._layer_checkpoints)}
    fleet.distributed_optimizer(paddle.optimizer.Adam(1e-3),
                                strategy).minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    ids = np.random.RandomState(0).randint(0, 256, (2, 1, 128)).astype(
        np.int64)
    return exe, loss, ids


@pytest.mark.parametrize("recompute, rise", [
    (False, (4, 4, 0, 4, 4, 0, 1, 1, 1, 0, 0, 8)),
    (True, (4, 0, 4, 4, 0, 4, 2, 2, 0, 1, 0, 12))], ids=["plain", "recompute"])
def test_a_trace_of_the_step_counts_its_routes(recompute, rise, monkeypatch):
    """With the flash gate open (here: the interpreter), one trace of the
    AMP train step lowers four scans, four expert layers and one flash
    forward on grouped KV heads; their backward by each op's grad rule on
    the forward's residuals, or, with a checkpoint at every layer boundary
    (the cell's way: the step does not fit the chip without), by the same
    backward functions under `jax.vjp` of a whole layer, the forward lowered
    once more. At the preset's widths (state 16, 8 features a head) every
    scan, forward and backward, is the `jax.numpy` form. The step's jaxpr
    holds no `[S, H, P, N]` value."""
    from paddle_tpu.ops import attention
    monkeypatch.setattr(attention, "_use_pallas",
                        lambda q: q.shape[2] % 128 == 0)
    exe, loss, ids = _amp_step(recompute, head_dim=64)
    before = [metrics.get(c) for c in _COUNTERS]
    jaxpr = str(exe.step_jaxpr({"tokens": ids}, [loss], k=2))
    assert tuple(int(metrics.get(c) - b)
                 for c, b in zip(_COUNTERS, before)) == rise
    # states per chunk [1, 4, 8, 8, 16], never per token [1, 128, 8, 8, 16]
    assert "f32[1,4,8,8,16]" in jaxpr
    assert not re.search(r"\[1,128,8,8,16\]|\[128,8,8,16\]", jaxpr)
    assert "repeat" not in jaxpr
    # the builder's device scopes reach the compiled step either way: inside
    # a recomputed segment too (parallel/transforms.py `_run_sub_ops`)
    hlo = exe.compiled_hlo({"tokens": ids}, [loss], k=2)
    for scope in ("ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.gate_norm",
                  "ssm.out_proj", "attn.proj", "moe.shared", "moe.experts"):
        assert f"/{scope}/" in hlo, scope


@pytest.mark.parametrize("recompute, kernels", [(False, 8), (True, 12)],
                         ids=["plain", "recompute"])
def test_at_the_cells_scan_widths_every_scan_of_the_step_is_a_kernel(
        recompute, kernels):
    """State 128, 64 features a head, two groups of two heads, 256
    positions in chunks of 128 (the cell's widths, a thirty-second of its
    heads and positions): one trace of the AMP train step sends every scan
    to the Pallas kernels, 4 forward and 4 backward, and under
    recomputation, the cell's way, the 4 forward once more; none keeps the
    `jax.numpy` form. The counter reads the calls the step keeps: neither
    the shapes-only walk nor the body JAX traces and drops where a segment
    is differentiated as a whole. Each kernel is traced once for the four
    layers, and the chunk states are the one `[.., 64, 128]` float32 value
    a layer writes."""
    exe, loss, ids = _amp_step(
        recompute, seq_len=256, chunk_size=128, mamba_num_heads=4,
        mamba_head_dim=64, ssm_state_size=128)
    ids = np.random.RandomState(0).randint(0, 256, (2, 1, 256)).astype(
        np.int64)
    from paddle_tpu.ops.pallas import ssm_chunk
    entries = (ssm_chunk._ssd_fwd, ssm_chunk._ssd_bwd)
    before = [metrics.get(c) for c in _ROUTES]
    traced = [f._cache_size() for f in entries]
    jaxpr = str(exe.step_jaxpr({"tokens": ids}, [loss], k=2))
    assert [int(metrics.get(c) - b) for c, b in zip(_ROUTES, before)] \
        == [kernels, 0]
    # the four layers enter each kernel through one jitted function: one
    # trace of it (none here if the other case of this test made it)
    assert all(f._cache_size() - t <= 1 for f, t in zip(entries, traced))
    for name in ("ssm-chunk-fwd", "ssm-chunk-bwd"):
        assert re.search(rf"name={name}\s", jaxpr), name
    assert "f32[1,2,4,64,128]" in jaxpr
    assert not re.search(r"f32\[1,2,2,2,128,128\]", jaxpr)


@pytest.mark.parametrize("recompute, kernels", [(False, 24), (True, 40)],
                         ids=["plain", "recompute"])
def test_at_an_unaligned_expert_width_every_grouped_matmul_is_a_kernel(
        recompute, kernels):
    """The cell's expert width is 1856 = 14.5 x 128; here hidden 128 and an
    expert width of 232 = 29 x 8: a lane tile or more, no multiple of 128.
    One trace of the AMP train step sends every grouped matmul (2 forward
    and 4 backward a layer; under recomputation the trace passes the
    forward's two twice more) to the Pallas kernels, the width as one
    block, and none to `jax.lax.ragged_dot`."""
    exe, loss, ids = _amp_step(recompute, hidden_size=128,
                               moe_intermediate_size=232)
    counters = ("moe.layers_lowered", "moe.grouped_pallas",
                "moe.grouped_xla")
    before = [metrics.get(c) for c in counters]
    jaxpr = str(exe.step_jaxpr({"tokens": ids}, [loss], k=2))
    assert [int(metrics.get(c) - b) for c, b in zip(counters, before)] \
        == [4, kernels, 0]
    assert "ragged_dot" not in jaxpr
    for form in ("gmm", "gmm-t", "tgmm"):
        assert re.search(rf"name=ragged-dot-{form}\s", jaxpr), form
    assert "bf16[8,128,232]" in jaxpr and "bf16[8,232,128]" in jaxpr
