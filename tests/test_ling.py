"""The Ling-3.0-family hybrid LM (`models/ling.py`: Kimi-delta linear
attention with latent attention in the last layer of every group, head-wise
output gates, sigmoid-routed experts picked inside the best groups, a
chip's share of the heads and of the experts) against its plain float32
reference (`benchmark/reference/ling3.py`), on the CPU at tiny widths with
seeded weights; and what the model forced on the ops: the chunked gated
delta rule (`ops/kda.py`) against the token-by-token recurrence, its grad
rule on the chunk states, the bounded decay gate, the L2 norm and the
head-wise gate, and `routed_moe` with group-limited selection.
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
from paddle_tpu.models import deepseek_v3, ling  # noqa: E402
from paddle_tpu.observability import metrics  # noqa: E402
from paddle_tpu.ops import registry  # noqa: E402
from paddle_tpu.testing import reset_programs  # noqa: E402
from benchmark.reference import ling3 as ref  # noqa: E402

S, B = 32, 4
# published layers 1..4 of a model whose groups are 3 layers: layer 1 KDA
# with the dense part, layer 2 latent with experts, layers 3 and 4 KDA with
# experts; heads 2..3 of 4, experts 4..7 of 16 (group 1 of 4)
CFG = dict(hidden_size=64, num_hidden_layers=12, layer_group_size=3,
           first_k_dense_replace=2, layers=4, first_layer=1,
           num_attention_heads=2, heads_total=4, head_offset=2, head_dim=16,
           qk_nope_head_dim=16, qk_rope_head_dim=8, rotary_dim=8,
           v_head_dim=16, kv_lora_rank=32, short_conv_kernel_size=4,
           kda_lower_bound=-5, kda_chunk_size=16, intermediate_size=128,
           moe_intermediate_size=32, moe_shared_expert_intermediate_size=32,
           num_experts=4, experts_total=16, expert_offset=4,
           num_experts_per_tok=2, n_group=4, topk_group=2,
           routed_scaling_factor=2.5, norm_topk_prob=True, rms_norm_eps=1e-6,
           rope_theta=6000000, expert_swiglu_limit_list=[0] * 12,
           share_expert_swiglu_limit_list=[0] * 12, vocab=256,
           reference_scan_tokens_per_block=8,
           assumed={"initializer_std": 0.02, "select_bias_std": 0.03})
SHARED = ("hidden_size", "num_hidden_layers", "layer_group_size",
          "first_k_dense_replace", "head_dim", "qk_nope_head_dim",
          "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
          "short_conv_kernel_size", "kda_lower_bound", "kda_chunk_size",
          "intermediate_size", "moe_intermediate_size",
          "moe_shared_expert_intermediate_size", "num_experts_per_tok",
          "n_group", "topk_group", "routed_scaling_factor", "norm_topk_prob",
          "rms_norm_eps", "rope_theta", "expert_offset", "first_layer")


def model_config(cfg, seq=S):
    return ling.LingConfig(
        vocab_size=cfg["vocab"], num_layers_held=cfg["layers"],
        num_experts=cfg["experts_total"], experts_held=cfg["num_experts"],
        num_attention_heads=cfg["heads_total"],
        heads_held=cfg["num_attention_heads"], seq_len=seq,
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
    _, loss, routed = ling.build_causal_lm_program(model_config(CFG))
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


# Tolerances, as in test_nemotron_h.py. float32: the program and the
# reference differ in the order of their float32 sums (the chunked delta
# rule with its triangular solve against the recurrence among them). AMP:
# every matmul operand is rounded to bf16 (2^-9 = 0.2 % an operand); over a
# leaf's gradient the roundings average to 2 to 5 per cent of the leaf's
# norm at this size (the head's own leaf reads 1.8), and Adam's first two
# steps move each weight by at most lr a step whatever the gradient's size.
# With 16 experts in 4 groups some token of the 128 sits at a near-tie of
# two experts' or two groups' scores under bf16 rounding in every data seed
# tried (17 of 17; one to four of the 256 choices of a layer differ): one
# token going to another expert is 10 to 50 % of the router's and of an
# expert's gradient here, a comparison of routings and not of arithmetic
# (on the chip `route_mismatch_share` is that comparison), so under AMP
# those leaves are held to ten times the tolerance; in float32 the routing
# is the reference's, token for token.
_ROUTED = ("router_w", "experts_gate_w", "experts_up_w", "experts_down_w")


@pytest.mark.parametrize("amp, grad_tol, loss_tol", [
    (False, 1e-4, 1e-6), (True, 6e-2, 2e-4)], ids=["float32", "amp"])
def test_program_follows_the_reference(amp, grad_tol, loss_tol):
    def tol(name):
        return grad_tol * (10 if amp and name.endswith(_ROUTED) else 1)

    ids, labels = batches(2, seed=DATA_SEED)
    states, ref_idx = reference_states(2, ids, labels)
    counters = ("kda.bwd_residual", "kda.bwd_recomputed",
                "moe.group_limited_layers")
    before = [metrics.get(c) for c in counters]
    losses, idx, scope = trained_program(amp, 1, ids)
    # the three delta-rule layers' backward took the rule, on the forward's
    # residuals; the three expert layers selected inside groups
    assert [metrics.get(c) - b for c, b in zip(counters, before)] == [3, 0, 3]
    loss1, grads1 = states[0][0], states[0][1]
    assert abs(losses[0] - loss1) / loss1 < loss_tol
    for name, want in grads1.items():
        got = np.asarray(scope.find(name + "_moment1_0"),
                         np.float32) / (1 - ref.ADAM["beta1"])
        err = np.linalg.norm(got - np.asarray(want)) / max(
            np.linalg.norm(np.asarray(want)), 1e-12)
        assert err < tol(name), (name, err)
    mismatch = (np.sort(idx[0].reshape(ref_idx.shape), 1)
                != np.sort(ref_idx, 1)).mean()
    assert mismatch <= (0.02 if amp else 0)
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
        # Adam's first steps move an element by lr times its gradient's
        # sign: one element of a norm weight of 16 whose tiny gradient
        # turned is 0.35 of the leaf's move, and a token routed elsewhere
        # turns signs all over the routed leaves
        share = (0.6 if name.endswith(_ROUTED) else 0.45) if amp else 2e-3
        assert np.linalg.norm(got - want) <= share * moved, name
        # against the accumulator's size after either step: where the two
        # steps' gradients cancel (a decay's A_log, two numbers a layer) the
        # sum is a tenth of its terms and carries their rounding
        for acc, want, first in (("_moment1_0", m, states[0][3]),
                                 ("_moment2_0", v, states[0][4])):
            got = np.asarray(scope.find(name + acc), np.float32)
            err = np.linalg.norm(got - np.asarray(want[name])) / max(
                np.linalg.norm(np.asarray(want[name])),
                np.linalg.norm(np.asarray(first[name])), 1e-20)
            assert err < 2 * tol(name), (name, acc, err)


@pytest.mark.parametrize("fault, moved, least", [
    (dict(kda_state_dtype="bfloat16"), "the delta rule's state in bf16",
     0.005),
    (dict(kda_no_delta=True), "beta k k^T S left out", 0.05),
    (dict(no_group_limit=True), "plain top-k of all experts", 0.2),
    (dict(kda_heads_kept=1), "half of the delta rule's heads left out",
     0.5)], ids=lambda v: v if isinstance(v, str) else "")
def test_the_reference_tells_each_fault_apart(fault, moved, least):
    """What the new mechanisms admit going wrong each moves the reference's
    own gradients by far more than the float32 tolerance above (32 tokens
    here; the chip's `calibrate` has the readings at 8,192)."""
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
# the gated delta rule: chunks against the recurrence
# ---------------------------------------------------------------------------

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
                          dict(CFG, reference_scan_tokens_per_block=8))


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
    attrs = {"chunk_size": chunk}
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
        ctx, {k: [v] for k, v in low.items()}, {"chunk_size": 64})
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
    attrs = {"chunk_size": 64}
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
        attrs = {"chunk_size": 64}
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
    text = re.sub(r"kda\.py:\d+", "kda.py:N", text)
    assert hashlib.sha256(text.encode()).hexdigest() == _FORM_DIGEST
    monkeypatch.undo()
    text, rise = traced(128, jnp.bfloat16)
    assert rise == [2, 0]
    assert "triangular_solve" not in text
    for name in ("kda-chunk-fwd", "kda-chunk-bwd"):
        assert re.search(rf"name={name}\s", text), name


_FORM_DIGEST = (
    "96cc656f1231dad9df940ed0fdce2b88748c3f45290b6aee7f3436e89b4e56b5")


def _run_op(op_type, inputs, outputs, attrs):
    ctx = registry.LowerCtx(rng_key=jax.random.key(0))
    got = registry.get(op_type).lower(
        ctx, {k: [jnp.asarray(v)] for k, v in inputs.items()}, attrs)
    return [np.asarray(got[o][0]) for o in outputs]


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


def test_new_ops_have_specs_and_amp_placement():
    from paddle_tpu.amp.auto_cast import (black_list, keep_f32_slots,
                                          white_list)
    from paddle_tpu.analysis import op_specs  # noqa: F401
    for op in ("kda_gate", "kda_scan", "l2_norm", "head_gate"):
        assert registry.get_spec(op) is not None, op
        assert op not in black_list
    assert "kda_scan" in white_list and "kda_gate" not in white_list
    assert keep_f32_slots["kda_scan"] >= {"G", "Beta", "States"}
    opdef = registry.get("kda_scan")
    assert opdef.grad is not None
    assert opdef.residual_slots == ("States",)


# ---------------------------------------------------------------------------
# group-limited selection
# ---------------------------------------------------------------------------

def _selection_by_loop(sel, n_group, topk_group, top_k):
    """Each token's chosen experts, a loop a token: the groups' scores, the
    best groups (ties to the lower index), the best experts among theirs."""
    t, e = sel.shape
    size = e // n_group
    out = []
    for row in sel:
        score = [sum(sorted(row[j * size:(j + 1) * size])[-2:])
                 for j in range(n_group)]
        kept = sorted(range(n_group), key=lambda j: (-score[j], j))[
            :topk_group]
        allowed = [i for i in range(e) if i // size in kept]
        out.append(sorted(sorted(allowed, key=lambda i: (-row[i], i))[
            :top_k]))
    return np.asarray(out)


@pytest.mark.parametrize("n_group, topk_group, top_k", [
    (8, 4, 8), (4, 1, 3), (2, 2, 4)], ids=lambda v: str(v))
def test_group_limited_selection_against_a_plain_loop(n_group, topk_group,
                                                      top_k):
    """`routed_moe`'s TopIdx with `n_group` > 1, and the reference's
    selection, are a plain loop's; with every group kept it is the plain
    top-k."""
    rng = np.random.RandomState(n_group)
    n, d, total, held, f = 96, 16, 32, 4, 8
    x = rng.randn(n, d).astype(np.float32)
    wg = rng.randn(d, total).astype(np.float32) * 0.5
    bias = rng.randn(total).astype(np.float32) * 0.1
    eg, eu = (rng.randn(held, d, f).astype(np.float32) for _ in range(2))
    ed = rng.randn(held, f, d).astype(np.float32)
    attrs = {"top_k": top_k, "routed_scaling": 2.5, "norm_topk": True,
             "experts_total": total, "expert_offset": 8, "n_group": n_group,
             "topk_group": topk_group}
    before = metrics.get("moe.group_limited_layers")
    out, idx, load = _run_op(
        "routed_moe", {"X": x, "GateW": wg, "SelectBias": bias,
                       "ExpertGate": eg, "ExpertUp": eu, "ExpertDown": ed},
        ["Out", "TopIdx", "ExpertLoad"], attrs)
    assert metrics.get("moe.group_limited_layers") == before + 1
    sel = 1 / (1 + np.exp(-(x.astype(np.float64) @ wg))) + bias
    want = _selection_by_loop(sel, n_group, topk_group, top_k)
    assert (np.sort(idx, 1) == want).all()
    cfg = dict(n_group=n_group, topk_group=topk_group,
               num_experts_per_tok=top_k, norm_topk_prob=True,
               routed_scaling_factor=2.5, assumed={})
    ref_idx, _ = ref.route(jnp.asarray(x), jnp.asarray(wg),
                           jnp.asarray(bias), cfg)
    assert (np.sort(np.asarray(ref_idx), 1) == want).all()
    assert (load == np.bincount(want.reshape(-1), minlength=total)[8:12]).all()
    if topk_group == n_group:
        plain = np.sort(np.argsort(-sel, 1, kind="stable")[:, :top_k], 1)
        assert (want == plain).all()
    with pytest.raises(ValueError, match="groups"):
        _run_op("routed_moe", {"X": x, "GateW": wg, "ExpertGate": eg,
                               "ExpertUp": eu, "ExpertDown": ed}, ["Out"],
                dict(attrs, n_group=5))


def _jaxpr_digest(fn, *structs, cut=r"(moe|grouped_matmul)\.py:\d+"):
    text = str(jax.make_jaxpr(fn)(*structs))
    return hashlib.sha256(re.sub(cut, r"\1.py:N", text).encode()).hexdigest()


def test_without_groups_routed_moe_traces_as_before(monkeypatch):
    """`n_group` 1 (every cell the benchmark had): the op's forward and its
    grad rule trace to the jaxpr of the tree before group-limited selection
    (commit 40a2d5b, jax 0.9.0; the digest is `tests/test_nemotron_h.py`'s
    for sigmoid scoring with a bias, made there and changed with it by
    PR 41's route), whether the attr is left out or given as 1."""
    from paddle_tpu.ops.pallas import grouped_matmul
    monkeypatch.setattr(grouped_matmul, "interpret_mode", lambda: False)
    n, d, f, held, total = 512, 128, 256, 4, 16
    opdef = registry.get("routed_moe")

    def step(attrs):
        def fn(x, wg, sb, eg, eu, ed, g):
            ctx = registry.LowerCtx(rng_key=None)
            ins = {"X": [x], "GateW": [wg], "ExpertGate": [eg],
                   "ExpertUp": [eu], "ExpertDown": [ed], "SelectBias": [sb]}
            outs = opdef.lower(ctx, ins, attrs)
            grads = opdef.grad(ctx, ins, attrs,
                               {s: outs[s] for s in opdef.residual_slots},
                               {"Out": [g]})
            return outs["Out"][0], [grads[s][0] for s in (
                "X", "GateW", "ExpertGate", "ExpertUp", "ExpertDown")]
        return fn

    bf, sd = jnp.bfloat16, jax.ShapeDtypeStruct
    structs = (sd((n, d), jnp.float32), sd((d, total), jnp.float32),
               sd((total,), jnp.float32), sd((held, d, f), bf),
               sd((held, d, f), bf), sd((held, f, d), bf), sd((n, d), bf))
    attrs = {"top_k": 2, "routed_scaling": 2.5, "norm_topk": True,
             "experts_total": total, "expert_offset": 4,
             "scoring": "sigmoid"}
    want = "756425e5b9df58c88211a24260d81274489de5600b84480885065b2d97d77f9f"
    assert _jaxpr_digest(step(attrs), *structs) == want
    assert _jaxpr_digest(step(dict(attrs, n_group=1, topk_group=1)),
                         *structs) == want
    assert _jaxpr_digest(step(dict(attrs, n_group=4, topk_group=2)),
                         *structs) != want


def test_latent_attention_as_kanana_calls_it_traces_as_before():
    """`models/deepseek_v3.latent_attention` was given a sibling
    (`ling.gated_latent_attention`) and not an option: the tiny preset's
    float32 train step traces to the jaxpr of the tree before this model
    (commit 40a2d5b, jax 0.9.0; the digest was made there, source lines
    cut, and again with PR 41's route in `routed_moe`)."""
    reset_programs(0)
    cfg = deepseek_v3.DeepseekV3Config.tiny()
    _, loss, _ = deepseek_v3.build_causal_lm_program(cfg)
    paddle.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    ids = np.zeros((2, 2, cfg.seq_len), np.int64)
    text = re.sub(r"[\w/.\-]+\.py:\d+", "F:N",
                  str(exe.step_jaxpr({"tokens": ids}, [loss], k=2)))
    assert text.count("rsqrt") >= 3 * 3
    assert hashlib.sha256(text.encode()).hexdigest() == KANANA_DIGEST


KANANA_DIGEST = (
    "b55dd5734b173553d7c9752e5e345b292002dc0118da0dfaf078759bc831ca74")


# ---------------------------------------------------------------------------
# the shares add up
# ---------------------------------------------------------------------------

def _attention_program(kind, cfg, x, params, pre):
    """One share's attention layer of `kind` through a Program."""
    reset_programs(0)
    mcfg = model_config(cfg, seq=x.shape[1])
    xv = layers.data(name="x", shape=list(x.shape[1:]), dtype="float32")
    build = (ling.gated_latent_attention if kind == ling.LATENT
             else ling.kda_attention)
    out = build(xv, mcfg, pre)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    for name, value in params.items():
        assert tuple(fluid.global_scope().find(name).shape) == tuple(
            value.shape), name
        fluid.global_scope().set(name, jnp.asarray(value))
    return np.asarray(exe.run(feed={"x": x}, fetch_list=[out])[0])


def _head_share(params, cfg, lo, hi):
    """The leaves of heads lo..hi of an uncut attention layer: columns of
    the projections into heads (and their conv kernels and per-head
    parameters), rows of W_o; what every chip holds whole as it is."""
    hd, total = cfg["head_dim"], cfg["heads_total"]
    out = {}
    for name, value in params.items():
        value = np.asarray(value)
        leaf = name.split("_", 1)[1]
        if leaf == "o_proj_w":
            rows = value.reshape(total, -1, value.shape[1])[lo:hi]
            out[name] = rows.reshape(-1, value.shape[1])
        elif leaf in ("kv_a_proj_w",) or leaf.endswith("_scale"):
            out[name] = value
        else:    # [..., heads x width]: the held heads' columns
            cols = value.reshape(value.shape[:-1] + (total, -1))[..., lo:hi, :]
            out[name] = cols.reshape(value.shape[:-1] + (-1,))
    assert out[name.split("_", 1)[0] + "_o_proj_w"].shape[0] == (hi - lo) * hd
    return out


@pytest.mark.parametrize("kind, n", [(ling.KDA, 1), (ling.LATENT, 2)])
def test_the_two_head_shares_add_up_to_the_uncut_attention(kind, n):
    """Heads 0..1 and 2..3 of 4, as the configuration cuts 32 into two of
    16: the two shares' attention outputs (each through the program, built
    for its held heads only) sum to the reference's uncut layer, in both
    kinds of layer; each share is the reference's share."""
    whole = dict(CFG, num_attention_heads=4, head_offset=0, layers=12,
                 first_layer=0)
    pre = f"l{n}_"
    attn = [k for k in ref.param_shapes(whole) if k.startswith(pre) and not (
        "norm_scale" in k and k.split("_", 1)[1] in (
            "attn_norm_scale", "ffn_norm_scale")) and not any(
        part in k for part in ("mlp_", "experts_", "shared_", "router_"))]
    key = jax.random.key(5)
    params = {k: ref.init_leaf(whole, key, k) for k in attn}
    rng = np.random.RandomState(4)
    x = rng.randn(2, 32, CFG["hidden_size"]).astype(np.float32)
    attend = ref.latent_attention if kind == ling.LATENT else ref.kda_attention
    with jax.default_matmul_precision("highest"):
        want = np.asarray(attend(jnp.asarray(x), params, pre, whole))
        total = 0.0
        for lo in (0, 2):
            share_cfg = dict(CFG, head_offset=lo)
            share = _head_share(params, whole, lo, lo + 2)
            got = _attention_program(kind, share_cfg, x, share, pre)
            part = np.asarray(attend(
                jnp.asarray(x), {k: jnp.asarray(v) for k, v in share.items()},
                pre, share_cfg))
            np.testing.assert_allclose(got, part, rtol=2e-4, atol=2e-6)
            total = total + got
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-6)
    assert np.abs(want).max() > 1e-3


def _uncut_expert_layer(seed=0, n=96, d=32, f=16, total=32):
    rng = np.random.RandomState(seed)
    mat = lambda *shape: rng.randn(*shape).astype(np.float32) * 0.2  # noqa: E731
    return rng.randn(n, d).astype(np.float32), {
        "router_w": mat(d, total) * 1.5, "router_bias": mat(total) * 0.25,
        "experts_gate_w": mat(total, d, f), "experts_up_w": mat(total, d, f),
        "experts_down_w": mat(total, f, d), "shared_gate_w": mat(d, f),
        "shared_up_w": mat(d, f), "shared_down_w": mat(f, d)}


def _expert_cfg(held, total, offset):
    return dict(num_experts=held, experts_total=total, expert_offset=offset,
                num_experts_per_tok=4, n_group=8, topk_group=4,
                norm_topk_prob=True, routed_scaling_factor=2.5, assumed={})


def test_the_ranks_routed_parts_and_the_shared_expert_add_up():
    """32 experts in 8 groups of 4, cut into 16 shares of 2 as the
    configuration cuts 512 into 64 of 8: the routed parts all shares give
    (`routed_moe` with the group limit, through a Program), plus the shared
    expert that every rank computes alike counted ONCE, are the uncut
    reference's expert layer; every share's TopIdx is the reference's
    group-limited choice."""
    x, params = _uncut_expert_layer()
    p = {"l_" + k: jnp.asarray(v) for k, v in params.items()}
    with jax.default_matmul_precision("highest"):
        whole, want_idx = ref.expert_layer(jnp.asarray(x)[None], p, "l_",
                                           _expert_cfg(32, 32, 0))
    want_idx = np.asarray(want_idx)
    total, loads = 0.0, []
    for offset in range(0, 32, 2):
        reset_programs(0)
        sl = slice(offset, offset + 2)
        arrays = {"gate_w": params["router_w"],
                  "eg": params["experts_gate_w"][sl],
                  "eu": params["experts_up_w"][sl],
                  "ed": params["experts_down_w"][sl]}
        xv = layers.data(name="x", shape=[x.shape[1]], dtype="float32")
        var = {k: layers.create_parameter(list(v.shape), "float32", name=k)
               for k, v in arrays.items()}
        bias = layers.create_parameter([32], "float32", name="bias")
        out, idx, load = layers.routed_moe(
            xv, var["gate_w"], var["eg"], var["eu"], var["ed"], top_k=4,
            select_bias=bias, routed_scaling=2.5, experts_total=32,
            expert_offset=offset, n_group=8, topk_group=4)
        exe = fluid.Executor()
        exe.run(fluid.default_startup_program())
        for k, v in dict(arrays, bias=params["router_bias"]).items():
            fluid.global_scope().set(k, jnp.asarray(v))
        out, idx, load = (np.asarray(t) for t in exe.run(
            feed={"x": x}, fetch_list=[out, idx, load]))
        total = total + out
        loads.append(load)
        assert (np.sort(idx, 1) == np.sort(want_idx, 1)).all()
    op = next(op for op in fluid.default_main_program().global_block().ops
              if op.type == "routed_moe")
    assert (op.attrs["n_group"], op.attrs["topk_group"]) == (8, 4)
    shared = np.asarray(ref.swiglu_ffn(
        jnp.asarray(x), p["l_shared_gate_w"], p["l_shared_up_w"],
        p["l_shared_down_w"]))
    np.testing.assert_allclose(total + shared, np.asarray(whole)[0],
                               rtol=2e-4, atol=2e-5)
    assert (np.concatenate(loads) == np.bincount(
        want_idx.reshape(-1), minlength=32)).all()
    # the limit bites: some token's plain top-4 leaves its four best groups
    plain, _ = ref.route(jnp.asarray(x), p["l_router_w"], p["l_router_bias"],
                         dict(_expert_cfg(32, 32, 0),
                              assumed={"no_group_limit": True}))
    assert (np.sort(np.asarray(plain), 1) != np.sort(want_idx, 1)).any()


# ---------------------------------------------------------------------------
# the builder
# ---------------------------------------------------------------------------

_COUNTERS = ("kda.layers_lowered", "kda.bwd_residual", "kda.bwd_recomputed",
             "moe.layers_lowered", "moe.bwd_residual", "moe.bwd_recomputed",
             "moe.group_limited_layers", "attention.flash_full",
             "attention.flash_bwd_residual",
             "attention.flash_bwd_recomputed")


def test_builder_names_scopes_and_checkpoints_and_verifies():
    from paddle_tpu.analysis import verifier
    from paddle_tpu.observability import trace
    reset_programs(0)
    trace.clear()
    cfg = ling.LingConfig.tiny()
    _, loss, routed = ling.build_causal_lm_program(cfg)
    built = [e for e in trace.events() if e["name"] == "program.build"]
    assert built and built[-1]["args"]["model"] == "ling"
    prog = fluid.default_main_program()
    ops = prog.global_block().ops
    # published layers 1..4 of groups of 3: KDA, latent, KDA, KDA; the
    # dense part in layer 1, experts from layer 2 on
    kinds = {"kda_scan": "K", "fused_attention": "L", "routed_moe": "E"}
    assert "".join(kinds[op.type] for op in ops
                   if op.type in kinds) == "KLEKEKE"
    assert [cfg.kind(n) for n in cfg.layers_here()] == [
        "kda", "latent", "kda", "kda"]
    names = {p.name for p in prog.global_block().all_parameters()}
    assert {"l1_mlp_gate_w", "l2_q_norm_scale", "l2_kv_a_proj_w",
            "l3_f_proj_w", "l4_A_log", "l4_experts_down_w"} <= names
    assert not any(n.startswith(("l0_", "l5_")) for n in names)
    scopes = {op.attrs.get("name_scope") for op in ops}
    assert {"kda.proj", "kda.conv", "kda.gate", "kda.scan", "kda.out",
            "mla.proj", "mla.attend", "moe.shared"} <= scopes
    scan = next(op for op in ops if op.type == "kda_scan")
    assert scan.attrs["chunk_size"] == 16
    assert scan.attrs["name_scope"] == "kda.scan"
    assert "States" in scan.outputs
    # no rotary in the KDA layers; q and k of the one latent layer
    assert [op.type for op in ops].count("rotary_embedding") == 2
    assert [op.type for op in ops].count("head_gate") == 4
    convs = [op for op in ops if op.type == "causal_conv1d"]
    assert len(convs) == 9 and not any("Bias" in op.inputs for op in convs)
    moe_ops = [op for op in ops if op.type == "routed_moe"]
    assert all("ExpertGate" in op.inputs and "SelectBias" in op.inputs
               and (op.attrs["n_group"], op.attrs["topk_group"]) == (4, 2)
               for op in moe_ops)
    assert len(loss._layer_checkpoints) == 4 and len(routed) == 3
    paddle.optimizer.Adam(1e-4).minimize(loss)
    errors = [f for f in verifier.verify_program(prog)
              if f.severity == "error"]
    assert not errors, errors
    rules = ling.sharding_rules()
    assert tuple(rules.spec_for("l2_experts_up_w")) == ("ep",)
    assert tuple(rules.spec_for("l1_f_proj_w")) == (None, "tp")
    assert tuple(rules.spec_for("l1_k_conv_w")) == (None, "tp")
    assert tuple(rules.spec_for("l1_A_log")) == ("tp",)
    assert tuple(rules.spec_for("l1_o_proj_w")) == ("tp", None)
    assert tuple(rules.spec_for("l2_kv_a_proj_w")) == ()
    with pytest.raises(ValueError, match="clamp"):
        reset_programs(0)
        cfg.expert_swiglu_limit_list = (0, 0, 0, 4)
        ling.build_causal_lm_program(cfg)
    with pytest.raises(ValueError, match="heads"):
        reset_programs(0)
        ling.build_causal_lm_program(ling.LingConfig(
            **{**vars(ling.LingConfig.tiny()), "heads_held": 5}))


def _amp_step(recompute, **changed):
    """(executor, loss, ids [2, 1, 128]) of the tiny preset at 128 tokens
    in chunks of 64 with `changed` set, its AMP train step built through
    fleet, with a checkpoint at every layer boundary if `recompute`."""
    reset_programs(0)
    cfg = ling.LingConfig.tiny()
    cfg.seq_len, cfg.kda_chunk_size = 128, 64
    for key, value in changed.items():
        setattr(cfg, key, value)
    _, loss, _ = ling.build_causal_lm_program(cfg)
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
    (False, (3, 3, 0, 3, 3, 0, 3, 1, 1, 0)),
    (True, (3, 0, 3, 3, 0, 3, 3, 2, 0, 1))], ids=["plain", "recompute"])
def test_a_trace_of_the_step_counts_its_routes(recompute, rise, monkeypatch):
    """With the flash gate open (here: the interpreter), one trace of the
    AMP train step lowers three delta-rule layers, three group-limited
    expert layers and one flash forward; their backward by each op's grad
    rule on the forward's residuals, or, with a checkpoint at every layer
    boundary, by the same backward functions under `jax.vjp` of a whole
    layer, the forward lowered once more. The step's jaxpr holds no
    `[S, H, K, V]` value: the states are a chunk's."""
    from paddle_tpu.ops import attention
    monkeypatch.setattr(attention, "_use_pallas",
                        lambda q: q.shape[2] % 128 == 0)
    exe, loss, ids = _amp_step(recompute, qk_nope_head_dim=56,
                               v_head_dim=64, head_dim=64)
    before = [metrics.get(c) for c in _COUNTERS]
    routes = [metrics.get(c) for c in _ROUTES]
    jaxpr = str(exe.step_jaxpr({"tokens": ids}, [loss], k=2))
    assert tuple(int(metrics.get(c) - b)
                 for c, b in zip(_COUNTERS, before)) == rise
    # heads of 64 are half a lane tile: every scan the step keeps, forward,
    # backward and the forward lowered once more, is the `jax.numpy` form
    assert [int(metrics.get(c) - r) for c, r in zip(_ROUTES, routes)] \
        == [0, 9 if recompute else 6]
    # 4 heads of 64 x 64 at 128 positions in 2 chunks
    assert "f32[1,2,4,64,64]" in jaxpr
    assert not re.search(r"\[1,128,4,64,64\]|\[1,4,128,64,64\]", jaxpr)
    assert "triangular_solve" in jaxpr and "kda-chunk" not in jaxpr


@pytest.mark.parametrize("recompute, kernels", [(False, 12), (True, 18)],
                         ids=["plain", "recompute"])
def test_at_the_cells_scan_widths_every_scan_of_the_step_is_a_kernel(
        recompute, kernels):
    """Heads of 128 x 128 in chunks of 64, six delta-rule layers of seven
    (the cell's widths and its layers, a quarter of its heads and a
    sixty-fourth of its positions): one trace of the AMP train step sends
    every `kda_scan` to the Pallas kernels, 6 forward and 6 backward, and
    under recomputation, the cell's way, the 6 forward once more; none
    keeps the `jax.numpy` form, and no triangular solve is left in the step.
    The counter reads the calls the step keeps: neither the shapes-only
    walk nor the body JAX traces and drops where a segment is
    differentiated as a whole. Each kernel is traced once for the six
    layers, and the chunk states are the one `[.., 128, 128]` float32 value
    a layer writes."""
    exe, loss, ids = _amp_step(
        recompute, head_dim=128, num_hidden_layers=14, layer_group_size=7,
        first_layer=7, num_layers_held=7)
    from paddle_tpu.ops.pallas import kda_chunk
    entries = (kda_chunk._kda_fwd, kda_chunk._kda_bwd)
    before = [metrics.get(c) for c in _ROUTES]
    lowered = metrics.get("kda.layers_lowered")
    traced = [f._cache_size() for f in entries]
    jaxpr = str(exe.step_jaxpr({"tokens": ids}, [loss], k=2))
    assert int(metrics.get("kda.layers_lowered") - lowered) == 6
    assert [int(metrics.get(c) - b) for c, b in zip(_ROUTES, before)] \
        == [kernels, 0]
    # the six layers enter each kernel through one jitted function: one
    # trace of it (none here if the other case of this test made it)
    assert all(f._cache_size() - t <= 1 for f, t in zip(entries, traced))
    for name in ("kda-chunk-fwd", "kda-chunk-bwd"):
        assert re.search(rf"name={name}\s", jaxpr), name
    assert "f32[1,2,4,128,128]" in jaxpr
    assert "triangular_solve" not in jaxpr
    assert not re.search(r"f32\[1,2,4,64,64\]", jaxpr)


def _tiny_step_digest(build):
    """sha256 of a builder's tiny float32 train step of k = 2, source lines
    cut."""
    reset_programs(0)
    loss, feed = build()
    paddle.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    text = re.sub(r"[\w/.\-]+\.py:\d+", "F:N",
                  str(exe.step_jaxpr(feed, [loss], k=2)))
    return hashlib.sha256(text.encode()).hexdigest()


def _causal_lm(module, cfg):
    def build():
        _, loss, _ = module.build_causal_lm_program(cfg)
        return loss, {"tokens": np.zeros((2, 2, cfg.seq_len), np.int64)}
    return build


def _bert_pretrain():
    from paddle_tpu.models import bert
    cfg = bert.BertConfig.tiny()
    _, _, loss = bert.build_pretrain_program(cfg)
    return loss, {"input_ids": np.zeros((2, 2, cfg.seq_len), np.int64),
                  "mlm_labels": np.zeros((2, 2, cfg.seq_len, 1), np.int64)}


@pytest.mark.parametrize("cell, digest", [
    ("bert", "680dd440f44ce047ab42aefcc7b90d4a5196cb72a073633a721ac25285f98ca7"),
    ("mellum", "ece5b162f3f2d703cacc41a43320bc2ae04f46f1dfcef901271578999e044e88"),
    ("latent_hybrid",
     "ce94d43b5b24963e07c270f8de8b711da0ef8d8ea3d4a1909f00d4de6d174779")])
def test_the_other_cells_builders_trace_as_before(cell, digest):
    """The delta rule's kernels are reached from `ops/kda.py` alone, and
    `models/ling.py` is its only caller: the tiny float32 train step of the
    builders behind the other cells traces to the jaxpr of the tree before
    the kernels (commit fa2014b, jax 0.9.0; the digests were made there,
    source lines cut, the two sparse ones again with PR 41's route in
    `routed_moe`). BERT's (both BERT cells), the sliding-window one's
    and the latent-expert hybrid's here; kanana's is
    `test_latent_attention_as_kanana_calls_it_traces_as_before` above, the
    hybrid's `tests/test_nemotron3_super.py::
    test_without_a_latent_and_with_every_head_the_model_traces_as_before`."""
    from paddle_tpu.models import mellum, nemotron_h
    build = {
        "bert": _bert_pretrain,
        "mellum": _causal_lm(mellum, mellum.MellumConfig.tiny()),
        "latent_hybrid": _causal_lm(
            nemotron_h, nemotron_h.NemotronHConfig.tiny_latent_share()),
    }[cell]
    assert _tiny_step_digest(build) == digest
