"""The Solar-Open2-family hybrid LM (`models/solar.py`: Kimi-delta linear
attention in its original form, 3 : 1 with gated softmax attention on
grouped KV heads without rotary positions, every layer sparse beside a shared
expert, a chip's share of the heads and of the experts) against its plain
float32 reference (`benchmark/reference/solar_open2.py`), on the CPU at tiny
widths with seeded weights; and what the model forced on the ops: `kda_scan`
for a decay WITHOUT a lower bound (a chunk's decayed products level by
level, no factor above 1, in the `jax.numpy` form and in both Pallas
kernels), the unbounded gate, `beta` in (0, 2), an element-wise output gate
on `grouped_attention`, and a router of 320 over 40 ranks.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import causal_lm_harness as harness
from causal_lm_harness import S, counter_rise, run_op as _run_op

import paddle_tpu as paddle
import paddle_tpu.fluid as fluid
from paddle_tpu.models import causal_lm, solar
from paddle_tpu.observability import metrics
from paddle_tpu.ops import kda, registry
from paddle_tpu.testing import reset_programs
from benchmark.reference import ling3
from benchmark.reference import solar_open2 as ref

# published layers 0..3 of a model of 8 whose layers 0 and 4 attend by
# softmax: one whole period. Query heads 2..3 of 8 on KV head 1 of 4, KDA
# heads 2..3 of 8 (heads are alike to the program: which ones is the
# loader's business), experts 4..7 of 8
CFG = dict(hidden_size=64, num_hidden_layers=8, gqa_layers=[0, 4], layers=4,
           first_layer=0, num_attention_heads=2, heads_total=8,
           num_key_value_heads=1, kv_heads_total=4, head_dim=16,
           linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 16,
                               "num_heads": 2, "num_kv_heads": None},
           linear_heads_total=8, use_rope=False, use_gqa_gate=True,
           kda_use_full_proj=False, kda_allow_neg_eigval=True,
           kda_chunk_size=16, first_k_dense_replace=0, intermediate_size=128,
           moe_intermediate_size=32, n_routed_experts=4, experts_total=8,
           expert_offset=4, n_shared_experts=1, num_experts_per_tok=2,
           norm_topk_prob=True, routed_scaling_factor=1, rms_norm_eps=1e-5,
           vocab=256, reference_scan_tokens_per_block=8,
           assumed={"initializer_std": 0.02})
SHARED = ("hidden_size", "num_hidden_layers", "head_dim", "use_rope",
          "use_gqa_gate", "kda_use_full_proj", "kda_allow_neg_eigval",
          "kda_chunk_size", "first_k_dense_replace", "intermediate_size",
          "moe_intermediate_size", "n_shared_experts", "num_experts_per_tok",
          "norm_topk_prob", "routed_scaling_factor", "rms_norm_eps",
          "expert_offset", "first_layer")


def model_config(cfg, seq=S):
    lin = cfg["linear_attn_config"]
    return solar.SolarConfig(
        vocab_size=cfg["vocab"], num_layers_held=cfg["layers"],
        gqa_layers=tuple(cfg["gqa_layers"]),
        n_routed_experts=cfg["experts_total"],
        experts_held=cfg["n_routed_experts"],
        num_attention_heads=cfg["heads_total"],
        heads_held=cfg["num_attention_heads"],
        num_key_value_heads=cfg["kv_heads_total"],
        kv_heads_held=cfg["num_key_value_heads"],
        linear_num_heads=cfg["linear_heads_total"],
        linear_heads_held=lin["num_heads"], linear_head_dim=lin["head_dim"],
        short_conv_kernel_size=lin["short_conv_kernel_size"], seq_len=seq,
        **{k: cfg[k] for k in SHARED})


def seeded_params():
    return ref.init_params(CFG, jax.random.key(3))


def trained_program(amp, k, ids):
    return harness.trained_program(solar, model_config(CFG), ref,
                                   seeded_params(), amp, k, ids)


DATA_SEED = 1
# Tolerances and their reasons: `tests/test_ling.py`'s, the same mechanisms
# at the same size (float32: the order of sums, the chunked delta rule with
# its solve against the recurrence; AMP: every matmul operand rounded to
# bf16, a token at a near-tie routed elsewhere turns signs all over the
# routed leaves). Two differences from that model, read over three data
# seeds (worst leaves 0.09 to 0.36): every layer here is sparse, so a
# layer's second norm feeds the router and the experts ALONE and its scale
# is a routed leaf too (0.06 to 0.11; ling's dense layer hid that); and a
# head's `A_log` under the unbounded gate is a sum over every position and
# channel of terms of both signs, two numbers a layer (0.21 at one seed of
# three, under 0.06 at the others).
_ROUTED = ("router_w", "experts_gate_w", "experts_up_w", "experts_down_w",
           "ffn_norm_scale", "A_log")


@pytest.mark.parametrize("amp, grad_tol, loss_tol", [
    (False, 1e-4, 1e-6), (True, 6e-2, 2e-4)], ids=["float32", "amp"])
def test_program_follows_the_reference(amp, grad_tol, loss_tol):
    """Loss, every leaf's gradient and two Adam steps over published layers
    0..3 (softmax, KDA, KDA, KDA; experts in all four), through
    `Executor.run_steps`; the three delta-rule layers' backward took the
    grad rule and every scan was the form for an unbounded decay."""
    def tol(name):
        return grad_tol * (10 if amp and name.endswith(_ROUTED) else 1)

    ids, labels = harness.batches(CFG["vocab"], 2, seed=DATA_SEED)
    states, ref_idx = harness.reference_states(
        ref, CFG, ref.split_state(CFG, seeded_params()), 2, ids, labels)
    (losses, idx, scope), rise = counter_rise(
        lambda: trained_program(amp, 1, ids),
        ("kda.bwd_residual", "kda.bwd_recomputed", "kda.scan_exact",
         "kda.scan_bounded"))
    assert rise == (3, 0, 6, 0)
    loss1, grads1 = states[0][0], states[0][1]
    assert abs(losses[0] - loss1) / loss1 < loss_tol
    for name, err in harness.first_step_gaps(scope, grads1, ref).items():
        assert err < tol(name), (name, err)
    assert harness.route_mismatch(idx[0], ref_idx) <= (0.02 if amp else 0)
    losses, _, scope = trained_program(amp, 2, ids)
    for t in range(2):
        assert abs(losses[t] - states[t][0]) / states[t][0] < loss_tol
    lr = ref.ADAM["lr"]
    for name, worst, gap, moved, moments in harness.second_step_gaps(
            scope, states, seeded_params(), floor_by_first_step=True):
        assert worst <= (4.1 if amp else 0.5) * lr, name
        share = (0.6 if name.endswith(_ROUTED) else 0.45) if amp else 2e-3
        if amp and name.endswith("A_log"):
            # two numbers, each moved by lr times its gradient's sign: one
            # that turned in one of the two steps is 0.7 of the leaf's move
            share = 1.0
        assert gap <= share * moved, name
        for acc, err in moments.items():
            assert err < 2 * tol(name), (name, acc, err)


def test_the_references_own_follow_is_its_block_grads_and_adam():
    """`follow` (moments on the host between steps, every leaf updated by
    itself) gives the losses, the first moments' norms and the parameters'
    change of the plain loop over whole trees, and the least log decay of
    step 1's forward."""
    ids, labels = harness.batches(CFG["vocab"], 2, seed=DATA_SEED)
    states, _ = harness.reference_states(
        ref, CFG, ref.split_state(CFG, seeded_params()), 2, ids, labels)
    got = ref.follow(CFG, seeded_params,
                     [{"ids": ids[t], "labels": labels[t]} for t in range(2)],
                     rows_per_block=2)
    p0 = seeded_params()
    for t in range(2):
        assert abs(got["losses"][t] - states[t][0]) < 1e-5 * states[t][0]
    _, _, params, m, _ = states[1]
    for name in params:
        want = float(jnp.linalg.norm(m[name]))
        assert abs(got["moment1_norms"][name] - want) <= 1e-4 * want + 1e-12
        moved = float(jnp.linalg.norm(params[name] - p0[name]))
        assert abs(got["delta_norms"][name] - moved) <= 1e-3 * moved + 1e-9
    assert set(got["moment1_vectors"]) == set(ref.vector_leaves(CFG))
    # the seeded gate is mild: softplus of a step in [0.001, 0.1] times a
    # rate in [1, 16]
    assert -3.0 < got["min_log_decay"] < 0.0


@pytest.mark.parametrize("fault, least", [
    ("beta_unscaled", 0.3), ("bounded_gate", 0.5),
    ("attn_gate_left_out", 0.5)])
def test_the_reference_tells_each_fault_apart(fault, least):
    """Each thing the new mechanisms admit going wrong moves some leaf's
    gradient in the reference itself by far more than the float32
    tolerance above (32 tokens here; the chip's `calibrate` has the
    readings at 4,096)."""
    ids, labels = harness.batches(CFG["vocab"], 1, seed=DATA_SEED)
    bad_cfg = dict(CFG, assumed=dict(CFG["assumed"], fault=fault))
    worst = harness.worst_leaf_gap(
        ref, CFG, bad_cfg, ref.split_state(CFG, seeded_params()), ids[0],
        labels[0])
    assert worst > least, (fault, worst)


def test_the_fp8_control_moves_the_reference():
    ids, labels = harness.batches(CFG["vocab"], 1, seed=DATA_SEED)
    state = ref.split_state(CFG, seeded_params())
    key = ref._cfg_key(CFG)
    _, _, want = ref._block_grad(*state, ids[0], labels[0], key, None)
    _, _, got = ref._block_grad(*state, ids[0], labels[0], key, "fp8")
    worst = max(float(jnp.linalg.norm(got[n] - want[n])
                      / jnp.linalg.norm(want[n])) for n in want)
    assert worst > 0.02, worst


# ---------------------------------------------------------------------------
# kda_scan without a bound on the decay
# ---------------------------------------------------------------------------

_REF_CFG = {"reference_scan_tokens_per_block": 8, "assumed": {}}
_KERNEL_SHAPE = dict(b=1, s=192, h=2, dk=128, dv=128)
_ROUTES = ("kda.scan_pallas", "kda.scan_xla", "kda.scan_exact",
           "kda.scan_bounded")


def _planted_operands(seed, b=2, s=128, h=3, dk=16, dv=16):
    """q, k L2-normed as the builder norms them; g mostly mild, 3 % of the
    channels between -5 and -40, and one whole position at -40 on every
    channel; Beta before its sigmoid."""
    rng = np.random.RandomState(seed)
    unit = lambda t: t / np.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    g = -5.0 * rng.uniform(0, 1, (b, s, h, dk)) ** 3
    g = np.where(rng.uniform(size=g.shape) < 0.03,
                 -rng.uniform(5, 40, g.shape), g)
    g[:, 5] = -40.0
    return {"Q": unit(rng.randn(b, s, h, dk)) * dk ** -0.5,
            "K": unit(rng.randn(b, s, h, dk)), "V": rng.randn(b, s, h, dv),
            "G": g, "Beta": rng.randn(b, s, h)}


def _recurrence(ins, beta_scale):
    q, k, v, g, raw = (jnp.asarray(ins[n], jnp.float32)
                       for n in ("Q", "K", "V", "G", "Beta"))
    return ling3.delta_rule(q, k, v, g, beta_scale * jax.nn.sigmoid(raw),
                            _REF_CFG)


@pytest.mark.parametrize("chunk, shape", [
    (16, {}), (64, {}), (64, _KERNEL_SHAPE)],
    ids=["chunk16", "chunk64", "kernel"])
def test_chunked_delta_rule_for_any_decay_is_the_recurrence(chunk, shape):
    """`kda_scan` without a `lower_bound`, `beta` = 2 sigmoid(.), in chunks
    of 16 and 64 (the `jax.numpy` form) and at widths the Pallas kernels
    take (under the interpreter), against the token-by-token recurrence
    with PLANTED decays down to -40 a token: the output at 5e-6 and the
    gradient of every operand by the op's grad rule on the forward's
    residual. The form for a bounded decay reads inf on the same operands
    (a block against itself passes exp(88) there)."""
    ins = {k: jnp.asarray(v, jnp.float32)
           for k, v in _planted_operands(chunk, **shape).items()}
    assert float(ins["G"].min()) == -40.0
    opdef = registry.get("kda_scan")
    ctx = registry.LowerCtx(rng_key=jax.random.key(0))
    attrs = {"chunk_size": chunk, "beta_scale": 2.0}
    routes = [metrics.get(c) for c in _ROUTES]
    with jax.default_matmul_precision("highest"):
        outs = opdef.lower(ctx, {k: [v] for k, v in ins.items()}, attrs)
        want, vjp = jax.vjp(lambda t: _recurrence(t, 2.0), ins)
        cot = jnp.asarray(np.random.RandomState(9).randn(*want.shape),
                          jnp.float32)
        grads = opdef.grad(ctx, {k: [v] for k, v in ins.items()}, attrs,
                           {s: outs[s] for s in opdef.residual_slots},
                           {"Y": [cot]})
        bounded = opdef.lower(ctx, {k: [v] for k, v in ins.items()},
                              dict(attrs, lower_bound=-5.0))["Y"][0]
    # forward and backward of the form asked for, the bounded forward
    assert [metrics.get(c) - r for c, r in zip(_ROUTES, routes)] \
        == ([3, 0, 2, 1] if shape else [0, 3, 2, 1])
    assert not bool(jnp.isfinite(bounded).all())
    y = outs["Y"][0]
    assert bool(jnp.isfinite(y).all())
    assert float(jnp.abs(y - want).max() / jnp.abs(want).max()) < 5e-6
    for name, ref_grad in vjp(cot)[0].items():
        assert bool(jnp.isfinite(grads[name][0]).all()), name
        err = float(jnp.linalg.norm(grads[name][0] - ref_grad)
                    / jnp.linalg.norm(ref_grad))
        assert err < 5e-6, (name, err)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
def test_the_kernels_follow_the_form_for_any_decay(dtype):
    """`ops/pallas/kda_chunk.py` with `exact` beside `ops/kda.py`'s
    `jax.numpy` form on planted decays: `Y`, `States` and the five gradients
    to float32's last digits; in bf16 inside the form's own gap to the
    float32 result on the same rounded rows."""
    from paddle_tpu.ops.pallas import kda_chunk
    ins = _planted_operands(7, b=1, s=128, h=2, dk=128, dv=128)
    ops = tuple(jnp.asarray(ins[n], dtype if n in "QKV" else jnp.float32)
                for n in ("Q", "K", "V", "G")) \
        + (2.0 * jax.nn.sigmoid(jnp.asarray(ins["Beta"], jnp.float32)),)
    plan = kda_chunk.plan(ops[0].shape, ops[2].shape, 64, dtype, exact=True)
    assert plan.exact and plan[:5] == (2, 128, 64, 1, 2)
    cot = jnp.asarray(np.random.RandomState(9).randn(*ops[2].shape), dtype)

    def gaps(got, want):
        return [float(jnp.linalg.norm((g - w).astype(jnp.float32))
                      / jnp.linalg.norm(w.astype(jnp.float32)))
                for g, w in zip(got, want)]

    with jax.default_matmul_precision("highest"):
        exact = tuple(t.astype(jnp.float32) for t in ops)
        y, states = kda._kda_fwd(64, *exact, exact=True)
        want = (y, states) + kda._kda_bwd(
            64, *exact, states, cot.astype(jnp.float32), exact=True)
        y, states = kda._kda_fwd(64, *ops, exact=True)
        form = (y, states) + kda._kda_bwd(64, *ops, states, cot, exact=True)
        y, states = kda_chunk.kda_fwd(plan, *ops)
        got = (y, states) + kda_chunk.kda_bwd(plan, *ops, states, cot)
    assert all(bool(jnp.isfinite(t.astype(jnp.float32)).all()) for t in got)
    for mine, its in zip(gaps(got, want), gaps(form, want)):
        assert mine < (1.25 * its + 1e-4 if dtype == jnp.bfloat16 else 5e-6)


def test_the_bound_picks_the_form_and_the_gates_and_beta():
    """`kda_scan` keeps the products around the blocks' running sums where
    the builder gives a bound of -88 / 16 or above and makes them level by
    level without one or under a lower one; `kda_gate` without a bound is
    -exp(A_log) softplus(.); `beta_scale` 2 puts beta in (0, 2)."""
    q = jnp.zeros((1, 32, 1, 16))
    for attrs, exact in (({"lower_bound": -5.0}, False),
                         ({"lower_bound": -5.5}, False),
                         ({"lower_bound": -6.0}, True), ({}, True)):
        assert kda._form(q, dict(attrs, chunk_size=16)) == (16, exact)
    rng = np.random.RandomState(2)
    x = rng.randn(2, 5, 3 * 4).astype(np.float32) * 3
    a_log = np.log(rng.uniform(1, 16, 3)).astype(np.float32)
    dt_bias = rng.randn(12).astype(np.float32)
    g, = _run_op("kda_gate", {"X": x, "ALog": a_log, "DtBias": dt_bias},
                 ["G"], {})
    pre = (x + dt_bias).reshape(2, 5, 3, 4)
    np.testing.assert_allclose(
        g, -np.exp(a_log)[:, None] * np.logaddexp(0, pre), rtol=1e-5,
        atol=1e-6)
    assert g.dtype == np.float32 and g.max() <= 0 and g.min() < -20
    raw = rng.randn(4, 7).astype(np.float32) * 4
    assert (np.asarray(kda._beta(raw)) < 1).all()
    twice = np.asarray(kda._beta_of({"beta_scale": 2.0})(raw))
    assert twice.max() > 1.9 and twice.min() > 0 and twice.max() < 2
    np.testing.assert_allclose(twice, 2 / (1 + np.exp(-raw)), rtol=1e-6)


# ---------------------------------------------------------------------------
# the shares add up
# ---------------------------------------------------------------------------

def _mixer(kind, cfg, x, params, pre):
    build = (solar.gated_grouped_attention if kind == solar.GQA
             else solar.kda_attention)
    return harness.mixer_program(build, model_config(cfg, seq=x.shape[1]),
                                 x, params, pre)


def _head_share(params, cfg, kind, share, shares):
    """The leaves of one of `shares` equal shares of an uncut mixer's
    heads: columns of the projections into heads (conv kernels, per-head
    parameters and the gate's second factor with them), rows of W_o; what
    every chip holds whole (the low-rank pairs' first factors, the output
    norm's scale) as it is."""
    whole = ("f_a_proj_w", "g_a_proj_w", "o_norm_scale")
    out = {}
    for name, value in params.items():
        value = np.asarray(value)
        if name.endswith(whole):
            out[name] = value
            continue
        axis = 0 if name.endswith("o_proj_w") else value.ndim - 1
        n = value.shape[axis] // shares
        out[name] = np.take(value, range(share * n, (share + 1) * n), axis)
    return out


@pytest.mark.parametrize("kind, n", [(solar.GQA, 0), (solar.KDA, 1)])
def test_the_head_shares_add_up_to_the_uncut_layer(kind, n):
    """Layer n's mixer uncut (8 heads; 4 KV heads) and as the four shares a
    layer's heads are divided into here (2 heads each; query heads on one KV
    head): the shares' outputs, each through the program built for the held
    heads, add up to the uncut reference's, and each is the reference's
    share."""
    whole = dict(CFG, num_attention_heads=8, num_key_value_heads=4,
                 linear_attn_config=dict(CFG["linear_attn_config"],
                                         num_heads=8))
    pre = f"l{n}_"
    names = [k for k in ref.param_shapes(whole) if k.startswith(pre) and not (
        k.endswith(("attn_norm_scale", "ffn_norm_scale")) or any(
            part in k for part in ("experts_", "shared_", "router_")))]
    all_params = ref.init_params(whole, jax.random.key(5))
    params = {k: np.asarray(all_params[k]) for k in names}
    if kind == solar.KDA:      # a gate's bias that is not 0
        params[pre + "g_b_proj_b"] = np.random.RandomState(1).randn(
            *params[pre + "g_b_proj_b"].shape).astype(np.float32)
    x = np.random.RandomState(4).randn(2, S, 64).astype(np.float32)
    fn = ref.attention if kind == solar.GQA else ref.kda_attention
    with jax.default_matmul_precision("highest"):
        want = np.asarray(fn(jnp.asarray(x), {
            k: jnp.asarray(v) for k, v in params.items()}, pre, whole)[0])
        total = 0.0
        for share in range(4):
            held = _head_share(params, whole, kind, share, 4)
            got = _mixer(kind, CFG, x, held, pre)
            part = np.asarray(fn(jnp.asarray(x), {
                k: jnp.asarray(v) for k, v in held.items()}, pre, CFG)[0])
            np.testing.assert_allclose(got, part, rtol=2e-4, atol=2e-6)
            total = total + got
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=5e-6)


def _expert_cfg(held, total, offset, top_k):
    return dict(n_routed_experts=held, experts_total=total,
                expert_offset=offset, num_experts_per_tok=top_k,
                norm_topk_prob=True, routed_scaling_factor=1, assumed={})


def test_the_ranks_routed_parts_and_the_shared_expert_add_up():
    """16 experts cut into 8 ranks of 2, top-4: the routed parts all ranks
    give plus the shared expert COUNTED ONCE are the uncut reference's
    layer; every rank's TopIdx is the reference's choice and the loads are
    its counts."""
    x, params = harness.uncut_expert_layer(16)
    p = {"l_" + k: jnp.asarray(v) for k, v in params.items()}
    with jax.default_matmul_precision("highest"):
        whole, want_idx = ref.expert_layer(jnp.asarray(x)[None], p, "l_",
                                           _expert_cfg(16, 16, 0, 4))
    want_idx = np.asarray(want_idx)
    total, loads = 0.0, []
    for offset in range(0, 16, 2):
        out, idx, load = harness.routed_share(
            x, harness.held_arrays(params, offset, 2), 4, 16, offset)
        total = total + out
        loads.append(load)
        assert (np.sort(idx, 1) == np.sort(want_idx, 1)).all()
    shared = np.asarray(ref.swiglu_ffn(
        jnp.asarray(x), p["l_shared_gate_w"], p["l_shared_up_w"],
        p["l_shared_down_w"]))
    np.testing.assert_allclose(total + shared, np.asarray(whole)[0],
                               rtol=2e-4, atol=2e-5)
    assert (np.concatenate(loads) == np.bincount(
        want_idx.reshape(-1), minlength=16)).all()


@pytest.mark.parametrize("rank", [0, 17, 39])
def test_a_router_of_320_over_40_ranks(rank):
    """320 experts, the first router width that is neither a power of two
    nor a whole number of lane tiles (2.5 x 128), top-8, 8 held a rank of
    40, the first count of ranks that is no power of two: a rank's routed
    part, choice and loads are the reference's."""
    x, params = harness.uncut_expert_layer(320, n=160, seed=rank)
    offset = 8 * rank
    out, idx, load = harness.routed_share(
        x, harness.held_arrays(params, offset, 8), 8, 320, offset)
    cut = {"l_" + k: jnp.asarray(v if k.startswith(("router", "shared"))
                                 else v[offset:offset + 8])
           for k, v in params.items()}
    with jax.default_matmul_precision("highest"):
        want, want_idx = ref.routed_experts(
            jnp.asarray(x), cut, "l_", _expert_cfg(8, 320, offset, 8))
    want_idx = np.asarray(want_idx)
    assert idx.shape == (160, 8) and want_idx.max() >= 300
    assert (np.sort(idx, 1) == np.sort(want_idx, 1)).all()
    np.testing.assert_allclose(out, np.asarray(want), rtol=2e-4, atol=2e-6)
    assert (load == np.bincount(want_idx.reshape(-1), minlength=320)[
        offset:offset + 8]).all()


# ---------------------------------------------------------------------------
# the builder
# ---------------------------------------------------------------------------

def test_builder_names_scopes_and_checkpoints_and_verifies():
    from paddle_tpu.analysis import verifier
    from paddle_tpu.observability import scopes, trace
    reset_programs(0)
    trace.clear()
    cfg = solar.SolarConfig.tiny()
    _, loss, routed = solar.build_causal_lm_program(cfg)
    built = [e for e in trace.events() if e["name"] == "program.build"]
    assert built and built[-1]["args"]["model"] == "solar"
    prog = fluid.default_main_program()
    ops = prog.global_block().ops
    kinds = {"kda_scan": "K", "fused_attention": "A", "routed_moe": "E"}
    assert "".join(kinds[op.type] for op in ops
                   if op.type in kinds) == "AEKEKEKE"
    assert [cfg.kind(n) for n in cfg.layers_here()] == [
        "gqa", "kda", "kda", "kda"]
    names = {p.name: tuple(p.shape)
             for p in prog.global_block().all_parameters()}
    assert names["l0_g_proj_w"] == (64, 32) == names["l0_q_proj_w"]
    assert names["l0_k_proj_w"] == (64, 16)
    assert names["l1_f_a_proj_w"] == (64, 16) == names["l1_g_a_proj_w"]
    assert names["l1_f_b_proj_w"] == (16, 32) == names["l1_g_b_proj_w"]
    assert names["l1_g_b_proj_b"] == (32,) and names["l3_A_log"] == (2,)
    assert names["l2_router_w"] == (64, 8)
    assert names["l2_experts_up_w"] == (4, 64, 32)
    assert not any(n.startswith("l4_") or "mlp_" in n for n in names)
    found = {op.attrs.get("name_scope") for op in ops}
    want = {"kda.proj", "kda.conv", "kda.gate", "kda.scan", "kda.out",
            "attn.proj", "attn.attend.full", "moe.shared"}
    assert want <= found and want <= set(scopes.CATALOGUE)
    scan = next(op for op in ops if op.type == "kda_scan")
    assert "lower_bound" not in scan.attrs
    assert (scan.attrs["chunk_size"], scan.attrs["beta_scale"],
            scan.attrs["name_scope"]) == (16, 2.0, "kda.scan")
    gate = next(op for op in ops if op.type == "kda_gate")
    assert "lower_bound" not in gate.attrs
    # no rotary anywhere; one element-wise gate a layer
    assert [op.type for op in ops].count("rotary_embedding") == 0
    assert [op.type for op in ops].count("head_gate") == 4
    moe_ops = [op for op in ops if op.type == "routed_moe"]
    assert all("ExpertGate" in op.inputs and "SelectBias" in op.inputs
               and op.attrs["experts_total"] == 8 for op in moe_ops)
    assert len(loss._layer_checkpoints) == 4 and len(routed) == 4
    paddle.optimizer.Adam(1e-4).minimize(loss)
    errors = [f for f in verifier.verify_program(prog)
              if f.severity == "error"]
    assert not errors, errors
    rules = solar.sharding_rules()
    assert tuple(rules.spec_for("l2_experts_up_w")) == ("ep",)
    assert tuple(rules.spec_for("l1_f_b_proj_w")) == (None, "tp")
    assert tuple(rules.spec_for("l1_f_a_proj_w")) == ()
    assert tuple(rules.spec_for("l0_g_proj_w")) == (None, "tp")
    assert tuple(rules.spec_for("l1_g_b_proj_b")) == ("tp",)
    assert tuple(rules.spec_for("l1_o_proj_w")) == ("tp", None)
    for key, value in (("use_rope", True), ("kda_use_full_proj", True),
                       ("first_k_dense_replace", 1), ("heads_held", 9)):
        with pytest.raises(ValueError, match=key):
            reset_programs(0)
            solar.build_causal_lm_program(solar.SolarConfig(
                **{**vars(solar.SolarConfig.tiny()), key: value}))


def test_grouped_attention_without_a_gate_builds_no_gate():
    """`gate` False (every builder the benchmark had): no `head_gate` op, no
    `g_proj_w`; with it one of each, between the attention and the output
    projection, inside the scope `attn.proj`, which `attn_proj_time_pct`
    reads."""
    def built(gate):
        reset_programs(0)
        cfg = solar.SolarConfig.tiny()
        x = fluid.layers.data(name="x", shape=[cfg.seq_len, 64],
                              dtype="float32")
        causal_lm.grouped_attention(x, cfg, "l0_", 2, 1, gate=gate)
        block = fluid.default_main_program().global_block()
        return ([op.type for op in block.ops],
                {p.name for p in block.all_parameters()},
                {op.type: op.attrs.get("name_scope") for op in block.ops})

    ops, names, found = built(False)
    assert "head_gate" not in ops and "l0_g_proj_w" not in names
    gated, names, found = built(True)
    assert "l0_g_proj_w" in names and found["head_gate"] == "attn.proj"
    at = gated.index("head_gate")
    assert gated[:at - 1] + gated[at + 1:] == ops
    assert gated[at - 1] == "mul" and "fused_attention" in gated[:at]


def test_an_amp_step_under_recomputation_counts_its_routes():
    """One trace of the tiny AMP train step with a checkpoint at every layer
    boundary (the cell's way): three delta-rule layers, each scan the form
    for an unbounded decay (heads of 16: the `jax.numpy` lowering), forward,
    the forward once more in its segment, and backward."""
    cfg = solar.SolarConfig.tiny()
    exe, loss, ids = harness.amp_step(solar, cfg, recompute=True)
    _, rise = counter_rise(
        lambda: str(exe.step_jaxpr({"tokens": ids}, [loss], k=2)),
        ("kda.layers_lowered", "kda.scan_xla", "kda.scan_pallas",
         "kda.scan_exact", "kda.scan_bounded", "moe.layers_lowered"))
    assert rise == (3, 9, 0, 9, 0, 4)
