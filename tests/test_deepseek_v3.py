"""The DeepSeek-V3-family LM (`models/deepseek_v3.py`: latent attention,
sigmoid-routed experts without drops, shared experts, one expert-parallel
rank's share) against its plain float32 reference
(`benchmark/reference/kanana2.py`), on the CPU at tiny widths with seeded
weights: loss, every gradient leaf, the state after two Adam steps, the
shares adding up to the uncut layer, no drops under skew, and the new ops
against `jax.numpy`.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import causal_lm_harness as harness
from causal_lm_harness import B, S, counter_rise, run_op as _run_op

import paddle_tpu as paddle
import paddle_tpu.fluid as fluid
from paddle_tpu.models import deepseek_v3 as ds
from paddle_tpu.observability import metrics
from paddle_tpu.testing import reset_programs
from benchmark.reference import kanana2 as ref

CFG = dict(hidden_size=64, num_attention_heads=4, qk_nope_head_dim=16,
           qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
           intermediate_size=128, moe_intermediate_size=32,
           n_routed_experts=4, experts_total=8, expert_offset=2,
           n_shared_experts=2, num_experts_per_tok=2,
           first_k_dense_replace=1, routed_scaling_factor=2.448,
           norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=1e6, layers=3,
           vocab=256,
           assumed={"initializer_std": 0.02, "select_bias_std": 0.05})
SHARED = ("hidden_size", "num_attention_heads", "qk_nope_head_dim",
          "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
          "intermediate_size", "moe_intermediate_size", "n_shared_experts",
          "num_experts_per_tok", "first_k_dense_replace",
          "routed_scaling_factor", "norm_topk_prob", "rms_norm_eps",
          "rope_theta")


def model_config(cfg):
    return ds.DeepseekV3Config(
        vocab_size=cfg["vocab"], num_hidden_layers=cfg["layers"],
        n_routed_experts=cfg["experts_total"],
        experts_held=cfg["n_routed_experts"],
        expert_offset=cfg["expert_offset"], seq_len=S,
        **{k: cfg[k] for k in SHARED})


def seeded_params():
    return ref.init_params(CFG, jax.random.key(3))


def trained_program(amp, k, ids):
    return harness.trained_program(ds, model_config(CFG), ref,
                                   seeded_params(), amp, k, ids)


# Tolerances. float32: the program and the reference differ in the order of
# their float32 sums (XLA's CPU dots against `highest`-precision matmuls),
# 1e-6 relative on a leaf. AMP: every matmul operand is rounded to bf16
# (8 bits of mantissa, 2^-9 = 0.2 % an operand); over a leaf's gradient the
# roundings average to well under 2 % of the leaf's norm, and Adam's first
# two steps move each weight by at most lr x 1.0 a step whatever the
# gradient's size, so a weight differs by at most 4 lr where a tiny gradient
# changed sign in both steps; over a leaf such weights are few (one of the
# 32 of a latent norm's scale is 2 lr of a change of norm 8 lr): the
# parameters' change differs by under 30 % of its norm on any leaf.
@pytest.mark.parametrize("amp, grad_tol, loss_tol", [
    (False, 2e-5, 1e-6), (True, 2e-2, 2e-4)], ids=["float32", "amp"])
def test_program_follows_the_reference(amp, grad_tol, loss_tol):
    # the data seed is one at which no token sits at a near-tie of two
    # experts' scores in either expert layer: where bf16 rounding upstream
    # moves a choice, one token of the 128 going to another expert is 10 to
    # 20 % of a leaf's gradient at this size, and the comparison would be
    # of routings, not of arithmetic (on the chip `route_mismatch_share`
    # is that comparison)
    ids, labels = harness.batches(CFG["vocab"], 2, seed=1)
    states, ref_idx = harness.reference_states(
        ref, CFG, ref.split_state(CFG, seeded_params()), 2, ids, labels)

    # one step: Adam's first moment is (1 - beta1) x the gradient, leaf by
    # leaf
    losses, idx, scope = trained_program(amp, 1, ids)
    loss1, grads1 = states[0][0], states[0][1]
    assert abs(losses[0] - loss1) / loss1 < loss_tol
    for name, err in harness.first_step_gaps(scope, grads1, ref).items():
        assert err < grad_tol, (name, err)
    assert harness.route_mismatch(idx[0], ref_idx) == 0
    # two steps: losses, parameters and both moments
    losses, _, scope = trained_program(amp, 2, ids)
    for t in range(2):
        assert abs(losses[t] - states[t][0]) / states[t][0] < loss_tol
    lr = ref.ADAM["lr"]
    for name, worst, gap, moved, moments in harness.second_step_gaps(
            scope, states, seeded_params()):
        assert worst <= (4.1 * lr if amp else 1e-2 * lr), name
        assert gap <= (0.3 if amp else 1e-3) * moved, name
        for acc, err in moments.items():
            assert err < 2 * grad_tol, (name, acc, err)


def _layer_share(x, params, offset, held, total, bias=True, norm=True,
                 scaling=2.448, top_k=3, **grad):
    """One share's `routed_moe` output, TopIdx and ExpertLoad through a
    Program, experts `offset` .. `offset + held` of `total` (with `cot` in
    `grad`: its gradients, `harness.routed_share`)."""
    return harness.routed_share(
        x, harness.held_arrays(params, offset, held, bias=bias), top_k,
        total, offset, routed_scaling=scaling, norm_topk=norm, **grad)


def _uncut_layer(seed=0, skew=None, n=96, d=32, f=16, total=16, top_k=3):
    rng = np.random.RandomState(seed)
    params = {"router_w": rng.randn(d, total).astype(np.float32) * 0.3,
              "router_bias": rng.randn(total).astype(np.float32) * 0.1,
              "experts_gate_w": rng.randn(total, d, f).astype(np.float32) * .2,
              "experts_up_w": rng.randn(total, d, f).astype(np.float32) * .2,
              "experts_down_w": rng.randn(total, f, d).astype(np.float32) * .2,
              "shared_gate_w": rng.randn(d, 2 * f).astype(np.float32) * .2,
              "shared_up_w": rng.randn(d, 2 * f).astype(np.float32) * .2,
              "shared_down_w": rng.randn(2 * f, d).astype(np.float32) * .2}
    if skew is not None:
        params["router_bias"][skew] += 0.8
    x = rng.randn(n, d).astype(np.float32)
    cfg = dict(n_routed_experts=total, experts_total=total, expert_offset=0,
               num_experts_per_tok=top_k, routed_scaling_factor=2.448)
    return x, params, cfg


def _reference_layer(x, params, cfg, **route_kw):
    p = {"l_" + k: jnp.asarray(v) for k, v in params.items()}
    routed, idx = ref.routed_experts(jnp.asarray(x), p, "l_", cfg,
                                     **route_kw)
    return (np.asarray(routed + ref.shared_expert(jnp.asarray(x), p, "l_")),
            np.asarray(idx))


def test_shares_add_up_to_the_uncut_layer():
    """16 experts cut into 4 shares of 4: the parts all shares give, the
    shared expert counted once, are the uncut reference's layer output, and
    every share's TopIdx is the reference's choice."""
    x, params, cfg = _uncut_layer()
    want, want_idx = _reference_layer(x, params, cfg)
    total = np.asarray(ref.shared_expert(
        jnp.asarray(x), {"l_" + k: jnp.asarray(v)
                         for k, v in params.items()}, "l_"))
    loads = []
    for offset in (0, 4, 8, 12):
        out, idx, load = _layer_share(x, params, offset, 4, 16)
        total = total + out
        loads.append(load)
        assert (idx == want_idx).all()
    # float32 sums in another order
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-6)
    assert int(np.concatenate(loads).sum()) == x.shape[0] * 3
    assert (np.concatenate(loads) == np.bincount(
        want_idx.reshape(-1), minlength=16)).all()


def test_no_token_is_dropped_under_skew():
    """A selection bias that sends 9 tokens in 10 to one expert: the layer
    still is the reference's (nothing is dropped: the op has no capacity),
    and only the loads say so. Leaving the bias, the normalisation or the
    scaling factor out fails the same comparison."""
    x, params, cfg = _uncut_layer(seed=1, skew=5, n=128)
    want, want_idx = _reference_layer(x, params, cfg)
    out, idx, load = _layer_share(x, params, 0, 16, 16)
    shared = np.asarray(ref.shared_expert(
        jnp.asarray(x), {"l_" + k: jnp.asarray(v)
                         for k, v in params.items()}, "l_"))
    assert load[5] >= 0.9 * x.shape[0] and load.sum() == x.shape[0] * 3
    assert load.max() / load.mean() > 4
    assert (idx == want_idx).all()
    np.testing.assert_allclose(out + shared, want, rtol=2e-5, atol=2e-6)
    for fault in (dict(bias=False), dict(norm=False), dict(scaling=1.0)):
        bad, bad_idx, _ = _layer_share(x, params, 0, 16, 16, **fault)
        gap = np.abs(bad + shared - want).max() / np.abs(want).max()
        assert gap > 0.05 or (bad_idx != want_idx).mean() > 0.2, fault
    # and the reference says the same of itself
    for kw in (dict(use_bias=False), dict(norm=False), dict(scaling=1.0)):
        other, _ = _reference_layer(x, params, cfg, **kw)
        assert np.abs(other - want).max() / np.abs(want).max() > 0.05, kw


def test_routed_moe_counts_its_lowerings():
    before = metrics.get("moe.layers_lowered")
    x, params, _ = _uncut_layer()
    _layer_share(x, params, 0, 4, 16)
    assert metrics.get("moe.layers_lowered") == before + 1


def test_record_expert_load_sets_the_gauges():
    loads = np.array([[[10, 30, 20, 20]], [[20, 20, 20, 20]]])  # [L, k, E]
    got = ds.record_expert_load(loads, tokens=100)
    assert got["local_assignments_per_token"] == pytest.approx(0.8)
    assert got["load_max_over_mean"] == pytest.approx((1.5 + 1.0) / 2)
    assert metrics.get("moe.local_assignments_per_token") == pytest.approx(
        0.8)
    assert metrics.get("moe.tokens_dropped") == 0


def test_rms_norm_op():
    rng = np.random.RandomState(0)
    x = rng.randn(3, 5, 16).astype(np.float32)
    scale = rng.rand(16).astype(np.float32) + 0.5
    y, = _run_op("rms_norm", {"X": x, "Scale": scale}, ["Y"],
                 {"epsilon": 1e-6})
    want = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-6) * scale
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)
    half, = _run_op("rms_norm", {"X": x.astype(jnp.bfloat16),
                                 "Scale": scale}, ["Y"], {})
    assert half.dtype == jnp.bfloat16


def test_rotary_embedding_op_turns_interleaved_pairs_of_the_last_features():
    rng = np.random.RandomState(1)
    x = rng.randn(2, 3, 7, 24).astype(np.float32)      # [B, nh, S, D]
    out, = _run_op("rotary_embedding", {"X": x}, ["Out"],
                   {"theta": 1e6, "rotary_dim": 8})
    np.testing.assert_array_equal(out[..., :16], x[..., :16])
    pos = np.arange(7)[:, None]
    freq = 1.0 / 1e6 ** (np.arange(0, 8, 2) / 8)
    z = (x[..., 16::2] + 1j * x[..., 17::2]) * np.exp(1j * pos * freq)
    np.testing.assert_allclose(out[..., 16::2], z.real, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out[..., 17::2], z.imag, rtol=1e-5, atol=1e-5)
    # the reference's own rotary is the same function
    np.testing.assert_allclose(
        out[..., 16:], np.asarray(ref.rope(jnp.asarray(x[..., 16:]), 1e6)),
        rtol=1e-5, atol=1e-5)
    # q . k depends on the distance of the positions alone
    same = np.broadcast_to(x[:1, :1, :1], x.shape).copy()
    turned, = _run_op("rotary_embedding", {"X": same}, ["Out"],
                      {"theta": 1e6, "rotary_dim": 8})
    rot = turned[0, 0, :, 16:]
    assert np.dot(rot[1], rot[3]) == pytest.approx(np.dot(rot[4], rot[6]),
                                                   rel=1e-4)


def test_swiglu_op():
    rng = np.random.RandomState(2)
    g, u = rng.randn(4, 9).astype(np.float32), rng.randn(4, 9).astype(
        np.float32)
    out, = _run_op("swiglu", {"Gate": g, "Up": u}, ["Out"], {})
    np.testing.assert_allclose(out, g / (1 + np.exp(-g)) * u, rtol=1e-5,
                               atol=1e-6)


def test_new_ops_have_specs_and_amp_placement():
    from paddle_tpu.amp.auto_cast import (black_list, keep_f32_slots,
                                          white_list)
    from paddle_tpu.analysis import op_specs  # noqa: F401
    from paddle_tpu.ops import registry
    for op in ("rms_norm", "rotary_embedding", "swiglu", "routed_moe"):
        assert registry.get_spec(op) is not None, op
    assert "rms_norm" in black_list and "routed_moe" in white_list
    assert keep_f32_slots["routed_moe"] >= {"X", "GateW", "SelectBias"}


def test_builder_names_scopes_and_checkpoints_and_verifies():
    from paddle_tpu.analysis import verifier
    reset_programs(0)
    _, loss, routed = ds.build_causal_lm_program(ds.DeepseekV3Config.tiny())
    prog = fluid.default_main_program()
    scopes = {op.attrs.get("name_scope") for op in prog.global_block().ops}
    assert {"mla.proj", "mla.attend", "moe.shared"} <= scopes
    assert len(loss._layer_checkpoints) == 3 and len(routed) == 2
    paddle.optimizer.Adam(1e-4).minimize(loss)
    adam = [op for op in prog.global_block().ops if op.type == "adam"]
    assert adam and all(op.attrs["name_scope"] == "optimizer.adam"
                        for op in adam)
    bias = [p for p in prog.all_parameters()
            if p.name.endswith("router_bias")]
    assert len(bias) == 2 and not any(p.trainable for p in bias)
    assert not any(op.inputs.get("Param") == [p.name]
                   for op in adam for p in bias)
    errors = [f for f in verifier.verify_program(prog) if f.severity == "error"]
    assert not errors, errors
    rules = ds.sharding_rules()
    assert tuple(rules.spec_for("l1_experts_up_w")) == ("ep",)
    assert tuple(rules.spec_for("l0_q_proj_w")) == (None, "tp")


# ---------------------------------------------------------------------------
# routed_moe's grad rule (ops/moe.py `_routed_moe_grad`): the backward on the
# h, u and sort the forward wrote, six grouped matmuls and none of the
# forward's again
# ---------------------------------------------------------------------------

_MOE_COUNTERS = ("moe.bwd_residual", "moe.bwd_recomputed")


# once per grouped matmul lowered, by the way its shapes sent it: the
# Pallas kernels, `jax.lax.ragged_dot`
_GROUPED_COUNTERS = ("moe.grouped_pallas", "moe.grouped_xla")


def _share_gradients(x, params, cot, offset, held, total, withhold=False):
    """d sum(Out * cot) / d (x, GateW, ExpertGate, ExpertUp, ExpertDown) of
    one share's `routed_moe` through a Program, and the counters' rise
    while its step was traced."""
    return counter_rise(lambda: _layer_share(
        x, params, offset, held, total, cot=cot, withhold=withhold),
        _MOE_COUNTERS)


def _reference_share_gradients(x, params, cot, offset, held, total, top_k=3):
    cfg = dict(n_routed_experts=held, experts_total=total,
               expert_offset=offset, num_experts_per_tok=top_k,
               routed_scaling_factor=2.448)
    sl = slice(offset, offset + held)

    def loss(x, router_w, eg, eu, ed):
        p = {"l_router_w": router_w,
             "l_router_bias": jnp.asarray(params["router_bias"]),
             "l_experts_gate_w": eg, "l_experts_up_w": eu,
             "l_experts_down_w": ed}
        out, _ = ref.routed_experts(x, p, "l_", cfg)
        return jnp.sum(out * cot)

    return [np.asarray(g) for g in jax.grad(loss, argnums=(0, 1, 2, 3, 4))(
        jnp.asarray(x), jnp.asarray(params["router_w"]),
        *(jnp.asarray(params[f"experts_{n}_w"][sl])
          for n in ("gate", "up", "down")))]


_GRAD_NAMES = ("X", "GateW", "ExpertGate", "ExpertUp", "ExpertDown")


@pytest.fixture(scope="module")
def _share_case():
    x, params, _ = _uncut_layer(seed=2, skew=5)
    cot = np.random.RandomState(7).randn(*x.shape).astype(np.float32)
    got, rise = _share_gradients(x, params, cot, 4, 4, 16)
    want = _reference_share_gradients(x, params, cot, 4, 4, 16)
    _, _, load = _layer_share(x, params, 4, 4, 16)
    return got, rise, want, load, (x, params, cot)


@pytest.mark.parametrize("leaf", range(5), ids=_GRAD_NAMES)
def test_grad_rule_follows_the_reference_layer(leaf, _share_case):
    """A share of 4 of 16 experts under a skewed selection: most slots are
    foreign, the held experts' loads uneven; the rule's gradient of every
    input is `jax.grad`'s of the plain float32 reference layer."""
    got, rise, want, load, _ = _share_case
    assert rise == (1, 0)
    assert load.sum() < 0.5 * 96 * 3 and load.max() > 3 * max(load.min(), 1)
    err = np.linalg.norm(got[leaf] - want[leaf]) / np.linalg.norm(want[leaf])
    assert err < 2e-5, (_GRAD_NAMES[leaf], err)
    assert np.linalg.norm(want[leaf]) > 0


def test_generic_route_agrees_when_the_residuals_are_withheld(_share_case):
    """A program whose `routed_moe` lacks the rule's outputs: the rule
    declines, the generic `__vjp__` lowers the forward again, and its
    gradients are the rule's (one algebra: `_experts_bwd`)."""
    by_rule, _, _, _, inputs = _share_case
    generic, rise = _share_gradients(*inputs, 4, 4, 16, withhold=True)
    assert rise == (0, 1)
    for name, a, b in zip(_GRAD_NAMES, by_rule, generic):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7, err_msg=name)


def _census_trainer(withhold=False):
    """A tiny AMP train step whose expert buffers' shapes are no other
    value's: k*N = 256 rows of d = 64 (the vocabulary is not 256)."""
    cfg = dict(CFG, vocab=320)
    exe, loss, _ = harness.train_step(
        ds, model_config(cfg), True,
        built=harness.withhold_residuals if withhold else None)
    ids = np.random.RandomState(0).randint(0, 320, (2, B, S)).astype(np.int64)
    return exe, loss, {"tokens": ids}


def test_train_step_census_nine_grouped_matmuls_a_layer():
    """A trace of the step lowers 9 grouped matmuls an expert layer (3
    forward, 6 backward; 12 with the forward's three repeated), counted by
    `moe.grouped_pallas` + `moe.grouped_xla` (at this preset's widths, under
    one lane tile of 128, every one is a `ragged_dot_general`), nothing
    under `jax.checkpoint`, and no float32 value of the `[k, N, d]` /
    `[k*N, d]` buffers' size, forward or backward: the combine weight goes
    in ahead of the down projection, over f columns."""
    expert_layers = CFG["layers"] - CFG["first_k_dense_replace"]
    k, n, d = CFG["num_experts_per_tok"], B * S, CFG["hidden_size"]
    exe, loss, feed = _census_trainer()
    census = _MOE_COUNTERS + _GROUPED_COUNTERS
    jaxpr, rise = counter_rise(
        lambda: str(exe.step_jaxpr(feed, [loss], k=2)), census)
    assert rise == (expert_layers, 0, 0, 9 * expert_layers)
    assert jaxpr.count("ragged_dot_general") == 9 * expert_layers
    assert "checkpoint" not in jaxpr and "remat" not in jaxpr
    assert f"bf16[{k * n},{d}]" in jaxpr
    for wide in (f"f32[{k},{n},{d}]", f"f32[{k * n},{d}]"):
        assert wide not in jaxpr, wide
    # the generic route on the same model: the forward's three again
    exe, loss, feed = _census_trainer(withhold=True)
    jaxpr, rise = counter_rise(
        lambda: str(exe.step_jaxpr(feed, [loss], k=2)), census)
    assert rise == (0, expert_layers, 0, 12 * expert_layers)
    assert jaxpr.count("ragged_dot_general") == 12 * expert_layers
