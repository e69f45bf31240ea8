"""The Mellum-2-family LM (`models/mellum.py`: sliding-window and full
attention layers in a period, query heads grouped on fewer KV heads, yarn on
the full layers, softmax-routed experts, one expert-parallel rank's share)
against its plain float32 reference (`benchmark/reference/mellum2.py`), on
the CPU at tiny widths with seeded weights; and what the model forced on the
ops: the flash kernels' window and grouped KV heads under the Pallas
interpreter against the dense route, the rotary frequency rules against
hand-worked numbers, softmax scoring's grad rule.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import causal_lm_harness as harness
from causal_lm_harness import S, counter_rise, run_op as _run_op

import paddle_tpu as paddle
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.models import mellum
from paddle_tpu.observability import metrics
from paddle_tpu.ops import attention, llm_ops
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.testing import reset_programs
from benchmark.reference import mellum2 as ref

SLIDING, FULL = "sliding_attention", "full_attention"
ROPE = {FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
               "original_max_position_embeddings": 8192, "beta_fast": 32,
               "beta_slow": 1, "attention_factor": 1.2772588722239782},
        SLIDING: {"rope_type": "default", "rope_theta": 500000}}
CFG = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
           head_dim=16, moe_intermediate_size=32, num_experts=4,
           experts_total=8, expert_offset=2, num_experts_per_tok=2,
           norm_topk_prob=True, rms_norm_eps=1e-6, sliding_window=8,
           layer_types=[SLIDING, SLIDING, SLIDING, FULL],
           rope_parameters=ROPE, layers=4, vocab=256,
           assumed={"initializer_std": 0.02})
SHARED = ("hidden_size", "num_attention_heads", "num_key_value_heads",
          "head_dim", "moe_intermediate_size", "num_experts_per_tok",
          "norm_topk_prob", "rms_norm_eps", "sliding_window",
          "rope_parameters")


def model_config(cfg):
    return mellum.MellumConfig(
        vocab_size=cfg["vocab"], num_hidden_layers=cfg["layers"],
        num_experts=cfg["experts_total"], experts_held=cfg["num_experts"],
        expert_offset=cfg["expert_offset"], seq_len=S,
        layer_types=tuple(cfg["layer_types"]),
        **{k: cfg[k] for k in SHARED})


def seeded_params():
    return ref.init_params(CFG, jax.random.key(3))


def trained_program(amp, k, ids):
    return harness.trained_program(mellum, model_config(CFG), ref,
                                   seeded_params(), amp, k, ids)


# Tolerances, as in test_deepseek_v3.py. float32: the program and the
# reference differ in the order of their float32 sums, 1e-6 relative on a
# leaf. AMP: every matmul operand is rounded to bf16 (2^-9 = 0.2 % an
# operand); over a leaf's gradient the roundings average to under 2 % of the
# leaf's norm, and Adam's first two steps move each weight by at most lr a
# step whatever the gradient's size, so a weight differs by at most 4 lr
# where a tiny gradient changed sign in both steps; over a leaf such weights
# are few: the parameters' change differs by under 30 % of its norm.
@pytest.mark.parametrize("amp, grad_tol, loss_tol", [
    (False, 2e-5, 1e-6), (True, 2e-2, 2e-4)], ids=["float32", "amp"])
def test_program_follows_the_reference(amp, grad_tol, loss_tol):
    # the data seed is one at which no token sits at a near-tie of two
    # experts' scores in any of the four layers, at either step, under bf16
    # rounding (one seed in eight at this size: softmax scores of weights
    # drawn at 0.02 lie close together): one token of the 128 going to
    # another expert is 10 to 20 % of a leaf's gradient here, a comparison
    # of routings and not of arithmetic (on the chip `route_mismatch_share`
    # is that comparison)
    ids, labels = harness.batches(CFG["vocab"], 2, seed=DATA_SEED)
    states, ref_idx = harness.reference_states(
        ref, CFG, (seeded_params(),), 2, ids, labels)

    losses, idx, scope = trained_program(amp, 1, ids)
    loss1, grads1 = states[0][0], states[0][1]
    assert abs(losses[0] - loss1) / loss1 < loss_tol
    for name, err in harness.first_step_gaps(scope, grads1, ref).items():
        assert err < grad_tol, (name, err)
    assert harness.route_mismatch(idx[0], ref_idx) == 0
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


DATA_SEED = 3


@pytest.mark.parametrize("fault, moved", [
    (dict(sliding_window=S), "window ignored in the sliding layers"),
    (dict(rope_parameters=dict(ROPE, **{FULL: ROPE[SLIDING]})),
     "yarn left out of the full layers"),
    (dict(assumed=dict(CFG["assumed"], scoring="sigmoid")),
     "sigmoid scores"),
    (dict(assumed=dict(CFG["assumed"], kv_head_rule="first")),
     "KV head 0 served to every query head")], ids=lambda v: (
         v if isinstance(v, str) else "cfg"))
def test_the_reference_tells_each_fault_apart(fault, moved):
    """What the new mechanisms admit going wrong each moves the reference's
    own gradients by far more than any tolerance above."""
    ids, labels = harness.batches(CFG["vocab"], 1, seed=DATA_SEED)
    worst = harness.worst_leaf_gap(ref, CFG, dict(CFG, **fault),
                                   (seeded_params(),), ids[0], labels[0])
    assert worst > 0.1, (moved, worst)


# ---------------------------------------------------------------------------
# the expert layer with softmax scores
# ---------------------------------------------------------------------------

def _uncut_layer(seed=0, skew=None, n=96, d=32, f=16, total=16):
    rng = np.random.RandomState(seed)
    params = {"router_w": rng.randn(d, total).astype(np.float32) * 0.3,
              "experts_gate_w": rng.randn(total, d, f).astype(np.float32) * .2,
              "experts_up_w": rng.randn(total, d, f).astype(np.float32) * .2,
              "experts_down_w": rng.randn(total, f, d).astype(np.float32) * .2}
    x = rng.randn(n, d).astype(np.float32)
    if skew is not None:
        # a router column that follows the tokens' common direction
        x = x + 1.0
        params["router_w"][:, skew] = 0.5
    return x, params


def _ref_cfg(held, total, offset, top_k=3, **assumed):
    return dict(num_experts=held, experts_total=total, expert_offset=offset,
                num_experts_per_tok=top_k, norm_topk_prob=True,
                assumed=assumed)


def _share_program(x, params, offset, held, total, top_k=3, **grad):
    """One share's `routed_moe` (softmax scoring, no bias) through a
    Program: [Out, TopIdx, ExpertLoad], or with `cot` the gradients of
    sum(Out * cot) with respect to (x, GateW, ExpertGate, ExpertUp,
    ExpertDown) (`harness.routed_share`)."""
    return harness.routed_share(
        x, harness.held_arrays(params, offset, held), top_k, total, offset,
        scoring="softmax", **grad)


def _reference_layer(x, params, cfg):
    p = {"l_" + k: jnp.asarray(v) for k, v in params.items()}
    out, idx = ref.routed_experts(jnp.asarray(x), p, "l_", cfg)
    return np.asarray(out), np.asarray(idx)


def test_the_four_ranks_parts_add_up_to_the_uncut_layer():
    """16 experts cut into 4 shares of 4, as the configuration cuts 64 into
    4 of 16: the parts all shares give are the uncut reference's layer, and
    every share's TopIdx is the reference's choice."""
    x, params = _uncut_layer()
    want, want_idx = _reference_layer(x, params, _ref_cfg(16, 16, 0))
    total, loads = 0.0, []
    for offset in (0, 4, 8, 12):
        out, idx, load = _share_program(x, params, offset, 4, 16)
        part, _ = _reference_layer(
            x, {k: (v if k == "router_w" else v[offset:offset + 4])
                for k, v in params.items()}, _ref_cfg(4, 16, offset))
        np.testing.assert_allclose(out, part, rtol=2e-5, atol=2e-6)
        total = total + out
        loads.append(load)
        assert (idx == want_idx).all()
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-6)
    assert (np.concatenate(loads) == np.bincount(
        want_idx.reshape(-1), minlength=16)).all()


def test_no_token_is_dropped_under_a_skewed_router():
    """A router that sends nearly every token to one expert first: the
    layer still is the reference's, only the loads say so; sigmoid scores
    in softmax's place fail the same comparison."""
    x, params = _uncut_layer(seed=1, skew=5, n=128)
    want, want_idx = _reference_layer(x, params, _ref_cfg(16, 16, 0))
    before = metrics.get("moe.layers_lowered")
    out, idx, load = _share_program(x, params, 0, 16, 16)
    assert metrics.get("moe.layers_lowered") == before + 1
    assert load[5] >= 0.9 * x.shape[0] and load.sum() == x.shape[0] * 3
    assert load.max() / load.mean() > 4
    assert (idx == want_idx).all()
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-6)
    other, _ = _reference_layer(x, params,
                                _ref_cfg(16, 16, 0, scoring="sigmoid"))
    assert np.abs(other - want).max() / np.abs(want).max() > 0.05


_GRAD_NAMES = ("X", "GateW", "ExpertGate", "ExpertUp", "ExpertDown")


def test_softmax_scorings_grad_rule_against_generic_route_and_reference():
    """A share of 4 of 16 experts: the rule's gradients (on the forward's
    residuals) are the generic route's (the forward lowered again) and
    `jax.grad`'s of the plain float32 reference layer."""
    x, params = _uncut_layer(seed=2, skew=5)
    cot = np.random.RandomState(7).randn(*x.shape).astype(np.float32)
    counters = ("moe.bwd_residual", "moe.bwd_recomputed")
    rises = []
    for withhold in (False, True):
        got, rise = counter_rise(lambda: _share_program(
            x, params, 4, 4, 16, withhold=withhold, cot=cot), counters)
        rises.append(rise)
        if withhold:
            for name, a, b in zip(_GRAD_NAMES, by_rule, got):
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7,
                                           err_msg=name)
        else:
            by_rule = got
    assert rises == [(1, 0), (0, 1)]
    cfg = _ref_cfg(4, 16, 4)

    def loss(x, router_w, eg, eu, ed):
        p = {"l_router_w": router_w, "l_experts_gate_w": eg,
             "l_experts_up_w": eu, "l_experts_down_w": ed}
        return jnp.sum(ref.routed_experts(x, p, "l_", cfg)[0] * cot)

    want = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(
        jnp.asarray(x), jnp.asarray(params["router_w"]),
        *(jnp.asarray(params[f"experts_{n}_w"][4:8])
          for n in ("gate", "up", "down")))
    for name, a, b in zip(_GRAD_NAMES, by_rule, want):
        err = np.linalg.norm(a - np.asarray(b)) / np.linalg.norm(b)
        assert err < 2e-5 and np.linalg.norm(b) > 0, (name, err)


# ---------------------------------------------------------------------------
# rotary positions: the frequency rules, the half-split layout, the scale
# ---------------------------------------------------------------------------

def test_yarn_frequencies_against_hand_worked_numbers():
    """head_dim 128, theta 500000, factor 16, original 8192, beta 32 / 1:
    c(32) = 18.08 and c(1) = 34.98, so low 18 and high 35; pair 18 is
    untouched, pair 26 sits 8/17 up the ramp, pair 35 is divided by 16."""
    for table in (llm_ops.rotary_frequencies(500000, 128, "yarn", 16, 8192,
                                             32, 1),
                  ref.rope_frequencies(ROPE[FULL], 128)):
        plain = 500000.0 ** (-2.0 * np.arange(64) / 128)
        assert table[18] == pytest.approx(0.0249554, rel=1e-5)
        assert table[26] == pytest.approx(0.00270438, rel=1e-5)
        assert table[35] == pytest.approx(4.77811e-05, rel=1e-5)
        np.testing.assert_allclose(table[:19], plain[:19], rtol=1e-12)
        np.testing.assert_allclose(table[35:], plain[35:] / 16, rtol=1e-12)
        ramp = (plain[19:35] - table[19:35]) / (plain[19:35] * (1 - 1 / 16))
        np.testing.assert_allclose(ramp, np.arange(1, 17) / 17, rtol=1e-9)
    np.testing.assert_allclose(
        llm_ops.rotary_frequencies(500000, 128),
        ref.rope_frequencies(ROPE[SLIDING], 128), rtol=1e-12)


@pytest.mark.parametrize("kind", [SLIDING, FULL])
def test_rotary_embedding_op_half_split_pairs_by_rule(kind):
    rng = np.random.RandomState(1)
    x = rng.randn(2, 3, 9, 16).astype(np.float32)       # [B, nh, S, D]
    r = ROPE[kind]
    attrs = {"theta": float(r["rope_theta"]), "rotary_dim": 16,
             "layout": "half"}
    if kind == FULL:
        attrs.update(rope_type="yarn", factor=16.0,
                     original_max_position=8192, beta_fast=32.0,
                     beta_slow=1.0, scale=r["attention_factor"])
    out, = _run_op("rotary_embedding", {"X": x}, ["Out"], attrs)
    freq = ref.rope_frequencies(r, 16)
    z = (x[..., :8] + 1j * x[..., 8:]) * np.exp(
        1j * np.arange(9)[:, None] * freq) * r.get("attention_factor", 1.0)
    np.testing.assert_allclose(out[..., :8], z.real, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out[..., 8:], z.imag, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out, np.asarray(ref.rope(jnp.asarray(x), r)),
                               rtol=1e-5, atol=1e-5)
    # the interleaved layout turns the same pairs, permuted: q . k agree
    if kind == SLIDING:
        inter, = _run_op("rotary_embedding", {"X": x}, ["Out"],
                         {"theta": 500000.0, "rotary_dim": 16})
        perm = np.arange(16).reshape(8, 2).T.reshape(-1)   # 0,2,..,1,3,..
        again, = _run_op("rotary_embedding", {"X": x[..., perm]}, ["Out"],
                         attrs)
        np.testing.assert_allclose(again, inter[..., perm], rtol=1e-5,
                                   atol=1e-5)


# ---------------------------------------------------------------------------
# the flash kernels: window x KV heads, interpreted, against the dense route
# ---------------------------------------------------------------------------

def _dense(q, k, v, scale, window):
    bias = attention._causal_bias(q.shape[2], window)
    return attention._xla_attention(q, k, v, bias, scale, 0.0, None)


@pytest.mark.parametrize("s, nh, nkv, window", [
    (256, 4, 2, 100),       # a window that is no multiple of a block
    (256, 4, 1, None),      # grouped heads alone
    (256, 2, 2, 128),       # a window alone, one block wide
    (256, 4, 2, 256),       # S <= window: the full triangle
    (384, 8, 2, 130)])      # blocks of 128 where 384 has no larger divisor
def test_flash_window_and_kv_heads_match_the_dense_route(s, nh, nkv, window):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, nh, s, 64), jnp.float32)
    k, v = (jnp.asarray(rng.randn(2, nkv, s, 64), jnp.float32)
            for _ in range(2))
    cot = jnp.asarray(rng.randn(2, nh, s, 64), jnp.float32)
    kw = dict(scale=0.125, causal=True, window=window, block_q=128,
              block_k=128)
    out, lse = fa.flash_attention(q, k, v, return_lse=True, **kw)
    want, vjp = jax.vjp(lambda q, k, v: _dense(q, k, v, 0.125, window),
                        q, k, v)
    grads = fa.flash_attention_bwd(q, k, v, out, lse, cot, **kw)
    assert grads[1].shape == grads[2].shape == (2, nkv, s, 64)
    # float32: the order of the online softmax's sums, and of the sum over
    # a group's query heads
    for name, got, ref_val in zip(("out", "dq", "dk", "dv"),
                                  (out,) + tuple(grads), (want,) + vjp(cot)):
        err = float(jnp.abs(got - ref_val).max() / jnp.abs(ref_val).max())
        assert err < 2e-5, (name, err)
    # differentiated by JAX, the same kernels
    by_jax = jax.grad(lambda k: jnp.sum(fa.flash_attention(q, k, v, **kw)
                                        * cot))(k)
    np.testing.assert_allclose(by_jax, grads[1], rtol=1e-6, atol=1e-6)


def test_grouped_heads_take_masks_and_dropout_like_single_ones():
    """A key-padding mask and a per-head bias ride into the grouped kernels
    (the mask follows the QUERY head); with dropout, whose mask is hashed
    from the query head's index, the grouped kernels give what the single
    ones give on K and V repeated, dK and dV summed over the group."""
    rng = np.random.RandomState(3)
    q, cot = (jnp.asarray(rng.randn(2, 4, 256, 64), jnp.float32)
              for _ in range(2))
    k, v = (jnp.asarray(rng.randn(2, 2, 256, 64), jnp.float32)
            for _ in range(2))
    kw = dict(scale=0.125, causal=True, block_q=128, block_k=128)
    for shape in ((2, 1, 1, 256), (1, 4, 256, 256)):
        bias = np.where(rng.rand(*shape) < 0.2, -1e9, 0.0)
        bias[..., :8] = 0.0             # no query loses every key
        bias = jnp.asarray(bias, jnp.float32)
        out, lse = fa.flash_attention(q, k, v, mask=bias, window=100,
                                      return_lse=True, **kw)
        grads = fa.flash_attention_bwd(q, k, v, out, lse, cot, mask=bias,
                                       window=100, **kw)
        want, vjp = jax.vjp(lambda q, k, v: attention._xla_attention(
            q, k, v, attention._causal_bias(256, 100) + bias, 0.125, 0.0,
            None), q, k, v)
        for got, ref_val in zip((out,) + tuple(grads), (want,) + vjp(cot)):
            assert float(jnp.abs(got - ref_val).max()) < 2e-5
    drop = dict(kw, dropout=0.1, seed=7)
    out, lse = fa.flash_attention(q, k, v, return_lse=True, **drop)
    dq, dk, dv = fa.flash_attention_bwd(q, k, v, out, lse, cot, **drop)
    kr, vr = (jnp.repeat(t, 2, axis=1) for t in (k, v))
    out1, lse1 = fa.flash_attention(q, kr, vr, return_lse=True, **drop)
    dq1, dk1, dv1 = fa.flash_attention_bwd(q, kr, vr, out1, lse1, cot, **drop)
    np.testing.assert_allclose(out, out1, atol=1e-6)
    np.testing.assert_allclose(dq, dq1, atol=1e-5)
    for got, single in ((dk, dk1), (dv, dv1)):
        np.testing.assert_allclose(
            got, single.reshape(2, 2, 2, 256, 64).sum(2), atol=1e-5)


def test_a_window_needs_causal_and_heads_must_divide():
    x = jnp.zeros((1, 4, 128, 64))
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(x, x, x, window=16)
    with pytest.raises(ValueError, match="head counts"):
        fa.flash_attention(x, x[:, :3], x[:, :3], causal=True)
    reset_programs(0)
    var = layers.data(name="q", shape=[4, 128, 64], dtype="float32")
    with pytest.raises(ValueError, match="window"):
        layers.fused_attention(var, var, var, window=16)


def _kernels_jaxpr(causal, masked):
    def sd(*shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt)
    drop = 0.1 if masked else 0.0

    def step(q, k, v, do, mask, seed):
        o, lse = fa.flash_attention(q, k, v, scale=0.125, causal=causal,
                                    dropout=drop, seed=seed, mask=mask,
                                    return_lse=True)
        return (o,) + fa.flash_attention_bwd(
            q, k, v, o, lse, do, scale=0.125, causal=causal, dropout=drop,
            seed=seed, mask=mask)

    x = sd(2, 2, 256, 64)
    text = str(jax.make_jaxpr(step)(
        x, x, x, x, sd(2, 1, 1, 256, dt=jnp.float32) if masked else None,
        sd(dt=jnp.int32)))
    return harness.cut_source_lines(text, "flash_attention")


@pytest.mark.parametrize("causal, masked, digest", [
    (True, False,
     "d043b132e35d17bb5e47cf54f130f6e8373c212d28777b5356f430b1ced56d1e"),
    (False, True,
     "7a8ac7519efd115275fd4b599ff84389f8875cb81fbf10e34e36b181f3ca3590")],
    ids=["causal", "mask-and-dropout"])
def test_without_window_and_groups_the_kernels_trace_as_before(
        causal, masked, digest, monkeypatch):
    """`window=None, nkv == nh`: the three kernels' jaxpr, source lines cut,
    holds no trace of a window or of grouped heads: the cells that run
    these kernels without either must not pay for them. A deliberate change
    to the kernels changes the digests with it. They are PR 46's (jax
    0.9.0): the large sums in VMEM scratch and a range of one or two blocks
    written out without a loop, in both launches; in the causal one the
    loop over the blocks before the diagonal, whose body compares no
    positions. Until then they were the tree's before windows and groups
    (commit ed39f67)."""
    monkeypatch.setattr(fa, "interpret_mode", lambda: False)
    text = _kernels_jaxpr(causal, masked)
    assert text.count("pallas_call") == 3
    assert harness.sha256(text) == digest


# ---------------------------------------------------------------------------
# the builder
# ---------------------------------------------------------------------------

_COUNTERS = ("attention.flash_window", "attention.flash_full",
             "attention.flash_kv_grouped", "attention.flash_kv_expanded",
             "attention.flash_bwd_residual",
             "attention.flash_blocks_interior", "attention.flash_blocks_edge",
             "moe.layers_lowered",
             "moe.bwd_residual", "moe.bwd_recomputed",
             "moe.grouped_pallas", "moe.grouped_xla")


def test_builder_names_scopes_and_checkpoints_and_verifies():
    from paddle_tpu.analysis import verifier
    from paddle_tpu.observability import trace
    reset_programs(0)
    trace.clear()
    _, loss, routed = mellum.build_causal_lm_program(
        mellum.MellumConfig.tiny())
    built = [e for e in trace.events() if e["name"] == "program.build"]
    assert built and built[-1]["args"]["model"] == "mellum"
    prog = fluid.default_main_program()
    ops = prog.global_block().ops
    scopes = [op.attrs.get("name_scope") for op in ops
              if op.type == "fused_attention"]
    assert scopes == ["attn.attend.window"] * 3 + ["attn.attend.full"]
    assert [op.attrs.get("window") for op in ops
            if op.type == "fused_attention"] == [8, 8, 8, None]
    assert "attn.proj" in {op.attrs.get("name_scope") for op in ops}
    rotary = [op.attrs for op in ops if op.type == "rotary_embedding"]
    assert len(rotary) == 8 and all(a["layout"] == "half" for a in rotary)
    assert [a.get("rope_type", "default") for a in rotary] == (
        ["default"] * 6 + ["yarn"] * 2)
    assert rotary[-1]["scale"] == pytest.approx(1.2772588722239782)
    moe = [op.attrs for op in ops if op.type == "routed_moe"]
    assert len(moe) == 4 and all(a["scoring"] == "softmax" for a in moe)
    assert not any("SelectBias" in op.inputs for op in ops
                   if op.type == "routed_moe")
    assert len(loss._layer_checkpoints) == 4 and len(routed) == 4
    paddle.optimizer.Adam(1e-4).minimize(loss)
    errors = [f for f in verifier.verify_program(prog)
              if f.severity == "error"]
    assert not errors, errors
    rules = mellum.sharding_rules()
    assert tuple(rules.spec_for("l1_experts_up_w")) == ("ep",)
    assert tuple(rules.spec_for("l0_k_proj_w")) == (None, "tp")
    assert tuple(rules.spec_for("l3_o_proj_w")) == ("tp", None)


def test_a_trace_of_the_step_counts_its_routes(monkeypatch):
    """With the flash gate open (here: the interpreter), one trace of the
    AMP train step lowers three windowed and one full flash forward, all
    four on grouped KV heads and none expanded, their backward on the
    forward's residuals, and four expert layers by the op's grad rule, whose
    36 grouped matmuls (9 a layer) all go to the Pallas kernels at widths
    that are multiples of 128, as the cell's are. The step's jaxpr holds K
    and V at the KV heads' count only, and no `ragged_dot`."""
    monkeypatch.setattr(attention, "_use_pallas",
                        lambda q: q.shape[2] % 128 == 0)
    cfg = mellum.MellumConfig.tiny()
    cfg.seq_len, cfg.head_dim, cfg.sliding_window = 128, 64, 48
    cfg.num_attention_heads, cfg.num_key_value_heads = 6, 2
    cfg.hidden_size, cfg.moe_intermediate_size = 128, 256
    exe, loss, ids = harness.amp_step(mellum, cfg)
    jaxpr, rise = counter_rise(
        lambda: str(exe.step_jaxpr({"tokens": ids}, [loss], k=2)), _COUNTERS)
    assert dict(zip(_COUNTERS, rise)) == {
        "attention.flash_window": 3, "attention.flash_full": 1,
        "attention.flash_kv_grouped": 4, "attention.flash_kv_expanded": 0,
        "attention.flash_bwd_residual": 4,
        # 128 positions are one block: the diagonal crosses it
        "attention.flash_blocks_interior": 0, "attention.flash_blocks_edge": 4,
        "moe.layers_lowered": 4,
        "moe.bwd_residual": 4, "moe.bwd_recomputed": 0,
        "moe.grouped_pallas": 36, "moe.grouped_xla": 0}
    assert jaxpr.count("name=flash_attention_") == 12
    assert "ragged_dot" not in jaxpr and "name=ragged-dot-" in jaxpr
    # q, o, dq, dO at 6 heads; k, v, dk, dv at 2 and never at 6: the only
    # [1, 6, 128, 64] values are q's, and a KV tensor repeated to the query
    # heads would be a `broadcast_in_dim` / `repeat` to that shape from 2
    assert "bf16[2,128,64]" in jaxpr and "f32[2,128,64]" in jaxpr
    assert not re.search(r"bf16\[1,2,\d+,128,64\]|bf16\[1,2,3,128,64\]",
                         jaxpr)
    assert "repeat" not in jaxpr
