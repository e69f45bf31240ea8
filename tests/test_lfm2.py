"""The LFM2-family LM (`models/lfm2.py`: gated short-convolution mixers in a
period with attention on grouped KV heads normed a head, leading dense
layers then sigmoid-routed experts with a selection bias, a tied head, one
expert-parallel rank's share) against its plain float32 reference
(`benchmark/reference/lfm2.py`), on the CPU at tiny widths with seeded
weights; and what the model forced on the ops and on `models/causal_lm.py`:
the one-op mixer against its shifted products, the per-head norm's place,
the selection bias, the tied embedding's gradient, the router's epsilon.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import causal_lm_harness as harness
from causal_lm_harness import B, counter_rise, run_op

import paddle_tpu as paddle
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.models import causal_lm, lfm2
from paddle_tpu.observability import metrics
from paddle_tpu.ops import attention
from paddle_tpu.testing import reset_programs
from benchmark.reference import lfm2 as ref

S = 30                      # a row that is no multiple of 4
DATA_SEED = 0
CONV, FULL = "conv", "full_attention"
CFG = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
           head_dim=16, conv_L_cache=3, intermediate_size=128,
           moe_intermediate_size=32, num_experts=4, experts_total=8,
           expert_offset=2, num_experts_per_tok=2, norm_topk_prob=True,
           routed_scaling_factor=1, use_expert_bias=True, norm_eps=1e-5,
           num_dense_layers=2, num_hidden_layers=8,
           layer_types=[CONV, CONV, FULL, CONV] * 2,
           rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
           first_layer=1, layers=4, vocab=256,
           reference_tokens_per_block=10,
           assumed={"initializer_std": 0.02})
SHARED = ("hidden_size", "num_attention_heads", "num_key_value_heads",
          "head_dim", "conv_L_cache", "intermediate_size",
          "moe_intermediate_size", "num_experts_per_tok", "norm_topk_prob",
          "routed_scaling_factor", "use_expert_bias", "norm_eps",
          "num_dense_layers", "num_hidden_layers", "first_layer",
          "expert_offset")


def model_config(cfg):
    return lfm2.Lfm2Config(
        vocab_size=cfg["vocab"], num_layers_held=cfg["layers"],
        num_experts=cfg["experts_total"], experts_held=cfg["num_experts"],
        layer_types=tuple(cfg["layer_types"]), seq_len=S,
        rope_theta=cfg["rope_parameters"]["rope_theta"],
        **{k: cfg[k] for k in SHARED})


def batches(k, seed=DATA_SEED):
    """(ids, labels) [k, B, S]: the harness's seeded rows cut to S."""
    ids = harness.batches(CFG["vocab"], k, seed=seed)[0][:, :, :S]
    return ids, np.concatenate([ids[:, :, 1:], np.full((k, B, 1), -100)], 2)


def seeded_params(cfg=CFG, bias_std=0.0):
    """The reference's seeded weights; the taps ten times larger, so that a
    mixer moves the residual stream by as much as attention does at this
    width; `bias_std` plants a selection bias (the configuration's is 0)."""
    rng = np.random.RandomState(11)
    out = {}
    for n, v in ref.init_params(cfg, jax.random.key(3)).items():
        if n.endswith("conv_w"):
            v = v * 10
        if n.endswith("router_bias") and bias_std:
            v = jnp.asarray(rng.randn(*v.shape).astype(np.float32) * bias_std)
        out[n] = v
    return out


# Tolerances, as in test_mellum.py. float32: the order of float32 sums, 1e-6
# relative on a leaf. AMP: every matmul operand and both gates' products are
# rounded to bf16; the roundings average to under 2 % of a leaf's norm (3 %
# on the taps' leaf of 3 x 64 numbers), Adam's first two steps move a weight
# by at most lr a step, so a weight differs by at most 4 lr. The data seeds
# are ones at which no token sits at a near-tie of two experts' scores in any
# layer at either step under bf16 rounding (two seeds in thirteen at this size):
# one token of the 120 going to another expert is 10 to 30 % of a leaf's
# gradient here, a comparison of routings and not of arithmetic (on the chip
# `route_mismatch_share` is that comparison).
@pytest.mark.parametrize("amp, grad_tol, loss_tol", [
    (False, 2e-5, 1e-6), (True, 2e-2, 2e-4)], ids=["float32", "amp"])
@pytest.mark.parametrize("first_layer, dense, data_seed", [
    (1, 2, 5), (0, 1, 11)], ids=["layers_1_to_4", "layers_0_to_3"])
def test_program_follows_the_reference(amp, grad_tol, loss_tol, first_layer,
                                       dense, data_seed):
    """Loss, every leaf's gradient (the tied embedding's among them), two
    Adam steps and the routed choice, under a planted selection bias; over
    published layers 1..4 with two leading dense layers (what the cell cuts)
    and over 0..3 with one."""
    cfg = dict(CFG, first_layer=first_layer, num_dense_layers=dense)
    ids, labels = batches(2, data_seed)

    def p0():           # anew for every use: a step donates what it is given
        return seeded_params(cfg, bias_std=0.05)

    states, ref_idx = harness.reference_states(
        ref, cfg, ref.split_state(cfg, p0()), 2, ids, labels)

    def trained(k):
        return harness.trained_program(lfm2, model_config(cfg), ref, p0(),
                                       amp, k, ids)

    losses, idx, scope = trained(1)
    loss1, grads1 = states[0][0], states[0][1]
    assert abs(losses[0] - loss1) / loss1 < loss_tol
    for name, err in harness.first_step_gaps(scope, grads1, ref).items():
        assert err < grad_tol, (name, err)
    assert harness.route_mismatch(idx[0], ref_idx) == 0
    losses, _, scope = trained(2)
    for t in range(2):
        assert abs(losses[t] - states[t][0]) / states[t][0] < loss_tol
    lr = ref.ADAM["lr"]
    for name, worst, gap, moved, moments in harness.second_step_gaps(
            scope, states, p0()):
        assert worst <= (4.1 * lr if amp else 1e-2 * lr), name
        assert gap <= (0.3 if amp else 1e-3) * moved, name
        for acc, err in moments.items():
            assert err < 2 * grad_tol, (name, acc, err)
    # the buffer no gradient reaches is where it was
    bias = f"l{cfg['num_dense_layers']}_router_bias"
    np.testing.assert_array_equal(np.asarray(scope.find(bias)), p0()[bias])


@pytest.mark.parametrize("wrong, moved", [
    ("gate_left_out", "y = c: the second gate dropped"),
    ("taps_reversed", "the taps in the order w_2, w_1, w_0"),
    ("qk_norm_left_out", "q and k turned without their norm"),
    ("head_untied", "a head of its own in the embedding's place")])
def test_the_reference_tells_each_fault_apart(wrong, moved):
    """What the new mechanisms admit going wrong each moves the reference's
    own gradients by far more than any tolerance above."""
    ids, labels = batches(1)
    bad = dict(CFG, assumed=dict(CFG["assumed"], fault=wrong))
    state = ref.split_state(CFG, seeded_params())
    _, _, want = ref._block_grad(*state, ids[0], labels[0],
                                 ref._cfg_key(CFG), None)
    _, _, got = ref._block_grad(
        *ref.split_state(bad, seeded_params(bad)), ids[0], labels[0],
        ref._cfg_key(bad), None)
    worst = max(float(jnp.linalg.norm(got[n] - want[n])
                      / jnp.linalg.norm(want[n])) for n in want)
    assert worst > 0.1, (moved, worst)


# ---------------------------------------------------------------------------
# the mixer alone
# ---------------------------------------------------------------------------

def _shifted_products(bcx, w):
    """C_t (w_0 g_{t-2} + w_1 g_{t-1} + w_2 g_t), g = B u, written out."""
    c3 = bcx.shape[-1] // 3
    b, c, u = bcx[..., :c3], bcx[..., c3:2 * c3], bcx[..., 2 * c3:]
    g = b * u
    zero = jnp.zeros_like(g[:, :1])
    g1 = jnp.concatenate([zero, g[:, :-1]], axis=1)             # g_{t-1}
    g2 = jnp.concatenate([zero, zero, g[:, :-2]], axis=1)       # g_{t-2}
    return c * (w[0] * g2 + w[1] * g1 + w[2] * g)


def test_the_mixer_op_is_its_three_shifted_products():
    """`gated_short_conv` forward and both gradients against the products
    written out; a row's first two positions read zeros where the row
    began (position 0 is w_2 g_0 alone), and the taps are in the published
    order (w_2 meets the current position)."""
    rng = np.random.RandomState(0)
    bcx = rng.randn(2, S, 3 * 8).astype(np.float32)
    w = rng.randn(3, 8).astype(np.float32)
    cot = rng.randn(2, S, 8).astype(np.float32)
    (out,), rise = counter_rise(
        lambda: run_op("gated_short_conv", {"X": bcx, "W": w}, ["Out"], {}),
        ("conv.layers_lowered",))
    assert rise == (1,)
    np.testing.assert_allclose(out, _shifted_products(bcx, w), rtol=1e-6,
                               atol=1e-6)
    b, c, u = bcx[..., :8], bcx[..., 8:16], bcx[..., 16:]
    np.testing.assert_allclose(out[:, 0], c[:, 0] * w[2] * b[:, 0] * u[:, 0],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        out[:, 1], c[:, 1] * (w[1] * b[:, 0] * u[:, 0]
                              + w[2] * b[:, 1] * u[:, 1]),
        rtol=1e-6, atol=1e-6)

    reset_programs(0)
    xv = layers.data(name="x", shape=[S, 24], dtype="float32")
    xv.stop_gradient = False
    y = layers.gated_short_conv(xv, 3, param_attr=fluid.ParamAttr(name="w"))
    cv = layers.data(name="cot", shape=[S, 8], dtype="float32")
    loss = layers.reduce_sum(layers.elementwise_mul(y, cv))
    wv = fluid.default_main_program().global_block().var("w")
    fetch = fluid.gradients(loss, [xv, wv])
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    fluid.global_scope().set("w", jnp.asarray(w))
    got = exe.run(feed={"x": bcx, "cot": cot}, fetch_list=fetch)
    want = jax.grad(lambda a, t: jnp.sum(_shifted_products(a, t) * cot),
                    argnums=(0, 1))(jnp.asarray(bcx), jnp.asarray(w))
    for name, a, b_ in zip(("X", "W"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=2e-5,
                                   atol=2e-5, err_msg=name)


def test_the_mixer_through_a_program_is_the_references():
    cfg = model_config(CFG)
    params = {k[len("l1_"):]: v for k, v in seeded_params().items()
              if k.startswith("l1_conv")}
    x = np.random.RandomState(1).randn(B, S, 64).astype(np.float32)
    got = harness.mixer_program(
        lfm2.short_conv_mixer, cfg, x,
        {"m_" + k: v for k, v in params.items()}, "m_")
    want = ref.short_conv(jnp.asarray(x),
                          {"m_" + k: v for k, v in params.items()}, "m_", CFG)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# attention: the per-head norm and its place
# ---------------------------------------------------------------------------

def test_q_and_k_are_normed_a_head_before_the_rotary_turn():
    """The Program's ops in order: each of q and k goes reshape / transpose
    -> rms_norm (scale of `head_dim`, one for all the heads) ->
    rotary_embedding; the attention layer's output is the reference's, and
    is not what the norm AFTER the turn gives under a scale that is not
    flat (a turn mixes feature j with j + head_dim / 2, so a scale that
    differs between them does not commute with it)."""
    cfg = model_config(CFG)
    rng = np.random.RandomState(2)
    params = {k[len("l2_"):]: v for k, v in seeded_params().items()
              if k.startswith("l2_") and ("_proj_w" in k or "_norm_scale" in k)
              and "operator" not in k and "ffn" not in k}
    for n in ("q_norm_scale", "k_norm_scale"):
        params[n] = jnp.asarray(1 + rng.rand(16).astype(np.float32))
    named = {"m_" + k: v for k, v in params.items()}
    x = rng.randn(B, S, 64).astype(np.float32)
    got = harness.mixer_program(lfm2.grouped_attention, cfg, x, named, "m_")
    ops = fluid.default_main_program().global_block().ops
    kinds = [op.type for op in ops]
    norms = [i for i, t in enumerate(kinds) if t == "rms_norm"]
    turns = [i for i, t in enumerate(kinds) if t == "rotary_embedding"]
    assert len(norms) == 2 and [i + 1 for i in norms] == turns
    assert all(ops[i].attrs["name_scope"] == "attn.qk_norm" for i in norms)
    assert all(ops[i].attrs["epsilon"] == 1e-5 for i in norms)
    block = fluid.default_main_program().global_block()
    assert tuple(block.var("m_q_norm_scale").shape) == (16,)
    want = ref.attention(jnp.asarray(x), named, "m_", CFG)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-5, atol=1e-7)

    def norm_after_the_turn(t, scale, eps):
        return t                                   # the norm moved below

    import benchmark.reference.lfm2 as module
    turned = module.rope
    try:
        module.rms_norm, kept = norm_after_the_turn, module.rms_norm
        module.rope = lambda t, r: kept(
            turned(t, r), named["m_q_norm_scale" if t.shape[1] == 4
                                else "m_k_norm_scale"], 1e-5)
        other = module.attention(jnp.asarray(x), named, "m_", CFG)
    finally:
        module.rms_norm, module.rope = kept, turned
    assert np.abs(np.asarray(other) - got).max() > 1e-2 * np.abs(got).max()


# ---------------------------------------------------------------------------
# the expert layer: the selection bias, the epsilon, the shares
# ---------------------------------------------------------------------------

def _uncut_layer(seed=0, n=96, d=32, f=16, total=16):
    rng = np.random.RandomState(seed)
    params = {"router_w": rng.randn(d, total).astype(np.float32) * 0.3,
              "router_bias": np.zeros(total, np.float32),
              "experts_gate_w": rng.randn(total, d, f).astype(np.float32) * .2,
              "experts_up_w": rng.randn(total, d, f).astype(np.float32) * .2,
              "experts_down_w": rng.randn(total, f, d).astype(np.float32) * .2}
    return rng.randn(n, d).astype(np.float32), params


def _ref_cfg(held, total, offset, top_k=4):
    return dict(num_experts=held, experts_total=total, expert_offset=offset,
                num_experts_per_tok=top_k, norm_topk_prob=True,
                routed_scaling_factor=1, assumed={})


def _share_program(x, params, offset, held, total, top_k=4, **more):
    return harness.routed_share(
        x, harness.held_arrays(params, offset, held), top_k, total, offset,
        norm_topk_eps=lfm2.NORM_TOPK_EPS, **more)


def _reference_layer(x, params, cfg):
    p = {"l_" + k: jnp.asarray(v) for k, v in params.items()}
    out, idx = ref.routed_experts(jnp.asarray(x), p, "l_", cfg)
    return np.asarray(out), np.asarray(idx)


def _cut(params, offset, held):
    return {k: (v if k.startswith("router") else v[offset:offset + held])
            for k, v in params.items()}


def test_the_eight_ranks_routed_parts_add_up_to_the_uncut_layer():
    """16 experts cut into 8 shares of 2, as the configuration cuts 64 into
    8 of 8, top-4 under a planted selection bias: the parts all shares give
    are the uncut reference's layer, and every share's TopIdx is the
    reference's choice."""
    x, params = _uncut_layer()
    params["router_bias"] = np.random.RandomState(5).randn(16).astype(
        np.float32) * 0.1
    want, want_idx = _reference_layer(x, params, _ref_cfg(16, 16, 0))
    total, loads = 0.0, []
    for offset in range(0, 16, 2):
        out, idx, load = _share_program(x, params, offset, 2, 16)
        part, _ = _reference_layer(x, _cut(params, offset, 2),
                                   _ref_cfg(2, 16, offset))
        np.testing.assert_allclose(out, part, rtol=2e-5, atol=2e-6)
        total = total + out
        loads.append(load)
        assert (np.sort(idx, 1) == np.sort(want_idx, 1)).all()
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-6)
    assert (np.concatenate(loads) == np.bincount(
        want_idx.reshape(-1), minlength=16)).all()


def test_a_planted_selection_bias_moves_the_choice_and_no_weight():
    """With b != 0 the program's choice and output are the reference's
    under the same b; many tokens choose other experts than under b = 0;
    and a token whose chosen set b did not change gets the same output (to
    the order of a float32 sum): b is in the selection alone."""
    x, params = _uncut_layer(seed=1)
    plain = _share_program(x, params, 0, 16, 16)
    params["router_bias"] = np.random.RandomState(6).randn(16).astype(
        np.float32) * 0.05
    out, idx, _ = _share_program(x, params, 0, 16, 16)
    want, want_idx = _reference_layer(x, params, _ref_cfg(16, 16, 0))
    assert (np.sort(idx, 1) == np.sort(want_idx, 1)).all()
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-6)
    same = (np.sort(idx, 1) == np.sort(plain[1], 1)).all(axis=1)
    assert 0.1 < same.mean() < 0.9
    np.testing.assert_allclose(out[same], plain[0][same], rtol=1e-6,
                               atol=1e-6)


def test_the_routers_epsilon_is_an_attr_only_this_builder_sets():
    """`norm_topk_eps` reaches the op from `causal_lm.expert_layer`'s
    argument and is absent from every other builder's op; with scores near
    0 the two epsilons give different weights, each the reference's rule."""
    reset_programs(0)
    lfm2.build_causal_lm_program(lfm2.Lfm2Config.tiny())
    moe = [op.attrs for op in
           fluid.default_main_program().global_block().ops
           if op.type == "routed_moe"]
    assert len(moe) == 3 and all(a["norm_topk_eps"] == 1e-6 for a in moe)
    x, params = _uncut_layer(seed=2)
    params["router_w"] = params["router_w"] * 0 - 1.0   # sigmoid(-sum x)
    x = np.abs(x) + 2.0                                 # scores ~ 1e-30
    arrays = harness.held_arrays(params, 0, 16)
    ours = harness.routed_share(x, arrays, 4, 16, 0, norm_topk_eps=1e-6)[0]
    theirs = harness.routed_share(x, arrays, 4, 16, 0)[0]
    want, _ = _reference_layer(x, params, _ref_cfg(16, 16, 0))
    np.testing.assert_allclose(ours, want, rtol=2e-5, atol=1e-30)
    assert np.abs(theirs).max() > 1e3 * np.abs(ours).max()


# ---------------------------------------------------------------------------
# the tied head
# ---------------------------------------------------------------------------

def _embedding_gradients(tie):
    """The gradients of the loss with respect to the embedding (and, untied,
    to a head set to its transpose) of the tiny model on one batch."""
    cfg = model_config(CFG)
    reset_programs(0)
    _, loss, _ = causal_lm.build_causal_lm_program(
        cfg, "lfm2", lfm2.decoder_layer, cfg.layers_here(), tie_head=tie)
    block = fluid.default_main_program().global_block()
    leaves = [block.var("embed_tokens")] + (
        [] if tie else [block.var("lm_head_w")])
    fetch = fluid.gradients(loss, leaves)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    for name, value in seeded_params().items():
        scope.set(name, value)
    if not tie:
        scope.set("lm_head_w", seeded_params()["embed_tokens"].T)
    ids, _ = batches(1)
    return [np.asarray(g) for g in exe.run(feed={"tokens": ids[0]},
                                           fetch_list=fetch)]


def test_the_tied_embeddings_gradient_is_the_sum_of_its_two_uses():
    """One parameter read by a gather and by a matmul: its gradient is the
    untied program's two gradients added by hand (the gather's scatter-add
    and the head's, transposed), and the reference's; the Program holds no
    `lm_head_w`, the head's op sits under `head.tied`, and the gauge says
    which head the last Program built has."""
    (tied,) = _embedding_gradients(True)
    block = fluid.default_main_program().global_block()
    assert not block.has_var("lm_head_w")
    head = [op for op in block.ops if op.type == "matmul"]
    assert len(head) == 1 and head[0].attrs["name_scope"] == "head.tied"
    assert "embed_tokens" in head[0].input_names()
    assert metrics.get("lm.tied_head") == 1
    gathered, headed = _embedding_gradients(False)
    assert metrics.get("lm.tied_head") == 0
    assert np.linalg.norm(gathered) > 0.1 * np.linalg.norm(headed.T) > 0
    np.testing.assert_allclose(tied, gathered + headed.T, rtol=2e-5,
                               atol=1e-8)
    ids, labels = batches(1)
    _, _, grads = ref._block_grad(
        *ref.split_state(CFG, seeded_params()), ids[0], labels[0],
        ref._cfg_key(CFG), None)
    want = np.asarray(grads["embed_tokens"]) / (labels[0] != -100).sum()
    assert harness.rel_gap(tied, want) < 2e-5


# ---------------------------------------------------------------------------
# the builder and a trace of its step
# ---------------------------------------------------------------------------

def test_builder_names_scopes_and_checkpoints_and_verifies():
    from paddle_tpu.analysis import verifier
    from paddle_tpu.observability import trace
    reset_programs(0)
    trace.clear()
    cfg = lfm2.Lfm2Config.tiny(num_dense_layers=2)
    assert cfg.seq_len % 4
    _, loss, routed = lfm2.build_causal_lm_program(cfg)
    built = [e for e in trace.events() if e["name"] == "program.build"]
    assert built and built[-1]["args"]["model"] == "lfm2"
    prog = fluid.default_main_program()
    ops = prog.global_block().ops
    mixers = [op.attrs["name_scope"] for op in ops
              if op.type in ("gated_short_conv", "fused_attention")]
    assert mixers == ["conv.mix", "conv.mix", "attn.attend.full", "conv.mix"]
    scopes = {op.attrs.get("name_scope") for op in ops}
    assert {"conv.in_proj", "conv.out_proj", "attn.proj", "attn.qk_norm",
            "head.tied"} <= scopes
    rotary = [op.attrs for op in ops if op.type == "rotary_embedding"]
    assert len(rotary) == 2 and all(
        a["layout"] == "half" and a["theta"] == 1e6 for a in rotary)
    moe = [op for op in ops if op.type == "routed_moe"]
    assert len(moe) == 2 and all(
        op.attrs["scoring"] == "sigmoid" and "SelectBias" in op.inputs
        and op.attrs["top_k"] == 2 for op in moe)
    assert len(loss._layer_checkpoints) == 4 and len(routed) == 2
    paddle.optimizer.Adam(1e-4).minimize(loss)
    errors = [f for f in verifier.verify_program(prog)
              if f.severity == "error"]
    assert not errors, errors
    rules = lfm2.sharding_rules()
    assert tuple(rules.spec_for("l2_experts_up_w")) == ("ep",)
    assert tuple(rules.spec_for("l2_k_proj_w")) == (None, "tp")
    assert tuple(rules.spec_for("l2_o_proj_w")) == ("tp", None)
    assert tuple(rules.spec_for("embed_tokens")) == ("tp", None)
    assert tuple(rules.spec_for("l0_mlp_down_w")) == ("tp", None)
    assert not tuple(rules.spec_for("l0_conv_in_proj_w"))


_COUNTERS = ("conv.layers_lowered", "attention.flash_full",
             "attention.flash_kv_grouped", "attention.flash_kv_expanded",
             "moe.layers_lowered", "moe.grouped_pallas", "moe.grouped_xla")


@pytest.mark.parametrize("recompute", [False, True],
                         ids=["residuals", "recompute"])
def test_a_trace_of_the_step_counts_its_routes(recompute, monkeypatch):
    """With the flash gate open (here: the interpreter) at a head of 64 on
    grouped KV heads, one trace of the AMP train step lowers three mixers
    (each as the one op `gated_short_conv`), one flash forward on grouped
    KV heads, none expanded, and three expert layers' 27 grouped matmuls on
    the Pallas kernels at widths that are multiples of 128. Under
    recomputation a layer's segment is lowered once too (it differentiates
    itself where it is lowered, `parallel/transforms.py`): the same mixers
    and flash forward, and 9 more grouped matmuls, an expert layer's
    forward three traced by the `custom_vjp`'s body and by its forward
    rule."""
    monkeypatch.setattr(attention, "_use_pallas",
                        lambda q: q.shape[2] % 128 == 0)
    cfg = lfm2.Lfm2Config.tiny()
    cfg.seq_len, cfg.head_dim = 128, 64
    cfg.num_attention_heads, cfg.num_key_value_heads = 4, 1
    cfg.hidden_size, cfg.moe_intermediate_size = 128, 256
    exe, loss, ids = harness.amp_step(lfm2, cfg, recompute)
    jaxpr, rise = counter_rise(
        lambda: str(exe.step_jaxpr({"tokens": ids}, [loss], k=2)), _COUNTERS)
    assert dict(zip(_COUNTERS, rise)) == {
        "conv.layers_lowered": 3,
        "attention.flash_full": 1,
        "attention.flash_kv_grouped": 1,
        "attention.flash_kv_expanded": 0, "moe.layers_lowered": 3,
        "moe.grouped_pallas": 27 + 9 * recompute, "moe.grouped_xla": 0}
    assert jaxpr.count("name=flash_attention_") == 3
    assert "bf16[1,128,384]" in jaxpr        # the projection, never split
