"""The Nemotron-H family's latent-expert member on one chip's share of a
layer's heads (`models/nemotron_h.py` with `moe_latent_size`,
`mamba_heads_held` / `mamba_groups_held`, `heads_held` / `kv_heads_held`)
against its plain float32 reference (`benchmark/reference/
nemotron3_super.py`), on the CPU at tiny widths with seeded weights; and what
the model forced on `routed_moe`: the experts' input as a slot of its own
beside the router's, and a row buffer of min(k, E_held) x N rows where a
token picks more experts than a rank holds. What every other configuration
calls traces to the jaxpr of the tree before (commit 147251f).
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import causal_lm_harness as harness
from causal_lm_harness import S, counter_rise

import paddle_tpu as paddle
import paddle_tpu.fluid as fluid
from paddle_tpu.analysis import verifier
from paddle_tpu.models import nemotron_h
from paddle_tpu.ops import registry
from paddle_tpu.testing import reset_programs
from benchmark.reference import nemotron3_super as ref
from benchmark.reference import nemotron_h as base_ref

# the reference's configuration of `NemotronHConfig.tiny_latent_share()`:
# the top-level keys say what is HELD, as the benchmark's file does
CFG = dict(hidden_size=64, hybrid_override_pattern="MEMEM*EME", layers=9,
           num_hidden_layers=52, rescale_prenorm_residual=True,
           num_attention_heads=2, num_key_value_heads=1, head_dim=16,
           mamba_num_heads=4, mamba_head_dim=8, n_groups=1,
           ssm_state_size=16, conv_kernel=4, chunk_size=8,
           moe_intermediate_size=32, moe_shared_expert_intermediate_size=64,
           moe_latent_size=24, n_routed_experts=4, experts_total=32,
           expert_offset=8, num_experts_per_tok=6, routed_scaling_factor=5.0,
           norm_topk_prob=True, layer_norm_epsilon=1e-5,
           time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4,
           vocab=256, reference_scan_tokens_per_block=8,
           assumed={"initializer_std": 0.02, "select_bias_std": 0.03})


def model_config(seq=S):
    cfg = nemotron_h.NemotronHConfig.tiny_latent_share()
    cfg.expert_offset, cfg.seq_len = CFG["expert_offset"], seq
    return cfg


def seeded_params():
    """The reference's seeded leaves, the routers' eight times as wide: at 64
    features a draw of std 0.02 gives logits of std 0.16, every score near
    a half and a near-tie at the 6th place in one token of a hundred; at
    the published 4,096 the same draw gives 1.28, which is what 0.16 a
    weight gives here."""
    params = ref.init_params(CFG, jax.random.key(3))
    return {n: v * 8.0 if n.endswith("router_w") else v
            for n, v in params.items()}


def trained_program(amp, k, ids):
    return harness.trained_program(nemotron_h, model_config(), ref,
                                   seeded_params(), amp, k, ids)


# Under bf16 rounding a token or two of the 128 sits at a near-tie of its
# 6th and 7th expert in every layer at every data seed (6 of 32 a token:
# three times the choices of `tiny()`), and one token going to another
# expert is 5 to 10 % of an expert leaf's gradient: a comparison of routings
# and not of arithmetic (on the chip `route_mismatch_share` is that
# comparison). This seed is one at which the tokens that move carry little
# (one to three a layer; the worst leaf 3 %, the others 1 %).
DATA_SEED = 10


def test_the_reference_holds_the_leaves_the_equations_name():
    shapes = ref.param_shapes(CFG)
    assert shapes["l1_latent_down_w"] == (64, 24)
    assert shapes["l1_latent_up_w"] == (24, 64)
    assert shapes["l1_experts_up_w"] == (4, 24, 32)
    assert shapes["l1_experts_down_w"] == (4, 32, 24)
    assert shapes["l1_router_w"] == (64, 32)
    assert shapes["l1_shared_up_w"] == (64, 64)
    # [z 32 | x 32 | B 16 | C 16 | dt 4] of one group of 4 heads
    assert shapes["l0_in_proj_w"] == (64, 100)
    assert shapes["l5_q_proj_w"] == (64, 32)
    assert shapes["l5_k_proj_w"] == (64, 16)
    # W_b is a projection back into the residual stream: its draw is
    # divided by sqrt(num_hidden_layers), W_a's is not
    key = jax.random.key(0)
    wide = dict(CFG, moe_latent_size=512)
    std = {n: float(jnp.std(ref.init_leaf(wide, key, n)))
           for n in ("l1_latent_down_w", "l1_latent_up_w")}
    assert std["l1_latent_down_w"] == pytest.approx(0.02, rel=0.05)
    assert std["l1_latent_up_w"] == pytest.approx(0.02 / 52 ** 0.5, rel=0.05)


# Tolerances as in test_nemotron_h.py: float32 differs in the order of
# sums; under AMP every matmul operand is rounded to bf16 and a leaf's
# gradient moves by a few per cent of its norm.
@pytest.mark.parametrize("amp, grad_tol, loss_tol, route_tol", [
    (False, 5e-5, 1e-6, 0.0), (True, 5e-2, 2e-4, 0.01)],
    ids=["float32", "amp"])
def test_program_follows_the_reference(amp, grad_tol, loss_tol, route_tol):
    ids, labels = harness.batches(CFG["vocab"], 2, seed=DATA_SEED)
    states, ref_idx = harness.reference_states(
        ref, CFG, ref.split_state(CFG, seeded_params()), 2, ids, labels)
    (losses, idx, scope), rise = counter_rise(
        lambda: trained_program(amp, 1, ids),
        ("moe.bwd_residual", "moe.rows_bounded", "moe.latent_layers_lowered"))
    # four expert layers: each by the rule, in a latent, on a bounded buffer
    assert rise == (4, 4, 4)
    loss1, grads1 = states[0][0], states[0][1]
    assert abs(losses[0] - loss1) / loss1 < loss_tol
    for name, err in harness.first_step_gaps(scope, grads1, ref).items():
        assert err < grad_tol, (name, err)
    assert harness.route_mismatch(idx[0], ref_idx) <= route_tol
    losses, _, scope = trained_program(amp, 2, ids)
    for t in range(2):
        assert abs(losses[t] - states[t][0]) / states[t][0] < loss_tol
    lr = ref.ADAM["lr"]
    errs = []
    for name, worst, gap, moved, moments in harness.second_step_gaps(
            scope, states, seeded_params()):
        assert worst <= (4.1 if amp else 0.5) * lr, name
        assert gap <= (0.3 if amp else 1e-3) * moved, name
        for acc, err in moments.items():
            errs.append(err)
            # the second batch has tokens of its own at a near-tie, which
            # an expert leaf feels (its second moment twice); the leaves'
            # median is the arithmetic
            assert err < (1.0 if amp else 2 * grad_tol), (name, acc)
    assert np.median(errs) < grad_tol


@pytest.mark.parametrize("wrong, moved, least", [
    (dict(num_experts_per_tok=3), "fewer slots a token", 0.3),
    (dict(routed_scaling_factor=1.0), "the weights without their factor",
     0.3),
    (dict(assumed=dict(CFG["assumed"], routed_left_out="l3_")),
     "one layer's routed part left out", 0.3),
    (dict(assumed=dict(CFG["assumed"], scan_state_dtype="float8_e4m3fn")),
     "the scan's states in float8", 0.05)], ids=lambda v: v if isinstance(
         v, str) else "")
def test_the_reference_tells_each_fault_apart(wrong, moved, least):
    """Each fault the benchmark's driver holds `correct` to moves the
    reference's own gradients by far more than the tolerances above."""
    ids, labels = harness.batches(CFG["vocab"], 1, seed=DATA_SEED)
    worst = harness.worst_leaf_gap(
        ref, CFG, dict(CFG, **wrong),
        ref.split_state(CFG, ref.init_params(CFG, jax.random.key(3))),
        ids[0], labels[0])
    assert worst > least, (moved, worst)


# ---------------------------------------------------------------------------
# routed_moe: more slots a token than experts held, and the experts' own input
# ---------------------------------------------------------------------------

def _layer_operands(gate, latent, seed=0, n=96, d=32, lat=16, f=24, total=32):
    """(x [N, d], z [N, lat] or None, params) of an uncut expert layer."""
    rng = np.random.RandomState(seed)

    def mat(*shape):
        return rng.randn(*shape).astype(np.float32) * 0.25

    width = lat if latent else d
    params = {"gate_w": mat(d, total) * 1.5, "bias": mat(total) * 0.2,
              "eu": mat(total, width, f), "ed": mat(total, f, width)}
    if gate:
        params["eg"] = mat(total, width, f)
    x = rng.randn(n, d).astype(np.float32)
    z = (x @ mat(d, lat)) if latent else None
    return x, z, params


def _plain_loop(x, z, p, offset, held, top_k, scale=5.0):
    """The share's part of sum_k w_k E_{i_k}(z), one held expert after
    another on every token, weighted by a mask: (out, idx)."""
    hi = jax.lax.Precision.HIGHEST
    dot = lambda a, b: jnp.dot(a, b, precision=hi)  # noqa: E731
    scores = jax.nn.sigmoid(dot(x, p["gate_w"]))
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(scores) + p["bias"], top_k)
    w = jnp.take_along_axis(scores, idx, axis=1)
    w = scale * w / jnp.sum(w, axis=1, keepdims=True)
    z = x if z is None else z
    out = jnp.zeros_like(z)
    for e in range(held):
        mine = jnp.sum(jnp.where(idx == offset + e, w, 0.0), axis=1)
        up = dot(z, p["eu"][e])
        act = (jnp.square(jax.nn.relu(up)) if "eg" not in p
               else jax.nn.silu(dot(z, p["eg"][e])) * up)
        out = out + mine[:, None] * dot(act, p["ed"][e])
    return out, idx


def _held(params, offset, held):
    return {k: (v[offset:offset + held] if k in ("eg", "eu", "ed") else v)
            for k, v in params.items()}


def _share_program(x, z, params, offset, top_k, total=32, **grad):
    """One share's `routed_moe` through a Program: [Out, TopIdx,
    ExpertLoad], or with `cot` the gradients of sum(Out * cot) with respect
    to (x, GateW, [ExpertGate,] ExpertUp, ExpertDown[, z])
    (`harness.routed_share`)."""
    return harness.routed_share(x, params, top_k, total, offset, z=z,
                                routed_scaling=5.0, **grad)


@pytest.mark.parametrize("gate, latent, top_k, held", [
    (False, True, 6, 4), (False, False, 6, 4), (True, True, 5, 2),
    (True, False, 3, 1), (False, True, 4, 4)],
    ids=["latent-6-of-4", "one-input-6-of-4", "gated-latent-5-of-2",
         "gated-3-of-1", "latent-4-of-4"])
def test_more_slots_than_experts_held_against_a_plain_loop(gate, latent,
                                                           top_k, held):
    """A share of `held` of 32 experts, a token picking `top_k`: the op's
    output, choice and load, and its gradients by the rule (on the
    forward's residuals) and by the generic route (the forward lowered
    again), are a plain loop's over the held experts and `jax.grad`'s of
    it; the route's gradient goes to what the router read and the experts'
    to what they read. With more slots than experts held the buffer has
    held x N rows (`moe.rows_bounded`), else k x N."""
    offset = 8
    x, z, params = _layer_operands(gate, latent, seed=top_k)
    share = _held(params, offset, held)
    out, idx, load = _share_program(x, z, share, offset, top_k)
    want, want_idx = _plain_loop(jnp.asarray(x), z, share, offset, held,
                                 top_k)
    assert out.shape == (x if z is None else z).shape
    np.testing.assert_allclose(out, np.asarray(want), rtol=2e-5, atol=2e-6)
    assert (np.sort(idx, 1) == np.sort(np.asarray(want_idx), 1)).all()
    assert (load == np.bincount(np.asarray(want_idx).reshape(-1),
                                minlength=32)[offset:offset + held]).all()
    assert load.sum() > 0

    cot = np.random.RandomState(7).randn(*out.shape).astype(np.float32)
    counters = ("moe.bwd_residual", "moe.bwd_recomputed", "moe.rows_bounded",
                "moe.latent_layers_lowered")
    rises, got = [], {}
    for withhold in (False, True):
        got[withhold], rise = counter_rise(lambda: _share_program(
            x, z, share, offset, top_k, cot=cot, withhold=withhold), counters)
        rises.append(rise)
    bounded, lat = int(top_k > held), int(latent)
    assert rises == [(1, 0, bounded, lat), (0, 1, bounded, lat)]
    names = ["X", "GateW"] + ["ExpertGate"] * gate + [
        "ExpertUp", "ExpertDown"] + ["ExpertX"] * latent
    keys = ["gate_w"] + ["eg"] * gate + ["eu", "ed"]

    def loss(x, z, *weights):
        p = dict(share, **dict(zip(keys, weights)))
        return jnp.sum(_plain_loop(x, z, p, offset, held, top_k)[0] * cot)

    want = jax.grad(loss, argnums=tuple(
        i for i in range(2 + len(keys)) if i != 1 or latent))(
        jnp.asarray(x), None if z is None else jnp.asarray(z),
        *(jnp.asarray(share[k]) for k in keys))
    want = list(want[:1]) + list(want[2:] if latent else want[1:]) + (
        [want[1]] if latent else [])
    for name, a, b, c in zip(names, got[False], got[True], want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6, err_msg=name)
        err = np.linalg.norm(a - np.asarray(c)) / np.linalg.norm(c)
        assert err < 2e-5 and np.linalg.norm(c) > 0, (name, err)


def test_the_residuals_have_the_bounded_buffers_rows():
    """22 slots a token on 8 held experts of 512, the cell's numbers at 128
    tokens: `U` is [8 x 128, f] and not [22 x 128, f], and no value of the
    op's forward or backward has 22 x 128 rows of features: the sort's own
    scalars a slot alone."""
    n, d, lat, f, held, total, k = 128, 64, 32, 48, 8, 512, 22
    attrs = {"top_k": k, "routed_scaling": 5.0, "norm_topk": True,
             "experts_total": total, "expert_offset": 0, "scoring": "sigmoid"}
    opdef = registry.get("routed_moe")

    def step(x, z, wg, sb, eu, ed, g):
        ctx = registry.LowerCtx(rng_key=None)
        ins = {"X": [x], "GateW": [wg], "ExpertUp": [eu], "ExpertDown": [ed],
               "SelectBias": [sb], "ExpertX": [z]}
        outs = opdef.lower(ctx, ins, attrs)
        grads = opdef.grad(ctx, ins, attrs,
                           {s: outs[s] for s in opdef.residual_slots
                            if s in outs}, {"Out": [g]})
        return outs, grads

    bf, sd = jnp.bfloat16, jax.ShapeDtypeStruct
    structs = (sd((n, d), jnp.float32), sd((n, lat), bf),
               sd((d, total), jnp.float32), sd((total,), jnp.float32),
               sd((held, lat, f), bf), sd((held, f, lat), bf),
               sd((n, lat), bf))
    outs, grads = jax.eval_shape(step, *structs)
    assert outs["U"][0].shape == (held * n, f)
    assert outs["Inv"][0].shape == (held * n,)
    assert outs["Order"][0].shape == outs["SortedW"][0].shape == (k * n,)
    assert outs["Out"][0].shape == grads["ExpertX"][0].shape == (n, lat)
    assert grads["X"][0].shape == (n, d)
    text = str(jax.make_jaxpr(step)(*structs))
    assert f"[{held * n},{f}]" in text and f"[{held * n},{lat}]" in text
    assert not re.search(rf"\[{k * n},({f}|{lat}|{d})\]", text)


# what the four sparse configurations of the benchmark call, at a small
# size: top_k <= the experts held, one input
_CALLS = {
    "kanana2": (dict(gate=True, bias=True, held=16, total=128, attrs={
        "top_k": 6, "routed_scaling": 2.5, "norm_topk": True,
        "scoring": "sigmoid"}),
        "3dc72d526f2140d1089f83a8e141efcb801baed0596627724782a08eb22381f0"),
    "mellum2": (dict(gate=True, bias=False, held=16, total=64, attrs={
        "top_k": 8, "routed_scaling": 1.0, "norm_topk": True,
        "scoring": "softmax"}),
        "654c4e85dab8f7eacf2ae98e6f9287531019d176c2d291bc9e02584803353e7a"),
    "nemotron_twotower": (dict(gate=False, bias=True, held=8, total=128,
                               attrs={"top_k": 6, "routed_scaling": 2.5,
                                      "norm_topk": True,
                                      "scoring": "sigmoid"}),
                          "1c71ac024a90176c6f3a5f4dd2cae00ed6723204c1842908"
                          "84be311f4b5cd52b"),
    "ling3": (dict(gate=True, bias=True, held=8, total=512, attrs={
        "top_k": 8, "routed_scaling": 2.5, "norm_topk": True,
        "scoring": "sigmoid", "n_group": 8, "topk_group": 4}),
        "b1d2f1e9a4db8534c42e2506e9f7d790fa876875b70e643275c4c297d288f1f8"),
}


@pytest.mark.parametrize("cell", sorted(_CALLS))
def test_as_the_four_sparse_cells_call_it_routed_moe_traces_as_before(
        cell, monkeypatch):
    """One input and no more slots a token than experts held: the op's
    forward and its grad rule trace to one jaxpr whatever the experts' own
    input and the bounded buffer added (jax 0.9.0, source lines cut). The
    digests were made at commit 147251f, before both, and again where a
    deliberate change to `routed_moe` changed them with it: PR 41's route
    without scalar gathers (`tests/test_moe_route.py` holds that route to
    the one before it, bit for bit)."""
    from paddle_tpu.ops.pallas import grouped_matmul
    monkeypatch.setattr(grouped_matmul, "interpret_mode", lambda: False)
    call, digest = _CALLS[cell]
    call = dict(call, attrs=dict(call["attrs"], experts_total=call["total"],
                                 expert_offset=0))
    assert harness.sha256(harness.routed_moe_jaxpr(n=256, **call)) == digest


def test_without_a_latent_and_with_every_head_the_model_traces_as_before():
    """`NemotronHConfig.tiny()` has no latent and holds every head: its
    float32 train step traces to the jaxpr it had before the new keys
    (jax 0.9.0; source lines cut; made at commit 147251f, and again with
    PR 41's route, the one change to its ops since)."""
    step, _ = harness.tiny_step_digests(harness.causal_lm(
        nemotron_h, nemotron_h.NemotronHConfig.tiny()))
    assert step == (
        "dc9e4d6bbb4513b2741e514caf9d4b7195cf9afe151839d818cf5f3bd66c00c9")


# ---------------------------------------------------------------------------
# the shares add up
# ---------------------------------------------------------------------------

# an uncut layer of 16 state-space heads in 8 groups and 16 query heads on 2
# KV heads, cut into 8 shares as the configuration cuts 128 in 8 and 32 on 2
WHOLE = dict(CFG, mamba_num_heads=16, n_groups=8, num_attention_heads=16,
             num_key_value_heads=2)
SHARE = dict(CFG, mamba_num_heads=2, n_groups=1, num_attention_heads=2,
             num_key_value_heads=1)


def _mixer_program(kind, x, params, pre):
    """One share's mixer through a Program, built for its held heads."""
    mcfg = model_config(seq=x.shape[1])
    mcfg.mamba_num_heads, mcfg.n_groups = 16, 8
    mcfg.mamba_heads_held, mcfg.mamba_groups_held = 2, 1
    mcfg.num_attention_heads, mcfg.num_key_value_heads = 16, 2
    mcfg.heads_held, mcfg.kv_heads_held = 2, 1
    build = (nemotron_h.mamba_mixer if kind == nemotron_h.MAMBA
             else nemotron_h.grouped_attention)
    return harness.mixer_program(build, mcfg, x, params, pre)


def _cols(value, widths, pick):
    """Of the blocks of columns `widths` of value [..., sum(widths)], the
    `pick(block)` of each: a share's columns of [z | x | B | C | dt]."""
    parts, lo = [], 0
    for i, w in enumerate(widths):
        parts.append(pick(i, value[..., lo:lo + w]))
        lo += w
    return np.concatenate(parts, axis=-1)


def _mamba_share(params, pre, g):
    """Group g's leaves of an uncut state-space mixer (8 groups of 2 heads
    of 8 features, state 16): its z, x, B, C and dt columns, its channels
    of the conv, its heads' parameters, its slice of the gated norm and its
    rows of the output projection."""
    hp, n, per = 8, 16, 2
    chan = slice(g * per * hp, (g + 1) * per * hp)
    state = slice(g * n, (g + 1) * n)
    head = slice(g * per, (g + 1) * per)
    take = [chan, chan, state, state, head]
    p = {k[len(pre):]: np.asarray(v) for k, v in params.items()}
    xbc = lambda v: _cols(v, [128, 128, 128],  # noqa: E731
                          lambda i, b: b[..., take[1 + i]])
    out = {"in_proj_w": _cols(p["in_proj_w"], [128, 128, 128, 128, 16],
                              lambda i, b: b[..., take[i]]),
           "conv_w": xbc(p["conv_w"]), "conv_b": xbc(p["conv_b"]),
           "dt_bias": p["dt_bias"][head], "A_log": p["A_log"][head],
           "D": p["D"][head], "ssm_norm_scale": p["ssm_norm_scale"][chan],
           "out_proj_w": p["out_proj_w"][chan]}
    return {pre + k: v for k, v in out.items()}


def _attention_share(params, pre, i):
    """Query heads 2i, 2i + 1 of 16 and the KV head they read (i // 4 of
    2): columns of the projections into heads, rows of W_o."""
    hd = 16
    q = slice(2 * i * hd, (2 * i + 2) * hd)
    kv = slice((i // 4) * hd, (i // 4 + 1) * hd)
    p = {k[len(pre):]: np.asarray(v) for k, v in params.items()}
    out = {"q_proj_w": p["q_proj_w"][:, q], "k_proj_w": p["k_proj_w"][:, kv],
           "v_proj_w": p["v_proj_w"][:, kv], "o_proj_w": p["o_proj_w"][q]}
    return {pre + k: v for k, v in out.items()}


@pytest.mark.parametrize("kind, n, cut, reference", [
    (nemotron_h.MAMBA, 0, _mamba_share, base_ref.mamba_mixer),
    (nemotron_h.ATTENTION, 5, _attention_share, base_ref.attention)],
    ids=["mamba2", "attention"])
def test_the_eight_head_shares_add_up_to_the_uncut_mixer(kind, n, cut,
                                                         reference):
    """A state-space mixer's 8 B/C groups one a chip, and 16 query heads on
    2 KV heads over 8 chips (a KV head on four of them): the 8 shares'
    mixer outputs, each through the program built for its held heads only,
    sum to the reference's uncut layer; each share is the reference's
    share."""
    pre = f"l{n}_"
    key = jax.random.key(5)
    leaves = [k for k in base_ref.param_shapes(WHOLE)
              if k.startswith(pre) and not k.endswith("_norm_scale")
              or k == pre + "ssm_norm_scale"]
    params = {k: base_ref.init_leaf(WHOLE, key, k) for k in leaves}
    if kind == nemotron_h.MAMBA:   # a gated norm whose slices differ
        params[pre + "ssm_norm_scale"] = 1.0 + 0.1 * jax.random.normal(
            key, (128,))
    x = np.random.RandomState(4).randn(2, S, 64).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference(jnp.asarray(x), params, pre, WHOLE))
        total = 0.0
        for i in range(8):
            share = cut(params, pre, i)
            got = _mixer_program(kind, x, share, pre)
            part = np.asarray(reference(
                jnp.asarray(x), {k: jnp.asarray(v) for k, v in share.items()},
                pre, SHARE))
            np.testing.assert_allclose(got, part, rtol=2e-4, atol=2e-6)
            total = total + got
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-6)
    assert np.abs(want).max() > 1e-3


def test_the_ranks_routed_parts_in_the_latent_and_the_shared_expert_add_up():
    """32 experts cut into 16 shares of 2, a token picking 6, as the
    configuration cuts 512 into 64 of 8 with 22 picked: the routed parts
    all shares give (`routed_moe` on z = x W_a, more slots than experts
    held), SUMMED IN THE LATENT and sent through W_b once, plus the shared
    expert that every rank computes alike counted once, are the reference's
    uncut expert layer; every share's TopIdx is the reference's choice."""
    x, _, params = _layer_operands(False, True, seed=3, d=32, lat=16, f=24)
    rng = np.random.RandomState(11)

    def mat(*shape):
        return rng.randn(*shape).astype(np.float32) * 0.25

    extra = {"latent_down_w": mat(32, 16), "latent_up_w": mat(16, 32),
             "shared_up_w": mat(32, 40), "shared_down_w": mat(40, 32)}
    names = {"gate_w": "router_w", "bias": "router_bias",
             "eu": "experts_up_w", "ed": "experts_down_w"}
    p = {"l_" + names.get(k, k): jnp.asarray(v)
         for k, v in dict(params, **extra).items()}
    cfg = dict(n_routed_experts=32, experts_total=32, expert_offset=0,
               num_experts_per_tok=6, norm_topk_prob=True,
               routed_scaling_factor=5.0, assumed={})
    with jax.default_matmul_precision("highest"):
        whole, want_idx = ref.expert_layer(jnp.asarray(x)[None], p, "l_", cfg)
    want_idx = np.asarray(want_idx)
    z = x @ extra["latent_down_w"]
    summed, loads = 0.0, []
    for offset in range(0, 32, 2):
        out, idx, load = _share_program(x, z, _held(params, offset, 2),
                                        offset, 6)
        summed = summed + out
        loads.append(load)
        assert (np.sort(idx, 1) == np.sort(want_idx, 1)).all()
    shared = np.asarray(base_ref.relu2_ffn(
        jnp.asarray(x), p["l_shared_up_w"], p["l_shared_down_w"]))
    np.testing.assert_allclose(summed @ extra["latent_up_w"] + shared,
                               np.asarray(whole)[0], rtol=2e-4, atol=2e-5)
    assert (np.concatenate(loads) == np.bincount(
        want_idx.reshape(-1), minlength=32)).all()


# ---------------------------------------------------------------------------
# the builder
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("held, message", [
    (dict(mamba_heads_held=3, mamba_groups_held=1), "whole groups"),
    (dict(mamba_heads_held=4, mamba_groups_held=2), "whole groups"),
    (dict(heads_held=3, kv_heads_held=2), "query heads on"),
    (dict(heads_held=5, kv_heads_held=1), "query heads on")],
    ids=lambda v: "" if isinstance(v, str) else "-".join(
        f"{k}{n}" for k, n in v.items()))
def test_a_share_that_is_no_share_is_refused(held, message):
    cfg = nemotron_h.NemotronHConfig.tiny_latent_share()
    for key, value in held.items():
        setattr(cfg, key, value)
    reset_programs(0)
    with pytest.raises(ValueError, match=message):
        nemotron_h.build_causal_lm_program(cfg)


def test_builder_names_scopes_and_verifies():
    reset_programs(0)
    cfg = model_config()
    _, loss, routed = nemotron_h.build_causal_lm_program(cfg)
    prog = fluid.default_main_program()
    moe_ops = [op for op in prog.global_block().ops
               if op.type == "routed_moe"]
    assert len(moe_ops) == len(routed) == 4
    assert all("ExpertX" in op.inputs and "ExpertGate" not in op.inputs
               and op.attrs["top_k"] == 6 and op.attrs["experts_total"] == 32
               for op in moe_ops)
    # the experts' input and output are the latent's width
    block = prog.global_block()
    assert all(tuple(block.var(op.inputs["ExpertX"][0]).shape)[-1] == 24
               and tuple(block.var(op.outputs["Out"][0]).shape)[-1] == 24
               for op in moe_ops)
    paddle.optimizer.Adam(1e-4).minimize(loss)
    errors = [f for f in verifier.verify_program(prog)
              if f.severity == "error"]
    assert not errors, errors


@pytest.mark.parametrize("recompute, rise", [
    (False, (4, 4, 0, 4, 4)), (True, (4, 0, 0, 4, 4))],
    ids=["plain", "recompute"])
def test_a_trace_of_the_amp_step_counts_its_routes(recompute, rise):
    """One trace of the AMP train step lowers four expert layers, each in a
    latent on a bounded buffer; their backward by the op's grad rule on the
    forward's residuals or, with a checkpoint at every layer boundary (the
    cell's way), under `jax.vjp` of a whole layer where its segment is
    lowered, once (`moe.bwd_recomputed` stays). The two projections'
    scopes reach the compiled step, forward and backward; the experts'
    input reaches the op in bf16 and the router's in float32."""
    exe, loss, ids = harness.amp_step(nemotron_h, model_config(), recompute)
    jaxpr, got = counter_rise(
        lambda: str(exe.step_jaxpr({"tokens": ids}, [loss], k=2)),
        ("moe.layers_lowered", "moe.bwd_residual", "moe.bwd_recomputed",
         "moe.rows_bounded", "moe.latent_layers_lowered"))
    assert got == rise
    # U [4 x 32, 32] in bf16: 4 held experts' rows, not 6 slots'
    assert "bf16[128,32]" in jaxpr and "bf16[192,32]" not in jaxpr
    hlo = exe.compiled_hlo({"tokens": ids}, [loss], k=2)
    for scope in ("moe.latent_down", "moe.latent_up", "moe.shared",
                  "moe.experts", "moe.route"):
        assert f"/{scope}/" in hlo, scope
    assert re.search(r"transpose\(jvp\([^)]*\)\)/moe\.latent_(down|up)/|"
                     r"moe\.latent_(down|up)/[^\"]*transpose", hlo)
