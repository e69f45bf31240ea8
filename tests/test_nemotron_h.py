"""The Nemotron-H-family hybrid LM (`models/nemotron_h.py`: a Mamba-2
state-space mixer, ungated relu^2 experts with a shared one, or attention on
grouped KV heads without rotary positions a layer, by a pattern string; one
expert-parallel rank's share) against its plain float32 reference
(`benchmark/reference/nemotron_h.py`), on the CPU at tiny widths with seeded
weights; and what the model forced on `routed_moe`: experts that have no
gate. The chunked selective scan (`ops/ssm.py`), the causal conv and the
gated grouped norm have their own file, `tests/test_ssm_scan.py`.
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
from paddle_tpu.models import nemotron_h
from paddle_tpu.ops import registry
from paddle_tpu.testing import reset_programs
from benchmark.reference import nemotron_h as ref

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


def seeded_params():
    return ref.init_params(CFG, jax.random.key(3))


def trained_program(amp, k, ids):
    return harness.trained_program(nemotron_h, model_config(CFG), ref,
                                   seeded_params(), amp, k, ids)


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
    ids, labels = harness.batches(CFG["vocab"], 2, seed=DATA_SEED)
    states, ref_idx = harness.reference_states(
        ref, CFG, ref.split_state(CFG, seeded_params()), 2, ids, labels)

    (losses, idx, scope), rise = counter_rise(
        lambda: trained_program(amp, 1, ids),
        ("ssm.bwd_residual", "ssm.bwd_recomputed"))
    # the four scans' backward took the rule, on the forward's residuals
    assert rise == (4, 0)
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
        assert worst <= (4.1 if amp else 0.5) * lr, name
        assert gap <= (0.3 if amp else 1e-3) * moved, name
        for acc, err in moments.items():
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
    ids, labels = harness.batches(CFG["vocab"], 1, seed=DATA_SEED)
    bad_cfg = dict(CFG, assumed=dict(CFG["assumed"], **fault))
    worst = harness.worst_leaf_gap(
        ref, CFG, bad_cfg, ref.split_state(CFG, seeded_params()), ids[0],
        labels[0])
    assert worst > least, (moved, worst)


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


def _share_program(x, params, offset, held, total, top_k=3, **grad):
    """One share's `routed_moe` without `ExpertGate` (sigmoid scoring, a
    selection bias) through a Program: [Out, TopIdx, ExpertLoad], or with
    `cot` the gradients of sum(Out * cot) with respect to (x, GateW,
    ExpertUp, ExpertDown) (`harness.routed_share`)."""
    return harness.routed_share(
        x, harness.held_arrays(params, offset, held), top_k, total, offset,
        routed_scaling=2.5, **grad)


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
        got, rise = counter_rise(lambda: _share_program(
            x, params, 4, 4, 32, withhold=withhold, cot=cot),
            counters + grouped)
        rises.append(rise[:2])
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
    attrs = {"top_k": 2, "routed_scaling": 2.5, "norm_topk": True,
             "experts_total": 16, "expert_offset": 4, "scoring": scoring}
    return harness.routed_moe_jaxpr(True, bias, 4, 16, attrs, n=512)


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
    assert harness.sha256(text) == digest


# ---------------------------------------------------------------------------
# the builder
# ---------------------------------------------------------------------------

_ROUTES = ("ssm.scan_pallas", "ssm.scan_xla")
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
    """(executor, loss, ids [2, 1, seq_len]) of the tiny preset at 128
    tokens in chunks of 32 with `changed` set, its AMP train step built
    through fleet, with a checkpoint at every layer boundary if
    `recompute`."""
    cfg = nemotron_h.NemotronHConfig.tiny()
    cfg.seq_len, cfg.chunk_size = 128, 32
    for key, value in changed.items():
        setattr(cfg, key, value)
    return harness.amp_step(nemotron_h, cfg, recompute)


@pytest.mark.parametrize("recompute, rise", [
    (False, (4, 4, 0, 4, 4, 0, 1, 1, 1, 0, 0, 8)),
    (True, (4, 0, 0, 4, 0, 0, 1, 1, 0, 0, 0, 12))], ids=["plain", "recompute"])
def test_a_trace_of_the_step_counts_its_routes(recompute, rise, monkeypatch):
    """With the flash gate open (here: the interpreter), one trace of the
    AMP train step lowers four scans, four expert layers and one flash
    forward on grouped KV heads; their backward by each op's grad rule on
    the forward's residuals, or, with a checkpoint at every layer boundary
    (the cell's way: the step does not fit the chip without), by the same
    backward functions under `jax.vjp` of a whole layer, taken where the
    layer's segment is lowered, once (no `*.bwd_recomputed`: nothing is
    lowered AGAIN; each scan's forward is traced by its `custom_vjp`'s body
    and by its forward rule). At the preset's widths (state 16, 8 features a head) every
    scan, forward and backward, is the `jax.numpy` form. The step's jaxpr
    holds no `[S, H, P, N]` value."""
    from paddle_tpu.ops import attention
    monkeypatch.setattr(attention, "_use_pallas",
                        lambda q: q.shape[2] % 128 == 0)
    exe, loss, ids = _amp_step(recompute, head_dim=64)
    jaxpr, got = counter_rise(
        lambda: str(exe.step_jaxpr({"tokens": ids}, [loss], k=2)), _COUNTERS)
    assert got == rise
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
    from paddle_tpu.ops.pallas import ssm_chunk
    entries = (ssm_chunk._ssd_fwd, ssm_chunk._ssd_bwd)
    traced = [f._cache_size() for f in entries]
    jaxpr, rise = counter_rise(
        lambda: str(exe.step_jaxpr({"tokens": ids}, [loss], k=2)), _ROUTES)
    assert rise == (kernels, 0)
    # the four layers enter each kernel through one jitted function: one
    # trace of it (none here if the other case of this test made it)
    assert all(f._cache_size() - t <= 1 for f, t in zip(entries, traced))
    for name in ("ssm-chunk-fwd", "ssm-chunk-bwd"):
        assert re.search(rf"name={name}\s", jaxpr), name
    assert "f32[1,2,4,64,128]" in jaxpr
    assert not re.search(r"f32\[1,2,2,2,128,128\]", jaxpr)


@pytest.mark.parametrize("recompute, kernels", [(False, 24), (True, 32)],
                         ids=["plain", "recompute"])
def test_at_an_unaligned_expert_width_every_grouped_matmul_is_a_kernel(
        recompute, kernels):
    """The cell's expert width is 1856 = 14.5 x 128; here hidden 128 and an
    expert width of 232 = 29 x 8: a lane tile or more, no multiple of 128.
    One trace of the AMP train step sends every grouped matmul (2 forward
    and 4 backward a layer; under recomputation the trace passes the
    forward's two once more, in the `custom_vjp`'s forward rule) to the
    Pallas kernels, the width as one
    block, and none to `jax.lax.ragged_dot`."""
    exe, loss, ids = _amp_step(recompute, hidden_size=128,
                               moe_intermediate_size=232)
    counters = ("moe.layers_lowered", "moe.grouped_pallas",
                "moe.grouped_xla")
    jaxpr, rise = counter_rise(
        lambda: str(exe.step_jaxpr({"tokens": ids}, [loss], k=2)), counters)
    assert rise == (4, kernels, 0)
    assert "ragged_dot" not in jaxpr
    for form in ("gmm", "gmm-t", "tgmm"):
        assert re.search(rf"name=ragged-dot-{form}\s", jaxpr), form
    assert "bf16[8,128,232]" in jaxpr and "bf16[8,232,128]" in jaxpr
