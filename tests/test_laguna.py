"""The Laguna-family LM (`models/laguna.py`: a query-head count that differs
with the layer's kind, 6-and-8 groups on the same KV heads, rotary positions
on the FIRST half of a head in the full layers and on all of it in the
sliding ones, an element-wise output gate, a dense layer before
sigmoid-routed experts scaled by 2.5 beside a shared one, one
expert-parallel rank's share) against its plain float32 reference
(`benchmark/reference/laguna_xs2.py`), on the CPU at tiny widths with seeded
weights; and what the model forced on the ops: `rotary_embedding`'s
`rotary_start`, the flash kernels under the Pallas interpreter at a group
of 6.
"""
import functools
import hashlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import causal_lm_harness as harness
from causal_lm_harness import S, run_op as _run_op
import test_step_scopes as step_scopes

import paddle_tpu as paddle
import paddle_tpu.fluid as fluid
from paddle_tpu.models import laguna
from paddle_tpu.observability import scopes
from paddle_tpu.ops import attention, registry
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.testing import reset_programs
from benchmark.reference import laguna_xs2 as ref

SLIDING, FULL = "sliding_attention", "full_attention"
ROPE = {FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 4,
               "original_max_position_embeddings": 16, "beta_slow": 1,
               "beta_fast": 64, "attention_factor": 1.4158883083359672,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000,
                  "partial_rotary_factor": 1}}
# published layers 0..2 of a model of 8 whose kinds alternate, so that three
# layers hold every combination the family has (full + dense, sliding +
# sparse, full + sparse) and one compiled step stays small: 12 and 16 query
# heads on 2 KV heads (groups of 6 and of 8), a window of 8 in rows of 32,
# experts 4..7 of 8
CFG = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=8,
           num_key_value_heads=2, head_dim=16,
           layer_types=[FULL, SLIDING] * 4,
           num_attention_heads_per_layer=[12, 16] * 4,
           mlp_layer_types=["dense"] + ["sparse"] * 7, sliding_window=8,
           rope_parameters=ROPE, gating=True, num_experts=4, experts_total=8,
           expert_offset=4, num_experts_per_tok=2, moe_intermediate_size=32,
           shared_expert_intermediate_size=32, moe_routed_scaling_factor=2.5,
           moe_apply_router_weight_on_input=False, rms_norm_eps=1e-6,
           layers=3, first_layer=0, vocab=256,
           assumed={"initializer_std": 0.02})
SHARED = ("hidden_size", "intermediate_size", "num_hidden_layers",
          "num_key_value_heads", "head_dim", "sliding_window",
          "rope_parameters", "gating", "num_experts_per_tok",
          "moe_intermediate_size", "shared_expert_intermediate_size",
          "moe_routed_scaling_factor", "moe_apply_router_weight_on_input",
          "rms_norm_eps", "expert_offset", "first_layer")


def model_config(cfg, seq=S):
    return laguna.LagunaConfig(
        vocab_size=cfg["vocab"], num_layers_held=cfg["layers"],
        num_experts=cfg["experts_total"], experts_held=cfg["num_experts"],
        layer_types=tuple(cfg["layer_types"]),
        num_attention_heads_per_layer=tuple(
            cfg["num_attention_heads_per_layer"]),
        mlp_layer_types=tuple(cfg["mlp_layer_types"]), seq_len=seq,
        **{k: cfg[k] for k in SHARED})


def seeded_params():
    return ref.init_params(CFG, jax.random.key(3))


DATA_SEED = 1
# Tolerances and their reasons: `tests/test_mellum.py`'s and
# `tests/test_solar.py`'s, the same mechanisms at the same size. float32:
# the order of sums. AMP: every matmul operand rounded to bf16; a token at a
# near-tie of two sigmoid scores routed elsewhere turns signs all over the
# routed leaves and the norm that feeds them alone.
_ROUTED = ("router_w", "experts_gate_w", "experts_up_w", "experts_down_w",
           "ffn_norm_scale")


@functools.lru_cache(maxsize=None)
def _trained(amp):
    """ONE built program a precision (float32 as built; AMP with a
    checkpoint at every layer boundary, the cell's way), run from the
    reference's seeded weights for two `run_steps(1)` calls: each step's
    loss, the first step's routed choice and per-leaf gradient gaps, the
    second step's per-leaf state gaps, and the `op_name` of every
    instruction of the compiled step that a lowering made (the k-step loop's
    own plumbing, whose `op_name` ends at the body's `closed_call`, is no
    op's)."""
    ids, states, _ = _reference_steps()
    exe, loss, routed = harness.train_step(
        laguna, model_config(CFG), amp, recompute=amp, lr=ref.ADAM["lr"])
    scope = fluid.global_scope()
    for name, value in seeded_params().items():
        assert tuple(scope.find(name).shape) == tuple(value.shape), name
        scope.set(name, value)
    fetch, got = [loss, routed[0][0]], {"losses": []}
    for t in range(2):
        out = exe.run_steps(1, feed={"tokens": ids[t:t + 1]},
                            fetch_list=fetch)
        got["losses"].append(float(np.asarray(out[0]).reshape(-1)[0]))
        if not t:
            got["first_route"] = np.asarray(out[1])[0]
            got["first_gaps"] = harness.first_step_gaps(scope, states[0][1],
                                                        ref)
    got["second_gaps"] = list(harness.second_step_gaps(
        scope, states, seeded_params(), floor_by_first_step=True))
    hlo = exe.compiled_hlo({"tokens": ids[:1]}, fetch, k=1)
    got["op_names"] = [
        op_name for opcode, op_name in step_scopes._INSTRUCTION.findall(hlo)
        if opcode not in ("parameter", "constant")
        and op_name.startswith("jit(")
        and not op_name.endswith("/closed_call")]
    exe.close()
    return got


@functools.lru_cache(maxsize=None)
def _reference_steps():
    ids, labels = harness.batches(CFG["vocab"], 2, seed=DATA_SEED)
    states, ref_idx = harness.reference_states(
        ref, CFG, (seeded_params(),), 2, ids, labels)
    return ids, states, ref_idx


@pytest.mark.parametrize("amp, grad_tol, loss_tol", [
    (False, 1e-4, 1e-6), (True, 6e-2, 2e-4)], ids=["float32", "amp"])
def test_program_follows_the_reference(amp, grad_tol, loss_tol):
    """Loss, every leaf's gradient and two Adam steps over published layers
    0..2 (full + dense, sliding + sparse, full + sparse), through
    `Executor.run_steps`."""
    def tol(name):
        return grad_tol * (10 if amp and name.endswith(_ROUTED) else 1)

    _, states, ref_idx = _reference_steps()
    got = _trained(amp)
    for t in range(2):
        assert abs(got["losses"][t] - states[t][0]) / states[t][0] < loss_tol
    for name, err in got["first_gaps"].items():
        assert err < tol(name), (name, err)
    assert harness.route_mismatch(got["first_route"], ref_idx) <= (
        0.02 if amp else 0)
    lr = ref.ADAM["lr"]
    for name, worst, gap, moved, moments in got["second_gaps"]:
        assert worst <= (4.1 if amp else 0.5) * lr, name
        share = (0.6 if name.endswith(_ROUTED) else 0.45) if amp else 2e-3
        assert gap <= share * moved, name
        for acc, err in moments.items():
            assert err < 2 * tol(name), (name, acc, err)


def test_the_references_own_follow_is_its_block_grads_and_adam():
    """`follow` (moments on the host between steps, every leaf updated by
    itself) gives the losses, the first moments' norms and the parameters'
    change of the plain loop over whole trees."""
    ids, states, ref_idx = _reference_steps()
    labels = harness.batches(CFG["vocab"], 2, seed=DATA_SEED)[1]
    got = ref.follow(CFG, seeded_params,
                     [{"ids": ids[t], "labels": labels[t]} for t in range(2)],
                     rows_per_block=harness.B)
    p0 = seeded_params()
    for t in range(2):
        assert abs(got["losses"][t] - states[t][0]) < 1e-5 * states[t][0]
    _, _, params, m, _ = states[1]
    for name in params:
        want = float(jnp.linalg.norm(m[name]))
        assert abs(got["moment1_norms"][name] - want) <= 1e-4 * want + 1e-12
        moved = float(jnp.linalg.norm(params[name] - p0[name]))
        assert abs(got["delta_norms"][name] - moved) <= 1e-3 * moved + 1e-9
    # the first moments the comparison takes as vectors: every k_proj_w
    assert sorted(got["moment1_vectors"]) == sorted(
        ref.vector_leaves(CFG)) == [f"l{n}_k_proj_w" for n in range(3)]
    for name, vector in got["moment1_vectors"].items():
        want = np.asarray(m[name])
        assert np.linalg.norm(vector - want) <= 1e-4 * np.linalg.norm(want)
    # the first SPARSE layer's choice: layer 0 is dense and routes nothing
    assert (np.asarray(got["first_route"]) == ref_idx).all()
    assert ref_idx.shape == (harness.B * S, 2)


# layers 0 and 1: a full and a sliding layer, a dense part and a sparse one
CFG2 = dict(CFG, layers=2)


@functools.lru_cache(maxsize=None)
def _sound_gradient():
    ids, labels = harness.batches(CFG["vocab"], 1, seed=DATA_SEED)
    params = ref.init_params(CFG2, jax.random.key(3))
    _, _, want = ref._block_grad(params, ids[0], labels[0],
                                 ref._cfg_key(CFG2), None)
    return params, ids[0], labels[0], want


@pytest.mark.parametrize("fault, least", [
    ("gate_left_out", 0.3), ("full_rotary_all", 0.05),
    ("rotary_last_half", 0.05), ("full_grouped_by_8", 0.1),
    ("window_ignored", 0.1), ("scaling_1", 0.3), ("fp8", 0.02)])
def test_the_reference_tells_each_fault_apart(fault, least):
    """Each thing the new mechanisms admit going wrong, and the fp8
    control, moves some leaf's gradient in the reference itself by far more
    than the float32 tolerance above (32 tokens here; the chip's
    `calibrate` has the readings at 8,192)."""
    params, ids, labels, want = _sound_gradient()
    bad = CFG2 if fault == "fp8" else dict(
        CFG2, assumed=dict(CFG2["assumed"], fault=fault))
    _, _, got = ref._block_grad(params, ids, labels, ref._cfg_key(bad),
                                "fp8" if fault == "fp8" else None)
    worst = max(float(jnp.linalg.norm(got[n] - want[n])
                      / jnp.linalg.norm(want[n])) for n in want)
    assert worst > least, (fault, worst)


# ---------------------------------------------------------------------------
# the shares add up
# ---------------------------------------------------------------------------

def _expert_cfg(held, total, offset, top_k):
    return dict(num_experts=held, experts_total=total, expert_offset=offset,
                num_experts_per_tok=top_k, moe_routed_scaling_factor=2.5,
                assumed={})


def _eight_shares(x, params, held, total, top_k):
    """Every rank's `routed_moe` (sigmoid scores, the selection bias, the
    scaling 2.5) in ONE Program on the same tokens, rank r holding experts
    `r * held` .. of `total`: [(Out, TopIdx, ExpertLoad) a rank]."""
    from paddle_tpu.fluid import layers
    reset_programs(0)
    xv = layers.data(name="x", shape=[x.shape[1]], dtype="float32")
    fetch, values = [], {}
    for rank in range(total // held):
        arrays = harness.held_arrays(params, rank * held, held)
        var = {k: layers.create_parameter(list(v.shape), "float32",
                                          name=f"r{rank}_{k}")
               for k, v in arrays.items()}
        var["bias"].stop_gradient = True
        values.update({f"r{rank}_{k}": v for k, v in arrays.items()})
        fetch += layers.routed_moe(
            xv, var["gate_w"], var["eg"], var["eu"], var["ed"], top_k=top_k,
            select_bias=var["bias"], experts_total=total,
            expert_offset=rank * held, routed_scaling=2.5)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    for name, value in values.items():
        fluid.global_scope().set(name, jnp.asarray(value))
    out = [np.asarray(v) for v in exe.run(feed={"x": x}, fetch_list=fetch)]
    return [tuple(out[i:i + 3]) for i in range(0, len(out), 3)]


def test_the_eight_ranks_routed_parts_and_the_shared_expert_add_up():
    """32 experts cut into 8 ranks of 4 (`expert_offset` 0, 4, ..), top-8
    under the scaling 2.5, as the configuration cuts 256 into 8 of 32: the
    routed parts all ranks give plus the shared expert COUNTED ONCE are the
    uncut reference's layer; each rank's part is the reference's share,
    every rank's TopIdx is the reference's choice and the loads are its
    counts."""
    x, params = harness.uncut_expert_layer(32)
    p = {"l_" + k: jnp.asarray(v) for k, v in params.items()}
    with jax.default_matmul_precision("highest"):
        whole, want_idx = ref.expert_layer(jnp.asarray(x)[None], p, "l_",
                                           _expert_cfg(32, 32, 0, 8))
        parts = ref.routed_experts(jnp.asarray(x), p, "l_",
                                   _expert_cfg(32, 32, 0, 8))[0]
    want_idx = np.asarray(want_idx)
    total, loads = 0.0, []
    for rank, (out, idx, load) in enumerate(_eight_shares(x, params, 4, 32,
                                                          8)):
        cut = {k: (v if k.startswith(("l_router", "l_shared"))
                   else v[4 * rank:4 * rank + 4]) for k, v in p.items()}
        with jax.default_matmul_precision("highest"):
            part, _ = ref.routed_experts(jnp.asarray(x), cut, "l_",
                                         _expert_cfg(4, 32, 4 * rank, 8))
        np.testing.assert_allclose(out, np.asarray(part), rtol=2e-4,
                                   atol=2e-6)
        total = total + out
        loads.append(load)
        assert (np.sort(idx, 1) == np.sort(want_idx, 1)).all()
    np.testing.assert_allclose(total, np.asarray(parts), rtol=2e-4,
                               atol=2e-5)
    shared = np.asarray(ref.swiglu_ffn(
        jnp.asarray(x), p["l_shared_gate_w"], p["l_shared_up_w"],
        p["l_shared_down_w"]))
    np.testing.assert_allclose(total + shared, np.asarray(whole)[0],
                               rtol=2e-4, atol=2e-5)
    assert (np.concatenate(loads) == np.bincount(
        want_idx.reshape(-1), minlength=32)).all()


# ---------------------------------------------------------------------------
# rotary positions with the turned part first
# ---------------------------------------------------------------------------

_YARN = dict(rope_type="yarn", factor=64.0, original_max_position=4096,
             beta_fast=64.0, beta_slow=1.0, scale=1.4158883083359672)


def test_yarn_over_the_turned_half_against_hand_worked_numbers():
    """64 turned features, theta 5e5, factor 64, original 4096, beta 64 / 1:
    c(64) = 64 ln(4096 / (128 pi)) / (2 ln 5e5) = 5.66 and c(1) = 15.80, so
    low 5 and high 16: pair 5 is untouched, pair 16 divided by 64, pair 10
    5/11 up the ramp; the table has 32 pairs, not 64."""
    from paddle_tpu.ops import llm_ops
    published = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                 "original_max_position_embeddings": 4096, "beta_slow": 1,
                 "beta_fast": 64}
    plain = 500000.0 ** (-2.0 * np.arange(32) / 64)
    for table in (llm_ops.rotary_frequencies(500000, 64, "yarn", 64, 4096,
                                             64, 1),
                  ref.rope_frequencies(published, 64)):
        assert table.shape == (32,)
        np.testing.assert_allclose(table[:6], plain[:6], rtol=1e-12)
        np.testing.assert_allclose(table[16:], plain[16:] / 64, rtol=1e-12)
        ramp = (plain[6:16] - table[6:16]) / (plain[6:16] * (1 - 1 / 64))
        np.testing.assert_allclose(ramp, np.arange(1, 11) / 11, rtol=1e-9)


@pytest.mark.parametrize("layout", ["half", "interleaved"])
@pytest.mark.parametrize("start", [0, 4, 8])
def test_rotary_embedding_turns_the_part_it_is_told(layout, start):
    """`rotary_start`: 8 of 16 features turn from there, the others pass,
    the pairs lie inside the turned part ((j, j + 4) or (2j, 2j + 1)), the
    table is over the 8; against the equations written out in complex
    numbers, and for the family's case (first half, yarn with its scale)
    against the reference's `rope`."""
    rng = np.random.RandomState(1)
    x = rng.randn(2, 3, 9, 16).astype(np.float32)        # [B, nh, S, D]
    attrs = {"theta": 500000.0, "rotary_dim": 8, "layout": layout,
             "rotary_start": start}
    scale = 1.0
    if layout == "half":
        attrs.update(_YARN, original_max_position=16, factor=4.0)
        scale = _YARN["scale"]
    out, = _run_op("rotary_embedding", {"X": x}, ["Out"], attrs)
    freq = ref.rope_frequencies(
        ROPE[FULL] if layout == "half" else {"rope_type": "default",
                                             "rope_theta": 500000}, 8)
    part = x[..., start:start + 8]
    a, b = ((part[..., :4], part[..., 4:]) if layout == "half"
            else (part[..., 0::2], part[..., 1::2]))
    z = (a + 1j * b) * np.exp(1j * np.arange(9)[:, None] * freq) * scale
    want = x.copy()
    if layout == "half":
        want[..., start:start + 4], want[..., start + 4:start + 8] = (
            z.real, z.imag)
    else:
        want[..., start:start + 8:2], want[..., start + 1:start + 8:2] = (
            z.real, z.imag)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    assert (out[..., :start] == x[..., :start]).all()
    assert (out[..., start + 8:] == x[..., start + 8:]).all()
    if layout == "half" and start == 0:
        np.testing.assert_allclose(
            out, np.asarray(ref.rope(jnp.asarray(x), ROPE[FULL])),
            rtol=1e-5, atol=1e-5)
    if start == 8:      # the last 8: what the op turns without the attr
        del attrs["rotary_start"]
        again, = _run_op("rotary_embedding", {"X": x}, ["Out"], attrs)
        assert (again == out).all()
    with pytest.raises(ValueError, match="rotary_start"):
        _run_op("rotary_embedding", {"X": x}, ["Out"],
                dict(attrs, rotary_start=9))


_TODAY = {"theta": 500000.0, "rotary_dim": 128, "layout": "half",
          "rope_type": "default", "factor": 1.0, "original_max_position": 0,
          "beta_fast": 32.0, "beta_slow": 1.0, "scale": 1.0}


@pytest.mark.parametrize("attrs, width, positions, digest", [
    # latent attention's rope part: the LAST 64 of 192, interleaved (kanana)
    (dict(_TODAY, theta=10000.0, rotary_dim=64, layout="interleaved"), 192,
     None, "7f600a4687bebc17ee6c90ac2610098f00b81c331cdfadd3e590f76e060e0564"),
    # a whole head, half-split, the default rule (mellum's sliding layers)
    (_TODAY, 128, None,
     "d7edb52f68822e3b99b74d80ee93d6b691f0cb93b2709af2fc997d36f1c3c8e8"),
    # yarn with its scale (mellum's full layers)
    (dict(_TODAY, rope_type="yarn", factor=16.0, original_max_position=8192,
          scale=1.2772588722239782), 128, None,
     "65f52052c64253498fc96fd1d690a86f40babaa1288c312d37076afe001cf694"),
    # the last 64 of 192, half-split (ling)
    ({"theta": 10000.0, "rotary_dim": 64, "layout": "half"}, 192, None,
     "d3c1673a71b18544971fd9a35d674cbec6518728c43c48564d6d9cf57368bdcf"),
    # three position streams (keye)
    ({"theta": 1000000.0, "rotary_dim": 128, "layout": "half",
      "sections": [16, 24, 24]}, 128, (3, 2, 32),
     "692436a425f620468f65926bef3fa8903b84f7d5475263f1236b5c9adfb9ad67")],
    ids=["interleaved-last", "half", "yarn", "half-last", "streams"])
def test_with_todays_attrs_the_op_traces_as_before(attrs, width, positions,
                                                   digest):
    """Without `rotary_start` the op's jaxpr, source lines cut, is the
    parent commit's (ce44d55, jax 0.9.0), at the attrs the cells that run it
    every step give it: they pay nothing for a turned part that may lie
    first."""
    opdef = registry.get("rotary_embedding")

    def lowered(x, *pos):
        ins = {"X": [x]}
        if pos:
            ins["Positions"] = [pos[0]]
        return opdef.lower(registry.LowerCtx(rng_key=None), ins,
                           attrs)["Out"][0]

    args = [jax.ShapeDtypeStruct((2, 4, 32, width), jnp.bfloat16)]
    if positions:
        args.append(jax.ShapeDtypeStruct(positions, jnp.int64))
    text = harness.cut_source_lines(str(jax.make_jaxpr(lowered)(*args)))
    assert hashlib.sha256(text.encode()).hexdigest() == digest


# ---------------------------------------------------------------------------
# the flash kernels at a group of 6, interpreted, against the dense route
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [None, 100], ids=["full", "window"])
def test_flash_at_a_group_of_six_matches_the_dense_route(window):
    """12 query heads on 2 KV heads of 128: the first group that is no
    power of two (`bwd_dkdv`'s grid `(B * nkv, group, k_block)`, the index
    map h -> h // 6). Forward and all three gradients, with and without a
    window that is no multiple of a block."""
    rng = np.random.RandomState(0)
    q, cot = (jnp.asarray(rng.randn(1, 12, 256, 128), jnp.float32)
              for _ in range(2))
    k, v = (jnp.asarray(rng.randn(1, 2, 256, 128), jnp.float32)
            for _ in range(2))
    scale = 128 ** -0.5
    kw = dict(scale=scale, causal=True, window=window, block_q=128,
              block_k=128)
    out, lse = fa.flash_attention(q, k, v, return_lse=True, **kw)
    grads = fa.flash_attention_bwd(q, k, v, out, lse, cot, **kw)
    assert grads[1].shape == grads[2].shape == (1, 2, 256, 128)

    def dense(q, k, v):
        return attention._xla_attention(
            q, k, v, attention._causal_bias(256, window), scale, 0.0, None)

    want, vjp = jax.vjp(dense, q, k, v)
    for name, got, ref_val in zip(("out", "dq", "dk", "dv"),
                                  (out,) + tuple(grads), (want,) + vjp(cot)):
        err = float(jnp.abs(got - ref_val).max() / jnp.abs(ref_val).max())
        assert err < 2e-5, (name, err)
    # head 5 reads KV head 0 and head 6 KV head 1: with 1 added to KV head
    # 1's values, the first six heads' outputs stay and the other six's
    # rise by 1 (a row of probabilities sums to 1)
    moved = fa.flash_attention(q, k, v.at[:, 1].add(1.0), **kw)
    assert bool((moved[:, :6] == out[:, :6]).all())
    np.testing.assert_allclose(moved[:, 6:], out[:, 6:] + 1.0, atol=1e-5)


# ---------------------------------------------------------------------------
# the builder
# ---------------------------------------------------------------------------

def test_builder_names_scopes_and_checkpoints_and_verifies():
    from paddle_tpu.analysis import verifier
    from paddle_tpu.observability import trace
    reset_programs(0)
    trace.clear()
    cfg = laguna.LagunaConfig.tiny()
    _, loss, routed = laguna.build_causal_lm_program(cfg)
    built = [e for e in trace.events() if e["name"] == "program.build"]
    assert built and built[-1]["args"]["model"] == "laguna"
    prog = fluid.default_main_program()
    ops = prog.global_block().ops
    attends = [op for op in ops if op.type == "fused_attention"]
    assert [op.attrs.get("name_scope") for op in attends] == [
        "attn.attend.full"] + ["attn.attend.window"] * 3 + [
        "attn.attend.full"]
    assert [op.attrs.get("window") for op in attends] == [
        None, 8, 8, 8, None]
    names = {p.name: tuple(p.shape)
             for p in prog.global_block().all_parameters()}
    # 12 heads of 16 where full, 16 where sliding, 2 KV heads everywhere
    assert names["l0_q_proj_w"] == names["l0_g_proj_w"] == (64, 192)
    assert names["l1_q_proj_w"] == names["l1_g_proj_w"] == (64, 256)
    assert names["l4_o_proj_w"] == (192, 64) and names["l3_o_proj_w"] == (
        256, 64)
    assert {names[f"l{n}_k_proj_w"] for n in range(5)} == {(64, 32)}
    assert names["l0_mlp_gate_w"] == (64, 128) and "l0_router_w" not in names
    assert names["l1_router_w"] == (64, 8) and "l1_mlp_up_w" not in names
    assert names["l2_experts_up_w"] == (4, 64, 32)
    assert names["l4_shared_down_w"] == (32, 64)
    assert not any(n.startswith("l5_") for n in names)
    rotary = [op.attrs for op in ops if op.type == "rotary_embedding"]
    assert len(rotary) == 10 and all(a["layout"] == "half" for a in rotary)
    # q and k of a full layer: the first 8 of 16, yarn; of a sliding layer
    # all 16, the default rule, and no `rotary_start` at all
    for a in rotary[:2] + rotary[8:]:
        assert (a["rotary_start"], a["rotary_dim"], a["rope_type"]) == (
            0, 8, "yarn")
        assert a["scale"] == pytest.approx(1.4158883083359672)
    for a in rotary[2:8]:
        assert "rotary_start" not in a and a["rotary_dim"] == 16
        assert (a["rope_type"], a["theta"], a["scale"]) == (
            "default", 10000.0, 1.0)
    assert [op.type for op in ops].count("head_gate") == 5
    moe_ops = [op for op in ops if op.type == "routed_moe"]
    assert len(moe_ops) == 4 and all(
        "SelectBias" in op.inputs and op.attrs["scoring"] == "sigmoid"
        and op.attrs["routed_scaling"] == 2.5
        and op.attrs["experts_total"] == 8
        and op.attrs["expert_offset"] == 4 for op in moe_ops)
    found = {op.attrs.get("name_scope") for op in ops}
    want = {"attn.proj", "attn.attend.full", "attn.attend.window",
            "ffn.dense", "moe.shared", "moe.io", "head.untied"}
    assert want <= found and want <= set(scopes.CATALOGUE)
    assert len(loss._layer_checkpoints) == 5 and len(routed) == 4
    paddle.optimizer.Adam(1e-4).minimize(loss)
    errors = [f for f in verifier.verify_program(prog)
              if f.severity == "error"]
    assert not errors, errors
    rules = laguna.sharding_rules()
    assert tuple(rules.spec_for("l2_experts_up_w")) == ("ep",)
    for leaf in ("l0_q_proj_w", "l0_k_proj_w", "l1_g_proj_w",
                 "l0_mlp_up_w", "l1_shared_gate_w"):
        assert tuple(rules.spec_for(leaf)) == (None, "tp"), leaf
    for leaf in ("l3_o_proj_w", "l0_mlp_down_w", "l1_shared_down_w"):
        assert tuple(rules.spec_for(leaf)) == ("tp", None), leaf
    assert tuple(rules.spec_for("l1_router_w")) == ()
    tiny = vars(laguna.LagunaConfig.tiny())
    for key, value in (("layer_types", (FULL,) * 7),
                       ("moe_apply_router_weight_on_input", True)):
        with pytest.raises(ValueError, match=key):
            laguna.LagunaConfig(**{**tiny, key: value})


def test_every_instruction_of_the_step_has_a_phase_and_a_known_scope():
    """The tiny AMP step under recomputation at every layer boundary (the
    cell's way; the program `test_program_follows_the_reference` trained),
    compiled: no instruction without a phase, recomputed work
    marked, every dotted name a phase or a catalogued scope, and no more
    instructions without a layer scope than in the builders the benchmark
    had (`tests/test_step_scopes.py`: Adam's two beta powers and the loss
    gradient's seed), so `unscoped_time_pct` starts no higher here."""
    op_names = _trained(True)["op_names"]
    classes = [scopes.classify(n) for n in op_names]
    phases = {phase for phase, _ in classes}
    assert "none" not in phases and {"fwd", "bwd", "opt",
                                     "recompute"} <= phases
    dotted = {name for n in op_names for name in scopes._NAMES.findall(n)
              if re.fullmatch(r"[a-z_0-9]+(\.[a-z_0-9]+)+", name)}
    assert not {d for d in dotted
                if d not in scopes.PHASES and not scopes.scope_of(d)}
    assert {"attn.proj", "attn.attend.full", "attn.attend.window",
            "ffn.dense", "moe.shared", "moe.experts", "layer.residual",
            "head.untied"} <= {s for _, s in classes}
    unscoped = [n for n, (_, s) in zip(op_names, classes) if s == "none"]
    assert len(unscoped) == 5, sorted(set(unscoped))
