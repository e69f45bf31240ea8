"""The Ling-3.0-family hybrid LM (`models/ling.py`: Kimi-delta linear
attention with latent attention in the last layer of every group, head-wise
output gates, sigmoid-routed experts picked inside the best groups, a
chip's share of the heads and of the experts) against its plain float32
reference (`benchmark/reference/ling3.py`), on the CPU at tiny widths with
seeded weights; and what the model forced on `routed_moe`: group-limited
selection. The chunked gated delta rule (`ops/kda.py`), the bounded decay
gate, the L2 norm and the head-wise gate have their own file,
`tests/test_kda_scan.py`.
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
from paddle_tpu.models import deepseek_v3, ling
from paddle_tpu.observability import metrics
from paddle_tpu.ops import registry
from paddle_tpu.testing import reset_programs
from benchmark.reference import ling3 as ref

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


def seeded_params():
    return ref.init_params(CFG, jax.random.key(3))


def trained_program(amp, k, ids):
    return harness.trained_program(ling, model_config(CFG), ref,
                                   seeded_params(), amp, k, ids)


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

    ids, labels = harness.batches(CFG["vocab"], 2, seed=DATA_SEED)
    states, ref_idx = harness.reference_states(
        ref, CFG, ref.split_state(CFG, seeded_params()), 2, ids, labels)
    (losses, idx, scope), rise = counter_rise(
        lambda: trained_program(amp, 1, ids),
        ("kda.bwd_residual", "kda.bwd_recomputed",
         "moe.group_limited_layers"))
    # the three delta-rule layers' backward took the rule, on the forward's
    # residuals; the three expert layers selected inside groups
    assert rise == (3, 0, 3)
    loss1, grads1 = states[0][0], states[0][1]
    assert abs(losses[0] - loss1) / loss1 < loss_tol
    for name, err in harness.first_step_gaps(scope, grads1, ref).items():
        assert err < tol(name), (name, err)
    assert harness.route_mismatch(idx[0], ref_idx) <= (0.02 if amp else 0)
    losses, _, scope = trained_program(amp, 2, ids)
    for t in range(2):
        assert abs(losses[t] - states[t][0]) / states[t][0] < loss_tol
    lr = ref.ADAM["lr"]
    # the accumulators against their size after either step: where the two
    # steps' gradients cancel (a decay's A_log, two numbers a layer) the
    # sum is a tenth of its terms and carries their rounding
    for name, worst, gap, moved, moments in harness.second_step_gaps(
            scope, states, seeded_params(), floor_by_first_step=True):
        assert worst <= (4.1 if amp else 0.5) * lr, name
        # Adam's first steps move an element by lr times its gradient's
        # sign: one element of a norm weight of 16 whose tiny gradient
        # turned is 0.35 of the leaf's move, and a token routed elsewhere
        # turns signs all over the routed leaves
        share = (0.6 if name.endswith(_ROUTED) else 0.45) if amp else 2e-3
        assert gap <= share * moved, name
        for acc, err in moments.items():
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


def test_without_groups_routed_moe_traces_as_before(monkeypatch):
    """`n_group` 1 (every cell the benchmark had): the op's forward and its
    grad rule trace to the jaxpr of the tree before group-limited selection
    (commit 40a2d5b, jax 0.9.0; the digest is `tests/test_nemotron_h.py`'s
    for sigmoid scoring with a bias, made there and changed with it by
    PR 41's route), whether the attr is left out or given as 1."""
    from paddle_tpu.ops.pallas import grouped_matmul
    monkeypatch.setattr(grouped_matmul, "interpret_mode", lambda: False)
    total = 16

    def digest(attrs):
        return harness.sha256(harness.routed_moe_jaxpr(
            True, True, 4, total, attrs, n=512))

    attrs = {"top_k": 2, "routed_scaling": 2.5, "norm_topk": True,
             "experts_total": total, "expert_offset": 4,
             "scoring": "sigmoid"}
    want = "756425e5b9df58c88211a24260d81274489de5600b84480885065b2d97d77f9f"
    assert digest(attrs) == want
    assert digest(dict(attrs, n_group=1, topk_group=1)) == want
    assert digest(dict(attrs, n_group=4, topk_group=2)) != want


def test_latent_attention_as_kanana_calls_it_traces_as_before():
    """`models/deepseek_v3.latent_attention` was given a sibling
    (`ling.gated_latent_attention`) and not an option: the tiny preset's
    float32 train step traces to the jaxpr of the tree before this model
    (commit 40a2d5b, jax 0.9.0; the digest was made there, source lines
    cut, and again with PR 41's route in `routed_moe`)."""
    step, _ = harness.tiny_step_digests(harness.causal_lm(
        deepseek_v3, deepseek_v3.DeepseekV3Config.tiny()))
    assert step == KANANA_DIGEST


KANANA_DIGEST = (
    "b55dd5734b173553d7c9752e5e345b292002dc0118da0dfaf078759bc831ca74")


# ---------------------------------------------------------------------------
# the shares add up
# ---------------------------------------------------------------------------

def _attention_program(kind, cfg, x, params, pre):
    """One share's attention layer of `kind` through a Program."""
    build = (ling.gated_latent_attention if kind == ling.LATENT
             else ling.kda_attention)
    return harness.mixer_program(build, model_config(cfg, seq=x.shape[1]),
                                 x, params, pre)


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
        out, idx, load = harness.routed_share(
            x, harness.held_arrays(params, offset, 2), 4, 32, offset,
            routed_scaling=2.5, n_group=8, topk_group=4)
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

_ROUTES = ("kda.scan_pallas", "kda.scan_xla")
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
    cfg = ling.LingConfig.tiny()
    cfg.seq_len, cfg.kda_chunk_size = 128, 64
    for key, value in changed.items():
        setattr(cfg, key, value)
    return harness.amp_step(ling, cfg, recompute)


@pytest.mark.parametrize("recompute, rise", [
    (False, (3, 3, 0, 3, 3, 0, 3, 1, 1, 0)),
    (True, (3, 0, 0, 3, 0, 0, 3, 1, 0, 0))], ids=["plain", "recompute"])
def test_a_trace_of_the_step_counts_its_routes(recompute, rise, monkeypatch):
    """With the flash gate open (here: the interpreter), one trace of the
    AMP train step lowers three delta-rule layers, three group-limited
    expert layers and one flash forward; their backward by each op's grad
    rule on the forward's residuals, or, with a checkpoint at every layer
    boundary, by the same backward functions under `jax.vjp` of a whole
    layer, taken where the layer's segment is lowered, once. The step's
    jaxpr holds no
    `[S, H, K, V]` value: the states are a chunk's."""
    from paddle_tpu.ops import attention
    monkeypatch.setattr(attention, "_use_pallas",
                        lambda q: q.shape[2] % 128 == 0)
    exe, loss, ids = _amp_step(recompute, qk_nope_head_dim=56,
                               v_head_dim=64, head_dim=64)
    jaxpr, got = counter_rise(
        lambda: str(exe.step_jaxpr({"tokens": ids}, [loss], k=2)),
        _COUNTERS + _ROUTES)
    assert got[:-2] == rise
    # heads of 64 are half a lane tile: every scan the step keeps, forward,
    # backward and the forward lowered once more, is the `jax.numpy` form
    assert got[-2:] == (0, 9 if recompute else 6)
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
    traced = [f._cache_size() for f in entries]
    jaxpr, rise = counter_rise(
        lambda: str(exe.step_jaxpr({"tokens": ids}, [loss], k=2)),
        ("kda.layers_lowered",) + _ROUTES)
    assert rise == (6, kernels, 0)
    # the six layers enter each kernel through one jitted function: one
    # trace of it (none here if the other case of this test made it)
    assert all(f._cache_size() - t <= 1 for f, t in zip(entries, traced))
    for name in ("kda-chunk-fwd", "kda-chunk-bwd"):
        assert re.search(rf"name={name}\s", jaxpr), name
    assert "f32[1,2,4,128,128]" in jaxpr
    assert "triangular_solve" not in jaxpr
    assert not re.search(r"f32\[1,2,4,64,64\]", jaxpr)
