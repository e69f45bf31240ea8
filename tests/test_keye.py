"""The Keye-VL-2.0-family language model (`models/keye.py`: attention over a
learned selection of keys on grouped KV heads, an indexer trained by its own
loss on a detached input, three-stream rotary positions, softmax-routed
experts, one expert-parallel rank's share) against its plain float32
reference (`benchmark/reference/keye_vl2.py`), on the CPU at tiny widths
with seeded weights. The ops it forced are held one by one in
`tests/test_sparse_index.py`.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import causal_lm_harness as harness
from causal_lm_harness import B, S, counter_rise

import paddle_tpu as paddle
import paddle_tpu.fluid as fluid
from paddle_tpu.models import keye
from paddle_tpu.observability import metrics
from paddle_tpu.ops import attention
from paddle_tpu.testing import reset_programs
from benchmark import counts_dsa_gqa
from benchmark.reference import keye_vl2 as ref

CFG = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
           head_dim=16, moe_intermediate_size=32, num_experts=4,
           experts_total=8, expert_offset=2, num_experts_per_tok=2,
           norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=1e7,
           rope_scaling={"mrope_section": [2, 2, 4]},
           sa_config={"indexer_num_heads": 2, "indexer_head_dim": 8,
                      "indexer_num_kv_heads": 1, "topk": 12},
           layers=2, vocab=256, reference_tokens_per_block=16,
           assumed={"initializer_std": 0.02, "indexer_norm_eps": 1e-6})
SHARED = ("hidden_size", "num_attention_heads", "num_key_value_heads",
          "head_dim", "moe_intermediate_size", "num_experts_per_tok",
          "norm_topk_prob", "rms_norm_eps", "rope_theta")
DATA_SEED = 3


def model_config(cfg, **more):
    sa = cfg["sa_config"]
    return keye.KeyeConfig(
        vocab_size=cfg["vocab"], num_hidden_layers=cfg["layers"],
        num_experts=cfg["experts_total"], experts_held=cfg["num_experts"],
        expert_offset=cfg["expert_offset"], seq_len=S,
        mrope_section=tuple(cfg["rope_scaling"]["mrope_section"]),
        indexer_num_heads=sa["indexer_num_heads"],
        indexer_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
        **{k: cfg[k] for k in SHARED}, **more)


def seeded_params():
    """The reference's seeded weights, the indexer's matrices ten times
    larger: at 0.02 its scores of a row of 32 lie within rounding of one
    another and the selection is a comparison of roundings."""
    return {n: v * 10 if "indexer_" in n and n.endswith("_w") else v
            for n, v in ref.init_params(CFG, jax.random.key(3)).items()}


def positions(k, seed=5):
    """[k, 3, B, S] unequal streams, as rows of image patches have them."""
    rng = np.random.RandomState(seed)
    return np.sort(rng.randint(0, 3 * S, (k, 3, B, S)), axis=-1)


def trained_program(amp, k, ids, pos=None, recompute=False):
    """(losses [k], indexer losses [k, layers], the first layer's routed
    choice and selection at every step, scope) after k steps of `run_steps`
    from the seeded weights."""
    cfg = model_config(CFG, position_streams=pos is not None)
    exe, loss, routed = harness.train_step(keye, cfg, amp, recompute,
                                           lr=ref.ADAM["lr"])
    scope = fluid.global_scope()
    for name, value in seeded_params().items():
        assert tuple(scope.find(name).shape) == tuple(value.shape), name
        scope.set(name, value)
    feed = {"tokens": ids[:k]}
    if pos is not None:
        feed["positions"] = pos[:k]
    out = exe.run_steps(k, feed=feed, fetch_list=[
        loss, routed[0][0], loss._selections[0]] + loss._auxiliary_losses)
    return (np.asarray(out[0]).reshape(-1),
            np.stack([np.asarray(v).reshape(-1) for v in out[3:]], axis=1),
            np.asarray(out[1]), np.asarray(out[2]) != 0, scope)


def reference_states(cfg, k, ids, labels, pos=None):
    """[(L, L_I a layer, grads, params, m, v) after each of k reference
    steps], the first step's routed choice and selection."""
    params = seeded_params()
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    states, first = [], None
    for t in range(k):
        (val, (index, idx, sel)), grads = ref._block_grad(
            params, ids[t], labels[t], float((labels[t] != -100).sum()),
            float(B * S), ref._cfg_key(cfg), None,
            None if pos is None else pos[t])
        first = first or (np.asarray(idx), np.asarray(sel))
        copy = jax.tree.map(jnp.array, (params, m, v))
        params, m, v = ref._adam(*copy, grads, float(t + 1))
        states.append((float(val), np.asarray(index), grads, params, m, v))
    return states, first


# Tolerances, as in test_mellum.py. float32: the order of float32 sums, 1e-6
# relative on a leaf. AMP: every matmul operand is rounded to bf16; beside
# the 2 % of a leaf's norm that the roundings average to, a query whose
# `topk`-th and next scores lie within a rounding takes another key, and a
# token another expert: each such choice is a few per cent of a leaf's
# gradient at 128 tokens (on the chip `select_mismatch_share` and
# `route_mismatch_share` are those comparisons), so the AMP case holds the
# leaves to 15 % and the choices to a few per cent of their count; and L_I,
# a divergence of two near-uniform distributions, moves with every rounded
# score: 2e-3 of L where the next-token loss alone holds 2e-4.
@pytest.mark.parametrize("amp, streams, grad_tol, loss_tol", [
    (False, False, 2e-5, 1e-6), (False, True, 2e-5, 1e-6),
    (True, False, 0.15, 2e-3)],
    ids=["float32", "float32_three_streams", "amp"])
def test_program_follows_the_reference(amp, streams, grad_tol, loss_tol):
    """L = L_LM + sum L_I, each layer's L_I, every leaf's gradient and two
    Adam steps; with three unequal position streams too."""
    ids, labels = harness.batches(CFG["vocab"], 2, seed=DATA_SEED)
    pos = positions(2) if streams else None
    states, (ref_idx, ref_sel) = reference_states(CFG, 2, ids, labels, pos)
    choice_tol = 0.05 if amp else 0.0

    losses, index, idx, sel, scope = trained_program(amp, 1, ids, pos)
    loss1, index1, grads1 = states[0][:3]
    assert abs(losses[0] - loss1) / loss1 < loss_tol
    np.testing.assert_allclose(index[0], index1, rtol=100 * loss_tol)
    assert index1.min() > 1e-3          # the indexer's loss is no rounding
    for name, err in harness.first_step_gaps(scope, grads1, ref).items():
        assert err < grad_tol, (name, err)
    assert harness.route_mismatch(idx[0], ref_idx) <= choice_tol
    assert (sel[0] & ~ref_sel).sum() <= choice_tol * ref_sel.sum()
    losses, index, _, _, scope = trained_program(amp, 2, ids, pos)
    for t in range(2):
        assert abs(losses[t] - states[t][0]) / states[t][0] < loss_tol
        np.testing.assert_allclose(index[t], states[t][1],
                                   rtol=100 * loss_tol)
    lr = ref.ADAM["lr"]
    for name, worst, gap, moved, moments in harness.second_step_gaps(
            scope, [s[1:] for s in states], seeded_params()):
        assert worst <= (4.1 * lr if amp else 1e-2 * lr), name
        assert gap <= (0.45 if amp else 1e-3) * moved, name
        for acc, err in moments.items():
            assert err < 2 * grad_tol, (name, acc, err)


def test_recomputation_finds_the_same_selection_and_step():
    """A checkpoint at every layer boundary (what the cell runs): the
    recomputed forward selects again and the step is the plain one's."""
    ids, _ = harness.batches(CFG["vocab"], 2, seed=DATA_SEED)
    plain = trained_program(False, 2, ids)
    again = trained_program(False, 2, ids, recompute=True)
    np.testing.assert_allclose(again[0], plain[0], rtol=1e-6)
    np.testing.assert_allclose(again[1], plain[1], rtol=1e-5)
    np.testing.assert_array_equal(again[3], plain[3])
    for name in seeded_params():
        assert harness.rel_gap(again[4].find(name + "_moment1_0"),
                               plain[4].find(name + "_moment1_0")) < 1e-5


def _fault(name):
    return dict(CFG, assumed=dict(CFG["assumed"], fault=name))


@pytest.mark.parametrize("wrong, moved", [
    (_fault("selection_ignored"), "dense causal attention"),
    (dict(CFG, sa_config=dict(CFG["sa_config"], topk=3)), "topk quartered"),
    (_fault("index_loss_left_out"), "the indexer's loss left out"),
    (_fault("index_input_attached"), "the indexer's input not detached"),
    (_fault("lowest_selected"), "the lowest scores taken"),
    (dict(CFG, rope_scaling={"mrope_section": [4, 2, 2]}),
     "other sections of the position streams")], ids=lambda v: (
         v if isinstance(v, str) else "cfg"))
def test_the_reference_tells_each_fault_apart(wrong, moved):
    """What the new mechanisms admit going wrong each moves the reference's
    own gradients by far more than any tolerance above (under three unequal
    position streams, so that the sections count)."""
    ids, labels = harness.batches(CFG["vocab"], 1, seed=DATA_SEED)
    pos = positions(1)
    want, got = (reference_states(cfg, 1, ids, labels, pos)[0][0][2]
                 for cfg in (CFG, wrong))
    worst = max(float(jnp.linalg.norm(got[n] - want[n])
                      / jnp.linalg.norm(want[n])) for n in want)
    assert worst > 0.1, (moved, worst)


def test_the_trunk_learns_from_the_lm_loss_and_the_indexer_from_its_own():
    """The reference's gradient of L_LM alone (L_I's gradient stopped) is
    zero on the indexer's leaves and the program's on every other leaf; of
    L_I alone it is zero off the indexer's leaves: no gradient reaches x
    from the indexer and none reaches q, k, v from the target."""
    ids, labels = harness.batches(CFG["vocab"], 1, seed=DATA_SEED)
    params = seeded_params()
    n_lab, n_q = float((labels[0] != -100).sum()), float(B * S)

    def part(which):
        def f(p):
            ce, kls, _, _ = ref.loss_parts(p, ids[0], labels[0], CFG)
            return ce / n_lab if which == "lm" else jnp.sum(kls) / n_q
        return jax.grad(f)(params)

    from_lm, from_index = part("lm"), part("index")
    indexer = set(ref.indexer_leaves(CFG))
    assert len(indexer) == 5 * CFG["layers"]
    for name in params:
        own, other = ((from_index, from_lm) if name in indexer
                      else (from_lm, from_index))
        assert float(jnp.abs(other[name]).max()) == 0.0, name
        assert float(jnp.linalg.norm(own[name])) > 0.0, name
    # and the program's step is their sum, leaf by leaf
    *_, scope = trained_program(False, 1, ids)
    for name, err in harness.first_step_gaps(
            scope, {n: from_lm[n] + from_index[n] for n in params},
            ref).items():
        assert err < 2e-5, (name, err)


def test_the_eight_ranks_routed_parts_add_up_to_the_uncut_layer():
    """16 experts cut into 8 shares of 2, as the configuration cuts 128
    into 8 of 16: the parts all shares give (`models/keye.py` calls the
    expert layer exactly as `models/mellum.py` does: softmax scores, no
    bias) are the uncut reference's layer, every share's choice the
    reference's, their loads its counts."""
    rng = np.random.RandomState(0)
    d, f, total = 32, 16, 16
    params = {"router_w": rng.randn(d, total).astype(np.float32) * 0.3,
              "experts_gate_w": rng.randn(total, d, f).astype(np.float32) * .2,
              "experts_up_w": rng.randn(total, d, f).astype(np.float32) * .2,
              "experts_down_w": rng.randn(total, f, d).astype(np.float32) * .2}
    x = rng.randn(96, d).astype(np.float32)

    def reference(held, offset):
        cfg = dict(num_experts=held, experts_total=total,
                   expert_offset=offset, num_experts_per_tok=3,
                   norm_topk_prob=True, assumed={})
        p = {"l_" + k: jnp.asarray(v if k == "router_w"
                                   else v[offset:offset + held])
             for k, v in params.items()}
        out, idx = ref.mellum2.routed_experts(jnp.asarray(x), p, "l_", cfg)
        return np.asarray(out), np.asarray(idx)

    want, want_idx = reference(total, 0)
    summed, loads = 0.0, []
    for offset in range(0, total, 2):
        out, idx, load = harness.routed_share(
            x, harness.held_arrays(params, offset, 2), 3, total, offset,
            scoring="softmax")
        np.testing.assert_allclose(out, reference(2, offset)[0], rtol=2e-5,
                                   atol=2e-6)
        assert (idx == want_idx).all()
        summed = summed + out
        loads.append(load)
    np.testing.assert_allclose(summed, want, rtol=2e-5, atol=2e-6)
    assert (np.concatenate(loads) == np.bincount(
        want_idx.reshape(-1), minlength=total)).all()


# ---------------------------------------------------------------------------
# the builder
# ---------------------------------------------------------------------------

def test_builder_names_scopes_and_checkpoints_and_verifies():
    from paddle_tpu.analysis import verifier
    from paddle_tpu.observability import trace
    reset_programs(0)
    trace.clear()
    cfg = keye.KeyeConfig.tiny()
    _, loss, routed = keye.build_causal_lm_program(cfg)
    built = [e for e in trace.events() if e["name"] == "program.build"]
    assert built and built[-1]["args"]["model"] == "keye"
    ops = fluid.default_main_program().global_block().ops
    attend = [op for op in ops if op.type == "fused_attention"]
    assert [op.attrs.get("name_scope") for op in attend] == [
        "attn.attend.sparse"] * cfg.num_hidden_layers
    assert all(op.inputs.get("Select") and op.outputs.get("Target")
               and op.attrs["return_target"] for op in attend)
    scopes = {op.attrs.get("name_scope") for op in ops}
    assert {"attn.proj", "attn.index.score"} <= scopes
    # the indexer reads the normed input through a detach, nothing else does
    for op in ops:
        if op.type == "detach":
            reads = [o.type for o in ops
                     if op.outputs["Out"][0] in o.input_names()]
            assert sorted(reads) == ["mul", "mul", "mul"], reads
    assert [op.attrs["topk"] for op in ops
            if op.type == "sparse_index"] == [12, 12]
    assert len(loss._layer_checkpoints) == cfg.num_hidden_layers
    assert len(loss._auxiliary_losses) == len(loss._selections) == len(
        loss._selected_pairs) == len(routed) == cfg.num_hidden_layers
    paddle.optimizer.Adam(1e-4).minimize(loss)
    errors = [f for f in verifier.verify_program(
        fluid.default_main_program()) if f.severity == "error"]
    assert not errors, errors
    rules = keye.sharding_rules()
    assert tuple(rules.spec_for("l1_experts_up_w")) == ("ep",)
    assert tuple(rules.spec_for("l0_k_proj_w")) == (None, "tp")
    # the indexer is whole on every rank
    for leaf in ("indexer_q_w", "indexer_k_w", "indexer_head_w"):
        assert not any(tuple(rules.spec_for("l0_" + leaf) or ()))


_COUNTERS = ("attn.sparse_layers_lowered", "attn.sparse_pallas",
             "attn.sparse_xla", "attn.index_pallas", "attn.index_xla",
             "attention.flash_bwd_residual",
             "attention.flash_bwd_recomputed",
             "attention.flash_blocks_interior", "attention.flash_blocks_edge",
             "moe.layers_lowered")


@pytest.mark.parametrize("recompute", [False, True],
                         ids=["plain", "recompute"])
def test_a_trace_of_the_step_counts_its_routes(recompute, monkeypatch):
    """With the flash gate open (here: the interpreter), one trace of the
    AMP train step lowers two indexers and two selected attentions to the
    kernels, with the target's kernel beside each; the backward takes the
    forward's residuals (plain) or the layer's segment differentiates
    itself where it is lowered, once, and keeps the selection, the target
    and the flash output for its backward (recompute). The indexer's heads
    are 64 wide, which its score kernels take: forward and backward of each
    layer count once (recompute: the forward once more, the recomputed
    scores the loss's backward reads), none falls to the `jax.numpy` form. The step's jaxpr holds no [B, heads, S, S] array,
    the indexer's heads' products [B, 2, block, S] among them, and the
    selection once a row, as int8."""
    monkeypatch.setattr(attention, "_use_pallas",
                        lambda q: q.shape[2] % 128 == 0)
    cfg = keye.KeyeConfig.tiny()
    cfg.seq_len, cfg.head_dim, cfg.index_topk = 128, 64, 40
    cfg.indexer_head_dim = 64
    cfg.num_attention_heads, cfg.num_key_value_heads = 6, 2
    cfg.hidden_size, cfg.moe_intermediate_size = 128, 256
    cfg.mrope_section = (8, 12, 12)
    exe, loss, ids = harness.amp_step(keye, cfg, recompute)
    jaxpr, rise = counter_rise(
        lambda: str(exe.step_jaxpr({"tokens": ids}, [loss], k=2)), _COUNTERS)
    assert dict(zip(_COUNTERS, rise)) == {
        "attn.sparse_layers_lowered": 2, "attn.sparse_pallas": 2,
        "attn.sparse_xla": 0,
        "attn.index_pallas": 6 if recompute else 4, "attn.index_xla": 0,
        "attention.flash_bwd_residual": 0 if recompute else 2,
        "attention.flash_bwd_recomputed": 0,
        # one block of 128 a layer, the diagonal's
        "attention.flash_blocks_interior": 0,
        "attention.flash_blocks_edge": 2,
        "moe.layers_lowered": 2}
    assert jaxpr.count("name=flash_attention_bwd") == 4
    # (the printer names an inner jit's jaxpr once, however many call it:
    # tests/test_recompute_keep.py counts the launches)
    assert "name=flash_attention_fwd" in jaxpr
    assert "name=selected_probs_sum" in jaxpr
    assert "name=index-scores-fwd" in jaxpr
    assert "name=index-scores-bwd" in jaxpr
    assert "i8[1,128,128]" in jaxpr
    assert "[1,6,128,128]" not in jaxpr and "[1,2,3,128,128]" not in jaxpr
    assert "f32[1,2,128,128]" not in jaxpr


def test_the_selection_gauge_is_the_counts_mean():
    """`record_selection` sets `attn.selected_pairs_per_query` from the
    fetched counts; a full selection reads sum_t min(t + 1, topk) / S, the
    count the roofline shares divide by: 1,792.1 of 4,096.5 at 8,192 tokens
    and 2,048 keys."""
    ids, _ = harness.batches(CFG["vocab"], 1, seed=DATA_SEED)
    exe, loss, _ = harness.train_step(keye, model_config(CFG), False)
    out = exe.run_steps(1, feed={"tokens": ids[:1]},
                        fetch_list=loss._selected_pairs)
    got = keye.record_selection(np.stack([np.asarray(v) for v in out]))
    assert metrics.get("attn.selected_pairs_per_query") == got
    assert got == pytest.approx(
        counts_dsa_gqa.selected_pairs(CFG, S) / S) == pytest.approx(
            np.minimum(np.arange(S) + 1, 12).mean())
    real = {"sa_config": {"topk": 2048}}
    assert counts_dsa_gqa.selected_pairs(real, 8192) == 14_681_088
    assert counts_dsa_gqa.causal_pairs(8192) == 33_558_528
