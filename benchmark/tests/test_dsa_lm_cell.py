"""What the learned-selection causal-LM cell brings: its file against the
published numbers, its counts against hand-worked numbers, its readers with
and without their sources, and `correct` shown to fail under each fault the
new mechanisms admit, at the rehearsal's size."""
import json
import os

import numpy as np
import pytest

from benchmark import (common, counts, counts_dsa_gqa, counts_window_gqa,
                       dsa_scopes, peaks, rehearse, run)

CELL = "keye_vl2_30b_a3b_ep8_s8192"
V5E = peaks.device_peaks("TPU v5 lite")
KEPT, CAUSAL = 14_681_088, 33_558_528


def _config():
    with open(os.path.join(common.HERE, "configs",
                           "keye_vl2_30b_a3b_ep8.json")) as f:
        return json.load(f)


def test_the_file_keeps_the_published_numbers_and_states_the_cut():
    cfg = _config()
    for key, value in cfg["published"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["layers", "num_experts", "vocab"]
    assert cfg["published"]["num_experts"] == cfg["experts_total"] == 128
    assert (cfg["layers"], cfg["num_experts"], cfg["vocab"]) == (5, 16, 18992)
    assert cfg["vocab"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["num_experts"] * 8 == cfg["experts_total"]
    assert "8 chips share each layer" in cfg["deployment"]
    assert set(cfg["reduced"]) <= set(cfg["assumed"])
    assert cfg["sa_config"] == cfg["published"]["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048}
    assert cfg["rope_scaling"]["mrope_section"] == [16, 24, 24]
    for said in ("vision_tower", "indexer", "indexer_input", "selection",
                 "chunk_sizes", "objective", "rope", "recompute"):
        assert cfg["assumed"][said], said
    assert cfg["assumed"]["recompute"] is True
    from benchmark.reference import keye_vl2
    shapes = keye_vl2.param_shapes(cfg)
    count = lambda keep: sum(  # noqa: E731
        int(np.prod(s)) for n, s in shapes.items() if keep(n))
    assert count(lambda n: True) == 562_289_280          # 562.3 M
    assert count(lambda n: n.startswith("l2_")) == 96_899_200
    assert count(lambda n: n.startswith("l0_indexer_")) == 2_261_120
    assert count(lambda n: n.startswith("l3_") and "experts" not in n
                 and "indexer" not in n) == 18_874_368 + 262_144 + 4_096
    assert count(lambda n: "experts" in n
                 and n.startswith("l0_")) == 16 * 3 * 2048 * 768
    assert count(lambda n: not n.startswith("l")
                 or n == "lm_head_w") == 2 * 18992 * 2048 + 2048
    assert not keye_vl2.buffer_shapes(cfg)


def test_pairs_a_full_selection_keeps():
    cfg = _config()
    assert counts_dsa_gqa.causal_pairs(8192) == CAUSAL
    # rows 0..2047 keep 1..2048 keys, the other 6,144 rows 2,048 each
    assert counts_dsa_gqa.selected_pairs(cfg, 8192) == (
        2_098_176 + 12_582_912) == KEPT
    assert KEPT / CAUSAL == pytest.approx(0.4375, abs=1e-4)
    assert KEPT / 8192 == pytest.approx(1792.1, abs=0.05)
    assert counts_dsa_gqa.selected_pairs(cfg, 2048) == (
        counts_dsa_gqa.causal_pairs(2048))
    assert counts_dsa_gqa.selected_pairs({"sa_config": {"topk": 2}},
                                         5) == 1 + 2 + 2 + 2 + 2


def test_flops_per_token_of_the_cut_model():
    cfg = _config()
    assert counts_window_gqa.gqa_proj_flops_per_token(cfg) == 2 * (
        8_388_608 + 2 * 1_048_576 + 8_388_608) == 37_748_736
    assert counts_dsa_gqa.index_proj_flops_per_token(cfg) == (
        2 * 2048 * (1024 + 64 + 16)) == 4_521_984
    # one product of the indexer: 16 heads of 64, 2,048 operations a pair
    assert counts_dsa_gqa.index_score_flops(cfg, CAUSAL / 8192) == (
        2048 * 4096.5)
    # QK^T and PV, 32 heads of 128: 16,384 operations a selected pair; the
    # target one product of them
    assert counts_dsa_gqa.attend_flops(cfg, KEPT / 8192) == 16384 * 1792.125
    assert counts_dsa_gqa.target_flops(cfg, KEPT / 8192) == 8192 * 1792.125
    assign = 1.0
    trunk = (37_748_736 + 16384 * 1792.125 + 2 * 2048 * 128
             + assign * 6 * 2048 * 768)
    indexer = (2 * 4_521_984 + 2048 * 4096.5 + 2 * 2048 * 1792.125
               + 8192 * 1792.125)
    want = 5 * (3 * trunk + indexer) + 3 * 2 * 2048 * 18992
    assert counts_dsa_gqa.lm_train_flops_per_token(cfg, 8192, assign) == (
        pytest.approx(want))
    assert want == 1_586_735_616
    # at full buffers (8 assignments a token) a step of 8,192 tokens
    full = counts_dsa_gqa.lm_train_flops_per_token(cfg, 8192, 8.0) * 8192
    assert full == pytest.approx(21.116e12, rel=1e-4)


def test_the_selected_attention_and_the_indexer_by_their_pairs():
    cfg = _config()
    flops, nbytes = counts_dsa_gqa.attend_train_flops_bytes(cfg, 1, 8192)
    assert flops == 5 * 6 * 2 * 32 * 128 * KEPT
    # q, o, q, o, dO, dq at 32 heads; k, v, k, v, dk, dv at 4; the
    # selection's byte a causal pair, forward and backward
    assert nbytes == 5 * (8192 * 128 * 2 * 6 * (32 + 4) + 2 * CAUSAL)
    least, bound = counts.roofline_seconds(flops, nbytes, V5E)
    assert bound == "flops" and least == pytest.approx(18.31e-3, rel=1e-3)
    # the same heads densely (`mellum2_12b_ep4`'s full layer): 43.75 %
    dense, _ = counts_window_gqa.flash_train_flops_bytes(
        dict(cfg, layer_types=["full_attention"] * 5), 1, 8192,
        "full_attention")
    assert flops / dense == pytest.approx(0.4375, abs=1e-4)
    flops, nbytes = counts_dsa_gqa.index_train_flops_bytes(cfg, 1, 8192)
    assert flops == 5 * (2048 * CAUSAL + 2 * 2048 * KEPT + 8192 * KEPT)
    operands = 8192 * (1024 + 64) * 2 + 8192 * 16 * 4
    assert nbytes == 5 * (
        operands + 4 * CAUSAL + 2 * operands + 4 * KEPT
        + 8192 * 36 * 128 * 2 + 8192 * 32 * 4 + CAUSAL + 4 * KEPT)
    # the accepted count of the experts' matmuls is this share's hand count
    flops, nbytes = counts_window_gqa.moe_experts_train_flops_bytes(cfg,
                                                                    8192.0)
    assert flops == 5 * 9 * 2 * 8192 * 2048 * 768
    weights = 16 * 3 * 2048 * 768 * 2
    assert nbytes == 5 * (3 * weights + 3 * 8192 * (2 * 2048 + 3 * 768) * 2)


HLO = '''
ENTRY %main {
  %flash_attention_fwd.3 = (bf16[32,8,8]{2,1,0}, f32[32,8,128]{2,1,0}) custom-call(%c, %q), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/while/body/checkpoint/attn.attend.sparse/flash_attention_fwd/pallas_call" source_file="x.py"}
  %selected_probs_sum.1 = f32[1,8,8]{2,1,0} custom-call(%c, %q), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/while/body/checkpoint/attn.attend.sparse/attn.index.target/selected_probs_sum/pallas_call"}
  %fusion.5 = f32[1,8,8]{2,1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/checkpoint/attn.index.score/while/body/dot_general"}
  %fusion.6 = s8[1,8,8]{2,1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/checkpoint/attn.index.select/while/body/reduce_sum"}
  %fusion.7 = f32[] fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/transpose(jvp(checkpoint))/attn.index.loss/reduce_sum"}
  %fusion.9 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/closed_call/attn.proj/mul"}
}
'''
SECONDS = {"flash_attention_fwd.3": 0.05, "selected_probs_sum.1": 0.02,
           "fusion.5": 0.04, "fusion.6": 0.1, "fusion.7": 0.01,
           "fusion.9": 1.0}


def _traced_ctx(monkeypatch):
    from benchmark import scopes
    monkeypatch.setattr(scopes, "instruction_seconds", lambda path: SECONDS)
    return {"kind": "train", "trace_path": "t", "step_hlo": HLO,
            "trace": {"busy0_s": 2.0}, "cfg": _config(), "chips": 1,
            "rows": 1, "seq": 8192, "k": 2, "traced_readings": 3,
            "peaks": V5E}


def test_the_indexers_and_the_attentions_time_are_told_apart(monkeypatch):
    ctx = _traced_ctx(monkeypatch)
    assert dsa_scopes.seconds_under(
        ctx, "attn.attend.sparse", "attn.index.target") == pytest.approx(0.05)
    assert dsa_scopes.seconds_under(ctx, "mla.attend", "x") is None
    read = lambda name: common.load_reader(common.HERE, name)(ctx)  # noqa: E731
    assert read("dsa_index_time_pct") == pytest.approx(100 * 0.17 / 2.0)
    assert read("dsa_select_time_pct") == pytest.approx(100 * 0.1 / 2.0)
    flops, nbytes = counts_dsa_gqa.attend_train_flops_bytes(ctx["cfg"], 1,
                                                            8192)
    least, _ = counts.roofline_seconds(flops, nbytes, V5E)
    assert read("dsa_attend_roofline") == pytest.approx(100 * 6 * least / 0.05)
    flops, nbytes = counts_dsa_gqa.index_train_flops_bytes(ctx["cfg"], 1,
                                                           8192)
    least, _ = counts.roofline_seconds(flops, nbytes, V5E)
    assert read("dsa_index_roofline") == pytest.approx(100 * 6 * least / 0.06)


NEW_READERS = ("dsa_lm_mfu_pct", "dsa_index_time_pct", "dsa_select_time_pct",
               "dsa_index_roofline", "dsa_attend_roofline")


def test_new_readers_return_nothing_without_their_sources():
    """A parent commit: a step without the scopes, or no trace at all."""
    ctx = {"kind": "train", "readings": [{"seconds": 1.0}], "trace": None,
           "cfg": _config(), "chips": 1, "rows": 1, "seq": 8192, "k": 2,
           "traced_readings": 3, "train_tok_s": 1.0, "peaks": V5E}
    for name in NEW_READERS:
        assert common.load_reader(common.HERE, name)(dict(ctx)) is None, name
    bare = dict(ctx, trace={"busy0_s": 1.0}, trace_path="t",
                step_hlo="ENTRY %main {\n}\n", _instr_seconds={"fusion.1": 1.0},
                _instr_scopes={})
    for name in NEW_READERS[1:]:
        assert common.load_reader(common.HERE, name)(dict(bare)) is None, name


def test_the_cell_lists_its_readers_and_the_accepted_ones_find_it():
    cell = common.find_cell(common.load_manifest(), CELL)
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW_READERS) <= names
    assert {"moe_time_pct", "optimizer_time_pct", "moe_local_assign_per_tok",
            "moe_load_max_over_mean", "flash_time_pct",
            "gqa_moe_expert_roofline"} <= names
    assert cell["chips"] == 1 and cell["traffic"] == "s8192_b1_causal_dsa"
    spec = cell["traffic_file"]
    assert (spec["batch_per_chip"], spec["seq"], spec["steps_per_reading"],
            spec["feed_ring"]) == (1, 8192, 2, 4)


def test_mfu_reader_uses_the_assignments_that_fell_here(monkeypatch):
    routing = {"routing": {"local_assignments_per_token": 1.0,
                           "load_max_over_mean": 1.2,
                           "selected_pairs_per_query": 1792.125}}
    ctx = dict(_traced_ctx(monkeypatch), train_tok_s=10000.0,
               readings=[routing] * 5)
    got = common.load_reader(common.HERE, "dsa_lm_mfu_pct")(ctx)
    assert got == pytest.approx(100 * 10000 * 1_586_735_616 / 197e12)


# ---------------------------------------------------------------------------
# `correct` at the rehearsal's size: true for the sound program, false under
# each fault the new mechanisms admit, the PROGRAM at fault and the
# reference as it is
# ---------------------------------------------------------------------------

def _rehearse():
    return run.run_cell(CELL, 2147483659, 1.0, 0,
                        rehearsal=rehearse.tiny_presets(CELL))


def _bad(result):
    return {c["name"] for c in result["checks"] if c["value"] > c["limit"]}


def _selection_ignored(monkeypatch, keye):
    real = keye.layers.sparse_index
    monkeypatch.setattr(keye.layers, "sparse_index",
                        lambda q, k, w, topk: real(q, k, w, 1 << 20))


def _topk_quartered(monkeypatch, keye):
    real = keye.layers.sparse_index
    monkeypatch.setattr(keye.layers, "sparse_index",
                        lambda q, k, w, topk: real(q, k, w, topk // 4))


def _index_loss_left_out(monkeypatch, keye):
    real = keye.layers.sparse_index_loss
    monkeypatch.setattr(
        keye.layers, "sparse_index_loss",
        lambda *a: keye.layers.detach(real(*a)))


def _index_input_attached(monkeypatch, keye):
    monkeypatch.setattr(keye.layers, "detach", lambda x: x)


def _lowest_selected(monkeypatch, keye):
    real = keye.layers.sparse_index
    monkeypatch.setattr(
        keye.layers, "sparse_index",
        lambda q, k, w, topk: real(q, k, keye.layers.scale(w, scale=-1.0),
                                   topk))


def test_sound_run_is_correct():
    sound = _rehearse()
    assert sound["correct"] and not sound["failed"], sound


@pytest.mark.parametrize("fault", [
    _selection_ignored, _topk_quartered, _index_loss_left_out,
    _index_input_attached, _lowest_selected],
    ids=lambda f: f.__name__.strip("_"))
def test_each_fault_of_the_program_is_not_correct(fault, monkeypatch):
    from paddle_tpu.models import keye
    fault(monkeypatch, keye)
    result = _rehearse()
    assert not result["correct"] and _bad(result) & {
        "loss_gap_step1", "loss_gap_step2", "moment1_gap", "delta_gap",
        "index_loss_gap", "select_mismatch_share"}, result["checks"]


def test_the_drivers_faults_move_the_reference_past_the_limits():
    """`calibrate`'s fault rows, at the rehearsal's size: the reference with
    each fault against the sound reference; and the fp8 control."""
    from benchmark import lm_traffic
    from benchmark.drivers import train_dsa_lm, train_lm
    cell, _, _ = common.open_cell(CELL, tiny=rehearse.tiny_presets(CELL))
    cfg, spec = cell["config_file"], cell["traffic_file"]
    limits = spec["limits"]

    class Stub:
        seed, seq, k = 5, spec["seq"], spec["steps_per_reading"]
        ref = common.load_reference(cfg)

    Stub.cfg = cfg
    host = lm_traffic.lm_feed(spec, cfg["vocab"], spec["batch_per_chip"], 5,
                              0)
    sound = train_lm.run_reference(Stub, host)

    def fails(gaps):
        return not all(v <= limits["loss_gap" if k.startswith("loss") else k]
                       for k, v in gaps.items())

    wrongs = train_dsa_lm.faults(cfg, Stub.seq)
    assert sorted(wrongs) == ["index_input_attached", "index_loss_left_out",
                              "lowest_selected", "selection_ignored",
                              "topk_quartered"]
    for name, wrong in wrongs.items():
        gaps = train_dsa_lm.compare_lm(
            train_lm.run_reference(Stub, host, cfg=wrong), sound)
        assert fails(gaps), (name, gaps)
    assert fails(train_dsa_lm.compare_lm(
        train_lm.run_reference(Stub, host, "fp8"), sound))
