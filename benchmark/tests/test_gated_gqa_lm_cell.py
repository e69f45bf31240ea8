"""What the gated cell with a head count a layer's kind brings: its file
against the published numbers, its counts against hand-worked numbers, its
readers with and without their sources, and `correct` shown to fail under
faults of the new mechanisms, at the rehearsal's size."""
import json
import os

import numpy as np
import pytest

from benchmark import (common, counts, counts_gated_gqa, peaks, rehearse,
                       run)

CELL = "laguna_xs2_33b_a3b_ep8_s8192"
V5E = peaks.device_peaks("TPU v5 lite")
FULL, SLIDING = "full_attention", "sliding_attention"


def _config():
    with open(os.path.join(common.HERE, "configs",
                           "laguna_xs2_33b_a3b_ep8.json")) as f:
        return json.load(f)


def test_the_file_keeps_the_published_numbers_and_states_the_cut():
    cfg = _config()
    for key, value in cfg["published"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["layers", "num_experts", "vocab"]
    published = cfg["published"]
    assert published["num_experts"] == cfg["experts_total"] == 256
    assert (cfg["layers"], cfg["first_layer"], cfg["num_experts"],
            cfg["expert_offset"], cfg["vocab"]) == (5, 0, 32, 0, 12544)
    assert cfg["vocab"] * 8 == published["vocab_size"]
    assert cfg["num_experts"] * 8 == cfg["experts_total"]
    # every width as published
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"],
            cfg["sliding_window"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"],
            cfg["num_experts_per_tok"]) == (2048, 128, 8, 512, 8192, 512, 512,
                                            8)
    assert cfg["num_attention_heads_per_layer"][:5] == [48, 64, 64, 64, 48]
    assert cfg["layer_types"][:5] == [FULL] + [SLIDING] * 3 + [FULL]
    assert cfg["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert len(cfg["layer_types"]) == len(cfg["mlp_layer_types"]) == len(
        cfg["num_attention_heads_per_layer"]) == 40
    rope = cfg["rope_parameters"]
    assert rope[FULL]["partial_rotary_factor"] == 0.5
    assert rope[FULL]["rope_type"] == "yarn" and rope[FULL]["factor"] == 64
    assert rope[SLIDING] == {"rope_type": "default", "rope_theta": 10000,
                             "partial_rotary_factor": 1}
    assert "8 chips share each layer" in cfg["deployment"]
    assert "no code stands in" in cfg["deployment"]
    assert "2 of the 5 layers" in cfg["deployment"]
    assert set(cfg["reduced"]) <= set(cfg["assumed"])
    assumed = cfg["assumed"]
    assert "element-wise" in assumed["gating"] and "NOT taken" in assumed[
        "gating"]
    assert "sigmoid" in assumed["router"] and "2.5" in assumed["router"]
    assert "FIRST 64" in assumed["rope"] and assumed["recompute"] is True
    from benchmark.reference import laguna_xs2
    shapes = laguna_xs2.param_shapes(cfg)
    count = lambda keep: sum(  # noqa: E731
        int(np.prod(s)) for n, s in shapes.items() if keep(n))
    assert count(lambda n: True) == 766_531_584          # 766.5 M
    # q, gate and W_o at 48 heads 12.58 M each, k + v 4.19 M; at 64 heads
    # 16.78 M each
    assert count(lambda n: n.startswith("l0_")
                 and n.endswith("_proj_w")) == 41_943_040
    assert count(lambda n: n.startswith("l1_")
                 and n.endswith("_proj_w")) == 54_525_952
    assert count(lambda n: n.startswith("l0_")) == 92_278_784
    assert count(lambda n: n.startswith("l2_")) == 158_863_360
    assert count(lambda n: n.startswith("l4_")) == 146_280_448
    assert count(lambda n: n.startswith("l3_experts_")) == 32 * 3 * 2048 * 512
    assert count(lambda n: not n.startswith("l")
                 or n == "lm_head_w") == 2 * 12544 * 2048 + 2048
    assert not laguna_xs2.buffer_shapes(cfg)


def test_flops_per_token_by_layer():
    cfg = _config()
    # q, gate, W_o at the layer's OWN head count, k and v at 8 KV heads
    assert counts_gated_gqa.proj_flops_per_token(cfg, 0) == 2 * (
        3 * 2048 * 6144 + 2 * 2048 * 1024) == 83_886_080
    assert counts_gated_gqa.proj_flops_per_token(cfg, 1) == 2 * (
        3 * 2048 * 8192 + 2 * 2048 * 1024) == 109_051_904
    assert counts_gated_gqa.layer_pairs(cfg, 8192, 0) == 33_558_528
    # rows 0..511 see 1..512 keys, the other 7,680 rows 512 each
    assert counts_gated_gqa.layer_pairs(cfg, 8192, 1) == (
        131_328 + 3_932_160) == 4_063_488
    assert 4_063_488 / 33_558_528 == pytest.approx(0.1211, abs=1e-4)
    assert counts_gated_gqa.attend_flops_per_token(cfg, 8192, 0) == (
        4 * 48 * 128 * 4096.5)
    assert counts_gated_gqa.attend_flops_per_token(cfg, 8192, 2) == (
        4 * 64 * 128 * 4_063_488 / 8192)
    assert counts_gated_gqa.ffn_flops_per_token(cfg, 0, 1.0) == (
        6 * 2048 * 8192)
    assert counts_gated_gqa.ffn_flops_per_token(cfg, 3, 1.0) == (
        2 * 2048 * 256 + 6 * 2048 * 512 + 1.0 * 6 * 2048 * 512)
    fwd = counts_gated_gqa.lm_forward_flops_per_token(cfg, 8192, 1.0)
    assert fwd == pytest.approx(
        2 * 83_886_080 + 3 * 109_051_904            # projections 494.9 M
        + 2 * 100_675_584 + 3 * 16_253_952          # causal products 250.1 M
        + 100_663_296 + 4 * 13_631_488              # dense 100.7, sparse 54.5
        + 2 * 2048 * 12544)                         # the head 51.4 M
    assert fwd == pytest.approx(951_610_368)
    assert counts_gated_gqa.lm_train_flops_per_token(cfg, 8192,
                                                     1.0) == 3 * fwd
    assert counts_gated_gqa.layers_of(cfg, FULL) == [0, 4]
    assert counts_gated_gqa.sparse_layers(cfg) == [1, 2, 3, 4]


def test_the_kernels_counts_are_the_mathematics():
    cfg = _config()
    flops, nbytes = counts_gated_gqa.flash_train_flops_bytes(
        cfg, 1, 8192, FULL)
    assert flops == 2 * 6 * 2 * 48 * 33_558_528 * 128
    # q, o, q, o, dO, dq at 48 heads; k, v, k, v, dk, dv at 8
    assert nbytes == 2 * 8192 * 128 * 2 * 6 * (48 + 8)
    least, bound = counts.roofline_seconds(flops, nbytes, V5E)
    assert bound == "flops" and least == pytest.approx(25.119e-3, rel=1e-3)
    wflops, wbytes = counts_gated_gqa.flash_train_flops_bytes(
        cfg, 1, 8192, SLIDING)
    assert wflops == 3 * 6 * 2 * 64 * 4_063_488 * 128
    assert wbytes == 3 * 8192 * 128 * 2 * 6 * (64 + 8)
    least, bound = counts.roofline_seconds(wflops, wbytes, V5E)
    assert bound == "flops" and least == pytest.approx(6.083e-3, rel=1e-3)
    # the grouped matmuls of the FOUR sparse layers, at one assignment a
    # token: bound by the bytes of 32 experts' weights
    flops, nbytes = counts_gated_gqa.moe_experts_train_flops_bytes(cfg,
                                                                   8192)
    assert flops == 4 * 9 * 2 * 8192 * 2048 * 512
    weights = 32 * 3 * 2048 * 512 * 2
    assert nbytes == 4 * (3 * weights + 3 * 8192 * (2 * 2048 + 3 * 512) * 2)
    least, bound = counts.roofline_seconds(flops, nbytes, V5E)
    assert bound == "bytes" and least == pytest.approx(4.302e-3, rel=1e-3)


def test_the_counts_at_the_rehearsals_size_by_hand():
    cfg = dict(_config(), **rehearse.tiny_presets(CELL)["config"])
    # hidden 64, heads 12 / 16 of 16 on 2 KV heads, window 8 in rows of 32
    assert counts_gated_gqa.proj_flops_per_token(cfg, 0) == 2 * (
        3 * 64 * 192 + 2 * 64 * 32)
    assert counts_gated_gqa.layer_pairs(cfg, 32, 1) == 36 + 24 * 8
    assert counts_gated_gqa.layer_pairs(cfg, 32, 4) == 32 * 33 // 2
    assert counts_gated_gqa.ffn_flops_per_token(cfg, 0, 0.5) == 6 * 64 * 128
    assert counts_gated_gqa.ffn_flops_per_token(cfg, 1, 0.5) == (
        2 * 64 * 8 + 6 * 64 * 32 + 0.5 * 6 * 64 * 32)
    flops, nbytes = counts_gated_gqa.flash_train_flops_bytes(
        cfg, 2, 32, SLIDING)
    assert flops == 3 * 6 * 2 * 2 * 16 * 228 * 16
    assert nbytes == 3 * 2 * 32 * 16 * 2 * 6 * (16 + 2)


HLO = '''
ENTRY %main {
  %flash_attention_fwd.3 = (bf16[64,8,8]{2,1,0}, f32[64,8,128]{2,1,0}) custom-call(%c, %q), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/while/body/closed_call/phase.fwd/attn.attend.window/flash_attention_fwd/pallas_call" source_file="x.py"}
  %flash_attention_fwd.5 = (bf16[64,8,8]{2,1,0}, f32[64,8,128]{2,1,0}) custom-call(%c, %q), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/while/body/closed_call/phase.bwd/rematted_computation/attn.attend.window/flash_attention_fwd/pallas_call"}
  %flash_attention_bwd_dkdv.1 = (f32[8,8,8]{2,1,0}, f32[8,8,8]{2,1,0}) custom-call(%c, %q), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/while/body/closed_call/phase.bwd/attn.attend.full/flash_attention_bwd_dkdv/pallas_call"}
  %fusion.7 = f32[64,8,128]{2,1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/closed_call/phase.fwd/attn.attend.window/broadcast_in_dim"}
  %fusion.9 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/closed_call/phase.fwd/attn.proj/mul"}
  %fusion.10 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/closed_call/phase.fwd/ffn.dense/dot_general"}
  %fusion.11 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/closed_call/phase.fwd/moe.shared/dot_general"}
  %fusion.12 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/closed_call/phase.fwd/moe.io/moe.experts/mul"}
  %ragged-dot-gmm.4 = bf16[8,8]{1,0} custom-call(%p), custom_call_target="tpu_custom_call"
}
'''


def _traced_ctx(monkeypatch):
    from benchmark import scopes
    monkeypatch.setattr(scopes, "instruction_seconds", lambda path: {
        "flash_attention_fwd.3": 0.03, "flash_attention_fwd.5": 0.01,
        "flash_attention_bwd_dkdv.1": 0.3, "fusion.7": 0.005,
        "fusion.9": 0.5, "fusion.10": 0.1, "fusion.11": 0.02,
        "fusion.12": 0.1, "ragged-dot-gmm.4": 0.5})
    routing = {"routing": {"local_assignments_per_token": 1.0,
                           "load_max_over_mean": 1.4}}
    return {"kind": "train", "trace_path": "t", "step_hlo": HLO,
            "trace": {"busy0_s": 2.0}, "cfg": _config(), "chips": 1,
            "rows": 1, "seq": 8192, "k": 2, "traced_readings": 3,
            "peaks": V5E, "train_tok_s": 20000.0, "readings": [routing] * 5}


def test_the_new_readers_on_a_recorded_join(monkeypatch):
    ctx = _traced_ctx(monkeypatch)
    read = lambda name: common.load_reader(common.HERE, name)(ctx)  # noqa: E731
    # 6 traced steps of each kind's least time over ITS kernels' time, the
    # forward a segment runs again included
    assert read("gated_gqa_window_flash_roofline") == pytest.approx(
        100 * 6 * 6.083e-3 / 0.04, rel=1e-3)
    assert read("gated_gqa_full_flash_roofline") == pytest.approx(
        100 * 6 * 25.119e-3 / 0.3, rel=1e-3)
    assert read("gated_gqa_moe_expert_roofline") == pytest.approx(
        100 * 6 * 4.302e-3 / 0.6, rel=1e-3)
    assert read("gated_gqa_lm_mfu_pct") == pytest.approx(
        100 * 20000 * 3 * 951_610_368 / 197e12, rel=1e-6)
    # the accepted readers this cell is listed under find their sources too
    assert read("window_attn_time_pct") == pytest.approx(100 * 0.045 / 2.0)
    assert read("moe_time_pct") == pytest.approx(100 * 0.6 / 2.0)
    assert read("moe_local_assign_per_tok") == pytest.approx(1.0)
    assert read("moe_load_max_over_mean") == pytest.approx(1.4)


def test_new_readers_return_nothing_without_their_sources():
    """A parent commit: a step without the scopes, or no trace at all."""
    ctx = {"kind": "train", "readings": [{"seconds": 1.0}], "trace": None,
           "cfg": _config(), "chips": 1, "rows": 1, "seq": 8192, "k": 2,
           "traced_readings": 3, "train_tok_s": 1.0, "peaks": V5E}
    names = ("gated_gqa_lm_mfu_pct", "gated_gqa_window_flash_roofline",
             "gated_gqa_full_flash_roofline",
             "gated_gqa_moe_expert_roofline")
    for name in names:
        assert common.load_reader(common.HERE, name)(dict(ctx)) is None, name
    bare = dict(ctx, trace={"busy0_s": 1.0}, trace_path="t",
                step_hlo="ENTRY %main {\n}\n", _instr_seconds={"fusion.1": 1.0},
                _instr_scopes={})
    for name in names[1:]:
        assert common.load_reader(common.HERE, name)(dict(bare)) is None, name


def test_the_cell_is_listed_where_the_manifest_says():
    manifest = common.load_manifest()
    cell = common.find_cell(manifest, CELL)
    assert cell["chips"] == 1
    assert cell["traffic"] == "s8192_b1_causal_gated_gqa"
    assert len(cell["why"]) <= 200 and "train_tok_s weighs" in cell["why"]
    assert "groups 8, 6" in cell["why"] and "correct" in cell["why"]
    spec = cell["traffic_file"]
    assert (spec["batch_per_chip"], spec["seq"], spec["steps_per_reading"],
            spec["feed_ring"]) == (1, 8192, 2, 4)
    assert spec["labels"] == "next_token" and not spec["padded"]
    assert "EIGHTH" in spec["why"] and "65,536" in spec["why"]
    assert "What train_tok_s weighs" in spec["why"]
    listed = {m["name"] for m in cell["per_layer"]}
    assert {"gated_gqa_lm_mfu_pct", "gated_gqa_window_flash_roofline",
            "gated_gqa_full_flash_roofline", "gated_gqa_moe_expert_roofline",
            "flash_time_pct", "window_attn_time_pct", "moe_time_pct",
            "optimizer_time_pct", "moe_local_assign_per_tok",
            "moe_load_max_over_mean", "recompute_time_pct",
            "attn_proj_time_pct", "dense_ffn_time_pct"} <= listed
    # no roofline whose counts read ONE `num_attention_heads` and know no
    # gate, dense layer or shared expert
    assert not listed & {"window_flash_roofline", "full_flash_roofline",
                         "gqa_moe_expert_roofline", "gqa_lm_mfu_pct",
                         "mfu_pct", "lm_mfu_pct"}
    mine = [m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == ["gated_gqa_lm_mfu_pct",
                    "gated_gqa_window_flash_roofline",
                    "gated_gqa_full_flash_roofline",
                    "gated_gqa_moe_expert_roofline"]
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    config = next(c for c in manifest["configs"]
                  if c["name"] == cell["config"])
    assert config["source"] == _config()["source"] == (
        "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json")


# ---------------------------------------------------------------------------
# `correct` at the rehearsal's size: true for the sound program, false with
# the PROGRAM at fault and the reference as it is
# ---------------------------------------------------------------------------

def _rehearse():
    return run.run_cell(CELL, 2147483659, 1.0, 0,
                        rehearsal=rehearse.tiny_presets(CELL))


def _bad(result):
    return {c["name"] for c in result["checks"] if c["value"] > c["limit"]}


def _gate_left_out(monkeypatch, laguna):
    from paddle_tpu.models import causal_lm
    monkeypatch.setattr(causal_lm.layers, "head_gate", lambda x, gate: x)


def _turned_half_last(monkeypatch, laguna):
    real = laguna.layers.rotary_embedding
    monkeypatch.setattr(
        laguna.layers, "rotary_embedding",
        lambda t, **kw: real(t, **dict(kw, rotary_start=None)))


def _full_layers_turn_all(monkeypatch, laguna):
    real = laguna.layers.rotary_embedding
    monkeypatch.setattr(
        laguna.layers, "rotary_embedding",
        lambda t, **kw: real(t, **dict(kw, rotary_start=None,
                                       rotary_dim=None)))


def _window_ignored(monkeypatch, laguna):
    from paddle_tpu.models import causal_lm
    real = causal_lm.layers.fused_attention
    monkeypatch.setattr(
        causal_lm.layers, "fused_attention",
        lambda q, k, v, **kw: real(q, k, v, **dict(kw, window=None)))


def _scaling_1(monkeypatch, laguna):
    from paddle_tpu.models import causal_lm
    real = causal_lm.layers.routed_moe
    monkeypatch.setattr(
        causal_lm.layers, "routed_moe",
        lambda *a, **kw: real(*a, **dict(kw, routed_scaling=1.0)))


def test_sound_run_is_correct():
    sound = _rehearse()
    assert sound["correct"] and not sound["failed"], sound
    assert {c["name"] for c in sound["checks"]} >= {
        "loss_gap_step1", "moment1_gap", "delta_gap", "route_mismatch_share",
        "moment1_dir_gap", "tokens_dropped"}
    with open(os.path.join(common.out_dir(CELL, 2147483659, 0),
                           "checks.json")) as f:
        kept = json.load(f)["reference"]
    assert "moment1_vectors" not in kept and "first_route" not in kept


def test_run_and_calibrate_are_bound_to_this_modules_names():
    from benchmark.drivers import train_gated_gqa_lm as driver
    from benchmark.drivers import train_kda_lm
    for fn in (driver.run, driver.calibrate):
        assert fn.__globals__["Trainer"] is driver.Trainer
        assert fn.__globals__["compare_lm"] is train_kda_lm.compare_lm
    assert driver.calibrate.__globals__["faults"] is driver.faults
    assert sorted(driver.faults(_config(), 8192)) == sorted(driver.FAULTS)


@pytest.mark.parametrize("fault", [
    _gate_left_out, _turned_half_last, _full_layers_turn_all,
    _window_ignored, _scaling_1], ids=lambda f: f.__name__.strip("_"))
def test_each_fault_of_the_program_is_not_correct(fault, monkeypatch):
    from paddle_tpu.models import laguna
    fault(monkeypatch, laguna)
    result = _rehearse()
    assert not result["correct"] and _bad(result) & {
        "loss_gap_step1", "loss_gap_step2", "moment1_gap", "delta_gap",
        "route_mismatch_share", "moment1_dir_gap"}, result["checks"]


def test_the_drivers_faults_move_the_reference():
    """`calibrate`'s fault rows, at the rehearsal's size: the reference with
    each fault against the sound reference, the fp8 control and a quarter of
    the row left out; each fails by at least one limit."""
    from benchmark import lm_traffic
    from benchmark.drivers import (train_gated_gqa_lm as driver,
                                   train_kda_lm, train_lm)
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
    assert sorted(sound["moment1_vectors"]) == sorted(
        Stub.ref.vector_leaves(cfg)) == [f"l{n}_k_proj_w" for n in range(5)]

    def fails(gaps):
        return not all(v <= limits["loss_gap" if k.startswith("loss") else k]
                       for k, v in gaps.items())

    for name, wrong in driver.faults(cfg, spec["seq"]).items():
        gaps = train_kda_lm.compare_lm(
            train_lm.run_reference(Stub, host, cfg=wrong), sound)
        assert fails(gaps), (name, gaps)
    fp8 = train_kda_lm.compare_lm(train_lm.run_reference(Stub, host, "fp8"),
                                  sound)
    assert fails(fp8), fp8
    assert train_lm._quarter_left_out(Stub, host, sound) > 10 * limits[
        "loss_gap"]
