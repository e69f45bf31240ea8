"""What the latent-expert / shared-heads cell brings: its file against the
published numbers, its counts against hand-worked numbers (the accepted
`counts_hybrid_ssm.py` at this share's heads among them), its readers with
and without their sources, and `correct` shown to fail under faults of the
new mechanisms, at the rehearsal's size."""
import json
import os

import numpy as np
import pytest

from benchmark import (common, counts, counts_hybrid_ssm,
                       counts_latent_hybrid, peaks, rehearse, run)

CELL = "nemotron3_super_120b_a12b_ep64_tp8_s4096"
V5E = peaks.device_peaks("TPU v5 lite")


def _config():
    with open(os.path.join(common.HERE, "configs",
                           "nemotron3_super_120b_a12b_ep64_tp8.json")) as f:
        return json.load(f)


def test_the_file_keeps_the_published_numbers_and_states_the_cut():
    cfg = _config()
    for key, value in cfg["published"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["reduced"] == [
        "layers", "n_routed_experts", "mamba_num_heads", "n_groups",
        "num_attention_heads", "num_key_value_heads", "vocab"]
    pub = cfg["published"]
    assert pub["n_routed_experts"] == cfg["experts_total"] == 512
    assert pub["mamba_num_heads"] == cfg["mamba_heads_total"] == 128
    assert pub["n_groups"] == cfg["mamba_groups_total"] == 8
    assert pub["num_attention_heads"] == cfg["heads_total"] == 32
    assert pub["num_key_value_heads"] == cfg["kv_heads_total"] == 2
    assert (cfg["layers"], cfg["n_routed_experts"], cfg["vocab"]) == (
        11, 8, 16384)
    assert (cfg["mamba_num_heads"], cfg["n_groups"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"]) == (
        16, 1, 4, 1)
    # an eighth of a layer's heads, a 64th of its experts, an eighth of the
    # vocabulary; no width is cut
    assert cfg["mamba_num_heads"] * 8 == pub["mamba_num_heads"]
    assert cfg["num_attention_heads"] * 8 == pub["num_attention_heads"]
    assert cfg["n_routed_experts"] * 64 == cfg["experts_total"]
    assert cfg["vocab"] * 8 == pub["vocab_size"]
    assert (cfg["moe_latent_size"], cfg["num_experts_per_tok"],
            cfg["routed_scaling_factor"], cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"]) == (
        1024, 22, 5, 2688, 5376)
    assert cfg["num_experts_per_tok"] > cfg["n_routed_experts"]
    assert "64 chips share each layer" in cfg["deployment"]
    assert set(cfg["reduced"]) <= set(cfg["assumed"])
    assert "NOT built" in cfg["assumed"]["multi_token_head"]
    assert pub["num_nextn_predict_layers"] == 1
    pattern = cfg["hybrid_override_pattern"]
    assert len(pattern) == cfg["num_hidden_layers"] == 88
    assert [pattern.count(k) for k in "ME*"] == [40, 40, 8]
    assert counts_hybrid_ssm.layer_kinds(cfg) == "MEMEMEM*EME"
    from benchmark.reference import nemotron3_super
    shapes = nemotron3_super.param_shapes(cfg)
    count = lambda keep: sum(  # noqa: E731
        int(np.prod(s)) for n, s in shapes.items() if keep(n))
    buffers = sum(int(np.prod(s)) for s in
                  nemotron3_super.buffer_shapes(cfg).values())
    assert count(lambda n: True) + buffers == 700_865_520      # 700.9 M
    assert count(lambda n: n.startswith("l0_")) == 13_708_592
    assert count(lambda n: n.startswith("l1_")) + 512 == 98_570_752
    assert count(lambda n: n.startswith("l1_experts")) == 8 * 5_505_024
    assert count(lambda n: n.startswith("l1_latent")) == 2 * 4096 * 1024
    assert count(lambda n: n.startswith("l1_shared")) == 2 * 4096 * 5376
    assert count(lambda n: n.startswith("l7_")) == 5_246_976
    assert count(lambda n: not n.startswith("l")
                 or n == "lm_head_w") == 2 * 16384 * 4096 + 4096
    assert set(nemotron3_super.buffer_shapes(cfg)) == {
        f"l{n}_router_bias" for n in (1, 3, 5, 8, 10)}


def test_flops_per_token_by_layer_kind():
    cfg = _config()
    assert counts_latent_hybrid.latent_proj_flops_per_token(cfg) == (
        4 * 4096 * 1024) == 16_777_216
    layer = counts_latent_hybrid.expert_layer_forward_flops_per_token
    # router 4.19 M, shared 88.08 M, the latent's two 16.78 M, and 11.01 M
    # an assignment that fell here
    assert layer(cfg, 0.0) == 2 * 4096 * 512 + 4 * 4096 * 5376 + 16_777_216
    assert layer(cfg, 1.0) - layer(cfg, 0.0) == 4 * 1024 * 2688 == 11_010_048
    # the accepted counts at this share's heads: [z 1024 | x 1024 | B 128 |
    # C 128 | dt 16] = 2,320 columns, 16 heads x 64 x 128 state elements
    assert counts_hybrid_ssm.ssm_proj_flops_per_token(cfg) == 2 * (
        4096 * 2320 + 4 * 1280 + 1024 * 4096) == 27_404_288
    assert counts_hybrid_ssm.ssm_scan_flops_per_token(cfg) == 5 * 16 * 64 * 128
    assert counts_hybrid_ssm.attn_proj_flops_per_token(cfg) == 2 * (
        4096 * 512 + 2 * 4096 * 128 + 512 * 4096) == 10_485_760
    fwd = counts_latent_hybrid.lm_forward_flops_per_token(cfg, 4096, 0.34375)
    kind = counts_hybrid_ssm.kind_forward_flops_per_token
    assert fwd == pytest.approx(
        5 * kind(cfg, 4096, "M", 0.0) + kind(cfg, 4096, "*", 0.0)
        + 5 * layer(cfg, 0.34375) + 2 * 4096 * 16384)
    # 5 x 28.06 M, 14.68 M, 5 x 112.84 M and the head's 134.22 M
    assert fwd == 853_380_096
    assert counts_latent_hybrid.lm_train_flops_per_token(
        cfg, 4096, 0.34375) == 3 * fwd
    with pytest.raises(ValueError):
        counts_latent_hybrid.lm_forward_flops_per_token(
            dict(cfg, hybrid_override_pattern="M-"), 4096, 0.3)


def test_the_accepted_scan_and_flash_counts_give_this_shares_hand_count():
    """`ssm_scan_roofline` and `hybrid_flash_roofline` read the top-level
    keys, which say what is held: 16 heads in 1 group, 4 query heads on 1
    KV head, 4,096 tokens."""
    cfg = _config()
    flops, nbytes = counts_hybrid_ssm.ssm_scan_train_flops_bytes(cfg, 1, 4096)
    assert flops == 5 * 3 * (5 * 16 * 64 * 128) * 4096
    # x, y forward and x, dy, dx backward at 1024; B, C forward and B, C,
    # dB, dC backward at 128, bf16; dt forward, dt and ddt backward, f32
    assert nbytes == 5 * 4096 * (2 * (5 * 1024 + 6 * 128) + 4 * 3 * 16)
    least, bound = counts.roofline_seconds(flops, nbytes, V5E)
    assert bound == "bytes" and least == pytest.approx(299.2e-6, rel=1e-3)
    flops, nbytes = counts_hybrid_ssm.flash_train_flops_bytes(cfg, 1, 4096)
    assert counts_hybrid_ssm.attend_pairs(4096) == 8_390_656
    assert flops == 6 * 2 * 4 * 8_390_656 * 128
    assert nbytes == 4096 * 128 * 2 * 6 * (4 + 1)
    flops, nbytes = counts_latent_hybrid.moe_experts_train_flops_bytes(
        cfg, 1408)
    assert flops == 5 * 6 * 2 * 1408 * 1024 * 2688
    weights = 8 * 2 * 1024 * 2688 * 2
    assert nbytes == 5 * (3 * weights + 3 * 1408 * (2 * 1024 + 2 * 2688) * 2)
    # whatever the buffer holds (8 x 4,096 rows), the count is of the
    # assignments: 23 times fewer here
    assert 8 * 4096 / 1408 > 23


HLO = '''
ENTRY %main {
  %flash_attention_fwd.3 = (bf16[4,8,8]{2,1,0}, f32[4,8,128]{2,1,0}) custom-call(%c, %q), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/while/body/closed_call/attn.attend.full/flash_attention_fwd/pallas_call" source_file="x.py"}
  %fusion.7 = f32[32,8,128]{2,1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/closed_call/ssm.scan/ssm.scan.intra/dot_general"}
  %fusion.9 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/closed_call/ssm.in_proj/dot_general"}
  %fusion.11 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/closed_call/moe.latent_down/dot_general"}
  %fusion.12 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/closed_call/transpose(jvp(moe.latent_up))/dot_general"}
  %fusion.13 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/closed_call/moe.shared/dot_general"}
  %ragged-dot-gmm.4 = bf16[8,8]{1,0} custom-call(%p), custom_call_target="tpu_custom_call"
}
'''


def _traced_ctx(monkeypatch):
    from benchmark import scopes
    monkeypatch.setattr(scopes, "instruction_seconds", lambda path: {
        "flash_attention_fwd.3": 0.03, "fusion.7": 0.05, "fusion.9": 0.04,
        "fusion.11": 0.02, "fusion.12": 0.06, "fusion.13": 1.0,
        "ragged-dot-gmm.4": 0.6})
    routing = {"routing": {"local_assignments_per_token": 0.34375,
                           "load_max_over_mean": 1.2}}
    return {"kind": "train", "trace_path": "t", "step_hlo": HLO,
            "trace": {"busy0_s": 2.0}, "cfg": _config(), "chips": 1,
            "rows": 1, "seq": 4096, "k": 2, "traced_readings": 3,
            "peaks": V5E, "train_tok_s": 9000.0, "readings": [routing] * 5}


def test_the_new_readers_on_a_recorded_join(monkeypatch):
    ctx = _traced_ctx(monkeypatch)
    read = lambda name: common.load_reader(common.HERE, name)(ctx)  # noqa: E731
    # the two projections' scopes, forward and backward, over the busy time
    assert read("latent_proj_time_pct") == pytest.approx(100 * 0.08 / 2.0)
    flops, nbytes = counts_latent_hybrid.moe_experts_train_flops_bytes(
        ctx["cfg"], 0.34375 * 4096)
    least, _ = counts.roofline_seconds(flops, nbytes, V5E)
    assert read("latent_moe_expert_roofline") == pytest.approx(
        100 * 6 * least / 0.6)
    assert read("latent_lm_mfu_pct") == pytest.approx(
        100 * 9000 * 3 * 853_380_096 / 197e12)
    # the accepted readers this cell is listed under find their sources too
    assert read("ssm_time_pct") == pytest.approx(100 * 0.09 / 2.0)
    assert read("ssm_scan_roofline") == pytest.approx(
        100 * 6 * 299.2e-6 / 0.05, rel=1e-3)
    flops, nbytes = counts_hybrid_ssm.flash_train_flops_bytes(
        ctx["cfg"], 1, 4096)
    least, _ = counts.roofline_seconds(flops, nbytes, V5E)
    assert read("hybrid_flash_roofline") == pytest.approx(
        100 * 6 * least / 0.03)
    assert read("moe_time_pct") == pytest.approx(100 * 0.6 / 2.0)
    assert read("moe_local_assign_per_tok") == pytest.approx(0.34375)
    assert read("moe_load_max_over_mean") == pytest.approx(1.2)


def test_new_readers_return_nothing_without_their_sources():
    """A parent commit: a step without the scopes, no trace at all, or a
    configuration without a latent."""
    ctx = {"kind": "train", "readings": [{"seconds": 1.0}], "trace": None,
           "cfg": _config(), "chips": 1, "rows": 1, "seq": 4096, "k": 2,
           "traced_readings": 3, "train_tok_s": 1.0, "peaks": V5E}
    names = ("latent_lm_mfu_pct", "latent_proj_time_pct",
             "latent_moe_expert_roofline")
    for name in names:
        assert common.load_reader(common.HERE, name)(dict(ctx)) is None, name
    bare = dict(ctx, trace={"busy0_s": 1.0}, trace_path="t",
                step_hlo="ENTRY %main {\n}\n", _instr_seconds={"fusion.1": 1.0},
                _instr_scopes={})
    for name in names[1:]:
        assert common.load_reader(common.HERE, name)(dict(bare)) is None, name
    routing = {"routing": {"local_assignments_per_token": 0.4}}
    whole = {k: v for k, v in _config().items() if k != "moe_latent_size"}
    other = dict(ctx, cfg=whole, readings=[routing] * 5)
    for name in ("latent_lm_mfu_pct", "latent_moe_expert_roofline"):
        assert common.load_reader(common.HERE, name)(dict(other)) is None, name


def test_the_cell_is_listed_where_the_manifest_says():
    cell = common.find_cell(common.load_manifest(), CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "s4096_b1_causal_latent"
    spec = cell["traffic_file"]
    assert (spec["batch_per_chip"], spec["seq"], spec["steps_per_reading"],
            spec["feed_ring"]) == (1, 4096, 2, 4)
    assert spec["labels"] == "next_token" and not spec["padded"]
    assert spec["label_rate"] == 4095 / 4096
    listed = {m["name"] for m in cell["per_layer"]}
    assert {"latent_proj_time_pct", "latent_moe_expert_roofline",
            "latent_lm_mfu_pct", "ssm_time_pct", "ssm_scan_roofline",
            "hybrid_flash_roofline", "flash_time_pct", "moe_time_pct",
            "optimizer_time_pct", "moe_local_assign_per_tok",
            "moe_load_max_over_mean"} <= listed
    assert not listed & {"mfu_pct", "lm_mfu_pct", "gqa_lm_mfu_pct",
                         "hybrid_lm_mfu_pct", "hybrid_moe_expert_roofline",
                         "kda_lm_mfu_pct", "mla_flash_roofline"}
    manifest = common.load_manifest()
    assert manifest["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in manifest["per_layer"][-3:]] == [
        "latent_proj_time_pct", "latent_moe_expert_roofline",
        "latent_lm_mfu_pct"]
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    assert len(manifest["workloads"]) == 7


# ---------------------------------------------------------------------------
# `correct` at the rehearsal's size: true for the sound program, false with
# the PROGRAM at fault and the reference as it is
# ---------------------------------------------------------------------------

def _rehearse():
    return run.run_cell(CELL, 2147483659, 1.0, 0,
                        rehearsal=rehearse.tiny_presets(CELL))


def _bad(result):
    return {c["name"] for c in result["checks"] if c["value"] > c["limit"]}


def _routed_part_left_out(monkeypatch):
    """W_b's product zeroed in every expert layer: the shared expert alone."""
    from paddle_tpu.models import nemotron_h
    real = nemotron_h._linear

    def skip(x, size, name, cfg):
        y = real(x, size, name, cfg)
        return (nemotron_h.layers.scale(y, 0.0)
                if name.endswith("latent_up_w") else y)

    monkeypatch.setattr(nemotron_h, "_linear", skip)


def _experts_read_the_first_columns_of_x(monkeypatch):
    """No W_a: the experts read x's first `moe_latent_size` features."""
    from paddle_tpu.models import nemotron_h
    real = nemotron_h._linear

    def first(x, size, name, cfg):
        if name.endswith("latent_down_w"):
            real(x, size, name, cfg)
            return nemotron_h.layers.slice(x, [2], [0], [size])
        return real(x, size, name, cfg)

    monkeypatch.setattr(nemotron_h, "_linear", first)


def _weights_without_their_factor(monkeypatch):
    from paddle_tpu.models import nemotron_h
    real = nemotron_h.layers.routed_moe

    def unscaled(*args, **kw):
        return real(*args, **dict(kw, routed_scaling=1.0))

    monkeypatch.setattr(nemotron_h.layers, "routed_moe", unscaled)


def test_sound_run_is_correct():
    sound = _rehearse()
    assert sound["correct"] and not sound["failed"], sound


@pytest.mark.parametrize("fault", [
    _routed_part_left_out, _experts_read_the_first_columns_of_x,
    _weights_without_their_factor], ids=lambda f: f.__name__.strip("_"))
def test_each_fault_of_the_program_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    result = _rehearse()
    assert not result["correct"] and _bad(result) & {
        "loss_gap_step1", "loss_gap_step2", "moment1_gap", "delta_gap",
        "route_mismatch_share"}, result["checks"]


def test_the_drivers_four_controls_and_fp8_fail():
    """`calibrate`'s fault rows, at the rehearsal's size: the reference with
    each fault against the sound reference. Top-6 for top-22 (here 2 for
    6), the weights without their factor, one routed part of five left out
    and the scan's states in float8 each fail by one of the rehearsal's
    limits, as do the fp8 control and a quarter of the row left out. (On
    the chip, at 4,096 tokens, the first three and fp8 fail by 20 times the
    cell's limits or more and the float8 states do NOT: PERF.md section 6,
    PR 39.)"""
    from benchmark import lm_traffic
    from benchmark.drivers import train_latent_hybrid_lm as driver
    from benchmark.drivers import train_lm
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

    wrongs = driver.faults(cfg)
    assert sorted(wrongs) == ["one_routed_part_left_out",
                              "scan_states_float8", "top6_for_top22",
                              "weights_without_factor"]
    assert wrongs["one_routed_part_left_out"]["assumed"][
        "routed_left_out"] == "l5_"
    wrongs["top6_for_top22"]["num_experts_per_tok"] = 2     # of the tiny 6
    for name, wrong in wrongs.items():
        gaps = driver.compare_lm(
            train_lm.run_reference(Stub, host, cfg=wrong), sound)
        assert fails(gaps), (name, gaps)
        if name == "top6_for_top22":
            assert gaps["route_mismatch_share"] == 1.0
    assert fails(driver.compare_lm(
        train_lm.run_reference(Stub, host, "fp8"), sound))
    assert train_lm._quarter_left_out(Stub, host, sound) > 10 * limits[
        "loss_gap"]
