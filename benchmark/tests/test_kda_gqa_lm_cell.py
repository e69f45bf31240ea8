"""What the unbounded-decay delta-rule / gated-softmax / all-sparse cell
brings: its file against the published numbers, its counts against
hand-worked numbers, its readers with and without their sources, and
`correct` shown to fail under faults of the new mechanisms, at the
rehearsal's size."""
import json
import os

import numpy as np
import pytest

from benchmark import common, counts, counts_kda_gqa, peaks, rehearse, run

CELL = "solar_open2_250b_ep40_tp8_s4096"
V5E = peaks.device_peaks("TPU v5 lite")


def _config():
    with open(os.path.join(common.HERE, "configs",
                           "solar_open2_250b_ep40_tp8.json")) as f:
        return json.load(f)


def test_the_file_keeps_the_published_numbers_and_states_the_cut():
    cfg = _config()
    for key, value in cfg["published"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["reduced"] == [
        "layers", "n_routed_experts", "num_attention_heads",
        "num_key_value_heads", "linear_attn_config", "vocab"]
    published = cfg["published"]
    assert published["n_routed_experts"] == cfg["experts_total"] == 320
    assert published["num_attention_heads"] == cfg["heads_total"] == 64
    assert published["num_key_value_heads"] == cfg["kv_heads_total"] == 8
    lin, whole = cfg["linear_attn_config"], published["linear_attn_config"]
    assert whole["num_heads"] == cfg["linear_heads_total"] == 64
    # the group's widths as published: only the count of heads is the share
    assert {k: v for k, v in lin.items() if k != "num_heads"} == {
        k: v for k, v in whole.items() if k != "num_heads"}
    assert (cfg["layers"], cfg["first_layer"], cfg["n_routed_experts"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            lin["num_heads"], cfg["vocab"]) == (4, 0, 8, 8, 1, 8, 24576)
    assert cfg["vocab"] * 8 == published["vocab_size"]
    assert cfg["n_routed_experts"] * 40 == cfg["experts_total"]
    assert cfg["num_attention_heads"] * 8 == cfg["heads_total"]
    assert "40 chips share each layer" in cfg["deployment"]
    assert "no code stands in" in cfg["deployment"]
    assert set(cfg["reduced"]) <= set(cfg["assumed"])
    assumed = cfg["assumed"]
    assert "sigmoid" in assumed["router"] and "element-wise" in assumed[
        "attention_gate"]
    assert "read by no layer" in assumed["intermediate_size"]
    assert not cfg["use_rope"] and cfg["first_k_dense_replace"] == 0
    assert counts_kda_gqa.layer_kinds(cfg) == ["gqa", "kda", "kda", "kda"]
    from benchmark.reference import solar_open2
    shapes = solar_open2.param_shapes(cfg)
    count = lambda keep: sum(  # noqa: E731
        int(np.prod(s)) for n, s in shapes.items() if keep(n))
    mixer = ("_proj_w", "_proj_b", "_conv_w", "A_log", "dt_bias",
             "o_norm_scale")
    assert count(lambda n: True) == 840_874_392          # 840.9 M
    # q 4.19 M, k + v 1.05 M, gate 4.19 M, W_o 4.19 M
    assert count(lambda n: n.startswith("l0_")
                 and n.endswith(mixer)) == 13_631_488
    # q, k, v 12.58 M, W_o 4.19 M, both low-rank pairs 0.66 M each, beta
    # 32 K, taps, A_log, dt_bias, the gate's bias, the head norm
    assert count(lambda n: n.startswith("l1_")
                 and n.endswith(mixer)) == 18_135_176
    assert count(lambda n: n.startswith("l2_experts_")) == 125_829_120
    assert count(lambda n: n.startswith("l3_shared_")
                 or n == "l3_router_w") == 17_039_360
    assert count(lambda n: not n.startswith("l")
                 or n == "lm_head_w") == 2 * 24576 * 4096 + 4096
    assert sorted(solar_open2.buffer_shapes(cfg)) == [
        f"l{n}_router_bias" for n in range(4)]


def test_flops_per_token_by_layer_kind():
    cfg = _config()
    # q, k, v to 1024 columns, two low-rank pairs 4096 -> 128 -> 1024, beta
    # to 8, three convs of 4 taps, W_o
    assert counts_kda_gqa.kda_proj_flops_per_token(cfg) == 2 * (
        3 * 4096 * 1024 + 2 * (4096 * 128 + 128 * 1024) + 4096 * 8
        + 3 * 4 * 1024 + 1024 * 4096) == 36_265_984
    # 8 heads x 128 x 128 state elements, 7 operations each
    assert counts_kda_gqa.kda_scan_flops_per_token(cfg) == 917_504
    assert counts_kda_gqa.gqa_proj_flops_per_token(cfg) == 2 * (
        3 * 4096 * 1024 + 2 * 4096 * 128) == 27_262_976
    assert counts_kda_gqa.causal_pairs(4096) == 8_390_656
    assert counts_kda_gqa.attend_flops_per_token(cfg, 4096) == (
        4 * 8 * 128 * 2048.5)
    sparse = 2 * 4096 * 320 + 1.2 * 6 * 4096 * 1280
    fwd = counts_kda_gqa.lm_forward_flops_per_token(cfg, 4096, 0.2)
    assert fwd == pytest.approx(
        27_262_976 + 4096 * 2048.5 + 3 * (36_265_984 + 917_504) + 4 * sparse
        + 2 * 4096 * 24576)
    assert fwd == pytest.approx(510_011_392)
    assert counts_kda_gqa.lm_train_flops_per_token(cfg, 4096,
                                                   0.2) == 3 * fwd
    # full buffers, 8 assignments a token
    assert counts_kda_gqa.lm_forward_flops_per_token(
        cfg, 4096, 8.0) == pytest.approx(1.4915e9, rel=1e-4)


def test_the_kernels_counts_are_the_mathematics():
    cfg = _config()
    flops, nbytes = counts_kda_gqa.kda_scan_train_flops_bytes(cfg, 1, 4096)
    assert flops == 3 * 3 * 917_504 * 4096
    # q, k, v, o forward and q, k, v, do, dq, dk, dv backward at 1024, bf16;
    # g forward, g and dg backward at 1024 and beta likewise at 8, float32
    assert nbytes == 3 * 4096 * (2 * 11 * 1024 + 4 * 3 * 1024 + 4 * 3 * 8)
    least, bound = counts.roofline_seconds(flops, nbytes, V5E)
    assert bound == "bytes" and least == pytest.approx(5.238e-4, rel=1e-3)
    flops, nbytes = counts_kda_gqa.flash_train_flops_bytes(cfg, 1, 4096)
    assert flops == 6 * 2 * 8 * 8_390_656 * 128
    assert nbytes == 4096 * 128 * 2 * 6 * (8 + 1)
    flops, nbytes = counts_kda_gqa.moe_experts_train_flops_bytes(cfg, 1024)
    assert flops == 4 * 9 * 2 * 1024 * 4096 * 1280
    weights = 8 * 3 * 4096 * 1280 * 2
    assert nbytes == 4 * (3 * weights + 3 * 1024 * (2 * 4096 + 3 * 1280) * 2)


HLO = '''
ENTRY %main {
  %flash_attention_fwd.3 = (bf16[8,8,8]{2,1,0}, f32[8,8,128]{2,1,0}) custom-call(%c, %q), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/while/body/closed_call/attn.attend.full/flash_attention_fwd/pallas_call" source_file="x.py"}
  %kda-chunk-fwd.2 = (bf16[8,8]{1,0}, f32[8,8]{1,0}) custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/while/body/closed_call/kda.scan/kda-chunk-fwd"}
  %kda-chunk-bwd.5 = (bf16[8,8]{1,0}, f32[8,8]{1,0}) custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/while/body/closed_call/transpose(jvp(kda.scan))/kda-chunk-bwd"}
  %fusion.8 = f32[8,8,128]{2,1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/closed_call/kda.scan/transpose"}
  %fusion.9 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/closed_call/kda.proj/dot_general"}
  %fusion.10 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/closed_call/attn.proj/mul"}
  %fusion.11 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/closed_call/kda.out/mul"}
  %fusion.12 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/closed_call/moe.experts/mul"}
  %ragged-dot-none.4 = bf16[8,8]{1,0} custom-call(%p), custom_call_target="tpu_custom_call"
}
'''


def _traced_ctx(monkeypatch):
    from benchmark import scopes
    monkeypatch.setattr(scopes, "instruction_seconds", lambda path: {
        "flash_attention_fwd.3": 0.03, "kda-chunk-fwd.2": 0.02,
        "kda-chunk-bwd.5": 0.04, "fusion.8": 0.01, "fusion.9": 0.04,
        "fusion.10": 1.0, "fusion.11": 0.02, "fusion.12": 0.1,
        "ragged-dot-none.4": 0.5})
    routing = {"routing": {"local_assignments_per_token": 0.2,
                           "load_max_over_mean": 1.3}}
    return {"kind": "train", "trace_path": "t", "step_hlo": HLO,
            "trace": {"busy0_s": 2.0}, "cfg": _config(), "chips": 1,
            "rows": 1, "seq": 4096, "k": 2, "traced_readings": 3,
            "peaks": V5E, "train_tok_s": 15000.0, "readings": [routing] * 5}


def test_the_new_readers_on_a_recorded_join(monkeypatch):
    ctx = _traced_ctx(monkeypatch)
    read = lambda name: common.load_reader(common.HERE, name)(ctx)  # noqa: E731
    # the kda.* scopes, kernels and the fusion beside them, over the busy
    # time: the accepted reader reads this cell as it reads the sibling's
    assert read("kda_time_pct") == pytest.approx(100 * 0.13 / 2.0)
    # 6 traced steps of the recurrence's least time over the two kernels'
    assert read("kda_exact_scan_roofline") == pytest.approx(
        100 * 6 * 5.238e-4 / 0.06, rel=1e-3)
    flops, nbytes = counts_kda_gqa.flash_train_flops_bytes(
        ctx["cfg"], 1, 4096)
    least, _ = counts.roofline_seconds(flops, nbytes, V5E)
    assert read("kda_gqa_flash_roofline") == pytest.approx(
        100 * 6 * least / 0.03)
    flops, nbytes = counts_kda_gqa.moe_experts_train_flops_bytes(
        ctx["cfg"], 0.2 * 4096)
    least, _ = counts.roofline_seconds(flops, nbytes, V5E)
    assert read("kda_gqa_moe_expert_roofline") == pytest.approx(
        100 * 6 * least / 0.6)
    assert read("kda_gqa_lm_mfu_pct") == pytest.approx(
        100 * 15000 * 3 * 510_011_392 / 197e12, rel=1e-6)
    # the accepted readers this cell is listed under find their sources too
    assert read("moe_time_pct") == pytest.approx(100 * 0.6 / 2.0)
    assert read("moe_local_assign_per_tok") == pytest.approx(0.2)
    assert read("moe_load_max_over_mean") == pytest.approx(1.3)


def test_new_readers_return_nothing_without_their_sources():
    """A parent commit: a step without the scopes, or no trace at all."""
    ctx = {"kind": "train", "readings": [{"seconds": 1.0}], "trace": None,
           "cfg": _config(), "chips": 1, "rows": 1, "seq": 4096, "k": 2,
           "traced_readings": 3, "train_tok_s": 1.0, "peaks": V5E}
    names = ("kda_gqa_lm_mfu_pct", "kda_exact_scan_roofline",
             "kda_gqa_flash_roofline", "kda_gqa_moe_expert_roofline")
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
    assert cell["traffic"] == "s4096_b1_causal_kda_gqa"
    assert len(cell["why"]) <= 200 and "bound train_tok_s" in cell["why"]
    assert "kda_time_pct" in cell["why"] and "guard the scan" in cell["why"]
    spec = cell["traffic_file"]
    assert (spec["batch_per_chip"], spec["seq"], spec["steps_per_reading"],
            spec["feed_ring"]) == (1, 4096, 2, 4)
    assert spec["labels"] == "next_token" and not spec["padded"]
    assert "FIFTH" in spec["why"] and "32,768" in spec["why"]
    listed = {m["name"] for m in cell["per_layer"]}
    assert {"kda_time_pct", "kda_exact_scan_roofline",
            "kda_gqa_flash_roofline", "kda_gqa_moe_expert_roofline",
            "kda_gqa_lm_mfu_pct", "flash_time_pct", "moe_time_pct",
            "optimizer_time_pct", "moe_local_assign_per_tok",
            "moe_load_max_over_mean", "recompute_time_pct",
            "attn_proj_time_pct", "dense_ffn_time_pct"} <= listed
    # (`dense_ffn_time_pct` reads the shared expert's `moe.shared`, as on the
    # other cells with one); no roofline whose counts read another family's keys
    assert not listed & {"kda_scan_roofline",
                         "kda_mla_flash_roofline", "kda_moe_expert_roofline",
                         "kda_lm_mfu_pct", "mfu_pct", "lm_mfu_pct"}
    mine = [m for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "kda_exact_scan_roofline", "kda_gqa_flash_roofline",
        "kda_gqa_moe_expert_roofline", "kda_gqa_lm_mfu_pct"]
    assert manifest["per_layer"][-4:] == mine
    assert manifest["workloads"][-1]["name"] == CELL
    assert len(manifest["workloads"]) == 10
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


# ---------------------------------------------------------------------------
# `correct` at the rehearsal's size: true for the sound program, false with
# the PROGRAM at fault and the reference as it is
# ---------------------------------------------------------------------------

def _rehearse():
    return run.run_cell(CELL, 2147483659, 1.0, 0,
                        rehearsal=rehearse.tiny_presets(CELL))


def _bad(result):
    return {c["name"] for c in result["checks"] if c["value"] > c["limit"]}


def _chunks_start_from_zero(monkeypatch):
    """The carry between chunks left out: every chunk opens on no state."""
    import jax.numpy as jnp
    from paddle_tpu.ops import kda
    monkeypatch.setattr(
        kda, "_carry", lambda u, wc, kendc, decay: jnp.zeros(
            wc.shape[:3] + (wc.shape[-1], u.shape[-1]), jnp.float32))


def _beta_not_doubled(monkeypatch):
    from paddle_tpu.ops import kda
    monkeypatch.setattr(kda, "_beta_of", lambda attrs: kda._beta)


def _bounded_gate(monkeypatch):
    """The sibling family's bounded gate in the unbounded one's place."""
    from paddle_tpu.models import solar
    inner = solar.layers.kda_gate
    monkeypatch.setattr(
        solar.layers, "kda_gate",
        lambda x, a_log, dt_bias: inner(x, a_log, dt_bias, -5.0))


def _no_gates(monkeypatch):
    """Both element-wise output gates left out."""
    from paddle_tpu.models import solar
    monkeypatch.setattr(solar.layers, "head_gate", lambda x, gate: x)


def test_sound_run_is_correct():
    sound = _rehearse()
    assert sound["correct"] and not sound["failed"], sound
    # the driver's own comparison ran: the first moments as vectors too,
    # and the least log decay is in checks.json and in the gauge
    assert "moment1_dir_gap" in {c["name"] for c in sound["checks"]}
    from paddle_tpu.observability import metrics
    with open(os.path.join(common.out_dir(CELL, 2147483659, 0),
                           "checks.json")) as f:
        kept = json.load(f)["reference"]
    assert kept["min_log_decay"] < 0
    assert metrics.get("kda.min_log_decay") == kept["min_log_decay"]
    assert "moment1_vectors" not in kept and "first_route" not in kept


def test_run_and_calibrate_are_bound_to_this_modules_names():
    from benchmark.drivers import train_kda_gqa_lm as driver
    for fn in (driver.run, driver.calibrate):
        assert fn.__globals__["compare_lm"] is driver.compare_lm
        assert fn.__globals__["Trainer"] is driver.Trainer
    assert driver.calibrate.__globals__["faults"] is driver.faults
    assert sorted(driver.faults(_config(), 4096)) == [
        "attn_gate_left_out", "beta_unscaled", "bounded_gate"]


@pytest.mark.parametrize("fault", [
    _chunks_start_from_zero, _beta_not_doubled, _bounded_gate, _no_gates],
    ids=lambda f: f.__name__.strip("_"))
def test_each_fault_of_the_program_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    result = _rehearse()
    assert not result["correct"] and _bad(result) & {
        "loss_gap_step1", "loss_gap_step2", "moment1_gap", "delta_gap",
        "route_mismatch_share", "moment1_dir_gap"}, result["checks"]


def test_steep_decays_tell_the_scans_two_forms_apart():
    """`calibrate`'s control `steep_decay`, at the rehearsal's size: with
    `dt_bias` drawn so that some channels' g lies far below -5.5 a token the
    program as built holds the cell's limits, and with `kda_scan` handed the
    sibling's bound (the form around the blocks' running sums) it overflows,
    which no limit holds."""
    from benchmark.drivers import train_kda_gqa_lm as driver
    cell, _, _ = common.open_cell(CELL, tiny=rehearse.tiny_presets(CELL))
    limits = cell["traffic_file"]["limits"]

    def holds(gaps):
        return all(v <= limits["loss_gap" if k.startswith("loss") else k]
                   for k, v in gaps.items())

    row = driver.steep_decay(cell, 2147483659)
    assert row["min_log_decay"] < -40
    assert holds(row["exact"]), row["exact"]
    assert not holds(row["bounded_scan"]), row["bounded_scan"]


def test_the_drivers_faults_move_the_reference():
    """`calibrate`'s fault rows, at the rehearsal's size: the reference with
    each fault against the sound reference, the fp8 control and a quarter of
    the row left out; each fails by at least one limit."""
    from benchmark import lm_traffic
    from benchmark.drivers import train_kda_gqa_lm as driver, train_lm
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
        Stub.ref.vector_leaves(cfg))

    def fails(gaps):
        return not all(v <= limits["loss_gap" if k.startswith("loss") else k]
                       for k, v in gaps.items())

    for name, wrong in driver.faults(cfg, spec["seq"]).items():
        gaps = driver.compare_lm(
            train_lm.run_reference(Stub, host, cfg=wrong), sound)
        assert fails(gaps), (name, gaps)
    fp8 = driver.compare_lm(train_lm.run_reference(Stub, host, "fp8"), sound)
    assert fails(fp8), fp8
    assert train_lm._quarter_left_out(Stub, host, sound) > 10 * limits[
        "loss_gap"]
