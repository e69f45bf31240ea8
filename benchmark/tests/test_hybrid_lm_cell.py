"""What the hybrid state-space / expert / attention cell brings: its file
against the published numbers, its counts against hand-worked numbers, its
readers with and without their sources, and `correct` shown to fail under
faults of the new mechanisms, at the rehearsal's size."""
import json
import os

import numpy as np
import pytest

from benchmark import (common, counts, counts_hybrid_ssm, peaks, rehearse,
                       run)

CELL = "nemotron_twotower_30b_a3b_ep16_s8192"
V5E = peaks.device_peaks("TPU v5 lite")


def _config():
    with open(os.path.join(common.HERE, "configs",
                           "nemotron_twotower_30b_a3b_ep16.json")) as f:
        return json.load(f)


def test_the_file_keeps_the_published_numbers_and_states_the_cut():
    cfg = _config()
    for key, value in cfg["published"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["layers", "n_routed_experts", "vocab"]
    assert cfg["published"]["n_routed_experts"] == cfg["experts_total"] == 128
    assert (cfg["layers"], cfg["n_routed_experts"], cfg["vocab"]) == (
        9, 8, 16384)
    assert cfg["vocab"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["n_routed_experts"] * 16 == cfg["experts_total"]
    assert "16 chips share each layer" in cfg["deployment"]
    assert set(cfg["reduced"]) <= set(cfg["assumed"])
    assert "denoiser" in cfg["assumed"]["towers"]
    pattern = cfg["hybrid_override_pattern"]
    assert len(pattern) == cfg["num_hidden_layers"] == 52
    assert [pattern.count(k) for k in "ME*"] == [23, 23, 6]
    assert counts_hybrid_ssm.layer_kinds(cfg) == "MEMEM*EME"
    from benchmark.reference import nemotron_h
    shapes = nemotron_h.param_shapes(cfg)
    count = lambda keep: sum(  # noqa: E731
        int(np.prod(s)) for n, s in shapes.items() if keep(n))
    assert count(lambda n: True) == 666_962_944          # 667.0 M
    assert count(lambda n: n.startswith("l0_")) == 38_744_896
    assert count(lambda n: n.startswith("l1_")
                 and "experts" not in n) == 20_302_464
    assert count(lambda n: n.startswith("l1_experts")) == 8 * 9_977_856
    assert count(lambda n: n.startswith("l5_")) == 23_399_040
    assert count(lambda n: not n.startswith("l")
                 or n == "lm_head_w") == 2 * 16384 * 2688 + 2688
    assert sorted(nemotron_h.buffer_shapes(cfg)) == [
        f"l{n}_router_bias" for n in (1, 3, 6, 8)]


def test_flops_per_token_by_layer_kind():
    cfg = _config()
    # [z 4096 | x 4096 | B 1024 | C 1024 | dt 64] = 10,304 columns
    assert counts_hybrid_ssm.ssm_proj_flops_per_token(cfg) == 2 * (
        2688 * 10304 + 4 * 6144 + 4096 * 2688) == 77_463_552
    # 64 heads x 64 x 128 state elements, 5 operations each
    assert counts_hybrid_ssm.ssm_scan_flops_per_token(cfg) == 2_621_440
    assert counts_hybrid_ssm.attn_proj_flops_per_token(cfg) == 2 * (
        2688 * 4096 + 2 * 2688 * 256 + 4096 * 2688) == 46_792_704
    assert counts_hybrid_ssm.attend_pairs(8192) == 33_558_528
    assert counts_hybrid_ssm.attend_flops_per_token(cfg, 8192) == (
        16384 * 4096.5)
    kind = counts_hybrid_ssm.kind_forward_flops_per_token
    assert kind(cfg, 8192, "M", 0.375) == 77_463_552 + 2_621_440
    assert kind(cfg, 8192, "E", 0.375) == (
        2 * 2688 * 128 + 4 * 2688 * 3712 + 0.375 * 4 * 2688 * 1856)
    assert kind(cfg, 8192, "*", 0.375) == 46_792_704 + 16384 * 4096.5
    with pytest.raises(ValueError):
        kind(cfg, 8192, "-", 0.375)
    fwd = counts_hybrid_ssm.lm_forward_flops_per_token(cfg, 8192, 0.375)
    assert fwd == pytest.approx(
        4 * kind(cfg, 8192, "M", 0.375) + 4 * kind(cfg, 8192, "E", 0.375)
        + kind(cfg, 8192, "*", 0.375) + 2 * 2688 * 16384)
    assert fwd == pytest.approx(714.66e6, rel=1e-4)
    assert counts_hybrid_ssm.lm_train_flops_per_token(
        cfg, 8192, 0.375) == 3 * fwd


def test_the_scan_is_counted_as_the_recurrence_and_is_bound_by_bytes():
    cfg = _config()
    flops, nbytes = counts_hybrid_ssm.ssm_scan_train_flops_bytes(cfg, 1, 8192)
    assert flops == 4 * 3 * 2_621_440 * 8192
    # x, y forward and x, dy, dx backward at 4096; B, C forward and B, C,
    # dB, dC backward at 1024, bf16; dt forward, dt and ddt backward, f32
    assert nbytes == 4 * 8192 * (2 * (5 * 4096 + 6 * 1024) + 4 * 3 * 64)
    least, bound = counts.roofline_seconds(flops, nbytes, V5E)
    assert bound == "bytes" and least == pytest.approx(2.161e-3, rel=1e-3)
    flops, nbytes = counts_hybrid_ssm.flash_train_flops_bytes(cfg, 1, 8192)
    assert flops == 6 * 2 * 32 * 33_558_528 * 128
    assert nbytes == 8192 * 128 * 2 * 6 * (32 + 2)
    flops, nbytes = counts_hybrid_ssm.moe_experts_train_flops_bytes(cfg, 3072)
    assert flops == 4 * 6 * 2 * 3072 * 2688 * 1856
    weights = 8 * 2 * 2688 * 1856 * 2
    assert nbytes == 4 * (3 * weights + 3 * 3072 * (2 * 2688 + 2 * 1856) * 2)


HLO = '''
ENTRY %main {
  %flash_attention_fwd.3 = (bf16[32,8,8]{2,1,0}, f32[32,8,128]{2,1,0}) custom-call(%c, %q), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/while/body/closed_call/attn.attend.full/flash_attention_fwd/pallas_call" source_file="x.py"}
  %fusion.7 = f32[32,8,128]{2,1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/closed_call/ssm.scan/ssm.scan.intra/dot_general"}
  %fusion.8 = f32[32,8,128]{2,1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/closed_call/transpose(jvp(ssm.scan))/ssm.scan.carry/mul"}
  %fusion.9 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/closed_call/ssm.in_proj/dot_general"}
  %fusion.10 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/closed_call/attn.proj/mul"}
  %ragged-dot-none.4 = bf16[8,8]{1,0} custom-call(%p), custom_call_target="tpu_custom_call"
}
'''


def _traced_ctx(monkeypatch):
    from benchmark import scopes
    monkeypatch.setattr(scopes, "instruction_seconds", lambda path: {
        "flash_attention_fwd.3": 0.03, "fusion.7": 0.05, "fusion.8": 0.01,
        "fusion.9": 0.04, "fusion.10": 1.0, "ragged-dot-none.4": 0.6})
    routing = {"routing": {"local_assignments_per_token": 0.375,
                           "load_max_over_mean": 1.2}}
    return {"kind": "train", "trace_path": "t", "step_hlo": HLO,
            "trace": {"busy0_s": 2.0}, "cfg": _config(), "chips": 1,
            "rows": 1, "seq": 8192, "k": 2, "traced_readings": 3,
            "peaks": V5E, "train_tok_s": 12000.0, "readings": [routing] * 5}


def test_the_new_readers_on_a_recorded_join(monkeypatch):
    ctx = _traced_ctx(monkeypatch)
    read = lambda name: common.load_reader(common.HERE, name)(ctx)  # noqa: E731
    # the five ssm.* scopes, forward and backward, over the busy time
    assert read("ssm_time_pct") == pytest.approx(100 * 0.10 / 2.0)
    # 6 traced steps of the recurrence's least time over `ssm.scan`'s
    assert read("ssm_scan_roofline") == pytest.approx(
        100 * 6 * 2.161e-3 / 0.06, rel=1e-3)
    flops, nbytes = counts_hybrid_ssm.flash_train_flops_bytes(
        ctx["cfg"], 1, 8192)
    least, _ = counts.roofline_seconds(flops, nbytes, V5E)
    assert read("hybrid_flash_roofline") == pytest.approx(
        100 * 6 * least / 0.03)
    flops, nbytes = counts_hybrid_ssm.moe_experts_train_flops_bytes(
        ctx["cfg"], 0.375 * 8192)
    least, _ = counts.roofline_seconds(flops, nbytes, V5E)
    assert read("hybrid_moe_expert_roofline") == pytest.approx(
        100 * 6 * least / 0.6)
    assert read("hybrid_lm_mfu_pct") == pytest.approx(
        100 * 12000 * 3 * 714.66e6 / 197e12, rel=1e-4)
    # the accepted readers this cell is listed under find their sources too
    assert read("moe_time_pct") == pytest.approx(100 * 0.6 / 2.0)
    assert read("moe_local_assign_per_tok") == pytest.approx(0.375)
    assert read("moe_load_max_over_mean") == pytest.approx(1.2)


def test_new_readers_return_nothing_without_their_sources():
    """A parent commit: a step without the scopes, or no trace at all."""
    ctx = {"kind": "train", "readings": [{"seconds": 1.0}], "trace": None,
           "cfg": _config(), "chips": 1, "rows": 1, "seq": 8192, "k": 2,
           "traced_readings": 3, "train_tok_s": 1.0, "peaks": V5E}
    names = ("hybrid_lm_mfu_pct", "ssm_time_pct", "ssm_scan_roofline",
             "hybrid_flash_roofline", "hybrid_moe_expert_roofline")
    for name in names:
        assert common.load_reader(common.HERE, name)(dict(ctx)) is None, name
    bare = dict(ctx, trace={"busy0_s": 1.0}, trace_path="t",
                step_hlo="ENTRY %main {\n}\n", _instr_seconds={"fusion.1": 1.0},
                _instr_scopes={})
    for name in names[1:]:
        assert common.load_reader(common.HERE, name)(dict(bare)) is None, name


def test_the_cell_is_listed_where_the_manifest_says():
    cell = common.find_cell(common.load_manifest(), CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "s8192_b1_causal_hybrid"
    spec = cell["traffic_file"]
    assert (spec["batch_per_chip"], spec["seq"], spec["steps_per_reading"],
            spec["feed_ring"]) == (1, 8192, 2, 4)
    assert spec["labels"] == "next_token" and not spec["padded"]
    listed = {m["name"] for m in cell["per_layer"]}
    assert {"ssm_time_pct", "ssm_scan_roofline", "hybrid_moe_expert_roofline",
            "hybrid_flash_roofline", "hybrid_lm_mfu_pct", "flash_time_pct",
            "moe_time_pct", "optimizer_time_pct", "moe_local_assign_per_tok",
            "moe_load_max_over_mean"} <= listed
    assert not listed & {"mfu_pct", "lm_mfu_pct", "gqa_lm_mfu_pct",
                         "mla_flash_roofline", "window_flash_roofline"}


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
    from paddle_tpu.ops import ssm
    monkeypatch.setattr(ssm, "_carry",
                        lambda states, total: jnp.zeros_like(states))


def _relu_not_squared(monkeypatch):
    from paddle_tpu.models import nemotron_h
    monkeypatch.setattr(nemotron_h.layers, "relu2", nemotron_h.layers.relu)


def _kv_head_0_for_all(monkeypatch):
    from paddle_tpu.models import nemotron_h
    real = nemotron_h.layers.fused_attention

    def first_head(q, k, v, **kw):
        one = [nemotron_h.layers.slice(t, [1], [0], [1]) for t in (k, v)]
        return real(q, *one, **kw)

    monkeypatch.setattr(nemotron_h.layers, "fused_attention", first_head)


def test_sound_run_is_correct():
    sound = _rehearse()
    assert sound["correct"] and not sound["failed"], sound


@pytest.mark.parametrize("fault", [
    _chunks_start_from_zero, _relu_not_squared, _kv_head_0_for_all],
    ids=lambda f: f.__name__.strip("_"))
def test_each_fault_of_the_program_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    result = _rehearse()
    assert not result["correct"] and _bad(result) & {
        "loss_gap_step1", "loss_gap_step2", "moment1_gap", "delta_gap",
        "route_mismatch_share"}, result["checks"]


def test_the_drivers_faults_move_the_reference():
    """`calibrate`'s fault rows, at the rehearsal's size: the reference with
    each fault against the sound reference, the fp8 control and a quarter of
    the row left out. A quarter of the scan's output left out, fp8 and a
    quarter of the row fail; the two bf16 roundings do NOT, here as on the
    chip: the comparison is of per-leaf norms, which an unbiased rounding
    moves by its square (PERF.md sections 6 and 7, PR 32)."""
    from benchmark import lm_traffic
    from benchmark.drivers import train_hybrid_lm, train_lm
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

    wrongs = train_hybrid_lm.faults(cfg)
    assert sorted(wrongs) == ["float32_parts_in_bf16", "scan_states_bf16",
                              "ssm_quarter_left_out"]
    gaps = {name: train_lm.compare_lm(
        train_lm.run_reference(Stub, host, cfg=wrong), sound)
        for name, wrong in wrongs.items()}
    assert fails(gaps["ssm_quarter_left_out"]), gaps
    for name in ("scan_states_bf16", "float32_parts_in_bf16"):
        assert not fails(gaps[name]) and gaps[name]["moment1_gap"] > 0, gaps
    assert fails(train_lm.compare_lm(
        train_lm.run_reference(Stub, host, "fp8"), sound))
    assert train_lm._quarter_left_out(Stub, host, sound) > 10 * limits[
        "loss_gap"]
