"""What the window / grouped-heads causal-LM cell brings: its file against
the published numbers, its counts against hand-worked numbers, its readers
without their sources, and `correct` shown to fail under each fault the
new mechanisms admit, at the rehearsal's size."""
import json
import os

import numpy as np
import pytest

from benchmark import (attn_scopes, common, counts, counts_window_gqa, peaks,
                       rehearse, run)

CELL = "mellum2_12b_ep4_s8192"
V5E = peaks.device_peaks("TPU v5 lite")


def _config():
    with open(os.path.join(common.HERE, "configs",
                           "mellum2_12b_ep4.json")) as f:
        return json.load(f)


def test_the_file_keeps_the_published_numbers_and_states_the_cut():
    cfg = _config()
    for key, value in cfg["published"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["layers", "num_experts", "vocab"]
    assert cfg["published"]["num_experts"] == cfg["experts_total"] == 64
    assert (cfg["layers"], cfg["num_experts"], cfg["vocab"]) == (4, 16, 24576)
    assert cfg["vocab"] * 4 == cfg["published"]["vocab_size"]
    assert cfg["num_experts"] * 4 == cfg["experts_total"]
    assert "4 chips share each layer" in cfg["deployment"]
    assert set(cfg["reduced"]) <= set(cfg["assumed"])
    # one whole period: three sliding layers and a full one, all sparse
    assert cfg["layer_types"][:4] == ["sliding_attention"] * 3 + [
        "full_attention"]
    assert len(cfg["layer_types"]) == len(cfg["mlp_layer_types"]) == 28
    assert set(cfg["mlp_layer_types"]) == {"sparse"}
    from benchmark.reference import mellum2
    shapes = mellum2.param_shapes(cfg)
    count = lambda keep: sum(  # noqa: E731
        int(np.prod(s)) for n, s in shapes.items() if keep(n))
    assert count(lambda n: True) == 595_153_152          # 595.15 M
    assert count(lambda n: n.startswith("l2_")) == 120_476_160
    assert count(lambda n: n.startswith("l3_")
                 and "experts" not in n) == 21_385_728
    assert count(lambda n: "experts" in n
                 and n.startswith("l0_")) == 16 * 3 * 2304 * 896
    assert count(lambda n: not n.startswith("l")
                 or n == "lm_head_w") == 2 * 24576 * 2304 + 2304
    assert not mellum2.buffer_shapes(cfg)


def test_pairs_inside_a_window():
    assert counts_window_gqa.attend_pairs(8192) == 33_558_528
    # rows 0..1023 see 1..1024 keys, the other 7,168 rows 1,024 each
    assert counts_window_gqa.attend_pairs(8192, 1024) == 524_800 + 7_340_032
    assert counts_window_gqa.attend_pairs(8192, 1024) / 33_558_528 == (
        pytest.approx(0.2344, abs=1e-4))
    assert counts_window_gqa.attend_pairs(4096, 1024) / (
        counts_window_gqa.attend_pairs(4096)) == pytest.approx(0.4374,
                                                               abs=1e-4)
    assert counts_window_gqa.attend_pairs(512, 1024) == 512 * 513 // 2
    assert counts_window_gqa.attend_pairs(5, 2) == 1 + 2 + 2 + 2 + 2


def test_flops_per_token_of_the_cut_model():
    cfg = _config()
    assert counts_window_gqa.gqa_proj_flops_per_token(cfg) == 2 * (
        9_437_184 + 2 * 1_179_648 + 9_437_184) == 42_467_328
    # QK^T and PV, 32 heads of 128: 16,384 operations a pair
    full = counts_window_gqa.attend_flops_per_token(cfg, 8192,
                                                    "full_attention")
    window = counts_window_gqa.attend_flops_per_token(cfg, 8192,
                                                      "sliding_attention")
    assert full == 16384 * 33_558_528 / 8192 == 16384 * 4096.5
    assert window == 16384 * 7_864_832 / 8192
    fwd = counts_window_gqa.lm_forward_flops_per_token(cfg, 8192, 2.0)
    sparse = 2 * 2304 * 64 + 2.0 * 6 * 2304 * 896
    assert fwd == pytest.approx(4 * (42_467_328 + sparse) + 3 * window + full
                                + 2 * 2304 * 24576)
    assert fwd == pytest.approx(497.69e6, rel=1e-4)
    assert counts_window_gqa.lm_train_flops_per_token(cfg, 8192, 2.0) == (
        3 * fwd)


def test_flash_by_kind_and_the_grouped_matmuls():
    cfg = _config()
    flops, nbytes = counts_window_gqa.flash_train_flops_bytes(
        cfg, 1, 8192, "full_attention")
    assert flops == 6 * 2 * 32 * 33_558_528 * 128
    # q, o, q, o, dO, dq at 32 heads; k, v, k, v, dk, dv at 4
    assert nbytes == 8192 * 128 * 2 * 6 * (32 + 4)
    least, bound = counts.roofline_seconds(flops, nbytes, V5E)
    assert bound == "flops" and least == pytest.approx(8.373e-3, rel=1e-3)
    wflops, wbytes = counts_window_gqa.flash_train_flops_bytes(
        cfg, 1, 8192, "sliding_attention")
    assert wflops == 3 * 6 * 2 * 32 * 7_864_832 * 128
    assert wbytes == 3 * nbytes
    assert wflops / (3 * flops) == pytest.approx(0.2344, abs=1e-4)
    flops, nbytes = counts_window_gqa.moe_experts_train_flops_bytes(
        cfg, 16384)
    assert flops == 4 * 9 * 2 * 16384 * 2304 * 896
    weights = 16 * 3 * 2304 * 896 * 2
    assert nbytes == 4 * (3 * weights + 3 * 16384 * (2 * 2304 + 3 * 896) * 2)


HLO = '''
ENTRY %main {
  %flash_attention_fwd.3 = (bf16[32,8,8]{2,1,0}, f32[32,8,128]{2,1,0}) custom-call(%c, %q), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/while/body/closed_call/attn.attend.window/flash_attention_fwd/pallas_call" source_file="x.py"}
  %flash_attention_bwd_dkdv.1 = (f32[4,8,8]{2,1,0}, f32[4,8,8]{2,1,0}) custom-call(%c, %q), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/while/body/closed_call/attn.attend.full/flash_attention_bwd_dkdv/pallas_call"}
  %fusion.7 = f32[32,8,128]{2,1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/closed_call/attn.attend.window/broadcast_in_dim"}
  %fusion.9 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/closed_call/attn.proj/mul"}
}
'''


def _traced_ctx(monkeypatch):
    from benchmark import scopes
    monkeypatch.setattr(scopes, "instruction_seconds", lambda path: {
        "flash_attention_fwd.3": 0.03, "flash_attention_bwd_dkdv.1": 0.02,
        "fusion.7": 0.005, "fusion.9": 1.0})
    return {"kind": "train", "trace_path": "t", "step_hlo": HLO,
            "trace": {"busy0_s": 2.0}, "cfg": _config(), "chips": 1,
            "rows": 1, "seq": 8192, "k": 2, "traced_readings": 3,
            "peaks": V5E}


def test_the_kernels_of_one_kind_of_layer_are_told_apart(monkeypatch):
    ctx = _traced_ctx(monkeypatch)
    assert attn_scopes.flash_seconds_under(
        ctx, "attn.attend.window") == pytest.approx(0.03)
    assert attn_scopes.flash_seconds_under(
        ctx, "attn.attend.full") == pytest.approx(0.02)
    assert attn_scopes.flash_seconds_under(ctx, "mla.attend") is None
    read = lambda name: common.load_reader(common.HERE, name)(ctx)  # noqa: E731
    wflops, _ = counts_window_gqa.flash_train_flops_bytes(
        ctx["cfg"], 1, 8192, "sliding_attention")
    assert read("window_flash_roofline") == pytest.approx(
        100 * 6 * wflops / 197e12 / 0.03)
    assert read("full_flash_roofline") == pytest.approx(
        100 * 6 * 8.373e-3 / 0.02, rel=1e-3)
    # the kernels and what the op lowers around them, over the busy time
    assert read("window_attn_time_pct") == pytest.approx(100 * 0.035 / 2.0)


def test_new_readers_return_nothing_without_their_sources():
    """A parent commit: a step without the scopes, or no trace at all."""
    ctx = {"kind": "train", "readings": [{"seconds": 1.0}], "trace": None,
           "cfg": _config(), "chips": 1, "rows": 1, "seq": 8192, "k": 2,
           "traced_readings": 3, "train_tok_s": 1.0, "peaks": V5E}
    names = ("gqa_lm_mfu_pct", "window_flash_roofline", "full_flash_roofline",
             "window_attn_time_pct", "gqa_moe_expert_roofline")
    for name in names:
        assert common.load_reader(common.HERE, name)(dict(ctx)) is None, name
    bare = dict(ctx, trace={"busy0_s": 1.0}, trace_path="t",
                step_hlo="ENTRY %main {\n}\n", _instr_seconds={"fusion.1": 1.0},
                _instr_scopes={})
    for name in names[1:]:
        assert common.load_reader(common.HERE, name)(dict(bare)) is None, name


def test_mfu_and_expert_readers_use_the_assignments_that_fell_here(
        monkeypatch):
    routing = {"routing": {"local_assignments_per_token": 2.0,
                           "load_max_over_mean": 1.2}}
    ctx = dict(_traced_ctx(monkeypatch), train_tok_s=25000.0,
               readings=[routing] * 5)
    got = common.load_reader(common.HERE, "gqa_lm_mfu_pct")(ctx)
    assert got == pytest.approx(100 * 25000 * 3 * 497.69e6 / 197e12, rel=1e-4)
    from benchmark import scopes
    monkeypatch.setattr(scopes, "instruction_seconds", lambda path: {
        "ragged-dot-none.4": 0.6, "fusion.9": 1.0})
    ctx = dict(ctx, _unused=None)
    ctx.pop("_instr_seconds", None)
    flops, nbytes = counts_window_gqa.moe_experts_train_flops_bytes(
        ctx["cfg"], 16384)
    least, _ = counts.roofline_seconds(flops, nbytes, V5E)
    assert common.load_reader(common.HERE, "gqa_moe_expert_roofline")(
        ctx) == pytest.approx(100 * 6 * least / 0.6)


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


def _window_ignored(monkeypatch, mellum):
    real = mellum.layers.fused_attention
    monkeypatch.setattr(
        mellum.layers, "fused_attention",
        lambda q, k, v, **kw: real(q, k, v, **dict(kw, window=None)))


def _yarn_left_out(monkeypatch, mellum):
    real = mellum.layers.rotary_embedding
    monkeypatch.setattr(
        mellum.layers, "rotary_embedding",
        lambda t, theta, layout, **kw: real(t, theta=theta, layout=layout))


def _sigmoid_scores(monkeypatch, mellum):
    real = mellum.layers.routed_moe
    monkeypatch.setattr(
        mellum.layers, "routed_moe",
        lambda *a, **kw: real(*a, **dict(kw, scoring="sigmoid")))


def _kv_head_0_for_all(monkeypatch, mellum):
    real = mellum.layers.fused_attention

    def first_head(q, k, v, **kw):
        one = [mellum.layers.slice(t, [1], [0], [1]) for t in (k, v)]
        return real(q, *one, **kw)

    monkeypatch.setattr(mellum.layers, "fused_attention", first_head)


def test_sound_run_is_correct():
    sound = _rehearse()
    assert sound["correct"] and not sound["failed"], sound


@pytest.mark.parametrize("fault", [
    _window_ignored, _yarn_left_out, _sigmoid_scores, _kv_head_0_for_all],
    ids=lambda f: f.__name__.strip("_"))
def test_each_fault_of_the_program_is_not_correct(fault, monkeypatch):
    from paddle_tpu.models import mellum
    fault(monkeypatch, mellum)
    result = _rehearse()
    assert not result["correct"] and _bad(result) & {
        "loss_gap_step1", "loss_gap_step2", "moment1_gap", "delta_gap",
        "route_mismatch_share"}, result["checks"]


def test_the_drivers_faults_move_the_reference_past_the_limits():
    """`calibrate`'s fault rows, at the rehearsal's size: the reference with
    each fault against the sound reference; and the fp8 control and a
    quarter of the row left out."""
    from benchmark import lm_traffic
    from benchmark.drivers import train_gqa_lm, train_lm
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

    wrongs = train_gqa_lm.faults(cfg, Stub.seq)
    assert sorted(wrongs) == ["kv_head_0_for_all", "sigmoid_scores",
                              "window_ignored", "yarn_left_out"]
    for name, wrong in wrongs.items():
        gaps = train_lm.compare_lm(
            train_lm.run_reference(Stub, host, cfg=wrong), sound)
        assert fails(gaps), (name, gaps)
    assert fails(train_lm.compare_lm(
        train_lm.run_reference(Stub, host, "fp8"), sound))
    assert train_lm._quarter_left_out(Stub, host, sound) > 10 * limits[
        "loss_gap"]
