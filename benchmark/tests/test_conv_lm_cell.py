"""What the short-convolution causal-LM cell brings: its file against the
published numbers, its counts against hand-worked numbers, its readers with
and without their sources, and `correct` shown to fail under each fault the
new mechanisms admit, at the rehearsal's size."""
import json
import os

import numpy as np
import pytest

from benchmark import (common, counts, counts_conv_gqa, counts_window_gqa,
                       peaks, rehearse, run)

CELL = "lfm2_24b_a2b_ep8_s8192"
V5E = peaks.device_peaks("TPU v5 lite")
CAUSAL = 33_558_528                     # pairs of a row of 8,192


def _config():
    with open(os.path.join(common.HERE, "configs",
                           "lfm2_24b_a2b_ep8.json")) as f:
        return json.load(f)


def test_the_file_keeps_the_published_numbers_and_states_the_cut():
    cfg = _config()
    for key, value in cfg["published"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["layers", "num_experts", "vocab"]
    assert cfg["published"]["num_experts"] == cfg["experts_total"] == 64
    assert (cfg["first_layer"], cfg["layers"], cfg["num_experts"],
            cfg["vocab"], cfg["expert_offset"]) == (1, 7, 8, 8192, 0)
    assert cfg["vocab"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["num_experts"] * 8 == cfg["experts_total"]
    assert "8 chips share each layer" in cfg["deployment"]
    kinds = cfg["published"]["layer_types"]
    assert len(kinds) == 40 and kinds.count("conv") == 30
    assert [n for n, k in enumerate(kinds) if k != "conv"] == list(
        range(2, 40, 4))
    assert counts_conv_gqa.layer_kinds(cfg) == [
        ("conv", False), ("full_attention", True), ("conv", True),
        ("conv", True), ("conv", True), ("full_attention", True),
        ("conv", True)]
    assert set(cfg["reduced"]) <= set(cfg["assumed"])
    assert cfg["head_dim"] == cfg["hidden_size"] // cfg["num_attention_heads"]
    for said in ("head_dim", "tied_head", "final_norm", "conv", "qk_norm",
                 "rope", "kv_head_rule", "router", "expert_bias",
                 "objective", "optimizer", "recompute",
                 "max_position_embeddings"):
        assert cfg["assumed"][said], said
    assert "1e-6" in cfg["assumed"]["router"]
    assert "not built" in cfg["assumed"]["expert_bias"]
    assert cfg["assumed"]["recompute"] is True
    from benchmark.reference import lfm2
    shapes = lfm2.param_shapes(cfg)
    count = lambda keep: sum(  # noqa: E731
        int(np.prod(s)) for n, s in shapes.items() if keep(n))
    assert count(lambda n: True) == 647_819_520              # 647.8 M
    assert count(lambda n: n.startswith("l1_")) == 89_139_200
    assert count(lambda n: n.startswith("l2_")) == 86_118_528
    assert count(lambda n: n.startswith("l3_")) == 92_416_000
    assert count(lambda n: n.startswith("l3_conv")) == 16_783_360
    assert count(lambda n: n.startswith("l6_") and "experts" not in n
                 and "router" not in n and "ffn" not in n
                 and "operator" not in n) == 10_485_888
    assert count(lambda n: not n.startswith("l")) == 8192 * 2048 + 2048
    assert "lm_head_w" not in shapes
    assert lfm2.buffer_shapes(cfg) == {
        f"l{n}_router_bias": (64,) for n in range(2, 8)}


def test_flops_per_token_of_the_cut_model():
    cfg = _config()
    assert counts_conv_gqa.conv_proj_flops_per_token(cfg) == 2 * (
        12_582_912 + 4_194_304) == 33_554_432
    assert counts_conv_gqa.conv_mix_flops_per_token(cfg) == 7 * 2048
    assert counts_conv_gqa.gqa_proj_flops_per_token(cfg) == 2 * (
        4_194_304 + 2 * 1_048_576 + 4_194_304) == 20_971_520
    assert counts_conv_gqa.gqa_proj_flops_per_token(cfg) == (
        counts_window_gqa.gqa_proj_flops_per_token(cfg))
    # QK^T and PV, 32 heads of 64: 8,192 operations a causal pair
    assert counts_conv_gqa.attend_flops_per_token(cfg, 8192) == (
        8192 * 4096.5) == 33_558_528
    assign = 0.5
    dense = 6 * 2048 * 11776
    sparse = 2 * 2048 * 64 + assign * 6 * 2048 * 1536
    mixer = 33_554_432 + 7 * 2048
    want = (mixer + dense + 2 * (20_971_520 + 33_558_528 + sparse)
            + 4 * (mixer + sparse) + 2 * 2048 * 8192)
    assert counts_conv_gqa.lm_forward_flops_per_token(cfg, 8192, assign) == (
        pytest.approx(want))
    assert want == pytest.approx(513.4e6, rel=1e-3)          # 513 M
    step = counts_conv_gqa.lm_train_flops_per_token(cfg, 8192, assign) * 8192
    assert step == pytest.approx(12.62e12, rel=1e-3)         # 12.6 T a step


def test_the_mix_the_attention_and_the_experts_by_their_bytes_and_pairs():
    cfg = _config()
    assert counts_conv_gqa.conv_mix_bytes_per_token(cfg) == 22 * 2048 == 45056
    flops, nbytes = counts_conv_gqa.conv_mix_train_flops_bytes(cfg, 1, 8192)
    assert nbytes == 5 * 8192 * 45056            # 369 MB a layer a step
    least, bound = counts.roofline_seconds(flops, nbytes, V5E)
    assert bound == "bytes" and least == pytest.approx(2.253e-3, rel=1e-3)
    flops, nbytes = counts_conv_gqa.flash_train_flops_bytes(cfg, 1, 8192)
    assert flops == 2 * 6 * 2 * 32 * 64 * CAUSAL
    assert nbytes == 2 * 8192 * 64 * 2 * 6 * (32 + 8)
    least, bound = counts.roofline_seconds(flops, nbytes, V5E)
    assert bound == "flops"
    # the same count as the accepted one of a full layer at these heads
    same = counts_window_gqa.flash_train_flops_bytes(
        dict(cfg, layers=2, layer_types=["full_attention"] * 2), 1, 8192,
        "full_attention")
    assert (flops, nbytes) == same
    flops, nbytes = counts_conv_gqa.moe_experts_train_flops_bytes(cfg, 4096.0)
    assert flops == 6 * 9 * 2 * 4096 * 2048 * 1536
    weights = 8 * 3 * 2048 * 1536 * 2
    assert nbytes == 6 * (3 * weights + 3 * 4096 * (2 * 2048 + 3 * 1536) * 2)


HLO = '''
ENTRY %main {
  %flash_attention_fwd.3 = (bf16[32,8,8]{2,1,0}, f32[32,8,128]{2,1,0}) custom-call(%c, %q), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/while/body/checkpoint/attn.attend.full/flash_attention_fwd/pallas_call" source_file="x.py"}
  %fusion.4 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/checkpoint/attn.attend.full/transpose"}
  %fusion.5 = bf16[1,8,8]{2,1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/checkpoint/conv.mix/mul"}
  %fusion.6 = bf16[1,8,24]{2,1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/transpose(jvp(checkpoint))/conv.mix/concatenate"}
  %fusion.7 = bf16[8,24]{1,0} fusion(%p), kind=kOutput, metadata={op_name="jit(s)/while/body/checkpoint/conv.in_proj/dot_general"}
  %fusion.8 = bf16[8,8]{1,0} fusion(%p), kind=kOutput, metadata={op_name="jit(s)/while/body/checkpoint/conv.out_proj/dot_general"}
  %ragged-dot-gmm.2 = bf16[8,8]{1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/while/body/checkpoint/moe.experts/ragged-dot-gmm"}
  %fusion.9 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/checkpoint/moe.experts/mul"}
  %fusion.10 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/closed_call/attn.qk_norm/mul"}
}
'''
SECONDS = {"flash_attention_fwd.3": 0.05, "fusion.4": 0.3, "fusion.5": 0.02,
           "fusion.6": 0.04, "fusion.7": 0.2, "fusion.8": 0.1,
           "ragged-dot-gmm.2": 0.08, "fusion.9": 0.02, "fusion.10": 1.0}


def _traced_ctx(monkeypatch):
    from benchmark import scopes
    monkeypatch.setattr(scopes, "instruction_seconds", lambda path: SECONDS)
    routing = {"routing": {"local_assignments_per_token": 0.5,
                           "load_max_over_mean": 1.2}}
    return {"kind": "train", "trace_path": "t", "step_hlo": HLO,
            "trace": {"busy0_s": 2.0}, "cfg": _config(), "chips": 1,
            "rows": 1, "seq": 8192, "k": 2, "traced_readings": 3,
            "peaks": V5E, "readings": [routing] * 5, "train_tok_s": 30000.0}


def test_the_readers_join_their_scopes_with_their_counts(monkeypatch):
    ctx = _traced_ctx(monkeypatch)
    read = lambda name: common.load_reader(common.HERE, name)(ctx)  # noqa: E731
    cfg = ctx["cfg"]
    assert read("conv_time_pct") == pytest.approx(100 * 0.36 / 2.0)

    def share(flops_bytes, taken):
        least, _ = counts.roofline_seconds(*flops_bytes, V5E)
        return 100 * 6 * least / taken

    assert read("conv_mix_roofline") == pytest.approx(share(
        counts_conv_gqa.conv_mix_train_flops_bytes(cfg, 1, 8192), 0.06))
    # the kernels under the scope, not the scope's other operations
    assert read("conv_flash_roofline") == pytest.approx(share(
        counts_conv_gqa.flash_train_flops_bytes(cfg, 1, 8192), 0.05))
    assert read("conv_moe_expert_roofline") == pytest.approx(share(
        counts_conv_gqa.moe_experts_train_flops_bytes(cfg, 4096.0), 0.10))
    assert read("conv_lm_mfu_pct") == pytest.approx(
        100 * 30000 * counts_conv_gqa.lm_train_flops_per_token(
            cfg, 8192, 0.5) / 197e12)
    assert read("conv_lm_mfu_pct") == pytest.approx(23.45, abs=0.02)


NEW_READERS = ("conv_lm_mfu_pct", "conv_time_pct", "conv_mix_roofline",
               "conv_flash_roofline", "conv_moe_expert_roofline")


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
            "moe_load_max_over_mean", "flash_time_pct"} <= names
    # counts_window_gqa counts every layer as an expert layer with attention
    assert not {"gqa_moe_expert_roofline", "full_flash_roofline"} & names
    assert cell["chips"] == 1 and cell["traffic"] == "s8192_b1_causal_conv"
    spec = cell["traffic_file"]
    assert (spec["batch_per_chip"], spec["seq"], spec["steps_per_reading"],
            spec["feed_ring"]) == (1, 8192, 2, 4)
    with open(os.path.join(common.HERE, "traffic",
                           "s8192_b1_causal.json")) as f:
        shape = json.load(f)
    assert {k: v for k, v in spec.items() if k not in ("limits", "why")} == {
        k: v for k, v in shape.items() if k not in ("limits", "why")}


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


def _gate_left_out(monkeypatch, lfm2):
    """y = conv(B * u): a projection whose C third is all ones."""
    from paddle_tpu.ops import ssm
    real = ssm._thirds

    def thirds(bcx):
        b, c, u = real(bcx)
        return b, c * 0 + 1, u

    monkeypatch.setattr(ssm, "_thirds", thirds)


def _taps_reversed(monkeypatch, lfm2):
    from paddle_tpu.ops import ssm
    real = ssm._taps
    monkeypatch.setattr(ssm, "_taps",
                        lambda x, w, reverse=False: real(x, w[::-1], reverse))


def _qk_norm_left_out(monkeypatch, lfm2):
    """q and k times their scale, not normed."""
    causal_lm = lfm2.causal_lm
    real = causal_lm._norm

    def norm(x, name, cfg):
        if not name.endswith(("q_norm_scale", "k_norm_scale")):
            return real(x, name, cfg)
        from paddle_tpu import initializer, layers
        from paddle_tpu.layer_helper import ParamAttr
        scale = layers.create_parameter(
            [int(x.shape[-1])], "float32", attr=ParamAttr(
                name=name, initializer=initializer.Constant(1.0)))
        return layers.elementwise_mul(x, scale, axis=-1)

    monkeypatch.setattr(causal_lm, "_norm", norm)


def _head_untied(monkeypatch, lfm2):
    causal_lm = lfm2.causal_lm
    real = causal_lm.build_causal_lm_program
    monkeypatch.setattr(
        causal_lm, "build_causal_lm_program",
        lambda *a, tie_head=False, **kw: real(*a, tie_head=False, **kw))


def test_sound_run_is_correct():
    sound = _rehearse()
    assert sound["correct"] and not sound["failed"], sound


@pytest.mark.parametrize("fault", [
    _gate_left_out, _taps_reversed, _qk_norm_left_out, _head_untied],
    ids=lambda f: f.__name__.strip("_"))
def test_each_fault_of_the_program_is_not_correct(fault, monkeypatch):
    from paddle_tpu.models import lfm2
    fault(monkeypatch, lfm2)
    result = _rehearse()
    assert not result["correct"] and _bad(result) & {
        "loss_gap_step1", "loss_gap_step2", "moment1_gap", "delta_gap",
        "moment1_dir_gap"}, result["checks"]


def test_the_drivers_faults_move_the_reference_past_the_limits():
    """`calibrate`'s fault rows, at the rehearsal's size: the reference with
    each fault against the sound reference; and the fp8 control."""
    from benchmark import lm_traffic
    from benchmark.drivers import train_conv_lm, train_lm
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

    wrongs = train_conv_lm.faults(cfg, Stub.seq)
    assert sorted(wrongs) == ["gate_left_out", "head_untied",
                              "qk_norm_left_out", "taps_reversed"]
    for name, wrong in wrongs.items():
        gaps = train_conv_lm.compare_lm(
            train_lm.run_reference(Stub, host, cfg=wrong), sound)
        assert fails(gaps), (name, gaps)
    assert fails(train_conv_lm.compare_lm(
        train_lm.run_reference(Stub, host, "fp8"), sound))
