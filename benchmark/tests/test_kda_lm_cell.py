"""What the linear-attention / latent-attention / group-limited-expert cell
brings: its file against the published numbers, its counts against
hand-worked numbers, its readers with and without their sources, and
`correct` shown to fail under faults of the new mechanisms, at the
rehearsal's size."""
import json
import os

import numpy as np
import pytest

from benchmark import common, counts, counts_kda_mla, peaks, rehearse, run

CELL = "ling3_flash_vl_ep64_tp2_s8192"
V5E = peaks.device_peaks("TPU v5 lite")


def _config():
    with open(os.path.join(common.HERE, "configs",
                           "ling3_flash_vl_ep64_tp2.json")) as f:
        return json.load(f)


def test_the_file_keeps_the_published_numbers_and_states_the_cut():
    cfg = _config()
    for key, value in cfg["published"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["layers", "num_experts", "num_attention_heads",
                              "vocab"]
    assert cfg["published"]["num_experts"] == cfg["experts_total"] == 512
    assert cfg["published"]["num_attention_heads"] == cfg["heads_total"] == 32
    assert (cfg["layers"], cfg["first_layer"], cfg["num_experts"],
            cfg["num_attention_heads"], cfg["vocab"]) == (7, 1, 8, 16, 19648)
    assert cfg["vocab"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["num_experts"] * 64 == cfg["experts_total"]
    assert cfg["num_attention_heads"] * 2 == cfg["heads_total"]
    # the held vocabulary lies below the image and video token ids
    assert cfg["vocab"] < min(cfg[k] for k in cfg if k.endswith("_token"))
    assert "64 chips share each layer" in cfg["deployment"]
    assert set(cfg["reduced"]) <= set(cfg["assumed"])
    assumed = cfg["assumed"]
    assert "no tower is built" in assumed["vision_tower"]
    assert "no multi-token head" in assumed["multi_token_head"]
    assert assumed["sibling_keys"]["topk_method"] == "noaux_tc"
    assert assumed["sibling_keys"]["rope_interleave"] is True
    # no clamp in any layer run, both lists as published
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        assert len(cfg[key]) == 42 and not any(cfg[key][1:8])
        assert any(cfg[key])
    assert counts_kda_mla.layer_kinds(cfg) == [
        ("kda", False), ("kda", True), ("kda", True), ("kda", True),
        ("latent", True), ("kda", True), ("kda", True)]
    from benchmark.reference import ling3
    shapes = ling3.param_shapes(cfg)
    count = lambda keep: sum(  # noqa: E731
        int(np.prod(s)) for n, s in shapes.items() if keep(n))
    norms = ("attn_norm_scale", "ffn_norm_scale")
    attn = ("_proj_w", "_conv_w", "A_log", "dt_bias", "o_norm_scale",
            "q_norm_scale", "k_norm_scale", "kv_a_norm_scale")
    assert count(lambda n: True) == 648_850_656          # 648.9 M
    assert count(lambda n: n.startswith("l2_")
                 and n.endswith(attn)) == 26_323_088
    assert count(lambda n: n.startswith("l5_")
                 and n.endswith(attn)) == 16_720_768
    assert count(lambda n: n.startswith("l1_mlp_")) == 47_185_920
    assert count(lambda n: n.startswith("l3_") and not n.endswith(attn)
                 and not n.endswith(norms)) == 54_394_880
    assert count(lambda n: not n.startswith("l")
                 or n == "lm_head_w") == 2 * 19648 * 2560 + 2560
    assert sorted(ling3.buffer_shapes(cfg)) == [
        f"l{n}_router_bias" for n in range(2, 8)]


def test_flops_per_token_by_layer_kind():
    cfg = _config()
    # q, k, v, decay to 2048 columns, beta and gate to 16, three convs of 4
    assert counts_kda_mla.kda_proj_flops_per_token(cfg) == 2 * (
        4 * 2560 * 2048 + 2 * 2560 * 16 + 3 * 4 * 2048
        + 2048 * 2560) == 52_641_792
    # 16 heads x 128 x 128 state elements, 7 operations each
    assert counts_kda_mla.kda_scan_flops_per_token(cfg) == 1_835_008
    assert counts_kda_mla.mla_proj_flops_per_token(cfg) == 2 * (
        2560 * 16 * 192 + 2560 * 576 + 512 * 16 * 256 + 2560 * 16
        + 16 * 128 * 2560) == 33_439_744
    assert counts_kda_mla.attend_pairs(8192) == 33_558_528
    assert counts_kda_mla.mla_attend_flops_per_token(cfg, 8192) == (
        2 * 16 * 320 * 4096.5)
    layer = counts_kda_mla.layer_forward_flops_per_token
    assert layer(cfg, 8192, "kda", False, 0.125) == (
        52_641_792 + 1_835_008 + 6 * 2560 * 6144)
    sparse = 2 * 2560 * 512 + 6 * 2560 * 768 + 0.125 * 6 * 2560 * 768
    assert layer(cfg, 8192, "kda", True, 0.125) == (
        52_641_792 + 1_835_008 + sparse)
    assert layer(cfg, 8192, "latent", True, 0.125) == (
        33_439_744 + 10240 * 4096.5 + sparse)
    with pytest.raises(ValueError):
        layer(cfg, 8192, "window", True, 0.125)
    fwd = counts_kda_mla.lm_forward_flops_per_token(cfg, 8192, 0.125)
    assert fwd == pytest.approx(692_573_184)
    assert counts_kda_mla.lm_train_flops_per_token(cfg, 8192,
                                                   0.125) == 3 * fwd
    # full buffers, 8 assignments a token: what ISSUE 36 calls 1.26 GFLOP
    assert counts_kda_mla.lm_forward_flops_per_token(
        cfg, 8192, 8.0) == pytest.approx(1.2499e9, rel=1e-4)


def test_the_delta_rule_is_counted_as_the_recurrence_and_is_bound_by_bytes():
    cfg = _config()
    flops, nbytes = counts_kda_mla.kda_scan_train_flops_bytes(cfg, 1, 8192)
    assert flops == 6 * 3 * 1_835_008 * 8192
    # q, k, v, o forward and q, k, v, do, dq, dk, dv backward at 2048, bf16;
    # g forward, g and dg backward at 2048 and beta likewise at 16, float32
    assert nbytes == 6 * 8192 * (2 * 11 * 2048 + 4 * 3 * 2048 + 4 * 3 * 16)
    least, bound = counts.roofline_seconds(flops, nbytes, V5E)
    assert bound == "bytes" and least == pytest.approx(4.1905e-3, rel=1e-3)
    flops, nbytes = counts_kda_mla.mla_flash_train_flops_bytes(cfg, 1, 8192)
    assert flops == 3 * 2 * 16 * 33_558_528 * 320
    assert nbytes == 16 * 8192 * 2 * 6 * 320
    flops, nbytes = counts_kda_mla.moe_experts_train_flops_bytes(cfg, 1024)
    assert flops == 6 * 9 * 2 * 1024 * 2560 * 768
    weights = 8 * 3 * 2560 * 768 * 2
    assert nbytes == 6 * (3 * weights + 3 * 1024 * (2 * 2560 + 3 * 768) * 2)


HLO = '''
ENTRY %main {
  %flash_attention_fwd.3 = (bf16[16,8,8]{2,1,0}, f32[16,8,128]{2,1,0}) custom-call(%c, %q), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/while/body/closed_call/mla.attend/flash_attention_fwd/pallas_call" source_file="x.py"}
  %fusion.7 = f32[16,8,128]{2,1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/closed_call/kda.scan/kda.scan.intra/dot_general"}
  %fusion.8 = f32[16,8,128]{2,1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/closed_call/transpose(jvp(kda.scan))/kda.scan.carry/mul"}
  %fusion.9 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/closed_call/kda.proj/dot_general"}
  %fusion.10 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/closed_call/mla.proj/mul"}
  %fusion.11 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/closed_call/kda.out/mul"}
  %ragged-dot-none.4 = bf16[8,8]{1,0} custom-call(%p), custom_call_target="tpu_custom_call"
}
'''


def _traced_ctx(monkeypatch):
    from benchmark import scopes
    monkeypatch.setattr(scopes, "instruction_seconds", lambda path: {
        "flash_attention_fwd.3": 0.03, "fusion.7": 0.05, "fusion.8": 0.01,
        "fusion.9": 0.04, "fusion.10": 1.0, "fusion.11": 0.02,
        "ragged-dot-none.4": 0.6})
    routing = {"routing": {"local_assignments_per_token": 0.125,
                           "load_max_over_mean": 1.3}}
    return {"kind": "train", "trace_path": "t", "step_hlo": HLO,
            "trace": {"busy0_s": 2.0}, "cfg": _config(), "chips": 1,
            "rows": 1, "seq": 8192, "k": 2, "traced_readings": 3,
            "peaks": V5E, "train_tok_s": 12000.0, "readings": [routing] * 5}


def test_the_new_readers_on_a_recorded_join(monkeypatch):
    ctx = _traced_ctx(monkeypatch)
    read = lambda name: common.load_reader(common.HERE, name)(ctx)  # noqa: E731
    # the five kda.* scopes, forward and backward, over the busy time
    assert read("kda_time_pct") == pytest.approx(100 * 0.12 / 2.0)
    # 6 traced steps of the recurrence's least time over `kda.scan`'s
    assert read("kda_scan_roofline") == pytest.approx(
        100 * 6 * 4.1905e-3 / 0.06, rel=1e-3)
    flops, nbytes = counts_kda_mla.mla_flash_train_flops_bytes(
        ctx["cfg"], 1, 8192)
    least, _ = counts.roofline_seconds(flops, nbytes, V5E)
    assert read("kda_mla_flash_roofline") == pytest.approx(
        100 * 6 * least / 0.03)
    flops, nbytes = counts_kda_mla.moe_experts_train_flops_bytes(
        ctx["cfg"], 0.125 * 8192)
    least, _ = counts.roofline_seconds(flops, nbytes, V5E)
    assert read("kda_moe_expert_roofline") == pytest.approx(
        100 * 6 * least / 0.6)
    assert read("kda_lm_mfu_pct") == pytest.approx(
        100 * 12000 * 3 * 692_573_184 / 197e12, rel=1e-6)
    # the accepted readers this cell is listed under find their sources too
    assert read("moe_time_pct") == pytest.approx(100 * 0.6 / 2.0)
    assert read("moe_local_assign_per_tok") == pytest.approx(0.125)
    assert read("moe_load_max_over_mean") == pytest.approx(1.3)


def test_new_readers_return_nothing_without_their_sources():
    """A parent commit: a step without the scopes, or no trace at all."""
    ctx = {"kind": "train", "readings": [{"seconds": 1.0}], "trace": None,
           "cfg": _config(), "chips": 1, "rows": 1, "seq": 8192, "k": 2,
           "traced_readings": 3, "train_tok_s": 1.0, "peaks": V5E}
    names = ("kda_lm_mfu_pct", "kda_time_pct", "kda_scan_roofline",
             "kda_mla_flash_roofline", "kda_moe_expert_roofline")
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
    assert cell["chips"] == 1 and cell["traffic"] == "s8192_b1_causal_kda"
    spec = cell["traffic_file"]
    assert (spec["batch_per_chip"], spec["seq"], spec["steps_per_reading"],
            spec["feed_ring"]) == (1, 8192, 2, 4)
    assert spec["labels"] == "next_token" and not spec["padded"]
    listed = {m["name"] for m in cell["per_layer"]}
    assert {"kda_time_pct", "kda_scan_roofline", "kda_moe_expert_roofline",
            "kda_mla_flash_roofline", "kda_lm_mfu_pct", "flash_time_pct",
            "moe_time_pct", "optimizer_time_pct", "moe_local_assign_per_tok",
            "moe_load_max_over_mean"} <= listed
    assert not listed & {"mfu_pct", "lm_mfu_pct", "gqa_lm_mfu_pct",
                         "hybrid_lm_mfu_pct", "mla_flash_roofline",
                         "ssm_scan_roofline"}
    layers = {m["name"]: m["layer"] for m in manifest["per_layer"]}
    assert layers["kda_time_pct"] == layers[
        "kda_scan_roofline"] == "Linear-attention layer"


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


def _plain_top_k(monkeypatch):
    """The group limit left out of the program's selection."""
    from paddle_tpu.ops import moe
    monkeypatch.setattr(moe, "_group_limited", lambda sel, *groups: sel)


def _no_head_gate(monkeypatch):
    from paddle_tpu.models import ling
    monkeypatch.setattr(ling.layers, "head_gate", lambda x, gate: x)


def _keys_not_normed(monkeypatch):
    from paddle_tpu.models import ling
    monkeypatch.setattr(ling.layers, "l2_norm",
                        lambda x, scale=1.0, epsilon=1e-6: x)


def test_sound_run_is_correct():
    sound = _rehearse()
    assert sound["correct"] and not sound["failed"], sound
    # the driver's own comparison ran: the first moments as vectors too
    assert "moment1_dir_gap" in {c["name"] for c in sound["checks"]}


def test_the_direction_gap_sees_what_a_norm_cannot():
    """A rounding that is right on average moves a leaf's norm by its
    square and the leaf, as a vector, by itself: `worst_leaf_gap` reads the
    one, `direction_gaps` the other. `run` and `calibrate` are bound to the
    comparison that takes both."""
    from benchmark.drivers import train_kda_lm
    from benchmark.drivers.train import worst_leaf_gap
    rng = np.random.RandomState(0)
    want = {"a": rng.randn(8192).astype(np.float32),
            "b": rng.randn(128).astype(np.float32)}
    got = {n: v * (1 + 0.05 * rng.randn(*v.shape)).astype(np.float32)
           for n, v in want.items()}
    norms = lambda d: {n: float(np.linalg.norm(v)) for n, v in d.items()}  # noqa: E731
    leaves = train_kda_lm.direction_gaps(got, want)
    assert sorted(leaves) == ["a", "b"]
    assert 0.03 < min(leaves.values()) <= max(leaves.values()) < 0.08
    assert worst_leaf_gap(norms(got), norms(want)) < 0.01
    assert set(train_kda_lm.direction_gaps(want, want).values()) == {0.0}
    for fn in (train_kda_lm.run, train_kda_lm.calibrate):
        assert fn.__globals__["compare_lm"] is train_kda_lm.compare_lm
        assert fn.__globals__["Trainer"] is train_kda_lm.Trainer
    assert train_kda_lm.run.__globals__[
        "_jsonable"]({"first_route": 1, "moment1_vectors": 2, "x": 3}) == {
            "x": 3}


@pytest.mark.parametrize("fault", [
    _chunks_start_from_zero, _plain_top_k, _no_head_gate, _keys_not_normed],
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
    the row left out. All fail here but the state rounded to bf16 after
    every token: over 32 tokens its drift stays inside every limit, the
    direction of the first moments included (the chip's rows, over 8,192
    tokens, are in PERF.md section 6, PR 36)."""
    from benchmark import lm_traffic
    from benchmark.drivers import train_kda_lm, train_lm
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

    wrongs = train_kda_lm.faults(cfg)
    assert sorted(wrongs) == ["kda_no_delta", "kda_quarter_left_out",
                              "kda_state_bf16", "no_group_limit"]
    gaps = {name: train_kda_lm.compare_lm(
        train_lm.run_reference(Stub, host, cfg=wrong), sound)
        for name, wrong in wrongs.items()}
    for name in ("kda_no_delta", "kda_quarter_left_out", "no_group_limit"):
        assert fails(gaps[name]), (name, gaps[name])
    assert gaps["no_group_limit"]["route_mismatch_share"] > limits[
        "route_mismatch_share"]
    assert gaps["kda_no_delta"]["moment1_dir_gap"] > limits["moment1_dir_gap"]
    assert 0 < gaps["kda_state_bf16"]["moment1_dir_gap"]
    assert gaps["kda_state_bf16"]["moment1_gap"] > 0
    fp8 = train_kda_lm.compare_lm(
        train_lm.run_reference(Stub, host, "fp8"), sound)
    assert fails(fp8) and fp8["moment1_dir_gap"] > limits["moment1_dir_gap"]
    assert train_lm._quarter_left_out(Stub, host, sound) > 10 * limits[
        "loss_gap"]
