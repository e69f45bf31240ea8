"""What the causal-LM cell brings: its counts against hand-worked numbers,
its generator, the join of a trace with the compiled step's scopes, and
`correct` shown to fail under each fault its limits are there for, at the
rehearsal's size."""
import json
import os

import numpy as np
import pytest

from benchmark import (common, counts, counts_mla_moe, lm_traffic, peaks,
                       rehearse, run, scopes)

CELL = "kanana2_30b_a3b_ep8_s4096"


def _config():
    with open(os.path.join(common.HERE, "configs",
                           "kanana2_30b_a3b_ep8.json")) as f:
        return json.load(f)


def test_the_file_keeps_the_published_numbers_and_states_the_cut():
    cfg = _config()
    for key, value in cfg["published"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["published"]["n_routed_experts"] == cfg["experts_total"] == 128
    assert (cfg["layers"], cfg["n_routed_experts"], cfg["vocab"]) == (
        5, 16, 16032)
    assert cfg["vocab"] * 8 == cfg["published"]["vocab_size"]
    assert set(cfg["reduced"]) <= set(cfg["assumed"])
    from benchmark.reference import kanana2
    shapes = kanana2.param_shapes(cfg)
    count = lambda keep: sum(  # noqa: E731
        int(np.prod(s)) for n, s in shapes.items() if keep(n))
    assert count(lambda n: True) == pytest.approx(575.96e6, rel=1e-4)
    assert count(lambda n: n.startswith("l0_")) == pytest.approx(64.1e6,
                                                                 rel=1e-3)
    assert count(lambda n: n.startswith("l1_")) == pytest.approx(111.5e6,
                                                                 rel=1e-3)
    assert count(lambda n: "experts" in n and n.startswith("l1_")) == (
        16 * 3 * 2048 * 768)


def test_flops_per_token_of_the_cut_model():
    cfg = _config()
    assert counts_mla_moe.mla_proj_flops_per_token(cfg) == 2 * (
        2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 32 * 128 * 2048)
    assert counts_mla_moe.mla_attend_flops_per_token(cfg, 4096) == (
        10240 * 4096)
    fwd = counts_mla_moe.lm_forward_flops_per_token(cfg, 4096, 0.75)
    layer0 = 52_690_944 + 41_943_040 + 6 * 2048 * 6144
    expert = (52_690_944 + 41_943_040 + 2 * 2048 * 128 + 6 * 2048 * 1536
              + 0.75 * 6 * 2048 * 768)
    assert fwd == pytest.approx(layer0 + 4 * expert + 2 * 2048 * 16032)
    assert fwd == pytest.approx(720e6, rel=5e-3)
    assert counts_mla_moe.lm_train_flops_per_token(cfg, 4096, 0.75) == 3 * fwd


def test_flash_at_two_widths_and_the_grouped_matmuls():
    cfg = _config()
    flops, nbytes = counts_mla_moe.mla_flash_train_flops_bytes(cfg, 2, 4096)
    pair = 2 * 2 * 32 * 4096 * 4096 // 2
    assert flops == 5 * (2 * pair * 192 + 2 * pair * 128 + pair * 192
                         + pair * 128) == 5 * 3 * pair * 320
    assert nbytes == 5 * 2 * 32 * 4096 * 2 * (6 * 192 + 6 * 128)
    least, bound = counts.roofline_seconds(
        flops, nbytes, peaks.device_peaks("TPU v5 lite"))
    assert bound == "flops" and least == pytest.approx(26.16e-3, rel=1e-2)
    flops, nbytes = counts_mla_moe.moe_experts_train_flops_bytes(
        cfg, 6144, 4)
    assert flops == 4 * 9 * 2 * 6144 * 2048 * 768
    weights = 16 * 3 * 2048 * 768 * 2
    assert nbytes == 4 * (3 * weights + 3 * 6144 * (2 * 2048 + 3 * 768) * 2)


def test_lm_batches_label_every_position_but_the_last():
    spec = {"steps_per_reading": 2, "seq": 16, "labels": "next_token",
            "label_rate": 15 / 16}
    a = lm_traffic.lm_feed(spec, 100, 3, 4000000007, 0)
    assert a["ids"].shape == a["labels"].shape == (2, 3, 16)
    assert (a["labels"][:, :, :-1] == a["ids"][:, :, 1:]).all()
    assert (a["labels"][:, :, -1] == lm_traffic.IGNORE).all()
    assert a["ids"].min() >= 0 and a["ids"].max() < 100
    again = lm_traffic.lm_feed(spec, 100, 3, 4000000007, 0)
    other = lm_traffic.lm_feed(spec, 100, 3, 4000000007, 1)
    assert (a["ids"] == again["ids"]).all()
    assert (a["ids"] != other["ids"]).any() and (a["ids"][0]
                                                 != a["ids"][1]).any()


HLO = '''
ENTRY %main {
  %fusion.3 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(s)/while/body/moe.experts/mul" source_file="x.py"}
  ROOT %fusion.4 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/while/body/optimizer.adam/sub"}
  %ragged-dot-none.2 = bf16[8,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %copy.1 = f32[8]{0} copy(%q)
}
'''


def test_scopes_join_instruction_names(monkeypatch):
    names = scopes.instruction_scopes(HLO)
    assert names["fusion.3"].endswith("moe.experts/mul")
    assert names["fusion.4"].endswith("optimizer.adam/sub")
    assert "copy.1" not in names
    monkeypatch.setattr(scopes, "instruction_seconds", lambda path: {
        "fusion.3": 0.25, "fusion.4": 0.5, "ragged-dot-none.2": 1.0,
        "copy.1": 2.0})
    ctx = {"trace_path": "t", "step_hlo": HLO, "trace": {"busy0_s": 5.0}}
    assert scopes.group_seconds(ctx, ("moe.experts",)) == 0.25
    assert scopes.group_seconds(ctx, ("moe.",), ("ragged-dot",)) == 1.25
    assert scopes.share_of_busy(ctx, ("optimizer.adam",)) == 10.0
    assert scopes.group_seconds(ctx, ("mla.attend",)) is None
    # a parent commit: no scopes in its step, or no step text at all
    assert scopes.group_seconds({"trace_path": "t"}, ("moe.",)) is None


def test_new_readers_return_nothing_without_their_sources():
    ctx = {"kind": "train", "readings": [{"seconds": 1.0}], "trace": None,
           "cfg": _config(), "chips": 1, "rows": 2, "seq": 4096, "k": 2,
           "traced_readings": 3, "train_tok_s": 1.0,
           "peaks": peaks.device_peaks("TPU v5 lite")}
    for name in ("lm_mfu_pct", "mla_flash_roofline", "moe_time_pct",
                 "moe_expert_roofline", "optimizer_time_pct",
                 "moe_local_assign_per_tok", "moe_load_max_over_mean"):
        assert common.load_reader(common.HERE, name)(dict(ctx)) is None, name


def test_mfu_reader_uses_the_assignments_that_fell_here():
    ctx = {"kind": "train", "cfg": _config(), "chips": 1, "seq": 4096,
           "train_tok_s": 18000.0,
           "peaks": peaks.device_peaks("TPU v5 lite"),
           "readings": [{"routing": {"local_assignments_per_token": 0.75,
                                     "load_max_over_mean": 2.0}}]}
    got = common.load_reader(common.HERE, "lm_mfu_pct")(ctx)
    assert got == pytest.approx(100 * 18000 * 3 * 720.3e6 / 197e12, rel=5e-3)
    assert common.load_reader(common.HERE, "moe_load_max_over_mean")(
        ctx) == 2.0


def _rehearse():
    return run.run_cell(CELL, 2147483659, 1.0, 0,
                        rehearsal=rehearse.tiny_presets(CELL))


def _bad(result):
    return {c["name"] for c in result["checks"] if c["value"] > c["limit"]}


def test_sound_run_is_correct_and_each_fault_is_not(monkeypatch):
    sound = _rehearse()
    assert sound["correct"] and not sound["failed"], sound
    # SelectBias left out of the program's selection
    from benchmark.drivers import train_lm
    real_leaf = train_lm.Trainer.fresh_leaf
    monkeypatch.setattr(
        train_lm.Trainer, "fresh_leaf", lambda self, name: real_leaf(
            self, name) * (0.0 if name.endswith("router_bias") else 1.0))
    assert "route_mismatch_share" in _bad(_rehearse())
    monkeypatch.setattr(train_lm.Trainer, "fresh_leaf", real_leaf)
    # a step that returns its state unchanged
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    real = fluid.Executor.run_steps

    def frozen(self, k, **kw):
        scope = fluid.global_scope()
        keep = {n: jnp.array(scope.find(n), copy=True)
                for n in scope.local_names()
                if hasattr(scope.find(n), "dtype")
                and jnp.issubdtype(scope.find(n).dtype, jnp.floating)}
        out = real(self, k, **kw)
        for n, v in keep.items():
            scope.set(n, v)
        return out

    monkeypatch.setattr(fluid.Executor, "run_steps", frozen)
    assert "delta_gap" in _bad(_rehearse())


def test_control_fp8_and_a_quarter_left_out_are_not_correct():
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
    # the blocks a gradient is taken in do not matter
    halves = train_lm.run_reference(
        Stub, host, cfg=dict(cfg, reference_tokens_per_block=2 * Stub.seq))
    assert max(train_lm.compare_lm(halves, sound).values()) < 1e-5
    gaps = train_lm.compare_lm(train_lm.run_reference(Stub, host, "fp8"),
                               sound)
    held = {k: v <= limits["loss_gap" if k.startswith("loss") else k]
            for k, v in gaps.items()}
    assert not all(held.values()), gaps
    assert train_lm._quarter_left_out(Stub, host, sound) > 10 * limits[
        "loss_gap"]
