"""Asserted collective budget for the bucketed dp data path (ISSUE 5).

PR 2-4 shrank the compute graph, the copy count, and the host boundary;
this pins the comms + memory dimension: the gradient bucketing pass
(parallel/zero.py) must keep the compiled dp step at <= bucket-count
grouped collectives (without it XLA has emitted 31 ungrouped per-gradient
all-reduces, depending on its version), and ZeRO-1 must halve dp=2 optimizer-state bytes
per device while staying bit-for-bit with the replicated update and
round-tripping through unsharded checkpoints in both directions.

Multi-device runs happen in sanitized CPU-mesh subprocesses
(conftest.cpu_mesh_env), each with the device count its case needs;
budgets come from the measured post-pass census
(docs/perf_notes.md "Bucketed collectives & ZeRO-1") with headroom, never
enough to readmit the ungrouped state. dp=2 only here (fast, tier-1);
wider sweeps carry the `slow` mark.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from conftest import cpu_mesh_env

import paddle_tpu as paddle
import paddle_tpu.fluid as fluid

# Tier-1 rebalance (ISSUE 16): ~87s of CPU-mesh subprocesses whose budget
# assertions are re-run by ci.py's collective-audit drill
# (scripts/collective_audit.py --assert) on every CI pass.
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, n_devices=2) -> dict:
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=cpu_mesh_env(n_devices), capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, f"subprocess failed:\n{r.stdout}\n{r.stderr}"
    return json.loads(r.stdout.strip().splitlines()[-1])


# tiny 2-layer BERT + the audit() census, shared by every subprocess arm
COMMON = """
import json, re, collections
import numpy as np
import jax
import paddle_tpu as paddle
import paddle_tpu.fluid as fluid
from paddle_tpu.models import bert
from paddle_tpu.distributed import fleet
from paddle_tpu.testing import reset_programs

def build(sharding=False, bucket_mb=32, stage=None):
    reset_programs(0)
    cfg = bert.BertConfig(vocab_size=256, hidden_size=32, num_layers=2,
                          num_heads=2, intermediate_size=64, max_position=32,
                          seq_len=16, hidden_dropout=0.0,
                          attention_dropout=0.0)
    ids, labels, loss = bert.build_pretrain_program(cfg)
    fleet.init(is_collective=True)
    s = fleet.DistributedStrategy()
    s.sharding = sharding
    if stage is not None:
        s.sharding_stage = stage
    s.fuse_grad_size_in_mb = bucket_mb
    opt = fleet.distributed_optimizer(
        paddle.optimizer.Adam(learning_rate=1e-3), s)
    opt.minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    feed = {"input_ids": rng.randint(0, 256, (8, 16)).astype(np.int64),
            "mlm_labels": rng.randint(0, 256, (8, 16, 1)).astype(np.int64)}
    return exe, feed, loss

# ONE census implementation: the same audit() the CI budget runs
# (scripts/collective_audit.py) — the tier-1 pin and the --assert budget
# must count identically or they drift apart across jax upgrades
import importlib.util, os
_repo = os.path.dirname(os.path.dirname(os.path.abspath(
    __import__("paddle_tpu").__file__)))
_spec = importlib.util.spec_from_file_location(
    "collective_audit", os.path.join(_repo, "scripts",
                                     "collective_audit.py"))
_audit_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_audit_mod)
census = _audit_mod.audit
"""


def test_bucketed_collective_counts_dp2():
    """dp=2 default strategy: the gradient sync is <= bucket-count grouped
    all-reduces (one 32 MB bucket + the scalar loss pmean here — NOT one
    per parameter), with total all-reduce bytes within 1% of the raw
    gradient bytes, and the step runs the manual bucketed lowering."""
    out = run_sub(COMMON + """
exe, feed, loss = build()
prog = fluid.default_main_program()
grad_bytes = 4 * sum(int(np.prod(p.shape)) for p in prog.all_parameters()
                     if p.trainable)
counts, byts = census(exe.compiled_hlo(feed, [loss]))
cb = list(exe._cache.values())[-1]
print(json.dumps({"counts": dict(counts),
                  "bytes": dict(byts), "grad_bytes": grad_bytes,
                  "manual": bool(getattr(cb, "manual_dp", False)),
                  "n_sync_ops": len(prog._grad_buckets["sync_buckets"])}))
""")
    counts = out["counts"]
    assert out["manual"], out
    assert out["n_sync_ops"] == 1                       # one 32 MB bucket
    assert counts.get("all-reduce", 99) <= 4, counts    # was 31 ungrouped
    assert not set(counts) - {"all-reduce"}, counts     # no other kinds
    # total AR volume = the gradients (+ the 4-byte loss pmean): within 1%
    assert abs(out["bytes"]["all-reduce"] - out["grad_bytes"]) \
        <= 0.01 * out["grad_bytes"] + 64, out


def test_bucket_size_knob_splits_buckets():
    """fuse_grad_size_in_mb mirrors the reference knob: shrinking it splits
    the gradient set into more sync ops (program-structural, no mesh
    needed — the pass runs at minimize on any geometry)."""
    from paddle_tpu.models import bert
    from paddle_tpu.distributed import fleet
    from paddle_tpu.testing import reset_programs

    def n_sync(bucket_mb):
        reset_programs(0)
        cfg = bert.BertConfig(vocab_size=256, hidden_size=32, num_layers=2,
                              num_heads=2, intermediate_size=64,
                              max_position=32, seq_len=16,
                              hidden_dropout=0.0, attention_dropout=0.0)
        ids, labels, loss = bert.build_pretrain_program(cfg)
        fleet.init(is_collective=True)
        s = fleet.DistributedStrategy()
        s.fuse_grad_size_in_mb = bucket_mb
        opt = fleet.distributed_optimizer(
            paddle.optimizer.Adam(learning_rate=1e-3), s)
        opt.minimize(loss)
        gb = fluid.default_main_program().global_block()
        return sum(op.type == "__bucket_sync__" for op in gb.ops)

    assert n_sync(32) == 1         # everything fits one default bucket
    # ~0.1 MB of grads at this geometry: a 0.02 MB cap must split them
    assert n_sync(0.02) >= 2
    # 0 disables the pass entirely (no sync ops, no metadata)
    assert n_sync(0) == 0
    assert getattr(fluid.default_main_program(), "_grad_buckets", None) \
        is None


def test_zero1_memory_parity_and_checkpoint_roundtrip():
    """The ZeRO-1 acceptance bundle on a dp=2 mesh, one subprocess:

    * optimizer-state bytes/device: flat dp-sharded buckets make the
      compiled step's per-device argument bytes drop by >= the replicated
      moment bytes' half (structural memory_analysis, no timing);
    * bit-for-bit loss parity with the replicated (stage-0 bucketed) arm
      over 6 steps;
    * checkpoints round-trip BOTH directions: save under ZeRO-1 at step 3
      -> load into a replicated program -> steps 4-6 bit-equal, and save
      replicated at step 3 -> load into a ZeRO-1 program (per-param
      moments adopt into the flat shards) -> steps 4-6 bit-equal."""
    out = run_sub(COMMON + """
import tempfile, os
from paddle_tpu.parallel.zero import optimizer_state_bytes

def steps(exe, feed, loss, n):
    return [float(exe.run(feed=feed, fetch_list=[loss])[0])
            for _ in range(n)]

tmp = tempfile.mkdtemp()

# arm A: replicated (stage-0 bucketing), 3 steps -> save -> 3 steps
exe, feed, loss = build(sharding=False)
prog = fluid.default_main_program()
la = steps(exe, feed, loss, 3)
paddle.fluid.io.save_persistables(exe, os.path.join(tmp, "repl"),
                                  main_program=prog)
la += steps(exe, feed, loss, 3)
ma_repl = exe.compiled_memory_analysis(feed, [loss])
moment_bytes = 4 * 2 * sum(
    int(np.prod(p.shape)) for p in prog.all_parameters() if p.trainable)

# arm B: ZeRO-1, 3 steps -> save -> 3 steps
exe, feed, loss = build(sharding=True)
prog_z = fluid.default_main_program()
lb = steps(exe, feed, loss, 3)
paddle.fluid.io.save_persistables(exe, os.path.join(tmp, "zero"),
                                  main_program=prog_z)
lb += steps(exe, feed, loss, 3)
ma_zero = exe.compiled_memory_analysis(feed, [loss])
acct = optimizer_state_bytes(prog_z, dp=2)
saved = dict(np.load(os.path.join(tmp, "zero", "persistables.npz")))

# arm C: ZeRO checkpoint -> REPLICATED program, steps 4-6
exe, feed, loss = build(sharding=False)
paddle.fluid.io.load_persistables(exe, os.path.join(tmp, "zero"),
                                  main_program=fluid.default_main_program())
lc = steps(exe, feed, loss, 3)

# arm D: replicated checkpoint -> ZERO program, steps 4-6 (flat adoption)
exe, feed, loss = build(sharding=True)
paddle.fluid.io.load_persistables(exe, os.path.join(tmp, "repl"),
                                  main_program=fluid.default_main_program())
ld = steps(exe, feed, loss, 3)
from paddle_tpu.framework.scope import global_scope
leftover = [n for n in global_scope().local_names()
            if "_moment" in n and not n.startswith("zero1_")]

print(json.dumps({
    "la": la, "lb": lb, "lc": lc, "ld": ld,
    "arg_repl": ma_repl.argument_size_in_bytes,
    "arg_zero": ma_zero.argument_size_in_bytes,
    "moment_bytes": moment_bytes, "acct": acct,
    "saved_flat": [n for n in saved if "zero1" in n],
    "saved_moments": sum("_moment" in n for n in saved),
    "leftover_per_param": leftover}))
""")
    # bit-for-bit parity: ZeRO-1 vs replicated, all 6 steps
    assert out["lb"] == out["la"], (out["la"], out["lb"])
    # checkpoint round-trip both directions, bit-for-bit continuation
    assert out["lc"] == out["la"][3:], (out["lc"], out["la"])
    assert out["ld"] == out["lb"][3:], (out["ld"], out["lb"])
    # structural memory: per-device argument bytes drop by >= half the
    # replicated moment footprint (dp=2 shards the other half away)
    saving = out["arg_repl"] - out["arg_zero"]
    assert saving >= 0.45 * out["moment_bytes"], out
    assert out["acct"]["zero_stage"] == 1
    assert out["acct"]["flat_state_bytes_per_device"] * 2 == \
        out["acct"]["flat_state_bytes_total"]
    # checkpoints are PORTABLE: flat buckets never serialize — per-param
    # moment views do, and loading the replicated ckpt into the ZeRO
    # program leaves no stale per-param entries in the scope
    assert out["saved_flat"] == []
    assert out["saved_moments"] > 0
    assert out["leftover_per_param"] == []


def test_zero_stages_parity_memory_and_overlap_dp2():
    """The ZeRO-2/3 acceptance bundle (ISSUE 6) on a dp=2 mesh, one
    subprocess, with a 0.02 MB bucket cap forcing a >=3-bucket pipeline:

    * dp=2 loss parity BIT-FOR-BIT for sharding_stage in {1,2,3} vs the
      replicated arm (6 steps each);
    * checkpoints round-trip bit-exact between every stage and replicated,
      BOTH directions (stage save -> replicated load continues identically,
      replicated save -> stage-3 load adopts params+moments into shards);
    * structural memory (compiled_memory_analysis, no timing): stage 3
      argument bytes drop by >= the replicated params' dp=2 half, and the
      stage-2 resident gradient shard adds ~grad_bytes/dp of OUTPUT state
      (the shard, never the full width — gradient bytes/device / dp);
    * overlap: the compiled stage-2/3 step carries K>=3 reduce-scatters
      INTERLEAVED with backward compute (collective groups separated by
      fusion/dot ops in the scheduled module — the bucket pipeline, not a
      post-backward sync wall), and stage 3 runs K on-demand param
      all-gathers with NO post-update gather (AG bytes <= one param
      volume);
    * sharding_stage=3 + tensor parallelism raises loudly."""
    out = run_sub(COMMON + """
import os, tempfile
from paddle_tpu.parallel.zero import optimizer_state_bytes

def steps(exe, feed, loss, n, prog):
    return [float(exe.run(program=prog, feed=feed, fetch_list=[loss])[0])
            for _ in range(n)]

# the ONE interleaving metric: the same collective_segments the CI
# __min_segments__ budget runs (drift rule as for census/audit above)
census_seg = _audit_mod.collective_segments

tmp = tempfile.mkdtemp()
res = {}
arms = {}
for stage in (0, 1, 2, 3):
    exe, feed, loss = build(bucket_mb=0.02, stage=stage)
    prog = fluid.default_main_program()
    arms[stage] = (exe, feed, loss, prog)
    ls = steps(exe, feed, loss, 3, prog)
    paddle.fluid.io.save_persistables(exe, os.path.join(tmp, f"s{stage}"),
                                      main_program=prog)
    ls += steps(exe, feed, loss, 3, prog)
    ma = exe.compiled_memory_analysis(feed, [loss])
    gbm = getattr(prog, "_grad_buckets", None)
    txt = exe.compiled_hlo(feed, [loss]) if stage >= 2 else ""
    counts, byts = census(txt) if stage >= 2 else ({}, {})
    res[stage] = {
        "losses": ls,
        "manual": bool(getattr(list(exe._cache.values())[-1],
                               "manual_dp", False)),
        "arg": int(ma.argument_size_in_bytes),
        "out": int(ma.output_size_in_bytes),
        "n_zero": len(gbm["zero_buckets"]) if gbm else 0,
        "acct": optimizer_state_bytes(prog, dp=2),
        "counts": dict(counts), "bytes": dict(byts),
        "segments": census_seg(txt) if stage >= 2 else 0,
    }

param_bytes = 4 * sum(int(np.prod(p.shape))
                      for p in arms[0][3].all_parameters() if p.trainable)

# checkpoint matrix: every stage ckpt -> the REPLICATED arm (cache hit),
# and the replicated ckpt -> the stage-3 arm (param+moment adoption)
exe0, feed0, loss0, prog0 = arms[0]
cont = {}
for stage in (1, 2, 3):
    paddle.fluid.io.load_persistables(exe0, os.path.join(tmp, f"s{stage}"),
                                      main_program=prog0)
    cont[stage] = steps(exe0, feed0, loss0, 3, prog0)
exe3, feed3, loss3, prog3 = arms[3]
paddle.fluid.io.load_persistables(exe3, os.path.join(tmp, "s0"),
                                  main_program=prog3)
cont["r3"] = steps(exe3, feed3, loss3, 3, prog3)
saved3 = dict(np.load(os.path.join(tmp, "s3", "persistables.npz")))

# stage 3 + tp>1 must raise loudly (2 devices -> a tp=2 mesh builds)
from paddle_tpu.models import bert as bert_mod
from paddle_tpu.testing import reset_programs
reset_programs(0)
cfg = bert_mod.BertConfig(vocab_size=256, hidden_size=32, num_layers=2,
                          num_heads=2, intermediate_size=64,
                          max_position=32, seq_len=16, hidden_dropout=0.0,
                          attention_dropout=0.0)
ids, labels, loss_tp = bert_mod.build_pretrain_program(cfg)
fleet.init(is_collective=True)
s_tp = fleet.DistributedStrategy(
    tensor_parallel_degree=2,
    tensor_parallel_rules=bert_mod.tp_sharding_rules())
s_tp.sharding_stage = 3
try:
    fleet.distributed_optimizer(
        paddle.optimizer.Adam(learning_rate=1e-3), s_tp).minimize(loss_tp)
    tp_guard = "no error"
except ValueError as e:
    tp_guard = "raised" if "stage" in str(e) else str(e)

print(json.dumps({"res": {str(k): v for k, v in res.items()},
                  "cont": {str(k): v for k, v in cont.items()},
                  "param_bytes": param_bytes, "tp_guard": tp_guard,
                  "saved3_flat": [n for n in saved3
                                  if n.startswith(("zero2_", "zero3_"))],
                  "saved3_params": sum(
                      not ("_moment" in n or "beta" in n or "@" in n)
                      for n in saved3)}))
""")
    res = out["res"]
    la = res["0"]["losses"]
    # bit-for-bit parity, every stage, all 6 steps, manual mode engaged
    for stage in ("1", "2", "3"):
        assert res[stage]["losses"] == la, (stage, res[stage]["losses"], la)
        assert res[stage]["manual"], stage
    # the small bucket cap split the grads into a real pipeline
    assert res["1"]["n_zero"] >= 3, res["1"]["n_zero"]
    # checkpoints: stage save -> replicated continues bit-equal; replicated
    # save -> stage-3 adopts and continues bit-equal
    for k in ("1", "2", "3", "r3"):
        assert out["cont"][k] == la[3:], (k, out["cont"][k], la[3:])
    # stage-3 checkpoints are the PORTABLE unsharded format: no flat
    # buckets serialize, per-param entries do
    assert out["saved3_flat"] == []
    assert out["saved3_params"] > 0
    # structural memory: stage-3 argument bytes shed >= the dp=2 half of
    # the replicated parameter footprint (parameter bytes/device / dp)
    assert res["1"]["arg"] - res["3"]["arg"] >= 0.45 * out["param_bytes"], \
        (res["1"]["arg"], res["3"]["arg"], out["param_bytes"])
    # stage-2 resident gradient shard: output state grows by the SHARD
    # (~grad/dp), never the full gradient volume
    grad_total = res["2"]["acct"]["flat_grad_bytes_total"]
    delta = res["2"]["out"] - res["1"]["out"]
    assert grad_total > 0
    assert 0.45 * grad_total <= delta <= 0.55 * grad_total, \
        (delta, grad_total)
    assert res["2"]["acct"]["flat_grad_bytes_per_device"] * 2 == grad_total
    # census: K reduce-scatters, AG bytes bounded by ONE param volume
    # (stage 2: post-update param AG only; stage 3: forward on-demand AG
    # only — gradients are NEVER all-gathered at either stage)
    for stage in ("2", "3"):
        k = res[stage]["n_zero"]
        counts = res[stage]["counts"]
        assert counts.get("reduce-scatter", 0) >= 3, (stage, counts)
        assert counts.get("reduce-scatter", 0) <= k + 1, (stage, counts)
        assert counts.get("all-gather", 0) <= k + 1, (stage, counts)
        assert res[stage]["bytes"]["all-gather"] <= \
            1.02 * out["param_bytes"] + 8192, (stage, res[stage]["bytes"])
        # the overlap pipeline: collectives interleave with backward
        # compute (>= 3 separated groups), not one post-backward wall
        assert res[stage]["segments"] >= 3, (stage, res[stage]["segments"])
    assert out["tp_guard"] == "raised", out["tp_guard"]


def test_zero_fallback_causes_are_counted():
    """The fallback matrix is observable from monitor stats alone: a
    sharding_stage request that gradient-merge (or pipeline/PS) programs
    cannot take falls back to GSPMD specs and counts
    executor.zero_manual_fallbacks.<cause> (no mesh needed — the decline
    happens at minimize time)."""
    from paddle_tpu import monitor
    from paddle_tpu.fluid import layers
    from paddle_tpu.distributed import fleet
    from paddle_tpu.testing import reset_programs

    reset_programs(0)
    monitor.stat_reset("executor.zero_manual_fallbacks.grad_merge")
    x = layers.data(name="x", shape=[6], dtype="float32")
    y = layers.data(name="y", shape=[1], dtype="float32")
    loss = layers.mean(layers.square_error_cost(layers.fc(x, 1), y))
    fleet.init(is_collective=True)
    s = fleet.DistributedStrategy()
    s.sharding_stage = 2
    s.gradient_merge = True
    s.gradient_merge_configs = {"k_steps": 2}
    fleet.distributed_optimizer(
        paddle.optimizer.Adam(learning_rate=1e-2), s).minimize(loss)
    prog = fluid.default_main_program()
    assert not getattr(prog, "_zero_buckets", None)
    assert monitor.stat_get(
        "executor.zero_manual_fallbacks.grad_merge") >= 1
    assert monitor.stat_get("executor.zero_manual_fallbacks") >= 1

    # unknown stages still fail loudly
    reset_programs(0)
    x = layers.data(name="x", shape=[6], dtype="float32")
    y = layers.data(name="y", shape=[1], dtype="float32")
    loss = layers.mean(layers.square_error_cost(layers.fc(x, 1), y))
    fleet.init(is_collective=True)
    s4 = fleet.DistributedStrategy()
    s4.sharding_stage = 4
    with pytest.raises(ValueError):
        fleet.distributed_optimizer(
            paddle.optimizer.Adam(learning_rate=1e-2), s4).minimize(loss)


def test_bucket_pipeline_places_syncs_in_backward_schedule():
    """Program-structural overlap check (no mesh): with a small bucket cap
    the per-bucket __zero_update__ ops sit at their buckets' backward-ready
    points — interleaved into the backward region in gradient-production
    order — instead of forming one wall after the last grad op."""
    from paddle_tpu.models import bert
    from paddle_tpu.distributed import fleet
    from paddle_tpu.framework.program import OpRole
    from paddle_tpu.testing import reset_programs

    reset_programs(0)
    cfg = bert.BertConfig(vocab_size=256, hidden_size=32, num_layers=2,
                          num_heads=2, intermediate_size=64, max_position=32,
                          seq_len=16, hidden_dropout=0.0,
                          attention_dropout=0.0)
    ids, labels, loss = bert.build_pretrain_program(cfg)
    fleet.init(is_collective=True)
    s = fleet.DistributedStrategy()
    s.sharding_stage = 1
    s.fuse_grad_size_in_mb = 0.02
    fleet.distributed_optimizer(
        paddle.optimizer.Adam(learning_rate=1e-3), s).minimize(loss)
    gb = fluid.default_main_program().global_block()
    upd_pos = [i for i, op in enumerate(gb.ops)
               if op.type == "__zero_update__"]
    bwd_pos = [i for i, op in enumerate(gb.ops)
               if op.attrs.get("op_role", 0) == OpRole.Backward]
    assert len(upd_pos) >= 3, upd_pos
    # at least one bucket op fires BEFORE the last backward op (the
    # pipeline), and backward ops run between the first and last bucket op
    assert upd_pos[0] < max(bwd_pos), (upd_pos, max(bwd_pos))
    between = [i for i in bwd_pos if upd_pos[0] < i < upd_pos[-1]]
    assert len(between) >= 1, (upd_pos, bwd_pos[-5:])


def test_unknown_strategy_attribute_raises():
    """DistributedStrategy typos must fail loudly (the reference proto
    silently drops unknown fields): sharding/fuse_grad_size_in_mb typos
    can no longer no-op into replicated training."""
    from paddle_tpu.distributed import fleet
    s = fleet.DistributedStrategy()
    s.sharding = True                     # known key: fine
    s.fuse_grad_size_in_mb = 16           # known key: fine
    with pytest.raises(AttributeError) as ei:
        s.shardingg = True
    assert "sharding" in str(ei.value)    # the known-key list is printed
    with pytest.raises(AttributeError):
        s.fuse_grad_size_mb = 16
    with pytest.raises(TypeError):
        fleet.DistributedStrategy(shardingg=True)


@pytest.mark.slow
def test_bucketed_counts_wider_meshes():
    """dp=4 and dp=8 sweeps (acceptance: grouped counts hold across mesh
    widths with bytes constant in N)."""
    for ndev in (4, 8):
        out = run_sub(COMMON + """
exe, feed, loss = build()
counts, byts = census(exe.compiled_hlo(feed, [loss]))
print(json.dumps({"counts": dict(counts), "bytes": dict(byts)}))
""", n_devices=ndev)
        assert out["counts"].get("all-reduce", 99) <= 4, (ndev, out)


@pytest.mark.slow
def test_zero1_parity_when_dp_does_not_divide_padding():
    """dp=6 does not divide the 64-element bucket padding: ZeRO-1 must fall
    back to the full-width update WITH the gradient average (a missing psum
    here trains replicas on divergent local grads — the silent-desync class
    this test exists for). Bit-equal vs the stage-0 arm."""
    code = (COMMON + """
def arm(sharding):
    exe, feed, loss = build(sharding=sharding)
    ls = [float(exe.run(feed=feed, fetch_list=[loss])[0]) for _ in range(4)]
    return ls, bool(list(exe._cache.values())[-1].manual_dp)

l0, m0 = arm(False)
l1, m1 = arm(True)
print(json.dumps({"l0": l0, "l1": l1, "manual": m0 and m1}))
""").replace("(8, 16)", "(12, 16)")      # batch 12: divisible by dp=6,
    code = code.replace("(8, 16, 1)", "(12, 16, 1)")   # not by the padding
    out = run_sub(code, n_devices=6)
    assert out["manual"], out
    assert out["l0"] == out["l1"], out


@pytest.mark.slow
def test_zero3_layer_scan_gathers_per_segment_dp2():
    """The ZeRO-3 x rolled-layer composition: @LAYERS stacked scan params
    store as [L, padded] trailing-axis dp shards and the __layer_scan__
    body all_gathers ONE layer slice per scan iteration (jax.vjp
    transposes it into a per-iteration psum_scatter) — bit-for-bit with
    the rolled replicated arm, params+moments sharded in the compiled
    step's argument bytes."""
    out = run_sub(COMMON + """
from paddle_tpu.testing import reset_programs

def build_rolled(stage):
    reset_programs(0)
    cfg = bert.BertConfig(vocab_size=256, hidden_size=32, num_layers=4,
                          num_heads=2, intermediate_size=64, max_position=32,
                          seq_len=16, hidden_dropout=0.0,
                          attention_dropout=0.0)
    ids, labels, loss = bert.build_pretrain_program(cfg)
    fleet.init(is_collective=True)
    s = fleet.DistributedStrategy()
    s.layer_scan = True
    s.sharding_stage = stage
    s.fuse_grad_size_in_mb = 0.05
    fleet.distributed_optimizer(
        paddle.optimizer.Adam(learning_rate=1e-3), s).minimize(loss)
    prog = fluid.default_main_program()
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    feed = {"input_ids": rng.randint(0, 256, (8, 16)).astype(np.int64),
            "mlm_labels": rng.randint(0, 256, (8, 16, 1)).astype(np.int64)}
    return exe, feed, loss, prog

res = {}
for stage in (0, 3):
    exe, feed, loss, prog = build_rolled(stage)
    n_scan = sum(op.type == "__layer_scan__"
                 for op in prog.global_block().ops)
    stacked = [b for b in (getattr(prog, "_zero_buckets", None) or [])
               if b.get("layout") == "stacked"]
    ls = [float(exe.run(program=prog, feed=feed,
                        fetch_list=[loss])[0]) for _ in range(4)]
    ma = exe.compiled_memory_analysis(feed, [loss])
    res[stage] = {"losses": ls, "n_scan": n_scan,
                  "n_stacked": len(stacked),
                  "arg": int(ma.argument_size_in_bytes)}
print(json.dumps({str(k): v for k, v in res.items()}))
""")
    assert out["0"]["n_scan"] == 1 and out["3"]["n_scan"] == 1, out
    assert out["3"]["n_stacked"] >= 3, out["3"]
    assert out["3"]["losses"] == out["0"]["losses"], out
    # stacked params + moments sharded: the rolled stage-3 step's argument
    # bytes drop well below the rolled replicated step's
    assert out["3"]["arg"] < 0.75 * out["0"]["arg"], out


@pytest.mark.slow
def test_zero_stages_parity_when_dp_does_not_divide_padding():
    """dp=6 does not divide the 64-element bucket padding: stages 2/3 must
    fall back to the full-width update WITH the gradient average, bit-equal
    vs the stage-0 arm (the silent-desync class)."""
    code = (COMMON + """
def arm(stage):
    exe, feed, loss = build(stage=stage)
    ls = [float(exe.run(feed=feed, fetch_list=[loss])[0]) for _ in range(4)]
    return ls, bool(list(exe._cache.values())[-1].manual_dp)

l0, m0 = arm(0)
l2, m2 = arm(2)
l3, m3 = arm(3)
print(json.dumps({"l0": l0, "l2": l2, "l3": l3,
                  "manual": m0 and m2 and m3}))
""").replace("(8, 16)", "(12, 16)").replace("(8, 16, 1)", "(12, 16, 1)")
    out = run_sub(code, n_devices=6)
    assert out["manual"], out
    assert out["l2"] == out["l0"], out
    assert out["l3"] == out["l0"], out


@pytest.mark.slow
def test_zero1_run_steps_parity_dp2():
    """ZeRO-1 composes with the k-step device loop: run_steps(3) losses
    bit-equal three per-step runs."""
    out = run_sub(COMMON + """
exe, feed, loss = build(sharding=True)
per = [float(exe.run(feed=feed, fetch_list=[loss])[0]) for _ in range(3)]
exe2, feed2, loss2 = build(sharding=True)
stacked = exe2.run_steps(3, feed=feed2, fetch_list=[loss2])
print(json.dumps({"per": per,
                  "stacked": [float(v) for v in np.asarray(stacked[0])]}))
""")
    assert out["per"] == out["stacked"], out
