"""Distributed tests on the virtual 8-device CPU mesh.

Reference test strategy (SURVEY §4): loss-parity between distributed and
single-process runs (test_dist_base.py), collective numerics
(test_collective_base.py), and graph-rewrite assertions for strategies
(fleet_meta_optimizer tests). Multi-device runs happen in sanitized
subprocesses (conftest.cpu_mesh_env), each with the device count its case
needs.
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

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, n_devices=8) -> dict:
    """Run python code in an n-device CPU mesh subprocess; it must print one
    JSON line on stdout (reference _run_cluster pattern, test_dist_base.py:769)."""
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=cpu_mesh_env(n_devices), capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, f"subprocess failed:\n{r.stdout}\n{r.stderr}"
    return json.loads(r.stdout.strip().splitlines()[-1])


COMMON = """
import json
import numpy as np
import jax
import paddle_tpu as paddle
import paddle_tpu.fluid as fluid
from paddle_tpu.distributed import fleet
"""


def test_dp_loss_parity_with_single_device():
    """2-trainer-equivalent: DP-sharded training must track the single-device
    loss exactly (same global batch), the reference's core distributed test."""
    out = run_sub(COMMON + """
def build_and_train(use_dp):
    from paddle_tpu.framework import program as pm, scope as sm, unique_name
    pm._main_program = pm.Program(); pm._startup_program = pm.Program()
    sm._reset_global_scope(); unique_name.switch()
    paddle.seed(5)
    x = fluid.layers.data(name="x", shape=[8], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="float32")
    h = fluid.layers.fc(x, 16, act="relu")
    pred = fluid.layers.fc(h, 1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    opt = paddle.optimizer.SGD(learning_rate=0.05)
    if use_dp:
        fleet.init(is_collective=True)
        opt = fleet.distributed_optimizer(opt, fleet.DistributedStrategy())
    opt.minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    xv = rng.rand(32, 8).astype(np.float32)
    yv = xv.sum(1, keepdims=True) * 0.3
    losses = []
    for _ in range(10):
        lv, = exe.run(feed={"x": xv, "y": yv}, fetch_list=[loss])
        losses.append(float(lv))
    return losses

single = build_and_train(False)
dp = build_and_train(True)
print(json.dumps({"single": single, "dp": dp,
                  "n_dev": jax.device_count()}))
""")
    assert out["n_dev"] == 8
    np.testing.assert_allclose(out["single"], out["dp"], rtol=2e-4, atol=1e-5)
    assert out["dp"][-1] < out["dp"][0] * 0.5


def test_tp_sharding_runs_and_matches():
    """Megatron-style TP on fc weights: results must match unsharded run.
    (TP is beyond-reference capability, SURVEY §2.8 last row.)"""
    out = run_sub(COMMON + """
from jax.sharding import PartitionSpec as P
from paddle_tpu.parallel import ShardingRules, DistConfig, attach, build_mesh

def build(rules):
    from paddle_tpu.framework import program as pm, scope as sm, unique_name
    pm._main_program = pm.Program(); pm._startup_program = pm.Program()
    sm._reset_global_scope(); unique_name.switch()
    paddle.seed(3)
    x = fluid.layers.data(name="x", shape=[16], dtype="float32")
    h = fluid.layers.fc(x, 32, act="relu", param_attr=paddle.ParamAttr(name="w1"))
    o = fluid.layers.fc(h, 4, param_attr=paddle.ParamAttr(name="w2"))
    loss = fluid.layers.mean(o)
    paddle.optimizer.SGD(learning_rate=0.1).minimize(loss)
    prog = fluid.default_main_program()
    if rules is not None:
        mesh = build_mesh(dp=2, tp=4)
        attach(prog, DistConfig(mesh=mesh, param_rules=rules))
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(1)
    xv = rng.rand(8, 16).astype(np.float32)
    losses = [float(exe.run(feed={"x": xv}, fetch_list=[loss])[0][0] if False else exe.run(feed={"x": xv}, fetch_list=[loss])[0]) for _ in range(5)]
    return losses

plain = build(None)
# column-parallel w1, row-parallel w2 (Megatron pattern)
tp_rules = ShardingRules([("w1", P(None, "tp")), ("w2", P("tp", None))])
tp = build(tp_rules)
print(json.dumps({"plain": plain, "tp": tp}))
""")
    np.testing.assert_allclose(out["plain"], out["tp"], rtol=2e-4, atol=1e-5)


def test_run_steps_preserves_tp_sharding():
    """run_steps must keep the DistConfig (TP placements) rather than fall
    back to GSPMD inference — replicated params can OOM precisely where TP
    rules exist. Parity: k scanned steps == k sequential run() calls, and the
    compiled entry must carry the mesh."""
    out = run_sub(COMMON + """
from jax.sharding import PartitionSpec as P
from paddle_tpu.parallel import ShardingRules, DistConfig, attach, build_mesh

def build():
    from paddle_tpu.framework import program as pm, scope as sm, unique_name
    pm._main_program = pm.Program(); pm._startup_program = pm.Program()
    sm._reset_global_scope(); unique_name.switch()
    paddle.seed(7)
    x = fluid.layers.data(name="x", shape=[16], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="float32")
    h = fluid.layers.fc(x, 32, act="relu",
                        param_attr=paddle.ParamAttr(name="w1"))
    pred = fluid.layers.fc(h, 1, param_attr=paddle.ParamAttr(name="w2"))
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    paddle.optimizer.SGD(learning_rate=0.05).minimize(loss)
    prog = fluid.default_main_program()
    rules = ShardingRules([("w1", P(None, "tp")), ("w2", P("tp", None))])
    attach(prog, DistConfig(mesh=build_mesh(dp=2, tp=4), param_rules=rules))
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    return exe, loss

rng = np.random.RandomState(2)
xs = rng.rand(4, 8, 16).astype(np.float32)
ys = xs.sum(2, keepdims=True).astype(np.float32) * 0.3

exe, loss = build()
seq = [float(exe.run(feed={"x": xs[i], "y": ys[i]}, fetch_list=[loss])[0])
       for i in range(4)]

exe2, loss2 = build()
stacked, = exe2.run_steps(4, feed={"x": xs, "y": ys}, fetch_list=[loss2])
multi_entries = [c for k, c in exe2._cache.items() if k[0] == "multi"]
print(json.dumps({"seq": seq, "scanned": np.asarray(stacked).reshape(-1).tolist(),
                  "mesh_kept": all(c.mesh is not None for c in multi_entries),
                  "n_multi": len(multi_entries)}))
""")
    assert out["n_multi"] == 1 and out["mesh_kept"], \
        "run_steps dropped the DistConfig mesh"
    np.testing.assert_allclose(out["seq"], out["scanned"], rtol=2e-4,
                               atol=1e-5)


def test_collective_allreduce_numerics():
    """reference test_collective_base.py: allreduce across dp shards."""
    out = run_sub(COMMON + """
import jax.numpy as jnp
import paddle_tpu.distributed as dist
from paddle_tpu.parallel import build_mesh, set_mesh
mesh = build_mesh(dp=8)
set_mesh(mesh)
x = np.arange(16, dtype=np.float32).reshape(16, 1)  # 2 rows per device
sharded = dist.split_batch(x)
t = paddle.Tensor(sharded)
res = dist.all_reduce(t)
# per-shard sum over dp of each row-shard: every device's 2 rows summed
print(json.dumps({"shape": list(res.shape),
                  "vals": np.asarray(res.value).reshape(-1).tolist()}))
""")
    # allreduce over 'dp' of the sharded rows: each shard (2,1) summed -> (2,1)
    expect = np.arange(16, dtype=np.float32).reshape(8, 2).sum(0)
    assert out["shape"] == [2, 1]
    np.testing.assert_allclose(np.array(out["vals"]), expect)


def test_fleet_strategy_amp_bf16():
    out = run_sub(COMMON + """
fleet.init(is_collective=True)
paddle.seed(0)
x = fluid.layers.data(name="x", shape=[8], dtype="float32")
y = fluid.layers.data(name="y", shape=[1], dtype="float32")
pred = fluid.layers.fc(x, 1)
loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
s = fleet.DistributedStrategy(); s.amp = True
opt = fleet.distributed_optimizer(paddle.optimizer.SGD(0.05), s)
opt.minimize(loss)
exe = fluid.Executor()
exe.run(fluid.default_startup_program())
rng = np.random.RandomState(0)
xv = rng.rand(16, 8).astype(np.float32)
yv = xv.sum(1, keepdims=True) * 0.2
losses = [float(exe.run(feed={"x": xv, "y": yv}, fetch_list=[loss])[0])
          for _ in range(20)]
print(json.dumps({"first": losses[0], "last": losses[-1]}))
""")
    assert out["last"] < out["first"] * 0.5


def test_fleet_strategy_recompute_matches_baseline():
    out = run_sub(COMMON + """
def train(recompute):
    from paddle_tpu.framework import program as pm, scope as sm, unique_name
    pm._main_program = pm.Program(); pm._startup_program = pm.Program()
    sm._reset_global_scope(); unique_name.switch()
    paddle.seed(9)
    x = fluid.layers.data(name="x", shape=[8], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="float32")
    h1 = fluid.layers.fc(x, 16, act="relu")
    h2 = fluid.layers.fc(h1, 16, act="relu")
    pred = fluid.layers.fc(h2, 1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fleet.init(is_collective=True)
    s = fleet.DistributedStrategy()
    if recompute:
        s.recompute = True
        s.recompute_configs = {"checkpoints": [h1.name, h2.name]}
    opt = fleet.distributed_optimizer(paddle.optimizer.SGD(0.05), s)
    opt.minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(2)
    xv = rng.rand(16, 8).astype(np.float32)
    yv = xv.sum(1, keepdims=True) * 0.2
    return [float(exe.run(feed={"x": xv, "y": yv}, fetch_list=[loss])[0])
            for _ in range(8)]

base = train(False)
rc = train(True)
print(json.dumps({"base": base, "rc": rc}))
""")
    np.testing.assert_allclose(out["base"], out["rc"], rtol=1e-4, atol=1e-6)


def test_recompute_segment_outputs_in_production_order():
    """A `__segment__`'s `Out` / `out_names` list the variables that leave
    it in the order its sub-ops first produce them, never in the order of a
    set of names: the list reaches the step's HLO, and XLA's persistent
    compile cache keys the step by it (PERF.md section 6, PR 35). WHICH
    variables leave is the old rule: read by a later segment, a checkpoint,
    or read by nobody."""
    from paddle_tpu.framework.backward import append_backward
    from paddle_tpu.parallel.transforms import apply_recompute
    prog = fluid.default_main_program()
    gb = prog.global_block()
    gb.create_var(name="x", shape=(4, 8), dtype="float32", is_data=True)
    w = gb.create_parameter(name="w", shape=(8, 8), dtype="float32")
    for n in ("z_first", "inner", "a_second", "m_third", "h1",
              "d", "e", "unread", "loss"):
        gb.create_var(name=n, shape=(1,) if n == "loss" else (4, 8),
                      dtype="float32")

    def op(kind, out, x, y=None, **attrs):
        ins = {"X": [x]} if y is None else {"X": [x], "Y": [y]}
        gb.append_op(kind, inputs=ins, outputs={"Out": [out]}, attrs=attrs)

    # segment 1, closed by the checkpoint h1
    op("mul", "z_first", "x", w.name)        # read by segment 2
    op("relu", "inner", "z_first")           # read inside segment 1 only
    op("scale", "a_second", "inner", scale=2.0)        # read by segment 2
    op("elementwise_add", "m_third", "a_second", "z_first")    # segment 2
    op("elementwise_add", "h1", "m_third", "inner")            # checkpoint
    # segment 2
    op("elementwise_add", "d", "h1", "z_first")        # read inside only
    op("elementwise_add", "e", "d", "a_second")        # read inside only
    op("elementwise_add", "unread", "e", "m_third")    # read by nobody
    op("mean", "loss", "e")                            # read by nobody
    apply_recompute(prog, ["h1"])

    segs = [o for o in gb.ops if o.type == "__segment__"]
    assert [o.type for o in gb.ops] == ["__segment__", "__segment__"]
    assert segs[0].inputs["X"] == ["x", "w"]
    assert segs[0].outputs["Out"] == ["z_first", "a_second", "m_third", "h1"]
    assert segs[1].inputs["X"] == ["h1", "z_first", "a_second", "m_third"]
    assert segs[1].outputs["Out"] == ["unread", "loss"]
    for seg in segs:
        assert seg.attrs["out_names"] == seg.outputs["Out"]
        assert seg.attrs["in_names"] == seg.inputs["X"]

    # the backward built from it follows: each `__vjp__` re-lowers its
    # segment from the same lists, one cotangent slot an output
    append_backward(gb.var("loss"))
    vjps = [o for o in gb.ops if o.type == "__vjp__"]
    assert len(vjps) == 2
    for vjp, seg in zip(vjps, reversed(segs)):
        assert vjp.attrs["fwd_attrs"]["out_names"] == seg.outputs["Out"]
        assert vjp.inputs["X"] == seg.inputs["X"]
        assert len(vjp.inputs["OG:Out"]) == len(seg.outputs["Out"])

    # and the rewritten program runs
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    paddle.global_scope().set("w", np.eye(8, dtype=np.float32))
    xv = np.random.RandomState(0).rand(4, 8).astype(np.float32)
    got, grad = exe.run(feed={"x": xv}, fetch_list=["loss", "w@GRAD"])
    z, a = xv, 2.0 * xv                 # w = I, x >= 0
    np.testing.assert_allclose(
        got, np.mean((a + z) + z + z + a, keepdims=True), rtol=1e-6)
    assert np.asarray(grad).shape == (8, 8) and np.abs(grad).sum() > 0


def test_fleet_strategy_gradient_merge():
    """k=2 gradient merge over halved batches == full-batch SGD every step
    (reference GradientMergeOptimizer semantics)."""
    out = run_sub(COMMON + """
def train_full():
    from paddle_tpu.framework import program as pm, scope as sm, unique_name
    pm._main_program = pm.Program(); pm._startup_program = pm.Program()
    sm._reset_global_scope(); unique_name.switch()
    paddle.seed(4)
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="float32")
    pred = fluid.layers.fc(x, 1, param_attr=paddle.ParamAttr(name="w"))
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    paddle.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(); exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(7)
    xv = rng.rand(8, 4).astype(np.float32)
    yv = xv.sum(1, keepdims=True)
    for _ in range(3):
        exe.run(feed={"x": xv, "y": yv}, fetch_list=[loss])
    return np.asarray(paddle.global_scope().find("w")).tolist()

def train_merged():
    from paddle_tpu.framework import program as pm, scope as sm, unique_name
    pm._main_program = pm.Program(); pm._startup_program = pm.Program()
    sm._reset_global_scope(); unique_name.switch()
    paddle.seed(4)
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="float32")
    pred = fluid.layers.fc(x, 1, param_attr=paddle.ParamAttr(name="w"))
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fleet.init(is_collective=True)
    s = fleet.DistributedStrategy()
    s.gradient_merge = True
    s.gradient_merge_configs = {"k_steps": 2}
    opt = fleet.distributed_optimizer(paddle.optimizer.SGD(0.1), s)
    opt.minimize(loss)
    exe = fluid.Executor(); exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(7)
    xv = rng.rand(8, 4).astype(np.float32)
    yv = xv.sum(1, keepdims=True)
    # feed the two half-batches; update applies on the 2nd micro-step
    for _ in range(3):
        exe.run(feed={"x": xv[:4], "y": yv[:4]}, fetch_list=[loss])
        exe.run(feed={"x": xv[4:], "y": yv[4:]}, fetch_list=[loss])
    return np.asarray(paddle.global_scope().find("w")).tolist()

print(json.dumps({"full": train_full(), "merged": train_merged()}))
""")
    np.testing.assert_allclose(out["full"], out["merged"], rtol=1e-4,
                               atol=1e-6)


def test_zero1_sharding_strategy():
    out = run_sub(COMMON + """
fleet.init(is_collective=True)
paddle.seed(0)
x = fluid.layers.data(name="x", shape=[16], dtype="float32")
y = fluid.layers.data(name="y", shape=[1], dtype="float32")
pred = fluid.layers.fc(x, 1)
loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
s = fleet.DistributedStrategy(); s.sharding = True
opt = fleet.distributed_optimizer(
    paddle.optimizer.Adam(learning_rate=0.01), s)
opt.minimize(loss)
exe = fluid.Executor()
exe.run(fluid.default_startup_program())
rng = np.random.RandomState(0)
xv = rng.rand(16, 16).astype(np.float32)
yv = xv.sum(1, keepdims=True) * 0.1
losses = [float(exe.run(feed={"x": xv, "y": yv}, fetch_list=[loss])[0])
          for _ in range(15)]
print(json.dumps({"first": losses[0], "last": losses[-1]}))
""")
    assert out["last"] < out["first"] * 0.7
