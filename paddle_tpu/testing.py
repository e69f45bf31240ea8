"""Test/dryrun environment helpers.

Sharding and collective behaviour is tested without a cluster, following
the reference's no-cluster pattern (test_dist_base.py:769 spawns fresh
localhost processes): a subprocess whose environment targets a virtual
n-device CPU mesh. This is the one canonical copy of that recipe —
conftest, __graft_entry__ and the CPU tools under scripts/ all use it.
None of it is a way to reach an accelerator: the chip is driven by
chip_smoke.py and bench.py, one process per chip.
"""
from __future__ import annotations

import os
import re
import sys


def cpu_mesh_env(n_devices: int = 8, base_env: dict | None = None) -> dict:
    """Sanitized env for a subprocess needing an n-device virtual CPU mesh."""
    env = dict(os.environ if base_env is None else base_env)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+",
                       f"--xla_force_host_platform_device_count={n_devices}",
                       flags)
    else:
        flags = (flags +
                 f" --xla_force_host_platform_device_count={n_devices}")
    env["XLA_FLAGS"] = flags.strip()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    # NOTE: the persistent XLA compilation cache is deliberately NOT set
    # here. A/B measurement showed no suite speedup (XLA *CPU* compiles
    # are ~0.2 s; tracing dominates), and the cache's LRU atime tracking
    # emits warnings when concurrent test processes race on eviction —
    # which would break the suite's zero-warnings contract. TPU entry
    # points turn it on through paddle_tpu.compile_cache.enable().
    return env


def reset_programs(seed: int = 0) -> None:
    """Fresh default main/startup programs + global scope + name counters —
    the per-test/per-bench reset (the reference makes a new Program() per
    unit test). One canonical copy; conftest, bench.py and __graft_entry__
    all use it."""
    import paddle_tpu as paddle
    from paddle_tpu.framework import program as pm, scope as sm, unique_name
    pm._main_program = pm.Program()
    pm._startup_program = pm.Program()
    sm._reset_global_scope()
    unique_name.switch()
    paddle.seed(seed)


def virtual_cpu_mesh_ready(n_devices: int) -> bool:
    """True if THIS process's env already provides an n-device CPU mesh
    (checked without initializing jax, which would open the device)."""
    if os.environ.get("JAX_PLATFORMS", "") != "cpu":
        return False
    m = re.search(r"xla_force_host_platform_device_count=(\d+)",
                  os.environ.get("XLA_FLAGS", ""))
    return m is not None and int(m.group(1)) >= n_devices


def run_as_cpu_tool(n_devices: int, script: str, argv) -> None:
    """Entry guard of the CPU audit/smoke tools under scripts/. They read
    compiled HLO and check parity on a virtual CPU mesh by design, so
    each run says on stderr that it is a CPU run — a pass here says
    nothing about an accelerator. Call before anything imports jax: when
    this process's environment is not yet an n-device CPU mesh, the
    script is re-executed once in a child that is, and this process
    exits with the child's code."""
    name = os.path.basename(script)
    if os.environ.get("PADDLE_TPU_AUDIT_CHILD") != "1" \
            and not virtual_cpu_mesh_ready(n_devices):
        import subprocess
        env = cpu_mesh_env(n_devices)
        env["PADDLE_TPU_AUDIT_CHILD"] = "1"
        proc = subprocess.run([sys.executable, script, *argv], env=env,
                              timeout=3600)
        sys.exit(proc.returncode)
    print(f"[{name}] CPU tool: runs on a virtual CPU mesh "
          "(JAX_PLATFORMS=cpu); this is not a run on the chip",
          file=sys.stderr)


# --- ZeRO dp-resize oracle harness ---------------------------------------
# One canonical copy of the train-on-N / resume-on-M drill, consumed (in
# cpu_mesh_env subprocesses) by BOTH tests/test_elastic.py and
# scripts/chaos_smoke.py --preemption-drill — the CI drill and the tier-1
# test must exercise the SAME arms or they drift apart silently.

def zero_resize_attach(prog, dp) -> None:
    """Attach a dp-wide mesh + the program's ZeRO state specs."""
    import jax
    from paddle_tpu.parallel import attach, DistConfig, build_mesh
    attach(prog, DistConfig(
        mesh=build_mesh(dp=dp, devices=jax.devices()[:dp]),
        state_specs=dict(getattr(prog, "_zero_state_specs", None) or {})))


def zero_resize_flat_build(dp, stage):
    """The flat (unrolled) resize model: 8->32(tanh)->1 fc regression,
    Adam, tiny buckets so every stage produces several. Returns
    (exe, prog, loss, feed)."""
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.distributed import fleet

    reset_programs(0)
    x = layers.data(name="x", shape=[8], dtype="float32")
    y = layers.data(name="y", shape=[1], dtype="float32")
    h = layers.fc(x, 32, act="tanh")
    pred = layers.fc(h, 1)
    loss = layers.mean(layers.square_error_cost(pred, y))
    fleet.init(is_collective=True)
    s = fleet.DistributedStrategy()
    if stage:
        s.sharding_stage = stage
    s.fuse_grad_size_in_mb = 0.001        # force several tiny buckets
    fleet.distributed_optimizer(
        paddle.optimizer.Adam(learning_rate=1e-2), s).minimize(loss)
    prog = fluid.default_main_program()
    zero_resize_attach(prog, dp)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())

    def feed(step):
        rng = np.random.RandomState(100 + step)
        xv = rng.randn(8, 8).astype(np.float32)
        return {"x": xv, "y": xv.sum(1, keepdims=True).astype(np.float32)}

    return exe, prog, loss, feed


def zero_resize_case(build, stage, dp_from=4, dp_to=2, workdir=None,
                     steps=3) -> dict:
    """Three arms: train dp_from under ZeRO `stage` -> portable checkpoint
    -> resume dp_to ZeRO (the flat-bucket repack under test) vs resume
    dp_to REPLICATED from the SAME checkpoint (the oracle). Returns
    {losses_equal, mismatched, l_zero, l_repl}; bit-for-bit means
    losses_equal and an empty mismatched list."""
    import tempfile
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.io import _portable_arrays
    from paddle_tpu.resilience import CheckpointManager

    workdir = workdir or tempfile.mkdtemp(prefix="resize_")

    def arm(dp, arm_stage, resume, n):
        exe, prog, loss, feed = build(dp, arm_stage)
        mgr = CheckpointManager(workdir, max_keep=2)
        start = 0
        if resume:
            restored = mgr.restore_latest()
            assert restored is not None, "no checkpoint to resume"
            start = restored + 1
        losses = []
        for step in range(start, start + n):
            out, = exe.run(feed=feed(step), fetch_list=[loss])
            losses.append(repr(float(np.asarray(out).ravel()[0])))
        return losses, _portable_arrays(prog, paddle.global_scope()), prog

    _, _, prog = arm(dp_from, stage, False, steps)
    CheckpointManager(workdir, max_keep=2).save(
        steps - 1, program=prog, scope=paddle.global_scope())
    l_zero, p_zero, _ = arm(dp_to, stage, True, steps)
    l_repl, p_repl, _ = arm(dp_to, 0, True, steps)
    mismatched = sorted(set(p_zero) ^ set(p_repl)) + [
        k for k in sorted(set(p_zero) & set(p_repl))
        if not np.array_equal(p_zero[k], p_repl[k])]
    return {"losses_equal": l_zero == l_repl, "mismatched": mismatched,
            "l_zero": l_zero, "l_repl": l_repl}
