#!/usr/bin/env python
"""CI driver: run the test suite sharded over worker processes.

Reference counterpart: paddle/scripts/paddle_build.sh (the CI entry that
builds + runs ctest with parallelism). This image has no pytest-xdist, so
the driver shards test FILES over N pytest subprocesses with
longest-processing-time-first bin packing (weights below are measured
single-process seconds, round 4) and the sanitized CPU-mesh environment
every test expects. The whole suite lands well under the single-process
wall time (~22 min -> ~4-6 min at N=6 on an idle host).

Usage:  python scripts/ci.py [-n WORKERS] [--pytest-arg ...]
Exit code: 0 iff every shard passed.
"""
from __future__ import annotations

import argparse
import glob
import os
import re
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# measured single-process seconds (suite_r04 report); unlisted files get 10
WEIGHTS = {
    "test_ring_attention.py": 230, "test_book_models.py": 200,
    "test_examples.py": 90,
    "test_vision_text.py": 140, "test_detection_pipelines.py": 90,
    "test_ps_pass.py": 60, "test_data_pipeline.py": 80,
    "test_detection_train_ops.py": 60, "test_moe.py": 100,
    "test_sequence_rnn.py": 50, "test_dygraph.py": 45,
    "test_distributed.py": 45, "test_ps_kvstore.py": 45,
    "test_dense_tail_ops.py": 40, "test_flash_attention.py": 40,
    "test_detection_assign_ops.py": 40, "test_elastic.py": 55,
    "test_launch.py": 10,
    "test_strategies.py": 35, "test_collective_budget.py": 90,
    "test_cost_parity.py": 45,
    "test_lod_ops.py": 30, "test_heter_ps.py": 30,
    "test_federated.py": 25, "test_tail_ops.py": 35, "test_dy2static.py": 25,
    "test_jit_inference.py": 30, "test_executor_basic.py": 30,
    "test_crf_ner_book.py": 25, "test_quantization.py": 20,
    "test_run_steps.py": 20, "test_extra_ops.py": 25,
    "test_sequence_tail_ops.py": 20, "test_control_flow.py": 20,
    "test_backward_and_optimizers.py": 20, "test_lr_and_optimizers.py": 20,
    "test_dynamic_rnn.py": 20, "test_capi_serving.py": 20,
    "test_serving.py": 40, "test_paged_ops.py": 10,
    "test_serving_resilience.py": 60,
}


# Host-stall budget check (ISSUE-4 CI satellite): a 20-step loop logging
# every 5 under async dispatch must emit the executor.host_blocked_ms stat
# and sync EXACTLY steps/log_every times — a regression that silently
# drains every step (or never materializes) flips the count and fails CI
# before any hardware round records a poisoned number.
HOST_STALL_CHECK = r'''
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu import monitor

x = layers.data(name="x", shape=[6], dtype="float32")
y = layers.data(name="y", shape=[1], dtype="float32")
h = layers.fc(x, 8, act="tanh")
pred = layers.fc(h, 1)
loss = layers.mean(layers.square_error_cost(pred, y))
paddle.optimizer.Adam(learning_rate=1e-2).minimize(loss)
exe = fluid.Executor()
exe.run(fluid.default_startup_program())
rng = np.random.RandomState(0)
feed = {"x": rng.randn(16, 6).astype(np.float32)}
feed["y"] = feed["x"].sum(1, keepdims=True).astype(np.float32)
exe.run(feed=feed, fetch_list=[loss])          # compile + warm
for s in ("executor.host_blocked_ms", "executor.fetch_sync_count"):
    monitor.stat_reset(s)
steps, log_every = 20, 5
for step in range(steps):
    out, = exe.run(feed=feed, fetch_list=[loss], sync=False)
    if (step + 1) % log_every == 0:
        float(out)                             # the ONLY materializations
want = steps // log_every
syncs = int(monitor.stat_get("executor.fetch_sync_count"))
blocked = monitor.stat_get("executor.host_blocked_ms")
try:
    assert syncs == want, f"fetch_sync_count {syncs} != {want}"
    assert blocked > 0.0, "host_blocked_ms stat was not emitted"
except AssertionError:
    # a failed budget check ships the full typed snapshot: the ONE line a
    # postmortem needs to see what the loop actually did
    import json, sys
    from paddle_tpu.observability import metrics as obs_metrics
    print("metrics snapshot: " + json.dumps(obs_metrics.snapshot()),
          file=sys.stderr)
    raise
print(f"host-stall budget OK: fetch_sync_count={syncs} "
      f"(= {steps} steps / log every {log_every}), "
      f"host_blocked_ms={blocked:.2f}")
'''


def start_host_stall(env):
    """Launch the host-stall budget script in a fresh interpreter on the
    CPU mesh. Started BEFORE the shard loop so its runtime overlaps the
    shards instead of extending the critical path; collect_host_stall
    reaps it after the shards finish."""
    return subprocess.Popen([sys.executable, "-c", HOST_STALL_CHECK],
                            cwd=ROOT, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)


def collect_host_stall(proc, timeout=600) -> bool:
    """True iff the budget holds. A hung interpreter — the dispatch-stall
    class this check exists for — must record a FAIL, not crash the CI
    driver before its aggregate lines print."""
    try:
        out_s, err_s = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        print(f"[host-stall] FAIL timed out after {timeout}s "
              "(wedged dispatch?)")
        return False
    out = (out_s or "").strip()
    # 15 lines: enough stderr for the metrics-snapshot line to survive
    # above the interpreter's traceback on a budget failure
    tail = (err_s or "").strip().splitlines()[-15:]
    status = "OK " if proc.returncode == 0 else "FAIL"
    print(f"[host-stall] {status} {out}" + (
        "\n" + "\n".join(tail) if proc.returncode != 0 else ""))
    return proc.returncode == 0


def host_stall_check(env) -> bool:
    """Serial convenience wrapper (tests / ad-hoc use)."""
    return collect_host_stall(start_host_stall(env))


# Trace-smoke check (ISSUE-8 CI satellite): capture one short traced step
# loop and schema-validate the exported chrome trace — X spans carrying
# ts+dur for stage/dispatch/fetch, thread-name metadata covering every
# span lane, and s/f flow pairs binding dispatch to its fetch — plus a
# flight-recorder dump round-trip. A regression that silently stops
# recording spans (or breaks the export schema) fails CI before the next
# wedge postmortem discovers the black box is empty.
TRACE_SMOKE = r'''
import json, sys, tempfile, threading
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers

x = layers.data(name="x", shape=[8], dtype="float32")
loss = layers.mean(layers.square(layers.fc(x, 8)))
exe = fluid.Executor()
exe.run(fluid.default_startup_program())
feed = {"x": np.ones((4, 8), np.float32)}
exe.run(feed=feed, fetch_list=[loss])              # compile + warm
paddle.profiler.reset_profiler()
from paddle_tpu.observability import flight, trace
flight.clear()
staged = exe.stage(feed)                           # H2D -> "stage" span
for _ in range(3):
    out, = exe.run(feed=staged, fetch_list=[loss], sync=False)
    staged = exe.stage(feed)
t = threading.Thread(target=out.numpy, name="smoke-drain")
t.start(); t.join()
path = tempfile.mktemp(suffix=".json")
trace.export_chrome_trace(path)
try:
    with open(path) as f:
        evs = json.load(f)["traceEvents"]
    spans = [e for e in evs if e.get("ph") == "X"]
    names = {e["name"] for e in spans}
    for want in ("stage", "fetch.materialize"):
        assert want in names, f"missing span {want!r} in {sorted(names)}"
    assert "executor.step" in names and "executor.launch" in names, names
    assert all("ts" in e and "dur" in e for e in spans)
    metas = [e for e in evs if e.get("ph") == "M"
             and e["name"] == "thread_name"]
    assert {e["tid"] for e in spans} <= {e["tid"] for e in metas}, \
        "span lane without thread-name metadata"
    starts = {e["id"]: e for e in evs if e.get("ph") == "s"}
    ends = {e["id"]: e for e in evs if e.get("ph") == "f"}
    linked = set(starts) & set(ends)
    assert linked, "no s/f flow pair in the trace"
    assert any(starts[i]["tid"] != ends[i]["tid"] for i in linked), \
        "no flow crosses threads (dispatch->drain arrow missing)"
    dump = flight.dump("ci_trace_smoke", path=tempfile.mktemp(".json"))
    assert dump, "flight recorder dump returned None"
    with open(dump) as f:
        fr = json.load(f)
    assert fr["steps"] and fr["trace_events"] and fr["metrics"]
except AssertionError:
    from paddle_tpu.observability import metrics as obs_metrics
    print("metrics snapshot: " + json.dumps(obs_metrics.snapshot()),
          file=sys.stderr)
    raise
print(f"trace smoke OK: {len(spans)} spans, {len(linked)} flow pair(s), "
      f"{len(fr['steps'])} flight step(s)")
'''


def start_trace_smoke(env):
    return subprocess.Popen([sys.executable, "-c", TRACE_SMOKE],
                            cwd=ROOT, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)


def collect_trace_smoke(proc, timeout=600) -> bool:
    try:
        out_s, err_s = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        print(f"[trace-smoke] FAIL timed out after {timeout}s")
        return False
    out = (out_s or "").strip()
    tail = (err_s or "").strip().splitlines()[-15:]
    status = "OK " if proc.returncode == 0 else "FAIL"
    print(f"[trace-smoke] {status} {out}" + (
        "\n" + "\n".join(tail) if proc.returncode != 0 else ""))
    return proc.returncode == 0


# Pod-trace smoke (ISSUE-11 CI satellite): scripts/pod_trace.py --smoke —
# a REAL 2-process supervised gang (launch.py --collect-dumps) of dp=2
# trainers with an induced straggler; validates the merged pod timeline
# (per-rank lanes, >= 1 cross-rank collective flow pair) and that the
# straggler report names the stalled rank. Overlapped with the shards.
def start_pod_trace_smoke(env):
    script = os.path.join(ROOT, "scripts", "pod_trace.py")
    return subprocess.Popen(
        [sys.executable, script, "--smoke", "--smoke-port", "7461"],
        cwd=ROOT, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)


def collect_pod_trace_smoke(proc, timeout=900) -> bool:
    try:
        out_s, err_s = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        print(f"[pod-trace] FAIL timed out after {timeout}s")
        return False
    lines = (out_s or "").strip().splitlines()
    status = "OK " if proc.returncode == 0 else "FAIL"
    body = "\n".join("    " + ln for ln in lines[-6:])
    tail = (err_s or "").strip().splitlines()[-25:]
    print(f"[pod-trace] {status}\n{body}" + (
        "\n" + "\n".join(tail) if proc.returncode != 0 else ""))
    return proc.returncode == 0


# Collective budget check (ISSUE-5 CI satellite): the per-mesh census of
# scripts/collective_audit.py --assert — the dp rows must carry the
# GROUPED bucket collectives (<= 4 per step, parallel/zero.py), not one
# all-reduce per parameter; ZeRO-1's reduce_scatter/all_gather shape and
# the tp/sp rows are budgeted too. Started alongside the shards so its
# ~2-3 min of compiles overlap instead of extending the critical path.
def start_collective_audit(env, skip_zero_rows=False):
    script = os.path.join(ROOT, "scripts", "collective_audit.py")
    child_env = dict(env)
    child_env["PADDLE_TPU_AUDIT_CHILD"] = "1"  # env already is the CPU mesh
    cmd = [sys.executable, script, "--assert"]
    if skip_zero_rows:
        cmd.append("--skip-zero-rows")
    return subprocess.Popen(cmd,
                            cwd=ROOT, env=child_env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)


def collect_collective_audit(proc, timeout=1500) -> bool:
    try:
        out_s, err_s = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        print(f"[collective-budget] FAIL timed out after {timeout}s")
        return False
    lines = (out_s or "").strip().splitlines()
    status = "OK " if proc.returncode == 0 else "FAIL"
    body = "\n".join("    " + ln for ln in lines)
    tail = (err_s or "").strip().splitlines()[-5:]
    print(f"[collective-budget] {status}\n{body}" + (
        "\n" + "\n".join(tail) if proc.returncode != 0 else ""))
    return proc.returncode == 0


# Program lint (ISSUE-10 CI satellite): scripts/program_lint.py --assert —
# the static analysis sweep over the example-model program zoo (verifier +
# donation/alias + collective-consistency, paddle_tpu/analysis/). Build-only
# (no XLA compiles), so it is the cheapest overlapped check; a failing
# assert prints the typed JSON findings report like the budget checks.
def start_program_lint(env):
    script = os.path.join(ROOT, "scripts", "program_lint.py")
    child_env = dict(env)
    child_env["PADDLE_TPU_AUDIT_CHILD"] = "1"  # env already is the CPU mesh
    return subprocess.Popen([sys.executable, script, "--assert"],
                            cwd=ROOT, env=child_env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)


# Sharding lint (ISSUE-13 CI satellite): the static sharding/plan sweep —
# program_lint.py --sharding runs spec propagation + plan checking over
# the zoo at the representative mesh points (dp=2; dp=2,tp=2) and gates
# rule coverage (--assert-coverage: every zoo op must carry an OpSpec
# sharding rule). Build-only like the base lint; overlapped with the
# shards (--no-sharding-lint to skip).
def start_sharding_lint(env):
    script = os.path.join(ROOT, "scripts", "program_lint.py")
    child_env = dict(env)
    child_env["PADDLE_TPU_AUDIT_CHILD"] = "1"  # env already is the CPU mesh
    return subprocess.Popen(
        [sys.executable, script, "--sharding", "--assert",
         "--assert-coverage"],
        cwd=ROOT, env=child_env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)


def collect_sharding_lint(proc, timeout=900) -> bool:
    try:
        out_s, err_s = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        print(f"[sharding-lint] FAIL timed out after {timeout}s")
        return False
    lines = (out_s or "").strip().splitlines()
    status = "OK " if proc.returncode == 0 else "FAIL"
    body = "\n".join("    " + ln for ln in lines[-14:])
    tail = (err_s or "").strip().splitlines()[-120:]
    print(f"[sharding-lint] {status}\n{body}" + (
        "\n" + "\n".join(tail) if proc.returncode != 0 else ""))
    return proc.returncode == 0


def collect_program_lint(proc, timeout=900) -> bool:
    try:
        out_s, err_s = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        print(f"[program-lint] FAIL timed out after {timeout}s")
        return False
    lines = (out_s or "").strip().splitlines()
    status = "OK " if proc.returncode == 0 else "FAIL"
    body = "\n".join("    " + ln for ln in lines)
    # stderr carries the typed JSON findings report (failing rows only) on
    # a failing assert; 120 lines holds several rows' worth of findings
    tail = (err_s or "").strip().splitlines()[-120:]
    print(f"[program-lint] {status}\n{body}" + (
        "\n" + "\n".join(tail) if proc.returncode != 0 else ""))
    return proc.returncode == 0


# Preemption drill (ISSUE-7 CI satellite): scripts/chaos_smoke.py
# --preemption-drill — SIGTERM-mid-step restart parity plus the ZeRO
# dp=4 -> dp=2 resharded resume, both bit-for-bit (docs/resilience.md
# "Elasticity & preemption"). Overlapped with the shards like the
# collective audit.
def start_preemption_drill(env):
    script = os.path.join(ROOT, "scripts", "chaos_smoke.py")
    return subprocess.Popen(
        [sys.executable, script, "--preemption-drill"],
        cwd=ROOT, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)


def collect_preemption_drill(proc, timeout=1500) -> bool:
    try:
        out_s, err_s = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        print(f"[preemption-drill] FAIL timed out after {timeout}s")
        return False
    lines = (out_s or "").strip().splitlines()
    status = "OK " if proc.returncode == 0 else "FAIL"
    body = "\n".join("    " + ln for ln in lines)
    tail = (err_s or "").strip().splitlines()[-5:]
    print(f"[preemption-drill] {status}\n{body}" + (
        "\n" + "\n".join(tail) if proc.returncode != 0 else ""))
    return proc.returncode == 0


# Serving smoke (ISSUE-14 CI satellite): scripts/serving_smoke.py — boot
# the continuous-batching decode engine, stream 32 concurrent requests
# with staggered arrivals and mixed lengths/sampling, assert all complete,
# TTFT histogram non-empty, ZERO per-token KV-cache copies via the
# compiled-HLO census (serving/audit.py) and zero findings on the static
# donation twin — plus the supervised 2-worker decode gang
# (launch.py-hosted). Overlapped with the shards (--no-serving-smoke).
def start_serving_smoke(env):
    script = os.path.join(ROOT, "scripts", "serving_smoke.py")
    child_env = dict(env)
    child_env["PADDLE_TPU_AUDIT_CHILD"] = "1"  # env already is the CPU mesh
    return subprocess.Popen(
        [sys.executable, script, "--supervised"],
        cwd=ROOT, env=child_env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)


def collect_serving_smoke(proc, timeout=1200) -> bool:
    try:
        out_s, err_s = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        print(f"[serving-smoke] FAIL timed out after {timeout}s")
        return False
    lines = (out_s or "").strip().splitlines()
    status = "OK " if proc.returncode == 0 else "FAIL"
    body = "\n".join("    " + ln for ln in lines[-6:])
    tail = (err_s or "").strip().splitlines()[-25:]
    print(f"[serving-smoke] {status}\n{body}" + (
        "\n" + "\n".join(tail) if proc.returncode != 0 else ""))
    return proc.returncode == 0


# Speculative-decoding smoke (ISSUE-19 CI satellite):
# scripts/serving_smoke.py --spec — run the same mixed greedy + seeded
# top-k traffic through a spec-off and a spec-on engine and assert
# token-for-token bit-parity, acceptance over >= 1 round, zero
# pool-shaped copies in the verify program, and a clean span>1 static
# twin. Overlapped with the shards (--no-spec-smoke to skip).
def start_spec_smoke(env):
    script = os.path.join(ROOT, "scripts", "serving_smoke.py")
    child_env = dict(env)
    child_env["PADDLE_TPU_AUDIT_CHILD"] = "1"  # env already is the CPU mesh
    return subprocess.Popen(
        [sys.executable, script, "--spec"],
        cwd=ROOT, env=child_env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)


def collect_spec_smoke(proc, timeout=1200) -> bool:
    try:
        out_s, err_s = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        print(f"[spec-smoke] FAIL timed out after {timeout}s")
        return False
    lines = (out_s or "").strip().splitlines()
    status = "OK " if proc.returncode == 0 else "FAIL"
    body = "\n".join("    " + ln for ln in lines[-4:])
    tail = (err_s or "").strip().splitlines()[-25:]
    print(f"[spec-smoke] {status}\n{body}" + (
        "\n" + "\n".join(tail) if proc.returncode != 0 else ""))
    return proc.returncode == 0


# Pallas kernel smoke (ISSUE-17 CI satellite): scripts/kernel_smoke.py —
# interpret-mode BITWISE parity of the fused paged-attention decode
# kernel vs the dense-gather oracle (f32/bf16/int8 x block sizes) and of
# the fused flat-bucket optimizer update vs the jitted registry rules,
# plus the decode-window HLO census: zero dense cache-view
# materializations with the kernel on. Overlapped with the shards
# (--no-kernel-smoke to skip).
def start_kernel_smoke(env):
    script = os.path.join(ROOT, "scripts", "kernel_smoke.py")
    child_env = dict(env)
    child_env["PADDLE_TPU_AUDIT_CHILD"] = "1"  # env already is the CPU mesh
    return subprocess.Popen(
        [sys.executable, script],
        cwd=ROOT, env=child_env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)


def collect_kernel_smoke(proc, timeout=1200) -> bool:
    try:
        out_s, err_s = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        print(f"[kernel-smoke] FAIL timed out after {timeout}s")
        return False
    lines = (out_s or "").strip().splitlines()
    status = "OK " if proc.returncode == 0 else "FAIL"
    body = "\n".join("    " + ln for ln in lines[-4:])
    tail = (err_s or "").strip().splitlines()[-25:]
    print(f"[kernel-smoke] {status}\n{body}" + (
        "\n" + "\n".join(tail) if proc.returncode != 0 else ""))
    return proc.returncode == 0


# Serving chaos drill (ISSUE-15 CI satellite): scripts/chaos_smoke.py
# --serving-drill — a FaultPlan kills one of two decode replicas
# mid-stream; the drill pins 0 failed requests, bit-parity vs the
# undisturbed oracle run, exact shed/failover counters, and the killed
# replica's canary-gated resurrection. Overlapped with the shards
# (--no-serving-chaos to skip). ISSUE-19 chains the speculative drill
# onto the same run: draft killed mid-stream (degrade + canary re-arm)
# and a spec-on replica killed mid-window (failover replay parity),
# both bf16 bit-parity vs the spec-off oracle.
def start_serving_chaos(env):
    script = os.path.join(ROOT, "scripts", "chaos_smoke.py")
    return subprocess.Popen(
        [sys.executable, script, "--serving-drill", "--spec-drill"],
        cwd=ROOT, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)


def collect_serving_chaos(proc, timeout=1200) -> bool:
    try:
        out_s, err_s = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        print(f"[serving-chaos] FAIL timed out after {timeout}s")
        return False
    lines = (out_s or "").strip().splitlines()
    status = "OK " if proc.returncode == 0 else "FAIL"
    body = "\n".join("    " + ln for ln in lines[-8:])
    tail = (err_s or "").strip().splitlines()[-25:]
    print(f"[serving-chaos] {status}\n{body}" + (
        "\n" + "\n".join(tail) if proc.returncode != 0 else ""))
    return proc.returncode == 0


# Integrity drill (ISSUE-16 CI satellite): scripts/chaos_smoke.py
# --integrity-drill — four legs over resilience/snapshot.py +
# integrity.py (docs/resilience.md "Snapshots & integrity"): (A) a
# 2-rank gang loses rank 1 mid-run and the full-world relaunch resumes
# it from its buddy's peer-replicated snapshot bit-identically, no disk
# checkpoint; (B) a silent bit flip in one rank's Adam moment is named
# by the divergence sentinel within one fingerprint interval and
# quorum-healed; (C) a NaN batch rolls back + skips bit-identically to
# the never-poisoned schedule; (D) async snapshot capture stays within
# 5% mean step-time overhead. Overlapped with the shards
# (--no-integrity-drill to skip).
def start_integrity_drill(env):
    script = os.path.join(ROOT, "scripts", "chaos_smoke.py")
    return subprocess.Popen(
        [sys.executable, script, "--integrity-drill"],
        cwd=ROOT, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)


def collect_integrity_drill(proc, timeout=1200) -> bool:
    try:
        out_s, err_s = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        print(f"[integrity-drill] FAIL timed out after {timeout}s")
        return False
    lines = (out_s or "").strip().splitlines()
    status = "OK " if proc.returncode == 0 else "FAIL"
    body = "\n".join("    " + ln for ln in lines[-10:])
    tail = (err_s or "").strip().splitlines()[-25:]
    print(f"[integrity-drill] {status}\n{body}" + (
        "\n" + "\n".join(tail) if proc.returncode != 0 else ""))
    return proc.returncode == 0


def shard(files, n):
    """LPT bin packing by weight."""
    bins = [(0.0, []) for _ in range(n)]
    for f in sorted(files, key=lambda f: -WEIGHTS.get(os.path.basename(f),
                                                      10)):
        w = WEIGHTS.get(os.path.basename(f), 10)
        i = min(range(n), key=lambda j: bins[j][0])
        bins[i] = (bins[i][0] + w, bins[i][1] + [f])
    return [b for _, b in bins if b]


def main():
    ap = argparse.ArgumentParser()
    # shards beyond the core count only thrash (XLA CPU uses every core)
    ap.add_argument("-n", type=int, default=max(1, min(6, os.cpu_count()
                                                       or 1)))
    ap.add_argument("--no-host-stall", action="store_true",
                    help="skip the host-stall budget check")
    ap.add_argument("--no-collective-audit", action="store_true",
                    help="skip the collective budget check "
                         "(scripts/collective_audit.py --assert)")
    ap.add_argument("--no-zero-rows", action="store_true",
                    help="keep the collective audit but drop its ZeRO "
                         "stage-2/3 + overlap rows (2 extra compiles)")
    ap.add_argument("--no-preemption-drill", action="store_true",
                    help="skip the preemption drill "
                         "(scripts/chaos_smoke.py --preemption-drill)")
    ap.add_argument("--no-trace-smoke", action="store_true",
                    help="skip the trace-smoke check (capture + schema-"
                         "validate one step trace and a flight dump)")
    ap.add_argument("--no-program-lint", action="store_true",
                    help="skip the static program-lint sweep "
                         "(scripts/program_lint.py --assert)")
    ap.add_argument("--no-sharding-lint", action="store_true",
                    help="skip the static sharding/plan lint sweep "
                         "(scripts/program_lint.py --sharding --assert "
                         "--assert-coverage)")
    ap.add_argument("--no-serving-smoke", action="store_true",
                    help="skip the serving smoke (continuous-batching "
                         "engine + 32 streamed requests + KV copy census "
                         "+ supervised decode gang, "
                         "scripts/serving_smoke.py)")
    ap.add_argument("--no-spec-smoke", action="store_true",
                    help="skip the speculative-decoding smoke (spec-on "
                         "vs spec-off bit-parity + acceptance + verify "
                         "copy census, scripts/serving_smoke.py --spec)")
    ap.add_argument("--no-kernel-smoke", action="store_true",
                    help="skip the Pallas kernel smoke (fused decode + "
                         "optimizer-update interpret parity and the "
                         "dense-gather HLO census, "
                         "scripts/kernel_smoke.py)")
    ap.add_argument("--no-serving-chaos", action="store_true",
                    help="skip the serving chaos drill (replica killed "
                         "mid-decode -> failover bit-parity + "
                         "resurrection, scripts/chaos_smoke.py "
                         "--serving-drill)")
    ap.add_argument("--no-integrity-drill", action="store_true",
                    help="skip the integrity drill (peer-snapshot "
                         "recovery + divergence sentinel + poison-batch "
                         "rollback + snapshot overhead budget, "
                         "scripts/chaos_smoke.py --integrity-drill)")
    ap.add_argument("--no-pod-trace", action="store_true",
                    help="skip the pod-trace smoke (2-process supervised "
                         "gang -> merged timeline + straggler report, "
                         "scripts/pod_trace.py --smoke)")
    ap.add_argument("rest", nargs="*", help="extra pytest args")
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(ROOT, "tests"))
    from conftest import cpu_mesh_env
    env = cpu_mesh_env(8)
    env["PADDLE_TPU_TEST_REEXEC"] = "1"

    stall_proc = None
    if not args.no_host_stall:
        stall_proc = start_host_stall(env)   # overlaps the shards below
    audit_proc = None
    if not args.no_collective_audit:
        audit_proc = start_collective_audit(       # overlaps the shards too
            env, skip_zero_rows=args.no_zero_rows)
    drill_proc = None
    if not args.no_preemption_drill:
        drill_proc = start_preemption_drill(env)   # overlaps the shards too
    smoke_proc = None
    if not args.no_trace_smoke:
        smoke_proc = start_trace_smoke(env)        # overlaps the shards too
    lint_proc = None
    if not args.no_program_lint:
        lint_proc = start_program_lint(env)        # overlaps the shards too
    shard_lint_proc = None
    if not args.no_sharding_lint:
        shard_lint_proc = start_sharding_lint(env)  # overlaps the shards
    pod_proc = None
    if not args.no_pod_trace:
        pod_proc = start_pod_trace_smoke(env)      # overlaps the shards too
    serving_proc = None
    if not args.no_serving_smoke:
        serving_proc = start_serving_smoke(env)    # overlaps the shards too
    spec_proc = None
    if not args.no_spec_smoke:
        spec_proc = start_spec_smoke(env)          # overlaps the shards too
    kernel_proc = None
    if not args.no_kernel_smoke:
        kernel_proc = start_kernel_smoke(env)      # overlaps the shards too
    chaos_proc = None
    if not args.no_serving_chaos:
        chaos_proc = start_serving_chaos(env)      # overlaps the shards too
    integrity_proc = None
    if not args.no_integrity_drill:
        integrity_proc = start_integrity_drill(env)   # overlaps the shards

    files = sorted(glob.glob(os.path.join(ROOT, "tests", "test_*.py")))
    shards = shard(files, args.n)
    t0 = time.time()
    procs = []
    for i, fs in enumerate(shards):
        cmd = [sys.executable, "-m", "pytest", "-q", *args.rest, *fs]
        logp = os.path.join(ROOT, f".ci_shard_{i}.log")
        procs.append((i, fs, logp,
                      subprocess.Popen(cmd, cwd=ROOT, env=env,
                                       stdout=open(logp, "w"),
                                       stderr=subprocess.STDOUT)))
    failed = False
    totals = {}
    for i, fs, logp, p in procs:
        rc = p.wait()
        tail = ""
        try:
            with open(logp) as f:
                text = f.read()
            tail = "".join(text.splitlines(keepends=True)[-3:])
            # pytest's final summary line: "N passed, M skipped, K warnings
            # in 12.3s" — aggregate across shards so the round notes can
            # quote ONE line that matches the artifacts byte-for-byte
            lines = text.splitlines()
            m = re.findall(
                r"(\d+) (passed|failed|errors?|skipped|warnings?|"
                r"xfailed|xpassed|deselected)", lines[-1]) if lines else []
            for n, kind in m:
                kind = {"error": "errors", "warning": "warnings"}.get(
                    kind, kind)
                totals[kind] = totals.get(kind, 0) + int(n)
        except OSError:
            pass
        status = "OK " if rc == 0 else "FAIL"
        print(f"[shard {i}] {status} rc={rc} files={len(fs)}\n{tail}")
        failed = failed or rc != 0
    kinds = ["passed", "failed", "skipped", "warnings"]
    kinds += sorted(k for k in totals if k not in kinds)
    agg = ", ".join(f"{totals.get(k, 0)} {k}" for k in kinds)
    print(f"CI aggregate: {agg}")
    if stall_proc is not None:
        failed = failed or not collect_host_stall(stall_proc)
    if audit_proc is not None:
        failed = failed or not collect_collective_audit(audit_proc)
    if drill_proc is not None:
        failed = failed or not collect_preemption_drill(drill_proc)
    if smoke_proc is not None:
        failed = failed or not collect_trace_smoke(smoke_proc)
    if lint_proc is not None:
        failed = failed or not collect_program_lint(lint_proc)
    if shard_lint_proc is not None:
        failed = failed or not collect_sharding_lint(shard_lint_proc)
    if pod_proc is not None:
        failed = failed or not collect_pod_trace_smoke(pod_proc)
    if serving_proc is not None:
        failed = failed or not collect_serving_smoke(serving_proc)
    if spec_proc is not None:
        failed = failed or not collect_spec_smoke(spec_proc)
    if kernel_proc is not None:
        failed = failed or not collect_kernel_smoke(kernel_proc)
    if chaos_proc is not None:
        failed = failed or not collect_serving_chaos(chaos_proc)
    if integrity_proc is not None:
        failed = failed or not collect_integrity_drill(integrity_proc)
    print(f"CI total: {time.time() - t0:.0f}s over {len(shards)} shards -> "
          f"{'FAILED' if failed else 'PASSED'}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
