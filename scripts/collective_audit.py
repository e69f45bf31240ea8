#!/usr/bin/env python
"""Audit the collectives in the compiled sharded train step.

The BASELINE "8→64 chip scaling efficiency" metric cannot be measured in
a single-chip environment, but the thing that DETERMINES it — what
collectives the compiled program runs per step, and how their volume
scales with mesh width — is fully auditable from the optimized HLO on a
virtual device mesh. This script compiles the BERT train step under
several meshes and reports each collective kind, its count, and its
total tensor bytes.

What to expect (and what round-5 runs showed — docs/perf_notes.md
"Collective audit"):

* dp=N: ONE fused tupled all-reduce per step carrying every gradient
  (the program's DataParallel sync; XLA fuses all grads natively — the
  reference needs its fuse_all_reduce_ops pass for this). Bytes are
  constant in N, so ring time approaches a flat 2x gradient bytes as N
  grows: that is the weak-scaling story.
* tp=2: GSPMD inserts the Megatron activation all-reduces (2 per layer
  per direction) plus gather/scatter around the sharded embedding/head.
* sp=4: collective-permute dominates — the ring-attention K/V rotation
  (hops x layers x fwd/bwd), with almost nothing else: sequence
  parallelism rides ICI neighbor links, not global collectives.

The `--assert` mode turns the census into a machine-checkable budget
(per-mesh kind -> max count, max MB — CLOSED lists, an unbudgeted
collective kind appearing is a failure too) and exits non-zero on any
regression; scripts/ci.py runs it next to the host-stall check, so an
ungrouping regression (back to one all-reduce per parameter) can never
land silently. The dp / ZeRO rows DERIVE their expected counts from the
compile-free predictor (`analysis.predict_cost` — see STATIC_BUDGETS
comment), so the static cost model and the runtime census are pinned to
each other and parameterize by world size automatically; the GSPMD
tp/sp rows keep measured static budgets. `--predict` prints the
predicted sequence next to each measured row.

Usage (a CPU tool: re-execs into an 8-device CPU-mesh child when the
environment is not one, and says on stderr that it ran on the CPU):
  python scripts/collective_audit.py [--assert] [--predict]
"""
from __future__ import annotations

import collections
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

DT_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f64": 8,
            "pred": 1, "s8": 1, "u8": 1, "s64": 8, "u64": 8}


def build_step(axes, batch, sp_flag=False, sharding=False, stage=None,
               bucket_mb=None):
    """Build + attach the tiny-BERT train step for one audit row; returns
    {exe, feed, loss, program, plan} — `plan` is the analysis PlanPoint
    mirroring the mesh the step will actually compile on, so the static
    predictor and the HLO census look at the same point."""
    import numpy as np
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import bert
    from paddle_tpu.distributed import fleet
    from paddle_tpu.parallel import build_mesh, DistConfig, attach
    from paddle_tpu.testing import reset_programs
    from paddle_tpu import analysis

    reset_programs(seed=0)
    cfg = bert.BertConfig(vocab_size=512, hidden_size=64, num_layers=2,
                          num_heads=4, intermediate_size=128,
                          max_position=64, seq_len=32, hidden_dropout=0.0,
                          attention_dropout=0.0, sequence_parallel=sp_flag)
    ids, labels, loss = bert.build_pretrain_program(cfg)
    fleet.init(is_collective=True)
    strategy = fleet.DistributedStrategy(
        tensor_parallel_degree=axes.get("tp", 1),
        tensor_parallel_rules=bert.tp_sharding_rules())
    strategy.sharding = sharding                       # ZeRO-1 arm
    if stage is not None:                              # ZeRO-2/3 arms
        strategy.sharding_stage = stage
    if bucket_mb is not None:   # small buckets force the K-bucket pipeline
        strategy.fuse_grad_size_in_mb = bucket_mb
    opt = fleet.distributed_optimizer(
        paddle.optimizer.Adam(learning_rate=1e-3), strategy)
    opt.minimize(loss)
    prog = fluid.default_main_program()
    ndev = 1
    for v in axes.values():
        ndev *= v
    if ndev > 1:
        mesh = build_mesh(devices=jax.devices()[:ndev], **axes)
        attach(prog, DistConfig(
            mesh=mesh, param_rules=bert.tp_sharding_rules(),
            state_specs=dict(getattr(prog, "_zero_state_specs", None)
                             or {})))
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    feed = {"input_ids": np.zeros((batch, 32), np.int64),
            "mlm_labels": np.zeros((batch, 32, 1), np.int64)}
    # the plan mirrors the ATTACHED mesh (the "dp=1" row really compiles
    # on fleet.init's full default mesh), so world-size parameterization
    # is automatic: the same derivation covers dp=2..N
    dist = getattr(prog, "_dist_config", None)
    mesh_axes = {}
    if dist is not None:
        for a, n in dist.resolve_mesh().shape.items():
            if int(n) > 1:
                mesh_axes[a] = int(n)
    plan = analysis.PlanPoint(mesh_axes=mesh_axes,
                              param_rules=bert.tp_sharding_rules(),
                              batch=batch)
    return {"exe": exe, "feed": feed, "loss": loss, "program": prog,
            "plan": plan}


def compiled_text(axes, batch, sp_flag=False, sharding=False, stage=None,
                  bucket_mb=None):
    """Compile one audit row; return optimized HLO (via the public
    Executor.compiled_hlo — no executor internals)."""
    row = build_step(axes, batch, sp_flag=sp_flag, sharding=sharding,
                     stage=stage, bucket_mb=bucket_mb)
    return row["exe"].compiled_hlo(row["feed"], [row["loss"]])


def audit(txt):
    """(kind -> count, kind -> total bytes) over every collective HLO op;
    tuple-typed ops (XLA's fused gradient all-reduce) sum their leaves."""
    counts = collections.Counter()
    byte_tot = collections.Counter()
    for line in txt.splitlines():
        m = re.search(r"%\S+ = (.*?) (all-reduce|all-gather|reduce-scatter|"
                      r"collective-permute|all-to-all)(?:-start)?\(", line)
        if not m:
            continue
        ty, kind = m.groups()
        n_bytes = 0
        for dm in re.finditer(r"(\w+)\[([\d,]*)\]", ty):
            dt, shape = dm.groups()
            n = 1
            for d in shape.split(","):
                if d:
                    n *= int(d)
            n_bytes += n * DT_BYTES.get(dt, 4)
        counts[kind] += 1
        byte_tot[kind] += n_bytes
    return counts, byte_tot


_COLL_RE = re.compile(r"%\S+ = .*? (all-reduce|all-gather|reduce-scatter|"
                      r"collective-permute|all-to-all)(-start|-done)?\(")
_COMPUTE_RE = re.compile(r"%\S+ = .*? (fusion|dot|convolution)\(")


def collective_segments(txt) -> int:
    """Overlap evidence: the number of collective GROUPS separated by real
    compute (fusion/dot) in the optimized module's printed instruction
    order (post-scheduling). A bucket pipeline that emits each sync at its
    bucket's backward-ready point shows K>1 groups interleaved with the
    remaining backward compute (xCxCxC...); a single post-backward
    synchronization wall shows 1-2. On TPU executables the same census
    sees the async -start/-done pairs straddling the compute between
    them — both orders count identically here."""
    segments = 0
    in_group = False
    seen_compute = True
    for line in txt.splitlines():
        if _COLL_RE.search(line):
            if not in_group and seen_compute:
                segments += 1
            in_group = True
            seen_compute = False
        elif _COMPUTE_RE.search(line):
            in_group = False
            seen_compute = True
    return segments


# --assert budgets. Two sources:
#
# 1. DERIVED (the dp / ZeRO rows): `analysis.predict_cost` predicts the
#    manual-dp collective sequence EXACTLY from bucket metadata — the
#    expected-count side of each budget row comes from that prediction
#    (count = predicted count, bytes ceiling = predicted * 1.01), so the
#    static model and the runtime census can never silently drift: a
#    bucketing regression trips the count, a predictor regression trips
#    the same row from the other side. Because the prediction takes the
#    attached mesh as input, these rows are parameterized by world size
#    for free — dp=2..N all derive their own budget (ROADMAP item 5).
# 2. STATIC (tp / sp / mixed rows, below): GSPMD owns collective
#    placement there, the analysis is an estimate (exact=False), so the
#    budgets stay the measured round-6..8 census with headroom.
#
# CLOSED lists either way — an unbudgeted collective kind appearing is a
# failure too. The overlap floors (__min_segments__) are structural
# requirements on SCHEDULING, not on the collective set, and stay static.
STATIC_BUDGETS = {
    # mixed/tp/sp meshes stay on the GSPMD lowering (measured round 6-8)
    "tp=2":        {"all-reduce": (40, 1.0), "all-gather": (55, 2.2),
                    "collective-permute": (16, 0.6)},
    "dp=2 tp=2":   {"all-reduce": (75, 1.0), "all-gather": (55, 2.0),
                    "collective-permute": (20, 0.5),
                    "all-to-all": (12, 0.5)},
    "sp=4":        {"all-reduce": (12, 0.2), "all-gather": (8, 0.7),
                    "collective-permute": (45, 0.8)},
}

# ZeRO-2/3 overlap proof: the bucket collectives must interleave with
# backward compute (collective_segments), never one post-backward wall
MIN_SEGMENTS = {"dp=2 zero2": 4, "dp=2 zero3": 4}

def derive_budget(program, plan, loss_name, label):
    """(budget-or-None, CostReport): the predict_cost-derived budget row
    when the point is exactly predictable; GSPMD rows return None and
    keep their static budgets. The report rides along so --predict does
    not re-run the prediction."""
    from paddle_tpu import analysis
    report = analysis.predict_cost(program, plan, fetch_names=[loss_name],
                                   with_findings=False)
    if not report.exact:
        return None, report
    budget = {}
    for kind, (n, b) in report.totals().items():
        budget[kind] = (n, b * 1.01 / 1e6)
    if label in MIN_SEGMENTS:
        budget["__min_segments__"] = MIN_SEGMENTS[label]
    return budget, report


def check_budget(label, counts, byts, txt=None, budget=None):
    """List of violation strings (empty = within budget)."""
    if budget is None:
        budget = STATIC_BUDGETS.get(label)
    if budget is None:
        return []
    bad = []
    for kind, n in counts.items():
        if kind not in budget:
            bad.append(f"unbudgeted {kind} x{n}")
            continue
        max_n, max_mb = budget[kind]
        if n > max_n:
            bad.append(f"{kind} count {n} > {max_n}")
        if byts[kind] > max_mb * 1e6:
            bad.append(f"{kind} {byts[kind] / 1e6:.2f} MB > {max_mb} MB")
    min_seg = budget.get("__min_segments__")
    if min_seg is not None and txt is not None:
        seg = collective_segments(txt)
        if seg < min_seg:
            bad.append(f"collective/compute interleaving: {seg} "
                       f"segment(s) < {min_seg} (bucket pipeline "
                       f"collapsed into a sync wall)")
    return bad


def stall_mode(argv) -> int:
    """`--stall`: the pod-scope arrival-skew census for a dryrun gang.

    Where the default mode audits WHAT collectives the compiled step runs
    (static HLO census), this mode audits WHEN each rank arrives at them:
    it drives the 2-process supervised-gang smoke (scripts/pod_trace.py —
    dp=2 manual-dp workers with an induced straggler) and prints the
    per-collective arrival-skew table + straggler scores from the merged
    pod telemetry (observability/podscope.py; docs/perf_notes.md
    "Collective audit" cross-links here). `--stall-s 0` drills a healthy
    gang instead."""
    stall_s = 0.4
    if "--stall-s" in argv:
        stall_s = float(argv[argv.index("--stall-s") + 1])
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import pod_trace
    from paddle_tpu.observability import podscope
    out = pod_trace.run_smoke(stall_s=stall_s, port=7471,
                              stall_rank=1 if stall_s > 0 else -1)
    dumps = podscope.find_rank_dumps(out["pod_dir"])
    telemetry = podscope.collective_telemetry(dumps)
    print("\nper-collective arrival skew (slowest stalls first):")
    print(podscope.format_stall_table(telemetry, top_k=15))
    return 0


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    assert_mode = "--assert" in argv
    predict_mode = "--predict" in argv
    if "--stall" in argv:
        return stall_mode(argv)
    # --skip-zero-rows (or PADDLE_TPU_AUDIT_SKIP_ZERO=1): drop the ZeRO
    # stage-2/3 + overlap rows (scripts/ci.py --no-zero-rows passes this)
    skip_zero = ("--skip-zero-rows" in argv
                 or os.environ.get("PADDLE_TPU_AUDIT_SKIP_ZERO") == "1")
    from paddle_tpu.testing import run_as_cpu_tool
    run_as_cpu_tool(8, os.path.abspath(__file__), argv)

    import jax
    nd = jax.device_count()
    rows = [({"dp": 1}, 8, {}), ({"dp": 2}, 16, {}),
            ({"dp": 2}, 16, {"sharding": True}),
            # ZeRO-2/3 + overlap rows: a small bucket cap forces a K>1
            # bucket pipeline so the interleaving budget has teeth
            ({"dp": 2}, 16, {"stage": 2, "bucket_mb": 0.15}),
            ({"dp": 2}, 16, {"stage": 3, "bucket_mb": 0.15}),
            ({"dp": 4}, 32, {}), ({"dp": 8}, 64, {}),
            ({"tp": 2}, 8, {}), ({"dp": 2, "tp": 2}, 8, {}),
            ({"sp": 4}, 8, {"sp_flag": True})]
    if skip_zero:
        rows = [r for r in rows if "stage" not in r[2]]
    failures = 0
    for axes, batch, kw in rows:
        needed = 1
        for v in axes.values():
            needed *= v
        if needed > nd:
            print(f"{axes}: skipped (need {needed} devices, have {nd})")
            continue
        desc = " ".join(f"{k}={v}" for k, v in axes.items())
        if kw.get("sharding"):
            desc += " zero1"
        if kw.get("stage"):
            desc += f" zero{kw['stage']}"
        try:
            row = build_step(
                axes, batch, sp_flag=kw.get("sp_flag", False),
                sharding=kw.get("sharding", False),
                stage=kw.get("stage"), bucket_mb=kw.get("bucket_mb"))
            derived, rep = derive_budget(row["program"], row["plan"],
                                         row["loss"].name, desc)
            txt = row["exe"].compiled_hlo(row["feed"], [row["loss"]])
            counts, byts = audit(txt)
        except Exception as e:   # one broken config must not kill the audit
            print(f"{desc:12s} batch {batch:3d}: FAILED ({e!r:.120})")
            if assert_mode and (desc in STATIC_BUDGETS
                                or "tp" not in axes and "sp" not in axes):
                failures += 1
            continue
        summary = ", ".join(
            f"{k} x{counts[k]} ({byts[k] / 1e6:.2f} MB)"
            for k in sorted(counts)) or "none"
        if kw.get("stage"):
            summary += f", {collective_segments(txt)} interleaved segments"
        verdict = ""
        if predict_mode:
            pt = rep.totals()
            verdict = "  predicted[" + ("exact" if rep.exact else "est") \
                + "]: " + (", ".join(
                    f"{k} x{n} ({b / 1e6:.2f} MB)"
                    for k, (n, b) in sorted(pt.items())) or "none")
        if assert_mode:
            bad = check_budget(desc, counts, byts, txt, budget=derived)
            if derived is None and desc not in STATIC_BUDGETS:
                # a dp/ZeRO row that derives no budget means the predictor
                # lost exactness on a manual-dp point (bucketing pass or
                # plan_mode regression) — the row would otherwise pass
                # VACUOUSLY with zero checks, the exact failure mode the
                # budget exists to catch
                bad.append(f"no derived budget (prediction mode="
                           f"{rep.mode}, exact={rep.exact}) — dp/ZeRO "
                           "rows must be exactly predictable")
            if bad:
                failures += 1
                verdict += "  BUDGET FAIL: " + "; ".join(bad)
            elif derived is not None:
                verdict += "  budget OK (predict-derived)"
            elif desc in STATIC_BUDGETS:
                verdict += "  budget OK"
        print(f"{desc:12s} batch {batch:3d}: {summary}{verdict}")
    if assert_mode:
        print(f"collective budget: {'FAILED' if failures else 'PASSED'} "
              f"({failures} row(s) over budget)")
        return 1 if failures else 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
