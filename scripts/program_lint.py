#!/usr/bin/env python
"""Program linter: static analysis over the example-model program zoo.

Reference counterpart: the `ir/*_tester.cc` pass testers + OpDesc/OpProto
validation — every reference graph rewrite ships with a static check that
the result is well-formed. This CLI is that check for THIS repo's program
pipeline: it builds the model-program zoo (the examples/ model families,
through fleet minimize with the real pass combinations — AMP, layer scan,
recompute, gradient merge, ZeRO stages 1-3) and runs the full
paddle_tpu/analysis suite over each program WITHOUT compiling anything:

* structural verifier (analysis/verifier.py) over main + startup programs,
* donation/alias prediction + hazards (analysis/alias.py),
* collective-consistency + rank-divergence checks (analysis/collectives.py).

Build-only: the zoo never runs an Executor, so the whole sweep is seconds
of tracing, no XLA compiles. Wired into scripts/ci.py as an overlapped
subprocess (--no-program-lint to skip).

With a mesh point the lint adds the STATIC SHARDING layer
(paddle_tpu/analysis/sharding.py): spec propagation + plan checking —
illegal compositions (stage3+tp), the manual-dp fallback matrix promoted
to build-time warnings naming the op and the runtime counter it predicts,
implicit-reshard/spec-conflict findings, and (--predict) the compile-free
collective/memory cost table (analysis/cost.py). Still build-only: the
whole sweep performs ZERO XLA compiles.

Usage (a CPU tool on any machine: re-execs into a CPU-mesh child when the
environment is not one, and says on stderr that it ran on the CPU):

  python scripts/program_lint.py                # table of findings
  python scripts/program_lint.py --assert       # exit 1 on any error
  python scripts/program_lint.py --json         # typed JSON report
  python scripts/program_lint.py --only zero    # substring filter
  python scripts/program_lint.py --mesh dp=2,tp=2   # + sharding lint
  python scripts/program_lint.py --sharding     # representative mesh sweep
  python scripts/program_lint.py --mesh dp=2 --predict  # + cost table
  python scripts/program_lint.py --stage 3      # extra bert arm @ stage 3
  python scripts/program_lint.py --sharding --assert-coverage
                                 # fail on sharding-rule coverage debt
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


# ---------------------------------------------------------------------------
# the zoo: each builder returns (main, startup, feed_names, fetch_names)
# ---------------------------------------------------------------------------

def _fresh():
    from paddle_tpu.testing import reset_programs
    reset_programs(seed=0)


def _programs():
    import paddle_tpu.fluid as fluid
    return fluid.default_main_program(), fluid.default_startup_program()


def _data_names(program):
    return sorted(v.name for b in program.blocks for v in b.vars.values()
                  if v.is_data)


def build_linreg_sgd():
    import paddle_tpu as paddle
    from paddle_tpu.fluid import layers
    _fresh()
    x = layers.data(name="x", shape=[13], dtype="float32")
    y = layers.data(name="y", shape=[1], dtype="float32")
    loss = layers.mean(layers.square(layers.fc(x, 1) - y))
    paddle.optimizer.SGD(learning_rate=0.05).minimize(loss)
    main, startup = _programs()
    return main, startup, _data_names(main), [loss.name]


def _mlp_loss():
    from paddle_tpu.fluid import layers
    x = layers.data(name="x", shape=[16], dtype="float32")
    y = layers.data(name="y", shape=[1], dtype="float32")
    h1 = layers.fc(x, 32, act="tanh")
    h2 = layers.fc(h1, 32, act="tanh")
    loss = layers.mean(layers.square_error_cost(layers.fc(h2, 1), y))
    return loss, [h1, h2]


def build_mlp_recompute():
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    _fresh()
    loss, ckpts = _mlp_loss()
    fleet.init(is_collective=True)
    s = fleet.DistributedStrategy()
    s.recompute = True
    s.recompute_configs = {"checkpoints": [c.name for c in ckpts]}
    fleet.distributed_optimizer(
        paddle.optimizer.Adam(learning_rate=1e-3), s).minimize(loss)
    main, startup = _programs()
    return main, startup, _data_names(main), [loss.name]


def build_mlp_gradient_merge():
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    _fresh()
    loss, _ = _mlp_loss()
    fleet.init(is_collective=True)
    s = fleet.DistributedStrategy()
    s.gradient_merge = True
    s.gradient_merge_configs = {"k_steps": 4, "avg": True}
    fleet.distributed_optimizer(
        paddle.optimizer.Adam(learning_rate=1e-3), s).minimize(loss)
    main, startup = _programs()
    return main, startup, _data_names(main), [loss.name]


def build_moe_mlp():
    import paddle_tpu as paddle
    from paddle_tpu.fluid import layers
    _fresh()
    x = layers.data(name="x", shape=[16], dtype="float32")
    y = layers.data(name="y", shape=[1], dtype="float32")
    h, aux = layers.switch_moe(x, num_experts=4, d_ff=32)
    loss = layers.mean(layers.square_error_cost(layers.fc(h, 1), y)) \
        + 0.01 * aux
    paddle.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    main, startup = _programs()
    return main, startup, _data_names(main), [loss.name]


def _bert_builder(layer_scan=False, amp=True, zero_stage=0):
    def build():
        import paddle_tpu as paddle
        from paddle_tpu.distributed import fleet
        from paddle_tpu.models import bert
        _fresh()
        cfg = bert.BertConfig(vocab_size=256, hidden_size=16, num_layers=4,
                              num_heads=2, intermediate_size=32,
                              max_position=32, seq_len=8,
                              hidden_dropout=0.1, attention_dropout=0.1)
        ids, labels, loss = bert.build_pretrain_program(cfg)
        fleet.init(is_collective=True)
        s = fleet.DistributedStrategy()
        s.amp = amp
        s.layer_scan = layer_scan
        if zero_stage:
            s.sharding = True
            s.sharding_stage = zero_stage
        fleet.distributed_optimizer(
            paddle.optimizer.Adam(learning_rate=1e-4), s).minimize(loss)
        main, startup = _programs()
        return main, startup, _data_names(main), [loss.name]
    return build


def build_gpt_tiny():
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models import gpt
    _fresh()
    cfg = gpt.GPTConfig(vocab_size=256, hidden_size=16, num_layers=2,
                        num_heads=2, intermediate_size=32, seq_len=16,
                        max_position=32, hidden_dropout=0.0,
                        attention_dropout=0.0)
    tokens, loss = gpt.build_lm_program(cfg)
    fleet.init(is_collective=True)
    fleet.distributed_optimizer(
        paddle.optimizer.Adam(learning_rate=1e-4),
        fleet.DistributedStrategy()).minimize(loss)
    main, startup = _programs()
    return main, startup, _data_names(main), [loss.name]


def build_wide_deep():
    import paddle_tpu as paddle
    from paddle_tpu.models import wide_deep
    _fresh()
    feeds, predict, loss, auc = wide_deep.build_ctr(
        sparse_slots=4, dense_dim=13, vocab_size=1001, emb_dim=8)
    paddle.optimizer.SGD(learning_rate=1e-3).minimize(loss)
    main, startup = _programs()
    return main, startup, _data_names(main), [loss.name, auc.name]


def build_serving_decode():
    """The serving decode step as a static program (the zero-copy twin of
    paddle_tpu/serving/engine.py): paged_cache_update writes the donated
    pools in place, paged_attention reads them — the donation analysis
    must classify the pools as donated written state with NO
    fetch_of_donated / write_after_donate hazard."""
    from paddle_tpu.serving.program import build_decode_step_program
    _fresh()
    feed_names, fetch_names = build_decode_step_program()
    main, startup = _programs()
    return main, startup, feed_names, fetch_names


ZOO = [
    ("linreg_sgd", build_linreg_sgd),
    ("mlp_recompute", build_mlp_recompute),
    ("mlp_gradient_merge", build_mlp_gradient_merge),
    ("moe_mlp", build_moe_mlp),
    ("bert_tiny_amp", _bert_builder()),
    ("bert_tiny_layer_scan", _bert_builder(layer_scan=True)),
    ("bert_tiny_zero1", _bert_builder(zero_stage=1)),
    ("bert_tiny_zero2", _bert_builder(zero_stage=2)),
    ("bert_tiny_zero3_rolled", _bert_builder(layer_scan=True,
                                             zero_stage=3)),
    ("gpt_tiny", build_gpt_tiny),
    ("wide_deep_ctr", build_wide_deep),
    ("serving_decode", build_serving_decode),
]


def lint_one(name, build, mesh_points=(), predict=False) -> dict:
    from paddle_tpu.analysis import (analyze_donation, check_collectives,
                                     collective_sequence, verify_program)
    t0 = time.time()
    main, startup, feed_names, fetch_names = build()
    findings = verify_program(main, feed_names=feed_names,
                              fetch_names=fetch_names)
    findings += [_tag(f, "startup") for f in verify_program(startup)]
    findings += check_collectives(main)
    report = analyze_donation(main, feed_names=feed_names,
                              fetch_names=fetch_names)
    findings += report.findings
    # Plan-point diagnostics stay SEPARATE from program findings: an
    # `illegal_plan` error against the dp=2,tp=2 point is the analysis
    # CORRECTLY rejecting a plan (e.g. stage3+tp), not a defect in the
    # program — --assert gates on program errors; plan errors are the
    # planner's pruning signal and are reported per mesh point.
    sharding_rows = []
    for axes in mesh_points:
        from paddle_tpu.analysis import PlanPoint, predict_cost
        plan = PlanPoint(mesh_axes=dict(axes), batch=8 * plan_dp(axes))
        rep = predict_cost(main, plan, fetch_names=fetch_names)
        srow = {"mesh": dict(axes), "mode": rep.mode,
                "errors": sum(f.severity == "error" for f in rep.findings),
                "warnings": sum(f.severity == "warning"
                                for f in rep.findings),
                "findings": [_tag(f, plan.describe()).to_dict()
                             for f in rep.findings]}
        if predict:
            srow["predicted"] = rep.to_dict()
        sharding_rows.append(srow)
    return {
        "program": name,
        "build_s": round(time.time() - t0, 2),
        "ops": sum(len(b.ops) for b in main.blocks),
        "collectives": len(collective_sequence(main)),
        "donated": len(report.donated),
        "sharding": sharding_rows,
        "errors": sum(f.severity == "error" for f in findings),
        "warnings": sum(f.severity == "warning" for f in findings),
        "findings": [f.to_dict() for f in findings],
    }


def plan_dp(axes) -> int:
    return max(int(axes.get("dp", 1)), 1)


# findings that are COVERAGE DEBT (an op the analysis tables don't know),
# not model findings: --assert-coverage promotes exactly these to fatal so
# the zoo can gate "every op has a spec + sharding rule" in CI
COVERAGE_CHECKS = ("unknown_sharding_rule", "unregistered_op")


def _tag(finding, where):
    finding.message = f"[{where}] {finding.message}"
    return finding


def main():
    ap = argparse.ArgumentParser(
        description="static analysis over the example-model program zoo")
    ap.add_argument("--assert", dest="assert_", action="store_true",
                    help="exit 1 on any error-severity finding")
    ap.add_argument("--json", action="store_true",
                    help="print the typed JSON findings report")
    ap.add_argument("--only", default="",
                    help="substring filter on zoo program names")
    ap.add_argument("--mesh", action="append", default=[],
                    help="mesh point for the sharding lint, e.g. "
                         "dp=2,tp=2 (repeatable)")
    ap.add_argument("--sharding", action="store_true",
                    help="sharding lint at the representative mesh sweep "
                         "(dp=2; dp=2,tp=2) — what CI runs")
    ap.add_argument("--stage", type=int, default=None,
                    help="add a bert arm built at this ZeRO stage")
    ap.add_argument("--predict", action="store_true",
                    help="include the compile-free predict_cost table "
                         "per mesh point (implies --sharding when no "
                         "--mesh given)")
    ap.add_argument("--assert-coverage", dest="assert_coverage",
                    action="store_true",
                    help="exit 1 on sharding-rule/spec coverage debt "
                         "(unknown_sharding_rule / unregistered_op "
                         "warnings) — keeps the op tables closed over "
                         "the zoo")
    args = ap.parse_args()

    from paddle_tpu.analysis.sharding import parse_mesh
    mesh_points = [parse_mesh(m) for m in args.mesh]
    if (args.sharding or args.predict) and not mesh_points:
        mesh_points = [{"dp": 2}, {"dp": 2, "tp": 2}]

    from paddle_tpu.testing import run_as_cpu_tool
    run_as_cpu_tool(1, os.path.abspath(__file__), sys.argv[1:])

    zoo = list(ZOO)
    if args.stage is not None:
        zoo.append((f"bert_tiny_stage{args.stage}",
                    _bert_builder(layer_scan=args.stage >= 3,
                                  zero_stage=args.stage)))

    rows = []
    for name, build in zoo:
        if args.only and args.only not in name:
            continue
        try:
            rows.append(lint_one(name, build, mesh_points=mesh_points,
                                 predict=args.predict))
        except Exception as e:   # a broken build is itself a finding
            rows.append({"program": name, "build_s": 0.0, "ops": 0,
                         "collectives": 0, "donated": 0, "sharding": [],
                         "errors": 1, "warnings": 0,
                         "findings": [{"check": "build_failed",
                                       "severity": "error",
                                       "message": repr(e)[:300]}]})

    n_err = sum(r["errors"] for r in rows)
    n_warn = sum(r["warnings"] for r in rows)
    n_cov = sum(f["check"] in COVERAGE_CHECKS
                for r in rows
                for f in (r["findings"]
                          + [f for s in r.get("sharding", ())
                             for f in s["findings"]]))
    if args.json:
        print(json.dumps({"programs": rows, "errors": n_err,
                          "warnings": n_warn, "coverage_debt": n_cov},
                         indent=1))
    else:
        for r in rows:
            print(f"{r['program']:24s} ops {r['ops']:4d} "
                  f"collectives {r['collectives']:2d} "
                  f"donated {r['donated']:3d} errors {r['errors']:2d} "
                  f"warnings {r['warnings']:3d} ({r['build_s']:.1f}s)")
            for s in r.get("sharding", ()):
                mesh = ",".join(f"{k}={v}" for k, v in s["mesh"].items())
                line = (f"    sharding @{mesh}: mode={s['mode']} "
                        f"plan-errors={s['errors']} "
                        f"plan-warnings={s['warnings']}")
                pred = s.get("predicted")
                if pred:
                    tot = ", ".join(
                        f"{k} x{v['count']} ({v['bytes'] / 1e6:.2f} MB)"
                        for k, v in sorted(pred["totals"].items())) \
                        or "none"
                    tag = "exact" if pred["exact"] else "est"
                    arg_mb = (pred["memory"]["argument_bytes_per_device"]
                              / 1e6)
                    line += (f"\n      predicted[{tag}]: {tot}; "
                             f"arg {arg_mb:.2f} MB/dev")
                print(line)
                for f in s["findings"]:
                    if f["severity"] == "error" or not args.assert_:
                        print(f"      [{f['severity']}] {f['check']}: "
                              f"{f['message'][:150]}")
            for f in r["findings"]:
                if f["severity"] == "error" or not args.assert_:
                    print(f"    [{f['severity']}] {f['check']}: "
                          f"{f['message'][:160]}")
        print(f"program lint: {len(rows)} programs, {n_err} errors, "
              f"{n_warn} warnings, {n_cov} coverage-debt")
    if args.assert_coverage and n_cov:
        # name every offending op on stderr: coverage findings are
        # warnings, which the --assert stdout path suppresses — the CI
        # log must still say exactly which op needs an OpSpec entry
        print(f"sharding-rule coverage debt: {n_cov} finding(s) "
              "(add OpSpec entries in analysis/op_specs.py):",
              file=sys.stderr)
        for r in rows:
            for f in (r["findings"]
                      + [f for s in r.get("sharding", ())
                         for f in s["findings"]]):
                if f["check"] in COVERAGE_CHECKS:
                    print(f"  {r['program']}: [{f['check']}] "
                          f"{f['message'][:160]}", file=sys.stderr)
        return 1
    if args.assert_ and n_err:
        # the typed report is the postmortem artifact — always ship it on
        # a failing assert, like the CI budget checks do. Only the FAILING
        # rows go to stderr: the CI collector tails stderr, and a clean
        # row must never push a failing one out of the window.
        if not args.json:
            bad = [r for r in rows if r["errors"]]
            print(json.dumps({"programs": bad, "errors": n_err},
                             indent=1), file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
