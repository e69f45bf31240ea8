"""Where a traced step's device time went, by phase and by layer scope:
every instant device 0 was busy in exactly one row (`benchmark/
step_account.py`), so the rows sum to the busy time.

    python3 scripts/step_table.py --run <cell> --seed <n> [--seconds 30]
        one benchmark cell with `--trace 1` in THIS process (needs the
        cell's chips), then the table of that run; its rows go to
        chiprun_out/step_tables/<cell>_seed<n>.json
    python3 scripts/step_table.py --table <file>
        the table of rows saved that way
    python3 scripts/step_table.py --trace <xplane.pb> --hlo <file>
        the table of a saved trace and the step's optimized HLO text
        (`Executor.compiled_hlo`)

Columns: milliseconds a step where the run says how many steps the trace
holds (else over the whole trace), and per cent of device 0's busy time.
Below the table, the named residue of `unscoped_time_pct`: the rows without
a layer scope by kind of instruction (`copy`, `copy-done`, `fusion`, ...:
most are XLA's own, made after the program's names were given out) and the
longest of them by instruction name and `op_name`.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import step_account, xplane  # noqa: E402

KEPT = 400              # instructions saved with a run's rows
RESIDUE = 10            # unscoped instructions printed


def record_of(ctx: dict) -> dict:
    """What `print_table` needs of a run's account, JSON-ready."""
    rows = step_account.instructions(ctx)
    if rows is None:
        raise SystemExit("no account: no trace, no HLO, or a tree without "
                         "paddle_tpu/observability/scopes.py")
    steps = ctx.get("traced_readings", 0) * ctx.get("k", 0)
    unscoped = [list(r) for r in rows if r[4] == step_account.NONE]
    kinds = defaultdict(lambda: [0, 0.0])
    for seconds, instr, *_ in unscoped:
        kind = kinds[xplane.base_name(instr)]
        kind[0] += 1
        kind[1] += seconds
    return {"busy0_s": ctx["trace"]["busy0_s"], "steps": steps or None,
            "table": [[phase, scope, s] for (phase, scope), s
                      in sorted(step_account.table(ctx).items())],
            "instructions": [list(r) for r in rows[:KEPT]],
            "unscoped": unscoped[:KEPT],
            "unscoped_kinds": sorted(
                ([k, n, s] for k, (n, s) in kinds.items()),
                key=lambda row: -row[2])}


def print_table(rec: dict, out=None):
    out = out or sys.stdout
    busy, steps = rec["busy0_s"], rec["steps"]
    per = 1e3 / steps if steps else 1e3
    unit = "ms/step" if steps else "ms"
    cells, scopes = defaultdict(float), defaultdict(float)
    for phase, scope, s in rec["table"]:
        cells[phase, scope] += s
        scopes[scope] += s
    phases = [p for p in step_account.PHASES
              if any(ph == p for ph, _ in cells)]
    print(f"{'scope':22s}" + "".join(f"{p:>11s}" for p in phases)
          + f"{unit:>11s}{'% busy':>8s}", file=out)
    for scope, s in sorted(scopes.items(), key=lambda kv: -kv[1]):
        print(f"{scope:22s}"
              + "".join(f"{cells.get((p, scope), 0.0) * per:11.3f}"
                        for p in phases)
              + f"{s * per:11.3f}{100 * s / busy:8.2f}", file=out)
    by_phase = [sum(s for (ph, _), s in cells.items() if ph == p)
                for p in phases]
    print(f"{'sum':22s}" + "".join(f"{s * per:11.3f}" for s in by_phase)
          + f"{sum(by_phase) * per:11.3f}"
          f"{100 * sum(by_phase) / busy:8.2f}", file=out)
    print(f"{'% busy':22s}"
          + "".join(f"{100 * s / busy:11.2f}" for s in by_phase), file=out)
    print(f"device 0 busy {busy * per:.3f} {unit}; unscoped "
          f"{100 * scopes.get(step_account.NONE, 0.0) / busy:.2f} %, by "
          f"kind of instruction ({unit}, instructions): "
          + ", ".join(f"{kind} {s * per:.3f} ({n})" for kind, n, s
                      in rec.get("unscoped_kinds", [])[:RESIDUE])
          + "; the longest:", file=out)
    for s, instr, op_name, phase, _ in rec["unscoped"][:RESIDUE]:
        print(f"  {s * per:9.3f} {100 * s / busy:6.2f} %  {phase:9s} "
              f"{instr}  {op_name or '(no op_name)'}", file=out)


def _run(cell: str, seed: int, seconds: float) -> dict:
    """The cell with `--trace 1`, the result line printed, the readers'
    `ctx` caught on its way to them."""
    from benchmark import common, run
    caught, inner = {}, common.read_per_layer

    def catching(cell, ctx):
        caught["ctx"] = ctx
        return inner(cell, ctx)

    common.read_per_layer = catching
    try:
        print(run.run_cell(cell, seed, seconds, 1,
                           t_start=run.T_PROCESS_START), flush=True)
    finally:
        common.read_per_layer = inner
    return caught["ctx"]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--run")
    ap.add_argument("--table")
    ap.add_argument("--trace")
    ap.add_argument("--hlo")
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    if args.table:
        with open(args.table) as f:
            print_table(json.load(f))
        return 0
    out = args.out
    if args.run:
        ctx = _run(args.run, args.seed, args.seconds)
        out = out or os.path.join(ROOT, "chiprun_out", "step_tables",
                                  f"{args.run}_seed{args.seed}.json")
    else:
        from benchmark import xplane
        with open(args.hlo) as f:
            hlo = f.read()
        ctx = {"kind": "train", "trace": xplane.reduce_trace(args.trace),
               "trace_path": args.trace, "step_hlo": hlo}
    rec = record_of(ctx)
    if out:
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        with open(out, "w") as f:
            json.dump(rec, f)
    print_table(rec, out=sys.stderr if args.run else sys.stdout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
