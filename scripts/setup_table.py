"""Where a process's time to its first timed step went, from the program's
own span ring: every microsecond from `startup.boot`'s start to the start
of the window's first root in exactly one row, so the rows sum to the
interval (the benchmark's `setup_s`, give or take the interpreter's own
start).

    python3 scripts/setup_table.py --run <cell> --seed <n> [--seconds 30]
        one benchmark cell with `--trace 1` in THIS process (needs the
        cell's chips), then the table of that run; the ring's set-up part
        goes to chiprun_out/setup_rings/<cell>_seed<n>.json
    python3 scripts/setup_table.py --ring <file>
        the table of a saved ring ({"k", "readings", "spans"})
    python3 scripts/setup_table.py --rehearse <cell> --out <file>
        a CPU rehearsal at the tiny preset, its ring saved the same way
        (times of a CPU say nothing about the chip)

A moment under several spans goes to the innermost (shortest) one, so
the program's own lowering (`executor.lower_block`) comes out of JAX's
`compile.trace` that surrounds it, and a fetched executable
(`compile.backend` with `cache: "hit"`) apart from a built one.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import spans  # noqa: E402  (the readers' own view of the ring)

ROWS = ("boot", "import", "build", "startup run",
        "step: the program's lowering", "step: JAX's tracing and MLIR",
        "step: backend, fetched", "step: backend, built",
        "step: rest of the dispatches", "the harness's own compiles",
        "other spans", "outside spans")


def _row_of(e: dict, by: dict) -> str:
    """The row a moment belongs to when `e` is the innermost span over it."""
    chain = [e]
    while chain[-1]["parent"] is not None and chain[-1]["parent"] in by:
        chain.append(by[chain[-1]["parent"]])
    names = [c["name"] for c in chain]
    if names[-1] == "startup.boot":
        return "boot"
    if names[-1] == "startup.import":
        return "import"
    if {"program.build", "optimizer.minimize"} & set(names):
        return "build"
    root = chain[-1] if names[-1] == "executor.step" else None
    if root is None:
        return ("the harness's own compiles"
                if e["name"].startswith("compile.") else "other spans")
    if root.get("args", {}).get("program") == "startup":
        return "startup run"
    if "executor.lower_block" in names:
        return "step: the program's lowering"
    if e["name"] in ("compile.trace", "compile.lower"):
        return "step: JAX's tracing and MLIR"
    if e["name"] == "compile.backend":
        return ("step: backend, fetched"
                if e.get("args", {}).get("cache") == "hit"
                else "step: backend, built")
    return "step: rest of the dispatches"


def window_start(evs: list, k: int, readings: int) -> float:
    return spans.roots(evs, kind="run_steps", k=k)[-readings]["ts"]


def breakdown(evs: list, k: int, readings: int) -> dict:
    """{row: seconds} over [startup.boot's start, the window's first
    root's start]; the rows of ROWS, summing to the interval."""
    evs = [e for e in evs if e.get("ph") == "X" and "id" in e]
    by = {e["id"]: e for e in evs}
    boot, = [e for e in evs if e["name"] == "startup.boot"]
    lo, hi = boot["ts"], window_start(evs, k, readings)
    clipped = [(max(e["ts"], lo), min(e["ts"] + e["dur"], hi), e)
               for e in evs]
    clipped = [c for c in clipped if c[1] > c[0]]
    cuts = sorted({lo, hi} | {c[0] for c in clipped} | {c[1] for c in clipped})
    starts = sorted(clipped, key=lambda c: c[0])
    out = dict.fromkeys(ROWS, 0.0)
    active, nxt = [], 0
    for a, b in zip(cuts, cuts[1:]):
        while nxt < len(starts) and starts[nxt][0] <= a:
            active.append(starts[nxt])
            nxt += 1
        active = [c for c in active if c[1] > a]
        if not active:
            out["outside spans"] += b - a
            continue
        inner = min(active, key=lambda c: c[2]["dur"])[2]
        out[_row_of(inner, by)] += b - a
    return {r: s * 1e-6 for r, s in out.items()}


def step_compiles(evs: list) -> list:
    """[fun, cache, seconds, fetch_s] of every `compile.backend` under a
    main-program root, oldest first."""
    out = []
    for e in sorted(spans.under_roots(evs, {"compile.backend"},
                                      program="main"),
                    key=lambda e: e["ts"]):
        a = e.get("args", {})
        out.append([a.get("fun"), a.get("cache"), e["dur"] * 1e-6,
                    a.get("fetch_s")])
    return out


def by_op_of_the_step(evs: list) -> list:
    """The `by_op` table of the longest walk that was not for shapes."""
    walks = [e for e in evs if e["name"] == "executor.lower_block"
             and not e["args"]["shapes_only"]]
    return max(walks, key=lambda e: e["dur"])["args"]["by_op"] if walks else []


def cut_to_setup(evs: list, k: int, readings: int) -> list:
    """Set-up's spans and the window's roots with their children: what the
    readers and this table need, without the reference's own jits."""
    evs = [e for e in evs if e.get("ph") == "X" and "id" in e]
    t0 = window_start(evs, k, readings)
    window = {e["id"] for e in spans.roots(evs, kind="run_steps", k=k)
              if e["ts"] >= t0}
    return [e for e in evs if e["ts"] < t0 or e["id"] in window
            or e["parent"] in window]


def print_table(rec: dict, setup_s=None, out=sys.stdout):
    evs, k, n = rec["spans"], rec["k"], rec["readings"]
    rows = breakdown(evs, k, n)
    total = sum(rows.values())
    for name in ROWS:
        print(f"{name:34s} {rows[name]:9.3f}", file=out)
    print(f"{'sum':34s} {total:9.3f}"
          + (f"   (setup_s {setup_s:.3f})" if setup_s is not None else ""),
          file=out)
    for fun, cache, secs, fetch_s in step_compiles(evs):
        print(f"compile.backend {fun}: cache={cache} {secs:.3f}s"
              + (f" fetch_s={fetch_s:.3f}" if fetch_s is not None else ""),
              file=out)
    for op, count, secs in by_op_of_the_step(evs):
        print(f"by_op {op:28s} {count:5d} {secs:9.3f}", file=out)
    return rows


def _series(cell: str, seed: int, trace: int) -> dict:
    from benchmark import common
    with open(os.path.join(common.OUT_ROOT, cell,
                           f"seed{seed}_trace{trace}", "series.json")) as f:
        return json.load(f)


def _ring_of_this_process(k: int, readings: int) -> dict:
    from paddle_tpu.observability import metrics, trace
    return {"k": k, "readings": readings,
            "spans": cut_to_setup(trace.events(), k, readings),
            "metrics": {n: v for n, v in metrics.flat().items()
                        if n.startswith(("compile.", "startup."))}}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--run")
    ap.add_argument("--rehearse")
    ap.add_argument("--ring")
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, default=1)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    if args.ring:
        with open(args.ring) as f:
            print_table(json.load(f))
        return 0
    from benchmark import common, run
    cell = args.run or args.rehearse
    spec = common.find_cell(common.load_manifest(), cell)["traffic_file"]
    if args.run:
        print(run.run_cell(cell, args.seed, args.seconds, args.trace,
                           t_start=run.T_PROCESS_START), flush=True)
        series = _series(cell, args.seed, args.trace)
        setup_s, readings = series["setup_s"], len(series["readings"])
        out = args.out or os.path.join(
            ROOT, "chiprun_out", "setup_rings", f"{cell}_seed{args.seed}.json")
    else:
        from benchmark import rehearse
        tiny = rehearse.tiny_presets(cell)
        spec = dict(spec, **tiny.get("traffic", {}))
        res = run.run_cell(cell, args.seed, args.seconds, 0, rehearsal=tiny)
        setup_s, readings, out = None, res["attempted"], args.out
    rec = _ring_of_this_process(spec["steps_per_reading"], readings)
    if out:
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        with open(out, "w") as f:
            json.dump(rec, f)
    print_table(rec, setup_s, out=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
