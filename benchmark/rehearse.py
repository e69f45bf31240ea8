"""Every cell end to end on the CPU at a tiny preset, before a chip minute
is spent: `python3 benchmark/rehearse.py` (all cells, one child process
each; a four-chip cell on four virtual devices) or `--cell <name>`.

It finds wrong paths, arguments and control flow, and whether `correct`
comes out true at the tiny size. It prints no time, rate or share: a
number from the CPU is never written under the name of a device metric.
The tiny sizes are `rehearsal/<config>.json` and `rehearsal/<traffic>.json`,
found by name like everything else.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def tiny_presets(cell_name: str) -> dict:
    """The tiny sizes of a cell's configuration and traffic mix."""
    from benchmark import common
    cell = common.find_cell(common.load_manifest(), cell_name)
    out = {}
    for kind in ("config", "traffic"):
        path = os.path.join(HERE, "rehearsal", cell[kind] + ".json")
        if not os.path.exists(path):
            raise SystemExit(f"rehearsal: no tiny preset {path}")
        with open(path) as f:
            out[kind] = json.load(f)
    return out


def rehearse_cell(name: str, seconds: float, seed: int, trace: int) -> dict:
    from benchmark import run
    return run.run_cell(name, seed, seconds, trace,
                        rehearsal=tiny_presets(name))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args(argv)
    if args.cell:
        res = rehearse_cell(args.cell, args.seconds, args.seed, args.trace)
        print(json.dumps({k: res[k] for k in
                          ("rehearsal", "correct", "attempted", "failed")}))
        return 0 if res["correct"] and not res["failed"] else 1
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = json.load(f)["workloads"]
    bad = []
    for cell in cells:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count="
                             f"{cell['chips']}")
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--cell",
             cell["name"], "--seconds", str(args.seconds), "--seed",
             str(args.seed), "--trace", str(args.trace)],
            env=env, cwd=ROOT, stdout=subprocess.PIPE, text=True)
        last = (proc.stdout.strip().splitlines() or ["(no output)"])[-1]
        print(f"{cell['name']}: rc={proc.returncode} {last}", flush=True)
        if proc.returncode:
            bad.append(cell["name"])
    print("rehearsal " + ("FAILED: " + ", ".join(bad) if bad else "passed")
          + " (CPU, tiny sizes: says nothing about speed)")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
