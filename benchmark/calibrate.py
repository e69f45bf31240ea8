"""Readings for the limits of `correct`, made on the chip at a cell's own
size: `python3 benchmark/calibrate.py --workload <name> --seeds 12
--control-seeds 3`. Writes chiprun_out/calibrate_<name>.json.
The benchmark's own runs never call this; PERF.md records what it read."""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    from benchmark import common
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3000000019)
    ap.add_argument("--cpu-tiny", action="store_true",
                    help="tiny preset on the CPU: checks the path only")
    args = ap.parse_args(argv)
    tiny = None
    if args.cpu_tiny:
        from benchmark.rehearse import tiny_presets
        tiny = tiny_presets(args.workload)
    cell, _, driver = common.open_cell(args.workload, tiny=tiny)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    rows = driver.calibrate(cell, seeds, set(seeds[:args.control_seeds]))
    out = os.path.join(common.ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    path = os.path.join(out, f"calibrate_{args.workload}.json")
    with open(path, "w") as f:
        json.dump(rows, f, indent=1)
    print(json.dumps(rows))


if __name__ == "__main__":
    main()
