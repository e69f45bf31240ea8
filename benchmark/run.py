"""One cell, once: `python3 benchmark/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>`. Needs the chips the cell asks for; prints
the contract's JSON object as the last line of stdout."""
from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_cell(workload: str, seed: int, seconds: float, trace: int,
             rehearsal: dict | None = None, t_start: float | None = None,
             root: str | None = None):
    """Drive one cell. `rehearsal`, given only by rehearse.py and the
    tests, skips the look for a chip and shrinks the sizes; its result
    carries no metric."""
    from benchmark import common
    cell, device, driver = common.open_cell(workload, root or common.ROOT,
                                            rehearsal)
    return driver.run(cell, seed=seed, seconds=seconds, trace=trace,
                      device=device, rehearsal=rehearsal,
                      t_start=t_start or time.time())


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    line = run_cell(args.workload, args.seed, args.seconds, args.trace,
                    t_start=T_PROCESS_START)
    sys.stdout.flush()
    print(line, flush=True)


if __name__ == "__main__":
    main()
