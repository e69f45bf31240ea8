#!/usr/bin/env python
"""The sweep behind `ops/pallas/ssm_chunk.py`'s grid: one state-space
layer's selective scan at the hybrid cell's size (`chip_smoke.FULL
["scan"]`), the `jax.numpy` form beside the two kernels with a whole
group's heads a grid step (the shape rule's plan) and with fewer (more
grid steps, the group's scores made again in each), milliseconds a launch
on the host's clock and the share of the least time the bytes take.

    python scripts/ssm_scan_sweep.py [--heads 8,4,2]

A time only on a TPU; elsewhere it refuses. Writes
chiprun_out/ssm_scan_sweep.json.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--heads", default="8,4,2")
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("ssm_scan_sweep: needs a TPU", file=sys.stderr)
        return 2
    from paddle_tpu import compile_cache
    compile_cache.enable()
    rows = {}
    for i, heads in enumerate(int(v) for v in args.heads.split(",")):
        rows[heads] = chip_smoke.ssm_scan_forms(
            **chip_smoke.FULL["scan"], heads=heads, time_xla=i == 0)
        for name in ("fwd", "bwd"):
            row = rows[heads][name]
            print(f"[ssm_scan_sweep] {heads} heads a step, {name}: "
                  f"{row['ms_kernel']} ms, {row['bytes_least_share']} of "
                  f"its bytes' least time"
                  + (f"; jax.numpy form {row['ms_xla']} ms"
                     if "ms_xla" in row else ""), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/ssm_scan_sweep.json", "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
