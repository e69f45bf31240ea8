#!/usr/bin/env python
"""The sweep behind `ops/pallas/kda_chunk.py`'s grid: one linear-attention
layer's gated delta rule at the KDA cell's size (`chip_smoke.FULL
["delta"]`), the `jax.numpy` form beside the two kernels at 1, 2, 4, 8 and
16 heads a grid step, milliseconds a launch on the host's clock and the
share of the least time the bytes take; at the first count also the
forward kernel with the solve left out (a wrong answer, timed only: what
the in-kernel solve costs).

    python scripts/kda_scan_sweep.py [--heads 4,8,16,2,1]

`--unbounded B,S,H,D` instead times, at that shape and the shape rule's own
heads a step, the two ways a chunk's decayed products are made: around the
blocks' running sums (a decay bounded at -5.5 a token) and level by level
with no factor above 1 (any decay), both kernels of each (`PERF.md` section
6, PR 51, which also holds the reading of the candidate that lost: a block
against itself summed pair by pair on the VPU).

A time only on a TPU; elsewhere it refuses. Writes
chiprun_out/kda_scan_sweep.json.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402


def unbounded(b, s, h, d):
    shape = dict(b=b, s=s, h=h, d=d, chunk=chip_smoke.FULL["delta"]["chunk"])
    rows = {"shape": shape}
    for form in ("bounded", "exact"):
        rows[form] = chip_smoke.kda_scan_forms(
            **shape, time_xla=form == "exact", exact=form == "exact")
        for name in ("fwd", "bwd"):
            row = rows[form][name]
            print(f"[kda_scan_sweep] {form} {name}: {row['ms_kernel']} ms"
                  + (f"; jax.numpy form {row['ms_xla']}"
                     if "ms_xla" in row else ""), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kda_scan_sweep_unbounded.json", "w") as f:
        json.dump(rows, f, indent=1)
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--heads", default="4,8,16,2,1")
    ap.add_argument("--unbounded", default=None, metavar="B,S,H,D")
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("kda_scan_sweep: needs a TPU", file=sys.stderr)
        return 2
    from paddle_tpu import compile_cache
    compile_cache.enable()
    if args.unbounded:
        return unbounded(*(int(v) for v in args.unbounded.split(",")))
    rows = {}
    for i, heads in enumerate(int(v) for v in args.heads.split(",")):
        try:
            rows[heads] = chip_smoke.kda_scan_forms(
                **chip_smoke.FULL["delta"], heads=heads, time_xla=i == 0,
                without_solve=i == 0)
            said = [f"{name}: {row['ms_kernel']} ms, "
                    f"{row['bytes_least_share']} of its bytes' least time"
                    + "".join(f"; {what} {row[key]}" for key, what in (
                        ("ms_kernel_without_solve", "without the solve"),
                        ("ms_xla", "jax.numpy form")) if key in row)
                    for name, row in rows[heads].items() if name != "plan"]
        except Exception as e:      # a count the chip refuses: the next one
            rows[heads] = {"error": f"{type(e).__name__}: {e}"[:400]}
            said = [rows[heads]["error"]]
        for line in said:
            print(f"[kda_scan_sweep] {heads} heads a step, {line}", flush=True)
        # what is read so far survives a later count's failure
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/kda_scan_sweep.json", "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
