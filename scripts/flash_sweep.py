#!/usr/bin/env python
"""Flash-attention block-size sweep at long sequence (S=1024) on real TPU.

The long-context row is the flash kernels' whole reason to exist (dense
attention OOMs at S=1024 — docs/perf_notes.md), so its MFU is the
long-context story. This harness makes the tuning reproducible: time the
masked BERT S=1024 config across (block_q, block_k) grids and print a
ranked table; export the winner via PADDLE_TPU_FLASH_BLOCK_Q/K or fold it
into the kernel defaults.

One process per chip: this parent never imports JAX, and the sweep points
run as children one at a time, each of which opens the chip, refuses a
non-TPU backend and exits.

Usage: python scripts/flash_sweep.py [--batch 16] [--steps 10]
       [--grid 128,256,512]
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--grid", default="128,256,512,1024")
    ap.add_argument("--seq", type=int, default=1024)
    args = ap.parse_args()
    sizes = [int(s) for s in args.grid.split(",")]

    results = []
    for bq, bk in itertools.product(sizes, repeat=2):
        if bq > args.seq or bk > args.seq:
            continue
        # each point runs in a subprocess: the kernels read the env at
        # import and the executor caches compiled blocks per-process
        env = dict(os.environ)
        env["PADDLE_TPU_FLASH_BLOCK_Q"] = str(bq)
        env["PADDLE_TPU_FLASH_BLOCK_K"] = str(bk)
        code = (
            "import sys; sys.path.insert(0, %r)\n"
            "import json, bench\n"
            "import jax\n"
            "from paddle_tpu import compile_cache\n"
            "if jax.default_backend() != 'tpu':\n"
            "    sys.exit('no TPU: a CPU timing is not a sweep point')\n"
            "compile_cache.enable()\n"
            "tps, mfu, _ = bench.bench_bert(%d, %d, %d, masked=True)\n"
            "print(json.dumps({'tps': tps, 'mfu': mfu}))\n"
            % (ROOT, args.batch, args.seq, args.steps))
        t0 = time.time()
        try:
            proc = subprocess.run([sys.executable, "-c", code], env=env,
                                  capture_output=True, text=True,
                                  timeout=1200)
        except subprocess.TimeoutExpired:
            print(f"bq={bq} bk={bk}: TIMEOUT (>1200s); continuing sweep",
                  file=sys.stderr)
            continue
        line = (proc.stdout.strip().splitlines() or ["{}"])[-1]
        try:
            d = json.loads(line)
        except json.JSONDecodeError:
            d = {}
        if proc.returncode != 0 or "tps" not in d:
            print(f"bq={bq} bk={bk}: FAILED rc={proc.returncode} "
                  f"{proc.stderr.strip()[-200:]}", file=sys.stderr)
            continue
        results.append((d["tps"], d["mfu"], bq, bk))
        print(f"bq={bq:4d} bk={bk:4d}: {d['tps']:9.0f} tok/s  "
              f"mfu={d['mfu']:.4f}  ({time.time() - t0:.0f}s)", flush=True)

    if not results:
        return 1
    results.sort(reverse=True)
    print("\nranked:")
    for tps, mfu, bq, bk in results:
        print(f"  bq={bq:4d} bk={bk:4d}: {tps:9.0f} tok/s  mfu={mfu:.4f}")
    best = results[0]
    print(f"\nbest: PADDLE_TPU_FLASH_BLOCK_Q={best[2]} "
          f"PADDLE_TPU_FLASH_BLOCK_K={best[3]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
