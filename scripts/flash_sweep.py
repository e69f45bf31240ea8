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

`--layouts`: the three kernels ALONE (no model around them), in the two
layouts they take, at the shapes of the cells that launch them: BERT's
[32, 512, 12 x 64] with dropout 0.1 and the key-padding mask, and one row of
8,192 on 32-on-4 heads of 128, causal, with a window of 1,024 and without.
Per shape, ms a launch of the forward and of the two backward kernels for:
  heads        q, k, v, dout already [B, nh, S, hd] (what the kernels took
               until PR 52, the relayouts not counted)
  heads+moves  the same from and to [B, S, nh*hd], transposes counted
  rows         layout "bshd", the arrays as the projection leaves them; at
               64 wide the pair's stacked rows (`_stack_pair`, form (b);
               form (a), the one-head body twice over 64-lane slices, lost
               here at PR 52 and was taken out: `PERF.md` section 6)
one child per shape, the variants in turn inside it.
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


LAYOUT_SHAPES = {
    # name: (B, S, nh, nkv, hd, causal, window, dropout, key-padding mask)
    "bert_s512": (32, 512, 12, 12, 64, False, None, 0.1, True),
    "s8192_32on4_window": (1, 8192, 32, 4, 128, True, 1024, 0.0, False),
    "s8192_32on4_full": (1, 8192, 32, 4, 128, True, None, 0.0, False),
}


def time_layouts(name, steps):
    """Child: the variants of one shape on the chip, one JSON line."""
    sys.path.insert(0, ROOT)
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa
    if jax.default_backend() != "tpu":
        sys.exit("no TPU: a CPU timing is not a sweep point")
    b, s, nh, nkv, hd, causal, window, dropout, padded = LAYOUT_SHAPES[name]
    rng = np.random.RandomState(0)

    def rows(heads):
        return jnp.asarray(rng.randn(b, s, heads * hd), jnp.bfloat16)

    q, k, v, do = rows(nh), rows(nkv), rows(nkv), rows(nh)
    mask = None
    if padded:
        lens = rng.randint(s // 2, s + 1, size=b)
        mask = jnp.asarray(np.where(np.arange(s)[None] < lens[:, None], 0.0,
                                    -1e9)[:, None, None, :], jnp.float32)
    kw = dict(causal=causal, window=window, dropout=dropout, mask=mask,
              seed=jnp.int32(7) if dropout else None)

    def split(t, heads, layout):
        t = t.reshape(b, s, heads, hd)
        return t if layout == "bshd" else jnp.swapaxes(t, 1, 2)

    def merge(t, layout):
        if layout != "bshd":
            t = jnp.swapaxes(t, 1, 2)
        return t.reshape(b, s, -1)

    def forward(layout):
        def f(q, k, v):
            out, lse = fa.flash_attention(
                split(q, nh, layout), split(k, nkv, layout),
                split(v, nkv, layout), return_lse=True, layout=layout, **kw)
            return merge(out, layout), lse
        return f

    def backward(layout):
        def f(q, k, v, out, lse, do):
            grads = fa.flash_attention_bwd(
                split(q, nh, layout), split(k, nkv, layout),
                split(v, nkv, layout), split(out, nh, layout), lse,
                split(do, nh, layout), layout=layout, **kw)
            return [merge(t, layout) for t in grads]
        return f

    def ms(fn, *args):
        fn = jax.jit(fn)
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(steps):
            got = fn(*args)
        jax.block_until_ready(got)
        return (time.perf_counter() - t0) / steps * 1e3

    result = {}

    def variant(tag, layout, pre=False):
        args = (q, k, v)
        if pre:          # hand the kernels what the transposes would give
            heads = [jax.jit(lambda t, n=n: jnp.swapaxes(
                t.reshape(b, s, n, hd), 1, 2))(t)
                for t, n in ((q, nh), (k, nkv), (v, nkv), (do, nh))]

            def fwd(q, k, v):
                return fa.flash_attention(q, k, v, return_lse=True, **kw)

            def bwd(q, k, v, out, lse, do):
                return fa.flash_attention_bwd(q, k, v, out, lse, do, **kw)
            out, lse = jax.jit(fwd)(*heads[:3])
            result[tag] = {"fwd_ms": ms(fwd, *heads[:3]),
                           "bwd_ms": ms(bwd, *heads[:3], out, lse, heads[3])}
            return
        out, lse = jax.jit(forward(layout))(*args)
        result[tag] = {"fwd_ms": ms(forward(layout), *args),
                       "bwd_ms": ms(backward(layout), *args, out, lse, do)}

    variant("heads", "bhsd", pre=True)
    variant("heads+moves", "bhsd")
    variant("rows", "bshd")
    print(json.dumps({"shape": name, "steps": steps, **result}))


def sweep_layouts(steps, shapes):
    code = ("import sys; sys.path.insert(0, %r)\n"
            "import flash_sweep\n"
            "flash_sweep.time_layouts(%r, %d)\n")
    failed = 0
    for name in shapes:
        proc = subprocess.run(
            [sys.executable, "-c",
             code % (os.path.join(ROOT, "scripts"), name, steps)],
            capture_output=True, text=True, timeout=1200)
        line = (proc.stdout.strip().splitlines() or [""])[-1]
        if proc.returncode != 0 or not line.startswith("{"):
            print(f"{name}: FAILED rc={proc.returncode} "
                  f"{proc.stderr.strip()[-2000:]}", file=sys.stderr)
            failed += 1
            continue
        print(line, flush=True)
    return 1 if failed else 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layouts", action="store_true",
                    help="time the kernels alone in both layouts")
    ap.add_argument("--shapes", default=",".join(LAYOUT_SHAPES),
                    help="with --layouts: which of " + ", ".join(
                        LAYOUT_SHAPES))
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--grid", default="128,256,512,1024")
    ap.add_argument("--seq", type=int, default=1024)
    args = ap.parse_args()
    if args.layouts:
        return sweep_layouts(args.steps, args.shapes.split(","))
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
