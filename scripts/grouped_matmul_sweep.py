#!/usr/bin/env python
"""The sweep that sized `ops/pallas/grouped_matmul.py`'s tiles: the expert
layer's grouped matmuls at the three sparse cells' shapes, XLA's
`ragged_dot` beside the Pallas kernel under the shape rule's tiles, then
the kernel under each alternative of `_alternatives` (the rule's tiles
with one thing moved), milliseconds a launch on the host's clock and the
share of the MXU's bf16 peak (197 TFLOP/s on a v5e) that is. Where the
expert width is no multiple of 128 (the hybrid cell's 1856, one full-width
block under the rule) the last column is the same kernels on operands
padded to the next multiple (1920), its share still of the unpadded
width's operations.

    python scripts/grouped_matmul_sweep.py [cell ...] [--alts rule,tm=512]

A time only on a TPU; elsewhere it refuses. Writes
chiprun_out/grouped_matmul_sweep.json.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402

PEAK = 197e12
CELLS = {"mellum2_12b_ep4_s8192": (65536, 2304, 896, 16),
         "kanana2_30b_a3b_ep8_s4096": (49152, 2048, 768, 16),
         "nemotron_twotower_30b_a3b_ep16_s8192": (49152, 2688, 1856, 8)}


def _alternatives(gm):
    """name -> retile(form, tiles): the rule's tiles with one thing moved."""
    def cut_k(parts):
        def retile(form, t):
            tk = max(d for d in gm._lane_divisors(t.tk)
                     if d <= max(t.tk // parts, 128))
            return t._replace(tk=tk)
        return retile

    def cut_n(form, t):
        return t._replace(tn=128 if t.tn % 256 else 256)

    def rows(tm):
        return lambda form, t: t._replace(tm=tm)

    def resized(retile):
        def with_bytes(form, t):
            t = retile(form, t)
            resident = gm._tgmm_resident if form == "tgmm" \
                else gm._gmm_resident
            return t._replace(resident_bytes=resident(t.tm, t.tk, t.tn, 2, 2))
        return with_bytes

    alts = {"tk/2": cut_k(2), "tn=xla": cut_n, "tm=128": rows(128),
            "tm=512": rows(512), "tm=1024": rows(1024)}
    return {"rule": None, **{k: resized(v) for k, v in alts.items()}}


# the alternatives that cut a width: a width that is one block has no cut
_CUTS_A_WIDTH = ("tk/2", "tn=xla")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cells", nargs="*", default=list(CELLS))
    ap.add_argument("--alts", default="", help="only these, comma-separated")
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("grouped_matmul_sweep: needs a TPU", file=sys.stderr)
        return 2
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    out = {}
    for cell in args.cells:
        rows, d, f, e = CELLS[cell]
        flops = 2 * rows * d * f
        padded = gm._in_vmem(f)
        alts = {k: v for k, v in _alternatives(gm).items()
                if f == padded or k not in _CUTS_A_WIDTH}
        if f != padded:
            alts[f"f={padded}"] = None
        if args.alts:
            alts = {k: alts[k] for k in args.alts.split(",")}
        for alt, retile in alts.items():
            try:
                facts = chip_smoke.grouped_matmul_forms(
                    rows, d, padded if alt.startswith("f=") else f, e,
                    retile, time_xla=alt == "rule")
            except Exception as exc:  # a tile Mosaic refuses is a finding
                print(f"{cell} {alt}: {type(exc).__name__}: "
                      f"{str(exc)[:300]}", flush=True)
                continue
            for name, row in facts.items():
                line = f"{cell} {alt} {name}: tiles {row['tiles'][:3]}"
                for side in ("kernel", "xla"):
                    if "ms_" + side in row:
                        row["mxu_pct_" + side] = round(
                            100 * flops / PEAK / (row["ms_" + side] * 1e-3), 1)
                        line += (f", {side} {row['ms_' + side]} ms "
                                 f"({row['mxu_pct_' + side]} %)")
                print(f"{line}, gap {row['max_abs_diff']:.4g}", flush=True)
            out[f"{cell}.{alt}"] = facts
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/grouped_matmul_sweep.json", "w") as fh:
        json.dump(out, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
