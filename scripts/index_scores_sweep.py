#!/usr/bin/env python
"""The sweep behind `ops/pallas/index_scores.py`'s tiles: one layer's
indexer at the learned-selection cell's size (`chip_smoke.FULL["index"]`),
the `jax.numpy` form beside the two kernels at several (queries a tile, keys
a tile, query rows an inner step), milliseconds a launch on the host's clock
and the share of the least time the causal pairs' products take.

    python scripts/index_scores_sweep.py [--blocks 512,512,32:256,256,32]

A time only on a TPU; elsewhere it refuses. Writes
chiprun_out/index_scores_sweep.json.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402

BLOCKS = ("512,512,32:256,256,32:512,256,32:256,512,32:512,512,64:"
          "512,512,16:1024,512,32:512,1024,32")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", default=BLOCKS)
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("index_scores_sweep: needs a TPU", file=sys.stderr)
        return 2
    from paddle_tpu import compile_cache
    compile_cache.enable()
    rows = {}
    for i, spec in enumerate(args.blocks.split(":")):
        blocks = tuple(int(v) for v in spec.split(","))
        try:
            rows[spec] = chip_smoke.index_scores_forms(
                **chip_smoke.FULL["index"], blocks=blocks, time_xla=i == 0)
            said = [f"{name}: {row['ms_kernel']} ms, "
                    f"{row['flops_least_share']} of its products' least time"
                    + (f"; jax.numpy form {row['ms_xla']}"
                       if "ms_xla" in row else "")
                    for name, row in rows[spec].items() if name != "plan"]
        except Exception as e:      # tiles the chip refuses: the next ones
            rows[spec] = {"error": f"{type(e).__name__}: {e}"[:400]}
            said = [rows[spec]["error"]]
        for line in said:
            print(f"[index_scores_sweep] blocks {spec}, {line}", flush=True)
        # what is read so far survives a later failure
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/index_scores_sweep.json", "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
