#!/usr/bin/env python
"""The sweep behind `ops/fused_ce.py`'s `ROW_BLOCK` and its loop order: the
masked-LM head's op alone, forward and forward with backward, at the BERT
cells' shape (`chip_smoke.FULL["head"]`: `[16384, 768] x [768, 30522]`,
kept shares 0.1125, 0.1484 and 1.0) for several row blocks, milliseconds a
launch on the host's clock beside `head.rows_computed_share`; then the
backward in the other loop order (row blocks outside, vocabulary chunks
inside, the whole `[V, H]` float32 weight gradient carried from block to
block) at the file's own `ROW_BLOCK`.

    python scripts/head_rows_sweep.py [--blocks 512,1024,2048,16384]

A block of 16,384 is every row in one block: the op as it was before it
looked at the labels, plus the sort. A time only on a TPU; elsewhere it
refuses. Writes chiprun_out/head_rows_sweep.json.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402


def bwd_rows_outside(chunk, ignore_index, res, g):
    """`fused_ce._ce_bwd` with the loops the other way round."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import fused_ce as ce

    x, w, b, (order, lab_p, blocks, lse_p) = res
    w_chunks, b_chunks, n_chunks, v = ce._pad_w(w, b, chunk)
    h = x.shape[-1]
    xf = x.reshape(-1, h)
    n_rows = xf.shape[0]
    rows = ce._row_block(n_rows)
    xp = ce._pack(xf, order, blocks, rows)
    gp = ce._pack(g.reshape(-1).astype(jnp.float32), order, blocks, rows)
    ignored, valid = ce._token_grade(lab_p, v, ignore_index)
    gp = jnp.where(ignored, 0.0, jnp.where(valid, gp, jnp.nan))

    def block_body(i, carry):
        dxp, dw, db = carry
        x_b, lse_b = ce._cut(xp, i, rows), ce._cut(lse_p, i, rows)
        lab_b, g_b = ce._cut(lab_p, i, rows), ce._cut(gp, i, rows)

        def chunk_body(dx_b, leaves):
            w_c, b_c, idx = leaves
            c0 = idx * chunk
            l_c = ce._chunk_logits(x_b, w_c, b_c, c0, chunk, v)
            onehot = jax.nn.one_hot(lab_b - c0, chunk, dtype=jnp.float32)
            dl = (jnp.exp(l_c - lse_b[:, None]) - onehot) * g_b[:, None]
            dx_b = dx_b + jnp.einsum("rc,ch->rh", dl,
                                     w_c.astype(jnp.float32))
            return dx_b, (jnp.einsum("rc,rh->ch", dl,
                                     x_b.astype(jnp.float32)),
                          jnp.sum(dl, axis=0))

        dx_b, (dw_i, db_i) = jax.lax.scan(
            chunk_body, jnp.zeros((rows, h), jnp.float32),
            (w_chunks, b_chunks, jnp.arange(n_chunks)))
        return ce._put(dxp, dx_b, i, rows), dw + dw_i, db + db_i

    dxp, dw, db = jax.lax.fori_loop(
        0, blocks, block_body,
        (jnp.zeros(order.shape + (h,), jnp.float32),
         jnp.zeros((n_chunks, chunk, h), jnp.float32),
         jnp.zeros((n_chunks, chunk), jnp.float32)))
    dx = ce._unpack(dxp, order, blocks, rows, n_rows,
                    x.dtype).reshape(x.shape)
    return (dx, dw.reshape(n_chunks * chunk, h)[:v].astype(w.dtype),
            db.reshape(n_chunks * chunk)[:v].astype(b.dtype), None)


def said(tag, facts):
    for name, row in facts.items():
        print(f"[head_rows_sweep] {tag} {name}: {row['labelled']} labelled, "
              f"share {row['rows_computed_share']}, fwd {row['ms_fwd']} ms, "
              f"fwd+bwd {row['ms_fwd_bwd']} ms"
              + (f", gaps {row['gaps']}" if "gaps" in row else ""),
              flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", default="512,1024,2048,16384")
    ap.add_argument("--tiny", action="store_true",
                    help="the CPU rehearsal's shapes; its times mean nothing")
    args = ap.parse_args()
    import jax
    if not args.tiny and jax.devices()[0].platform != "tpu":
        print("head_rows_sweep: needs a TPU", file=sys.stderr)
        return 2
    from paddle_tpu import compile_cache
    from paddle_tpu.ops import fused_ce
    compile_cache.enable()
    shape = (chip_smoke.TINY if args.tiny else chip_smoke.FULL)["head"]
    out = {"device": jax.devices()[0].device_kind}

    def keep():
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/head_rows_sweep.json", "w") as f:
            json.dump(out, f, indent=1)

    for block in (int(v) for v in args.blocks.split(",")):
        out[f"chunks_outside_r{block}"] = chip_smoke.head_rows_forms(
            **shape, row_blocks=(block,))
        said("chunks outside", out[f"chunks_outside_r{block}"])
        keep()
    fused_ce._chunked_lm_ce.defvjp(fused_ce._ce_fwd, bwd_rows_outside)
    try:
        out["rows_outside"] = chip_smoke.head_rows_forms(**shape)
    finally:
        fused_ce._chunked_lm_ce.defvjp(fused_ce._ce_fwd, fused_ce._ce_bwd)
    said("rows outside", out["rows_outside"])
    keep()
    return 0


if __name__ == "__main__":
    sys.exit(main())
