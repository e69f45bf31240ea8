"""The two delta-rule kernels' share of their roofline where the decay has
no bound: the least time the chip could take for the RECURRENCE of the
traced steps (decay, read, rank-one update and read-out a token, the bytes
of q, k, v, g, beta in and o out, forward and backward:
benchmark/counts_kda_gqa.py) over the time of the `kda-chunk-fwd` and
`kda-chunk-bwd` kernels in the trace. The chunked form's level-by-level
products and its solve are the implementation's and not counted, so the
share cannot pass 100 %, and it reads the same yardstick as the bounded
sibling's `kda_scan_roofline`."""
from benchmark import counts, counts_kda_gqa, scopes


def read(ctx):
    if ctx["kind"] != "train":
        return None
    taken = scopes.group_seconds(ctx, (), ("kda-chunk",))
    if not taken:
        return None
    flops, nbytes = counts_kda_gqa.kda_scan_train_flops_bytes(
        ctx["cfg"], ctx["rows"] // ctx["chips"], ctx["seq"])
    least, _ = counts.roofline_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * ctx["traced_readings"] * ctx["k"] * least / taken
