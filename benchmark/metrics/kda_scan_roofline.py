"""The gated delta rule's share of its roofline: the least time the chip
could take for the RECURRENCE of the traced steps (decay, read, rank-one
update and read-out a token, the bytes of q, k, v, g, beta in and o out,
forward and backward: benchmark/counts_kda_mla.py) over the time under the
`kda.scan` scope. The chunked form's matmuls and its triangular solve are
the implementation's and not counted, so the share cannot pass 100 %."""
from benchmark import counts, counts_kda_mla, scopes


def read(ctx):
    if ctx["kind"] != "train":
        return None
    taken = scopes.group_seconds(ctx, ("kda.scan",))
    if not taken:
        return None
    flops, nbytes = counts_kda_mla.kda_scan_train_flops_bytes(
        ctx["cfg"], ctx["rows"] // ctx["chips"], ctx["seq"])
    least, _ = counts.roofline_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * ctx["traced_readings"] * ctx["k"] * least / taken
