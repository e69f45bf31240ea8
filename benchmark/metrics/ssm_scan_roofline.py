"""The selective scan's share of its roofline: the least time the chip
could take for the RECURRENCE of the traced steps (state update and
read-out a token, the bytes of x, B, C, dt in and y out, forward and
backward: benchmark/counts_hybrid_ssm.py) over the time under the
`ssm.scan` scope. The chunked form's matmuls are the implementation's and
not counted, so the share cannot pass 100 %."""
from benchmark import counts, counts_hybrid_ssm, scopes


def read(ctx):
    if ctx["kind"] != "train":
        return None
    taken = scopes.group_seconds(ctx, ("ssm.scan",))
    if not taken:
        return None
    flops, nbytes = counts_hybrid_ssm.ssm_scan_train_flops_bytes(
        ctx["cfg"], ctx["rows"] // ctx["chips"], ctx["seq"])
    least, _ = counts.roofline_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * ctx["traced_readings"] * ctx["k"] * least / taken
