"""The attention layers' flash kernels' share of their roofline beside
short-convolution layers: the least time for six products over the causal
pairs with q, o, dq, dO at the query heads' count and k, v, dk, dv at the KV
heads' (benchmark/counts_conv_gqa.py) over the time of the kernels lowered
under `attn.attend.full`."""
from benchmark import attn_scopes, counts, counts_conv_gqa


def read(ctx):
    if ctx["kind"] != "train":
        return None
    taken = attn_scopes.flash_seconds_under(ctx, "attn.attend.full")
    if not taken:
        return None
    flops, nbytes = counts_conv_gqa.flash_train_flops_bytes(
        ctx["cfg"], ctx["rows"] // ctx["chips"], ctx["seq"])
    least, _ = counts.roofline_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * ctx["traced_readings"] * ctx["k"] * least / taken
