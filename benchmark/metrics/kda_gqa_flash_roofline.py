"""The softmax layers' flash kernels' share of their roofline beside
delta-rule layers: the least time for the causal pairs at the held query
heads on the held KV heads, heads of 128, no rotary
(benchmark/counts_kda_gqa.py), over the time of the kernels lowered under
`attn.attend.full`."""
from benchmark import attn_scopes, counts, counts_kda_gqa


def read(ctx):
    if ctx["kind"] != "train":
        return None
    taken = attn_scopes.flash_seconds_under(ctx, "attn.attend.full")
    if not taken:
        return None
    flops, nbytes = counts_kda_gqa.flash_train_flops_bytes(
        ctx["cfg"], ctx["rows"] // ctx["chips"], ctx["seq"])
    least, _ = counts.roofline_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * ctx["traced_readings"] * ctx["k"] * least / taken
