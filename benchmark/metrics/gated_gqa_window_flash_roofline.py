"""The sliding-window layers' flash kernels' share of their roofline where
a layer's kind sets its head count: the least time for the pairs INSIDE
the window at 64 query heads on 8 KV heads
(benchmark/counts_gated_gqa.py) over the time of the kernels lowered under
`attn.attend.window`. Kernels that walk the whole triangle read about an
eighth of `gated_gqa_full_flash_roofline` here."""
from benchmark import attn_scopes, counts, counts_gated_gqa

KIND = "sliding_attention"


def read(ctx):
    if ctx["kind"] != "train":
        return None
    taken = attn_scopes.flash_seconds_under(ctx, attn_scopes.SCOPE[KIND])
    if not taken:
        return None
    flops, nbytes = counts_gated_gqa.flash_train_flops_bytes(
        ctx["cfg"], ctx["rows"] // ctx["chips"], ctx["seq"], KIND)
    least, _ = counts.roofline_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * ctx["traced_readings"] * ctx["k"] * least / taken
