"""The gates' and the convolution's share of their roofline: the least time
the chip could take for one pass over the projection forward and one
backward in every conv layer of the traced steps (22 x 2048 B a token a
layer at bf16, benchmark/counts_conv_gqa.py: the bytes bind) over the time
under the `conv.mix` scope, however many ops implement it."""
from benchmark import counts, counts_conv_gqa, scopes


def read(ctx):
    if ctx["kind"] != "train":
        return None
    taken = scopes.group_seconds(ctx, ("conv.mix",))
    if not taken:
        return None
    flops, nbytes = counts_conv_gqa.conv_mix_train_flops_bytes(
        ctx["cfg"], ctx["rows"] // ctx["chips"], ctx["seq"])
    least, _ = counts.roofline_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * ctx["traced_readings"] * ctx["k"] * least / taken
