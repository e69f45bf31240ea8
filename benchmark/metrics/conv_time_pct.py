"""Share of the device-busy time of a step spent in the short-convolution
mixers: the projection into [B | C | u], both gates with the convolution,
and the output projection, forward and backward (the `conv.in_proj`,
`conv.mix`, `conv.out_proj` scopes of the compiled step,
benchmark/scopes.py)."""
from benchmark import scopes

SCOPES = ("conv.in_proj", "conv.mix", "conv.out_proj")


def read(ctx):
    if ctx["kind"] != "train":
        return None
    return scopes.share_of_busy(ctx, SCOPES)
