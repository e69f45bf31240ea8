"""Share of the device-busy time of a step spent in the linear-attention
(Kimi-delta) layers: the projections, the three short convs with their
norms, the decay gate, the gated delta rule and the gated output
projection, forward and backward (the `kda.proj`, `kda.conv`, `kda.gate`,
`kda.scan`, `kda.out` scopes of the compiled step, benchmark/scopes.py)."""
from benchmark import scopes

SCOPES = ("kda.proj", "kda.conv", "kda.gate", "kda.scan", "kda.out")


def read(ctx):
    if ctx["kind"] != "train":
        return None
    return scopes.share_of_busy(ctx, SCOPES)
