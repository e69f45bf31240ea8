"""Share of the device-busy time of a step spent in the state-space
mixers: input projection, causal conv, selective scan, gated norm and
output projection, forward and backward (the `ssm.in_proj`, `ssm.conv`,
`ssm.scan`, `ssm.gate_norm`, `ssm.out_proj` scopes of the compiled step,
benchmark/scopes.py)."""
from benchmark import scopes

SCOPES = ("ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.gate_norm",
          "ssm.out_proj")


def read(ctx):
    if ctx["kind"] != "train":
        return None
    return scopes.share_of_busy(ctx, SCOPES)
