"""Share of the device-busy time of a step spent under
`attn.attend.window`: the sliding-window layers' attention, kernels and
what the op lowers around them (benchmark/scopes.py)."""
from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "train":
        return None
    return scopes.share_of_busy(ctx, ("attn.attend.window",))
