"""Share of the device-busy time of a step spent finding the selection:
each query's `topk` best-scored keys of its causal row, as a mask (the
`attn.index.select` scope of the compiled step, benchmark/scopes.py)."""
from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "train":
        return None
    return scopes.share_of_busy(ctx, ("attn.index.select",))
