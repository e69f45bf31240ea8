"""Share of the device-busy time of a step spent in the sparse-attention
indexer: its projections, rotary, score products, relu and weighted sum,
the selection, the head-summed target and its loss, forward and backward
(the `attn.index.score`, `attn.index.select`, `attn.index.target`,
`attn.index.loss` scopes of the compiled step, benchmark/scopes.py)."""
from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "train":
        return None
    return scopes.share_of_busy(ctx, ("attn.index.",))
