"""The indexer's products' share of their roofline: the least time the
chip could take for the scores over the causal pairs forward, their
gradients over the selected pairs, and the target's one product over the
selected pairs (benchmark/counts_dsa_gqa.py) over the time under the
`attn.index.score` and `attn.index.target` scopes (projections, rotary,
relu and weighting are in the time and not in the count)."""
from benchmark import counts, counts_dsa_gqa, scopes


def read(ctx):
    if ctx["kind"] != "train":
        return None
    taken = scopes.group_seconds(ctx, ("attn.index.score",
                                       "attn.index.target"))
    if not taken:
        return None
    flops, nbytes = counts_dsa_gqa.index_train_flops_bytes(
        ctx["cfg"], ctx["rows"] // ctx["chips"], ctx["seq"])
    least, _ = counts.roofline_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * ctx["traced_readings"] * ctx["k"] * least / taken
