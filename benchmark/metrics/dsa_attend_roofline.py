"""The selected attention's share of its roofline: the least time for six
products over the SELECTED pairs with q, o, dq, dO at the query heads'
count and k, v, dk, dv at the KV heads' (benchmark/counts_dsa_gqa.py) over
the time under `attn.attend.sparse` less the indexer's target inside it.
Kernels that walk the whole causal triangle and mask read at most the share
of the pairs that is kept (44 % at 8,192 tokens and 2,048 keys a query)."""
from benchmark import counts, counts_dsa_gqa, dsa_scopes


def read(ctx):
    if ctx["kind"] != "train":
        return None
    taken = dsa_scopes.seconds_under(ctx, "attn.attend.sparse",
                                     without="attn.index.target")
    if not taken:
        return None
    flops, nbytes = counts_dsa_gqa.attend_train_flops_bytes(
        ctx["cfg"], ctx["rows"] // ctx["chips"], ctx["seq"])
    least, _ = counts.roofline_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * ctx["traced_readings"] * ctx["k"] * least / taken
