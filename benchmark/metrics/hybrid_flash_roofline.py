"""The attention layers' flash kernels' share of their roofline in a
hybrid LM: the least time for the causal triangle at 32 query heads with k,
v, dk, dv at the 2 KV heads (benchmark/counts_hybrid_ssm.py) over the time
of the kernels lowered under `attn.attend.full`."""
from benchmark import attn_scopes, counts, counts_hybrid_ssm


def read(ctx):
    if ctx["kind"] != "train":
        return None
    taken = attn_scopes.flash_seconds_under(ctx, "attn.attend.full")
    if not taken:
        return None
    flops, nbytes = counts_hybrid_ssm.flash_train_flops_bytes(
        ctx["cfg"], ctx["rows"] // ctx["chips"], ctx["seq"])
    least, _ = counts.roofline_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * ctx["traced_readings"] * ctx["k"] * least / taken
