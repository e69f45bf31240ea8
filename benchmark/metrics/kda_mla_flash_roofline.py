"""The latent layers' flash kernels' share of their roofline beside
linear-attention layers: the least time for the causal pairs at the held
heads, q and k 192 wide, v and o 128 (benchmark/counts_kda_mla.py), over the
time of the kernels lowered under `mla.attend`."""
from benchmark import attn_scopes, counts, counts_kda_mla


def read(ctx):
    if ctx["kind"] != "train":
        return None
    taken = attn_scopes.flash_seconds_under(ctx, "mla.attend")
    if not taken:
        return None
    flops, nbytes = counts_kda_mla.mla_flash_train_flops_bytes(
        ctx["cfg"], ctx["rows"] // ctx["chips"], ctx["seq"])
    least, _ = counts.roofline_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * ctx["traced_readings"] * ctx["k"] * least / taken
