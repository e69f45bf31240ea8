"""The flash kernels' share of their roofline where q and k are wider than
v (latent attention): the least time the chip could take for the causal
attention of the traced steps (2 matmuls forward and 4 backward at their
true widths, bytes of q, k and of v, o: benchmark/counts_mla_moe.py) over
the three kernels' time in the trace."""
from benchmark import counts, counts_mla_moe, xplane


def read(ctx):
    tr = ctx.get("trace")
    if ctx["kind"] != "train" or not tr:
        return None
    flash = xplane.kernel_seconds(tr, "flash_attention")
    if not flash:
        return None
    flops, nbytes = counts_mla_moe.mla_flash_train_flops_bytes(
        ctx["cfg"], ctx["rows"] // ctx["chips"], ctx["seq"])
    least, _ = counts.roofline_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * ctx["traced_readings"] * ctx["k"] * least / flash
