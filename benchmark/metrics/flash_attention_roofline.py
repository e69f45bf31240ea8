"""The flash kernels' share of their roofline: the least time the chip
could take for the attention of the traced steps (operations and bytes
from shapes, benchmark/counts.py) over the kernels' time in the trace,
the three kernels summed."""
from benchmark import counts, xplane


def read(ctx):
    tr = ctx.get("trace")
    if ctx["kind"] != "train" or not tr:
        return None
    flash = xplane.kernel_seconds(tr, "flash_attention")
    if not flash:
        return None
    cfg = ctx["cfg"]
    flops, nbytes = counts.flash_train_flops_bytes(
        batch=ctx["rows"] // ctx["chips"], heads=cfg["num_attention_heads"],
        seq=ctx["seq"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        layers=cfg["num_hidden_layers"], causal=False)
    least, _ = counts.roofline_seconds(flops, nbytes, ctx["peaks"])
    steps = ctx["traced_readings"] * ctx["k"]
    return 100.0 * steps * least / flash
