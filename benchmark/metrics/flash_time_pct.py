"""Share of the device-busy time of a step spent in the flash attention
kernels (forward, dq, dkdv), from the trace."""
from benchmark import xplane


def read(ctx):
    tr = ctx.get("trace")
    if ctx["kind"] != "train" or not tr:
        return None
    flash = xplane.kernel_seconds(tr, "flash_attention")
    return 100.0 * flash / tr["busy0_s"] if flash else None
