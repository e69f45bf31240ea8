"""1 - device-busy time over the traced window (first to last device
operation), averaged over the chips."""


def read(ctx):
    tr = ctx.get("trace")
    if ctx["kind"] != "train" or not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
