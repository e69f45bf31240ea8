"""Device-busy milliseconds per train step: the union of the device
operations' intervals over the traced readings, averaged over the chips,
per step."""


def read(ctx):
    tr = ctx.get("trace")
    if ctx["kind"] != "train" or not tr:
        return None
    return 1e3 * tr["busy_s"] / (ctx["traced_readings"] * ctx["k"])
