"""Share of the traced stretch in which, on device 0, a collective ran
and no other operation did: the part of the gradient all-reduce that
compute does not hide (`xplane.reduce_trace`'s `collective_exposed_s`)."""


def read(ctx):
    tr = ctx.get("trace")
    if ctx["kind"] != "train" or not tr:
        return None
    return 100.0 * tr["collective_exposed_s"] / tr["window_s"]
