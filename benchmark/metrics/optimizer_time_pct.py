"""Share of the device-busy time of a step spent in the optimizer's update
(the `optimizer.adam` scope of the compiled step, benchmark/scopes.py)."""
from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "train":
        return None
    return scopes.share_of_busy(ctx, ("optimizer.adam",))
