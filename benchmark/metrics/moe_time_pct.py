"""Share of the device-busy time of a step spent in the routed experts:
router, sort, gather, grouped matmuls and combine, forward and backward
(the `moe.route`, `moe.dispatch`, `moe.experts`, `moe.combine` scopes of
the compiled step and XLA's `ragged-dot` kernels, benchmark/scopes.py).
The shared expert is plain matmuls beside them and not in it."""
from benchmark import scopes

SCOPES = ("moe.route", "moe.dispatch", "moe.experts", "moe.combine")


def read(ctx):
    if ctx["kind"] != "train":
        return None
    return scopes.share_of_busy(ctx, SCOPES, ("ragged-dot",))
