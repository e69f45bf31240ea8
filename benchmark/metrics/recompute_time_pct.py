"""Share of the device-busy time of a step spent running forward work a
second time to save memory: the instructions JAX marks
`rematted_computation` inside a checkpoint's backward (a `__segment__`'s,
a rolled layer's with `remat`), whatever layer they belong to
(benchmark/step_account.py). A step that recomputes nothing gives
nothing."""
from benchmark import step_account


def read(ctx):
    return step_account.share(ctx, phases=("recompute",)) or None
