"""Share of the device-busy time of a step in the backward pass: the
instructions lowered under the executor's `phase.bwd` (`__vjp__` ops, the
repeated gradients' sums), less the forward a checkpoint runs again there,
which `recompute_time_pct` reads (benchmark/step_account.py)."""
from benchmark import step_account


def read(ctx):
    return step_account.share(ctx, phases=("bwd",))
