"""Share of the device-busy time of a step in the forward pass: the
instructions lowered under the executor's `phase.fwd` (forward and loss
ops), less what JAX marks as a checkpoint's forward run again
(benchmark/step_account.py)."""
from benchmark import step_account


def read(ctx):
    return step_account.share(ctx, phases=("fwd",))
