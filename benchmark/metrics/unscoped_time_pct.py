"""Share of the device-busy time of a step in instructions that carry no
catalogued layer scope, those without any `op_name` included: what no
layer's metric can see (the `none` scope rows of benchmark/step_account.py,
every phase)."""
from benchmark import step_account


def read(ctx):
    return step_account.share(ctx, layer_scopes=(step_account.NONE,))
