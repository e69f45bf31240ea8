"""Share of the device-busy time of a step spent on the residual stream
itself: every layer's pre-norms and residual adds (BERT: its dropouts,
adds and layer norms), the scope `layer.residual`, all phases
(benchmark/step_account.py)."""
from benchmark import step_account


def read(ctx):
    return step_account.share(ctx, layer_scopes=("layer.residual",)) or None
