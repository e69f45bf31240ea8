"""Share of the device-busy time of a step spent in dense feed-forward
parts: a dense layer's (`ffn.dense`) and the shared expert beside the
routed ones (`moe.shared`), all phases (benchmark/step_account.py). A
step with neither gives nothing."""
from benchmark import step_account

SCOPES = ("ffn.dense", "moe.shared")


def read(ctx):
    return step_account.share(ctx, layer_scopes=SCOPES) or None
