"""Share of the device-busy time of a step spent at the two ends of the
model: the embedding's gather and its scatter-add (`embed.tokens`), the
final norm, the matmul over the vocabulary held, the cross entropy and the
losses' sum (`head.norm`, `head.untied`, `head.tied`, `head.loss`; BERT's
`head.mlm`), forward, backward and recomputed (benchmark/step_account.py)."""
from benchmark import step_account

SCOPES = ("embed.tokens", "head.norm", "head.untied", "head.tied",
          "head.loss", "head.mlm")


def read(ctx):
    return step_account.share(ctx, layer_scopes=SCOPES) or None
