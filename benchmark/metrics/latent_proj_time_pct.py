"""Share of the device-busy time of a step spent in the two projections
around latent experts, x W_a in front of the dispatch and (sum) W_b behind
the combine, forward and backward (the `moe.latent_down` and
`moe.latent_up` scopes of the compiled step, benchmark/scopes.py). A step
without them (a parent commit, experts at the full width) gives nothing."""
from benchmark import scopes

SCOPES = ("moe.latent_down", "moe.latent_up")


def read(ctx):
    if ctx["kind"] != "train":
        return None
    return scopes.share_of_busy(ctx, SCOPES)
