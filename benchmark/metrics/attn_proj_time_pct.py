"""Share of the device-busy time of a step spent around the attention
kernels: the projections into and out of the heads, rotary, the head split
and concat, a per-head norm (`attn.proj`, `mla.proj`, `attn.qk_norm`,
BERT's `attn.mask`), all phases (benchmark/step_account.py). The kernels
themselves are `flash_time_pct`'s."""
from benchmark import step_account

SCOPES = ("attn.proj", "mla.proj", "attn.qk_norm", "attn.mask")


def read(ctx):
    return step_account.share(ctx, layer_scopes=SCOPES) or None
