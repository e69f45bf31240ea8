"""The full-attention layers' flash kernels' share of their roofline: the
least time for the causal triangle with k, v, dk, dv at the KV heads' count
(benchmark/counts_window_gqa.py) over the time of the kernels lowered under
`attn.attend.full`."""
from benchmark import attn_scopes


def read(ctx):
    return attn_scopes.flash_roofline_pct(ctx, "full_attention")
