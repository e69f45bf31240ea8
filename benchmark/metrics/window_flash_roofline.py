"""The sliding-window layers' flash kernels' share of their roofline: the
least time for the pairs INSIDE the window (benchmark/counts_window_gqa.py)
over the time of the kernels lowered under `attn.attend.window`. Kernels
that walk the whole triangle read about a quarter of `full_flash_roofline`
here."""
from benchmark import attn_scopes


def read(ctx):
    return attn_scopes.flash_roofline_pct(ctx, "sliding_attention")
