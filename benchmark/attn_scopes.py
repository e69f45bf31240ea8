"""Device time of the attention kernels of ONE kind of layer: the flash
kernels (`flash_attention_fwd`, `_bwd_dq`, `_bwd_dkdv`) whose call was
lowered under a given `program.name_scope` (`attn.attend.window`,
`attn.attend.full`). `scopes.group_seconds` adds a scope's operations OR
a kind of instruction; this file wants the kernels AND the scope, from the
same join of the trace with the compiled step."""
from __future__ import annotations

from . import counts, counts_window_gqa, scopes

SCOPE = {"sliding_attention": "attn.attend.window",
         "full_attention": "attn.attend.full"}


def flash_seconds_under(ctx: dict, scope: str,
                        kernel: str = "flash_attention") -> float | None:
    """Device-0 seconds of the `kernel` instructions lowered under `scope`.
    None where the run has no trace, no compiled step's text, or no such
    kernel (a parent commit, a dense route)."""
    if scopes.group_seconds(ctx, (scope,)) is None:
        return None
    names = ctx["_instr_scopes"]
    total = sum(seconds for instr, seconds in ctx["_instr_seconds"].items()
                if kernel in instr and scope in names.get(instr, ""))
    return total or None


def flash_roofline_pct(ctx: dict, kind: str) -> float | None:
    """The share of their roofline the flash kernels of the layers of
    `kind` reach: the least time for the pairs that kind needs and for q,
    o, dq, dO at the query heads' count and k, v, dk, dv at the KV heads'
    (counts_window_gqa.flash_train_flops_bytes) over their trace time."""
    if ctx["kind"] != "train":
        return None
    taken = flash_seconds_under(ctx, SCOPE[kind])
    if not taken:
        return None
    flops, nbytes = counts_window_gqa.flash_train_flops_bytes(
        ctx["cfg"], ctx["rows"] // ctx["chips"], ctx["seq"], kind)
    least, _ = counts.roofline_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * ctx["traced_readings"] * ctx["k"] * least / taken
