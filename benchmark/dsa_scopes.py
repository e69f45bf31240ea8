"""Device time under one `program.name_scope` WITHOUT what an inner scope
names: the selected attention's kernels sit under `attn.attend.sparse`, and
so does the indexer's target, which the attention op computes and names
`attn.index.target` inside it. `scopes.group_seconds` adds scopes; this
file takes one away, from the same join of the trace with the compiled
step."""
from __future__ import annotations

from . import scopes


def seconds_under(ctx: dict, scope: str, without: str) -> float | None:
    """Device-0 seconds of the operations lowered under `scope` and not
    under `without`. None where the run has no trace, no compiled step's
    text, or nothing under `scope` (a parent commit)."""
    if scopes.group_seconds(ctx, (scope,)) is None:
        return None
    names = ctx["_instr_scopes"]
    total = sum(seconds for instr, seconds in ctx["_instr_seconds"].items()
                if scope in names.get(instr, "")
                and without not in names.get(instr, ""))
    return total or None
