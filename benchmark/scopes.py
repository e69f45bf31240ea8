"""Device time of a GROUP of operations of the compiled step, from the raw
trace: which `program.name_scope` / `jax.named_scope` each device operation
was lowered under.

A TPU trace names a device operation by its HLO instruction
(`%fusion.12 = ...`) and says nothing of the program op it came from. The
compiled step's text does: every instruction carries
`metadata={op_name=".../moe.experts/..."}`. So the driver hands the readers
the step's optimized HLO (`Executor.compiled_hlo`, the executable the
window ran, from the executor's own cache) and the path of the trace; this
file joins the two by instruction name. A fusion carries its root's
`op_name`. Kernels that XLA itself makes of one instruction (the grouped
matmul `ragged-dot`) lose the scope and are found by instruction name.

A program without such scopes (a parent commit) gives an empty join: every
reader then returns None.
"""
from __future__ import annotations

import re

from . import xplane

_INSTR = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"',
    re.M)


def instruction_scopes(hlo_text: str) -> dict:
    """{instruction name: its op_name metadata} of an optimized HLO text."""
    return {m.group(1): m.group(2) for m in _INSTR.finditer(hlo_text)}


def instruction_seconds(trace_path: str) -> dict:
    """{instruction name: device seconds} on device 0 over the trace,
    loop containers left out (their bodies' operations are counted)."""
    from jax.profiler import ProfileData
    out = {}
    pd = ProfileData.from_file(trace_path)
    for plane in pd.planes:
        m = xplane.DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(2)) != 0:
            continue
        for line in plane.lines:
            if line.name != xplane.OPS_LINE:
                continue
            for e in line.events:
                name = e.name.split(" = ", 1)[0].strip().lstrip("%")
                if e.duration_ns > 0 \
                        and xplane.base_name(e.name) not in xplane.CONTAINERS:
                    out[name] = out.get(name, 0.0) + e.duration_ns * 1e-9
    return out


def group_seconds(ctx: dict, scopes=(), instructions=()) -> float | None:
    """Device-0 seconds of every operation lowered under one of `scopes`
    (substrings of its op_name) or whose instruction name holds one of
    `instructions`. None where the run has no trace or no HLO, or where
    nothing matches."""
    path, hlo = ctx.get("trace_path"), ctx.get("step_hlo")
    if not path or not hlo:
        return None
    if "_instr_seconds" not in ctx:
        ctx["_instr_seconds"] = instruction_seconds(path)
        ctx["_instr_scopes"] = instruction_scopes(hlo)
    names = ctx["_instr_scopes"]
    total = 0.0
    for instr, seconds in ctx["_instr_seconds"].items():
        op_name = names.get(instr, "")
        if any(s in op_name for s in scopes) \
                or any(i in instr for i in instructions):
            total += seconds
    return total or None


def share_of_busy(ctx: dict, scopes=(), instructions=()) -> float | None:
    """The group's share of device 0's busy time, in per cent."""
    tr = ctx.get("trace")
    got = group_seconds(ctx, scopes, instructions)
    if not tr or got is None:
        return None
    return 100.0 * got / tr["busy0_s"]
