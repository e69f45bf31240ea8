"""What the per-layer readers share to read the PROGRAM's own span tree
(`paddle_tpu/observability/trace.py`): every span is an event with `name`,
`ts` and `dur` in microseconds, an `id`, the `parent` id (None for a
root) and `args`. The root of a dispatch is `executor.step` with args
`step`, `exe`, `kind` ("run" | "run_steps"), `k`, `program` ("startup" |
"main"), `ops`. A program without that tree (a parent commit) yields an
empty list, and every reader then finds nothing and returns None.
"""
from __future__ import annotations

import statistics

ROOT = "executor.step"


def of(ctx: dict) -> list:
    """The spans a reader works on: the ring of this process, read once
    the run is over; a test hands a recorded ring in as ctx["spans"]."""
    if "spans" in ctx:
        return ctx["spans"]
    try:
        from paddle_tpu.observability import trace
    except ImportError:
        return []
    return [e for e in trace.events()
            if e.get("ph") == "X" and "id" in e and "parent" in e]


def _ancestors(e: dict, by: dict):
    p = e["parent"]
    while p is not None and p in by:
        e = by[p]
        yield e
        p = e["parent"]


def outermost(evs: list, names) -> list:
    """Spans called one of `names` that lie under no other such span: the
    fleet wrapper's `optimizer.minimize` holds the inner optimizer's."""
    by = {e["id"]: e for e in evs}
    return [e for e in evs if e["name"] in names
            and not any(a["name"] in names for a in _ancestors(e, by))]


def _step_of(e: dict, by: dict):
    """The `executor.step` a span lies under (or is), if any."""
    for a in [e] + list(_ancestors(e, by)):
        if a["name"] == ROOT:
            return a
    return None


def _matches(root: dict, args: dict) -> bool:
    return all(root.get("args", {}).get(k) == v for k, v in args.items())


def under_roots(evs: list, names, **root_args) -> list:
    """Outermost spans called one of `names` under an `executor.step`
    whose args match `root_args`. What ran under no root (the benchmark's
    reference, its weights) is nobody's layer and left out."""
    by = {e["id"]: e for e in evs}
    out = []
    for e in outermost(evs, names):
        root = _step_of(e, by)
        if root is not None and _matches(root, root_args):
            out.append(e)
    return out


def roots(evs: list, **args) -> list:
    """`executor.step` spans whose args match, oldest first."""
    return sorted((e for e in evs if e["name"] == ROOT and _matches(e, args)),
                  key=lambda e: e["ts"])


def seconds(spans: list) -> float | None:
    return sum(e["dur"] for e in spans) * 1e-6 if spans else None


def window_phase_ms(ctx: dict, phase: str) -> float | None:
    """Median milliseconds of the child span `phase` of the window's
    readings: the last len(ctx["readings"]) `run_steps` roots of the
    cell's `k` (set-up's two calls come before them)."""
    if ctx["kind"] != "train" or not ctx["readings"]:
        return None
    evs = of(ctx)
    window = roots(evs, kind="run_steps", k=ctx["k"])[-len(ctx["readings"]):]
    ids = {r["id"] for r in window}
    durs = [e["dur"] for e in evs
            if e["name"] == phase and e["parent"] in ids]
    return 1e-3 * statistics.median(durs) if durs else None
