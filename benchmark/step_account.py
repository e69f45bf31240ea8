"""The device step's account: every instant device 0 was busy in a traced
run, in exactly one row (phase, layer scope).

The program names both in every instruction's `op_name`
(`paddle_tpu/observability/scopes.py`: the executor's `phase.fwd` /
`phase.bwd` / `phase.opt`, JAX's `rematted_computation` for the forward a
checkpoint runs again, and the catalogue of layer scopes). `scopes.py`
joins a trace's device operations with the step's optimized HLO by
instruction name; this file classifies what it joined, once a run, and the
`*_time_pct` readers beside it each add up a few rows.

The rows sum to `trace["busy0_s"]`, the union of device 0's operation
intervals: where two operations overlap (a collective under compute, a
copy under a fusion) the instant goes to the one that started last, so no
instant is counted twice. An instruction without `op_name` metadata, or
whose `op_name` holds no catalogued name, goes to a `none` row: nothing is
left out.

A tree without the catalogue (a parent commit) gives None, and every
reader over it then returns None.
"""
from __future__ import annotations

import glob
import heapq
import os
import re
from collections import defaultdict

from . import common, scopes, xplane

NONE = "none"
PHASES = ("fwd", "bwd", "recompute", "opt", NONE)


def _catalogue():
    try:
        from paddle_tpu.observability import scopes as names
    except ImportError:
        return None
    return names


def exclusive(events: list) -> dict:
    """{name: nanoseconds} of (start, end, name) events, every instant of
    the union of their intervals given to ONE event: of those running,
    the one that started last."""
    events = sorted(events)
    cuts = sorted({t for lo, hi, _ in events for t in (lo, hi)})
    out, running, nxt = defaultdict(float), [], 0
    for a, b in zip(cuts, cuts[1:]):
        while nxt < len(events) and events[nxt][0] <= a:
            lo, hi, name = events[nxt]
            heapq.heappush(running, (-lo, hi, name))
            nxt += 1
        while running and running[0][1] <= a:
            heapq.heappop(running)
        if running:
            out[running[0][2]] += b - a
    return dict(out)


def exclusive_seconds(trace_path: str) -> dict:
    """{instruction name: seconds} on device 0, loop containers left out
    (`xplane.CONTAINERS`), overlapping operations counted once
    (`exclusive`). Sums to `reduce_trace(trace_path)["busy0_s"]`."""
    from jax.profiler import ProfileData
    # the events `xplane.reduce_trace` takes `busy0_s` from
    planes = xplane._device_planes(ProfileData.from_file(trace_path))
    line = xplane._line(planes[0], xplane.OPS_LINE)
    events = [(float(e.start_ns), float(e.start_ns + e.duration_ns),
               e.name.split(" = ", 1)[0].strip().lstrip("%"))
              for e in xplane._leaf_ops(line.events)]
    return {name: ns * 1e-9 for name, ns in exclusive(events).items()}


def _rebuilt_step(ctx: dict):
    """(trace path, step HLO) for a driver whose `ctx` carries neither
    (`drivers/train.py`, the two BERT cells): the newest trace under the
    output root, which this run has just written, and the step compiled
    again by a trainer of the same configuration, traffic and seed (the
    executable comes from the persistent cache, as `calibrate`'s do; one
    module compiled twice has the same instruction names). `table` checks
    the pair against the run's own `busy0_s` before it believes it."""
    cfg = ctx.get("cfg") or {}
    if cfg.get("driver") != "train":
        return None, None
    found = glob.glob(os.path.join(common.OUT_ROOT, "*", "seed*_trace1",
                                   "trace"))
    traces = []
    for logdir in found:
        try:
            traces.append(xplane.newest_trace(logdir))
        except FileNotFoundError:
            pass
    if not traces:
        return None, None
    path = max(traces, key=os.path.getmtime)
    seed = int(re.search(r"seed(\d+)_trace1", path).group(1))
    from .drivers import train
    tr = train.Trainer(cfg, ctx["spec"], seed, ctx["chips"])
    feed, _ = tr.device_feed(0)
    hlo = tr.exe.compiled_hlo(feed, [tr.loss], k=tr.k)
    tr.exe.close()
    return path, hlo


def instructions(ctx: dict):
    """[(seconds, instruction, op_name, phase, scope)] of the traced step,
    longest first, kept in `ctx`; None where the run has no trace, the
    tree no catalogue, or the rows do not sum to the run's busy time."""
    if "_step_account" in ctx:
        return ctx["_step_account"]
    ctx["_step_account"] = None
    names, tr = _catalogue(), ctx.get("trace")
    if names is None or not tr or ctx.get("kind") != "train":
        return None
    path, hlo = ctx.get("trace_path"), ctx.get("step_hlo")
    if not path or not hlo:
        path, hlo = ctx["trace_path"], ctx["step_hlo"] = _rebuilt_step(ctx)
    if not path or not hlo:
        return None
    seconds = exclusive_seconds(path)
    if abs(sum(seconds.values()) - tr["busy0_s"]) > 1e-6 * tr["busy0_s"]:
        return None
    op_names = scopes.instruction_scopes(hlo)
    if 2 * sum(s for i, s in seconds.items() if i in op_names) \
            < tr["busy0_s"]:
        return None         # not this trace's program
    ctx["_step_account"] = sorted(
        ((s, instr, op_name, *names.classify(op_name))
         for instr, s in seconds.items()
         for op_name in [op_names.get(instr, "")]), reverse=True)
    return ctx["_step_account"]


def table(ctx: dict):
    """{(phase, scope): device-0 seconds}, summing to `busy0_s`."""
    rows = instructions(ctx)
    if rows is None:
        return None
    out = defaultdict(float)
    for seconds, _, _, phase, scope in rows:
        out[phase, scope] += seconds
    return dict(out)


def share(ctx: dict, phases=None, layer_scopes=None):
    """Per cent of device 0's busy time in the rows whose phase is one of
    `phases` and whose scope is one of `layer_scopes` (None: any); 0.0
    where no such row ran (a reader of a layer some cells lack turns that into
    None). None where there is no table."""
    rows = table(ctx)
    if rows is None:
        return None
    got = sum(s for (phase, scope), s in rows.items()
              if (phases is None or phase in phases)
              and (layer_scopes is None or scope in layer_scopes))
    return 100.0 * got / ctx["trace"]["busy0_s"]
