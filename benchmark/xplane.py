"""Reduction of a JAX profiler trace (`*.xplane.pb`) to the numbers the
per-layer metrics read: device busy and idle time, time per operation and
per kernel, collective time not hidden behind compute, and the idle gaps
named by what the host was doing in them.

Reads the file with `jax.profiler.ProfileData` and nothing else. Checked
against a small recorded trace in tests/data.
"""
from __future__ import annotations

import glob
import os
import re
import sys
from collections import defaultdict

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast|send|recv)")
_SUFFIX = re.compile(r"\.\d+(?=\.|$)")
MIN_GAP_NS = 20_000          # shorter gaps are launch spacing, not idling
MAX_GAPS_NAMED = 400


def newest_trace(logdir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


# control flow whose event spans the operations it contains, which have
# events of their own: counting both would count the time twice and hide
# every gap inside a loop
CONTAINERS = {"while", "conditional", "call"}


def base_name(name: str) -> str:
    """`%fusion.123 = bf16[..] fusion(..)` -> `fusion`: a TPU trace names
    an operation by its whole HLO line; `%copy.4.1` -> `copy`."""
    name = name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", name) or name    # fusion.9.remat -> fusion.remat


def _leaf_ops(events) -> list:
    return [e for e in events if e.duration_ns > 0
            and base_name(e.name) not in CONTAINERS]


def _intervals(events) -> np.ndarray:
    arr = np.array([(e.start_ns, e.start_ns + e.duration_ns)
                    for e in events if e.duration_ns > 0], dtype=np.float64)
    return arr.reshape(-1, 2)


def union(intervals: np.ndarray) -> np.ndarray:
    """Merged, sorted intervals."""
    if len(intervals) == 0:
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0])]
    out = [list(iv[0])]
    for lo, hi in iv[1:]:
        if lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return np.array(out)


def total(intervals: np.ndarray) -> float:
    return float(np.sum(intervals[:, 1] - intervals[:, 0])) if len(
        intervals) else 0.0


def subtract(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """The part of the merged intervals `a` that no interval of merged
    `b` covers."""
    out = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        i = j
        while i < len(b) and b[i][0] < hi:
            if b[i][0] > cur:
                out.append((cur, b[i][0]))
            cur = max(cur, b[i][1])
            i += 1
        if cur < hi:
            out.append((cur, hi))
    return np.array(out, dtype=np.float64).reshape(-1, 2)


def _line(plane, name):
    for line in plane.lines:
        if line.name == name:
            return line
    return None


def _device_planes(pd):
    found = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            found.append((int(m.group(2)), plane))
    return [p for _, p in sorted(found, key=lambda t: t[0])]


def _host_events(pd):
    """(name, start, end) of every host event that has a duration."""
    names, spans = [], []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.duration_ns > 0:
                    names.append(e.name)
                    spans.append((e.start_ns, e.start_ns + e.duration_ns))
    return names, np.array(spans, dtype=np.float64).reshape(-1, 2)


def _name_gaps(gaps: np.ndarray, names, spans) -> list:
    """Idle seconds by the host span they fell under: for each gap the
    SHORTEST host event that covers at least half of it (a parent always
    covers what its child covers, so the shortest is the most specific),
    or, failing that, the one that overlaps it most."""
    by_name = defaultdict(float)
    if len(gaps) == 0:
        return []
    order = np.argsort(gaps[:, 0] - gaps[:, 1])[:MAX_GAPS_NAMED]
    for lo, hi in gaps[order]:
        label = "(no host event)"
        if len(spans):
            overlap = np.minimum(spans[:, 1], hi) - np.maximum(spans[:, 0], lo)
            half = np.nonzero(overlap >= 0.5 * (hi - lo))[0]
            if len(half):
                dur = spans[half, 1] - spans[half, 0]
                label = names[int(half[np.argmin(dur)])]
            elif overlap.max() > 0:
                label = names[int(np.argmax(overlap))]
        by_name[_clean(label)] += (hi - lo) * 1e-9
    return sorted(by_name.items(), key=lambda kv: -kv[1])


def _clean(label: str) -> str:
    label = label.lstrip("$")
    label = re.sub(r"^.*/([^/ ]+\.py:\d+)", r"\1", label)
    return re.sub(r"[^A-Za-z0-9_.:\-]+", "_", label)[:80]


def reduce_trace(path: str) -> dict:
    """The whole reduction. Times in seconds.

    `window_s`: first to last device operation on device 0. `busy_s`:
    union of device-operation intervals, averaged over the device planes.
    `ops`: seconds per operation base name on device 0, every entry.
    `modules`: seconds and calls per XLA module (jitted program) base name.
    `collective_s` / `collective_exposed_s`: on device 0, collectives in
    all, and the part of them during which no other operation ran.
    `idle_gaps`: [(host span, seconds)] for the longest gaps on device 0.
    """
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = _device_planes(pd)
    if not planes:
        raise RuntimeError(
            f"{path}: no device plane among "
            f"{[p.name for p in pd.planes]}")
    busy, first = [], None
    for idx, plane in enumerate(planes):
        line = _line(plane, OPS_LINE)
        events = _leaf_ops(line.events) if line is not None else []
        merged = union(_intervals(events))
        busy.append(total(merged))
        if idx == 0:
            first = (events, merged)
    events, merged = first
    if len(merged) == 0:
        raise RuntimeError(f"{path}: no operation ran on the device")
    window_ns = merged[-1][1] - merged[0][0]
    ops = defaultdict(float)
    coll, comp = [], []
    for e in events:
        name = base_name(e.name)
        ops[name] += e.duration_ns * 1e-9
        (coll if COLLECTIVE.match(name) else comp).append(
            (e.start_ns, e.start_ns + e.duration_ns))
    coll_u = union(np.array(coll, dtype=np.float64).reshape(-1, 2))
    comp_u = union(np.array(comp, dtype=np.float64).reshape(-1, 2))
    modules = defaultdict(lambda: [0.0, 0])
    mline = _line(planes[0], MODULES_LINE)
    for e in (mline.events if mline is not None else []):
        rec = modules[re.sub(r"\(\d+\)$", "", e.name)]
        rec[0] += e.duration_ns * 1e-9
        rec[1] += 1
    window = np.array([[merged[0][0], merged[-1][1]]])
    gaps = subtract(window, merged)
    gaps = gaps[(gaps[:, 1] - gaps[:, 0]) >= MIN_GAP_NS] if len(gaps) else gaps
    names, spans = _host_events(pd)
    return {
        "devices": len(planes),
        "window_s": window_ns * 1e-9,
        "busy_s": float(np.mean(busy)) * 1e-9,
        "busy0_s": busy[0] * 1e-9,
        "ops": dict(ops),
        "modules": {k: {"seconds": v[0], "calls": v[1]}
                    for k, v in modules.items()},
        "collective_s": total(coll_u) * 1e-9,
        "collective_exposed_s": total(subtract(coll_u, comp_u)) * 1e-9,
        "idle_gaps": _name_gaps(gaps, names, spans),
    }


def kernel_seconds(summary: dict, *substrings: str) -> float:
    """Device seconds of every operation whose name holds a substring."""
    return sum(s for name, s in summary["ops"].items()
               if any(sub in name for sub in substrings))


def breakdown(summary: dict, top: int = 10) -> dict:
    ops = sorted(summary["ops"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in summary["idle_gaps"][:top]]}


def describe(path: str) -> None:
    """Planes, lines and the commonest event names: look at a trace by
    hand before trusting the reduction on a new device."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            counts = defaultdict(lambda: [0, 0.0])
            for e in line.events:
                rec = counts[base_name(e.name)]
                rec[0] += 1
                rec[1] += e.duration_ns * 1e-9
            top = sorted(counts.items(), key=lambda kv: -kv[1][1])[:6]
            print("  LINE", line.name, sum(c[0] for c in counts.values()),
                  [(n, c[0], round(c[1], 5)) for n, c in top])


if __name__ == "__main__":
    describe(sys.argv[1])
