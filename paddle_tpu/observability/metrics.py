"""Typed metrics registry: counters, gauges, histograms under dotted names.

Reference counterpart: platform/monitor.h:34-154 (STAT_ADD/STAT_GET — a
named int/float registry exported through pybind). The repro's old
`monitor.py` was a flat float dict; this registry keeps that module's API
alive as a shim while adding what the flat dict could not express:

* **types** — a counter (monotonic sum: retries, fallbacks, h2d_ms) is not
  a gauge (last value: queue depth) is not a histogram (distribution:
  time to first token, decode-window ms with p50/p99);
* **snapshot/delta views** — the flight recorder diffs two snapshots to
  attribute metric movement to ONE step (observability/flight.py);
* **export** — `snapshot()` is plain JSON (bench.py stamps it into
  every record, flight dumps carry it).

Hot-path cost: one lock + one dict/float op per record (no allocation on
the counter/gauge path), measured ≤5% of step time by
tests/test_observability.py's no-op A/B. Namespaces in use are tabled in
docs/observability.md (`executor.*`, `resilience.*`,
`executor.zero_manual_fallbacks.*`, `trace.*`).
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional

_lock = threading.Lock()

# kind tags (first use wins; stat_add on a gauge still adds — the legacy
# flat-dict semantics the monitor shim promises)
COUNTER = "counter"
GAUGE = "gauge"
HISTOGRAM = "histogram"

# histogram reservoir: percentiles come from the most recent observations
# (a bounded ring), count/sum/min/max from the full stream
_HIST_KEEP = 2048


class _Scalar:
    __slots__ = ("kind", "value")

    def __init__(self, kind: str, value: float = 0.0):
        self.kind = kind
        self.value = value


class _Hist:
    __slots__ = ("count", "total", "min", "max", "ring", "ring_pos")

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self.min = None
        self.max = None
        self.ring: List[float] = []
        self.ring_pos = 0

    def observe(self, v: float):
        self.count += 1
        self.total += v
        if self.min is None or v < self.min:
            self.min = v
        if self.max is None or v > self.max:
            self.max = v
        if len(self.ring) < _HIST_KEEP:
            self.ring.append(v)
        else:
            self.ring[self.ring_pos] = v
            self.ring_pos = (self.ring_pos + 1) % _HIST_KEEP

    def percentiles(self, *qs: float) -> List[Optional[float]]:
        if not self.ring:
            return [None] * len(qs)
        s = sorted(self.ring)           # ONE sort serves every quantile
        return [s[min(len(s) - 1, max(0, int(round(q * (len(s) - 1)))))]
                for q in qs]


_scalars: Dict[str, _Scalar] = {}
_hists: Dict[str, _Hist] = {}


# ---- recording (hot path) ---------------------------------------------------

def inc(name: str, value: float = 1.0):
    """Counter add (monotonic). First use of `name` types it as a counter."""
    with _lock:
        s = _scalars.get(name)
        if s is None:
            _scalars[name] = _Scalar(COUNTER, value)
        else:
            s.value += value


def set_gauge(name: str, value: float):
    """Gauge set (last value wins). First use types `name` as a gauge."""
    with _lock:
        s = _scalars.get(name)
        if s is None:
            _scalars[name] = _Scalar(GAUGE, value)
        else:
            s.value = value


def observe(name: str, value: float):
    """Histogram observation (p50/p99 over a bounded recent window)."""
    with _lock:
        h = _hists.get(name)
        if h is None:
            h = _hists[name] = _Hist()
        h.observe(float(value))


def get(name: str) -> float:
    """Scalar value (counter total / gauge last value); histogram names
    return their observation count; unknown names return 0 (the legacy
    flat-dict contract)."""
    with _lock:
        s = _scalars.get(name)
        if s is not None:
            return s.value
        h = _hists.get(name)
        return float(h.count) if h is not None else 0


def reset(name: Optional[str] = None):
    with _lock:
        if name is None:
            _scalars.clear()
            _hists.clear()
        else:
            _scalars.pop(name, None)
            _hists.pop(name, None)


# ---- views ------------------------------------------------------------------

def flat() -> Dict[str, float]:
    """The legacy monitor.all_stats() view: {name: value} for counters and
    gauges (histograms are typed views — see snapshot())."""
    with _lock:
        return {n: s.value for n, s in _scalars.items()}


def snapshot(percentiles: bool = True) -> Dict[str, dict]:
    """Typed point-in-time view of every metric:

        {"executor.h2d_ms":   {"type": "counter", "value": 12.5},
         "executor.dispatch_queue_depth": {"type": "gauge", "value": 1},
         "serving.ttft_ms": {"type": "histogram", "count": 20,
                             "sum": ..., "min": ..., "max": ...,
                             "p50": ..., "p99": ...}}

    percentiles=False skips the p50/p99 fields — they cost a sort of each
    histogram's reservoir, which the flight recorder's twice-per-step
    delta attribution (count/sum only) must not pay on the hot path.
    """
    with _lock:
        out: Dict[str, dict] = {
            n: {"type": s.kind, "value": s.value}
            for n, s in _scalars.items()}
        for n, h in _hists.items():
            row = {"type": HISTOGRAM, "count": h.count,
                   "sum": h.total, "min": h.min, "max": h.max}
            if percentiles:
                row["p50"], row["p99"] = h.percentiles(0.50, 0.99)
            out[n] = row
        return out


def delta(prev: Dict[str, dict],
          cur: Optional[Dict[str, dict]] = None) -> Dict[str, dict]:
    """What moved between two snapshots (flight-recorder per-step
    attribution): counters/histograms diff their monotonic fields, gauges
    report their current value; metrics that did not move are omitted."""
    cur = snapshot(percentiles=False) if cur is None else cur
    out: Dict[str, dict] = {}
    for name, c in cur.items():
        p = prev.get(name)
        if c["type"] == HISTOGRAM:
            pc = p["count"] if p and p.get("type") == HISTOGRAM else 0
            ps = p["sum"] if p and p.get("type") == HISTOGRAM else 0.0
            if c["count"] != pc:
                out[name] = {"type": HISTOGRAM, "count": c["count"] - pc,
                             "sum": c["sum"] - ps}
        elif c["type"] == GAUGE:
            if p is None or p.get("value") != c["value"]:
                out[name] = {"type": GAUGE, "value": c["value"]}
        else:
            pv = p["value"] if p and "value" in p else 0.0
            if c["value"] != pv:
                out[name] = {"type": COUNTER, "value": c["value"] - pv}
    return out
