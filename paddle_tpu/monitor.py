"""Stat registry COMPAT SHIM over observability/metrics.py.

Reference counterpart: platform/monitor.h:34-154 STAT_ADD/STAT_GET (named
int/float counters exported through pybind; e.g. GPU mem watermarks). The
flat float dict this module used to be now lives as a view over the typed
registry: `stat_add` records a counter, `stat_set` a gauge, and every
existing call site (`executor.*`, `resilience.*`,
`executor.zero_manual_fallbacks.*`) therefore lands in the same registry
the tracer/flight recorder snapshot and diff. New code should use
`paddle_tpu.observability.metrics` directly (histograms with p50/p99,
snapshot/delta as plain JSON); the dotted-namespace tables formerly split
across this docstring, docs/perf_notes.md and docs/resilience.md are
consolidated in docs/observability.md.
"""
from __future__ import annotations

from typing import Dict

from .observability import metrics as _metrics


def stat_add(name: str, value: float = 1):
    _metrics.inc(name, value)


def stat_set(name: str, value: float):
    _metrics.set_gauge(name, value)


def stat_get(name: str) -> float:
    return _metrics.get(name)


def stat_reset(name: str = None):
    _metrics.reset(name)


def all_stats() -> Dict[str, float]:
    return _metrics.flat()


def device_memory_stats() -> Dict[str, int]:
    """HBM stats from the runtime (reference STAT_GPU mem watermark)."""
    try:
        import jax
        d = jax.devices()[0]
        ms = d.memory_stats() or {}
        return {k: int(v) for k, v in ms.items()
                if isinstance(v, (int, float))}
    except Exception:
        return {}
