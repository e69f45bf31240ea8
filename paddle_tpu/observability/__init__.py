"""Observability subsystem: typed metrics, step-scoped tracing, flight
recorder (reference aux layer: platform/profiler.cc RecordEvent spans,
device_tracer.cc CUPTI timelines, monitor.h stat registry, tools/
timeline.py — unified here; see docs/observability.md).

Layering:

* `metrics` — counters / gauges / histograms under dotted namespaces with
  snapshot/delta views (plain JSON). `paddle_tpu.monitor` is a compat
  shim over it (stat_add -> counter, stat_set -> gauge).
* `trace` — RecordEvent spans (a tree: id, parent, the root's step),
  instants and cross-thread flow events in a bounded always-on ring, each
  span mirrored as a `pt/<name>` TraceAnnotation into any jax.profiler
  capture; chrome-trace/Perfetto export. `paddle_tpu.profiler`
  (fluid.profiler / paddle.profiler.Profiler) is a compat shim over it.
* `compile_events` — JAX's trace / lower / backend-compile reports as
  `compile.*` child spans; what the persistent cache did for each
  `compile.backend` as its `cache` arg, and as counters.
* `flight` — the last N steps' spans + metric deltas, auto-dumped on step
  watchdog trips, gang failures, and degraded bench rows.
* `podscope` — pod-scale aggregation: N per-rank flight dumps merged into
  ONE clock-aligned Perfetto timeline (per-rank lanes, cross-rank
  collective flow arrows) + collective arrival-skew telemetry and a
  straggler report (the reference's tools/timeline.py multi-device merge,
  at process scope).
"""
from . import metrics  # noqa: F401
from . import trace  # noqa: F401
from . import compile_events  # noqa: F401
from . import flight  # noqa: F401
from . import podscope  # noqa: F401
from .trace import RecordEvent  # noqa: F401
