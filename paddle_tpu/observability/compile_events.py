"""JAX's own compile phases as spans of the program's span tree.

JAX traces, lowers and compiles inside the FIRST CALL of a jitted function
— for the executor, inside the `executor.launch` span of the first
dispatch of a program — and reports each phase through `jax.monitoring`
when it ends. One listener turns each report into a span ending now and
`duration` long, whose parent is the span open on that thread:

    /jax/core/compile/jaxpr_trace_duration          -> compile.trace
    /jax/core/compile/jaxpr_to_mlir_module_duration -> compile.lower
    /jax/core/compile/backend_compile_duration      -> compile.backend

`compile.backend` covers a fetch from the persistent cache as well as a
real compilation; the counters `compile.persistent_cache_hits` /
`compile.persistent_cache_misses` (from `/jax/compilation_cache/*`) tell
the two apart. A report under no span of the program (a user's own `jit`)
yields a span with no parent.

`jaxpr_trace_duration` fires for every nested `jit` (each `jax.numpy`
call inside a traced function is one) before it fires for the outer one.
A trace that ran inside another trace is part of it: only a report made
at the top level of tracing becomes a span, so a sum over `compile.trace`
counts no time twice and a 12-layer model does not flood the ring.
"""
from __future__ import annotations

import time

import jax.core
import jax.monitoring as _monitoring

from . import metrics as _metrics
from . import trace as _trace

_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
}
_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "compile.persistent_cache_hits",
    "/jax/compilation_cache/cache_misses": "compile.persistent_cache_misses",
}
_installed = False


def _on_duration(event: str, seconds: float, **kw):
    name = _PHASES.get(event)
    if name is None:
        return
    if name == "compile.trace" and not jax.core.trace_ctx.is_top_level():
        return
    end = time.perf_counter_ns()
    fun = kw.get("fun_name")
    _trace.complete(name, end - int(seconds * 1e9), end, cat="compile",
                    args={"fun": str(fun)} if fun else None)


def _on_event(event: str, **_):
    name = _COUNTERS.get(event)
    if name is not None:
        _metrics.inc(name)


def install():
    """Register the listeners once per process (paddle_tpu's import does)."""
    global _installed
    if _installed:
        return
    _installed = True
    _monitoring.register_event_duration_secs_listener(_on_duration)
    _monitoring.register_event_listener(_on_event)
