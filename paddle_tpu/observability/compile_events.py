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
real compilation, and its arg `cache` says which it was:

    "hit"           fetched (`fetch_s`: seconds the retrieval took)
    "miss_written"  compiled, and JAX wrote the entry afterwards
    "miss"          compiled, entry NOT written (under the size or compile
                    time thresholds, host callbacks, not process 0): the
                    next process compiles it again
    "off"           this compile did not use the persistent cache

from the events JAX fires on the compiling thread inside the interval
the duration report closes: `compile_requests_use_cache` (the cache is in
use for this compile), `cache_hits`, `cache_misses` (fired only where the
entry IS written, `jax/_src/compilation_cache.py` `put_executable_and_time`)
and the duration `cache_retrieval_time_sec`. They fill a thread-local
record that the next `compile.backend` of that thread consumes. The
counters `compile.persistent_cache_hits` / `_misses` / `_unwritten` are
the same outcomes as process-wide totals. JAX 0.9.0 hands the cache KEY to
no listener (it is only logged, at DEBUG unless `jax_explain_cache_misses`
or `jax_log_compiles` raise the level), so the span carries none.
A report under no span of the program (a user's own `jit`) yields a span
with no parent.

`jaxpr_trace_duration` fires for every nested `jit` (each `jax.numpy`
call inside a traced function is one) before it fires for the outer one.
A trace that ran inside another trace is part of it: only a report made
at the top level of tracing becomes a span, so a sum over `compile.trace`
counts no time twice and a 12-layer model does not flood the ring.
"""
from __future__ import annotations

import threading
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
_CACHE_USED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_WRITTEN = "/jax/compilation_cache/cache_misses"
_CACHE_FETCH = "/jax/compilation_cache/cache_retrieval_time_sec"
_COUNTERS = {
    _CACHE_HIT: "compile.persistent_cache_hits",
    _CACHE_WRITTEN: "compile.persistent_cache_misses",
}
_installed = False
# .cache: what the persistent cache has done for the compile in progress on
# this thread, as the span's args {"cache": ..., "fetch_s": ...}; the next
# compile.backend of the thread takes it
_tls = threading.local()


def _cache_args() -> dict:
    """Consume the thread's record: the span's `cache` / `fetch_s`."""
    rec = getattr(_tls, "cache", None)
    _tls.cache = None
    if rec is None:
        return {"cache": "off"}
    if rec["cache"] == "miss":
        _metrics.inc("compile.persistent_cache_unwritten")
    return rec


def _on_duration(event: str, seconds: float, **kw):
    if event == _CACHE_FETCH:
        rec = getattr(_tls, "cache", None)
        if rec is not None:
            rec["fetch_s"] = float(seconds)
        return
    name = _PHASES.get(event)
    if name is None:
        return
    if name == "compile.trace" and not jax.core.trace_ctx.is_top_level():
        return
    end = time.perf_counter_ns()
    fun = kw.get("fun_name")
    args = {"fun": str(fun)} if fun else {}
    if name == "compile.backend":
        args.update(_cache_args())
    elif name == "compile.lower":
        # a compile that raised left its record behind: the lowering that
        # precedes every compile drops it
        _tls.cache = None
    _trace.complete(name, end - int(seconds * 1e9), end, cat="compile",
                    args=args or None)


def _on_event(event: str, **_):
    if event == _CACHE_USED:
        _tls.cache = {"cache": "miss"}      # until a hit or a write says more
        return
    name = _COUNTERS.get(event)
    if name is None:
        return
    _metrics.inc(name)
    rec = getattr(_tls, "cache", None)
    if rec is not None:
        rec["cache"] = "hit" if event == _CACHE_HIT else "miss_written"


def install():
    """Register the listeners once per process (paddle_tpu's import does)."""
    global _installed
    if _installed:
        return
    _installed = True
    _monitoring.register_event_duration_secs_listener(_on_duration)
    _monitoring.register_event_listener(_on_event)
