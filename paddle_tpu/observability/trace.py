"""Step-scoped host tracer: RAII spans, flow events, chrome-trace export.

Reference counterpart: platform/profiler.cc RecordEvent spans through the
op loop (operator.cc:1057,1073,1086) + device_tracer.cc's CUPTI timeline +
tools/timeline.py's chrome://tracing converter. TPU-native mapping: the
executor lowers whole blocks, so the interesting host timeline is the
PIPELINE around the jitted step — stage() H2D, dispatch, donation-conflict
copies, FetchHandle materialization, dataloader prefetch fill, checkpoint
save/publish, retries — and the device side is jax.profiler's own capture
(profiler.start_profiler(logdir=...)).

Storage is a bounded RING (FLAGS_trace_buffer_events; oldest events drop,
counted in the `trace.dropped_events` metric) so recording can stay ALWAYS
ON as the flight recorder's backing store (observability/flight.py) with a
hard memory bound. Thread ids are REAL idents, with thread-name metadata
("M" phase) emitted at export so chrome/Perfetto label the lanes; flow
events ("s"/"f" phases sharing cat+name+id) link a step's dispatch to its
later fetch materialization across threads.

Spans form a TREE: a thread-local stack gives every RecordEvent an `id`
and the `parent` id of the span open on its thread (None for a root), and
a span inherits `step` and `exe` from its root — `executor.step`, opened
by Executor._step_window — so the events of one dispatch can be gathered
and `self_times` computed. Names are fixed strings (docs/observability.md
has the table); what varies rides in `args`.

One clock with the device trace: every span also enters a
`jax.profiler.TraceAnnotation` named `pt/<name>`, so in ANY jax.profiler
capture, whoever started it, the program's spans lie in the xplane's host
plane on the profiler's own timeline beside the device operations.

Overhead: one flag lookup when disabled (FLAGS_trace_events=0); enabled,
two perf_counter_ns calls, a TraceMe enter/exit (a no-op check while no
capture runs) + a locked deque append per span — bounded ≤5% of step time
by tests/test_observability.py's timing A/B.
"""
from __future__ import annotations

import collections
import itertools
import json
import os
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

from ..flags import flag
from . import metrics as _metrics

_lock = threading.Lock()
_events: "collections.deque[dict]" = collections.deque(maxlen=65536)
_thread_names: Dict[int, str] = {}
_flow_ids = itertools.count(1)
_span_ids = itertools.count(1)
_dropped = 0
_tls = threading.local()     # .stack: the spans open on this thread

# prefix of the program's spans in a jax.profiler capture's host plane
ANNOTATION_PREFIX = "pt/"


def now_us() -> float:
    """The trace clock (chrome trace ts unit: microseconds)."""
    return time.perf_counter_ns() / 1000.0


def clock_handshake() -> dict:
    """The wall clock and the trace clock read back to back: the pair maps
    this process's trace (perf_counter) epoch onto the shared wall clock.
    flight.dump writes it as `clock` (podscope aligns ranks by it);
    process_created_us moves the OS's creation time onto the trace clock."""
    return {"wall_time_us": time.time() * 1e6, "trace_ts_us": now_us()}


def process_created_us() -> Optional[float]:
    """When the OS created this process, on the trace clock (microseconds;
    negative where the process is older than perf_counter's epoch). Linux:
    the start time of /proc/self/stat in clock ticks since boot, against
    /proc/uptime, gives the process's age at the instant of a handshake;
    the wall clock less the age is the creation time, which the handshake's
    offset moves onto the trace clock. None where the OS gives none."""
    try:
        with open("/proc/self/stat") as f:
            # fields after the command's closing parenthesis: state is the
            # first, starttime the 20th (proc(5) field 22)
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        clock = clock_handshake()
        with open("/proc/uptime") as f:
            up_s = float(f.read().split()[0])
        age_us = (up_s - ticks / os.sysconf("SC_CLK_TCK")) * 1e6
    except (OSError, ValueError, IndexError):
        return None
    # wall_time_us - age_us is the creation time on the wall clock; the
    # handshake's offset (wall_time_us - trace_ts_us) taken off it leaves:
    return clock["trace_ts_us"] - age_us


def enabled() -> bool:
    return bool(flag("FLAGS_trace_events"))


def set_buffer_size(n: int):
    """Re-bound the ring (tests; FLAGS_trace_buffer_events seeds the
    initial bound). Existing events are kept up to the new bound."""
    global _events
    with _lock:
        _events = collections.deque(_events, maxlen=max(16, int(n)))


_flag_capacity: Optional[int] = None   # last applied flag value


def _resize_from_flag():
    """Apply FLAGS_trace_buffer_events when it CHANGED — re-checked by
    _append whenever the ring is full, so a runtime set_flags on the
    capacity takes effect without clobbering an explicit
    set_buffer_size() (which wins until the flag moves again)."""
    global _flag_capacity
    n = int(flag("FLAGS_trace_buffer_events"))
    if n and n != _flag_capacity:
        _flag_capacity = n
        set_buffer_size(n)


def _append(ev: dict):
    global _dropped
    tid = threading.get_ident()
    ev["pid"] = os.getpid()
    ev["tid"] = tid
    if len(_events) == _events.maxlen and (_dropped & 0x1FF) == 0:
        # ring full — steady state of a long always-on run — is the one
        # moment a runtime set_flags on the capacity matters. Re-read it
        # BEFORE taking _lock (set_buffer_size locks), but only every 512
        # drops: a per-event flag lookup would tax every span forever.
        _resize_from_flag()
    with _lock:
        if tid not in _thread_names:
            _thread_names[tid] = threading.current_thread().name
        if len(_events) == _events.maxlen:
            _dropped += 1
        _events.append(ev)


def _stack() -> list:
    try:
        return _tls.stack
    except AttributeError:
        _tls.stack = []
        return _tls.stack


def _inherit(args: Optional[dict], parent: "Optional[RecordEvent]"):
    """A child's args: its own plus its root's `step` and `exe`."""
    if parent is None or not parent.args:
        return args
    up = {k: parent.args[k] for k in ("step", "exe") if k in parent.args}
    if not up:
        return args
    if args:
        up.update(args)
    return up


class RecordEvent:
    """RAII host span (reference platform/profiler.h RecordEvent): a
    complete ("X") chrome-trace event over the with-block's wall time,
    with an `id`, the `parent` id of the span it was opened under (None
    for a root) and its root's `step` / `exe` among its args. `args` ride
    into the trace verbatim; extra args can be attached mid-span with
    add_args()."""

    __slots__ = ("name", "cat", "args", "id", "_t0", "_on", "_parent",
                 "_ann")

    def __init__(self, name: str, cat: str = "host", args: Optional[dict] = None):
        self.name = name
        self.cat = cat
        self.args = args

    def add_args(self, **kw):
        if self.args is None:
            self.args = {}
        self.args.update(kw)
        return self

    def __enter__(self):
        self._on = enabled()
        if self._on:
            stack = _stack()
            parent = stack[-1] if stack else None
            self.id = next(_span_ids)
            self._parent = None if parent is None else parent.id
            self.args = _inherit(self.args, parent)
            stack.append(self)
            self._ann = TraceAnnotation(ANNOTATION_PREFIX + self.name)
            self._ann.__enter__()
            self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *a):
        if self._on:
            t1 = time.perf_counter_ns()
            self._ann.__exit__(None, None, None)
            stack = _stack()
            if stack and stack[-1] is self:
                stack.pop()
            elif self in stack:
                # a child left open (an exception between a bare enter
                # and exit) must not misparent later spans
                del stack[stack.index(self):]
            ev = {"name": self.name, "ph": "X", "cat": self.cat,
                  "ts": self._t0 / 1000.0, "dur": (t1 - self._t0) / 1000.0,
                  "id": self.id, "parent": self._parent}
            if self.args:
                ev["args"] = dict(self.args)
            _append(ev)
        return False


def current_span() -> "Optional[RecordEvent]":
    """The span open on this thread, if any."""
    stack = _stack()
    return stack[-1] if stack else None


def complete(name: str, start_ns: int, end_ns: int, cat: str = "host",
             args: Optional[dict] = None):
    """Record a span from two `perf_counter_ns` readings taken elsewhere
    (the package import, a jax.monitoring duration that ends now): its
    parent is the span open on this thread."""
    if not enabled():
        return
    parent = current_span()
    ev = {"name": name, "ph": "X", "cat": cat, "ts": start_ns / 1000.0,
          "dur": (end_ns - start_ns) / 1000.0, "id": next(_span_ids),
          "parent": None if parent is None else parent.id}
    args = _inherit(args, parent)
    if args:
        ev["args"] = dict(args)
    _append(ev)


def record_event(name, **kw):
    return RecordEvent(name, **kw)


def instant(name: str, args: Optional[dict] = None, cat: str = "host"):
    """Point-in-time marker ("i" phase): retries, fallbacks, conflicts."""
    if not enabled():
        return
    ev = {"name": name, "ph": "i", "cat": cat, "ts": now_us(), "s": "t"}
    if args:
        ev["args"] = dict(args)
    _append(ev)


# ---- flow events (cross-thread dispatch -> fetch linkage) -------------------

def new_flow() -> int:
    return next(_flow_ids)


def flow_start(name: str, flow_id: int, args: Optional[dict] = None) -> int:
    """Open flow `flow_id` here (an "s" event). The matching flow_end may
    fire on ANY thread — chrome binds s/f pairs by (cat, name, id)."""
    if enabled():
        ev = {"name": name, "ph": "s", "cat": "flow", "id": int(flow_id),
              "ts": now_us()}
        if args:
            ev["args"] = dict(args)
        _append(ev)
    return flow_id


def flow_end(name: str, flow_id: int, args: Optional[dict] = None):
    if not enabled():
        return
    ev = {"name": name, "ph": "f", "bp": "e", "cat": "flow",
          "id": int(flow_id), "ts": now_us()}
    if args:
        ev["args"] = dict(args)
    _append(ev)


# ---- views / export ---------------------------------------------------------

def events(since_ts: Optional[float] = None) -> List[dict]:
    """A copy of the ring (optionally only events ending at/after
    `since_ts`, trace-clock microseconds)."""
    with _lock:
        evs = list(_events)
    if since_ts is None:
        return evs
    return [e for e in evs
            if e["ts"] + e.get("dur", 0.0) >= since_ts]


def self_times(evs: List[dict]) -> Dict[int, float]:
    """{span id: self time in microseconds}: a span's duration less the
    part of its interval that its child spans cover (children that
    overlap each other are counted once)."""
    kids: Dict[int, list] = {}
    for e in evs:
        if e.get("ph") == "X" and e.get("parent") is not None:
            kids.setdefault(e["parent"], []).append(
                (e["ts"], e["ts"] + e["dur"]))
    out = {}
    for e in evs:
        if e.get("ph") != "X" or "id" not in e:
            continue
        lo, hi = e["ts"], e["ts"] + e["dur"]
        covered, cur = 0.0, lo
        for a, b in sorted(kids.get(e["id"], ())):
            a, b = max(a, cur), min(b, hi)
            if b > a:
                covered += b - a
                cur = b
        out[e["id"]] = e["dur"] - covered
    return out


_dropped_mirrored = 0


def dropped_events() -> int:
    """Drop count; also mirrors it into the `trace.dropped_events` counter.
    The mirror happens HERE (and so at every export/dump, which call this)
    rather than per-drop in _append — a full ring would otherwise pay a
    metrics-lock acquire on every span forever."""
    global _dropped_mirrored
    d = _dropped
    if d != _dropped_mirrored:
        _metrics.inc("trace.dropped_events", d - _dropped_mirrored)
        _dropped_mirrored = d
    return d


def clear():
    global _dropped, _dropped_mirrored
    with _lock:
        _events.clear()
        _dropped = 0
    _dropped_mirrored = 0


def thread_metadata_events() -> List[dict]:
    """One "M" thread_name event per thread seen, so trace viewers label
    lanes with real thread names instead of bare idents."""
    pid = os.getpid()
    with _lock:
        names = dict(_thread_names)
    return [{"name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
             "args": {"name": name}} for tid, name in sorted(names.items())]


def process_metadata_events() -> List[dict]:
    """Process-lane metadata ("M" process_name / process_sort_index /
    process_labels): rank, role, and world size from the launcher's env
    contract (PADDLE_TRAINER_ID / TRAINING_ROLE / PADDLE_TRAINERS_NUM), so
    even a single-rank trace opens in Perfetto with a labeled lane instead
    of a bare pid — and a pod-merged trace (observability/podscope.py)
    sorts its per-rank lanes in rank order."""
    pid = os.getpid()
    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0") or 0)
    world = int(os.environ.get("PADDLE_TRAINERS_NUM", "1") or 1)
    role = os.environ.get("TRAINING_ROLE", "TRAINER").lower()
    return [
        {"name": "process_name", "ph": "M", "pid": pid,
         "args": {"name": f"rank {rank} ({role})"}},
        {"name": "process_sort_index", "ph": "M", "pid": pid,
         "args": {"sort_index": rank}},
        {"name": "process_labels", "ph": "M", "pid": pid,
         "args": {"labels": f"rank={rank},world={world},role={role},"
                            f"pid={pid}"}},
    ]


def export_chrome_trace(path: str,
                        since_ts: Optional[float] = None,
                        extra_events: Optional[List[dict]] = None,
                        events_override: Optional[List[dict]] = None) -> str:
    """Write a chrome://tracing / Perfetto JSON file: thread-name metadata
    first, then the (optionally windowed) span/flow/instant events.
    `events_override` replaces the ring read with a caller-captured event
    list (Profiler step windows) — metadata and dropped_events still ride
    along."""
    evs = (list(events_override) if events_override is not None
           else events(since_ts))
    payload = {
        "traceEvents": process_metadata_events() + thread_metadata_events()
        + evs + list(extra_events or []),
        "displayTimeUnit": "ms",
        "otherData": {"dropped_events": dropped_events()},
    }
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f)
    return path


_resize_from_flag()
