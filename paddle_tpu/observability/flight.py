"""Flight recorder: the last N steps' spans + metric deltas, always on,
dumped automatically when something dies.

The diagnostics PRs 1-7 leaned on (host-stall ledger, zero fallback
counters, step-deadline thread dumps, bench watchdogs) were one-off
mechanisms with no common timeline — the r05 wedge postmortem had to be
reconstructed from prints. This module is the black box those incidents
wanted: Executor.run/run_steps mark step boundaries here (begin_step/
end_step), each closed step keeps its wall window + the metrics that moved
during it (metrics.delta of two snapshots), and the bounded step ring plus
the trace ring (observability/trace.py) are serialized by dump() when:

* the step hang watchdog trips (`FLAGS_step_deadline_ms`,
  framework/executor.py `_deadline_call`) — next to the thread-stack dump;
* the gang supervisor fails a launch (distributed/launch.py);
* a bench.py row raises (the record carries the dump path).

Overhead when nothing is wrong: two metrics snapshots (a locked dict copy
of ~tens of entries) per step — bounded with the tracer's ≤5% A/B in
tests/test_observability.py. Disable entirely with FLAGS_flight_recorder=0
(also the timing A/B's baseline arm).

Dump location: FLAGS_flight_dump_dir, default <tmpdir>/paddle_tpu_flight;
file name flight_r<rank>_<pid>_<reason>_<seq>.json — rank AND pid ride in
the name so N ranks of a gang dumping into one shared dir (the pod-scope
collection contract, observability/podscope.py) can never overwrite each
other. Format (docs/observability.md "Flight-recorder dumps"):

    {"reason": ..., "rank": ..., "world": ..., "pid": ..., "wall_time": ...,
     "clock": {"wall_time_us": ..., "trace_ts_us": ...},  # pod clock anchor
     "dropped_events": ...,
     "steps":  [{"step": k, "exe": <executor id>, "t0_us": ..., "t1_us": ...,
                 "status": "ok", "metrics_delta": {...}}, ...],
     "trace_events": [...chrome-trace events covering those steps...],
     "metrics": {...full typed snapshot...}}

`clock` is the trace-clock → wall-clock offset handshake: both clocks are
read back-to-back at dump time, so a pod aggregator can place every rank's
perf_counter-epoch events on one shared wall timeline (podscope.py;
clock-skew caveats in docs/observability.md "Pod-scope").

Under the gang launcher two extra contracts apply: `end_step` mirrors the
last step index + duration into the worker's heartbeat file
(PADDLE_LAUNCH_HEARTBEAT_FILE) so the supervisor can name a suspected
straggler LIVE, and PADDLE_FLIGHT_DUMP_AT_EXIT=1 registers an atexit
dump("exit") so clean workers still leave a black box for `--collect-dumps`.
"""
from __future__ import annotations

import json
import os
import tempfile
import threading
import time
from typing import Optional

from ..flags import flag
from . import metrics as _metrics
from . import trace as _trace

_lock = threading.Lock()
_steps: list = []           # closed step records, oldest first, bounded
_open: dict = {}    # (owner, step idx) -> (t0_us, snapshot), in-flight steps
_dump_seq = 0


def enabled() -> bool:
    return bool(flag("FLAGS_flight_recorder"))


def keep_steps() -> int:
    return max(1, int(flag("FLAGS_flight_steps")))


def begin_step(idx: int, owner: int = 0):
    """Mark a step window open (Executor.run / run_steps entry). `owner`
    disambiguates executors: every Executor restarts its step counter at 1,
    so a train+eval pair would otherwise collide on the same idx key."""
    # executor metric, not a recorder metric: counts with the recorder off
    # so A/B arms' snapshots stay comparable
    _metrics.inc("executor.steps")
    if not enabled():
        return
    # percentile-free: delta() only reads count/sum, and the p50/p99 sort
    # would otherwise be paid twice per step forever once a reservoir fills
    snap = _metrics.snapshot(percentiles=False)
    with _lock:
        _open[(int(owner), int(idx))] = (_trace.now_us(), snap)


def end_step(idx: int, status: str = "ok", owner: int = 0):
    """Close a step window: record (t0, t1, metric delta) in the ring."""
    # pop BEFORE the enabled() check: a flag toggle mid-step must not leak
    # a phantom in-flight entry into every later dump()
    with _lock:
        opened = _open.pop((int(owner), int(idx)), None)
    # liveness, not recording: the heartbeat step note flows even with the
    # flight recorder off, so the supervisor's straggler naming never goes
    # blind to a FLAGS_flight_recorder=0 trainer
    hb = os.environ.get("PADDLE_LAUNCH_HEARTBEAT_FILE")
    if hb:
        dur_ms = (None if opened is None
                  else (_trace.now_us() - opened[0]) / 1000.0)
        _note_heartbeat_step(hb, idx, dur_ms)
    if opened is None or not enabled():
        return
    t0, snap0 = opened
    rec = {"step": int(idx), "exe": int(owner), "t0_us": t0,
           "t1_us": _trace.now_us(), "status": status,
           "metrics_delta": _metrics.delta(snap0)}
    with _lock:
        _steps.append(rec)
        del _steps[:-keep_steps()]


def _note_heartbeat_step(path: str, idx: int, dur_ms: Optional[float]):
    """Mirror (last step, step duration) into the launcher heartbeat file
    (distributed/launch.py) — JSON content, written via atomic replace so
    the supervisor never reads a torn record. The supervisor uses the
    last-step spread across ranks to name the suspected straggler in its
    gang-failure message. Never raises: a full disk must not fail a step."""
    try:
        rec = {"pid": os.getpid(), "step": int(idx),
               "wall_us": time.time() * 1e6}
        if dur_ms is not None:
            rec["step_ms"] = round(float(dur_ms), 3)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(rec, f)
        os.replace(tmp, path)
    except OSError:
        pass


def pod_identity() -> dict:
    """This process's gang coordinates from the launcher env contract:
    {"rank", "world", "role"} (rank 0 / world 1 / trainer outside a gang)."""
    return {
        "rank": int(os.environ.get("PADDLE_TRAINER_ID", "0") or 0),
        "world": int(os.environ.get("PADDLE_TRAINERS_NUM", "1") or 1),
        "role": os.environ.get("TRAINING_ROLE", "TRAINER").lower(),
    }


def steps() -> list:
    with _lock:
        return [dict(s) for s in _steps]


def clear():
    with _lock:
        _steps.clear()
        _open.clear()


def dump_dir() -> str:
    d = str(flag("FLAGS_flight_dump_dir") or "")
    return d or os.path.join(tempfile.gettempdir(), "paddle_tpu_flight")


def dump(reason: str, path: Optional[str] = None,
         extra: Optional[dict] = None) -> Optional[str]:
    """Serialize the black box: last-N step records + the trace-ring events
    covering them (all events when no step closed yet) + the full metrics
    snapshot. Returns the written path, or None when the recorder is off.
    Never raises — a failing dump must not mask the crash it documents."""
    global _dump_seq
    if not enabled():
        return None
    try:
        with _lock:
            step_recs = [dict(s) for s in _steps]
            # a step that never closed (the watchdog tripped mid-dispatch)
            # is the most interesting one: include it as in-flight
            for (owner, idx), (t0, snap0) in _open.items():
                step_recs.append({"step": idx, "exe": owner, "t0_us": t0,
                                  "t1_us": None, "status": "in_flight",
                                  "metrics_delta": _metrics.delta(snap0)})
            _dump_seq += 1
            seq = _dump_seq
        since = min((s["t0_us"] for s in step_recs), default=None)
        ident = pod_identity()
        payload = {
            "format": 1,
            "reason": reason,
            "pid": os.getpid(),
            "rank": ident["rank"],
            "world": ident["world"],
            "role": ident["role"],
            "wall_time": time.time(),
            # pod-scope merging places every rank on the wall clock by it
            # (podscope.align-events)
            "clock": _trace.clock_handshake(),
            "dropped_events": _trace.dropped_events(),
            "steps": step_recs,
            "trace_events": (_trace.process_metadata_events()
                             + _trace.thread_metadata_events()
                             + _trace.events(since)),
            "metrics": _metrics.snapshot(),
        }
        if extra:
            payload["extra"] = extra
        if path is None:
            d = dump_dir()
            os.makedirs(d, exist_ok=True)
            path = os.path.join(
                d,
                f"flight_r{ident['rank']}_{os.getpid()}_{reason}_{seq}.json")
        else:
            pd = os.path.dirname(path)
            if pd:
                os.makedirs(pd, exist_ok=True)
        with open(path, "w") as f:
            json.dump(payload, f)
        return path
    except Exception:
        return None


# Clean-exit black box for the gang launcher's --collect-dumps: a worker
# that finishes normally still leaves its flight dump for the supervisor's
# pod aggregation. Opt-in via env (set by distributed/launch.py) so plain
# local runs never write surprise files at interpreter exit.
if os.environ.get("PADDLE_FLIGHT_DUMP_AT_EXIT") == "1":
    import atexit

    atexit.register(lambda: dump("exit"))
