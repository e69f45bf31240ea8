"""Observability subsystem: typed metrics, step-scoped tracer, flight
recorder (docs/observability.md).

The ISSUE-8 acceptance lives here: a 20-step async loop under the tracer
exports a chrome trace with stage/dispatch/fetch spans and a flow event
crossing threads; an induced step-deadline trip writes a flight-recorder
dump (last-N step windows + metric deltas) next to the thread-stack dump;
and tracer-off overhead on the hot path is bounded by a timing A/B with
bounded retry (wall-clock comparisons on shared CI hosts hiccup — noise
only ever ADDS time, so one clean pass demonstrates the bound).
"""
import json
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.fluid as fluid
from paddle_tpu import monitor
from paddle_tpu.fluid import layers
from paddle_tpu.flags import set_flags
from paddle_tpu.observability import flight, metrics, trace


def _fresh():
    from paddle_tpu.framework import program as pm, scope as sm, unique_name
    pm._main_program = pm.Program()
    pm._startup_program = pm.Program()
    sm._reset_global_scope()
    unique_name.switch()


def _build(width=8):
    x = layers.data(name="x", shape=[6], dtype="float32")
    y = layers.data(name="y", shape=[1], dtype="float32")
    h = layers.fc(x, width, act="tanh")
    pred = layers.fc(h, 1)
    loss = layers.mean(layers.square_error_cost(pred, y))
    paddle.optimizer.Adam(learning_rate=1e-2).minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(16, 6).astype(np.float32)}
    feed["y"] = feed["x"].sum(1, keepdims=True).astype(np.float32)
    return exe, loss, feed


# --------------------------------------------------------------------------
# typed metrics registry
# --------------------------------------------------------------------------

def test_metrics_types_snapshot_delta_json():
    for n in ("t.c", "t.g", "t.h"):
        metrics.reset(n)
    metrics.inc("t.c")
    metrics.inc("t.c", 2.5)
    metrics.set_gauge("t.g", 7)
    metrics.set_gauge("t.g", 3)          # last value wins
    for v in range(100):
        metrics.observe("t.h", float(v))
    snap = metrics.snapshot()
    assert snap["t.c"] == {"type": "counter", "value": 3.5}
    assert snap["t.g"] == {"type": "gauge", "value": 3}
    h = snap["t.h"]
    assert h["type"] == "histogram" and h["count"] == 100
    assert h["min"] == 0.0 and h["max"] == 99.0
    assert h["p50"] in (49.0, 50.0) and h["p99"] in (98.0, 99.0)
    # get(): scalar value; histogram names return their count
    assert metrics.get("t.c") == 3.5 and metrics.get("t.h") == 100
    assert metrics.get("t.nope") == 0
    # flat(): the legacy monitor view — scalars only
    flat = metrics.flat()
    assert flat["t.c"] == 3.5 and "t.h" not in flat

    # delta(): only what moved, typed
    prev = metrics.snapshot()
    metrics.inc("t.c", 1.5)
    metrics.observe("t.h", 5.0)
    d = metrics.delta(prev)
    assert d["t.c"] == {"type": "counter", "value": 1.5}
    assert d["t.h"]["count"] == 1 and d["t.h"]["sum"] == 5.0
    assert "t.g" not in d                # unmoved gauge omitted

    # the export IS the snapshot: plain JSON, what bench.py stamps into its
    # records and flight dumps carry (export_jsonl wrote a file nothing read)
    byname = json.loads(json.dumps(metrics.snapshot()))
    assert byname["t.c"] == {"type": "counter", "value": 5.0}
    assert byname["t.h"]["count"] == 101 and byname["t.h"]["sum"] == 4955.0
    for n in ("t.c", "t.g", "t.h"):
        metrics.reset(n)


def test_monitor_shim_lands_in_registry():
    monitor.stat_reset("shim.x")
    monitor.stat_add("shim.x", 2)
    assert metrics.snapshot()["shim.x"]["type"] == "counter"
    assert metrics.get("shim.x") == 2
    monitor.stat_set("shim.y", 9)
    assert metrics.snapshot()["shim.y"]["type"] == "gauge"
    monitor.stat_reset("shim.x")
    monitor.stat_reset("shim.y")


# --------------------------------------------------------------------------
# trace ring: bounded storage, dropped counter, real thread ids
# --------------------------------------------------------------------------

def test_trace_ring_bounds_drops_and_real_tids():
    trace.clear()
    metrics.reset("trace.dropped_events")
    old = trace._events.maxlen
    trace.set_buffer_size(16)
    try:
        for i in range(40):
            with trace.RecordEvent(f"spin{i}"):
                pass
        evs = trace.events()
        assert len(evs) == 16            # ring-bounded, oldest dropped
        assert trace.dropped_events() == 24
        assert metrics.get("trace.dropped_events") == 24
        # REAL thread idents (the old shim stored tid % 10000)
        assert all(e["tid"] == threading.get_ident() for e in evs)
        metas = trace.thread_metadata_events()
        assert {"tid": threading.get_ident()} \
            .items() <= {k: v for m in metas for k, v in m.items()}.items()
        name = threading.current_thread().name
        assert any(m["args"]["name"] == name for m in metas)
    finally:
        trace.set_buffer_size(old)
        trace.clear()
        metrics.reset("trace.dropped_events")


def test_trace_disabled_records_nothing():
    trace.clear()
    set_flags({"FLAGS_trace_events": False})
    try:
        assert not trace.enabled()
        with trace.RecordEvent("ghost"):
            pass
        trace.instant("ghost_i")
        trace.flow_start("ghost_f", trace.new_flow())
        assert trace.events() == []
    finally:
        set_flags({"FLAGS_trace_events": True})
        trace.clear()


# --------------------------------------------------------------------------
# the acceptance loop: 20 async steps -> one chrome trace
# --------------------------------------------------------------------------

def test_traced_async_loop_exports_chrome_trace(tmp_path):
    """20-step async loop with staged feeds: the exported JSON holds host
    spans for stage/dispatch/fetch, per-step annotations, no trace of the
    removed XLA cost attribution, and a flow event linking a step's
    dispatch to its materialization on ANOTHER thread."""
    _fresh()
    exe, loss, feed = _build()
    exe.run(feed=feed, fetch_list=[loss])           # compile + warm
    trace.clear()
    # XLA's cost analysis is returned to the caller and recorded nowhere:
    # no counter track, no gauges, no args on the dispatch spans
    cost = exe.annotate_step_cost(feed=feed, fetch_list=[loss])
    assert cost["device_flops"] > 0
    assert not [e for e in trace.events() if e.get("ph") == "C"]
    assert not any(n.startswith("executor.step_")
                   for n in metrics.snapshot())
    flight.clear()
    handles = []
    staged = exe.stage(feed)
    for _ in range(20):
        out, = exe.run(feed=staged, fetch_list=[loss], sync=False)
        handles.append(out)
        staged = exe.stage(feed)
    # materialize the last fetch on a worker thread: the flow must close
    # there, drawing the cross-thread dispatch->drain arrow
    t = threading.Thread(target=handles[-1].numpy, name="drain-thread")
    t.start()
    t.join()
    path = str(tmp_path / "timeline.json")
    trace.export_chrome_trace(path)
    with open(path) as f:
        payload = json.load(f)
    evs = payload["traceEvents"]

    spans = [e for e in evs if e.get("ph") == "X"]
    names = {e["name"] for e in spans}
    assert "stage" in names and "fetch.materialize" in names
    dispatch = [e for e in spans if e["name"] == "executor.launch"]
    assert len(dispatch) >= 20
    # every dispatch carries its root's step; names carry no payload
    steps_seen = {e["args"]["step"] for e in dispatch}
    assert len(steps_seen) >= 20
    assert not any("device_flops" in e["args"] for e in dispatch)
    assert not any("#" in n for n in names)
    # every span lane has thread-name metadata
    metas = [e for e in evs if e.get("ph") == "M"
             and e["name"] == "thread_name"]
    assert {e["tid"] for e in spans} <= {e["tid"] for e in metas}
    # flow linkage: one s/f pair, crossing threads
    starts = {e["id"]: e for e in evs if e.get("ph") == "s"}
    ends = {e["id"]: e for e in evs if e.get("ph") == "f"}
    linked = set(starts) & set(ends)
    assert linked
    assert any(starts[i]["tid"] != ends[i]["tid"] for i in linked)

    # the flight recorder saw the same steps: bounded ring of windows,
    # each with the metrics that moved during it
    recs = flight.steps()
    assert 1 <= len(recs) <= flight.keep_steps()
    assert all(r["status"] == "ok" and r["t1_us"] > r["t0_us"]
               for r in recs)
    moved = set().union(*(r["metrics_delta"] for r in recs))
    assert any(k.startswith("executor.") for k in moved)


def test_run_steps_slice_inherits_fetch_flow():
    """The documented stacked-fetch pattern — run_steps(sync=False), then
    `handle[-1].numpy()` — closes the dispatch flow on the SLICE's drain,
    so the run_steps path draws the dispatch->fetch arrow too."""
    _fresh()
    exe, loss, feed = _build()
    exe.run(feed=feed, fetch_list=[loss])            # compile + warm
    trace.clear()
    stk, = exe.run_steps(4, feed=feed, fetch_list=[loss], sync=False)
    last = stk[-1]                                   # lazy device slice
    evs = trace.events()
    starts = [e for e in evs if e.get("ph") == "s"]
    assert len(starts) == 1 and not any(e.get("ph") == "f" for e in evs)
    float(last)                                      # drain the slice
    ends = [e for e in evs if e.get("ph") == "f"] or \
        [e for e in trace.events() if e.get("ph") == "f"]
    assert len(ends) == 1 and ends[0]["id"] == starts[0]["id"]
    # the claim is one-shot across the whole handle family: a second
    # slice and the parent drain without emitting dangling flow ends
    float(stk[0])
    stk.numpy()
    assert len([e for e in trace.events() if e.get("ph") == "f"]) == 1


# --------------------------------------------------------------------------
# flight recorder: dump on an induced step-deadline trip
# --------------------------------------------------------------------------

def test_flight_dump_on_step_deadline_trip(tmp_path):
    """The watchdog's trip path (the SAME _deadline_call the executor
    wraps dispatch/fetch in) writes a flight dump — last-N step windows +
    metric deltas + covering trace events — next to the thread-stack dump,
    and the error message names both."""
    from paddle_tpu.framework import errors
    from paddle_tpu.framework.executor import _deadline_call
    _fresh()
    exe, loss, feed = _build()
    flight.clear()
    for _ in range(3):                   # real step windows in the ring
        exe.run(feed=feed, fetch_list=[loss])
    monitor.stat_reset("executor.step_deadline_trips")
    set_flags({"FLAGS_flight_dump_dir": str(tmp_path)})
    release = threading.Event()
    try:
        with pytest.raises(errors.DeadlineExceededError) as ei:
            _deadline_call(release.wait, 150.0, "induced wedge")
    finally:
        release.set()                    # unwedge the worker thread
        set_flags({"FLAGS_flight_dump_dir": ""})
    msg = str(ei.value)
    assert "induced wedge" in msg and "thread stacks" in msg
    dumps = [f for f in os.listdir(tmp_path) if f.startswith("flight_")]
    assert len(dumps) == 1 and dumps[0] in msg
    with open(tmp_path / dumps[0]) as f:
        d = json.load(f)
    assert d["reason"] == "step_deadline"
    assert d["extra"]["what"] == "induced wedge"
    assert "thread_stacks" in d["extra"]
    assert len(d["steps"]) == 3
    assert all(s["status"] == "ok" and s["metrics_delta"]
               for s in d["steps"])
    # the covering trace events include those steps' dispatch spans
    dnames = [e["name"] for e in d["trace_events"]]
    assert dnames.count("executor.step") >= 3
    assert d["metrics"]["executor.step_deadline_trips"]["value"] == 1


def test_flight_dump_never_raises_when_disabled():
    flight.clear()
    set_flags({"FLAGS_flight_recorder": False})
    try:
        flight.begin_step(1)
        flight.end_step(1)
        assert flight.steps() == []
        assert flight.dump("unit") is None
    finally:
        set_flags({"FLAGS_flight_recorder": True})


def test_flight_flag_toggle_mid_step_and_recorder_off_step_count(tmp_path):
    """Disabling the recorder mid-step must not leak a phantom in-flight
    entry into later dumps, and executor.steps counts even recorder-off
    (it is an executor metric — A/B arms' snapshots stay comparable)."""
    flight.clear()
    before = metrics.snapshot().get("executor.steps", {}).get("value", 0)
    flight.begin_step(7)
    set_flags({"FLAGS_flight_recorder": False})
    try:
        flight.end_step(7)                      # pops despite recorder off
        flight.begin_step(8)                    # recorder-off: no window...
        flight.end_step(8)
    finally:
        set_flags({"FLAGS_flight_recorder": True})
    # ...but both begin_step calls counted
    after = metrics.snapshot()["executor.steps"]["value"]
    assert after == before + 2
    path = flight.dump("toggle", path=str(tmp_path / "d.json"))
    with open(path) as f:
        recs = json.load(f)["steps"]
    assert not any(r["status"] == "in_flight" for r in recs), recs


def test_flight_windows_keyed_per_executor(tmp_path):
    """Two executors (train + eval) each restart their step counter at 1;
    flight windows are keyed (owner, idx) so their records interleave
    without one executor popping the other's window."""
    _fresh()
    exe_a, loss, feed = _build()
    exe_b = fluid.Executor()
    flight.clear()
    exe_a.run(feed=feed, fetch_list=[loss])
    exe_b.run(fluid.default_startup_program())
    exe_a.run(feed=feed, fetch_list=[loss])
    recs = flight.steps()
    owners = {r["exe"] for r in recs}
    assert len(owners) == 2 and all(r["status"] == "ok" for r in recs)
    by_owner = {o: [r["step"] for r in recs if r["exe"] == o]
                for o in owners}
    assert sorted(by_owner.values(), key=len) == [[1], [2, 3]]


def test_reset_profiler_preserves_flight_black_box(tmp_path):
    """Legacy per-epoch reset_profiler() advances the EXPORT window but
    must not blank the shared trace ring the flight recorder dumps."""
    trace.clear()
    with trace.RecordEvent("pre_reset_span"):
        pass
    paddle.profiler.reset_profiler()
    names = {e["name"] for e in trace.events()}
    assert "pre_reset_span" in names            # black box intact
    with trace.RecordEvent("post_reset_span"):
        pass
    path = paddle.profiler.export_chrome_tracing(str(tmp_path / "t.json"))
    with open(path) as f:
        exported = {e["name"] for e in json.load(f)["traceEvents"]
                    if e.get("ph") == "X"}
    assert "post_reset_span" in exported        # window starts at reset
    assert "pre_reset_span" not in exported


# --------------------------------------------------------------------------
# Profiler step-window scheduling (the silent-no-op satellite)
# --------------------------------------------------------------------------

def test_make_scheduler_state_machine():
    from paddle_tpu.profiler import ProfilerState, make_scheduler
    sched = make_scheduler(closed=1, ready=1, record=2, repeat=1,
                           skip_first=2)
    got = [sched(i) for i in range(8)]
    assert got == [ProfilerState.CLOSED, ProfilerState.CLOSED,  # skip_first
                   ProfilerState.CLOSED, ProfilerState.READY,
                   ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN,
                   ProfilerState.CLOSED, ProfilerState.CLOSED]  # repeat=1


def test_profiler_step_drives_windows(tmp_path):
    """scheduler=(2, 5) records steps 2..4 only; on_trace_ready fires when
    the window closes; export() writes that window's spans."""
    _fresh()
    exe, loss, feed = _build()
    exe.run(feed=feed, fetch_list=[loss])           # compile + warm
    trace.clear()
    ready = []
    prof = paddle.profiler.Profiler(scheduler=(2, 5),
                                    on_trace_ready=ready.append)
    prof.step()                                     # before start: no-op
    assert prof.step_num == 0
    prof.start()
    for step in range(8):
        with trace.RecordEvent(f"probe#{step}"):
            exe.run(feed=feed, fetch_list=[loss])
        prof.step()
    assert ready == [prof]                          # one window closed
    prof.stop()
    assert len(ready) == 1                          # nothing re-fired
    path = str(tmp_path / "window.json")
    prof.export(path)
    with open(path) as f:
        evs = json.load(f)["traceEvents"]
    probes = sorted(e["name"] for e in evs if e["name"].startswith("probe#"))
    assert probes == ["probe#2", "probe#3", "probe#4"]


def test_stop_profiler_writes_nothing_without_path(monkeypatch):
    import paddle_tpu.profiler as prof_mod
    calls = []
    monkeypatch.setattr(prof_mod, "export_chrome_tracing",
                        lambda p: calls.append(p) or p)
    prof_mod.start_profiler()
    assert prof_mod.stop_profiler() is None         # no /tmp/profile
    with prof_mod.profiler():
        pass
    assert calls == []
    with prof_mod.profiler(profile_path="/tmp/asked_for_it.json"):
        pass
    assert calls == ["/tmp/asked_for_it.json"]


# --------------------------------------------------------------------------
# hot-path overhead: tracer+flight on vs off, bounded
# --------------------------------------------------------------------------

def test_tracer_overhead_bounded():
    """Tracer-on adds <=5% to the median step time of a real-compute loop.
    Wall-clock A/Bs on shared hosts need real per-step work (a
    microsecond step is all scheduler noise) and a bounded retry — noise
    only ever ADDS time, so one clean pass demonstrates the bound."""
    # measure from a clean slate: the flight recorder's per-step snapshot
    # cost scales with registry size, and a full-suite run arrives here
    # with hundreds of stale metric names from earlier tests (~0.4ms/step
    # at 400 entries — an environmental, not hot-path, cost)
    metrics.reset()
    trace.clear()
    flight.clear()
    _fresh()
    x = layers.data(name="x", shape=[256], dtype="float32")
    y = layers.data(name="y", shape=[1], dtype="float32")
    h = x
    for _ in range(4):
        h = layers.fc(h, 256, act="relu")
    pred = layers.fc(h, 1)
    loss = layers.mean(layers.square_error_cost(pred, y))
    paddle.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(7)
    feed = {"x": rng.randn(128, 256).astype(np.float32),
            "y": rng.randn(128, 1).astype(np.float32)}
    exe.run(feed=feed, fetch_list=[loss])           # compile + warm

    def median_step_ms(steps=30):
        times = []
        for _ in range(steps):
            t0 = time.perf_counter()
            exe.run(feed=feed, fetch_list=[loss])
            times.append((time.perf_counter() - t0) * 1000.0)
        times.sort()
        return times[len(times) // 2]

    deltas = []
    for _ in range(5):
        set_flags({"FLAGS_trace_events": False,
                   "FLAGS_flight_recorder": False})
        try:
            off = median_step_ms()
        finally:
            set_flags({"FLAGS_trace_events": True,
                       "FLAGS_flight_recorder": True})
        on = median_step_ms()
        deltas.append(on / off)
        if on <= off * 1.05:
            return
    raise AssertionError(
        f"tracer overhead never came in under 5%: ratios {deltas}")


# --------------------------------------------------------------------------
# the span tree: ids, parents, the root's step; self time; compile phases
# --------------------------------------------------------------------------

def _spans(evs=None):
    return [e for e in (trace.events() if evs is None else evs)
            if e.get("ph") == "X"]


def _under(root, evs):
    """The spans of `evs` that have `root` among their ancestors."""
    by = {e["id"]: e for e in evs}
    out = []
    for e in evs:
        p = e["parent"]
        while p is not None and p != root["id"]:
            p = by[p]["parent"] if p in by else None
        if p is not None:
            out.append(e)
    return out


def test_every_span_has_id_parent_and_its_roots_step():
    _fresh()
    exe, loss, feed = _build()
    trace.clear()
    exe.run(feed=feed, fetch_list=[loss])
    exe.run_steps(2, feed=feed, fetch_list=[loss])
    with trace.RecordEvent("outside"):
        with trace.RecordEvent("inside", args={"n": 1}):
            pass
    spans = _spans()
    ids = [e["id"] for e in spans]
    assert len(ids) == len(set(ids)) and all("parent" in e for e in spans)
    assert not any("#" in e["name"] for e in spans)
    by = {e["id"]: e for e in spans}
    outside, = [e for e in spans if e["name"] == "outside"]
    inside, = [e for e in spans if e["name"] == "inside"]
    assert outside["parent"] is None and inside["parent"] == outside["id"]
    assert inside["args"] == {"n": 1}            # no step to inherit
    roots = [e for e in spans if e["name"] == "executor.step"]
    assert [r["args"]["kind"] for r in roots] == ["run", "run_steps"]
    assert [r["args"]["k"] for r in roots] == [1, 2]
    for root in roots:
        assert root["parent"] is None
        assert root["args"]["program"] == "main" and root["args"]["ops"] > 0
        kids = _under(root, spans)
        assert {"executor.prepare", "executor.launch",
                "executor.commit"} <= {e["name"] for e in kids}
        for e in kids:                           # grandchildren too
            assert e["args"]["step"] == root["args"]["step"]
            assert e["args"]["exe"] == root["args"]["exe"]
            assert by[e["parent"]]["ts"] <= e["ts"] + 1.0


@pytest.mark.parametrize("k", [None, 1, 4])
def test_one_dispatch_is_one_root_over_one_of_each_phase(k):
    """run() and run_steps(k) are ONE dispatch body (Executor._dispatch):
    whatever the shape, a dispatch is one root with the args the benchmark's
    readers select on, and under it exactly one prepare, launch, commit."""
    _fresh()
    exe, loss, feed = _build()

    def dispatch():
        if k is None:
            return exe.run(feed=feed, fetch_list=[loss])
        return exe.run_steps(k, feed=feed, fetch_list=[loss])

    dispatch()                                   # the compile, out of sight
    trace.clear()
    out, = dispatch()
    assert np.asarray(out).shape == (() if k is None else (k,))
    spans = _spans()
    root, = [e for e in spans if e["name"] == "executor.step"]
    assert root["parent"] is None
    assert set(root["args"]) == {"step", "exe", "kind", "k", "program",
                                 "ops"}
    assert root["args"]["kind"] == ("run" if k is None else "run_steps")
    assert root["args"]["k"] == (k or 1)
    assert root["args"]["program"] == "main" and root["args"]["ops"] > 0
    assert root["args"]["step"] == exe._step_counter
    assert [e["name"] for e in spans if e["parent"] == root["id"]] == [
        "executor.prepare", "executor.launch", "executor.commit"]
    assert "executor.build_block" not in {e["name"] for e in spans}


def test_startup_program_root_says_so():
    _fresh()
    x = layers.data(name="x", shape=[4], dtype="float32")
    layers.fc(x, 2)
    trace.clear()
    fluid.Executor().run(fluid.default_startup_program())
    root, = [e for e in _spans() if e["name"] == "executor.step"]
    assert root["args"]["program"] == "startup"
    assert root["args"]["kind"] == "run"


def test_self_times_on_a_hand_made_tree():
    def span(i, parent, ts, dur):
        return {"name": f"s{i}", "ph": "X", "ts": ts, "dur": dur, "id": i,
                "parent": parent}
    evs = [span(1, None, 0.0, 100.0),
           span(2, 1, 10.0, 30.0),              # 10..40
           span(3, 1, 35.0, 25.0),              # 35..60 overlaps 2 by 5
           span(4, 2, 15.0, 10.0),              # grandchild: 2's, not 1's
           span(5, 1, 90.0, 20.0),              # 90..110: clipped to 100
           {"name": "i", "ph": "i", "ts": 5.0}]
    st = trace.self_times(evs)
    assert st == {1: 100.0 - 50.0 - 10.0, 2: 20.0, 3: 25.0, 4: 10.0,
                  5: 20.0}


def test_a_span_left_open_by_an_exception_does_not_misparent_the_next():
    trace.clear()
    with pytest.raises(ValueError):
        with trace.RecordEvent("a"):
            trace.RecordEvent("leaked").__enter__()   # never exited
            raise ValueError
    with trace.RecordEvent("b"):
        pass
    by = {e["name"]: e for e in _spans()}
    assert by["b"]["parent"] is None and "leaked" not in by
    assert trace.current_span() is None


def test_run_steps_is_one_root_with_three_phases_and_cold_compile_spans():
    _fresh()
    exe, loss, feed = _build(width=5)            # a program no test compiled
    trace.clear()
    exe.run_steps(3, feed=feed, fetch_list=[loss])
    cold = _spans()
    trace.clear()
    exe.run_steps(3, feed=feed, fetch_list=[loss])
    warm = _spans()
    for spans, compiles in ((cold, True), (warm, False)):
        root, = [e for e in spans if e["name"] == "executor.step"]
        assert root["args"]["kind"] == "run_steps" and root["args"]["k"] == 3
        phases = [e["name"] for e in spans if e["parent"] == root["id"]]
        assert phases == ["executor.prepare", "executor.launch",
                          "executor.commit"]
        under = {e["name"] for e in _under(root, spans)}
        got = {"compile.trace", "compile.lower", "compile.backend"} & under
        assert got == ({"compile.trace", "compile.lower", "compile.backend"}
                       if compiles else set()), under
        assert ("executor.build_block" in under) == compiles
        assert "compile" not in under
    # the step program's own phases lie in the launch, where JAX runs them
    launch, = [e for e in cold if e["name"] == "executor.launch"]
    in_launch = [e for e in cold if e["parent"] == launch["id"]]
    # (the program's own two walks of the block close inside JAX's trace,
    # which is reported after them)
    assert [e["name"] for e in in_launch] == [
        "executor.lower_block", "executor.lower_block",
        "compile.trace", "compile.lower", "compile.backend"]
    assert all(e["cat"] == "compile" for e in in_launch[2:])
    # nested jits (every jnp call in the traced step) did not become spans
    assert sum(e["name"] == "compile.trace" for e in cold) < 20
    st = trace.self_times(cold)
    assert st[launch["id"]] < launch["dur"] - in_launch[0]["dur"] + 1.0


def test_a_users_own_jit_compiles_into_spans_without_a_parent():
    import jax
    import jax.numpy as jnp
    trace.clear()
    jax.jit(lambda a: jnp.tanh(a) * 3.25 + 7.5)(jnp.ones((3, 5)))
    mine = [e for e in _spans() if e["name"].startswith("compile.")]
    assert {"compile.trace", "compile.lower", "compile.backend"} <= {
        e["name"] for e in mine}
    assert all(e["parent"] is None and "step" not in e.get("args", {})
               for e in mine)


def test_persistent_cache_counters_follow_jax_events():
    from paddle_tpu.observability import compile_events
    before = (metrics.get("compile.persistent_cache_hits"),
              metrics.get("compile.persistent_cache_misses"))
    compile_events._on_event("/jax/compilation_cache/cache_hits")
    compile_events._on_event("/jax/compilation_cache/cache_misses")
    compile_events._on_event("/jax/compilation_cache/tasks_using_cache")
    assert (metrics.get("compile.persistent_cache_hits"),
            metrics.get("compile.persistent_cache_misses")) == (
        before[0] + 1, before[1] + 1)
    snap = metrics.snapshot()
    assert snap["compile.persistent_cache_hits"]["type"] == "counter"


def test_spans_lie_in_a_jax_profiler_capture_under_the_pt_prefix(tmp_path):
    """One clock with the device trace: in any jax.profiler capture the
    program's spans are host events named pt/<span> of the xplane."""
    import glob
    import jax
    from jax.profiler import ProfileData
    _fresh()
    exe, loss, feed = _build()
    exe.run_steps(2, feed=feed, fetch_list=[loss])       # compile + warm
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            exe.run_steps(2, feed=feed, fetch_list=[loss])
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    names = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names += [e.name for e in line.events
                          if e.name.startswith(trace.ANNOTATION_PREFIX)]
    for want in ("executor.step", "executor.prepare", "executor.launch",
                 "executor.commit"):
        assert names.count("pt/" + want) == 3, (want, sorted(set(names)))


def test_the_package_import_is_one_span_recorded_once():
    import subprocess
    import sys
    code = ("import sys, json, paddle_tpu\n"
            "from paddle_tpu.observability import trace\n"
            "evs = [e for e in trace.events()"
            " if e['name'] == 'startup.import']\n"
            "print(json.dumps({'n': len(evs), 'dur': evs[0]['dur'],"
            " 'parent': evs[0]['parent'],"
            " 'twice': 'paddle_tpu.__init__' in sys.modules}))\n")
    out = subprocess.run([sys.executable, "-c", code], text=True,
                         stdout=subprocess.PIPE, check=True, timeout=300,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["n"] == 1 and got["parent"] is None and got["dur"] > 0
    assert not got["twice"]      # fluid used to run the package body again


# --------------------------------------------------------------------------
# time to first step: startup.boot, the cache outcome of every
# compile.backend, executor.lower_block (PR 34)
# --------------------------------------------------------------------------

def _boot_child(prelude=""):
    """A fresh process that imports the package (after `prelude`) and
    prints its startup spans, with their start moved onto the wall clock
    by the same handshake flight.dump writes."""
    import subprocess
    import sys
    code = (prelude +
            "import json, paddle_tpu\n"
            "from paddle_tpu.observability import trace\n"
            "clock = trace.clock_handshake()\n"
            "off = (clock['wall_time_us'] - clock['trace_ts_us']) * 1e-6\n"
            "evs = [dict(e, wall_s=e['ts'] * 1e-6 + off)"
            " for e in trace.events() if e['name'].startswith('startup.')]\n"
            "print(json.dumps(evs))\n")
    t_spawn = time.time()
    out = subprocess.run([sys.executable, "-c", code], text=True,
                         stdout=subprocess.PIPE, check=True, timeout=300,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    return t_spawn, json.loads(out.stdout.strip().splitlines()[-1])


def test_boot_span_runs_from_the_os_creation_time_to_the_import():
    t_spawn, evs = _boot_child()
    boot, = [e for e in evs if e["name"] == "startup.boot"]   # once
    imp, = [e for e in evs if e["name"] == "startup.import"]
    assert boot["parent"] is None and boot["dur"] > 0
    # ends where startup.import starts (two views of one clock reading)
    assert boot["ts"] + boot["dur"] == pytest.approx(imp["ts"], abs=1.0)
    # starts at the process's creation: the spawn, give or take the OS's
    # clock tick and the fork
    assert -0.1 <= boot["wall_s"] - t_spawn <= 1.0
    assert boot["args"] == {"jax_imported": False,
                            "backend_initialized": False}


def test_boot_span_says_what_the_caller_had_done_first():
    _, evs = _boot_child("import jax\njax.devices()\n")
    boot, = [e for e in evs if e["name"] == "startup.boot"]
    assert boot["args"] == {"jax_imported": True,
                            "backend_initialized": True}
    assert boot["dur"] > 1e5         # jax's import is in it: over 0.1 s


def test_no_creation_time_from_the_os_no_boot_span(monkeypatch):
    import builtins
    real = builtins.open

    def no_proc(path, *a, **kw):
        if str(path).startswith("/proc/"):
            raise FileNotFoundError(path)
        return real(path, *a, **kw)

    monkeypatch.setattr(builtins, "open", no_proc)
    assert trace.process_created_us() is None
    monkeypatch.undo()
    created = trace.process_created_us()
    assert created is not None and created < trace.now_us()
    clock = trace.clock_handshake()
    assert set(clock) == {"wall_time_us", "trace_ts_us"}
    assert abs(clock["wall_time_us"] * 1e-6 - time.time()) < 5.0


def test_time_to_first_step_is_set_once_by_the_first_main_root(monkeypatch):
    from paddle_tpu.framework import executor as ex
    _fresh()
    metrics.reset("startup.time_to_first_step_s")
    monkeypatch.setattr(ex, "_first_step_unread", True)
    x = layers.data(name="x", shape=[6], dtype="float32")
    loss = layers.mean(layers.fc(x, 3))
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    # the startup program's root does not set it
    assert "startup.time_to_first_step_s" not in metrics.snapshot()
    feed = {"x": np.ones((4, 6), np.float32)}
    exe.run(feed=feed, fetch_list=[loss])
    got = metrics.snapshot()["startup.time_to_first_step_s"]
    assert got["type"] == "gauge"
    age = (trace.now_us() - trace.process_created_us()) * 1e-6
    assert 0 < got["value"] <= age + 0.05    # /proc/uptime ticks in 10 ms
    exe.run(feed=feed, fetch_list=[loss])
    assert metrics.get("startup.time_to_first_step_s") == got["value"]
    assert ex._first_step_unread is False


@pytest.fixture
def temp_compile_cache(tmp_path):
    """JAX's persistent cache pointed at an empty directory that keeps
    every entry, and put back as it was afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    cc.reset_cache()
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    yield
    cc.reset_cache()
    for k, v in before.items():
        jax.config.update(k, v)
    jax.clear_caches()


def _backend_spans(fun_name):
    got = [e for e in _spans() if e["name"] == "compile.backend"
           and e["args"].get("fun") == f"jit({fun_name})"]
    trace.clear()
    return got


def test_compile_backend_says_what_the_persistent_cache_did(
        temp_compile_cache):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    def cache_outcomes(a):
        return jnp.tanh(a) * 3.0625 + 9.75

    x = np.ones((3, 11), np.float32)
    counted = ("compile.persistent_cache_hits",
               "compile.persistent_cache_misses",
               "compile.persistent_cache_unwritten")
    before = [metrics.get(n) for n in counted]
    trace.clear()
    jax.jit(cache_outcomes)(x)
    first, = _backend_spans("cache_outcomes")
    assert first["args"]["cache"] == "miss_written"
    assert "fetch_s" not in first["args"]
    jax.clear_caches()
    jax.jit(cache_outcomes)(x)
    second, = _backend_spans("cache_outcomes")
    assert second["args"]["cache"] == "hit"
    assert 0 < second["args"]["fetch_s"] <= second["dur"] * 1e-6 + 1e-3
    # compiled, but under JAX's threshold for writing: the next process
    # compiles it again, and only this outcome says so
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e6)
    jax.jit(cache_outcomes)(np.ones((5, 13), np.float32))
    third, = _backend_spans("cache_outcomes")
    assert third["args"]["cache"] == "miss"
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.clear_caches()
    jax.jit(cache_outcomes)(x)
    off, = _backend_spans("cache_outcomes")
    assert off["args"] == {"fun": "jit(cache_outcomes)", "cache": "off"}
    assert [metrics.get(n) - b for n, b in zip(counted, before)] == [
        1, 1, 1]


def test_cache_outcome_is_per_thread_and_survives_a_failed_compile():
    """The record is the compiling thread's own, and one left behind (a
    compile that raised after JAX said it would use the cache) does not
    colour the next span of that thread."""
    from paddle_tpu.observability import compile_events as ce
    seen = {}

    def other():
        ce._on_event("/jax/compilation_cache/compile_requests_use_cache")
        ce._on_event("/jax/compilation_cache/cache_hits")
        ce._on_duration("/jax/compilation_cache/cache_retrieval_time_sec",
                        0.25)
        seen["other"] = ce._cache_args()

    ce._on_event("/jax/compilation_cache/compile_requests_use_cache")
    t = threading.Thread(target=other)
    t.start()
    t.join()
    assert seen["other"] == {"cache": "hit", "fetch_s": 0.25}
    trace.clear()
    # this thread's compile raised: no backend report. The next compile
    # starts with its lowering, which drops the stale record
    ce._on_duration("/jax/core/compile/jaxpr_to_mlir_module_duration", 0.01,
                    fun_name="jit(next)")
    ce._on_duration("/jax/core/compile/backend_compile_duration", 0.02,
                    fun_name="jit(next)")
    backend, = [e for e in _spans() if e["name"] == "compile.backend"]
    assert backend["args"] == {"fun": "jit(next)", "cache": "off"}


def _lower_blocks(spans=None):
    return [e for e in (spans if spans is not None else _spans())
            if e["name"] == "executor.lower_block"]


def _by_op_seconds(span):
    rows = span["args"]["by_op"]
    assert all(len(r) == 3 and isinstance(r[0], str) and r[1] >= 1
               and r[2] >= 0 for r in rows) and len(rows) <= 8
    assert [r[2] for r in rows] == sorted((r[2] for r in rows),
                                          reverse=True)
    return sum(r[2] for r in rows)


def test_lower_block_is_one_span_a_trace_and_none_on_a_warm_dispatch():
    _fresh()
    exe, loss, feed = _build(width=7)            # a program no test compiled
    trace.clear()
    exe.run(feed=feed, fetch_list=[loss])
    cold = _spans()
    walk, = _lower_blocks(cold)
    launch, = [e for e in cold if e["name"] == "executor.launch"]
    assert walk["parent"] == launch["id"]
    assert walk["args"]["ops"] == len(
        fluid.default_main_program().global_block().ops)
    assert walk["args"]["shapes_only"] is False
    assert walk["args"]["step"] == launch["args"]["step"]
    # it lies inside JAX's own trace of the step, which is reported after it
    outer, = [e for e in cold if e["name"] == "compile.trace"
              and e["parent"] == launch["id"]]
    assert outer["ts"] <= walk["ts"] + 1.0
    assert walk["ts"] + walk["dur"] <= outer["ts"] + outer["dur"] + 1.0
    assert 0 < _by_op_seconds(walk) <= walk["dur"] * 1e-6
    types = {r[0] for r in walk["args"]["by_op"]}
    assert types & {"mul", "grad(mul)", "adam", "tanh", "grad(tanh)"}, types
    assert not any(t == "__vjp__" for t in types)
    trace.clear()
    exe.run(feed=feed, fetch_list=[loss])
    assert _lower_blocks() == []


def test_run_steps_walks_the_block_twice_a_trace_once_for_shapes():
    _fresh()
    exe, loss, feed = _build(width=9)
    trace.clear()
    exe.run_steps(2, feed=feed, fetch_list=[loss])
    walks = _lower_blocks()
    assert [w["args"]["shapes_only"] for w in walks] == [True, False]
    assert len({w["parent"] for w in walks}) == 1
    trace.clear()
    exe.run_steps(2, feed=feed, fetch_list=[loss])
    assert _lower_blocks() == []


def test_ops_inside_a_segment_count_to_their_own_types():
    from paddle_tpu.parallel.transforms import apply_recompute
    _fresh()
    x = layers.data(name="x", shape=[6], dtype="float32")
    h = layers.fc(x, 10, act="tanh")
    out = layers.mean(layers.fc(h, 1))
    prog = fluid.default_main_program()
    apply_recompute(prog, [out.name])
    top = [op.type for op in prog.global_block().ops]
    assert "__segment__" in top and "tanh" not in top
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    trace.clear()
    exe.run(feed={"x": np.ones((4, 6), np.float32)}, fetch_list=[out])
    walk, = _lower_blocks()
    assert walk["args"]["ops"] == len(top)
    rows = {r[0]: r for r in walk["args"]["by_op"]}
    # the leaves by their own names, each counted; the container keeps
    # only what it spent outside them
    assert rows["tanh"][1] == 1 and rows["mul"][1] == 2
    assert rows["__segment__"][1] == top.count("__segment__")
    inside = sum(r[2] for t, r in rows.items() if t != "__segment__")
    assert inside > 0
    assert _by_op_seconds(walk) <= walk["dur"] * 1e-6


def test_lower_table_self_time_on_hand_made_lowerings(monkeypatch):
    """A container's row is its own time less the lowerings it ran; a
    `__vjp__` is `grad(<forward type>)`; outside a walk nothing is timed."""
    from paddle_tpu.framework import executor as ex

    def leaf(op_type, attrs=None):
        with ex._op_timer(op_type, attrs or {}):
            time.sleep(0.02)

    def box():
        with ex._op_timer("box", {}):
            time.sleep(0.01)
            leaf("leaf")
            leaf("__vjp__", {"fwd_type": "leaf"})

    assert ex._lower_table is None
    box()                                                    # not timed
    table = ex._LowerTable()
    monkeypatch.setattr(ex, "_lower_table", table)
    t0 = time.perf_counter()
    box()
    wall = time.perf_counter() - t0
    rows = table.rows
    assert set(rows) == {"box", "leaf", "grad(leaf)"}
    assert rows["leaf"][0] == rows["grad(leaf)"][0] == rows["box"][0] == 1
    assert rows["leaf"][1] >= 0.02 and rows["grad(leaf)"][1] >= 0.02
    assert 0.01 <= rows["box"][1] < rows["leaf"][1] + rows["grad(leaf)"][1]
    assert sum(r[1] for r in rows.values()) <= wall
    assert [r[0] for r in table.top()][-1] == "box"


def test_a_walk_inside_a_lowering_adds_to_the_open_table():
    """A sub-block's walk (a `__cond__` branch) opens no second span."""
    from paddle_tpu.framework import executor as ex
    trace.clear()
    with ex._lower_walk(3, False):
        outer = ex._lower_table
        with ex._op_timer("__cond__", {}):
            with ex._lower_walk(2, False):
                assert ex._lower_table is outer
                with ex._op_timer("scale", {}):
                    pass
        assert ex._lower_table is outer
    assert ex._lower_table is None
    walk, = _lower_blocks()
    assert walk["args"]["ops"] == 3 and walk["parent"] is None
    assert {r[0] for r in walk["args"]["by_op"]} == {"__cond__", "scale"}
    with pytest.raises(ValueError):              # a lowering that raises
        with ex._lower_walk(1, True):
            raise ValueError
    assert ex._lower_table is None


def _ring_shape(events):
    """What a dispatch put into the ring, without times and ids: the
    events in order, each with its parent's name and its arg keys."""
    by = {e["id"]: e["name"] for e in events if e.get("ph") == "X"}
    return [(e["ph"], e["name"], by.get(e.get("parent")),
             tuple(sorted(e.get("args", {})))) for e in events]


@pytest.mark.parametrize("k", [None, 3])
def test_a_warm_dispatch_puts_the_parents_events_into_the_ring(k):
    """The pin that the dispatch path got nothing from PR 34: a warm
    `run` / `run_steps(k)` records the root, its three phases and the
    fetch's flow pair, as before it."""
    _fresh()
    exe, loss, feed = _build()

    def dispatch():                  # lazy fetches: a FetchHandle each
        if k is None:
            return exe.run(feed=feed, fetch_list=[loss], sync=False)
        return exe.run_steps(k, feed=feed, fetch_list=[loss], sync=False)

    out, = dispatch()
    out.numpy()
    trace.clear()
    out, = dispatch()
    dispatched = _ring_shape(trace.events())
    step_args = ("exe", "step")
    root_args = ("exe", "k", "kind", "ops", "program", "step")
    assert dispatched == [
        ("X", "executor.prepare", "executor.step", step_args),
        ("X", "executor.launch", "executor.step", step_args),
        ("s", "fetch", None, ("name", "step")),
        ("X", "executor.commit", "executor.step", step_args),
        ("X", "executor.step", None, root_args)]
    out.numpy()                                  # the host reads the fetch
    drained = _ring_shape(trace.events())[len(dispatched):]
    assert drained == [("X", "fetch.materialize", None, ("name",)),
                       ("f", "fetch", None, ("name",))]
