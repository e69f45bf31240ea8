"""Fault-tolerant serving (paddle_tpu/serving/resilience.py): the
ISSUE-15 acceptance pins.

* replica FAILOVER is bit-lossless: a FaultPlan-killed engine's in-flight
  requests re-dispatch to a healthy replica and finish bit-identical to
  an undisturbed oracle run (decode is a pure function of
  (prompt, seed, token_idx)); the failover budget turns repeat victims
  into a typed RequestFailedError;
* ADMISSION CONTROL sheds typed: queue_full / deadline_unmeetable /
  unfundable / draining / admit_fault, each counted under
  serving.shed_total + serving.shed.<reason> and raised as ShedError;
* graceful DRAIN finishes in-flight work and hands back the unstarted
  queue;
* RESURRECTION rebuilds a dead engine's cache against the shared weights
  and re-admits it only past the canary gate
  (live -> suspect -> dead -> resurrecting -> live);
* replicas hold ONE weight copy (prepare_params never runs for a clone).
"""
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.flags import flag, set_flags
from paddle_tpu.models.gpt import GPTConfig, build_lm_program
from paddle_tpu.models import gpt_decode
from paddle_tpu.resilience import clear_plan, install_plan
from paddle_tpu.serving import (DecodeEngine, Health, NoHealthyReplicaError,
                                Request, RequestFailedError,
                                RoundRobinFrontend, ServingFrontend,
                                ShedError, replicated_engines)
from paddle_tpu.serving import engine as engine_mod
from paddle_tpu.serving.request import RequestState
from paddle_tpu.testing import reset_programs

# Tier-1 rebalance (ISSUE 16): ~41s; the failover/shed/resurrection pins
# here are re-proven end-to-end by ci.py's serving chaos drill
# (scripts/chaos_smoke.py --serving-drill) on every CI pass.
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def tiny_gpt():
    reset_programs(seed=0)
    cfg = GPTConfig.tiny()
    cfg.max_position = 64
    build_lm_program(cfg)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    return cfg, gpt_decode.params_from_scope(cfg)


@pytest.fixture(autouse=True)
def _fast_health_ticks():
    set_flags({"FLAGS_serving_health_interval_ms": 30.0})
    yield
    clear_plan()
    set_flags({"FLAGS_serving_health_interval_ms": 200.0})


GEO = dict(max_slots=3, block_size=8, num_blocks=32, max_len=32, window=4)


def _engine(cfg, params, **kw):
    base = dict(GEO)
    base.update(kw)
    return DecodeEngine(params, cfg, **base)


def _mixed_requests(cfg, n=6, seed=3):
    rng = np.random.RandomState(seed)
    reqs = []
    for i in range(n):
        sampled = i % 2 == 1            # greedy AND seeded top-k
        reqs.append(Request(
            prompt=rng.randint(0, cfg.vocab_size,
                               (int(rng.randint(3, 12)),)),
            max_new_tokens=int(rng.randint(4, 9)),
            temperature=0.8 if sampled else 0.0,
            top_k=16 if sampled else 0,
            seed=100 + i, uid=f"r{i}"))
    return reqs


def _oracle(cfg, params, reqs):
    clear_plan()
    eng = _engine(cfg, params)
    try:
        comps = eng.generate(reqs, timeout=240)
    finally:
        eng.stop()
    assert all(c.ok for c in comps), [(c.uid, c.state) for c in comps]
    return {c.uid: c.tokens for c in comps}


def _wait(pred, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return pred()


# ---------------------------------------------------------------------------
# one-weight-copy invariant (satellite: clone double-prepare fix)
# ---------------------------------------------------------------------------

def test_clone_prepares_once_and_shares_device_buffers(tiny_gpt,
                                                       monkeypatch):
    cfg, params = tiny_gpt
    calls = []
    real = engine_mod.prepare_params

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(engine_mod, "prepare_params", counting)
    engines = replicated_engines(3, params, cfg, **GEO)
    try:
        # prepare_params ran ONCE for the whole replica set...
        assert len(calls) == 1
        src = engines[0]
        for clone in engines[1:]:
            # ...and every clone holds the SAME device buffers (identity,
            # not equality: one weight copy in HBM)
            assert clone.params is src.params
            for k in src.params:
                assert clone.params[k] is src.params[k]
            assert clone.scales is src.scales
            assert clone.compute_dtype == src.compute_dtype
    finally:
        for e in engines:
            e.stop()


# ---------------------------------------------------------------------------
# failover: bit-parity + budget
# ---------------------------------------------------------------------------

def test_failover_bit_parity_vs_oracle(tiny_gpt):
    """The acceptance pin: a replica killed mid-decode (FaultPlan window
    fault) loses nothing — every request completes bit-identical to the
    undisturbed single-engine oracle, greedy and seeded top-k alike."""
    from paddle_tpu.observability import metrics as m
    cfg, params = tiny_gpt
    reqs = _mixed_requests(cfg, n=6)
    want = _oracle(cfg, params, reqs)
    for name in ("serving.failovers", "serving.engine_failures",
                 "serving.shed_total"):
        m.reset(name)
    plan = install_plan("serving.window:error:at=2", seed=0)
    engines = replicated_engines(2, params, cfg, **GEO)
    fe = ServingFrontend(engines, resurrect=False)
    try:
        handles = []
        for r in reqs:
            handles.append(fe.submit(r))
            time.sleep(0.002)       # staggered: both replicas get load
        comps = [h.result(timeout=240, raise_on_error=False)
                 for h in handles]
    finally:
        clear_plan()
        fe.stop()
    assert all(c.ok for c in comps), \
        [(c.uid, c.state, c.error) for c in comps if not c.ok]
    for c in comps:
        assert c.tokens == want[c.uid], (c.uid, c.tokens, want[c.uid])
    assert sum(r.fired for r in plan.rules) == 1
    assert m.get("serving.engine_failures") == 1
    assert m.get("serving.failovers") == len(fe.failover_log) >= 1
    assert m.get("serving.shed_total") == 0


def test_window_fault_single_victim_counts_one_failover(tiny_gpt):
    """FaultPlan-driven window fault with exactly one in-flight request
    -> exactly one failover counted, tokens still oracle-identical."""
    from paddle_tpu.observability import metrics as m
    cfg, params = tiny_gpt
    req = Request(prompt=np.arange(2, 8) % cfg.vocab_size,
                  max_new_tokens=12, uid="solo")
    want = _oracle(cfg, params, [req])
    m.reset("serving.failovers")
    install_plan("serving.window:error:at=2", seed=0)
    engines = replicated_engines(2, params, cfg, **GEO, )
    fe = ServingFrontend(engines, resurrect=False)
    try:
        c = fe.submit(req).result(timeout=240)
    finally:
        clear_plan()
        fe.stop()
    assert c.tokens == want["solo"]
    assert m.get("serving.failovers") == 1
    assert fe.failover_log == ["solo"]


def test_failover_budget_exhausted_raises_typed(tiny_gpt):
    """Every window faults on every replica: the request burns its
    failover budget and fails with the typed RequestFailedError; with
    resurrection off the frontend then has no healthy replica."""
    cfg, params = tiny_gpt
    set_flags({"FLAGS_serving_failover_budget": 1})
    install_plan("serving.window:error:every=1", seed=0)
    engines = replicated_engines(2, params, cfg, **GEO)
    fe = ServingFrontend(engines, resurrect=False)
    try:
        h = fe.submit(Request(prompt=np.arange(4) % cfg.vocab_size,
                              max_new_tokens=6, uid="doomed"))
        with pytest.raises(RequestFailedError) as ei:
            h.result(timeout=60)
        assert ei.value.completion.finish_reason in (
            "failover budget exhausted", "no healthy replica for failover")
        assert _wait(lambda: all(e._dead is not None for e in engines),
                     timeout=10)
        with pytest.raises(NoHealthyReplicaError):
            fe.submit(Request(prompt=np.arange(4) % cfg.vocab_size,
                              max_new_tokens=2))
    finally:
        clear_plan()
        set_flags({"FLAGS_serving_failover_budget": 2})
        fe.stop()


# ---------------------------------------------------------------------------
# admission control + load shedding
# ---------------------------------------------------------------------------

def test_shed_reason_table(tiny_gpt, monkeypatch):
    from paddle_tpu.observability import metrics as m
    cfg, params = tiny_gpt
    for name in ("serving.shed_total", "serving.shed.queue_full",
                 "serving.shed.deadline_unmeetable",
                 "serving.shed.unfundable", "serving.shed.draining",
                 "serving.shed.admit_fault"):
        m.reset(name)

    def mk(plen=4, new=4, **kw):
        return Request(prompt=np.arange(1, 1 + plen) % cfg.vocab_size,
                       max_new_tokens=new, **kw)

    # service thread disabled so the queue only grows
    eng = _engine(cfg, params, max_queue=3)
    monkeypatch.setattr(eng, "_ensure_thread", lambda: None)
    try:
        # admit_fault: the FaultPlan admission site sheds typed
        install_plan("serving.admit:error:at=1", seed=0)
        h = eng.submit(mk())
        clear_plan()
        with pytest.raises(ShedError) as ei:
            h.result(timeout=5)
        assert ei.value.reason == "admit_fault"

        assert eng.submit(mk()).state == RequestState.QUEUED
        assert eng.submit(mk()).state == RequestState.QUEUED

        # deadline_unmeetable: with a measured window EWMA and two queued
        # requests, a millisecond deadline cannot be met
        eng._window_ms_ewma = 1000.0
        assert eng.queue_wait_estimate_ms() > 0
        h = eng.submit(mk(new=4, deadline_ms=0.5))
        with pytest.raises(ShedError) as ei:
            h.result(timeout=5)
        assert ei.value.reason == "deadline_unmeetable"

        # queue_full: the submit-queue bound sheds past max_queue
        assert eng.submit(mk()).state == RequestState.QUEUED
        h = eng.submit(mk())
        with pytest.raises(ShedError) as ei:
            h.result(timeout=5)
        assert ei.value.reason == "queue_full"

        # draining: drained engines shed new work and hand back the queue
        unstarted = eng.drain(timeout_s=5)
        assert len(unstarted) == 3
        h = eng.submit(mk())
        with pytest.raises(ShedError) as ei:
            h.result(timeout=5)
        assert ei.value.reason in ("draining", "engine_dead")
        assert ei.value.reason == "draining" or eng._dead is None
    finally:
        eng.stop()

    # unfundable: a budget the pool could NEVER fund sheds at submit
    small = _engine(cfg, params, num_blocks=3, max_len=32)
    try:
        h = small.submit(mk(plen=9, new=10))
        with pytest.raises(ShedError) as ei:
            h.result(timeout=5)
        assert ei.value.reason == "unfundable"
    finally:
        small.stop()

    # 1 admit_fault + 1 deadline + 1 queue_full + 1 unfundable + 4
    # draining (3 handed-back by drain + 1 post-drain submit)
    assert m.get("serving.shed_total") == 8.0
    for reason in ("queue_full", "deadline_unmeetable", "admit_fault",
                   "unfundable"):
        assert m.get(f"serving.shed.{reason}") == 1.0, reason
    assert m.get("serving.shed.draining") == 4.0


def test_queue_wait_histogram_observed(tiny_gpt):
    from paddle_tpu.observability import metrics as m
    cfg, params = tiny_gpt
    m.reset("serving.queue_wait_ms")
    eng = _engine(cfg, params)
    try:
        comps = eng.generate(_mixed_requests(cfg, n=3, seed=9),
                             timeout=240)
    finally:
        eng.stop()
    assert all(c.ok for c in comps)
    snap = m.snapshot()["serving.queue_wait_ms"]
    assert snap["count"] == 3 and snap["p50"] is not None


def test_least_loaded_routing(tiny_gpt, monkeypatch):
    """Submissions land on the replica with the fewest pending decode
    tokens, not blindly round-robin."""
    cfg, params = tiny_gpt
    engines = replicated_engines(2, params, cfg, **GEO)
    for e in engines:
        monkeypatch.setattr(e, "_ensure_thread", lambda: None)
    fe = ServingFrontend(engines, resurrect=False)
    try:
        def mk(new, uid):
            return Request(prompt=np.arange(4) % cfg.vocab_size,
                           max_new_tokens=new, uid=uid)
        fe.submit(mk(8, "big"))            # engine A: load 8
        for i in range(4):
            fe.submit(mk(1, f"s{i}"))      # all land on B (loads 1..4)
        fe.submit(mk(1, "s4"))             # B at 4 < A at 8 -> B again
        loads = sorted(e.load() for e in engines)
        queues = sorted(len(e._queue) for e in engines)
        assert loads == [5, 8]
        assert queues == [1, 5]
    finally:
        fe.stop()


# ---------------------------------------------------------------------------
# graceful drain
# ---------------------------------------------------------------------------

def test_drain_finishes_inflight_and_hands_back_unstarted(tiny_gpt):
    cfg, params = tiny_gpt
    eng = _engine(cfg, params, max_slots=1, window=2)
    try:
        a = eng.submit(Request(prompt=np.arange(5) % cfg.vocab_size,
                               max_new_tokens=10, uid="inflight"))
        assert _wait(lambda: a.state == RequestState.DECODE, timeout=60)
        b = eng.submit(Request(prompt=np.arange(5) % cfg.vocab_size,
                               max_new_tokens=4, uid="unstarted"))
        unstarted = eng.drain(timeout_s=60)
        # the in-flight request DECODED TO COMPLETION...
        ca = a.result(timeout=60)
        assert ca.ok and len(ca.tokens) == 10
        # ...the unstarted one came back typed, with its Request intact
        assert [r.uid for r, _ in unstarted] == ["unstarted"]
        with pytest.raises(ShedError) as ei:
            b.result(timeout=5)
        assert ei.value.reason == "draining"
    finally:
        eng.stop()


def test_frontend_drain_returns_requests_and_sheds_new(tiny_gpt,
                                                       monkeypatch):
    cfg, params = tiny_gpt
    engines = replicated_engines(2, params, cfg, **GEO)
    for e in engines:
        monkeypatch.setattr(e, "_ensure_thread", lambda: None)
    fe = ServingFrontend(engines, resurrect=False)
    try:
        reqs = _mixed_requests(cfg, n=4, seed=5)
        handles = [fe.submit(r) for r in reqs]
        handed_back = fe.drain(timeout_s=10)
        assert sorted(r.uid for r in handed_back) == \
            sorted(r.uid for r in reqs)
        for h in handles:
            with pytest.raises(ShedError):
                h.result(timeout=5)
        # post-drain submits shed without touching any engine
        c = fe.submit(reqs[0]).result(timeout=5, raise_on_error=False)
        assert c.finish_reason == "shed:draining"
    finally:
        fe.stop()


# ---------------------------------------------------------------------------
# resurrection + canary gate
# ---------------------------------------------------------------------------

def test_resurrection_canary_gate(tiny_gpt):
    """A dead replica rebuilds its pool, passes the canary bit-compare
    against a live replica, and rejoins: live -> suspect -> dead ->
    resurrecting -> live. Then it serves again."""
    from paddle_tpu.observability import metrics as m
    cfg, params = tiny_gpt
    engines = replicated_engines(2, params, cfg, **GEO)
    fe = ServingFrontend(engines)
    try:
        # warm both replicas (compile) before the kill
        comps = fe.generate(_mixed_requests(cfg, n=4, seed=7),
                            timeout=240)
        assert all(c.ok for c in comps)
        victim = engines[1]
        m.reset("serving.resurrections")
        victim.kill("induced death")
        # the kill defers to the service thread's window boundary: wait
        # for death to land, THEN for the health loop to resurrect
        assert _wait(lambda: victim.health != Health.LIVE, timeout=30)
        assert _wait(lambda: victim.health == Health.LIVE
                     and victim._dead is None, timeout=60), \
            (victim.health, victim._dead, victim.health_history)
        assert victim.health_history == [
            Health.LIVE, Health.SUSPECT, Health.DEAD,
            Health.RESURRECTING, Health.LIVE]
        assert m.get("serving.resurrections") >= 1
        assert fe.stats()["live"] == 2
        # the resurrected replica serves real traffic again
        req = Request(prompt=np.arange(3, 9) % cfg.vocab_size,
                      max_new_tokens=5, uid="post")
        c = victim.submit(req).result(timeout=240)
        assert c.ok and len(c.tokens) == 5
    finally:
        fe.stop()


def test_resurrection_canary_mismatch_keeps_engine_dead(tiny_gpt):
    """The gate is real: a replica whose canary does NOT match the
    expectation never rejoins; the budget exhausts typed and counted."""
    from paddle_tpu.observability import metrics as m
    cfg, params = tiny_gpt
    set_flags({"FLAGS_serving_resurrect_budget": 2})
    engines = replicated_engines(2, params, cfg, **GEO)
    fe = ServingFrontend(engines)
    try:
        m.reset("serving.resurrect_gave_up")
        fe._canary_tokens = [-1, -1, -1]     # unsatisfiable expectation
        victim = engines[1]
        victim.kill("induced death")
        assert _wait(lambda: id(victim) in fe._gave_up, timeout=60)
        assert victim.health == Health.DEAD
        assert "canary" in (victim._dead or "") \
            or "resurrection budget" in (victim._dead or "")
        assert m.get("serving.resurrect_gave_up") == 1
        assert fe.stats()["live"] == 1       # survivor still serves
        c = fe.submit(Request(prompt=np.arange(4) % cfg.vocab_size,
                              max_new_tokens=3)).result(timeout=240)
        assert c.ok
    finally:
        set_flags({"FLAGS_serving_resurrect_budget": 3})
        fe.stop()


# ---------------------------------------------------------------------------
# SLA trip -> failover (the PR-14 fail-hard path, now recoverable)
# ---------------------------------------------------------------------------

def test_sla_trip_fails_over_instead_of_failing_requests(tiny_gpt):
    """PR 14's brittle contract inverted: behind the resilient frontend,
    an SLA-tripped window re-dispatches its in-flight requests instead of
    killing them."""
    cfg, params = tiny_gpt
    engines = replicated_engines(2, params, cfg, **GEO)
    fe = ServingFrontend(engines, resurrect=False)
    # warm both, then wedge ONLY replica 0's window dispatch
    comps = fe.generate(_mixed_requests(cfg, n=4, seed=11), timeout=240)
    assert all(c.ok for c in comps)
    victim = engines[0]
    real = victim._window_jit

    def wedged(*a, **kw):
        time.sleep(30)
        return real(*a, **kw)

    victim._window_jit = wedged
    set_flags({"FLAGS_step_deadline_ms": 300.0})
    try:
        req = Request(prompt=np.arange(6) % cfg.vocab_size,
                      max_new_tokens=6, uid="sla")
        h = victim.submit(req)          # force it onto the wedged replica
        c = h.result(timeout=120)       # raises if it FAILED
        assert c.ok and len(c.tokens) == 6
        assert h.failovers >= 1
        assert victim._dead is not None
    finally:
        set_flags({"FLAGS_step_deadline_ms": 0.0})
        fe.stop()


# ---------------------------------------------------------------------------
# bench row shape (degraded-capacity arm)
# ---------------------------------------------------------------------------

def test_bench_degraded_row_shape():
    import bench
    row = bench.bench_serving_degraded(
        streams=4, dtype="float32", prompt_len=8, new_tokens=4,
        model="tiny", replicas=2)
    assert row["metric"] == "serving_degraded_tokens_per_sec"
    assert row["serving_degraded_arm"] is True
    assert row["replicas"] == 2 and row["replicas_killed"] == 1
    assert row["value"] > 0
    assert row.get("failed_requests", 0) == 0
    assert "ttft_p99_ms" in row and "failovers" in row
