"""Fault-tolerant serving: replica failover, health loop, graceful drain.

The reference hardens its serving tier the same way it hardens training
(AnalysisPredictor Clone()-per-thread isolation, the PS stack's
retry/degraded-serving discipline); this module applies the PR-1/PR-7
resilience vocabulary — typed deadlines, seeded fault injection,
supervised recovery — to the decode service, built on ONE property the
training side does not have: decode is a pure function of
(prompt, seed, token_index) (`fold_in(PRNGKey(seed), idx)`), so a
request re-dispatched to a different replica REPLAYS bit-identically.
Failover is therefore provably lossless, not best-effort.

Pieces (docs/serving.md "Failure semantics"):

* **Replica failover** — a dying engine no longer hard-fails its work:
  `DecodeEngine._fail_all` hands every in-flight request (prompt, seed,
  tokens emitted so far) to the frontend's failover sink, which
  re-dispatches to the least-loaded healthy replica; the handle swallows
  the deterministic replay of already-streamed tokens
  (`RequestHandle._arm_resume`). A bounded per-request budget
  (`FLAGS_serving_failover_budget`) turns repeat victims into a typed
  `RequestFailedError` instead of a ping-pong.
* **Health states & resurrection** — live → suspect (the engine tripped)
  → dead (frontend-confirmed) → resurrecting → live. The frontend's
  health loop rebuilds a dead engine's cache pool against the SHARED
  weight arrays (`DecodeEngine.resurrect`, no recompile — the window jit
  survives) and re-admits it only after a CANARY decode matches a live
  replica's output bit-for-bit; attempts ride a `RetryPolicy`
  (`FLAGS_serving_resurrect_budget`), exhaustion parks the engine dead.
* **Least-loaded routing** — `ServingFrontend.submit` routes to the
  live replica with the fewest pending decode tokens (replacing the
  blind round-robin); no live replica raises the typed
  `NoHealthyReplicaError`.
* **Graceful drain** — `drain()` stops admission (new submits shed with
  reason `draining`), lets in-flight slots decode to completion, and
  hands back the unstarted queue as `Request` objects so a preempted
  serving worker (SIGTERM from the launch.py supervisor) sheds cleanly
  instead of failing its streams.

Everything is drivable deterministically through `resilience/faults.py`
sites `serving.window` / `serving.prefill` / `serving.admit`;
`scripts/chaos_smoke.py --serving-drill` kills a replica mid-stream and
pins bit-parity against an undisturbed oracle run.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import numpy as np

from ..flags import flag
from ..framework import errors as _errors
from ..observability import metrics as _metrics
from ..observability import trace as _trace
from ..resilience.retry import RetryPolicy
from .request import (Request, RequestFailedError, RequestHandle,
                      RequestState, ServingError)


class Health:
    """Engine health as the frontend sees it. SUSPECT is self-reported
    (the engine tripped and failed over its work); DEAD is the frontend's
    confirmation; RESURRECTING covers the rebuild + canary gate."""
    LIVE = "live"
    SUSPECT = "suspect"
    DEAD = "dead"
    RESURRECTING = "resurrecting"


class NoHealthyReplicaError(ServingError):
    """Every replica behind the frontend is dead (and resurrection, if
    enabled, has not brought one back). Typed so callers can distinguish
    "service down" from a per-request rejection."""


def shed_handle(handle: RequestHandle, reason: str,
                detail: str) -> RequestHandle:
    """Finish a handle as SHED with its typed shed reason — the ONE
    implementation of the shed contract (counters + trace instant +
    `shed:<reason>` finish), shared by the engine's admission control and
    the frontend's draining gate."""
    _metrics.inc("serving.shed_total")
    _metrics.inc(f"serving.shed.{reason}")
    _trace.instant("serving.shed",
                   args={"uid": handle.request.uid, "reason": reason})
    handle._finish(RequestState.REJECTED, f"shed:{reason}", error=detail)
    return handle


# the fixed canary request: tiny, greedy, deterministic — its tokens are a
# pure function of the weights, so a resurrected replica that reproduces a
# live replica's canary bit-for-bit is provably serving the same model
_CANARY_PROMPT_LEN = 4
_CANARY_NEW_TOKENS = 3


class ServingFrontend:
    """N replicas with least-loaded routing, failover, a health loop, and
    graceful drain. The production frontend; `RoundRobinFrontend` remains
    as the minimal baseline."""

    def __init__(self, engines: List, resurrect: bool = True):
        if not engines:
            raise ValueError("no engines")
        self.engines = list(engines)
        self._resurrect_enabled = bool(resurrect)
        self._lock = threading.Lock()
        self._stopped = False
        self._draining = False
        self._gave_up: set = set()          # engine ids past the budget
        self._unexpected_errors: Dict[int, int] = {}
        self._canary_tokens: Optional[List[int]] = None
        self.failover_total = 0             # monotonic (the stats value)
        self.failover_log: List[str] = []   # last 1024 re-dispatched uids
        for eng in self.engines:
            eng._failover = self._failover_sink
        self._health_thread = threading.Thread(
            target=self._health_loop, daemon=True,
            name="serving-frontend-health")
        self._health_thread.start()

    # ------------------------------------------------------------------
    # routing
    # ------------------------------------------------------------------
    def _live(self, exclude=None) -> List:
        return [e for e in self.engines
                if e is not exclude and e.health == Health.LIVE
                and e._dead is None]

    def submit(self, request: Request,
               bounded: bool = True) -> RequestHandle:
        if self._draining or self._stopped:
            return shed_handle(RequestHandle(request), "draining",
                               "frontend draining")
        # least-loaded over the live set, preferring replicas with queue
        # room (load is token-weighted, the queue bound entry-counted —
        # shedding queue_full while a sibling has room would be wrong);
        # the _probe submit returns None (no shed counters minted) if
        # the pick dies under our feet, so a routing retry that lands
        # elsewhere leaves no false telemetry
        for _ in range(len(self.engines)):
            live = self._live()
            if not live:
                break
            with_room = [e for e in live if not e.queue_full()]
            eng = min(with_room or live, key=lambda e: e.load())
            handle = eng.submit(request, _probe=True, bounded=bounded)
            if handle is not None:
                return handle
        dead = sum(1 for e in self.engines if e.health == Health.DEAD)
        raise NoHealthyReplicaError(
            f"no healthy replica ({len(self.engines)} total, "
            f"{dead} dead)")

    def generate(self, requests: List[Request], timeout: float = 300.0):
        """Batch-style (`bounded=False`, like DecodeEngine.generate): a
        finite known workload queues FCFS past the online admission
        bounds — a worker serving its request shard must not shed its own
        batch tail as queue_full."""
        handles = [self.submit(r, bounded=False) for r in requests]
        return [h.result(timeout=timeout, raise_on_error=False)
                for h in handles]

    # ------------------------------------------------------------------
    # failover
    # ------------------------------------------------------------------
    def _failover_sink(self, src, victims, why: str,
                       charge_unserved: bool = False):
        """Called by a dying engine with its snapshotted in-flight work:
        [(Request, RequestHandle)] — queued entries and live slots alike.
        Re-dispatch each to a healthy replica (deterministic re-decode
        replays the already-streamed tokens), bounded by the per-request
        failover budget."""
        budget = int(flag("FLAGS_serving_failover_budget"))
        for req, handle in victims:
            # an ENGINE DEATH does not charge a never-served queue victim
            # (it is freely re-routable — the same distinction drain()
            # draws); a PREFILL failure (charge_unserved=True) always
            # charges, because a deterministically-bad request would
            # otherwise ping-pong between live replicas forever
            if charge_unserved or handle.tokens_so_far():
                handle.failovers += 1
            if handle.failovers > budget:
                handle._finish(
                    RequestState.FAILED,
                    "failover budget exhausted",
                    error=f"{handle.failovers - 1} failover(s) already "
                          f"spent (budget {budget}); engine death: {why}")
                continue
            replay = handle._arm_resume()
            placed = False
            for eng in sorted(self._live(exclude=src),
                              key=lambda e: e.load()):
                if eng.submit(req, _handle=handle,
                              _failover=True) is not None:
                    placed = True
                    break
            if placed:
                _metrics.inc("serving.failovers")
                _trace.instant("serving.failover",
                               args={"uid": req.uid, "replay": replay,
                                     "attempt": handle.failovers})
                with self._lock:
                    self.failover_total += 1
                    self.failover_log.append(req.uid)
                    del self.failover_log[:-1024]   # bounded memory
            else:
                handle._finish(
                    RequestState.FAILED,
                    "no healthy replica for failover",
                    error=f"engine death: {why}")

    # ------------------------------------------------------------------
    # health loop + resurrection
    # ------------------------------------------------------------------
    def _health_loop(self):
        while not self._stopped:
            time.sleep(
                float(flag("FLAGS_serving_health_interval_ms")) / 1000.0)
            if self._stopped or self._draining:
                continue
            for eng in self.engines:
                if self._stopped or self._draining:
                    break
                try:
                    self._health_tick(eng)
                except Exception as e:  # noqa: BLE001 — the loop IS the
                    # resilience tier: an unexpected error (a canary
                    # result timing out, a rebuild raising) must never
                    # silently kill the daemon thread and with it every
                    # future confirmation/resurrection
                    _metrics.inc("serving.health_loop_errors")
                    _trace.instant("serving.health_loop_error",
                                   args={"engine": eng._id,
                                         "error": repr(e)})
                    if eng.health == Health.RESURRECTING:
                        eng._dead = f"resurrection error: {e!r}"
                        eng._set_health(Health.DEAD)
                    n = self._unexpected_errors.get(id(eng), 0) + 1
                    self._unexpected_errors[id(eng)] = n
                    if n >= int(flag("FLAGS_serving_resurrect_budget")):
                        self._gave_up.add(id(eng))
                        _metrics.inc("serving.resurrect_gave_up")

    def _health_tick(self, eng):
        h = eng.health
        if h == Health.LIVE and eng._dead is not None:
            # died without self-reporting (stop()-time _fail_all)
            eng._set_health(Health.SUSPECT)
        elif h == Health.SUSPECT:
            eng._set_health(Health.DEAD)    # frontend-confirmed
        elif (h == Health.DEAD and self._resurrect_enabled
                and id(eng) not in self._gave_up):
            self._try_resurrect(eng)
            return
        # the DRAFT arm walks the same ladder, one level down: a degraded
        # draft only costs speculation (the target keeps serving plain
        # decode, zero failed requests), so its resurrection runs behind
        # a LIVE target and re-arms only after the canary passes WITH
        # speculation armed — a valid gate because spec-on == spec-off
        # bitwise
        spec = getattr(eng, "spec", None)
        if spec is None or eng.health != Health.LIVE \
                or eng._dead is not None:
            return
        if spec.health == Health.SUSPECT:
            spec._set_health(Health.DEAD)   # frontend-confirmed
        elif (spec.health == Health.DEAD and self._resurrect_enabled
                and ("draft", id(eng)) not in self._gave_up):
            self._try_resurrect_draft(eng)

    def _try_resurrect(self, eng):
        policy = RetryPolicy(
            max_attempts=int(flag("FLAGS_serving_resurrect_budget")),
            base_delay_s=0.05, max_delay_s=1.0, deadline_s=None,
            retry_on=(_errors.UnavailableError,))
        try:
            policy.call(self._resurrect_once, eng,
                        site="serving.resurrect",
                        abort=lambda: self._stopped or self._draining)
        except _errors.DeadlineExceededError as e:
            eng._set_health(Health.DEAD)
            if self._stopped or self._draining:
                return    # ABORTED by shutdown/drain — the budget was not
                          # exhausted, so don't park the engine as such
            self._gave_up.add(id(eng))
            eng._dead = f"resurrection budget exhausted: {e}"
            _metrics.inc("serving.resurrect_gave_up")

    def _resurrect_once(self, eng):
        if self._stopped or self._draining:
            raise _errors.Unavailable("frontend stopping — resurrection "
                                      "of engine %d aborted", eng._id)
        eng.resurrect()
        expected = self._canary_expected()
        comp = self._run_canary(eng)
        if self._stopped:
            # stop() raced the canary: a "stopped" frontend must not leak
            # a revived engine with a live service thread + fresh pool
            eng.stop()
            raise _errors.Unavailable("frontend stopped during the canary "
                                      "of engine %d", eng._id)
        if eng._dead is not None:
            # the engine died DURING its canary — the failover sink may
            # have re-dispatched the canary to a healthy replica, whose
            # correct tokens must not vouch for this broken engine
            eng._set_health(Health.DEAD)
            raise _errors.Unavailable(
                "engine %d died during its canary decode (%s)",
                eng._id, eng._dead)
        if not comp.ok or (expected is not None
                           and comp.tokens != expected):
            eng._dead = (f"canary failed: got {comp.tokens} "
                         f"want {expected} ({comp.finish_reason})")
            eng._set_health(Health.DEAD)
            raise _errors.Unavailable("serving canary mismatch on engine "
                                      "%d", eng._id)
        if expected is None:
            # ADMITTED on completes-cleanly: no live replica existed to
            # derive the bit-match expectation — say so loudly, once per
            # ungated resurrection (not per retry attempt), because the
            # documented contract is a bit-match
            _metrics.inc("serving.canary_ungated")
            _trace.instant("serving.canary_ungated",
                           args={"engine": eng._id})
        eng._set_health(Health.LIVE)
        # a clean recovery forgives earlier transient health-loop errors:
        # without this, N transient canary timeouts spread over the
        # engine's lifetime would permanently disable its resurrection
        self._unexpected_errors.pop(id(eng), None)

    def _try_resurrect_draft(self, eng):
        policy = RetryPolicy(
            max_attempts=int(flag("FLAGS_serving_resurrect_budget")),
            base_delay_s=0.05, max_delay_s=1.0, deadline_s=None,
            retry_on=(_errors.UnavailableError,))
        try:
            policy.call(self._resurrect_draft_once, eng,
                        site="serving.spec.resurrect",
                        abort=lambda: self._stopped or self._draining)
        except _errors.DeadlineExceededError:
            eng.spec._set_health(Health.DEAD)
            if self._stopped or self._draining:
                return
            self._gave_up.add(("draft", id(eng)))
            _metrics.inc("serving.resurrect_gave_up")

    def _resurrect_draft_once(self, eng):
        if self._stopped or self._draining:
            raise _errors.Unavailable(
                "frontend stopping — draft resurrection of engine %d "
                "aborted", eng._id)
        spec = eng.spec
        spec.resurrect_draft()
        # provisional re-arm: the canary must decode THROUGH speculation
        # to vouch for the draft path, and the bit-parity contract makes
        # its expectation identical either way
        spec.rearm()
        expected = self._canary_expected()
        comp = self._run_canary(eng)
        if eng._dead is not None:
            # the TARGET died during the spec-armed canary: the draft
            # cannot be vouched for, and the engine's own ladder owns
            # the recovery now
            spec._set_health(Health.DEAD)
            raise _errors.Unavailable(
                "engine %d died during the spec-armed canary (%s)",
                eng._id, eng._dead)
        if not comp.ok or (expected is not None
                           and comp.tokens != expected):
            spec._set_health(Health.DEAD)
            raise _errors.Unavailable(
                "spec-armed canary mismatch on engine %d", eng._id)
        _metrics.inc("serving.spec.rearmed")
        _trace.instant("serving.spec.rearmed", args={"engine": eng._id})

    def _canary_expected(self) -> Optional[List[int]]:
        """The canary's expected tokens, derived (once) from a LIVE
        replica. If none is live the gate degrades to completes-cleanly —
        logged, because bit-comparison is the real contract."""
        if self._canary_tokens is None:
            live = self._live()
            if live:
                comp = self._run_canary(live[0])
                if comp.ok:
                    self._canary_tokens = comp.tokens
        return self._canary_tokens

    def _run_canary(self, eng):
        vocab = eng.model_config.vocab_size
        req = Request(
            prompt=np.arange(1, 1 + _CANARY_PROMPT_LEN) % vocab,
            max_new_tokens=_CANARY_NEW_TOKENS,
            uid=f"canary-e{eng._id}")
        handle = eng.submit(req)
        return handle.result(timeout=60.0, raise_on_error=False)

    # ------------------------------------------------------------------
    # drain + stop
    # ------------------------------------------------------------------
    def drain(self, timeout_s: Optional[float] = None) -> List[Request]:
        """Stop admission, finish in-flight windows, hand back the
        unstarted queue. New submits (and the handles of handed-back
        requests) shed with reason `draining`; the returned Requests can
        be re-submitted elsewhere by the caller (e.g. the supervisor's
        surviving serving workers)."""
        if timeout_s is None:
            timeout_s = float(flag("FLAGS_serving_drain_timeout_ms")) \
                / 1000.0
        self._draining = True
        deadline = time.monotonic() + timeout_s
        handed_back: List[Request] = []
        for eng in self.engines:
            if eng._dead is not None:
                continue
            # a small positive floor lets an engine past the deadline
            # still clear + hand back its queue (lock ops, cheap); the
            # total overshoot stays a fraction of a second per replica
            remaining = max(deadline - time.monotonic(), 0.1)
            handed_back.extend(
                req for req, _ in eng.drain(timeout_s=remaining))
        return handed_back

    def stop(self):
        self._stopped = True
        self._health_thread.join(timeout=5)
        for eng in self.engines:
            eng._failover = None     # stop()-time deaths must not bounce
        for eng in self.engines:
            eng.stop()

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------
    def stats(self) -> dict:
        per = [e.stats() for e in self.engines]
        return {
            "replicas": len(per),
            "live": sum(1 for e in self.engines
                        if e.health == Health.LIVE and e._dead is None),
            "health": {e._id: e.health for e in self.engines},
            "completed": sum(s["completed"] for s in per),
            "windows": sum(s["windows"] for s in per),
            "failovers": self.failover_total,
            "draining": self._draining,
            "per_replica": per,
        }
