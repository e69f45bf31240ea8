"""Replicated serving: N decode engines behind a round-robin frontend.

The reference scales AnalysisPredictor by Clone()-per-thread; the TPU
analog replicates the whole decode worker — each replica owns its slot
array and paged cache while SHARING the device-resident weights (params
are read-only to every window program). `replicated_engines` builds the
replicas from one prepared parameter set; `RoundRobinFrontend` spreads
submissions, skipping dead replicas, so one SLA-tripped engine degrades
capacity instead of availability.

Process-scale composition reuses the PR-7 supervisor: `worker_main` is a
launchable decode worker (heartbeat liveness, flight dumps, rank-sharded
request files) that `python -m paddle_tpu.distributed.launch
--nproc_per_node N scripts/serving_smoke.py --worker ...` hosts as a
supervised gang — the deadline-bounded rendezvous, fail-fast sibling
kill, and straggler naming all apply to serving workers exactly as to
trainers.
"""
from __future__ import annotations

import itertools
import json
import os
import signal
import threading
from typing import List, Optional

from .engine import DecodeEngine, EngineConfig
from .request import Request, RequestHandle
from .resilience import NoHealthyReplicaError, ServingFrontend  # noqa: F401
                                            # (re-exported: the serving
                                            # frontends live side by side)


def replicated_engines(n: int, params, model_config,
                       config: Optional[EngineConfig] = None,
                       **overrides) -> List[DecodeEngine]:
    """N engines over ONE weight set (prepare_params runs once inside the
    first engine; the rest adopt its device arrays, so replicas add cache
    HBM, not weight HBM)."""
    first = DecodeEngine(params, model_config, config=config, **overrides)
    return [first] + [_clone_engine(first) for _ in range(n - 1)]


def _clone_engine(src: DecodeEngine) -> DecodeEngine:
    """A replica sharing src's prepared params/scales (device arrays are
    immutable to the window program) with its own cache + scheduler.
    prepare_params NEVER runs for a clone — the _prepared fast path adopts
    src's exact device buffers, so HBM holds ONE weight copy (identity
    pinned per-array by tests/test_serving_resilience.py). A spec-enabled
    source hands its draft arm's prepared arrays over the same way: one
    draft weight copy across replicas."""
    return DecodeEngine(
        None, src.model_config, config=src.config,
        _prepared=(src.params, src.scales, src.compute_dtype),
        _draft_prepared=(src.spec.draft_prepared
                         if src.spec is not None else None))


class RoundRobinFrontend:
    """Spread requests over replicas; skip dead ones; aggregate stats."""

    def __init__(self, engines: List[DecodeEngine]):
        if not engines:
            raise ValueError("no engines")
        self.engines = list(engines)
        self._rr = itertools.count()
        self._lock = threading.Lock()

    def submit(self, request: Request,
               bounded: bool = True) -> RequestHandle:
        n = len(self.engines)
        with self._lock:
            start = next(self._rr)
        for probe in range(n):
            eng = self.engines[(start + probe) % n]
            if eng._dead is None:
                return eng.submit(request, bounded=bounded)
        # every replica dead: a typed signal the caller can act on
        # (restart the service, fail over to another pod) — silently
        # minting rejection handles hid total outage inside per-request
        # noise
        raise NoHealthyReplicaError(f"all {n} replicas dead")

    def generate(self, requests: List[Request], timeout: float = 300.0):
        """Batch-style: like every other batch caller, a finite known
        workload queues FCFS past the online admission bounds."""
        handles = [self.submit(r, bounded=False) for r in requests]
        return [h.result(timeout=timeout, raise_on_error=False)
                for h in handles]

    def stop(self):
        for e in self.engines:
            e.stop()

    def stats(self) -> dict:
        per = [e.stats() for e in self.engines]
        return {
            "replicas": len(per),
            "live": sum(1 for s in per if not s["dead"]),
            "completed": sum(s["completed"] for s in per),
            "windows": sum(s["windows"] for s in per),
            "per_replica": per,
        }


# ---------------------------------------------------------------------------
# supervised worker entry (distributed/launch.py gang member)
# ---------------------------------------------------------------------------

def worker_main(requests_path: str, out_dir: str,
                model: str = "tiny", dtype: str = "float32",
                max_slots: int = 4, max_len: int = 128,
                window: int = 0, replicas: int = 1) -> int:
    """One supervised decode worker: build the tiny GPT from seed 0, take
    the rank-th shard of the request file (JSONL: {"uid", "prompt",
    "max_new", "temperature"?, "top_k"?, "seed"?}), serve it through a
    ServingFrontend, write completions to <out_dir>/rank<r>.jsonl.
    Heartbeat + flight-dump plumbing is inherited from the launcher env
    contract.

    SIGTERM (the supervisor's preemption signal) triggers a GRACEFUL
    DRAIN bounded by the launcher-exported PADDLE_LAUNCH_GRACE_S budget:
    in-flight requests finish, unstarted ones are handed back and written
    to the output as state "handed_back" — the worker sheds cleanly and
    exits 0 instead of failing its streams."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from ..models.gpt import GPTConfig, build_lm_program
    from ..models.gpt_decode import params_from_scope
    from ..testing import reset_programs

    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0") or 0)
    world = int(os.environ.get("PADDLE_TRAINERS_NUM", "1") or 1)
    reset_programs(seed=0)
    cfg = GPTConfig.tiny() if model == "tiny" else GPTConfig()
    cfg.max_position = max(cfg.max_position, max_len)
    build_lm_program(cfg)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    params = params_from_scope(cfg)

    with open(requests_path) as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    mine = [r for i, r in enumerate(rows) if i % world == rank]

    out_path = os.path.join(out_dir, f"rank{rank}.jsonl")
    os.makedirs(out_dir, exist_ok=True)
    kw = dict(max_slots=max_slots, max_len=max_len, window=window,
              dtype=dtype)
    engines = (replicated_engines(replicas, params, cfg, **kw)
               if replicas > 1 else [DecodeEngine(params, cfg, **kw)])
    fe = ServingFrontend(engines)
    handed_back: List[Request] = []

    def _on_term(signum, frame):
        grace = float(os.environ.get("PADDLE_LAUNCH_GRACE_S", "10") or 10)
        handed_back.extend(fe.drain(timeout_s=max(grace * 0.5, 1.0)))

    prev_term = None
    if threading.current_thread() is threading.main_thread():
        prev_term = signal.signal(signal.SIGTERM, _on_term)
    try:
        completions = fe.generate([
            Request(prompt=np.asarray(r["prompt"], np.int32),
                    max_new_tokens=int(r["max_new"]),
                    temperature=float(r.get("temperature", 0.0)),
                    top_k=int(r.get("top_k", 0)),
                    seed=int(r.get("seed", 0)),
                    uid=str(r.get("uid", f"r{rank}-{i}")))
            for i, r in enumerate(mine)], timeout=600)
        handed = {r.uid for r in handed_back}
        with open(out_path, "w") as f:
            for c in completions:
                f.write(json.dumps({
                    "uid": c.uid,
                    "state": ("handed_back" if c.uid in handed
                              else c.state),
                    "tokens": c.tokens,
                    "finish_reason": c.finish_reason,
                    "ttft_ms": c.ttft_ms, "tpot_ms": c.tpot_ms,
                    "rank": rank}) + "\n")
    finally:
        if prev_term is not None:
            signal.signal(signal.SIGTERM, prev_term)
        fe.stop()
    # a drained worker sheds cleanly: handed-back / drain-shed requests
    # are NOT failures — the supervisor (or its surviving workers) owns
    # them now
    bad = [c for c in completions
           if not c.ok and c.uid not in handed
           and c.finish_reason != "shed:draining"]
    return 1 if bad else 0
