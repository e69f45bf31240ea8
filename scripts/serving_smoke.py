#!/usr/bin/env python
"""Serving smoke: boot the decode engine and stream concurrent traffic.

The CI leg of the serving subsystem (scripts/ci.py runs this overlapped
with the test shards; --no-serving-smoke skips). Default mode:

* build the tiny GPT from seed and boot a DecodeEngine (continuous
  batching + paged KV cache, paddle_tpu/serving/);
* stream N (default 32) concurrent requests with STAGGERED arrivals and
  mixed prompt/generation lengths plus mixed sampling (greedy and seeded
  top-k) from submitter threads — the admission/retire churn the slot
  array exists for; every third request shares one system prompt and the
  radix prefix cache is ON, so the shared-prefix admission path (prefix
  share + CoW + suffix prefill) is exercised under the same churn;
* assert every request completes, the TTFT histogram saw every request,
  the prefix cache actually hit (hits >= 1, prefill tokens saved > 0),
  and the compiled decode-window program contains ZERO per-token KV-cache
  copies (serving/audit.py census) while the static twin
  (serving/program.py) carries zero donation/alias findings;
* print one summary line: tokens/s, TTFT p50/p99, window count.

--supervised adds the pod leg: a REAL 2-process gang of decode workers
hosted by the PR-7 supervisor (distributed/launch.py --nproc_per_node 2
<this script> --worker ...): rank-sharded request file in, per-rank
completion JSONL out, heartbeat/rendezvous/fail-fast semantics identical
to a training gang. The smoke validates both ranks served their shard.

Usage (a CPU tool on any machine: re-execs into a CPU-mesh child when the
environment is not one, and says on stderr that it ran on the CPU):

  python scripts/serving_smoke.py
  python scripts/serving_smoke.py --requests 64 --replicas 2
  python scripts/serving_smoke.py --supervised
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _build_tiny_params():
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models.gpt import GPTConfig, build_lm_program
    from paddle_tpu.models.gpt_decode import params_from_scope
    from paddle_tpu.testing import reset_programs
    reset_programs(seed=0)
    cfg = GPTConfig.tiny()
    cfg.max_position = 128
    build_lm_program(cfg)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    return cfg, params_from_scope(cfg)


def _mixed_requests(n, vocab, seed=0):
    import numpy as np
    from paddle_tpu.serving import Request
    rng = np.random.RandomState(seed)
    # one shared system prompt (mid-block at block_size=8: exercises the
    # partial-tail copy-on-write path) carried by every third request
    sysp = rng.randint(0, vocab, (13,))
    reqs = []
    for i in range(n):
        plen = int(rng.randint(3, 24))
        new = int(rng.randint(2, 12))
        sampled = i % 3 == 2
        prompt = rng.randint(0, vocab, (plen,))
        if i % 3 == 0:
            prompt = np.concatenate([sysp, prompt])
        reqs.append(Request(
            prompt=prompt,
            max_new_tokens=new,
            temperature=0.8 if sampled else 0.0,
            top_k=16 if sampled else 0,
            seed=1000 + i, uid=f"smoke-{i}"))
    return reqs


def run_smoke(n_requests: int, replicas: int, window: int) -> int:
    from paddle_tpu.observability import metrics as _metrics
    from paddle_tpu.serving import (DecodeEngine, ServingFrontend,
                                    replicated_engines)
    from paddle_tpu.serving import audit
    from paddle_tpu.serving.program import analyze_decode_step

    cfg, params = _build_tiny_params()
    kw = dict(max_slots=4, block_size=8, num_blocks=96, max_len=64,
              window=window, prefix_cache=True)
    if replicas > 1:
        engines = replicated_engines(replicas, params, cfg, **kw)
        target = ServingFrontend(engines)   # the production frontend:
        census_engine = engines[0]          # least-loaded + failover
    else:
        census_engine = target = DecodeEngine(params, cfg, **kw)

    reqs = _mixed_requests(n_requests, cfg.vocab_size)
    handles = [None] * len(reqs)
    t0 = time.perf_counter()

    def submitter(lo, hi, delay):
        for i in range(lo, hi):
            time.sleep(delay)                 # staggered arrivals
            handles[i] = target.submit(reqs[i])

    quarters = max(len(reqs) // 4, 1)
    threads = [threading.Thread(target=submitter,
                                args=(q * quarters,
                                      min((q + 1) * quarters, len(reqs)),
                                      0.002 * (q + 1)))
               for q in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    comps = [h.result(timeout=600, raise_on_error=False) for h in handles
             if h is not None]
    wall = time.perf_counter() - t0
    if hasattr(target, "stop"):
        target.stop()

    bad = [c for c in comps if not c.ok]
    n_tok = sum(len(c.tokens) for c in comps)
    snap = _metrics.snapshot()
    ttft = snap.get("serving.ttft_ms", {})
    failures = []
    if bad:
        failures.append(f"{len(bad)} requests not done: "
                        f"{[(c.uid, c.state, c.error) for c in bad[:5]]}")
    if len(comps) != len(reqs):
        failures.append(f"only {len(comps)}/{len(reqs)} handles returned")
    if ttft.get("count", 0) < len(reqs):
        failures.append(f"TTFT histogram count {ttft.get('count')} < "
                        f"{len(reqs)}")

    if replicas > 1:
        hits = sum(e.stats().get("prefix_cache_hits", 0) for e in engines)
        saved = sum(e.stats().get("prefill_tokens_saved", 0)
                    for e in engines)
    else:
        stats = target.stats()
        hits = stats.get("prefix_cache_hits", 0)
        saved = stats.get("prefill_tokens_saved", 0)
    if hits < 1 or saved < 1:
        failures.append(
            f"prefix cache never hit (hits={hits}, saved={saved}) — "
            "the shared-prefix leg did not exercise the cache")

    census = audit.decode_copy_census(census_engine)
    if census["per_token_kv_copies"]:
        failures.append(
            f"KV copy census: {census['kv_copy_findings']}")
    twin = analyze_decode_step()
    if twin["errors"] or twin["warnings"]:
        failures.append(f"static twin findings: {twin['findings']}")

    print(f"serving smoke: {len(comps)} requests, {n_tok} tokens in "
          f"{wall:.1f}s ({n_tok / wall:.1f} tok/s), "
          f"TTFT p50={ttft.get('p50')} p99={ttft.get('p99')} ms, "
          f"kv-copies={census['per_token_kv_copies']} "
          f"(copy population {sum(census['copy_population'].values())}), "
          f"prefix cache {hits} hit(s) / {saved} token(s) saved, "
          f"twin findings={twin['errors'] + twin['warnings']}")
    for f in failures:
        print(f"FAIL: {f}", file=sys.stderr)
    return 1 if failures else 0


# ---------------------------------------------------------------------------
# speculative-decoding leg (scripts/ci.py runs this overlapped as its own
# process: serving_smoke.py --spec)
# ---------------------------------------------------------------------------

def run_spec_smoke(n_requests: int) -> int:
    """Boot one spec-OFF and one spec-ON engine over the same tiny model
    and the same mixed traffic (greedy + seeded top-k, shared prefixes —
    the prefix cache stays ON so speculation is exercised over prefix
    hits too) and assert:

    * bit-parity — every spec-on completion equals its spec-off twin
      token-for-token (the construction contract, docs/serving.md
      "Speculative decoding");
    * speculation actually ran — rounds >= 1 and accepted >= 1 (a draft
      arm of the SAME checkpoint agrees with the target far more often
      than not);
    * the verify program passes both audit arms (zero pool-shaped
      copies, fallback attend) and its static twin (span > 1) carries
      zero donation/alias findings.
    """
    from paddle_tpu.serving import DecodeEngine
    from paddle_tpu.serving import audit
    from paddle_tpu.serving.program import analyze_decode_step

    cfg, params = _build_tiny_params()
    kw = dict(max_slots=4, block_size=8, num_blocks=96, max_len=64,
              window=4, prefix_cache=True)
    reqs = _mixed_requests(n_requests, cfg.vocab_size, seed=7)

    base = DecodeEngine(params, cfg, **kw)
    t0 = time.perf_counter()
    ref = base.generate(reqs, timeout=600)
    base_wall = time.perf_counter() - t0
    base.stop()

    spec_eng = DecodeEngine(params, cfg, spec=True, **kw)
    t0 = time.perf_counter()
    got = spec_eng.generate(reqs, timeout=600)
    spec_wall = time.perf_counter() - t0
    stats = spec_eng.stats()

    failures = []
    bad = [c for c in ref + got if not c.ok]
    if bad:
        failures.append(f"{len(bad)} requests not done: "
                        f"{[(c.uid, c.state, c.error) for c in bad[:5]]}")
    mismatched = [r.uid for r, g in zip(ref, got) if r.tokens != g.tokens]
    if mismatched:
        failures.append(
            f"spec-on != spec-off for {len(mismatched)} request(s): "
            f"{mismatched[:5]} — the bit-parity contract is broken")
    if stats.get("spec_rounds", 0) < 1:
        failures.append("speculation never ran a round "
                        f"(stats: {stats.get('spec_rounds')})")
    if stats.get("spec_accepted", 0) < 1:
        failures.append(
            "the draft arm never had a proposal accepted "
            f"(proposed={stats.get('spec_proposed')}) — speculation is "
            "running but pure overhead")

    vrow = audit.verify_copy_census(spec_eng)
    if vrow["pool_copies"]:
        failures.append(f"verify KV copy census: "
                        f"{vrow['kv_copy_findings']}")
    spec_eng.stop()
    span = vrow["span"]
    twin = analyze_decode_step(span=span)
    if twin["errors"] or twin["warnings"]:
        failures.append(
            f"static verify twin findings: {twin['findings']}")

    n_tok = sum(len(c.tokens) for c in got)
    rate = stats.get("spec_accept_rate", 0.0)
    print(f"spec smoke: {len(got)} requests, {n_tok} tokens; "
          f"accept rate {rate:.2f} over {stats.get('spec_rounds')} "
          f"round(s) ({stats.get('spec_accepted')}/"
          f"{stats.get('spec_proposed')} tokens), "
          f"off {base_wall:.1f}s vs on {spec_wall:.1f}s, "
          f"verify kv-copies={vrow['pool_copies']} (span {span}), "
          f"twin findings={twin['errors'] + twin['warnings']}")
    for f in failures:
        print(f"FAIL: {f}", file=sys.stderr)
    return 1 if failures else 0


# ---------------------------------------------------------------------------
# supervised gang leg
# ---------------------------------------------------------------------------

def run_worker(args) -> int:
    """Gang-member mode (invoked by distributed/launch.py)."""
    from paddle_tpu.serving.frontend import worker_main
    return worker_main(args.requests_file, args.out_dir,
                       dtype=args.dtype, max_slots=4, max_len=64)


def run_supervised(n_requests: int) -> int:
    import subprocess
    import numpy as np
    tmp = tempfile.mkdtemp(prefix="serving_gang_")
    req_path = os.path.join(tmp, "requests.jsonl")
    out_dir = os.path.join(tmp, "out")
    rng = np.random.RandomState(5)
    rows = [{"uid": f"gang-{i}",
             "prompt": rng.randint(0, 512, (int(rng.randint(3, 16)),)
                                   ).tolist(),
             "max_new": int(rng.randint(2, 8)), "seed": i}
            for i in range(n_requests)]
    with open(req_path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--nproc_per_node", "2", "--port", "7481",
           os.path.abspath(__file__), "--worker",
           "--requests-file", req_path, "--out-dir", out_dir]
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    if proc.returncode != 0:
        print("supervised gang FAILED:\n" + proc.stdout[-2000:] + "\n"
              + proc.stderr[-2000:], file=sys.stderr)
        return 1
    done = {}
    for rank in (0, 1):
        path = os.path.join(out_dir, f"rank{rank}.jsonl")
        with open(path) as f:
            recs = [json.loads(ln) for ln in f if ln.strip()]
        assert recs, f"rank {rank} served nothing"
        assert all(r["state"] == "done" for r in recs), recs[:3]
        done[rank] = len(recs)
    assert sum(done.values()) == n_requests, done
    print(f"supervised serving gang: {done} completions across 2 workers")
    return 0


def main():
    ap = argparse.ArgumentParser(description="decode-service smoke")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--window", type=int, default=4)
    ap.add_argument("--supervised", action="store_true",
                    help="add the launch.py-hosted 2-worker gang leg")
    ap.add_argument("--spec", action="store_true",
                    help="run ONLY the speculative-decoding leg (spec-on "
                         "vs spec-off bit-parity + acceptance + verify "
                         "censuses); ci.py overlaps this as its own run")
    ap.add_argument("--worker", action="store_true",
                    help="internal: run as a supervised gang member")
    ap.add_argument("--requests-file", default="")
    ap.add_argument("--out-dir", default="")
    ap.add_argument("--dtype", default="float32")
    args = ap.parse_args()

    if args.worker:
        return run_worker(args)

    from paddle_tpu.testing import run_as_cpu_tool
    run_as_cpu_tool(1, os.path.abspath(__file__), sys.argv[1:])

    if args.spec:
        return run_spec_smoke(args.requests)

    rc = run_smoke(args.requests, args.replicas, args.window)
    if args.supervised:
        rc = rc or run_supervised(max(args.requests // 4, 4))
    return rc


if __name__ == "__main__":
    sys.exit(main())
