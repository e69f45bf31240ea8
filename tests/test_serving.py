"""Decode service (paddle_tpu/serving/): the ISSUE-14 acceptance pins.

* paged-cache decode is BIT-IDENTICAL to the dense ring-cache scan
  (models/gpt_decode.generate) — same block body, one implementation;
* continuous-batched output per request is BIT-IDENTICAL to sequential
  single-request decode under fixed sampling seeds (greedy + seeded
  top-k) — token draws are pure functions of (request seed, token index),
  never of slot index, window boundary, or batch composition;
* ZERO per-token KV-cache copies: the compiled window program carries no
  pool-shaped copy op (serving/audit.py census) AND the static twin
  program reports no fetch_of_donated / write_after_donate findings
  (analysis/alias.py);
* the service plumbing composes: TTFT/TPOT histograms, request flow
  events, the FLAGS_step_deadline_ms SLA watchdog, the C-API decode
  session, and the round-robin replica frontend.
"""
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.flags import set_flags
from paddle_tpu.models.gpt import GPTConfig, build_lm_program
from paddle_tpu.models import gpt_decode
from paddle_tpu.serving import (BlockAllocator, DecodeEngine, Request,
                                RoundRobinFrontend, ServingError,
                                replicated_engines)
from paddle_tpu.serving import audit as serving_audit
from paddle_tpu.serving.request import RequestState
from paddle_tpu.testing import reset_programs


@pytest.fixture(scope="module")
def tiny_gpt():
    reset_programs(seed=0)
    cfg = GPTConfig.tiny()
    cfg.max_position = 64
    build_lm_program(cfg)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    return cfg, gpt_decode.params_from_scope(cfg)


def _engine(cfg, params, **kw):
    base = dict(max_slots=3, block_size=8, num_blocks=24, max_len=32,
                window=4)
    base.update(kw)
    return DecodeEngine(params, cfg, **base)


# ---------------------------------------------------------------------------
# acceptance: bit parity
# ---------------------------------------------------------------------------

def test_paged_decode_bit_identical_to_dense_ring_cache(tiny_gpt):
    """Engine greedy output == models/gpt_decode.generate (the dense
    [B, nh, max_len, hd] ring-cache scan), token for token."""
    cfg, params = tiny_gpt
    rng = np.random.RandomState(7)
    prompt = rng.randint(0, cfg.vocab_size, (2, 8)).astype(np.int64)
    want = np.asarray(gpt_decode.generate(params, cfg, prompt, 6))
    eng = _engine(cfg, params)
    try:
        comps = eng.generate(
            [Request(prompt=prompt[i], max_new_tokens=6) for i in range(2)],
            timeout=240)
    finally:
        eng.stop()
    for i, c in enumerate(comps):
        assert c.ok, c
        np.testing.assert_array_equal(np.asarray(c.tokens), want[i, 8:])


def test_continuous_bit_identical_to_sequential(tiny_gpt):
    """The continuous-batching acceptance pin: mixed lengths, greedy AND
    seeded top-k requests, submitted all-at-once vs one-at-a-time through
    the same engine — per-request tokens identical."""
    cfg, params = tiny_gpt
    rng = np.random.RandomState(3)
    reqs = []
    for i, (plen, new) in enumerate(
            [(5, 6), (11, 3), (8, 9), (3, 5), (14, 4), (7, 7)]):
        reqs.append(Request(
            prompt=rng.randint(0, cfg.vocab_size, (plen,)),
            max_new_tokens=new,
            temperature=0.0 if i % 2 == 0 else 0.8,
            top_k=0 if i % 2 == 0 else 16,
            seed=100 + i, uid=f"r{i}"))
    eng = _engine(cfg, params)
    try:
        cont = eng.generate(reqs, timeout=240)
        seq = eng.generate_sequential(reqs, timeout=240)
    finally:
        eng.stop()
    for a, b in zip(cont, seq):
        assert a.ok and b.ok, (a, b)
        assert a.tokens == b.tokens, (a.uid, a.tokens, b.tokens)
    # the sampled requests actually sampled (not all greedy-identical)
    assert any(c.tokens != cont[0].tokens for c in cont[1:])


def test_eos_latches_and_truncates(tiny_gpt):
    cfg, params = tiny_gpt
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, cfg.vocab_size, (8,))
    eng = _engine(cfg, params)
    try:
        greedy = eng.generate([Request(prompt=prompt, max_new_tokens=6)],
                              timeout=240)[0]
        assert greedy.ok and len(greedy.tokens) == 6
        eos = int(greedy.tokens[2])   # an eos the greedy path WILL emit
        c = eng.generate([Request(prompt=prompt, max_new_tokens=6,
                                  eos_token=eos)], timeout=240)[0]
    finally:
        eng.stop()
    assert c.finish_reason == "eos"
    # truncated AT the first greedy occurrence of the eos token
    cut = greedy.tokens.index(eos) + 1
    assert c.tokens == greedy.tokens[:cut]


# ---------------------------------------------------------------------------
# acceptance: zero per-token KV-cache copies
# ---------------------------------------------------------------------------

def test_window_program_has_zero_kv_copies(tiny_gpt):
    cfg, params = tiny_gpt
    eng = _engine(cfg, params)
    row = serving_audit.assert_zero_kv_copies(eng)
    assert row["per_token_kv_copies"] == 0
    assert row["instructions"] > 100   # a real program was censused
    eng.stop()


def test_static_twin_donation_clean():
    """The build-time half: the serving decode Program's pools are donated
    written state with no aliasing hazard, and the verifier/specs pass."""
    from paddle_tpu.serving.program import analyze_decode_step
    rep = analyze_decode_step()
    assert rep["errors"] == 0 and rep["warnings"] == 0, rep["findings"]
    assert set(rep["donation"]["donated"]) == \
        {"serving_k_pool", "serving_v_pool"}
    hazard = {f["check"] for f in rep["donation"]["findings"]}
    assert not ({"fetch_of_donated", "write_after_donate"} & hazard)


def test_census_detects_seeded_pool_copy(tiny_gpt):
    """The census is not vacuous: a pool-shaped copy planted in HLO text
    is found and named."""
    cfg, params = tiny_gpt
    eng = _engine(cfg, params)
    shape = eng.cache.config.pool_shape()
    dims = ",".join(str(d) for d in shape)
    fake = (f"  %poisoned = f32[{dims}] copy(f32[{dims}] %kv_pool)\n")
    found = serving_audit.kv_copy_findings(fake, shape)
    assert len(found) == 1 and found[0]["instruction"] == "poisoned"
    eng.stop()


# ---------------------------------------------------------------------------
# scheduler / cache mechanics
# ---------------------------------------------------------------------------

def test_block_allocator_contract():
    a = BlockAllocator(8)            # 7 allocatable (block 0 = scratch)
    assert a.free_blocks == 7
    got = a.alloc(7)
    assert got is not None and 0 not in got
    assert a.alloc(1) is None        # all-or-nothing exhaustion
    a.free(got[:3])
    assert a.free_blocks == 3
    with pytest.raises(ValueError):
        a.free([0])                  # scratch is never freeable


def test_pool_exhaustion_queues_fcfs(tiny_gpt):
    """More concurrent requests than the pool can fund: the overflow waits
    QUEUED and completes after retirements free blocks — nothing fails,
    nothing is preempted mid-flight."""
    cfg, params = tiny_gpt
    # pool funds ~2 requests at a time: 9 usable blocks, 4 blocks each
    eng = _engine(cfg, params, max_slots=3, num_blocks=10)
    rng = np.random.RandomState(11)
    reqs = [Request(prompt=rng.randint(0, cfg.vocab_size, (9,)),
                    max_new_tokens=5, uid=f"x{i}") for i in range(5)]
    try:
        comps = eng.generate(reqs, timeout=240)
    finally:
        eng.stop()
    assert all(c.ok for c in comps), [(c.uid, c.state) for c in comps]
    assert eng.cache.allocator.free_blocks == 9   # everything released


def test_rejections(tiny_gpt):
    cfg, params = tiny_gpt
    eng = _engine(cfg, params)
    try:
        h = eng.submit(Request(prompt=np.arange(40), max_new_tokens=10))
        assert h.state == RequestState.REJECTED
        with pytest.raises(ServingError, match="exceeds"):
            h.result(timeout=5)
        h2 = eng.submit(Request(prompt=np.arange(4), max_new_tokens=0))
        assert h2.state == RequestState.REJECTED
        c = h2.result(timeout=5, raise_on_error=False)
        assert not c.ok and "max_new_tokens" in c.finish_reason
    finally:
        eng.stop()


def test_streaming_tokens_so_far(tiny_gpt):
    cfg, params = tiny_gpt
    eng = _engine(cfg, params, window=2)
    try:
        h = eng.submit(Request(prompt=np.arange(5) % cfg.vocab_size,
                               max_new_tokens=8))
        seen = 0
        deadline = time.time() + 240
        while not h.done() and time.time() < deadline:
            n = len(h.tokens_so_far())
            assert n >= seen
            seen = n
            time.sleep(0.01)
        c = h.result(timeout=240)
        assert len(c.tokens) == 8
        assert c.ttft_ms is not None and c.ttft_ms > 0
        assert c.tpot_ms is not None
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# observability + SLA composition
# ---------------------------------------------------------------------------

def test_serving_metrics_and_flow_events(tiny_gpt):
    from paddle_tpu.observability import metrics as m
    from paddle_tpu.observability import trace
    cfg, params = tiny_gpt
    for name in ("serving.ttft_ms", "serving.tpot_ms"):
        m.reset(name)
    trace.clear()
    eng = _engine(cfg, params)
    rng = np.random.RandomState(2)
    try:
        comps = eng.generate(
            [Request(prompt=rng.randint(0, cfg.vocab_size, (6,)),
                     max_new_tokens=4, uid=f"m{i}") for i in range(3)],
            timeout=240)
    finally:
        eng.stop()
    assert all(c.ok for c in comps)
    snap = m.snapshot()
    assert snap["serving.ttft_ms"]["count"] == 3
    assert snap["serving.tpot_ms"]["count"] == 3
    assert snap["serving.ttft_ms"]["p50"] is not None
    assert m.get("serving.completed") >= 3
    assert m.get("serving.windows") >= 1
    evs = trace.events()
    starts = {e["args"]["uid"] for e in evs
              if e.get("ph") == "s" and e["name"] == "serving.request"}
    ends = {e["args"]["uid"] for e in evs
            if e.get("ph") == "f" and e["name"] == "serving.request"}
    assert {"m0", "m1", "m2"} <= starts and {"m0", "m1", "m2"} <= ends
    spans = {e["name"] for e in evs if e.get("ph") == "X"}
    assert "serving.window" in spans and "serving.prefill" in spans


def test_sla_watchdog_fails_inflight_and_kills_engine(tiny_gpt):
    """FLAGS_step_deadline_ms bounds the serving window: a wedged window
    trips the typed watchdog, in-flight requests FAIL (not hang), the
    engine goes dead, and later submissions are rejected."""
    from paddle_tpu import monitor
    cfg, params = tiny_gpt
    eng = _engine(cfg, params)
    real = eng._window_jit

    def wedged(*a, **kw):
        time.sleep(30)
        return real(*a, **kw)

    eng._window_jit = wedged
    set_flags({"FLAGS_step_deadline_ms": 300.0})
    try:
        h = eng.submit(Request(prompt=np.arange(4) % cfg.vocab_size,
                               max_new_tokens=6))
        c = h.result(timeout=60, raise_on_error=False)
        assert c.state == RequestState.FAILED
        assert "DeadlineExceeded" in (c.error or "")
        assert eng._dead is not None
        h2 = eng.submit(Request(prompt=np.arange(4) % cfg.vocab_size,
                                max_new_tokens=2))
        assert h2.state == RequestState.REJECTED
        from paddle_tpu.observability import metrics as m
        assert m.get("serving.sla_trips") >= 1
        assert monitor.stat_get("executor.step_deadline_trips") >= 1
    finally:
        set_flags({"FLAGS_step_deadline_ms": 0.0})
        eng.stop()


# ---------------------------------------------------------------------------
# frontend + capi + weight arms
# ---------------------------------------------------------------------------

def test_round_robin_frontend(tiny_gpt):
    cfg, params = tiny_gpt
    engines = replicated_engines(2, params, cfg, max_slots=2, block_size=8,
                                 num_blocks=16, max_len=32, window=4)
    assert engines[0].params is engines[1].params   # one weight copy
    fe = RoundRobinFrontend(engines)
    rng = np.random.RandomState(1)
    try:
        comps = fe.generate(
            [Request(prompt=rng.randint(0, cfg.vocab_size, (6,)),
                     max_new_tokens=4) for _ in range(6)], timeout=240)
    finally:
        fe.stop()
    assert all(c.ok for c in comps)
    st = fe.stats()
    assert st["live"] == 2
    assert all(s["completed"] > 0 for s in st["per_replica"])


def test_round_robin_skips_dead_replica(tiny_gpt):
    """ISSUE-15 satellite: the dead-replica skip path, pinned — a killed
    replica degrades capacity, the survivor takes the whole stream."""
    cfg, params = tiny_gpt
    engines = replicated_engines(2, params, cfg, max_slots=2, block_size=8,
                                 num_blocks=16, max_len=32, window=4)
    fe = RoundRobinFrontend(engines)
    engines[0].kill("induced death")
    rng = np.random.RandomState(8)
    try:
        comps = fe.generate(
            [Request(prompt=rng.randint(0, cfg.vocab_size, (6,)),
                     max_new_tokens=3) for _ in range(4)], timeout=240)
    finally:
        fe.stop()
    assert all(c.ok for c in comps), [(c.uid, c.state) for c in comps]
    assert engines[0].stats()["completed"] == 0
    assert engines[1].stats()["completed"] == 4
    assert fe.stats()["live"] == 1


def test_round_robin_all_dead_raises_typed(tiny_gpt):
    """ISSUE-15 satellite: every replica dead used to silently mint
    rejection handles (total outage hidden in per-request noise) — now a
    typed NoHealthyReplicaError."""
    from paddle_tpu.serving import NoHealthyReplicaError
    cfg, params = tiny_gpt
    engines = replicated_engines(2, params, cfg, max_slots=2, block_size=8,
                                 num_blocks=16, max_len=32, window=4)
    fe = RoundRobinFrontend(engines)
    for e in engines:
        e.kill("induced death")
    try:
        with pytest.raises(NoHealthyReplicaError, match="2 replicas"):
            fe.submit(Request(prompt=np.arange(4) % cfg.vocab_size,
                              max_new_tokens=2))
    finally:
        fe.stop()


def test_capi_decode_session_runs_batched_decode(tiny_gpt, tmp_path):
    """ISSUE-14 satellite: the C-API create/run/fetch contract drives real
    batched decode — the session output is bit-identical to
    gpt_decode.generate, and clones share one engine."""
    from paddle_tpu.inference import capi_bridge
    from paddle_tpu.serving.session import export_decode_model
    cfg, params = tiny_gpt
    d = str(tmp_path / "decode_model")
    export_decode_model(d, cfg, params, max_new_tokens=5, max_slots=4,
                        max_len=32)
    sess = capi_bridge.create(d)
    assert capi_bridge.io_names(sess) == (["tokens"], ["generated"])
    prompt = np.random.RandomState(7).randint(
        0, cfg.vocab_size, (2, 8)).astype(np.int64)
    outs = capi_bridge.run_raw(
        sess, [("tokens", "int64", prompt.shape, prompt.tobytes())])
    name, dt, shape, buf = outs[0]
    gen = np.frombuffer(buf, np.int64).reshape(shape)
    want = np.asarray(gpt_decode.generate(params, cfg, prompt, 5))
    np.testing.assert_array_equal(gen, want)
    clone = sess.clone()
    assert clone._engine is sess._engine
    outs2 = capi_bridge.run_raw(
        clone, [("tokens", "int64", prompt.shape, prompt.tobytes())])
    np.testing.assert_array_equal(
        np.frombuffer(outs2[0][3], np.int64).reshape(outs2[0][2]), want)
    sess.stop()


def test_capi_predictor_session_unchanged(tmp_path):
    """The classic feed-forward C-API path (the pthread test's contract)
    still routes to the Predictor and matches it numerically."""
    from paddle_tpu.fluid import layers
    from paddle_tpu.inference import Config, Predictor, capi_bridge
    reset_programs(seed=0)
    x = layers.data(name="x", shape=[4], dtype="float32")
    p = layers.fc(layers.fc(x, 8, act="relu"), 3)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    d = str(tmp_path / "model")
    fluid.io.save_inference_model(d, ["x"], [p], exe)
    sess = capi_bridge.create(d)
    xv = np.random.RandomState(0).randn(5, 4).astype(np.float32)
    outs = capi_bridge.run_raw(sess, [("x", "float32", xv.shape,
                                       xv.tobytes())])
    got = np.frombuffer(outs[0][3], np.float32).reshape(outs[0][2])
    py = Predictor(Config(d))
    py.get_input_handle("x").copy_from_cpu(xv)
    np.testing.assert_allclose(got, np.asarray(py.run()[0]), rtol=1e-5)


def test_bf16_and_int8_weight_arms(tiny_gpt):
    """Serving dtype arms boot, decode validly, and the int8 dequant path
    reconstructs weights within the abs-max quantization bound."""
    import jax.numpy as jnp
    from paddle_tpu.serving.weights import dequant_params, quantize_params
    cfg, params = tiny_gpt
    payloads, scales = quantize_params(params)
    assert payloads["wte"].dtype == jnp.int8
    assert "final_ln_scale" not in scales          # LN excluded
    deq = dequant_params(payloads, scales)
    err = np.abs(np.asarray(deq["wte"], np.float32)
                 - np.asarray(params["wte"], np.float32)).max()
    assert err <= float(scales["wte"]) / 127.0 + 1e-6
    rng = np.random.RandomState(4)
    prompt = rng.randint(0, cfg.vocab_size, (6,))
    for dtype in ("bfloat16", "int8"):
        eng = _engine(cfg, params, max_slots=2, num_blocks=16, dtype=dtype)
        try:
            c = eng.generate([Request(prompt=prompt, max_new_tokens=4)],
                             timeout=240)[0]
        finally:
            eng.stop()
        assert c.ok and len(c.tokens) == 4
        assert all(0 <= t < cfg.vocab_size for t in c.tokens)


def test_bench_serving_rows(tiny_gpt, monkeypatch):
    """The bench-table acceptance shape: rows exist with tokens/s + p50/
    p99 TTFT across >= 3 concurrency levels, for BOTH decode-kernel A/B
    arms with their census stamps (tiny geometry here; hardware rounds
    run the GPT-2-small geometry via bench.py main)."""
    import bench
    # a shape test on a stand-in device: the roofline fields need peaks,
    # and bench.py has none for a CPU on purpose
    monkeypatch.setitem(bench.DEVICE_PEAKS, "cpu",
                        {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11})
    rows = bench.bench_serving(streams_levels=(1, 2, 3),
                               dtypes=("float32",),
                               prompt_len=8, new_tokens=4, model="tiny")
    assert len(rows) == 6       # 3 stream levels x kernel off/on
    by_arm = {k: [r for r in rows if r["pallas_decode"] is k]
              for k in (False, True)}
    for arm, arm_rows in by_arm.items():
        assert [r["streams"] for r in arm_rows] == [1, 2, 3]
        for r in arm_rows:
            assert r["metric"] == "serving_decode_tokens_per_sec"
            assert r["value"] > 0
            assert r["ttft_p50_ms"] is not None
            assert r["ttft_p99_ms"] is not None
            if arm:
                assert r["dense_gathers"] == 0
            else:
                assert r["dense_gathers"] > 0
                assert r["per_token_kv_copies"] == 0
