"""Benchmarks on one real TPU chip; prints ONE JSON line.

Primary metric: BERT-base pretrain train-step throughput (BASELINE config 3
geometry, bf16 AMP). Extras: ResNet-50 static-graph images/sec (config 2)
and Wide&Deep CTR with the native sparse PS (config 5). The reference
publishes no numbers (BASELINE.md). An `mfu` field reports model-FLOPs
utilization = tokens/s * 6 * params / peak_flops, with the peak taken from
DEVICE_PEAKS for the `device_kind` JAX reports.

The run needs a TPU: without one, or on a device kind DEVICE_PEAKS does not
list, it exits non-zero before measuring anything. A row that raises is
recorded under `error` and the process exits non-zero after the JSON line.

Perf notes: feeds are device_put once and stay resident; fetches use
return_numpy=False so steps dispatch asynchronously and only the final
fetch blocks — the executor pipeline stays full.
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

import numpy as np

# Published per-chip peaks, keyed by jax.devices()[0].device_kind. A kind
# that is not listed is an error, never a default: an MFU against another
# chip's peak is a wrong number that looks right.
DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def device_peaks(device_kind: str) -> dict:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise RuntimeError(
            f"no peak figures for device_kind {device_kind!r}; known kinds: "
            f"{sorted(DEVICE_PEAKS)}. Add the published peaks to "
            "bench.DEVICE_PEAKS before benchmarking on this device.") from None


def _peaks() -> dict:
    import jax
    return device_peaks(jax.devices()[0].device_kind)


def _peak_flops():
    """bf16 peak FLOP/s of the device under test, for MFU math."""
    return _peaks()["bf16_flops"]


def _peak_hbm_bw():
    """HBM bandwidth peak in bytes/s: the roofline denominator for
    bandwidth-bound rows — decode reads every cache/weight byte per token,
    the fused optimizer update reads each bucket once."""
    return _peaks()["hbm_bytes_per_s"]


def _roofline(cost: dict, step_time_s) -> dict:
    """Per-kernel roofline evidence (docs/perf_notes.md 'Pallas kernels'):
    XLA's own cost-analysis flops/bytes denominators over the measured
    step time, as fractions of the chip peaks. Fields the backend didn't
    report are absent, never fabricated."""
    out = {}
    if not cost or not step_time_s or step_time_s <= 0:
        return out
    if cost.get("device_flops"):
        out["pct_of_peak_flops"] = round(
            cost["device_flops"] / step_time_s / _peak_flops(), 4)
    if cost.get("device_bytes_accessed"):
        out["pct_of_peak_hbm_bw"] = round(
            cost["device_bytes_accessed"] / step_time_s / _peak_hbm_bw(), 4)
    return out


def _fresh_programs():
    from paddle_tpu.testing import reset_programs
    reset_programs(seed=0)


def _device_feed(feed):
    import jax
    return {k: jax.device_put(v) for k, v in feed.items()}


def _layer_scan_enabled():
    """PADDLE_TPU_LAYER_SCAN=1: run the transformer benches with the
    rolled-layer step program (parallel/transforms.apply_layer_scan)."""
    return os.environ.get("PADDLE_TPU_LAYER_SCAN", "0") == "1"


def _zero_stage():
    """PADDLE_TPU_ZERO=1|2|3: the ZeRO A/B arm — 1 shards optimizer state,
    2 keeps gradient shards resident, 3 shards parameter storage with
    on-demand gathers (parallel/zero.py; main() sets FLAGS_zero_stage so
    every fleet build in the process picks it up). 0 = replicated arm."""
    try:
        return max(0, min(3, int(os.environ.get("PADDLE_TPU_ZERO", "0"))))
    except ValueError:
        return 0


def _zero_enabled():
    return _zero_stage() > 0


# structural optimizer-state accounting of the LAST bench_bert build
# (per-device bytes from the program metadata + the compiled step's
# memory_analysis — no wall clock involved; reported as an extras row)
_OPT_STATE_REPORT = None


def _stash_opt_state_report(prog, exe, feed, loss):
    global _OPT_STATE_REPORT
    try:
        import jax
        from paddle_tpu.parallel.zero import optimizer_state_bytes
        dist = getattr(prog, "_dist_config", None)
        dp = int(dist.resolve_mesh().shape.get("dp", 1)) if dist else 1
        rep = optimizer_state_bytes(prog, dp=dp)
        # shares bench_bert's compile cache: lower+memory_analysis only
        ma = exe.compiled_memory_analysis(feed, [loss])
        rep["compiled_argument_bytes_per_device"] = \
            int(ma.argument_size_in_bytes)
        _OPT_STATE_REPORT = rep
    except Exception as e:  # structural extra, never a bench failure
        print(f"opt-state report failed: {e!r}", file=sys.stderr)


def _log(msg):
    print(f"[bench +{time.perf_counter() - _T0:.1f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.perf_counter()


def _timed_steps(exe, feed, fetch, steps):
    """One device-side k-step scan per measurement (Executor.run_steps):
    dispatch cost is paid once per k steps, so the recorded number reflects
    device throughput, not host round-trips. The warmup call runs the SAME
    k so the timed call reuses the compiled loop."""
    import jax
    _log("compiling + warmup...")
    out, = exe.run_steps(steps, feed=feed, fetch_list=[fetch],
                         return_numpy=False)
    jax.block_until_ready(out)
    _log(f"warm; timing {steps} steps (one dispatch)")
    t0 = time.perf_counter()
    out, = exe.run_steps(steps, feed=feed, fetch_list=[fetch],
                         return_numpy=False)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    return dt, float(np.asarray(out).reshape(-1)[-1])


def bench_bert(batch, seq_len, steps, masked=False, large=False,
               recompute=False):
    """masked=True runs the padded-batch path: a per-example key-padding
    mask feeds the flash kernels' in-kernel additive-mask operand, so the
    recorded number certifies the real-data BERT path, not just synthetic
    unpadded batches. large=True benches the 24L/1024H/16-head geometry
    (BASELINE metric 'BERT-large tokens/sec/chip', config 4 ERNIE-large);
    recompute=True wraps each encoder layer in jax.remat so bigger batches
    fit HBM at ~4/3 the model FLOPs."""
    import paddle_tpu as paddle
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import bert
    from paddle_tpu.distributed import fleet

    _log(f"bert: building program (batch={batch}, seq={seq_len}, "
         f"masked={masked}, large={large}, remat={recompute})")
    _fresh_programs()
    cfg = bert.BertConfig.large() if large else bert.BertConfig()
    cfg.seq_len = seq_len
    if seq_len > cfg.max_position:
        cfg.max_position = seq_len   # long-context configs (seq 1024)
    ids, labels, loss = bert.build_pretrain_program(
        cfg, use_input_mask=masked)
    gb = fluid.default_main_program().global_block()
    n_params = sum(
        int(np.prod(v.shape)) for v in gb.vars.values()
        if v.persistable and v.shape and all(d > 0 for d in v.shape))
    fleet.init(is_collective=True)
    strategy = fleet.DistributedStrategy()
    strategy.amp = True              # bf16 matmuls on the MXU
    # PADDLE_TPU_LAYER_SCAN=1 rolls the 12/24 isomorphic encoder layers
    # into ONE lax.scan over [L]-stacked weights (~L x smaller step HLO,
    # ~L x faster trace+compile) — the A/B toggle for the primary metric
    strategy.layer_scan = _layer_scan_enabled()
    # PADDLE_TPU_ZERO=1|2|3: the ZeRO sharding arm (the record stamps
    # zero_stage so numbers never read as drift)
    strategy.sharding_stage = _zero_stage()
    if recompute:
        strategy.recompute = True
        strategy.recompute_configs = {
            "checkpoints": loss._layer_checkpoints}
    opt = fleet.distributed_optimizer(
        paddle.optimizer.Adam(learning_rate=1e-4), strategy)
    opt.minimize(loss)

    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    np_feed = {
        "input_ids": rng.randint(0, cfg.vocab_size,
                                 (batch, seq_len)).astype(np.int64),
        "mlm_labels": rng.randint(0, cfg.vocab_size,
                                  (batch, seq_len, 1)).astype(np.int64),
    }
    if masked:
        # realistic padding: per-example lengths uniform in [S/2, S]
        lens = rng.randint(seq_len // 2, seq_len + 1, size=(batch, 1))
        np_feed["input_mask"] = (
            np.arange(seq_len)[None, :] < lens).astype(np.float32)
    feed = _device_feed(np_feed)
    dt, _ = _timed_steps(exe, feed, loss, steps)
    tokens_per_sec = batch * seq_len * steps / dt
    peak = _peak_flops()
    mfu = tokens_per_sec * 6.0 * n_params / peak
    _stash_opt_state_report(fluid.default_main_program(), exe, np_feed,
                            loss)
    try:
        # measured roofline row for the compiled train step (device
        # flops/bytes from XLA cost analysis over the per-step time)
        cost = exe.annotate_step_cost(feed=np_feed, fetch_list=[loss])
    except Exception:
        cost = {}
    return tokens_per_sec, mfu, _roofline(cost, dt / steps)


def bench_gpt(batch, seq_len, steps):
    """GPT-2-small causal LM train step (models/gpt.py, the causal-flash
    kernel configuration: causal=True + dropout at S>=512 — exactly the
    fused path the reference's multihead_matmul_op.cu exists for)."""
    import paddle_tpu as paddle
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import gpt
    from paddle_tpu.distributed import fleet

    _log(f"gpt: building program (batch={batch}, seq={seq_len})")
    _fresh_programs()
    cfg = gpt.GPTConfig()            # GPT-2 small geometry
    cfg.seq_len = seq_len
    if seq_len > cfg.max_position:
        cfg.max_position = seq_len
    tokens, loss = gpt.build_lm_program(cfg)
    gb = fluid.default_main_program().global_block()
    n_params = sum(
        int(np.prod(v.shape)) for v in gb.vars.values()
        if v.persistable and v.shape and all(d > 0 for d in v.shape))
    fleet.init(is_collective=True)
    strategy = fleet.DistributedStrategy()
    strategy.amp = True
    strategy.layer_scan = _layer_scan_enabled()
    opt = fleet.distributed_optimizer(
        paddle.optimizer.Adam(learning_rate=1e-4), strategy)
    opt.minimize(loss)

    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    feed = _device_feed({
        "tokens": rng.randint(0, cfg.vocab_size,
                              (batch, seq_len)).astype(np.int64)})
    dt, _ = _timed_steps(exe, feed, loss, steps)
    tokens_per_sec = batch * seq_len * steps / dt
    peak = _peak_flops()
    mfu = tokens_per_sec * 6.0 * n_params / peak
    return tokens_per_sec, mfu


def bench_gpt_decode(batch, prompt_len, new_tokens, iters):
    """KV-cache autoregressive generation throughput (models/gpt_decode.py):
    prefill + the whole decode scan compile to ONE XLA program, so the
    recorded number is device decode rate, not host round-trips.
    The reference has no in-tree serving loop to compare against (its
    inference story is the feed-forward AnalysisPredictor) — this row
    certifies the TPU-native capability the reference lacks."""
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import gpt
    from paddle_tpu.models.gpt_decode import generate, params_from_scope

    _log(f"gpt-decode: batch={batch}, prompt={prompt_len}, "
         f"new={new_tokens}")
    _fresh_programs()
    cfg = gpt.GPTConfig()
    cfg.seq_len = prompt_len
    if prompt_len + new_tokens > cfg.max_position:
        cfg.max_position = prompt_len + new_tokens
    gpt.build_lm_program(cfg)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    # bf16 weights: decode reads every weight per generated token, so
    # halving the bytes ~doubles the bandwidth-bound serving rate
    params = {k: jax.device_put(v)
              for k, v in params_from_scope(
                  cfg, dtype=os.environ.get("BENCH_DECODE_DTYPE",
                                            "bfloat16")).items()}
    rng = np.random.RandomState(0)
    prompt = np.asarray(rng.randint(0, cfg.vocab_size,
                                    (batch, prompt_len)), np.int32)
    out = generate(params, cfg, prompt, max_new_tokens=new_tokens)
    jax.block_until_ready(out)                     # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = generate(params, cfg, prompt, max_new_tokens=new_tokens,
                       seed=1)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    return batch * new_tokens * iters / dt


def bench_serving(streams_levels=(1, 8, 32), dtypes=("bfloat16",),
                  prompt_len=64, new_tokens=64, model="small"):
    """Decode-SERVICE throughput (paddle_tpu/serving/): continuous
    batching + paged KV cache under concurrent request streams. For each
    (dtype, streams) arm: submit `streams` concurrent requests through
    one engine and record aggregate tokens/s plus the p50/p99
    time-to-first-token from the serving histogram — the three-level
    concurrency sweep is the scaling story (1 stream = latency floor,
    max_slots streams = saturated slot array). Weight arms: bf16 halves
    the per-token weight bytes vs f32; int8 (abs-max, ops/int8_ops.py
    scheme) halves them again. Returns a list of bench rows."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import gpt
    from paddle_tpu.models.gpt_decode import params_from_scope
    from paddle_tpu.observability import metrics as _obs_metrics
    from paddle_tpu.serving import DecodeEngine, Request
    from paddle_tpu.serving import audit as serving_audit

    _log(f"serving: model={model}, prompt={prompt_len}, new={new_tokens}, "
         f"streams={streams_levels}, dtypes={dtypes}")
    _fresh_programs()
    cfg = gpt.GPTConfig.tiny() if model == "tiny" else gpt.GPTConfig()
    cfg.seq_len = prompt_len
    cfg.max_position = max(cfg.max_position, prompt_len + new_tokens)
    gpt.build_lm_program(cfg)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    params = params_from_scope(cfg)

    max_slots = max(streams_levels)
    block_size = int(os.environ.get("BENCH_SERVING_BLOCK", "16"))
    max_len = prompt_len + new_tokens
    if max_len % block_size:
        max_len += block_size - max_len % block_size
    blocks_per_slot = max_len // block_size
    rng = np.random.RandomState(0)
    rows = []
    # the fused-kernel A/B arm: PADDLE_TPU_PALLAS_DECODE pins one arm
    # when set, else every dtype runs the fallback AND the Pallas kernel
    # so the table carries the comparison directly
    if "PADDLE_TPU_PALLAS_DECODE" in os.environ:
        kernel_arms = (os.environ["PADDLE_TPU_PALLAS_DECODE"] == "1",)
    else:
        kernel_arms = (False, True)
    for dtype in dtypes:
        for use_kernel in kernel_arms:
            engine = DecodeEngine(
                params, cfg, max_slots=max_slots, block_size=block_size,
                num_blocks=max_slots * blocks_per_slot + 1, max_len=max_len,
                window=int(os.environ.get("BENCH_SERVING_WINDOW", "16")),
                dtype=dtype, decode_kernel=use_kernel)
            # the zero-copy claim ships WITH the number (fallback arm: a
            # window program that silently regressed into copying the
            # cache would not be a serving benchmark at all) and so does
            # the kernel proof (kernel arm: the dense cache-view census
            # must be empty — serving/audit.py)
            gather = serving_audit.decode_gather_census(engine)
            census = (None if use_kernel
                      else serving_audit.decode_copy_census(engine))
            # warm: compile prefill + window before any timed arm
            engine.generate([Request(
                prompt=rng.randint(0, cfg.vocab_size, (prompt_len,)),
                max_new_tokens=2)], timeout=600)
            try:
                ca = serving_audit.window_cost(engine)
            except Exception:
                ca = {}
            for streams in streams_levels:
                _obs_metrics.reset("serving.ttft_ms")
                _obs_metrics.reset("serving.tpot_ms")
                _obs_metrics.reset("serving.window_ms")
                reqs = [Request(
                    prompt=rng.randint(0, cfg.vocab_size, (prompt_len,)),
                    max_new_tokens=new_tokens, seed=i)
                    for i in range(streams)]
                t0 = time.perf_counter()
                comps = engine.generate(reqs, timeout=1200)
                dt = time.perf_counter() - t0
                n_tok = sum(len(c.tokens) for c in comps)
                bad = sum(not c.ok for c in comps)
                snap = _obs_metrics.snapshot()
                ttft = snap.get("serving.ttft_ms", {})
                tpot = snap.get("serving.tpot_ms", {})
                wms = snap.get("serving.window_ms", {})
                row = {
                    "metric": "serving_decode_tokens_per_sec",
                    "value": round(n_tok / dt, 1), "unit": "tokens/s",
                    "streams": streams, "dtype": dtype,
                    "prompt_len": prompt_len, "new_tokens": new_tokens,
                    "pallas_decode": use_kernel,
                    "dense_gathers": gather["dense_gathers"],
                    "ttft_p50_ms": (round(ttft["p50"], 2)
                                    if ttft.get("p50") is not None
                                    else None),
                    "ttft_p99_ms": (round(ttft["p99"], 2)
                                    if ttft.get("p99") is not None
                                    else None),
                    "tpot_p50_ms": (round(tpot["p50"], 2)
                                    if tpot.get("p50") is not None
                                    else None),
                    # every serving row carries the prefix-cache state +
                    # hit rate (None when the cache is off) so the table
                    # reads unambiguously next to the A/B rows below
                    "prefix_cache": bool(engine.config.prefix_cache),
                    "prefix_hit_rate": engine.stats().get(
                        "prefix_cache_hit_rate"),
                    # every serving row states its speculation arm too
                    # (the A/B rows live in bench_serving_spec)
                    "spec_decode": engine.config.spec is not None,
                    "spec_accept_rate": engine.stats().get(
                        "spec_accept_rate"),
                }
                if census is not None:
                    row["per_token_kv_copies"] = \
                        census["per_token_kv_copies"]
                # per-window roofline: decode is HBM-bound, so the
                # %-of-peak-BW row is the one that moves with the kernel
                if wms.get("p50"):
                    row.update(_roofline(ca, wms["p50"] / 1e3))
                if bad:
                    row["failed_requests"] = bad
                rows.append(row)
                _log(f"serving[{dtype} kernel={int(use_kernel)}] "
                     f"streams={streams}: {row['value']} tok/s, "
                     f"TTFT p50={row['ttft_p50_ms']} "
                     f"p99={row['ttft_p99_ms']} ms")
            engine.stop()
    return rows


def bench_serving_prefix(streams=16, dtype="bfloat16", prompt_len=64,
                         new_tokens=32, model="small", shared_frac=0.75):
    """Shared-prefix traffic A/B (the radix prefix cache's headline):
    `shared_frac` of the streams open with ONE long common system prompt
    (~70% of prompt_len, ending mid-block so the copy-on-write tail path
    is on the measured path); the identical traffic runs twice through
    identically-sized engines — prefix cache OFF, then ON — and the two
    rows carry tokens/s, TTFT p50/p99, the cache hit rate and prefill
    tokens saved. Bit-parity of the two arms is asserted inline: a cache
    that changed a single token would not be a benchmark but a bug."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import gpt
    from paddle_tpu.models.gpt_decode import params_from_scope
    from paddle_tpu.observability import metrics as _obs_metrics
    from paddle_tpu.serving import DecodeEngine, Request

    _log(f"serving-prefix: model={model}, streams={streams} "
         f"({shared_frac:.0%} shared), prompt={prompt_len}, "
         f"new={new_tokens}")
    _fresh_programs()
    cfg = gpt.GPTConfig.tiny() if model == "tiny" else gpt.GPTConfig()
    cfg.seq_len = prompt_len
    cfg.max_position = max(cfg.max_position, prompt_len + new_tokens)
    gpt.build_lm_program(cfg)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    params = params_from_scope(cfg)

    block_size = int(os.environ.get("BENCH_SERVING_BLOCK", "16"))
    max_len = prompt_len + new_tokens
    if max_len % block_size:
        max_len += block_size - max_len % block_size
    blocks_per_slot = max_len // block_size
    max_slots = min(streams, 32)

    rng = np.random.RandomState(7)
    # long system prompt ending MID-BLOCK (exercises the CoW tail)
    sys_len = (prompt_len * 7) // 10
    if sys_len % block_size == 0:
        sys_len -= 3
    sysp = rng.randint(0, cfg.vocab_size, (sys_len,))
    n_shared = max(1, int(round(streams * shared_frac)))
    reqs = []
    for i in range(streams):
        if i < n_shared:
            tail = rng.randint(0, cfg.vocab_size, (prompt_len - sys_len,))
            prompt = np.concatenate([sysp, tail])
        else:
            prompt = rng.randint(0, cfg.vocab_size, (prompt_len,))
        reqs.append(Request(prompt=prompt, max_new_tokens=new_tokens,
                            seed=i, uid=f"px-{i}"))
    # warm pair: px-warm0 publishes the system prompt's chain; px-warm1
    # (same shape as the shared streams: sysp + a tail NOT reused in the
    # timed wave) then hits it, compiling the suffix program at the
    # exact (p_pad, sbucket) key the timed shared streams will use
    # px-warm2 is a random full-length prompt: on the ON arm, px-warm1
    # hits the cache, so without it the COLD full-prompt bucket would
    # first compile inside the timed wave (the non-shared streams)
    warm = [Request(prompt=sysp, max_new_tokens=2, seed=999983,
                    uid="px-warm0"),
            Request(prompt=np.concatenate(
                        [sysp, rng.randint(0, cfg.vocab_size,
                                           (prompt_len - sys_len,))]),
                    max_new_tokens=2, seed=999979, uid="px-warm1"),
            Request(prompt=rng.randint(0, cfg.vocab_size, (prompt_len,)),
                    max_new_tokens=2, seed=999961, uid="px-warm2")]

    rows = []
    tokens_by_arm = {}
    off_p50 = None
    for cache_on in (False, True):
        engine = DecodeEngine(
            params, cfg, max_slots=max_slots, block_size=block_size,
            num_blocks=max_slots * blocks_per_slot + 16 + 1,
            max_len=max_len,
            window=int(os.environ.get("BENCH_SERVING_WINDOW", "16")),
            dtype=dtype, prefix_cache=cache_on)
        try:
            # warm compiles prefill/window (+ the suffix program on the
            # ON arm) and publishes the system prompt's chain, so the
            # timed wave measures steady-state cache behavior. The two
            # warm calls are SEQUENTIAL on purpose: px-warm1 can only
            # hit (and so compile the suffix program) after px-warm0 has
            # retired and published its chain
            engine.generate([warm[0]], timeout=600)
            engine.generate([warm[1]], timeout=600)
            engine.generate([warm[2]], timeout=600)
            st0 = engine.stats()
            _obs_metrics.reset("serving.ttft_ms")
            t0 = time.perf_counter()
            comps = engine.generate(reqs, timeout=1200)
            dt = time.perf_counter() - t0
            st1 = engine.stats()
        finally:
            engine.stop()
        bad = [c for c in comps if not c.ok]
        if bad:
            raise RuntimeError(
                f"prefix bench arm cache={cache_on}: {len(bad)} failed "
                f"request(s): {[(c.uid, c.state) for c in bad[:4]]}")
        tokens_by_arm[cache_on] = {c.uid: c.tokens for c in comps}
        hits = st1.get("prefix_cache_hits", 0) - st0.get(
            "prefix_cache_hits", 0)
        misses = st1.get("prefix_cache_misses", 0) - st0.get(
            "prefix_cache_misses", 0)
        saved = st1.get("prefill_tokens_saved", 0) - st0.get(
            "prefill_tokens_saved", 0)
        ttft = _obs_metrics.snapshot().get("serving.ttft_ms", {})
        n_tok = sum(len(c.tokens) for c in comps)
        row = {
            "metric": "serving_prefix_shared_tokens_per_sec",
            "value": round(n_tok / dt, 1), "unit": "tokens/s",
            "streams": streams, "shared_streams": n_shared,
            "dtype": dtype, "prompt_len": prompt_len,
            "sys_prompt_len": sys_len, "new_tokens": new_tokens,
            "prefix_cache": cache_on,
            "prefix_hit_rate": (round(hits / (hits + misses), 3)
                                if hits + misses else None),
            "prefill_tokens_saved": saved,
            "ttft_p50_ms": (round(ttft["p50"], 2)
                            if ttft.get("p50") is not None else None),
            "ttft_p99_ms": (round(ttft["p99"], 2)
                            if ttft.get("p99") is not None else None),
            "spec_decode": st1.get("spec_decode", False),
            "spec_accept_rate": st1.get("spec_accept_rate"),
        }
        if cache_on:
            if row["ttft_p50_ms"] and off_p50:
                row["ttft_p50_off_ms"] = off_p50
                row["ttft_p50_speedup"] = round(
                    off_p50 / row["ttft_p50_ms"], 2)
        else:
            off_p50 = row["ttft_p50_ms"]
        rows.append(row)
        _log(f"serving-prefix[cache={'on' if cache_on else 'off'}]: "
             f"{row['value']} tok/s, TTFT p50={row['ttft_p50_ms']} "
             f"p99={row['ttft_p99_ms']} ms, hit_rate="
             f"{row['prefix_hit_rate']}, saved={saved}")
    # the determinism contract IS the product: cache on == cache off
    diverged = [u for u in tokens_by_arm[False]
                if tokens_by_arm[False][u] != tokens_by_arm[True][u]]
    if diverged:
        raise RuntimeError(
            f"prefix cache broke bit-parity on {len(diverged)} "
            f"request(s): {diverged[:4]}")
    return rows


def bench_serving_degraded(streams=16, dtype="bfloat16", prompt_len=64,
                           new_tokens=64, model="small", replicas=2):
    """Degraded-capacity serving (ISSUE-15): N replicas behind the
    resilient frontend, ONE killed mid-run — the row records the
    throughput + tail-TTFT the service sustains while failover re-routes
    the victim's in-flight requests and the survivors absorb the load.
    The resilience contract rides the number: every request must still
    complete (failover is bit-lossless), so a row with failed_requests
    is a regression, not a slow day."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import gpt
    from paddle_tpu.models.gpt_decode import params_from_scope
    from paddle_tpu.observability import metrics as _obs_metrics
    from paddle_tpu.serving import (Request, ServingFrontend,
                                    replicated_engines)

    _log(f"serving-degraded: model={model}, replicas={replicas} (1 killed "
         f"mid-run), streams={streams}, dtype={dtype}")
    _fresh_programs()
    cfg = gpt.GPTConfig.tiny() if model == "tiny" else gpt.GPTConfig()
    cfg.seq_len = prompt_len
    cfg.max_position = max(cfg.max_position, prompt_len + new_tokens)
    gpt.build_lm_program(cfg)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    params = params_from_scope(cfg)

    block_size = int(os.environ.get("BENCH_SERVING_BLOCK", "16"))
    max_len = prompt_len + new_tokens
    if max_len % block_size:
        max_len += block_size - max_len % block_size
    per_slot = max_len // block_size
    slots = max(streams // replicas, 1)
    engines = replicated_engines(
        replicas, params, cfg, max_slots=slots, block_size=block_size,
        num_blocks=slots * per_slot + 1, max_len=max_len,
        window=int(os.environ.get("BENCH_SERVING_WINDOW", "16")),
        dtype=dtype)
    # resurrect=False: the row measures capacity WITHOUT the dead replica
    # for the whole run — a mid-measurement rejoin would blur the arm
    fe = ServingFrontend(engines, resurrect=False)
    rng = np.random.RandomState(0)
    # warm every replica's prefill+window compile before the timed run
    for eng in engines:
        eng.generate([Request(
            prompt=rng.randint(0, cfg.vocab_size, (prompt_len,)),
            max_new_tokens=2)], timeout=600)
    for name in ("serving.ttft_ms", "serving.failovers"):
        _obs_metrics.reset(name)
    reqs = [Request(prompt=rng.randint(0, cfg.vocab_size, (prompt_len,)),
                    max_new_tokens=new_tokens, seed=i)
            for i in range(streams)]
    t0 = time.perf_counter()
    handles = [fe.submit(r) for r in reqs]
    victim = engines[-1]
    # kill once the victim is mid-decode; bail out early if the whole
    # stream finishes first (tiny runs) — otherwise an idle victim would
    # hold the timed region open for the full poll deadline and record a
    # garbage near-zero throughput row
    kill_deadline = time.monotonic() + 30
    while (victim.stats()["active_slots"] == 0
           and not all(h.done() for h in handles)
           and time.monotonic() < kill_deadline):
        time.sleep(0.005)
    victim.kill("bench: injected replica kill")
    comps = [h.result(timeout=1200, raise_on_error=False)
             for h in handles]
    dt = time.perf_counter() - t0
    fe.stop()
    n_tok = sum(len(c.tokens) for c in comps)
    bad = sum(not c.ok for c in comps)
    snap = _obs_metrics.snapshot()
    ttft = snap.get("serving.ttft_ms", {})
    row = {
        "metric": "serving_degraded_tokens_per_sec",
        "value": round(n_tok / dt, 1), "unit": "tokens/s",
        "serving_degraded_arm": True,
        "replicas": replicas, "replicas_killed": 1,
        "streams": streams, "dtype": dtype,
        "prompt_len": prompt_len, "new_tokens": new_tokens,
        "ttft_p99_ms": (round(ttft["p99"], 2)
                        if ttft.get("p99") is not None else None),
        "failovers": int(_obs_metrics.get("serving.failovers")),
        "spec_decode": engines[0].config.spec is not None,
        "spec_accept_rate": None,
    }
    if bad:
        row["failed_requests"] = bad
    _log(f"serving-degraded[{dtype}] {replicas - 1}/{replicas} replicas: "
         f"{row['value']} tok/s, TTFT p99={row['ttft_p99_ms']} ms, "
         f"{row['failovers']} failover(s), {bad} failed")
    return row


def bench_serving_spec(streams_levels=(1, 8, 32), dtype="bfloat16",
                       prompt_len=64, new_tokens=64, model="small"):
    """Speculative-decoding A/B (ISSUE-19 headline): the same mixed
    greedy + seeded top-k traffic runs through a spec-OFF engine and a
    spec-ON twin (int8 weight arm of the SAME checkpoint drafting
    FLAGS_serving_spec_tokens per round, one batched verify window over
    the paged cache) at each concurrency level. Every spec-on row
    records the acceptance rate measured over that level's run and its
    tokens/s speedup vs the spec-off twin. Bit-parity is asserted
    inline per level: a spec-on row that disagrees with spec-off on a
    single token is REFUSED (RuntimeError), never published — the
    construction contract rides the number."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import gpt
    from paddle_tpu.models.gpt_decode import params_from_scope
    from paddle_tpu.observability import metrics as _obs_metrics
    from paddle_tpu.serving import DecodeEngine, Request

    _log(f"serving-spec: model={model}, prompt={prompt_len}, "
         f"new={new_tokens}, streams={streams_levels}, dtype={dtype}")
    _fresh_programs()
    cfg = gpt.GPTConfig.tiny() if model == "tiny" else gpt.GPTConfig()
    cfg.seq_len = prompt_len
    cfg.max_position = max(cfg.max_position, prompt_len + new_tokens)
    gpt.build_lm_program(cfg)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    params = params_from_scope(cfg)

    max_slots = max(streams_levels)
    block_size = int(os.environ.get("BENCH_SERVING_BLOCK", "16"))
    max_len = prompt_len + new_tokens
    if max_len % block_size:
        max_len += block_size - max_len % block_size
    blocks_per_slot = max_len // block_size
    rng = np.random.RandomState(11)
    # one request set per level, shared by BOTH arms (the parity check
    # compares token streams uid-for-uid); odd streams sample seeded
    # top-k so acceptance is measured on both sampling arms
    level_reqs = {
        s: [Request(prompt=rng.randint(0, cfg.vocab_size, (prompt_len,)),
                    max_new_tokens=new_tokens,
                    temperature=0.8 if i % 2 else 0.0,
                    top_k=16 if i % 2 else 0,
                    seed=i, uid=f"spec-{s}-{i}")
            for i in range(s)]
        for s in streams_levels}

    rows = []
    off_arm = {}
    for spec_on in (False, True):
        engine = DecodeEngine(
            params, cfg, max_slots=max_slots, block_size=block_size,
            num_blocks=max_slots * blocks_per_slot + 1, max_len=max_len,
            window=int(os.environ.get("BENCH_SERVING_WINDOW", "16")),
            dtype=dtype, spec=spec_on)
        try:
            # warm: compiles prefill + window, and on the spec arm the
            # draft window + verify program, before any timed level
            engine.generate([Request(
                prompt=rng.randint(0, cfg.vocab_size, (prompt_len,)),
                max_new_tokens=4, seed=999999)], timeout=600)
            for streams in streams_levels:
                reqs = level_reqs[streams]
                st0 = engine.stats()
                _obs_metrics.reset("serving.ttft_ms")
                t0 = time.perf_counter()
                comps = engine.generate(reqs, timeout=1200)
                dt = time.perf_counter() - t0
                st1 = engine.stats()
                bad = [c for c in comps if not c.ok]
                if bad:
                    raise RuntimeError(
                        f"spec bench arm spec={spec_on} "
                        f"streams={streams}: {len(bad)} failed "
                        f"request(s): {[(c.uid, c.state) for c in bad[:4]]}")
                toks = {c.uid: c.tokens for c in comps}
                n_tok = sum(len(t) for t in toks.values())
                tps = round(n_tok / dt, 1)
                ttft = _obs_metrics.snapshot().get("serving.ttft_ms", {})
                row = {
                    "metric": "serving_spec_tokens_per_sec",
                    "value": tps, "unit": "tokens/s",
                    "streams": streams, "dtype": dtype,
                    "prompt_len": prompt_len, "new_tokens": new_tokens,
                    "spec_decode": spec_on,
                    "spec_tokens": (engine.config.spec.tokens
                                    if spec_on else None),
                    "ttft_p50_ms": (round(ttft["p50"], 2)
                                    if ttft.get("p50") is not None
                                    else None),
                }
                if spec_on:
                    prop = (st1.get("spec_proposed", 0)
                            - st0.get("spec_proposed", 0))
                    acc = (st1.get("spec_accepted", 0)
                           - st0.get("spec_accepted", 0))
                    row["spec_accept_rate"] = (round(acc / prop, 3)
                                               if prop else None)
                    base = off_arm[streams]
                    diverged = [u for u in base["tokens"]
                                if base["tokens"][u] != toks[u]]
                    if diverged:
                        raise RuntimeError(
                            f"speculative decoding broke bit-parity at "
                            f"streams={streams} on {len(diverged)} "
                            f"request(s): {diverged[:4]} — spec-on row "
                            "refused")
                    row["speedup_vs_off"] = (round(tps / base["tps"], 3)
                                             if base["tps"] else None)
                else:
                    row["spec_accept_rate"] = None
                    off_arm[streams] = {"tps": tps, "tokens": toks}
                rows.append(row)
                _log(f"serving-spec[spec={'on' if spec_on else 'off'}] "
                     f"streams={streams}: {tps} tok/s"
                     + (f", accept_rate={row['spec_accept_rate']}, "
                        f"speedup={row.get('speedup_vs_off')}x"
                        if spec_on else ""))
        finally:
            engine.stop()
    return rows


def bench_resnet50(batch, steps):
    import paddle_tpu as paddle
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models.resnet import build_resnet50_program
    from paddle_tpu.distributed import fleet

    _fresh_programs()
    img, label, loss = build_resnet50_program()
    fleet.init(is_collective=True)
    strategy = fleet.DistributedStrategy()
    strategy.amp = True
    opt = fleet.distributed_optimizer(
        paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9), strategy)
    opt.minimize(loss)

    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    feed = _device_feed({
        "image": rng.randn(batch, 3, 224, 224).astype(np.float32),
        "label": rng.randint(0, 1000, (batch, 1)).astype(np.int64),
    })
    dt, _ = _timed_steps(exe, feed, loss, steps)
    return batch * steps / dt


# ResNet-50 model FLOPs: 2 * 2.05G MACs forward per 224x224 image (the
# canonical 4.1 GFLOP figure, He et al. 2015 table 1), x3 for fwd+bwd
# (bwd does ~2x fwd work) — used for the images/s -> MFU conversion
RESNET50_TRAIN_FLOPS_PER_IMAGE = 3 * 4.1e9


def bench_wide_deep(batch, steps):
    """CTR train step with the sparse table on the native KV service
    (in-process loopback server — the PS path the reference benches with
    dist_fleet_ctr)."""
    import paddle_tpu as paddle
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.ps import (KVServer, SparseTableConfig,
                                           distributed_embedding)

    _fresh_programs()
    slots, emb_dim, vocab = 26, 16, 100001
    srv = KVServer([SparseTableConfig("ctr_emb", dim=emb_dim,
                                      init_scale=0.01)])
    port = srv.start(0)
    try:
        dense = layers.data(name="dense_input", shape=[13], dtype="float32")
        ids = layers.data(name="ids", shape=[slots], dtype="int64")
        label = layers.data(name="label", shape=[1], dtype="float32")
        emb = distributed_embedding(ids, "ctr_emb", dim=emb_dim, lr=0.01)
        feat = layers.concat(
            [layers.reshape(emb, [-1, slots * emb_dim]), dense], axis=1)
        x = layers.fc(feat, 400, act="relu")
        x = layers.fc(x, 400, act="relu")
        logit = layers.fc(x, 1)
        loss = layers.mean(
            layers.sigmoid_cross_entropy_with_logits(logit, label))

        fleet.init(role_maker=fleet.UserDefinedRoleMaker(
            server_endpoints=[f"127.0.0.1:{port}"]))
        opt = fleet.distributed_optimizer(
            paddle.optimizer.Adam(learning_rate=1e-3),
            fleet.DistributedStrategy())
        opt.minimize(loss)
        fleet.init_worker()

        exe = fluid.Executor()
        exe.run(fluid.default_startup_program())
        rng = np.random.RandomState(0)
        # k-step PS windows (run_steps + pre_multi/post_multi): one pull /
        # one summed push / ONE device dispatch per k batches — the
        # amortization that lifts the path off the per-dispatch floor
        # (docs/perf_notes.md roofline)
        k = int(os.environ.get("BENCH_CTR_WINDOW", "16"))
        feed = {
            "dense_input": rng.randn(k, batch, 13).astype(np.float32),
            "ids": rng.randint(0, vocab, (k, batch, slots)).astype(np.int64),
            "label": rng.randint(0, 2, (k, batch, 1)).astype(np.float32),
        }
        windows = max(steps // k, 2)
        exe.run_steps(k, feed=feed, fetch_list=[loss])   # compile + warm
        t0 = time.perf_counter()
        for _ in range(windows):
            exe.run_steps(k, feed=feed, fetch_list=[loss])
        dt = time.perf_counter() - t0
        return batch * k * windows / dt
    finally:
        srv.stop()


def bench_pipelined_loop(batch, seq_len, steps=20, log_every=5):
    """Host–device overlap A/B (ISSUE-4 acceptance geometry): the SAME
    per-step BERT train loop, logging loss every `log_every` steps, run
    twice —

    * sync arm: every run() drains its fetch to numpy (the seed behavior:
      a full device sync + D2H per step);
    * async arm: run(sync=False) returns lazy FetchHandles, only the
      logged steps materialize, and the next step's feeds are staged
      (Executor.stage) while the current one executes.

    Both arms share one compiled program and report the executor's own
    ledger: host_blocked_ms, fetch_sync_count, h2d_ms (paddle_tpu.monitor)
    plus wall-clock tokens/s. The async arm must record fetch_sync_count
    <= steps/log_every and lower host_blocked_ms — checked in
    tests/test_async_dispatch.py and scripts/ci.py's host-stall budget;
    recording it here makes the win a number in the round record, not a
    claim."""
    import paddle_tpu as paddle
    import paddle_tpu.fluid as fluid
    from paddle_tpu import monitor
    from paddle_tpu.models import bert
    from paddle_tpu.distributed import fleet

    _log(f"pipelined-loop A/B: batch={batch}, seq={seq_len}, "
         f"steps={steps}, log_every={log_every}")
    _fresh_programs()
    cfg = bert.BertConfig()
    cfg.seq_len = seq_len
    ids, labels, loss = bert.build_pretrain_program(cfg)
    fleet.init(is_collective=True)
    strategy = fleet.DistributedStrategy()
    strategy.amp = True
    strategy.layer_scan = _layer_scan_enabled()
    opt = fleet.distributed_optimizer(
        paddle.optimizer.Adam(learning_rate=1e-4), strategy)
    opt.minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    np_feed = {
        "input_ids": rng.randint(0, cfg.vocab_size,
                                 (batch, seq_len)).astype(np.int64),
        "mlm_labels": rng.randint(0, cfg.vocab_size,
                                  (batch, seq_len, 1)).astype(np.int64),
    }
    exe.run(feed=np_feed, fetch_list=[loss])       # compile + warm
    stat_names = ("executor.host_blocked_ms", "executor.fetch_sync_count",
                  "executor.h2d_ms")
    arms = {}
    for arm in ("sync", "async"):
        for s in stat_names:
            monitor.stat_reset(s)
        is_async = arm == "async"
        last = None
        t0 = time.perf_counter()
        for step in range(steps):
            out, = exe.run(feed=np_feed, fetch_list=[loss],
                           sync=not is_async)
            if is_async and step + 1 < steps:
                exe.stage(np_feed)   # next window's H2D rides this step
            if (step + 1) % log_every == 0:
                last = float(np.asarray(out).reshape(-1)[0])
        if last is None:           # loop shorter than one logging period
            last = float(np.asarray(out).reshape(-1)[0])
        dt = time.perf_counter() - t0
        arms[arm] = {
            "wall_s": round(dt, 3),
            "tokens_per_sec": round(batch * seq_len * steps / dt, 1),
            "host_blocked_ms":
                round(monitor.stat_get("executor.host_blocked_ms"), 1),
            "fetch_sync_count":
                int(monitor.stat_get("executor.fetch_sync_count")),
            "h2d_ms": round(monitor.stat_get("executor.h2d_ms"), 1),
            "last_loss": round(last, 6),
        }
        _log(f"pipelined {arm}: {arms[arm]}")
    arms["async_wins"] = (
        arms["async"]["host_blocked_ms"] < arms["sync"]["host_blocked_ms"]
        and arms["async"]["fetch_sync_count"] <= steps // log_every)
    return arms


def _env_int(name, default):
    return int(os.environ.get(name, str(default)))


def _serving_rows():
    """The serving table's row thunks: tokens/s + p50/p99 TTFT across >= 3
    concurrency levels per weight arm, then the prefix-cache, degraded
    and speculative A/B rows (each skippable by its BENCH_SERVING_* = 0)."""
    streams = tuple(int(x) for x in os.environ.get(
        "BENCH_SERVING_STREAMS", "1,8,32").split(","))
    dtypes = tuple(os.environ.get(
        "BENCH_SERVING_DTYPES", "bfloat16,int8").split(","))
    common = dict(prompt_len=_env_int("BENCH_SERVING_PROMPT", 64),
                  new_tokens=_env_int("BENCH_SERVING_NEW", 64),
                  model=os.environ.get("BENCH_SERVING_MODEL", "small"))
    rows = [("serving", lambda: bench_serving(
        streams_levels=streams, dtypes=dtypes, **common))]
    if os.environ.get("BENCH_SERVING_PREFIX", "1") != "0":
        rows.append(("serving-prefix", lambda: bench_serving_prefix(
            streams=_env_int("BENCH_SERVING_PREFIX_STREAMS", 16),
            dtype=dtypes[0], **common)))
    if os.environ.get("BENCH_SERVING_DEGRADED", "1") != "0":
        rows.append(("serving-degraded", lambda: bench_serving_degraded(
            streams=_env_int("BENCH_SERVING_DEGRADED_STREAMS", 16),
            dtype=dtypes[0],
            replicas=_env_int("BENCH_SERVING_REPLICAS", 2), **common)))
    if os.environ.get("BENCH_SERVING_SPEC", "1") != "0":
        rows.append(("serving-spec", lambda: bench_serving_spec(
            streams_levels=streams, dtype=dtypes[0], **common)))
    return rows


def _extra_rows(which, batch, seq_len, steps):
    """(name, thunk) for every extras row BENCH_WHICH asks for; a thunk
    returns one record or a list of records."""
    half = max(steps // 2, 5)

    def tps_row(metric, tps, mfu, **more):
        return {"metric": metric, "value": round(tps, 1),
                "unit": "tokens/s", "mfu": round(mfu, 4), **more}

    def masked():
        tps, mfu, _ = bench_bert(batch, seq_len, steps, masked=True)
        return tps_row("bert_base_masked_pretrain_tokens_per_sec_per_chip",
                       tps, mfu)

    def longseq():
        # S=1024 engages the pallas flash kernels (ops/attention.py gates
        # them off below 512 where dense XLA wins): this row runs the
        # in-kernel mask+dropout flash path at the lengths it exists for
        tps, mfu, _ = bench_bert(_env_int("BENCH_LONG_BATCH", 16), 1024,
                                 half, masked=True)
        return tps_row("bert_base_seq1024_flash_tokens_per_sec_per_chip",
                       tps, mfu)

    def bertlarge():
        # BERT/ERNIE-large geometry (BASELINE config 4): per-layer remat
        # keeps batch 64 resident, see docs/perf_notes.md
        tps, mfu, _ = bench_bert(
            _env_int("BENCH_LARGE_BATCH", 64), seq_len, half, large=True,
            recompute=os.environ.get("BENCH_LARGE_REMAT", "1") == "1")
        return tps_row("bert_large_pretrain_tokens_per_sec_per_chip",
                       tps, mfu)

    def gpt():
        tps, mfu = bench_gpt(_env_int("BENCH_GPT_BATCH", 32),
                             _env_int("BENCH_GPT_SEQ", 512), half)
        return tps_row("gpt2_small_seq512_causal_lm_tokens_per_sec_per_chip",
                       tps, mfu)

    def decode():
        dps = bench_gpt_decode(_env_int("BENCH_DECODE_BATCH", 8),
                               _env_int("BENCH_DECODE_PROMPT", 128),
                               _env_int("BENCH_DECODE_NEW", 128), 2)
        return {"metric": "gpt2_small_kvcache_decode_tokens_per_sec",
                "value": round(dps, 1), "unit": "tokens/s",
                "dtype": os.environ.get("BENCH_DECODE_DTYPE", "bfloat16")}

    def resnet():
        ips = bench_resnet50(_env_int("BENCH_RESNET_BATCH", 64), steps)
        return {"metric": "resnet50_train_images_per_sec_per_chip",
                "value": round(ips, 1), "unit": "images/s",
                "mfu": round(ips * RESNET50_TRAIN_FLOPS_PER_IMAGE
                             / _peak_flops(), 4)}

    def widedeep():
        eps = bench_wide_deep(_env_int("BENCH_CTR_BATCH", 512), steps)
        return {"metric": "wide_deep_ps_examples_per_sec",
                "value": round(eps, 1), "unit": "examples/s"}

    def pipelined():
        # 20-step per-step loop logging every 5, sync vs async dispatch
        # in the SAME run: the async arm must record fetch_sync_count <= 4
        # and lower host_blocked_ms (both stamped in the row)
        arms = bench_pipelined_loop(batch, seq_len, steps=20, log_every=5)
        return {"metric": "pipelined_loop_host_blocked_ms_async",
                "value": arms["async"]["host_blocked_ms"], "unit": "ms",
                "arms": arms}

    table = {"masked": [("masked", masked)],
             "longseq": [("longseq", longseq)],
             "bertlarge": [("bertlarge", bertlarge)], "gpt": [("gpt", gpt)],
             "decode": [("decode", decode)], "serving": _serving_rows(),
             "resnet": [("resnet", resnet)],
             "widedeep": [("widedeep", widedeep)],
             "pipelined": [("pipelined", pipelined)]}
    return [row for name, rows in table.items()
            if which in ("all", name) for row in rows]


def main():
    import jax
    import jaxlib
    from paddle_tpu import compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        # a CPU timing under a device metric's name is worse than no number
        print(f"bench.py needs a TPU; JAX found platform={dev.platform!r} "
              f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})",
              file=sys.stderr)
        sys.exit(2)
    device_peaks(dev.device_kind)    # unknown kind: raise before any row
    cache_dir = compile_cache.enable()

    batch = _env_int("BENCH_BATCH", 128)
    seq_len = _env_int("BENCH_SEQ", 128)
    steps = _env_int("BENCH_STEPS", 20)
    which = os.environ.get("BENCH_WHICH", "all")
    if os.environ.get("PADDLE_TPU_ASYNC", "0") == "1":
        # the A/B toggle: every executor call in this process defaults to
        # lazy fetches (run(sync=False) semantics); the record is stamped
        # async_dispatch below
        from paddle_tpu.flags import set_flags
        set_flags({"FLAGS_async_dispatch": True})
    if _zero_enabled():
        # ZeRO arm: every fleet build in this process shards per the
        # requested stage (parallel/zero.py); stamped zero_stage
        from paddle_tpu.flags import set_flags
        set_flags({"FLAGS_zero_stage": _zero_stage()})

    errors = []

    def run_row(name, thunk):
        """A failed row must not cost the rows after it their numbers —
        but it does cost the run its exit code (see the end of main)."""
        try:
            return thunk()
        except Exception as e:
            traceback.print_exc(file=sys.stderr)
            errors.append(f"{name}: {e!r}")
            return None

    primary = run_row("bert", lambda: bench_bert(batch, seq_len, steps))
    tokens_per_sec, mfu, step_roofline = primary or (None, None, {})

    extras = []
    for name, thunk in _extra_rows(which, batch, seq_len, steps):
        out = run_row(name, thunk)
        if out is not None:
            extras.extend(out if isinstance(out, list) else [out])

    if _OPT_STATE_REPORT is not None:
        # structural row (no timing): optimizer-state bytes/device of the
        # primary BERT step — under ZeRO-1 the flat buckets divide by dp,
        # cross-checked against the compiled step's memory_analysis()
        extras.append({
            "metric": "optimizer_state_bytes_per_device",
            "value": _OPT_STATE_REPORT["state_bytes_per_device"],
            "unit": "bytes", **_OPT_STATE_REPORT})

    rec = {
        "metric": "bert_base_pretrain_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1) if tokens_per_sec else None,
        "unit": "tokens/s",
        "mfu": round(mfu, 4) if mfu is not None else None,
        # every number names the device it was measured on
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__},
        "compile_cache_dir": cache_dir,
        "extras": extras,
    }
    # A/B arms are stamped in EVERY record, so a number recorded under one
    # arm can never read as baseline drift against another
    rec["layer_scan"] = _layer_scan_enabled()
    rec["async_dispatch"] = os.environ.get("PADDLE_TPU_ASYNC", "0") == "1"
    rec["zero_stage"] = _zero_stage()
    rec["pallas_decode"] = os.environ.get(
        "PADDLE_TPU_PALLAS_DECODE", "0") == "1"
    rec["pallas_opt"] = os.environ.get("PADDLE_TPU_PALLAS_OPT", "0") == "1"
    # roofline of the primary train step (XLA cost-analysis flops/bytes
    # over per-step time vs the device's peaks)
    rec.update(step_roofline)
    if errors:
        rec["error"] = "; ".join(errors)
    try:
        # every record carries the typed metrics snapshot (compile cache
        # hits, fetch-sync histogram, fallback counters, ...) so a number
        # is never divorced from the observability state it ran under —
        # and a failed row ships its own flight-recorder timeline
        from paddle_tpu.observability import flight as _obs_flight
        from paddle_tpu.observability import metrics as _obs_metrics
        rec["extras"].append({"metric": "observability_metrics_snapshot",
                              "snapshot": _obs_metrics.snapshot()})
        if errors:
            fp = _obs_flight.dump("bench_row_failed",
                                  extra={"errors": errors})
            if fp:
                rec["flight_dump"] = fp
    except Exception as e:  # observability must never block the record
        print(f"metrics stamp failed: {e!r}", file=sys.stderr)
    print(json.dumps(rec))
    sys.exit(1 if errors else 0)


if __name__ == "__main__":
    main()
