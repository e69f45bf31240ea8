#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on a chip.

One process, started as `python chip_smoke.py` from the repo root, drives
the two main paths once through the entry points users call, at the
published width of models the repo already has:

  train_bert_base_s128         BERT-base pretraining (12 L, hidden 768), seq
                               128, batch 128, bf16 AMP, Adam through fleet,
                               fluid.Executor: startup, two run() steps, one
                               run_steps(4) window
  train_bert_base_s1024_flash  the same builder at seq 1024, batch 16, padded
                               batch + dropout — the one default path that
                               selects a Pallas kernel; proves from the
                               compiled HLO that the Mosaic kernels ran
  serve_gpt2_small             GPT-2 small (12 L, hidden 768, vocab 50257)
                               behind serving.DecodeEngine: a dozen mixed
                               requests from threads, then the engine's f32
                               parity oracle, then the same traffic through
                               the fused decode kernel and through
                               speculative decoding
  kernels                      every pallas_call family compiled by Mosaic
                               and compared with its jnp oracle
  ssm_scan                     the selective scan's two chunk kernels at
                               the hybrid cell's shape beside the
                               `jax.numpy` form on the same operands
  kda_scan                     the gated delta rule's two chunk kernels at
                               the linear-attention cell's shape, likewise
  index_scores                 the sparse-attention indexer's two score
                               kernels at the learned-selection cell's
                               shape, likewise
  head_rows                    the masked-LM head's op alone, forward and
                               backward, at the BERT cells' shape and kept
                               shares: it computes whole blocks of the
                               labelled rows and no others
  recompute_keep               the learned-selection cell's train step
                               (5 layers under `strategy.recompute`) built
                               and compiled, not run: its Mosaic calls by
                               name and its temporary bytes; the flash
                               forward and the target's kernel once a
                               layer (a segment keeps what they made)
  four_chips                   the first trainer on every visible device
                               (dp=N), replicated and ZeRO-1; runs when JAX
                               finds at least four

It needs a TPU: on any other backend it prints one line and exits 2 before
touching the program. A leg either passes or ends the process non-zero — no
leg's exception is reported as a warning. Stdout ends with two JSON lines:
the summary (`{"summary": {"legs": {...}, ..., "claim": null}}`: every leg,
wall / compile / run seconds, cache hits), then as the LAST line the result
the driver reads, exactly
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}`.
No result line is printed unless every leg passed.

The script claims nothing about speed. Wall, compile and run seconds are
printed per leg as information, labelled with the device they ran on.
"""
from __future__ import annotations

import functools
import json
import os
import sys
import threading
import time

import numpy as np

# ---------------------------------------------------------------------------
# sizes. FULL is what runs on the chip; TINY keeps the same code alive under
# tier-1 on the CPU (tests/test_chip_smoke.py), where Pallas is interpreted
# ---------------------------------------------------------------------------
FULL = {
    "bert": {},                          # BertConfig() == BERT-base
    "train": {"seq": 128, "batch": 128},
    "long": {"seq": 1024, "batch": 16, "flash_shape": (2, 12, 1024, 64)},
    # latent attention: q and k 192 wide, v and the output 128, causal, at
    # the sequence the benchmark's cell runs (the dkdv kernel's raised VMEM
    # limit); 8 heads so that the dense side's [1, 8, 4096, 4096] fits
    "latent": {"flash_shape": (1, 8, 4096, 192), "v_width": 128},
    # a group of 8 query heads on one KV head, causal, with and without a
    # window of 1024, as a sparse LM's sliding and full layers run them
    "grouped": {"flash_shape": (1, 8, 4096, 128), "kv_heads": 1,
                "window": 1024},
    # head counts that differ with a layer's kind on the same 8 KV heads,
    # at 8,192 keys: 48 query heads (groups of 6, the first that is no power
    # of two) over the whole triangle, 64 (groups of 8) inside a window of
    # 512, at or under one k block; the dense side two heads at a time
    "grouped_by_kind": {
        "full": {"flash_shape": (1, 48, 8192, 128), "kv_heads": 8},
        "window": {"flash_shape": (1, 64, 8192, 128), "kv_heads": 8,
                   "window": 512}},
    # an expert layer's grouped matmuls at widths that are odd multiples of
    # 128: rows x d x f over 16 experts (scripts/grouped_matmul_sweep.py
    # runs the same function at the benchmark's sizes, tile by tile)
    "experts": {"rows": 8192, "d": 2304, "f": 896, "experts": 16},
    # one state-space layer's selective scan at the hybrid cell's size:
    # 1 x 8,192 positions, 64 heads of 64 in 8 groups, state 128, chunks
    # of 128
    "scan": {"b": 1, "s": 8192, "h": 64, "p": 64, "g": 8, "n": 128,
             "chunk": 128},
    # one linear-attention layer's gated delta rule at the KDA cell's size:
    # 1 x 8,192 positions, 16 heads of 128, chunks of 64
    "delta": {"b": 1, "s": 8192, "h": 16, "d": 128, "chunk": 64},
    # one layer's indexer at the learned-selection cell's size: 16 heads of
    # 64 over 1 x 8,192 positions, 2,048 keys a query
    "index": {"b": 1, "h": 16, "s": 8192, "d": 64, "topk": 2048},
    # the masked-LM head at the BERT cells' shape: 16,384 rows of 768
    # against 30,522 vocabulary rows, labelled as `s512_b32_padded` (1,843
    # rows), as `s128_b128x4` (2,432) and as a causal LM (every row)
    "head": {"batch": 32, "seq": 512, "hidden": 768, "vocab": 30522,
             "shares": (0.1125, 0.1484, 1.0)},
    # the learned-selection cell's language model as one chip holds it
    # (benchmark/configs/keye_vl2_30b_a3b_ep8.json): 5 layers, 32 heads of
    # 128 on 4 KV heads, 16 of 128 experts, 18,992 vocabulary rows, 1 x
    # 8,192 tokens
    "keep": dict(vocab_size=18992, hidden_size=2048, num_hidden_layers=5,
                 num_attention_heads=32, num_key_value_heads=4, head_dim=128,
                 moe_intermediate_size=768, num_experts=128, experts_held=16,
                 num_experts_per_tok=8, mrope_section=(16, 24, 24),
                 rope_theta=1e7, indexer_num_heads=16, indexer_head_dim=64,
                 index_topk=2048, seq_len=8192),
    "gpt": {},                           # GPTConfig() == GPT-2 small
    "serve": {"max_slots": 8, "max_len": 512, "prompt_lens": (16, 300),
              "new_tokens": (8, 64), "prefix_len": 64, "requests": 12,
              "oracle_prompt": 24, "oracle_new": 12},
    "kernels": {"slots": 8, "heads": 12, "head_dim": 64, "block": 16,
                "positions": 1024,
                # one ZeRO bucket at the default 32 MB cap, and a ragged
                # length (buckets pad to 64, a dp-way shard to 64/dp)
                "buckets": (8 * 1024 * 1024, 1024 * 1024 + 16)},
    "expect_mosaic": True,
}
TINY = {
    "bert": dict(vocab_size=128, hidden_size=32, num_layers=1, num_heads=2,
                 intermediate_size=64, max_position=64),
    "train": {"seq": 16, "batch": 8},
    "long": {"seq": 32, "batch": 8, "flash_shape": (1, 1, 128, 64)},
    "latent": {"flash_shape": (1, 1, 128, 192), "v_width": 128},
    "grouped": {"flash_shape": (1, 2, 128, 64), "kv_heads": 1, "window": 48},
    "grouped_by_kind": {
        "full": {"flash_shape": (1, 12, 128, 64), "kv_heads": 2},
        "window": {"flash_shape": (1, 16, 128, 64), "kv_heads": 2,
                   "window": 48}},
    "experts": {"rows": 96, "d": 384, "f": 128, "experts": 4},
    "scan": {"b": 1, "s": 256, "h": 4, "p": 64, "g": 2, "n": 128,
             "chunk": 128},
    "delta": {"b": 1, "s": 128, "h": 2, "d": 128, "chunk": 64},
    "index": {"b": 1, "h": 2, "s": 256, "d": 64, "topk": 40},
    "head": {"batch": 4, "seq": 320, "hidden": 32, "vocab": 200,
             "shares": (0.1125, 1.0), "chunk": 64},
    "keep": dict(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                 num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                 moe_intermediate_size=32, num_experts=8,
                 num_experts_per_tok=2, mrope_section=(2, 2, 4),
                 indexer_num_heads=2, indexer_head_dim=8, index_topk=12,
                 seq_len=32),
    "gpt": dict(vocab_size=128, hidden_size=32, num_layers=1, num_heads=2,
                intermediate_size=64, max_position=64, seq_len=32,
                hidden_dropout=0.0, attention_dropout=0.0),
    "serve": {"max_slots": 4, "max_len": 64, "prompt_lens": (3, 24),
              "new_tokens": (2, 8), "prefix_len": 16, "requests": 6,
              "oracle_prompt": 6, "oracle_new": 4},
    "kernels": {"slots": 2, "heads": 2, "head_dim": 16, "block": 16,
                "positions": 64, "buckets": (5 * 1024, 1024 + 16)},
    "expect_mosaic": False,
}

# stated tolerances (CHANGES.md records what the chip actually showed)
FLASH_FWD_TOL = 2e-2      # bf16 flash vs dense XLA attention, max |diff|
FLASH_GRAD_TOL = 5e-2     # relative Frobenius error per gradient
GROUPED_TOL = 2e-2        # bf16 grouped matmul vs ragged_dot, over max |ref|
SCAN_TOL = 2e-2           # bf16 scan kernels vs the jax.numpy form, likewise
DP_LOSS_TOL = 2e-2        # |loss(dp=N) - loss(one device)| per step
NEAR_TIE = 0.05           # score gap (logit units) a bf16 rounding may decide
NEAR_TIE_F32 = 0.01       # ... and a float32 one


class SmokeFailure(AssertionError):
    """A leg's check did not hold. Never caught: it ends the process."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# compile accounting: JAX's own monitoring events, summed per leg
# ---------------------------------------------------------------------------
class CompileClock:
    """Sums the seconds JAX spends tracing, lowering and compiling (or
    fetching from the persistent cache), so a leg can report compile time
    apart from run time. Listeners are process-global: one instance."""

    TRACE = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration")
    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring as mon
        self.trace_s = self.compile_s = 0.0
        self.compiles = self.cache_hits = self.cache_misses = 0
        self._lock = threading.Lock()
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **_):
        with self._lock:
            if event == self.COMPILE:
                self.compile_s += seconds
                self.compiles += 1
            elif event in self.TRACE:
                self.trace_s += seconds

    def _on_event(self, event, **_):
        with self._lock:
            if event == self.HIT:
                self.cache_hits += 1
            elif event == self.MISS:
                self.cache_misses += 1

    def snapshot(self):
        with self._lock:
            return dict(trace_s=self.trace_s, compile_s=self.compile_s,
                        compiles=self.compiles, cache_hits=self.cache_hits,
                        cache_misses=self.cache_misses)


# ---------------------------------------------------------------------------
# trainer legs
# ---------------------------------------------------------------------------
def build_bert_trainer(preset, seq_len, batch, masked=False,
                       sharding_stage=0, one_device=False):
    """bench.py's bench_bert build, verbatim in its entry points:
    models.bert.build_pretrain_program, fleet.init + DistributedStrategy
    (amp) + Adam through fleet.distributed_optimizer, fluid.Executor.
    Returns (exe, loss, np_feed)."""
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.fluid as fluid
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models import bert
    from paddle_tpu.testing import reset_programs

    reset_programs(seed=0)
    cfg = bert.BertConfig(**preset["bert"])
    cfg.seq_len = seq_len
    cfg.max_position = max(cfg.max_position, seq_len)
    _, _, loss = bert.build_pretrain_program(cfg, use_input_mask=masked)
    fleet.init(is_collective=True)
    strategy = fleet.DistributedStrategy()
    strategy.amp = True
    strategy.sharding_stage = sharding_stage
    fleet.distributed_optimizer(
        paddle.optimizer.Adam(learning_rate=1e-4), strategy).minimize(loss)
    if one_device:
        # the reference arm of the four-chip leg: same program, same seed,
        # same global batch, mesh cut to the first device
        from paddle_tpu.parallel import DistConfig, attach, build_mesh
        prog = fluid.default_main_program()
        attach(prog, DistConfig(
            mesh=build_mesh(dp=1, devices=jax.devices()[:1]),
            param_rules=prog._dist_config.param_rules))
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    feed = {
        "input_ids": rng.randint(0, cfg.vocab_size,
                                 (batch, seq_len)).astype(np.int64),
        "mlm_labels": rng.randint(0, cfg.vocab_size,
                                  (batch, seq_len, 1)).astype(np.int64),
    }
    if masked:
        lens = rng.randint(seq_len // 2, seq_len + 1, size=(batch, 1))
        feed["input_mask"] = (
            np.arange(seq_len)[None, :] < lens).astype(np.float32)
    return exe, loss, feed


def _scalar(x):
    return float(np.asarray(x).reshape(-1)[-1])


def leg_train_s128(preset, clock):
    import jax
    from paddle_tpu.observability import metrics

    seq, batch = preset["train"]["seq"], preset["train"]["batch"]
    exe, loss, feed = build_bert_trainer(preset, seq, batch)
    losses = []
    out, = exe.run(feed=feed, fetch_list=[loss])
    losses.append(_scalar(out))

    # same shapes again: neither the executor nor jit may compile
    misses0 = metrics.get("executor.compile_cache_misses")
    compiles0 = clock.snapshot()["compiles"]
    t0 = time.perf_counter()
    out, = exe.run(feed=feed, fetch_list=[loss], return_numpy=False)
    jax.block_until_ready(out)
    step_s = time.perf_counter() - t0
    losses.append(_scalar(out))
    check(metrics.get("executor.compile_cache_misses") == misses0,
          "second exe.run with the same shapes missed the executor's "
          "compile cache")
    check(clock.snapshot()["compiles"] == compiles0,
          "second exe.run with the same shapes compiled a new XLA program")

    # one k-step window, compiled first so the timed call only runs
    k = 4
    out, = exe.run_steps(k, feed=feed, fetch_list=[loss],
                         return_numpy=False)
    jax.block_until_ready(out)
    losses.extend(float(v) for v in np.asarray(out).reshape(-1))
    t0 = time.perf_counter()
    out, = exe.run_steps(k, feed=feed, fetch_list=[loss],
                         return_numpy=False)
    dispatch_s = time.perf_counter() - t0
    jax.block_until_ready(out)
    window_s = time.perf_counter() - t0
    # block_until_ready must really wait: once it returns, a host read of
    # the same value has nothing left to wait for
    t0 = time.perf_counter()
    vals = np.asarray(out).reshape(-1)
    read_s = time.perf_counter() - t0
    check(read_s <= max(0.25 * window_s, 0.02),
          f"host read after block_until_ready took {read_s:.3f}s of a "
          f"{window_s:.3f}s window: block_until_ready did not wait")
    losses.extend(float(v) for v in vals)

    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall on a repeated batch: {losses}")
    exe.close()
    return {"losses": [round(v, 4) for v in losses],
            "step_s": round(step_s, 4), "window_steps": k,
            "window_dispatch_s": round(dispatch_s, 4),
            "window_s": round(window_s, 4),
            "host_read_after_wait_s": round(read_s, 5),
            "tokens_per_s_info": round(batch * seq * k / window_s, 1)}


FLASH_KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dq",
                 "flash_attention_bwd_dkdv")


def mosaic_calls(hlo_text):
    """{kernel name: count} over the Mosaic custom calls of an HLO text."""
    counts = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = "?"
        for known in FLASH_KERNELS + ("paged_attention_decode",
                                      "zero_update_", "selected_probs_sum",
                                      "index-scores-fwd", "index-scores-bwd",
                                      "ragged-dot-"):
            if known in line:
                name = known
                break
        counts[name] = counts.get(name, 0) + 1
    return counts


def flash_vs_dense(shape, v_width=None, causal=False, kv_heads=None,
                   window=None, dense_heads=None):
    """Op-level check: flash_attention forward and jax.grad against the
    dense XLA attention (ops/attention._xla_attention) in bf16; `v_width`
    gives v (and the output) another width than q and k, `kv_heads` gives k
    and v fewer heads than q, `window` (with `causal`) a sliding window.
    `dense_heads`: the dense side that many query heads at a time (a
    divisor of a group), where all heads' `[S, S]` scores do not fit; the
    loss is a sum over heads, so the parts' gradients are the whole's."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.attention import _causal_bias, _xla_attention
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    rng = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rng.randn(shape[0], heads, shape[2], width),
                           jnp.bfloat16)
               for heads, width in ((shape[1], shape[3]),
                                    (kv_heads or shape[1], shape[3]),
                                    (kv_heads or shape[1],
                                     v_width or shape[3])))
    scale = 1.0 / np.sqrt(shape[-1])
    mask = _causal_bias(shape[2], window) if causal else None

    def flash_loss(q, k, v):
        out = flash_attention(q, k, v, scale=scale, causal=causal,
                              window=window)
        return jnp.sum(out.astype(jnp.float32) ** 2), out

    def dense_loss(q, k, v):
        out = _xla_attention(q, k, v, mask, scale, 0.0, None)
        return jnp.sum(out.astype(jnp.float32) ** 2), out

    (_, out_f), g_f = jax.jit(jax.value_and_grad(
        flash_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    dense = jax.jit(jax.value_and_grad(dense_loss, argnums=(0, 1, 2),
                                       has_aux=True))
    if dense_heads is None:
        (_, out_d), g_d = dense(q, k, v)
    else:
        group = shape[1] // k.shape[1]
        outs, dqs = [], []
        dk, dv = (jnp.zeros(t.shape, jnp.float32) for t in (k, v))
        for h in range(0, shape[1], dense_heads):
            at = h // group
            (_, out), (gq, gk, gv) = dense(
                q[:, h:h + dense_heads], k[:, at:at + 1], v[:, at:at + 1])
            outs.append(out)
            dqs.append(gq)
            dk = dk.at[:, at:at + 1].add(gk.astype(jnp.float32))
            dv = dv.at[:, at:at + 1].add(gv.astype(jnp.float32))
        out_d = jnp.concatenate(outs, axis=1)
        g_d = (jnp.concatenate(dqs, axis=1), dk, dv)
    fwd = float(jnp.max(jnp.abs(out_f.astype(jnp.float32)
                                - out_d.astype(jnp.float32))))
    check(fwd <= FLASH_FWD_TOL,
          f"flash forward differs from dense by {fwd} > {FLASH_FWD_TOL}")
    grads = {}
    for name, a, b in zip(("dq", "dk", "dv"), g_f, g_d):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        rel = float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))
        check(np.isfinite(a).all() and rel <= FLASH_GRAD_TOL,
              f"flash {name} relative error {rel} > {FLASH_GRAD_TOL}")
        grads[name] = round(rel, 5)
    return {"shape": list(shape), "fwd_max_abs_diff": round(fwd, 5),
            "grad_rel_err": grads}


def leg_train_s1024_flash(preset, clock):
    import jax
    seq, batch = preset["long"]["seq"], preset["long"]["batch"]
    exe, loss, feed = build_bert_trainer(preset, seq, batch, masked=True)
    losses = []
    for _ in range(2):
        out, = exe.run(feed=feed, fetch_list=[loss], return_numpy=False)
        jax.block_until_ready(out)
        losses.append(_scalar(out))
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    facts = {"losses": [round(v, 4) for v in losses]}
    if preset["expect_mosaic"]:
        # the step that just ran (same cache key): its optimized HLO must
        # hold the Mosaic custom call of the forward and both backward
        # kernels — otherwise the dense path is what was measured
        calls = mosaic_calls(exe.compiled_hlo(feed, [loss]))
        for name in FLASH_KERNELS:
            check(calls.get(name, 0) >= 1,
                  f"compiled s{seq} step has no Mosaic call for {name}: "
                  f"{calls}")
        facts["mosaic_calls"] = calls
    exe.close()
    facts["flash_vs_dense"] = flash_vs_dense(preset["long"]["flash_shape"])
    return facts


def leg_attention_two_widths(preset, clock):
    """The three flash kernels where q and k are wider than v (latent
    attention), causal, against the dense route."""
    return flash_vs_dense(preset["latent"]["flash_shape"],
                          v_width=preset["latent"]["v_width"], causal=True)


def leg_attention_window_grouped(preset, clock):
    """The three flash kernels where a group of query heads shares a KV
    head (K and V at the KV heads' count in HBM, dK and dV summed over the
    group inside the dkdv kernel), causal, with a sliding window and
    without, against the dense route."""
    grouped = preset["grouped"]
    facts = {name: flash_vs_dense(grouped["flash_shape"], causal=True,
                                  kv_heads=grouped["kv_heads"], window=window)
             for name, window in (("window", grouped["window"]),
                                  ("full", None))}
    # and where the head count follows the layer's kind: groups of 6 and 8
    for name, case in preset["grouped_by_kind"].items():
        facts[name + "_by_kind"] = flash_vs_dense(
            case["flash_shape"], causal=True, kv_heads=case["kv_heads"],
            window=case.get("window"), dense_heads=2)
    return facts


# ---------------------------------------------------------------------------
# serving leg
# ---------------------------------------------------------------------------
def build_gpt_params(preset):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models.gpt import GPTConfig, build_lm_program
    from paddle_tpu.models.gpt_decode import params_from_scope
    from paddle_tpu.testing import reset_programs

    reset_programs(seed=0)
    cfg = GPTConfig(**preset["gpt"])
    cfg.max_position = max(cfg.max_position, preset["serve"]["max_len"])
    build_lm_program(cfg)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    return cfg, params_from_scope(cfg)


def smoke_requests(sv, vocab):
    """The traffic: prompt and output lengths spread over the stated
    ranges, every third request opening with one shared prefix, greedy
    and seeded top-k mixed. Request 0 carries the shared prefix and is
    served to completion before the rest arrive, so the later sharers
    find its chain published — the hit pattern does not depend on thread
    timing."""
    from paddle_tpu.serving import Request
    rng = np.random.RandomState(3)
    lo, hi = sv["prompt_lens"]
    nlo, nhi = sv["new_tokens"]
    prefix = rng.randint(0, vocab, (sv["prefix_len"],))
    reqs = []
    for i in range(sv["requests"]):
        plen = int(rng.randint(lo, hi + 1))
        new = int(rng.randint(nlo, nhi + 1))
        if i % 3 == 0:
            tail = rng.randint(0, vocab, (max(plen - len(prefix), 1),))
            prompt = np.concatenate([prefix, tail])
        else:
            prompt = rng.randint(0, vocab, (plen,))
        new = min(new, sv["max_len"] - len(prompt))
        sampled = i % 3 == 2
        reqs.append(Request(
            prompt=prompt, max_new_tokens=new,
            temperature=0.8 if sampled else 0.0,
            top_k=16 if sampled else 0, seed=1000 + i, uid=f"smoke-{i}"))
    return reqs


def stream_traffic(engine, reqs):
    """Request 0 alone, then the rest from four submitter threads with
    staggered arrivals (scripts/serving_smoke.py's pattern). Returns
    {uid: Completion}."""
    first = engine.generate([reqs[0]], timeout=900)
    rest = reqs[1:]
    handles = [None] * len(rest)

    def submitter(lo, hi, delay):
        for i in range(lo, hi):
            time.sleep(delay)
            handles[i] = engine.submit(rest[i])

    quarter = max((len(rest) + 3) // 4, 1)
    threads = [threading.Thread(
        target=submitter,
        args=(q * quarter, min((q + 1) * quarter, len(rest)),
              0.002 * (q + 1))) for q in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        check(not t.is_alive(), "a submitter thread did not finish")
    comps = list(first) + [
        h.result(timeout=900, raise_on_error=False) for h in handles]
    return {c.uid: c for c in comps}


def pool_blocks(sv, spare_slots=0):
    """Pool size that funds every slot to max_len (+ the scratch block);
    the block size is the engine's own default, the flag."""
    from paddle_tpu.flags import flag
    per_slot = -(-sv["max_len"] // int(flag("FLAGS_serving_block_size")))
    return (sv["max_slots"] + spare_slots) * per_slot + 1


def serve_pass(clock, params, cfg, sv, reqs, **engine_kw):
    """One engine, the whole traffic; every request must complete."""
    from paddle_tpu.observability import metrics
    from paddle_tpu.serving import DecodeEngine

    # two slots' worth of spare blocks for the prefix cache to keep chains
    kw = dict(max_slots=sv["max_slots"], max_len=sv["max_len"],
              num_blocks=pool_blocks(sv, spare_slots=2),
              dtype="bfloat16", prefix_cache=True)
    kw.update(engine_kw)
    metrics.reset("serving.ttft_ms")
    shed0 = metrics.get("serving.shed_total")
    compiles0 = clock.snapshot()["compiles"]
    engine = DecodeEngine(params, cfg, **kw)   # block size, window: flags
    t0 = time.perf_counter()
    try:
        comps = stream_traffic(engine, reqs)
        wall = time.perf_counter() - t0
        stats = engine.stats()
    finally:
        engine.stop()
    bad = [(c.uid, c.state, c.error) for c in comps.values() if not c.ok]
    check(not bad, f"requests failed or were shed: {bad[:4]}")
    check(len(comps) == len(reqs), f"{len(comps)}/{len(reqs)} completions")
    check(metrics.get("serving.shed_total") == shed0, "a request was shed")
    ttft = metrics.snapshot().get("serving.ttft_ms", {})
    check(ttft.get("count", 0) >= len(reqs),
          f"TTFT histogram saw {ttft.get('count')} of {len(reqs)} requests")
    check(stats.get("dead") is None, f"engine died: {stats.get('dead')}")
    tokens = {uid: list(c.tokens) for uid, c in comps.items()}
    n_tok = sum(len(t) for t in tokens.values())
    return tokens, stats, {
        "wall_s": round(wall, 2), "tokens": n_tok,
        "programs_compiled": clock.snapshot()["compiles"] - compiles0,
        "ttft_p50_ms": ttft.get("p50"), "ttft_p99_ms": ttft.get("p99"),
        "windows": stats["windows"],
        "prefix_hits": stats.get("prefix_cache_hits"),
        "prefill_tokens_saved": stats.get("prefill_tokens_saved")}


class ReferenceScores:
    """float32 next-token scores from models.gpt_decode.prefill over the
    weights an engine holds (`bf16_weights`: rounded to bf16 as
    serving.weights.prepare_params does, computed in f32) — the function
    every serving arm approximates. For a sampled request the score is
    the engine's own (serving.engine._sample_rows): temperature, top-k
    filter and the Gumbel draw of key fold_in(PRNGKey(seed), token
    index), so in every case the engine emits the argmax of the score."""

    def __init__(self, cfg, params, width, bf16_weights=True):
        self.cfg, self.width = cfg, width
        self._host_params, self._bf16 = params, bf16_weights
        self._logits = None         # weights move and compile on first use

    def _prepare(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.models.gpt_decode import prefill
        self.params = {
            k: jax.device_put(
                jnp.asarray(v).astype(jnp.bfloat16).astype(jnp.float32)
                if self._bf16 and "_ln" not in k else v)
            for k, v in self._host_params.items()}
        self._logits = jax.jit(lambda p, toks, n: prefill(
            p, self.cfg, toks, n, self.width)[2][0])

    def gap(self, req, emitted, token, tol=0.0):
        """How far `token` is from being the engine's choice after
        `emitted`: 0.0 when it is the choice. With `tol`, a logit within
        tol of the top-k threshold counts as on either side of it."""
        import jax
        import jax.numpy as jnp
        ctx = np.concatenate([np.asarray(req.prompt), np.asarray(
            emitted, np.int64)]).astype(np.int32)
        toks = np.zeros((1, self.width), np.int32)
        toks[0, :len(ctx)] = ctx
        if self._logits is None:
            self._prepare()
        z = self._logits(self.params, toks, len(ctx))
        if req.temperature == 0.0:
            return float(z.max() - z[token])
        z = z / req.temperature
        surely_in = possibly_in = jnp.ones(z.shape, bool)
        if req.top_k:
            kth = jnp.sort(z)[-min(req.top_k, z.shape[0])]
            surely_in, possibly_in = z >= kth + tol, z >= kth - tol
        key = jax.random.fold_in(jax.random.PRNGKey(req.seed), len(emitted))
        score = z + jax.random.gumbel(key, z.shape, z.dtype)
        if not possibly_in[token]:
            return float("inf")
        best = jnp.max(jnp.where(surely_in, score, -jnp.inf))
        return max(float(best - score[token]), 0.0)


def same_tokens(ref, reqs, want, got, arm, near_tie=NEAR_TIE):
    """`got` must be `want` ({uid: tokens}) — or, where a request's tokens
    part, both candidates at that position must lie within `near_tie` of
    the best reference score. Programs that XLA and Mosaic compile
    separately round the same values differently in the last bit; that
    can only decide a token at a near tie, and after it the two
    continuations are different sequences, not comparable."""
    ties = {}
    for uid, n in diverged(want, got).items():
        check(n < min(len(want[uid]), len(got[uid])),
              f"{arm}: {uid} emitted {len(got[uid])} tokens, expected "
              f"{len(want[uid])}")
        gaps = [round(ref.gap(reqs[uid], want[uid][:n], t, near_tie), 4)
                for t in (want[uid][n], got[uid][n])]
        check(max(gaps) <= near_tie,
              f"{arm}: {uid} parts from the expected tokens at index {n} "
              f"and it is no near tie: reference score gaps {gaps} "
              f"(expected, {arm})")
        ties[uid] = {"index": n, "gaps": gaps}
    return {"identical": len(want) - len(ties), "near_tie_splits": ties}


def diverged(a, b):
    """uids whose token lists differ, with the first differing index."""
    out = {}
    for uid in a:
        if a[uid] != b[uid]:
            n = next((i for i, (x, y) in enumerate(zip(a[uid], b[uid]))
                      if x != y), min(len(a[uid]), len(b[uid])))
            out[uid] = n
    return out


def oracle_pass(params, cfg, sv):
    """float32 engine, greedy, against models.gpt_decode.generate — the
    engine's own parity oracle (docs/serving.md)."""
    import jax
    from paddle_tpu.models.gpt_decode import generate
    from paddle_tpu.serving import DecodeEngine, Request

    rng = np.random.RandomState(5)
    plen, new = sv["oracle_prompt"], sv["oracle_new"]
    prompts = rng.randint(0, cfg.vocab_size, (3, plen))
    engine = DecodeEngine(params, cfg, max_slots=sv["max_slots"],
                          max_len=sv["max_len"], dtype="float32",
                          num_blocks=pool_blocks(sv))
    reqs = {f"oracle-{i}": Request(prompt=p, max_new_tokens=new,
                                   uid=f"oracle-{i}")
            for i, p in enumerate(prompts)}
    try:
        comps = engine.generate(list(reqs.values()), timeout=900)
    finally:
        engine.stop()
    check(all(c.ok for c in comps), "oracle pass: a request failed")
    dev_params = {k: jax.device_put(v) for k, v in params.items()}
    want = np.asarray(generate(dev_params, cfg, prompts,
                               max_new_tokens=new))[:, plen:]
    ref = ReferenceScores(cfg, params, sv["max_len"], bf16_weights=False)
    row = same_tokens(ref, reqs,
                      {u: list(map(int, w)) for u, w in zip(reqs, want)},
                      {c.uid: list(c.tokens) for c in comps},
                      "float32 engine vs gpt_decode.generate", NEAR_TIE_F32)
    return {"requests": len(comps), "tokens_each": new, **row}


def leg_serve(preset, clock):
    sv = preset["serve"]
    cfg, params = build_gpt_params(preset)
    reqs = smoke_requests(sv, cfg.vocab_size)
    by_uid = {r.uid: r for r in reqs}
    facts = {}
    plain, stats, facts["plain_bf16"] = serve_pass(
        clock, params, cfg, sv, reqs)
    check(stats.get("prefix_cache_hits", 0) >= 1,
          f"the prefix cache never hit: {stats}")
    # every request's first token against the reference: ties the engine
    # (prefill, prefix-hit suffix prefill, greedy and seeded sampling) to
    # an independent forward pass, and proves the reference itself
    ref = ReferenceScores(cfg, params, sv["max_len"])
    first_gaps = {uid: round(ref.gap(by_uid[uid], [], toks[0], NEAR_TIE), 4)
                  for uid, toks in plain.items()}
    check(max(first_gaps.values()) <= NEAR_TIE,
          f"first tokens are not the reference's choice: {first_gaps}")
    facts["plain_bf16"]["first_token_gap_max"] = max(first_gaps.values())
    facts["oracle_f32"] = oracle_pass(params, cfg, sv)
    for arm, kw in (("decode_kernel", {"decode_kernel": True}),
                    ("spec", {"spec": True})):
        tokens, stats, facts[arm] = serve_pass(
            clock, params, cfg, sv, reqs, **kw)
        facts[arm]["parity"] = same_tokens(ref, by_uid, plain, tokens, arm)
        if arm == "spec":
            check(stats.get("spec_rounds", 0) >= 1
                  and stats.get("spec_accepted", 0) >= 1,
                  f"speculation never ran or never accepted: {stats}")
            facts[arm]["accept_rate"] = stats.get("spec_accept_rate")
    return facts


# ---------------------------------------------------------------------------
# kernels leg
# ---------------------------------------------------------------------------
def _parity(got, want):
    g, w = np.asarray(got), np.asarray(want)
    check(g.shape == w.shape and g.dtype == w.dtype,
          f"shape/dtype {g.shape} {g.dtype} vs {w.shape} {w.dtype}")
    gf, wf = g.astype(np.float64), w.astype(np.float64)
    # an infinity of the reference (a masked score) is the kernel's too
    at = np.isfinite(wf)
    check(np.isfinite(gf[at]).all() and np.array_equal(gf[~at], wf[~at]),
          "kernel produced non-finite values")
    return {"bitwise": g.tobytes() == w.tobytes(),
            "max_abs_diff": float(np.max(np.abs(gf[at] - wf[at]))),
            "max_abs_ref": float(np.max(np.abs(wf[at])))}


# what the chip may differ by when parity is not bitwise (max |diff| over
# max |reference|): the kernels and their oracles run the same math, but
# Mosaic and XLA order f32 sums and pick MXU passes independently
PAGED_TOL = {"float32": 1e-2, "bfloat16": 1e-2, "int8": 1e-2}
ZERO_UPDATE_TOL = 1e-6


def leg_kernels(preset, clock):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import optimizer_ops  # noqa: F401  (registers)
    from paddle_tpu.ops import registry
    from paddle_tpu.ops.paged_ops import paged_attend, quantize_kv
    from paddle_tpu.ops.pallas.paged_attention import fused_paged_attention
    from paddle_tpu.ops.pallas.zero_update import fused_flat_update

    kp = preset["kernels"]
    b, nh, hd, bs = kp["slots"], kp["heads"], kp["head_dim"], kp["block"]
    mb = kp["positions"] // bs
    rng = np.random.RandomState(11)
    nb = b * mb + 1
    pt = (1 + rng.permutation(b * mb)).reshape(b, mb).astype(np.int32)
    # ragged frontiers, including the first and the last position
    pos = rng.randint(0, mb * bs, (b,)).astype(np.int32)
    pos[0], pos[-1] = 0, mb * bs - 1
    q32 = rng.randn(b, nh, 1, hd).astype(np.float32)
    k32 = rng.randn(2, nb, nh, bs, hd).astype(np.float32)
    v32 = rng.randn(2, nb, nh, bs, hd).astype(np.float32)

    facts = {"paged_decode": {}, "zero_update": {}}
    arms = {
        "float32": (q32, k32, v32, None),
        "bfloat16": tuple(jnp.asarray(a, jnp.bfloat16)
                          for a in (q32, k32, v32)) + (None,),
        "int8": (q32, quantize_kv(k32, 8.0), quantize_kv(v32, 8.0), 8.0),
    }
    for name, (q, kpool, vpool, kv_scale) in arms.items():
        kernel = jax.jit(lambda q, k, v: fused_paged_attention(
            q, k, v, pt, pos, block_size=bs, layer=1, kv_scale=kv_scale))
        oracle = jax.jit(lambda q, k, v: paged_attend(
            q, k, v, pt, pos, bs, layer=1, kv_scale=kv_scale))
        row = _parity(kernel(q, kpool, vpool), oracle(q, kpool, vpool))
        check(row["bitwise"] or row["max_abs_diff"]
              <= PAGED_TOL[name] * row["max_abs_ref"],
              f"paged decode {name}: {row} exceeds {PAGED_TOL[name]}")
        if preset["expect_mosaic"]:
            hlo = kernel.lower(q, kpool, vpool).compile().as_text()
            check(mosaic_calls(hlo).get("paged_attention_decode", 0) >= 1,
                  f"paged decode {name} was not compiled by Mosaic")
        facts["paged_decode"][name] = row

    oracle = jax.jit(lambda ins: registry.get("adam").lower(None, ins, {}))
    kernel = jax.jit(lambda ins: fused_flat_update("adam", ins, {}))
    for n in kp["buckets"]:
        ins = {"Param": [rng.randn(n).astype(np.float32)],
               "Grad": [rng.randn(n).astype(np.float32)],
               "Moment1": [rng.randn(n).astype(np.float32)],
               "Moment2": [np.abs(rng.randn(n)).astype(np.float32)],
               "LearningRate": [np.asarray([1e-3], np.float32)],
               "Beta1Pow": [np.asarray([0.9 ** 3], np.float32)],
               "Beta2Pow": [np.asarray([0.999 ** 3], np.float32)]}
        ins = jax.tree_util.tree_map(jnp.asarray, ins)
        got, want = kernel(ins), oracle(ins)
        check(sorted(got) == sorted(want), "adam output slots differ")
        rows = {}
        for slot in sorted(want):
            row = _parity(got[slot][0], want[slot][0])
            check(row["bitwise"] or row["max_abs_diff"]
                  <= ZERO_UPDATE_TOL * max(row["max_abs_ref"], 1.0),
                  f"zero_update adam [{n}] {slot}: {row} exceeds "
                  f"{ZERO_UPDATE_TOL}")
            rows[slot] = row
        if preset["expect_mosaic"]:
            hlo = kernel.lower(ins).compile().as_text()
            check(mosaic_calls(hlo).get("zero_update_", 0) >= 1,
                  "zero_update adam was not compiled by Mosaic")
        facts["zero_update"][str(n)] = rows
    return facts


def _ms_a_launch(launches, fn, *args):
    """Host-clock milliseconds a launch of a jitted `fn`, compiled and run
    once before the clock starts."""
    import jax
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(launches):
        out = fn(*args)
    jax.block_until_ready(out)
    return round(1e3 * (time.perf_counter() - t0) / launches, 4)


# ---------------------------------------------------------------------------
# the expert layer's grouped matmuls
# ---------------------------------------------------------------------------
def grouped_matmul_forms(rows, d, f, experts, retile=None, time_xla=True,
                         launches=10, seed=5):
    """The three forms of `ops/pallas/grouped_matmul.py` in both
    orientations an expert layer runs them (`[rows, d] x [E, d, f]`: gate /
    up forward, dx through down, dW of gate / up; `[rows, f] x [E, f, d]`:
    down forward, dx through gate / up, dW of down) in bf16 against
    `jax.lax.ragged_dot` / `ragged_dot_general` on the same operands: the
    gap over the largest reference value, and the host-clock milliseconds
    a launch of each (a time only on a chip). Uneven groups with an empty
    one, the last swollen to the buffer's end. `retile(form, tiles)` may
    replace the tiles the shape rule gives: the sweep's handle, which
    times `ragged_dot` once (`time_xla`) and the kernel tile by tile.
    Operands and results are held row-major, as a step's loop carries
    them: left to itself XLA stores an array whose last width is no
    multiple of 128 with the other width innermost, and a lone kernel
    would be timed with a relayout before and after it."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.ops import moe
    from paddle_tpu.ops.pallas import grouped_matmul as gm

    def row_major(ndim):
        return Format(Layout(major_to_minor=tuple(range(ndim))),
                      SingleDeviceSharding(jax.devices()[0]))

    rng = np.random.RandomState(seed)
    sizes = rng.multinomial(rows // 3, rng.dirichlet(np.ones(experts)))
    sizes[1] = 0
    sizes[-1] += rows - sizes.sum()
    sizes = jnp.asarray(sizes, jnp.int32)

    def operand(*shape):
        return jax.device_put(
            jnp.asarray(rng.randn(*shape) * 0.1, jnp.bfloat16),
            row_major(len(shape)))

    ms = functools.partial(_ms_a_launch, launches)

    facts = {}
    for k, n in ((d, f), (f, d)):
        x, g, w = operand(rows, k), operand(rows, n), operand(experts, k, n)
        # [E, n, k], its own buffer
        wt = jax.jit(lambda w: jnp.swapaxes(w, 1, 2),
                     out_shardings=row_major(3))(w)
        forms = {
            "gmm": (gm.gmm_tiles, (x, w), {},
                    lambda x, w: jax.lax.ragged_dot(
                        x, w, sizes, preferred_element_type=w.dtype)),
            "gmm-t": (gm.gmm_tiles, (x, wt), {"transpose_rhs": True},
                      lambda x, wt: jax.lax.ragged_dot(
                          x, jnp.swapaxes(wt, 1, 2), sizes,
                          preferred_element_type=wt.dtype)),
            "tgmm": (gm.tgmm_tiles, (x, g), {},
                     lambda x, g: jax.lax.ragged_dot_general(
                         x, g, sizes, moe._DW_DIMS,
                         preferred_element_type=g.dtype)),
        }
        for form, (rule, args, kw, xla) in forms.items():
            tiles = rule(rows, k, n)
            check(tiles is not None, f"{form} [{rows},{k}]x[{k},{n}]: the "
                  "kernel does not take the shape")
            if retile is not None:
                tiles = retile(form, tiles)
            call = gm.tgmm if form == "tgmm" else gm.gmm
            out = row_major(3 if form == "tgmm" else 2)
            kernel = jax.jit(lambda a, b: call(
                a, b, gm.group_visits(sizes, rows, tiles.tm), tiles=tiles,
                **kw), out_shardings=out)
            xla = jax.jit(xla, out_shardings=out)
            row = _parity(kernel(*args), xla(*args))
            check(row["max_abs_diff"] <= GROUPED_TOL * row["max_abs_ref"],
                  f"{form} {k}x{n}: {row} exceeds {GROUPED_TOL}")
            row.update(tiles=list(tiles), ms_kernel=ms(kernel, *args))
            if time_xla:
                row["ms_xla"] = ms(xla, *args)
            facts[f"{form}.{k}x{n}"] = row
    return facts


def leg_grouped_matmul(preset, clock):
    ep = preset["experts"]
    facts = grouped_matmul_forms(ep["rows"], ep["d"], ep["f"], ep["experts"])
    if preset["expect_mosaic"]:
        for name, row in facts.items():
            print(f"[chip_smoke] grouped_matmul {name}: tiles {row['tiles']} "
                  f"{row['ms_kernel']} ms, ragged_dot {row['ms_xla']} ms, "
                  f"gap {row['max_abs_diff']:.4g}", flush=True)
    return facts


# ---------------------------------------------------------------------------
# the selective scan's chunk kernels
# ---------------------------------------------------------------------------
def ssm_scan_forms(b, s, h, p, g, n, chunk, heads=None, time_xla=True,
                   launches=10, seed=7):
    """`ops/pallas/ssm_chunk.py`'s two kernels on one layer's operands in
    bf16 (decays and states float32) beside `ops/ssm.py`'s `jax.numpy`
    form on the same operands: each result's gap over the largest
    reference value, the host-clock milliseconds a launch of each (a time
    only on a chip), and the kernels' share of the least time their bytes
    take (the rows and `States` once each way; `None` off a chip).
    The rows enter as the `[B, S, H * P]` and `[B, S, G * N]` views a
    mixer's projection gives and are cut into heads inside the timed
    function, as in a step: the chip stores a `[.., 64, 64]` array with
    half its lanes empty, and a lone call on such operands would be timed
    with a relayout before and after it. `heads`: heads a grid step, in
    place of the shape rule's whole group: the sweep's handle, which times
    the `jax.numpy` form once (`time_xla`)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import ssm
    from paddle_tpu.ops.pallas import ssm_chunk

    rng = np.random.RandomState(seed)

    def rows(*shape, dtype=jnp.bfloat16):
        return jnp.asarray(rng.randn(*shape), dtype)

    x, dy = rows(b, s, h * p), rows(b, s, h * p)
    bm, cm = rows(b, s, g * n), rows(b, s, g * n)
    # steps and decays as the hybrid configuration's initialisation gives
    # them: dt around 0.01 to 0.1, A in -16 .. -1
    dt_raw = jnp.asarray(rng.uniform(-5.0, -2.0, (b, s, h)), jnp.float32)
    a_log = jnp.asarray(np.log(rng.uniform(1, 16, h)), jnp.float32)
    d = jnp.asarray(rng.randn(h), jnp.float32)
    dt, cum = jax.jit(lambda *a: ssm._decays(*a, chunk))(
        dt_raw, jnp.zeros((h,), jnp.float32), a_log)
    plan = ssm_chunk.plan((b, s, h, p), (b, s, g, n), chunk, x.dtype.itemsize,
                          heads=heads)
    check(plan is not None, f"x {(b, s, h, p)} B {(b, s, g, n)} chunks of "
          f"{chunk}, {heads} heads a step: the kernels do not take the shape")

    def by_heads(fn):
        """fn on x, B, C (and dy) cut into heads, its x-shaped and B-shaped
        results merged again."""
        def call(x, bm, cm, dt, cum, d, *rest):
            rest = tuple(t.reshape(b, s, h, p) if t.shape == x.shape else t
                         for t in rest)
            outs = fn(x.reshape(b, s, h, p), bm.reshape(b, s, g, n),
                      cm.reshape(b, s, g, n), dt, cum, d, *rest)
            return tuple(
                t.reshape(b, s, -1) if t.shape[:2] == (b, s) and t.ndim == 4
                else t for t in outs)
        return jax.jit(call)

    ops = (x, bm, cm, dt, cum, d)
    forward = {"kernel": by_heads(lambda *a: ssm_chunk.ssd_fwd(plan, *a)),
               "xla": by_heads(lambda *a: ssm._ssd_fwd(chunk, *a))}
    backward = {"kernel": by_heads(lambda *a: ssm_chunk.ssd_bwd(plan, *a)),
                "xla": by_heads(lambda *a: ssm._ssd_bwd(chunk, *a))}
    _, states = forward["xla"](*ops)
    rows_bytes = x.nbytes + bm.nbytes + cm.nbytes + dt.nbytes + 2 * cum.nbytes
    least = {"fwd": rows_bytes + x.nbytes + states.nbytes,
             "bwd": 2 * rows_bytes + x.nbytes + states.nbytes}
    return _kernels_beside_form(
        "ssm scan", plan, least, time_xla, launches,
        ("fwd", forward, ops, ("y", "states")),
        ("bwd", backward, ops + (states, dy),
         ("dx", "db", "dc", "ddt", "dcum", "dd")))


def _kernels_beside_form(label, plan, least, time_xla, launches, *passes,
                         tol=lambda out: SCAN_TOL, also=None):
    """The facts of `ssm_scan_forms` / `kda_scan_forms`: for each pass
    (name, {"kernel": fn, "xla": fn}, args, output names) every output's
    gap inside `tol(output)` of its largest reference value, the
    milliseconds a launch of the kernel (and of the form, `time_xla`), what
    `also(name, args)` adds, and the share of the least time `least[name]`
    bytes take at the chip's HBM rate (`None` off a chip)."""
    import jax
    ms = functools.partial(_ms_a_launch, launches)
    on_chip = jax.devices()[0].platform == "tpu"
    facts = {"plan": list(plan)}
    for name, forms, args, outs in passes:
        got, want = forms["kernel"](*args), forms["xla"](*args)
        row = {}
        for out, a, w in zip(outs, got, want):
            row[out] = _parity(a, w)
            check(row[out]["max_abs_diff"]
                  <= tol(out) * row[out]["max_abs_ref"],
                  f"{label} {name} {out}: {row[out]} exceeds {tol(out)}")
        row["ms_kernel"] = ms(forms["kernel"], *args)
        if time_xla:
            row["ms_xla"] = ms(forms["xla"], *args)
        if also is not None:
            row.update(also(name, args))
        row["bytes_least_share"] = None
        if on_chip:
            import bench
            peak = bench.device_peaks(
                jax.devices()[0].device_kind)["hbm_bytes_per_s"]
            row["bytes_least_share"] = round(
                least[name] / peak / (1e-3 * row["ms_kernel"]), 4)
        facts[name] = row
    return facts


def leg_ssm_scan(preset, clock):
    facts = ssm_scan_forms(**preset["scan"])
    if preset["expect_mosaic"]:
        for name in ("fwd", "bwd"):
            row = facts[name]
            print(f"[chip_smoke] ssm_scan {name}: kernel {row['ms_kernel']} "
                  f"ms ({row['bytes_least_share']} of its bytes' least "
                  f"time), jax.numpy form {row['ms_xla']} ms", flush=True)
    return facts


# ---------------------------------------------------------------------------
# the gated delta rule's chunk kernels
# ---------------------------------------------------------------------------
def kda_scan_forms(b, s, h, d, chunk, heads=None, time_xla=True,
                   without_solve=False, launches=10, seed=11, exact=False):
    """`ops/pallas/kda_chunk.py`'s two kernels on one layer's operands in
    bf16 (decay, beta and states float32) beside `ops/kda.py`'s `jax.numpy`
    form on the same operands: each result's gap over the largest
    reference value, the host-clock milliseconds a launch of each (a time
    only on a chip), and the kernels' share of the least time their bytes
    take (the rows and `States` once each way; `None` off a chip). The rows
    enter as the `[B, S, H * d]` views a layer's projection gives and are
    cut into heads inside the timed function, as in a step. q and k are
    L2-normed a head and g lies in (-5, 0), as the builder makes them.
    `heads`: heads a grid step, in place of the shape rule's own: the
    sweep's handle, which times the `jax.numpy` form once (`time_xla`).
    `without_solve` also times the forward kernel with the solve left out
    (a wrong answer, timed only): the difference is what the in-kernel
    solve costs. `exact`: the decayed products for a decay without a bound
    in both lowerings, on the same operands with a planted -40 a chunk."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import kda
    from paddle_tpu.ops.pallas import kda_chunk

    rng = np.random.RandomState(seed)
    bf = jnp.bfloat16

    def unit(t):
        return t / np.linalg.norm(t, axis=-1, keepdims=True)

    def merged(t, dtype):
        return jnp.asarray(t.reshape(b, s, h * d), dtype)

    q = merged(unit(rng.randn(b, s, h, d)) * d ** -0.5, bf)
    k = merged(unit(rng.randn(b, s, h, d)), bf)
    v, do = merged(rng.randn(b, s, h, d), bf), merged(rng.randn(b, s, h, d), bf)
    g = -5.0 * rng.uniform(0, 1, (b, s, h, d)) ** 0.5
    if exact:
        g[:, 5::chunk] = -40.0
    g = merged(g, jnp.float32)
    beta = jnp.asarray(1 / (1 + np.exp(-rng.randn(b, s, h))), jnp.float32)
    plan = kda_chunk.plan((b, s, h, d), (b, s, h, d), chunk, bf, heads=heads,
                          exact=exact)
    check(plan is not None, f"q {(b, s, h, d)} chunks of {chunk}, {heads} "
          "heads a step: the kernels do not take the shape")

    def by_heads(fn):
        """fn on q, k, v, g (and do) cut into heads, its row-shaped results
        merged again."""
        def call(*args):
            outs = fn(*(t.reshape(b, s, h, d) if t.shape == q.shape else t
                        for t in args))
            return tuple(t.reshape(b, s, h * d) if t.shape == (b, s, h, d)
                         else t for t in outs)
        return jax.jit(call)

    ops = (q, k, v, g, beta)
    forward = {"kernel": by_heads(lambda *a: kda_chunk.kda_fwd(plan, *a)),
               "xla": by_heads(lambda *a: kda._kda_fwd(chunk, *a,
                                                       exact=exact))}
    backward = {"kernel": by_heads(lambda *a: kda_chunk.kda_bwd(plan, *a)),
                "xla": by_heads(lambda *a: kda._kda_bwd(chunk, *a,
                                                        exact=exact))}
    _, states = forward["xla"](*ops)
    rows_bytes = q.nbytes + k.nbytes + v.nbytes + g.nbytes + beta.nbytes
    least = {"fwd": rows_bytes + v.nbytes + states.nbytes,
             "bwd": 2 * rows_bytes + v.nbytes + states.nbytes}

    def without(name, args):
        if not (without_solve and name == "fwd"):
            return {}
        return {"ms_kernel_without_solve": _ms_a_launch(launches, by_heads(
            lambda *a: kda_chunk.kda_fwd(plan, *a, solve=False)), *args)}

    # dg is a difference of sums as long as the chunk: the form's own bf16
    # gap to float32 is a tenth of its largest value
    return _kernels_beside_form(
        "kda scan", plan, least, time_xla, launches,
        ("fwd", forward, ops, ("y", "states")),
        ("bwd", backward, ops + (states, do),
         ("dq", "dk", "dv", "dg", "dbeta")),
        tol=lambda out: 10 * SCAN_TOL if out == "dg" else SCAN_TOL,
        also=without)


def leg_kda_scan(preset, clock):
    facts = kda_scan_forms(**preset["delta"])
    if preset["expect_mosaic"]:
        for name in ("fwd", "bwd"):
            row = facts[name]
            print(f"[chip_smoke] kda_scan {name}: kernel {row['ms_kernel']} "
                  f"ms ({row['bytes_least_share']} of its bytes' least "
                  f"time), jax.numpy form {row['ms_xla']} ms", flush=True)
    return facts


# ---------------------------------------------------------------------------
# the sparse-attention indexer's score kernels
# ---------------------------------------------------------------------------
def index_scores_forms(b, h, s, d, topk, blocks=None, time_xla=True,
                       launches=10, seed=13):
    """`ops/pallas/index_scores.py`'s two kernels on one layer's operands
    in bf16 (the weights and the cotangent float32) beside
    `ops/sparse_index.py`'s `jax.numpy` form on the same operands: each
    result's gap over the largest reference value, the host-clock
    milliseconds a launch of each (a time only on a chip), and the kernels'
    shares of the least time their bytes take (QI, KI, W and the `[S, S]`
    float32 array once each way) and of the least time the causal pairs'
    products take at the chip's bf16 rate (one forward, three backward;
    `None` off a chip). The cotangent is zero off the selection of `topk`
    keys a query, as the indexer's loss gives it. `blocks` (queries a tile,
    keys a tile, query rows an inner step) in place of the shape rule's
    own: the sweep's handle, which times the `jax.numpy` form once
    (`time_xla`)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import sparse_index
    from paddle_tpu.ops.pallas import index_scores

    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, h, s, d) * d ** -0.25, jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, s, d) * d ** -0.25, jnp.bfloat16)
    w = jnp.asarray(rng.randn(b, s, h) * h ** -0.5, jnp.float32)
    plan = index_scores.plan(q.shape, q.dtype, blocks)
    check(plan is not None, f"QI {(b, h, s, d)} in blocks {blocks}: the "
          "kernels do not take the shape")
    forward = {
        "kernel": jax.jit(lambda *a: (index_scores.scores_fwd(plan, *a),)),
        "xla": jax.jit(lambda *a: (sparse_index._index_fwd(*a, topk)[0],))}
    backward = {
        "kernel": jax.jit(lambda *a: index_scores.scores_bwd(plan, *a)),
        "xla": jax.jit(sparse_index._scores_bwd)}
    ds = jax.jit(lambda scores, noise: jnp.where(
        sparse_index.select_topk(scores, topk) != 0, noise, 0.0) / s)(
            forward["xla"](q, k, w)[0],
            jnp.asarray(rng.randn(b, s, s), jnp.float32))
    rows_bytes = q.nbytes + k.nbytes + w.nbytes + ds.nbytes
    facts = _kernels_beside_form(
        "index scores", plan,
        {"fwd": rows_bytes, "bwd": 2 * rows_bytes - ds.nbytes}, time_xla,
        launches, ("fwd", forward, (q, k, w), ("scores",)),
        ("bwd", backward, (q, k, w, ds), ("dq", "dk", "dw")))
    peak = None
    if jax.devices()[0].platform == "tpu":
        import bench
        peak = bench.device_peaks(jax.devices()[0].device_kind)["bf16_flops"]
    for name, products in (("fwd", 1), ("bwd", 3)):
        facts[name]["flops_least_share"] = peak and round(
            products * b * h * d * s * (s + 1) / peak
            / (1e-3 * facts[name]["ms_kernel"]), 4)
    return facts


def leg_index_scores(preset, clock):
    facts = index_scores_forms(**preset["index"])
    if preset["expect_mosaic"]:
        for name in ("fwd", "bwd"):
            row = facts[name]
            print(f"[chip_smoke] index_scores {name}: kernel "
                  f"{row['ms_kernel']} ms ({row['flops_least_share']} of its "
                  f"products' least time), jax.numpy form {row['ms_xla']} ms",
                  flush=True)
    return facts


# ---------------------------------------------------------------------------
# the masked-LM head over the labelled rows
# ---------------------------------------------------------------------------
HEAD_GAP = 2e-2     # bf16 operands, float32 sums, against float32 throughout


def head_rows_forms(batch, seq, hidden, vocab, shares, chunk=None,
                    row_blocks=(None,), launches=10, seed=17):
    """`fused_lm_head_ce`'s lowering alone on `[batch, seq, hidden]` bf16
    rows against an `[hidden, vocab]` bf16 weight and a float32 bias (what
    an AMP BERT step hands it), labelled at each of `shares` of the rows,
    the labels scattered: the rows it computes (`Rows`, and gauge
    `head.rows_computed_share`), which must be the labelled count up to
    whole blocks, and the host-clock milliseconds a launch of the forward
    and of the forward with the backward (a time only on a chip). At the
    first share the loss and the three gradients are held against the
    dense pair in float32. `row_blocks`: `ops/fused_ce.py` `ROW_BLOCK`
    values to run in place of the file's own (None), the sweep's handle."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.observability import metrics
    from paddle_tpu.ops import fused_ce

    rng = np.random.RandomState(seed)
    n_rows = batch * seq
    x = jnp.asarray(rng.randn(batch, seq, hidden), jnp.bfloat16)
    w = jnp.asarray(rng.randn(hidden, vocab) * hidden ** -0.5, jnp.bfloat16)
    b = jnp.asarray(rng.randn(vocab) * 0.1, jnp.float32)
    attrs = {"w_layout": "hv", "chunk": chunk}

    def op(x, w, b, labels):
        outs = fused_ce._fused_lm_head_ce(
            None, {"X": [x], "W": [w], "Bias": [b], "Label": [labels]},
            attrs)
        return outs["Loss"][0], outs["Rows"][0]

    def mean_loss(x, w, b, labels):
        loss, rows = op(x, w, b, labels)
        return jnp.mean(loss), rows

    def dense(x, w, b, labels):
        f32 = jnp.float32
        logits = jnp.einsum("bsh,hv->bsv", x.astype(f32), w.astype(f32),
                            precision="highest") + b
        lse = jax.nn.logsumexp(logits, axis=-1)
        lab = labels[..., 0]
        got = jnp.take_along_axis(logits, jnp.maximum(lab, 0)[..., None],
                                  axis=-1)[..., 0]
        return jnp.mean(jnp.where(lab == -100, 0.0, lse - got))

    def labels_at(share):
        kept = int(round(share * n_rows))
        lab = np.full((n_rows,), -100, np.int64)
        lab[rng.permutation(n_rows)[:kept]] = rng.randint(0, vocab, kept)
        return kept, jnp.asarray(lab.reshape(batch, seq, 1))

    facts = {}
    for block in row_blocks:
        own = fused_ce.ROW_BLOCK
        if block is not None:
            fused_ce.ROW_BLOCK = block
        try:
            r = fused_ce._row_block(n_rows)
            fwd = jax.jit(op)
            both = jax.jit(jax.value_and_grad(mean_loss, argnums=(0, 1, 2),
                                              has_aux=True))
            for i, share in enumerate(shares):
                kept, labels = labels_at(share)
                (loss, rows), grads = both(x, w, b, labels)
                rows = int(rows[0])
                check(rows == -(-kept // r) * r,
                      f"{kept} labelled rows in blocks of {r}: the op "
                      f"computed {rows}")
                got = fused_ce.record_rows_share(rows, n_rows)
                check(metrics.get("head.rows_computed_share") == got,
                      "gauge head.rows_computed_share was not set")
                row = {"labelled": kept, "rows_computed": rows,
                       "rows_computed_share": round(got, 4),
                       "ms_fwd": _ms_a_launch(launches, fwd, x, w, b,
                                              labels),
                       "ms_fwd_bwd": _ms_a_launch(launches, both, x, w, b,
                                                  labels)}
                if i == 0:
                    want, want_g = jax.jit(jax.value_and_grad(
                        dense, argnums=(0, 1, 2)))(x, w, b, labels)
                    gaps = {"loss": abs(float(loss) - float(want))
                            / abs(float(want))}
                    for name, g, wg in zip(("dx", "dw", "db"), grads,
                                           want_g):
                        g, wg = (np.asarray(a, np.float32) for a in (g, wg))
                        gaps[name] = float(np.abs(g - wg).max()
                                           / np.abs(wg).max())
                    check(all(v <= HEAD_GAP for v in gaps.values()),
                          f"head over labelled rows against the dense "
                          f"pair in float32: {gaps}")
                    row["gaps"] = {k: round(v, 6) for k, v in gaps.items()}
                facts[f"r{r}_share{share}"] = row
        finally:
            if block is not None:       # what was traced with it must go
                fused_ce.ROW_BLOCK = own
                jax.clear_caches()
    return facts


def leg_head_rows(preset, clock):
    facts = head_rows_forms(**preset["head"])
    for name, row in facts.items():
        print(f"[chip_smoke] head_rows {name}: {row['labelled']} labelled, "
              f"{row['rows_computed']} rows computed "
              f"(head.rows_computed_share {row['rows_computed_share']}), "
              f"fwd {row['ms_fwd']} ms, fwd+bwd {row['ms_fwd_bwd']} ms",
              flush=True)
    return facts


# ---------------------------------------------------------------------------
# what a recomputed segment keeps: the learned-selection cell's step
# ---------------------------------------------------------------------------
def leg_recompute_keep(preset, clock):
    """Build the learned-selection LM's AMP train step under
    `strategy.recompute` (a checkpoint at every layer boundary, as its cell
    runs it) and compile `run_steps(2)`'s program without running it: the
    Mosaic calls by name, the temporary bytes, the counters a trace raised.
    A segment keeps its selection, the target and the flash output beside
    the layer boundary (`ops/registry.py` `keep_under_recompute`), so the
    compiled step may launch the flash forward and the target's kernel
    once a layer, not twice."""
    import paddle_tpu as paddle
    import paddle_tpu.fluid as fluid
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models import keye
    from paddle_tpu.observability import metrics
    from paddle_tpu.testing import reset_programs

    reset_programs(0)
    cfg = keye.KeyeConfig(**preset["keep"])
    _, loss, _ = keye.build_causal_lm_program(cfg)
    fleet.init(is_collective=True)
    strategy = fleet.DistributedStrategy()
    strategy.amp = True
    strategy.recompute = True
    strategy.recompute_configs = {"checkpoints": list(loss._layer_checkpoints)}
    fleet.distributed_optimizer(paddle.optimizer.Adam(learning_rate=1e-4),
                                strategy).minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    feed = {"tokens": np.random.RandomState(0).randint(
        0, cfg.vocab_size, (2, 1, cfg.seq_len)).astype(np.int64)}
    kept = ("recompute.kept_values", "recompute.kept_bytes")
    before = [metrics.get(n) for n in kept]
    try:
        calls = mosaic_calls(exe.compiled_hlo(feed, [loss], k=2))
        memory = exe.compiled_memory_analysis(feed, [loss], k=2)
    finally:
        exe.close()
        scope = fluid.global_scope()
        for name in scope.local_names():
            scope.erase(name)
    values, nbytes = (int(metrics.get(n) - b) for n, b in zip(kept, before))
    layers = cfg.num_hidden_layers
    facts = {"layers": layers, "mosaic_calls": calls,
             "temp_bytes": int(memory.temp_size_in_bytes),
             "argument_bytes": int(memory.argument_size_in_bytes),
             "kept_values": values, "kept_bytes": nbytes}
    print(f"[chip_smoke] recompute_keep: {layers} layers, Mosaic calls "
          f"{calls}, temporaries {facts['temp_bytes'] / 1e9:.2f} GB, "
          f"{values} values kept ({nbytes / 1e9:.2f} GB)", flush=True)
    # 5 a routed layer and the selection a sparse one; with the kernels,
    # the target, the flash output and one lane of its logsumexp too
    check(values == layers * (9 if preset["expect_mosaic"] else 6),
          f"a trace kept {values} values over {layers} layers")
    if preset["expect_mosaic"]:
        for kernel in ("flash_attention_fwd", "selected_probs_sum"):
            check(0 < calls.get(kernel, 0) <= layers,
                  f"{kernel}: {calls.get(kernel, 0)} launches a step over "
                  f"{layers} layers (a kept value is made again)")
    return facts


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def leg_four_chips(preset, clock):
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.fluid as fluid
    from paddle_tpu import monitor

    n = jax.device_count()
    seq, batch = preset["train"]["seq"], preset["train"]["batch"]
    check(batch % n == 0, f"global batch {batch} does not divide by {n}")
    fallbacks0 = monitor.stat_get("executor.zero_manual_fallbacks")

    def run(steps, **build_kw):
        exe, loss, feed = build_bert_trainer(preset, seq, batch, **build_kw)
        prog = fluid.default_main_program()
        dist = prog._dist_config
        mesh = dist.resolve_mesh()
        # the feed goes where the program's own data-parallel rule puts it
        dev_feed = {k: jax.device_put(v, dist.feed_sharding(mesh, k, v.shape))
                    for k, v in feed.items()}
        losses, out = [], None
        for _ in range(steps):
            out, = exe.run(feed=dev_feed, fetch_list=[loss],
                           return_numpy=False)
            jax.block_until_ready(out)
            losses.append(_scalar(out))
        check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
        params = [v.name for v in prog.global_block().all_parameters()]
        state = paddle.global_scope().find(params[0])
        spans = {"feed": min(len(a.sharding.device_set)
                             for a in dev_feed.values()),
                 "loss": len(out.sharding.device_set),
                 "param": len(state.sharding.device_set)}
        exe.close()
        return losses, spans

    ref, _ = run(3, one_device=True)
    dp, spans = run(3)
    check(all(v == n for v in spans.values()),
          f"arrays do not span all {n} devices: {spans}")
    worst = max(abs(a - b) for a, b in zip(dp, ref))
    check(worst <= DP_LOSS_TOL,
          f"dp={n} losses {dp} differ from one-device {ref} by {worst}")
    zero1, spans1 = run(2, sharding_stage=1)
    check(all(v == n for v in spans1.values()),
          f"ZeRO-1 arrays do not span all {n} devices: {spans1}")
    worst1 = max(abs(a - b) for a, b in zip(zero1, ref))
    check(worst1 <= DP_LOSS_TOL,
          f"ZeRO-1 losses {zero1} differ from one-device {ref} by {worst1}")
    check(monitor.stat_get("executor.zero_manual_fallbacks") == fallbacks0,
          "the ZeRO step fell back from the manual shard_map regime")
    in_use = []
    for d in jax.devices():
        stats = d.memory_stats()
        if stats is not None:      # the CPU backend reports none
            check(stats.get("bytes_in_use", 0) > 0,
                  f"device {d.id} reports no memory in use")
            in_use.append(int(stats["bytes_in_use"]))
    return {"devices": n, "losses_one_device": [round(v, 4) for v in ref],
            "losses_dp": [round(v, 4) for v in dp],
            "losses_zero1": [round(v, 4) for v in zero1],
            "max_loss_diff": round(max(worst, worst1), 5),
            "bytes_in_use": in_use}


LEGS = (("train_bert_base_s128", leg_train_s128),
        ("train_bert_base_s1024_flash", leg_train_s1024_flash),
        ("attention_two_widths", leg_attention_two_widths),
        ("attention_window_grouped", leg_attention_window_grouped),
        ("serve_gpt2_small", leg_serve),
        ("kernels", leg_kernels),
        ("grouped_matmul", leg_grouped_matmul),
        ("ssm_scan", leg_ssm_scan),
        ("kda_scan", leg_kda_scan),
        ("index_scores", leg_index_scores),
        ("head_rows", leg_head_rows),
        ("recompute_keep", leg_recompute_keep),
        ("four_chips", leg_four_chips))


def run_leg(name, fn, preset, clock):
    """Time one leg; compile seconds apart from run seconds."""
    before = clock.snapshot()
    t0 = time.perf_counter()
    facts = fn(preset, clock)
    wall = time.perf_counter() - t0
    after = clock.snapshot()
    compile_s = after["compile_s"] - before["compile_s"]
    # compile_s: XLA/Mosaic compilation or its fetch from the persistent
    # cache. run_s: everything else, Python tracing and lowering included
    # (trace_s, which JAX reports with nested spans counted twice)
    row = {"pass": True, "wall_s": round(wall, 2),
           "programs_compiled": after["compiles"] - before["compiles"],
           "compile_s": round(compile_s, 2),
           "run_s": round(wall - compile_s, 2),
           "trace_s": round(after["trace_s"] - before["trace_s"], 2),
           "cache_hits": after["cache_hits"] - before["cache_hits"],
           "cache_misses": after["cache_misses"] - before["cache_misses"],
           "facts": facts}
    print(f"[chip_smoke] {name}: pass in {row['wall_s']}s (compile "
          f"{row['compile_s']}s, run {row['run_s']}s; persistent cache "
          f"{row['cache_hits']} hit / {row['cache_misses']} miss)",
          flush=True)
    return row


def main():
    # both switches are gone from the program; an environment that still
    # sets one expects a path that hides the kernels
    for var in ("PADDLE_TPU_PALLAS_INTERPRET", "PADDLE_TPU_DISABLE_PALLAS"):
        if os.environ.get(var):
            print(f"chip_smoke: refusing to start with {var} set",
                  file=sys.stderr)
            return 2

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform="
              f"{dev.platform!r} (JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS')!r})", file=sys.stderr)
        return 2

    import jaxlib
    from importlib import metadata
    from paddle_tpu import compile_cache, native
    cache_dir = compile_cache.enable()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": metadata.version("libtpu")}
    print(f"[chip_smoke] platform={device['platform']} "
          f"device_kind={device['kind']!r} devices={device['count']} "
          f"jax={versions['jax']} jaxlib={versions['jaxlib']} "
          f"libtpu={versions['libtpu']} compile_cache={cache_dir}",
          flush=True)

    clock = CompileClock()
    legs = {}
    for name, fn in LEGS:
        if name == "four_chips" and device["count"] < 4:
            legs[name] = f"not run ({device['count']} device)"
            print(f"[chip_smoke] four_chips: {legs[name]}", flush=True)
            continue
        legs[name] = run_leg(name, fn, FULL, clock)

    # neither main path may depend on a native library that quietly fell
    # back to Python (paddle_tpu/native/__init__.py returns None for one)
    check(all(lib is not None for lib in native._cache.values()),
          f"a native library failed to load: {native._cache}")
    total = clock.snapshot()
    print(json.dumps({"summary": {
        "device": device, "versions": versions,
        "compile_cache_dir": cache_dir,
        "compile_s_total": round(total["compile_s"], 2),
        "cache_hits": total["cache_hits"],
        "cache_misses": total["cache_misses"],
        "native_libs_loaded": sorted(native._cache),
        "legs": legs, "claim": None}}), flush=True)
    # the result line: these keys and no others (the driver parses it)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
