"""Continuous-batching decode engine over the paged KV cache.

Iteration-level scheduling (Orca, OSDI '22) in the static-shape TPU
idiom: the engine owns a FIXED slot array of width `max_slots` and runs
decode in fixed `window`-token `lax.scan` dispatches — ONE compiled XLA
program for the life of the engine. Between windows (and only between
windows) the host retires finished slots and admits queued requests, so
batch composition churns freely while the device program never retraces.

Each admitted request is prefilled once (a dense causal forward over its
padded prompt bucket — one compile per bucket size), its prompt k/v is
scattered into freshly assigned pool blocks, and its slot joins the next
window. Inside the window scan every step runs the SAME transformer block
body as models/gpt_decode (`_block` is imported, not reimplemented) with a
merge hook that writes the new position into the paged pool and gathers
the dense per-slot cache view back (ops/paged_ops.py). That single-
implementation rule is why paged continuous-batched decode is bit-
identical per request to the dense single-request scan — pinned by
tests/test_serving.py.

Zero-copy contract: the pools are DONATED into the window/prompt-write
dispatches (donate_argnums), so the per-token cache update aliases in
place in HBM. serving/audit.py reads the compiled HLO and asserts no
pool-shaped copy op exists anywhere in the window program; the static
twin (serving/program.py) gets the same verdict from the PR-9
donation/alias analysis without compiling anything.

Subsystem composition:
* window fetches come back as lazy FetchHandles (framework/fetch.py) —
  materialization pays into the one executor.fetch_sync ledger and closes
  a per-window trace flow;
* `FLAGS_step_deadline_ms` bounds each window dispatch+drain (the SLA
  watchdog): a trip raises the typed DeadlineExceededError, flight-dumps
  (framework/executor._deadline_call), fails every in-flight request and
  marks the engine dead;
* every request is one trace flow (submit -> admit -> prefill ->
  first_token -> retire) and feeds the `serving.ttft_ms` /
  `serving.tpot_ms` histograms; windows are flight-recorder steps, so a
  crash dump shows the serving timeline like a training run's.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from ..flags import flag
from ..framework.fetch import FetchHandle
from ..models.gpt import GPTConfig
from ..models.gpt_decode import _attend, _block, _embed, _ln
from ..observability import flight as _flight
from ..observability import metrics as _metrics
from ..observability import trace as _trace
from ..ops.paged_ops import (SCRATCH_BLOCK, paged_attend, paged_update,
                             paged_attend_span, paged_update_span,
                             fused_attend, quantize_kv)
from ..resilience.faults import FaultInjected, fault_point
from .cache import CacheConfig, PagedKVCache, RadixPrefixCache
from .request import Completion, Request, RequestHandle, RequestState
from .resilience import Health, shed_handle
from .weights import dequant_params, prepare_params

_engine_ids = itertools.count(1)


@dataclasses.dataclass
class EngineConfig:
    """Serving geometry. Every field is STATIC for the engine's lifetime —
    the continuous-batching contract is that admission/retirement never
    changes a compiled shape. 0 means "take the flag default"
    (FLAGS_serving_window / FLAGS_serving_block_size)."""
    max_slots: int = 4
    block_size: int = 0
    num_blocks: int = 64
    max_len: int = 128          # per-request prompt + generation budget
    window: int = 0
    dtype: str = "float32"      # "float32" | "bfloat16" | "int8"
    max_queue: int = 0          # submit-queue bound (admission control);
                                # 0 = FLAGS_serving_max_queue
    kv_dtype: str = ""          # "" = compute dtype; "int8" = quantized
                                # KV pools (abs-max grid, static kv_scale)
    kv_scale: float = 8.0       # int8-KV abs-max clip range: cache values
                                # land on the 255-level [-kv_scale,
                                # kv_scale] grid
    # None = resolve from PADDLE_TPU_PALLAS_DECODE / FLAGS_pallas_decode
    # at engine build; True/False pin the attention read path explicitly
    decode_kernel: Optional[bool] = None
    # radix prefix cache (serving/cache.RadixPrefixCache): retired
    # requests publish their prompt block chains, admission maps the
    # longest cached prefix read-only and prefills only the suffix.
    # Bit-parity contract: cache-on tokens == cache-off (docs/serving.md
    # "Prefix caching"); incompatible with kv_dtype="int8" (quantize-on-
    # write pools re-read a cached prefix through dequant — different
    # bits than the f32 values the cold prefill attended with)
    prefix_cache: bool = False
    # speculative decoding (serving/spec.py): None/False = off; True =
    # default SpecConfig (int8 draft arm of the same checkpoint, gamma =
    # FLAGS_serving_spec_tokens); a SpecConfig instance pins the draft
    # explicitly. Spec-on output is bit-identical to spec-off by
    # construction (docs/serving.md "Speculative decoding")
    spec: Optional[object] = None
    # set by resolve(): the pre-rounding budget the caller asked for (the
    # max_position guard compares THIS, so re-resolving an already-rounded
    # config — engine clones — never trips it on rounding slack)
    requested_max_len: Optional[int] = None

    def resolve(self) -> "EngineConfig":
        c = dataclasses.replace(self)
        if c.requested_max_len is None:
            c.requested_max_len = c.max_len
        if not c.block_size:
            c.block_size = int(flag("FLAGS_serving_block_size"))
        if not c.window:
            c.window = int(flag("FLAGS_serving_window"))
        if not c.max_queue:
            c.max_queue = int(flag("FLAGS_serving_max_queue"))
        if c.max_len % c.block_size:
            c.max_len += c.block_size - c.max_len % c.block_size
        if c.kv_dtype not in ("", "int8"):
            raise ValueError(f"kv_dtype must be '' or 'int8', "
                             f"got {c.kv_dtype!r}")
        if c.prefix_cache and c.kv_dtype == "int8":
            raise ValueError(
                "prefix_cache requires float KV pools: int8 pools "
                "quantize on write, so a shared prefix would be re-read "
                "through dequant and break the cache-on == cache-off "
                "bit-parity contract")
        if c.decode_kernel is None:
            from ..ops.pallas.paged_attention import decode_kernel_enabled
            c.decode_kernel = decode_kernel_enabled()
        if c.spec is False:
            c.spec = None
        if c.spec is not None:
            from .spec import SpecConfig
            c.spec = (SpecConfig() if c.spec is True else c.spec).resolve()
        return c


class _Slot:
    __slots__ = ("handle", "pos", "gen", "token", "eos", "max_new",
                 "temp", "top_k", "seed")

    def __init__(self, handle, pos, gen, token, eos, max_new, temp,
                 top_k, seed):
        self.handle = handle
        self.pos = pos
        self.gen = gen
        self.token = token
        self.eos = eos
        self.max_new = max_new
        self.temp = temp
        self.top_k = top_k
        self.seed = seed


class DecodeEngine:
    """One decode worker: a slot array, a paged cache, compiled prefill /
    prompt-write / window programs, and the service thread interleaving
    admission with decode windows."""

    def __init__(self, params: Dict, model_config: GPTConfig,
                 config: Optional[EngineConfig] = None,
                 _prepared: Optional[tuple] = None,
                 _draft_prepared: Optional[tuple] = None, **overrides):
        import jax
        self.model_config = model_config
        if config is not None and overrides:
            raise ValueError("pass EngineConfig or overrides, not both")
        raw = config or EngineConfig(**overrides)
        # guard on the REQUESTED budget; resolve() then rounds max_len up
        # to a block multiple, which only widens the (masked) gather view
        # — real positions are additionally bounded by request_budget, so
        # the rounded width may legitimately exceed max_position
        requested = (raw.requested_max_len
                     if raw.requested_max_len is not None else raw.max_len)
        if requested > model_config.max_position:
            raise ValueError(
                f"max_len {requested} exceeds model max_position "
                f"{model_config.max_position}")
        cfg = raw.resolve()
        self.config = cfg
        # per-request prompt+generation ceiling: every live position must
        # have a real wpe row
        self.request_budget = min(cfg.max_len, model_config.max_position)
        if _prepared is not None:
            # replica path (frontend._clone_engine): adopt the source
            # engine's ALREADY-PREPARED device arrays verbatim — running
            # prepare_params again would stage a second weight copy in HBM
            # just to throw it away (one-weight-copy invariant, pinned by
            # tests/test_serving_resilience.py)
            self.params, self.scales, self.compute_dtype = _prepared
        else:
            self.params, self.scales, self.compute_dtype = prepare_params(
                params, cfg.dtype)
        self.cache = self._build_cache()
        # prompt buckets: block-aligned, doubling up to the bucket cap
        # (each bucket is one prefill compile; serving loops stay hot
        # because real prompt lengths collapse onto few buckets). The cap
        # is additionally bounded by the largest block multiple inside
        # max_position: a prefill over bucket positions reads wpe[0:bucket]
        # densely, so unlike the (masked) decode gather width the bucket
        # can never exceed the position table
        bs = cfg.block_size
        cap = min(cfg.max_len,
                  (model_config.max_position // bs) * bs)
        self.buckets = []
        b = bs
        while b < cap:
            self.buckets.append(b)
            b *= 2
        self.buckets.append(cap)

        self._id = next(_engine_ids)
        self._queue: "List[tuple]" = []
        self._admitting: Optional[tuple] = None   # popped, not yet slotted
        self._slots: Dict[int, _Slot] = {}
        self._cv = threading.Condition()
        self._thread: Optional[threading.Thread] = None
        self._stop = False
        self._dead: Optional[str] = None
        self._kill: Optional[str] = None
        self._draining = False
        self._windows = 0
        self._completed = 0
        self._window_ms_ewma: Optional[float] = None
        # health + failover (serving/resilience.py): a ServingFrontend
        # installs its failover sink here; standalone engines keep the
        # fail-hard semantics (sink is None)
        self.health = Health.LIVE
        self.health_history: List[str] = [Health.LIVE]
        self._failover = None
        self._prefill_jits: Dict[int, object] = {}
        self._write_jits: Dict[int, object] = {}
        # radix prefix cache: None when off. Chains reference pool blocks,
        # so the cache is rebuilt with the pool (resurrect/_build_cache).
        self.prefix_cache = (RadixPrefixCache(cfg.block_size)
                             if cfg.prefix_cache else None)
        self._suffix_jits: Dict[tuple, object] = {}
        self._prefix_hits = 0
        self._prefix_misses = 0
        self._prefill_tokens_saved = 0
        # max_blocks (the page-table walk bound) is STATIC: each distinct
        # hint is one compile, and the hint ladder is power-of-two
        # bucketed so the compile count is log(max_blocks)-bounded
        self._window_jit = jax.jit(self._window_fn, donate_argnums=(2, 3),
                                   static_argnums=(14,))
        # speculative-decoding verify programs, keyed (span, max_blocks):
        # span is gamma+1 (fixed per engine) and max_blocks rides the same
        # power-of-two hint ladder, so the compile-key count stays bounded
        self._verify_jits: Dict[tuple, object] = {}
        self.spec = None
        if cfg.spec is not None:
            from .spec import SpecDecoder
            self.spec = SpecDecoder(self, cfg.spec, raw_params=params,
                                    _draft_prepared=_draft_prepared)

    def _kv_scale(self) -> Optional[float]:
        """Static int8-KV dequant scale, None for float pools."""
        if self.config.kv_dtype == "int8":
            return float(self.config.kv_scale)
        return None

    # narrowest page table the bounded-walk hint ladder engages on
    _LADDER_MIN_BLOCKS = 16

    def _max_blocks_hint(self, horizon: int) -> int:
        """Static hint: the furthest page-table column any slot can touch
        over the next `horizon` positions. Both read paths honor it — the
        fused kernel bounds its grid, the fallback slices its gather — so
        short contexts never pay full-`max_len` cache traffic. Rounded up
        to a power of two (capped at the table width) to bound
        recompiles: each distinct hint is a new compile, so the ladder
        only engages past _LADDER_MIN_BLOCKS columns — below that the
        bounded walk saves less than one recompile costs and the engine
        always reads the full (still tiny) table with ONE compiled
        program."""
        cfg = self.config
        mb = cfg.max_len // cfg.block_size
        if mb <= self._LADDER_MIN_BLOCKS:
            return mb
        mx = max((s.pos for s in self._slots.values()), default=None)
        if mx is None:
            return mb
        need = (mx + horizon - 1) // cfg.block_size + 1
        hint = 1
        while hint < need:
            hint *= 2
        return min(mb, hint)

    def _window_max_blocks(self) -> int:
        return self._max_blocks_hint(self.config.window)

    def _build_cache(self) -> PagedKVCache:
        import jax.numpy as jnp
        mc, cfg = self.model_config, self.config
        nh = mc.num_heads
        pool_dtype = ("int8" if cfg.kv_dtype == "int8"
                      else str(jnp.dtype(self.compute_dtype)))
        return PagedKVCache(CacheConfig(
            num_layers=mc.num_layers, num_heads=nh,
            head_dim=mc.hidden_size // nh,
            block_size=cfg.block_size, num_blocks=cfg.num_blocks,
            max_blocks_per_slot=cfg.max_len // cfg.block_size,
            dtype=pool_dtype))

    def _set_health(self, state: str):
        if state != self.health:
            self.health = state
            self.health_history.append(state)
            del self.health_history[:-64]   # bounded: weeks of uptime
            _trace.instant("serving.health",
                           args={"engine": self._id, "state": state})

    # ------------------------------------------------------------------
    # compiled programs
    # ------------------------------------------------------------------
    def _model_params(self, payloads, scales):
        if self.scales is None:
            return payloads
        return dequant_params(payloads, scales,
                              compute_dtype=self.compute_dtype)

    @staticmethod
    def _sample_rows(logits, temps, top_ks, seeds, gen_idx):
        """Per-slot sampling, greedy when temp == 0. Top-k filtering and
        temperature scaling follow models/gpt_decode._sample exactly; the
        key schedule fold_in(PRNGKey(seed), generated_index) makes every
        token's draw a pure function of (request seed, token index) — the
        property that makes continuous batching bit-reproducible."""
        import jax
        import jax.numpy as jnp
        b, v = logits.shape
        greedy = jnp.argmax(logits, axis=-1)
        scaled = logits.astype(jnp.float32) / \
            jnp.maximum(temps, 1e-6)[:, None]
        srt = jnp.sort(scaled, axis=-1)
        kth = srt[jnp.arange(b), v - jnp.clip(top_ks, 1, v)][:, None]
        filtered = jnp.where(scaled < kth, -jnp.inf, scaled)
        use = jnp.where((top_ks > 0)[:, None], filtered, scaled)
        keys = jax.vmap(
            lambda s, g: jax.random.fold_in(jax.random.PRNGKey(s), g)
        )(seeds, gen_idx)
        sampled = jax.vmap(
            lambda k, l: jax.random.categorical(k, l))(keys, use)
        return jnp.where(temps > 0.0, sampled, greedy).astype(jnp.int32)

    def _window_fn(self, payloads, scales, k_pool, v_pool, page_table,
                   tokens, pos, gen, live, temps, top_ks, seeds, eos_vec,
                   max_new, max_blocks):
        """W decode steps over the slot array (ONE lax.scan). Frozen rows
        (retired/empty slots, eos/length-finished mid-window) keep
        computing — static shapes — but their writes are redirected to the
        scratch block and their emissions flagged inactive.

        `max_blocks` (STATIC, from _window_max_blocks) bounds the
        page-table walk to blocks any live slot can reach this window —
        both read paths are bit-identical at any sufficient hint. The
        attention read itself is an attend override handed to _block:
        the fused Pallas kernel (config.decode_kernel) or the bounded
        dense-gather oracle (ops/paged_ops.paged_attend)."""
        import jax
        import jax.numpy as jnp
        cfg = self.model_config
        p = self._model_params(payloads, scales)
        bs = self.config.block_size
        n_layers = cfg.num_layers
        kv_scale = self._kv_scale()
        attend = fused_attend if self.config.decode_kernel else paged_attend

        def step(carry, _):
            k_pool, v_pool, tokens, pos, gen, done = carry
            act = ~done
            x = p["wte"][tokens[:, None]] + p["wpe"][pos][:, None]
            pools = [k_pool, v_pool]
            for i in range(n_layers):
                def merge(k1, v1, _i=i):
                    pools[0], pools[1] = paged_update(
                        pools[0], pools[1], k1[:, :, 0, :], v1[:, :, 0, :],
                        page_table, pos, bs, _i, active=act,
                        kv_scale=kv_scale)
                    return lambda q: attend(
                        q, pools[0], pools[1], page_table, pos, bs,
                        layer=_i, max_blocks=max_blocks, kv_scale=kv_scale)
                x, _ = _block(x, p, i, cfg, None, merge)
            k_pool, v_pool = pools
            x = _ln(x, p["final_ln_scale"], p["final_ln_bias"])
            logits = jnp.einsum(
                "bsh,vh->bsv", x, p["wte"],
                preferred_element_type=jnp.float32)[:, 0]
            nxt = self._sample_rows(logits, temps, top_ks, seeds, gen)
            hit_eos = (eos_vec >= 0) & (nxt == eos_vec)
            gen2 = gen + act.astype(jnp.int32)
            done2 = done | (act & (hit_eos | (gen2 >= max_new)))
            tokens2 = jnp.where(act, nxt, tokens)
            pos2 = pos + act.astype(jnp.int32)
            return ((k_pool, v_pool, tokens2, pos2, gen2, done2),
                    (nxt, act))

        carry0 = (k_pool, v_pool, tokens, pos, gen, ~live)
        (k_pool, v_pool, *_), (toks, acts) = jax.lax.scan(
            step, carry0, None, length=self.config.window)
        return k_pool, v_pool, toks, acts

    def _verify_fn(self, span: int, max_blocks: int):
        """The speculative-decoding verify program (serving/spec.py): ONE
        batched forward scoring `span` = gamma+1 candidate positions per
        slot over the paged cache — pos..pos+span-1 hold the slot's
        current token followed by the draft's proposals. Converts gamma
        sequential bandwidth-bound window steps into one compute-shaped
        pass: the weights are read once for span tokens.

        Bit-parity with the window is BY CONSTRUCTION, not by luck:

        * the k/v writes are the unrolled per-position paged_update the
          window step uses (paged_update_span), quantizing/masking
          identically — invalid rows (a slot whose clamped draft run is
          shorter than span) land on the scratch block;
        * the attend is span per-position calls with the window's EXACT
          op shape — q [B, nh, 1, hd], mask <= pos+s — so every
          reduction runs at the same width and tree position as the
          window's at that step (paged_attend_span). Positions written
          beyond s carry exactly-zero softmax weight, the same argument
          that makes stale blocks bit-neutral;
        * row s samples with the window's sample rule at generated index
          gen+s — fold_in(PRNGKey(seed), gen+s) — so the target token at
          every candidate position is the token spec-off decode would
          emit there, for greedy AND seeded top-k.

        The device also computes the per-slot accepted count: the length
        of the longest prefix where the draft's candidate equals the
        target's deterministic token. The round then emits v_0..v_A —
        accepted agreements plus the target's own correction/bonus token
        — which is exactly the spec-off stream. Pools are donated; the
        census (serving/audit.py verify_copy_census) pins zero
        pool-shaped copies on this program like the window."""
        import jax
        import jax.numpy as jnp
        cfg = self.model_config
        bs = self.config.block_size
        n_layers = cfg.num_layers
        kv_scale = self._kv_scale()
        use_kernel = bool(self.config.decode_kernel)

        def run(payloads, scales, k_pool, v_pool, page_table, cand, pos,
                live, valid, gen, temps, top_ks, seeds):
            p = self._model_params(payloads, scales)
            offs = jnp.arange(span, dtype=jnp.int32)
            # the window's embedding op family (row gathers); invalid
            # rows' wpe indices clamp in-bounds under jnp gather rules
            # and their outputs are ignored host-side
            x = p["wte"][cand] + p["wpe"][pos[:, None] + offs[None, :]]
            pools = [k_pool, v_pool]
            for i in range(n_layers):
                def merge(k1, v1, _i=i):
                    pools[0], pools[1] = paged_update_span(
                        pools[0], pools[1], k1, v1, page_table, pos, bs,
                        _i, active=live, valid=valid, kv_scale=kv_scale)
                    return lambda q: paged_attend_span(
                        q, pools[0], pools[1], page_table, pos, bs,
                        layer=_i, max_blocks=max_blocks,
                        kv_scale=kv_scale, use_kernel=use_kernel)
                x, _ = _block(x, p, i, cfg, None, merge)
            k_pool, v_pool = pools
            x = _ln(x, p["final_ln_scale"], p["final_ln_bias"])
            logits = jnp.einsum("bsh,vh->bsv", x, p["wte"],
                                preferred_element_type=jnp.float32)
            vtok = jnp.stack(
                [self._sample_rows(logits[:, s], temps, top_ks, seeds,
                                   gen + s) for s in range(span)], axis=1)
            agree = (cand[:, 1:] == vtok[:, :-1]) & valid[:, 1:]
            n_acc = jnp.sum(jnp.cumprod(agree.astype(jnp.int32), axis=1),
                            axis=1)
            return k_pool, v_pool, vtok, n_acc
        return jax.jit(run, donate_argnums=(2, 3))

    def _verify_jit_for(self, span: int, max_blocks: int):
        key = (span, max_blocks)
        fn = self._verify_jits.get(key)
        if fn is None:
            fn = self._verify_jits[key] = self._verify_fn(span, max_blocks)
        return fn

    def _prefill_fn(self, bucket: int):
        """Dense causal forward over one padded prompt bucket -> per-layer
        prompt k/v (pad positions zeroed) + the first sampled token. Same
        block body as the window, so prefill-produced cache values are
        bit-identical to what models/gpt_decode.prefill would hold."""
        import jax
        import jax.numpy as jnp
        cfg = self.model_config

        def run(payloads, scales, prompt, prompt_len, temp, top_k, seed):
            p = self._model_params(payloads, scales)
            x = _embed(p, prompt[None], 0)            # [1, bucket, H]
            qpos = jnp.arange(bucket)[:, None]
            kpos = jnp.arange(bucket)[None, :]
            causal = jnp.where(qpos >= kpos, 0.0,
                               -jnp.inf).astype(jnp.float32)
            keep = (jnp.arange(bucket) < prompt_len)[None, None, :, None]
            ks, vs = [], []
            for i in range(cfg.num_layers):
                x, (k, v) = _block(x, p, i, cfg, causal)
                ks.append(jnp.where(keep, k, 0.0).astype(k.dtype))
                vs.append(jnp.where(keep, v, 0.0).astype(v.dtype))
            k_seq = jnp.stack(ks)[:, 0]               # [L, nh, bucket, hd]
            v_seq = jnp.stack(vs)[:, 0]
            x = _ln(x, p["final_ln_scale"], p["final_ln_bias"])
            x_last = jax.lax.dynamic_slice_in_dim(x, prompt_len - 1, 1,
                                                  axis=1)
            logits = jnp.einsum(
                "bsh,vh->bsv", x_last, p["wte"],
                preferred_element_type=jnp.float32)[:, 0]   # [1, V]
            first = self._sample_rows(
                logits, temp[None], top_k[None], seed[None],
                jnp.zeros((1,), jnp.int32))
            return k_seq, v_seq, first[0]
        return jax.jit(run)

    def _write_fn(self, n_blocks: int):
        """Scatter one prefilled prompt's k/v into its assigned blocks
        (pools donated: the write aliases in place)."""
        import jax

        def run(k_pool, v_pool, k_seq, v_seq, blocks):
            nh = self.cache.config.num_heads
            bs = self.config.block_size
            hd = self.cache.config.head_dim
            L = self.model_config.num_layers
            kb = k_seq.reshape(L, nh, n_blocks, bs, hd) \
                .transpose(0, 2, 1, 3, 4)
            vb = v_seq.reshape(L, nh, n_blocks, bs, hd) \
                .transpose(0, 2, 1, 3, 4)
            kv = self._kv_scale()
            if kv is not None:
                kb, vb = quantize_kv(kb, kv), quantize_kv(vb, kv)
            k_pool = k_pool.at[:, blocks].set(kb.astype(k_pool.dtype))
            v_pool = v_pool.at[:, blocks].set(vb.astype(v_pool.dtype))
            return k_pool, v_pool
        return jax.jit(run, donate_argnums=(0, 1))

    def _suffix_prefill_fn(self, p_pad: int, sbucket: int,
                           width: Optional[int] = None):
        """Causal forward over ONLY the uncovered suffix of a prefix-
        cache hit: the matched prefix's k/v is GATHERED from the shared
        pool blocks instead of recomputed, the suffix's k/v is scattered
        into the slot's chain positions, and the first token is sampled
        from the last real suffix row — one jit per (padded prefix
        width, suffix bucket, attention width), pools donated.

        Bit-parity with the cold prefill needs TWO invariants:

        * position-indexed layout — column j of the merged attention
          k/v IS absolute position j (prefix gather at cols < m, suffix
          dynamically placed at offset m), so every real key sits at
          the index the cold prefill puts it at and carries the same
          bits (the pool write is a dtype-preserving astype);
        * exact COLD attention width — `width` is pinned to the cold
          prompt bucket, bucket(plen), NOT the natural buffer width
          p_pad*bs + sbucket. Reduction grouping is width-dependent in
          low precision: softmax sums and the attn@V contraction at a
          different width round differently (one bf16 ulp is enough to
          flip an argmax knife-edge tokens later), so end-padding is
          only bit-neutral at the SAME width. With the width equal,
          masked columns contribute exact zeros at identical tree
          positions in both programs and every reduction is
          bit-identical.

        Copy-on-write: the partially-filled tail block's rows are
        copied bit-exactly out of the prefix GATHER into the slot's
        private block as part of the suffix scatter itself, so shared
        blocks are never written AND the donated pool stays a single
        gather-then-scatter chain. (A separate block-copy write before
        the gathers' consumers would interleave a pool write inside the
        pool reads' live range — XLA then abandons the donation alias
        and re-copies the whole pool, which serving/audit.py's suffix
        census would flag.)"""
        import jax
        import jax.numpy as jnp
        cfg = self.model_config
        bs = self.config.block_size
        nh = cfg.num_heads
        hd = cfg.hidden_size // nh
        W_buf = p_pad * bs + sbucket    # merged-buffer width (>= plen)
        W = W_buf if width is None else width
        scale = 1.0 / math.sqrt(hd)

        def run(payloads, scales, k_pool, v_pool, prefix_blocks, m,
                suffix, suffix_len, slot_row, cow_dst, temp,
                top_k, seed):
            p = self._model_params(payloads, scales)
            # ONE gather per pool for all layers' prefix k/v, read from
            # the pre-write pool (the CoW copy below never touches a
            # prefix block, so gathering first is value-identical and
            # keeps the donated pool a single read-then-write chain —
            # scattering per-layer gathers around the writes costs the
            # donation alias and re-copies the whole pool)
            L = cfg.num_layers
            kp_all = k_pool[:, prefix_blocks].transpose(0, 2, 1, 3, 4) \
                .reshape(L, nh, p_pad * bs, hd)
            vp_all = v_pool[:, prefix_blocks].transpose(0, 2, 1, 3, 4) \
                .reshape(L, nh, p_pad * bs, hd)
            # positions via the SAME op shape cold prefill's _embed
            # uses — dynamic_slice of the wpe table. Under XLA's
            # default excess-precision rules the bf16 embedding add may
            # be kept in f32 where it fuses into the first LayerNorm,
            # and whether that rounding is elided follows the
            # surrounding op pattern: an explicit wpe ROW GATHER here
            # fused differently from _embed's dynamic_slice and shifted
            # every suffix activation by one bf16 ulp, silently
            # breaking cache-on/cache-off bit-parity at low precision.
            # The table is extended by sbucket zero rows so the traced
            # start never clamps near the table end (pad rows past the
            # real suffix are masked out and never scattered).
            wpe_ext = jnp.concatenate(
                [p["wpe"],
                 jnp.zeros((sbucket, cfg.hidden_size), p["wpe"].dtype)],
                axis=0)
            pos = jax.lax.dynamic_slice_in_dim(wpe_ext, m, sbucket, 0)
            x = p["wte"][suffix[None]] + pos[None]
            cols = jnp.arange(W)
            qpos = m + jnp.arange(sbucket)
            mask = jnp.where(cols[None, :] <= qpos[:, None], 0.0,
                             -jnp.inf).astype(jnp.float32)
            ks, vs = [], []
            for i in range(cfg.num_layers):
                def merge(k1, v1, _i=i):
                    kp = kp_all[_i][None]           # [1, nh, P*bs, hd]
                    vp = vp_all[_i][None]

                    def ctx(q):
                        pad = jnp.zeros((1, nh, sbucket, hd), k1.dtype)
                        k_all = jax.lax.dynamic_update_slice_in_dim(
                            jnp.concatenate([kp, pad], axis=2), k1, m,
                            axis=2)
                        v_all = jax.lax.dynamic_update_slice_in_dim(
                            jnp.concatenate([vp, pad], axis=2), v1, m,
                            axis=2)
                        # resize to the COLD bucket width W: real cols
                        # (< plen <= W) always survive; width-changing
                        # pad/slice only touches masked columns
                        if W_buf > W:
                            k_all = jax.lax.slice_in_dim(k_all, 0, W,
                                                         axis=2)
                            v_all = jax.lax.slice_in_dim(v_all, 0, W,
                                                         axis=2)
                        elif W_buf < W:
                            wpad = jnp.zeros((1, nh, W - W_buf, hd),
                                             k1.dtype)
                            k_all = jnp.concatenate([k_all, wpad],
                                                    axis=2)
                            v_all = jnp.concatenate([v_all, wpad],
                                                    axis=2)
                        return _attend(q, k_all, v_all, mask, scale)
                    return ctx
                x, (k1, v1) = _block(x, p, i, cfg, None, merge)
                ks.append(k1)
                vs.append(v1)
            x = _ln(x, p["final_ln_scale"], p["final_ln_bias"])
            x_last = jax.lax.dynamic_slice_in_dim(x, suffix_len - 1, 1,
                                                  axis=1)
            logits = jnp.einsum(
                "bsh,vh->bsv", x_last, p["wte"],
                preferred_element_type=jnp.float32)[:, 0]   # [1, V]
            first = self._sample_rows(
                logits, temp[None], top_k[None], seed[None],
                jnp.zeros((1,), jnp.int32))
            # ONE block-granular scatter per pool — the _write_fn idiom.
            # A per-(block, offset) element scatter here serializes on
            # CPU (every scattered row is a separate [nh, hd] update)
            # and cost more than the whole suffix forward; indexing
            # whole blocks keeps each update slice a contiguous
            # [nh, bs, hd] run. The written span is the n_w blocks
            # from the tail block onward: per layer, a position-indexed
            # buffer starts with the tail block's CoW rows lifted
            # bit-exact from the prefix gather, then the suffix k/v is
            # dynamically placed at its in-block offset (suffix rows
            # overwrite the gather's garbage tail, CoW rows < m % bs
            # survive in front). Blocks with no real row are redirected
            # to the scratch block; rows past the real suffix inside a
            # written block carry pad-token k/v exactly like the cold
            # write's bucket padding (never read: decode masks by pos).
            n_w = (bs - 1 + sbucket + bs - 1) // bs
            span = n_w * bs
            nf = m // bs
            nfbs = nf * bs             # tail block's gather column base
            wq = nf + jnp.arange(n_w)
            covers = wq * bs < m + suffix_len
            wblocks = jnp.where(
                covers,
                slot_row[jnp.clip(wq, 0, slot_row.shape[0] - 1)],
                SCRATCH_BLOCK)
            off0 = m - nfbs            # suffix offset in the tail block
            kw, vw = [], []
            for i in range(cfg.num_layers):
                cow_k = jax.lax.dynamic_slice(
                    kp_all[i], (0, nfbs, 0), (nh, bs, hd))
                cow_v = jax.lax.dynamic_slice(
                    vp_all[i], (0, nfbs, 0), (nh, bs, hd))
                zpad = jnp.zeros((nh, span - bs, hd), cow_k.dtype)
                kbuf = jax.lax.dynamic_update_slice_in_dim(
                    jnp.concatenate([cow_k, zpad], axis=1),
                    ks[i][0].astype(cow_k.dtype), off0, axis=1)
                vbuf = jax.lax.dynamic_update_slice_in_dim(
                    jnp.concatenate([cow_v, zpad], axis=1),
                    vs[i][0].astype(cow_v.dtype), off0, axis=1)
                kw.append(kbuf)
                vw.append(vbuf)
            kb = jnp.stack(kw).reshape(L, nh, n_w, bs, hd) \
                .transpose(0, 2, 1, 3, 4)
            vb = jnp.stack(vw).reshape(L, nh, n_w, bs, hd) \
                .transpose(0, 2, 1, 3, 4)
            k_pool = k_pool.at[:, wblocks].set(kb.astype(k_pool.dtype))
            v_pool = v_pool.at[:, wblocks].set(vb.astype(v_pool.dtype))
            return k_pool, v_pool, first[0]
        return jax.jit(run, donate_argnums=(2, 3))

    # ------------------------------------------------------------------
    # submission API
    # ------------------------------------------------------------------
    def submit(self, request: Request, _handle: Optional[RequestHandle]
               = None, _failover: bool = False, _probe: bool = False,
               bounded: bool = True) -> Optional[RequestHandle]:
        """Admit or reject a request. The shed reasons (docs/serving.md
        "Failure semantics") is typed: overload rejections finish the
        handle with `shed:<reason>` (result() raises ShedError) and count
        `serving.shed_total` + `serving.shed.<reason>`.

        `bounded=False` skips the OVERLOAD sheds (queue_full /
        deadline_unmeetable) while keeping validation and funding checks:
        batch-style callers (`generate`, the C-API decode session) submit
        a known, finite workload all at once and rely on FCFS queueing —
        admission control is for open-ended online traffic.

        `_failover=True` is the resilience re-dispatch path: the handle
        is mid-flight work already admitted elsewhere, so admission
        control is bypassed — a dead/draining engine returns None (handle
        untouched) and the caller tries the next replica. `_probe=True`
        (the frontend's routing path) likewise returns None on a
        dead/draining engine instead of minting a shed handle, so a
        routing retry that succeeds elsewhere does not pollute the shed
        counters."""
        if _failover:
            if self._dead is not None or self._draining or self._stop:
                return None
            with self._cv:
                entry = (request, _handle)
                self._queue.append(entry)
                _metrics.set_gauge("serving.queue_depth", len(self._queue))
                self._ensure_thread()
                self._cv.notify_all()
            if (self._dead is not None or self._draining or self._stop) \
                    and self._unqueue(entry):
                return None     # died/drained between check and append
            return _handle
        if _probe and (self._dead is not None or self._draining
                       or self._stop):
            return None
        fid = _trace.new_flow()
        handle = RequestHandle(request, flow_id=fid)
        if self._dead:
            return self._shed(handle, "engine_dead",
                              f"engine dead: {self._dead}")
        if self._draining:
            return self._shed(handle, "draining", "engine draining")
        reason = self._reject_reason(request)
        if reason is not None:
            handle._finish(RequestState.REJECTED, reason)
            return handle
        # a budget the pool could NEVER fund must shed now, not park at
        # the FCFS head forever wedging every request behind it
        plen = int(request.prompt.shape[0])
        usable = self.config.num_blocks - 1
        need = self._block_budget(plen, request.max_new_tokens)
        if need > usable:
            return self._shed(
                handle, "unfundable",
                f"request needs {need} cache blocks but the pool has "
                f"only {usable} (num_blocks={self.config.num_blocks} "
                "incl. scratch)")
        if bounded:
            with self._cv:
                depth = len(self._queue)
            if depth >= self.config.max_queue:
                return self._shed(
                    handle, "queue_full",
                    f"submit queue at its bound "
                    f"({self.config.max_queue})")
            if request.deadline_ms is not None:
                est = self.queue_wait_estimate_ms()
                if est > request.deadline_ms:
                    return self._shed(
                        handle, "deadline_unmeetable",
                        f"estimated queue wait {est:.0f} ms exceeds "
                        f"request deadline {request.deadline_ms:.0f} ms")
        try:
            fault_point("serving.admit")
        except FaultInjected as e:
            return self._shed(handle, "admit_fault", repr(e))
        _trace.flow_start("serving.request", fid,
                          args={"uid": request.uid})
        with self._cv:
            entry = (request, handle)
            self._queue.append(entry)
            _metrics.set_gauge("serving.queue_depth", len(self._queue))
            self._ensure_thread()
            self._cv.notify_all()
        if (self._dead is not None or self._draining or self._stop) \
                and self._unqueue(entry):
            # the engine died/drained/stopped between the liveness checks
            # and the append: the fail/drain snapshot missed this entry,
            # so it would strand unfinished in a dead queue. A _probe
            # caller (frontend routing) gets None so it retries a healthy
            # sibling; a direct caller gets the typed shed
            if _probe:
                return None
            reason = "engine_dead" if self._dead is not None \
                else "draining"
            return self._shed(handle, reason,
                              f"engine {reason.replace('_', ' ')} during "
                              f"submit: {self._dead or 'draining'}")
        return handle

    def _unqueue(self, entry) -> bool:
        """Remove a just-appended queue entry if it is still there (False
        means the service/fail path already claimed it). Matches by
        IDENTITY: `list.remove` would `==`-compare earlier entries, and
        Request carries an ndarray whose ambiguous truth value raises."""
        with self._cv:
            for i, e in enumerate(self._queue):
                if e is entry:
                    del self._queue[i]
                    _metrics.set_gauge("serving.queue_depth",
                                       len(self._queue))
                    return True
            return False

    def _shed(self, handle: RequestHandle, reason: str,
              detail: str) -> RequestHandle:
        return shed_handle(handle, reason, detail)

    def _block_budget(self, plen: int, max_new: int) -> int:
        bs = self.config.block_size
        return max(self._bucket_for(plen) // bs, -(-(plen + max_new) // bs))

    def _reject_reason(self, req: Request) -> Optional[str]:
        """Validation-only rejects (malformed requests); capacity-driven
        rejections go through the shed reasons instead."""
        plen = int(req.prompt.shape[0])
        if plen < 1:
            return "empty prompt"
        if req.max_new_tokens < 1:
            return "max_new_tokens must be >= 1"
        if req.temperature < 0.0:
            return f"temperature must be >= 0, got {req.temperature}"
        if req.top_k < 0:
            return f"top_k must be >= 0, got {req.top_k}"
        if plen + req.max_new_tokens > self.request_budget:
            return (f"prompt {plen} + {req.max_new_tokens} new exceeds "
                    f"engine budget {self.request_budget} "
                    f"(max_len/max_position)")
        if plen > self.buckets[-1]:
            return (f"prompt {plen} exceeds the largest prefill bucket "
                    f"{self.buckets[-1]} (block-aligned max_position)")
        return None

    def load(self) -> int:
        """Pending decode tokens (queued + in-flight remaining): the
        least-loaded routing key and the queue-wait estimator's input."""
        with self._cv:
            queued = sum(r.max_new_tokens for r, _ in self._queue)
            active = sum(max(s.max_new - s.gen, 0)
                         for s in self._slots.values())
        return queued + active

    def queue_full(self) -> bool:
        """Whether a submit right now would shed queue_full — the routing
        hint that lets the frontend prefer a replica with queue room over
        a token-lighter one that would reject (load is token-weighted,
        the queue bound is entry-counted; they can disagree)."""
        with self._cv:
            return len(self._queue) >= self.config.max_queue

    def queue_wait_estimate_ms(self) -> float:
        """Deadline-aware admission: pending tokens over the window
        throughput, scaled by the measured window wall time (EWMA). 0.0
        until the first window lands (no basis to shed on)."""
        ewma = self._window_ms_ewma
        if not ewma:
            return 0.0
        per_window = max(self.config.window * self.config.max_slots, 1)
        return self.load() / per_window * ewma

    def generate(self, requests: List[Request],
                 timeout: float = 300.0) -> List[Completion]:
        """Continuous-batched: submit everything, wait for everything.
        Batch-style (`bounded=False`): a finite known workload queues
        FCFS past the online admission bounds."""
        handles = [self.submit(r, bounded=False) for r in requests]
        return [h.result(timeout=timeout, raise_on_error=False)
                for h in handles]

    def generate_sequential(self, requests: List[Request],
                            timeout: float = 300.0) -> List[Completion]:
        """The parity baseline: one request at a time, each fully retired
        before the next is submitted — same compiled programs, batch of
        one live slot."""
        return [self.submit(r, bounded=False).result(
                    timeout=timeout, raise_on_error=False)
                for r in requests]

    # ------------------------------------------------------------------
    # service loop
    # ------------------------------------------------------------------
    def _ensure_thread(self):
        if self._draining:
            return      # a drain-racing submit must not revive the
                        # service thread (its entry is unqueued + shed)
        if self._thread is None or not self._thread.is_alive():
            self._stop = False
            self._thread = threading.Thread(
                target=self._service_loop, daemon=True,
                name=f"serving-engine-{self._id}")
            self._thread.start()

    def start(self):
        with self._cv:
            self._ensure_thread()
        return self

    def stop(self, join_timeout_s: float = 60.0):
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        t = self._thread
        if t is not None and t.is_alive() \
                and t is not threading.current_thread():
            t.join(timeout=join_timeout_s)
        if self._queue or self._slots:
            # stop() abandons in-flight work: their callers must get a
            # terminal FAILED completion, never block forever
            self._fail_all("engine stopped")
        if self.prefix_cache is not None:
            # drop the cache-owned chain references so the shared-block
            # gauge returns to zero before the allocator retires
            self.prefix_cache.clear(self.cache.allocator)
        if self.spec is not None:
            self.spec.close()   # retire the draft arm's pool too
        self.cache.close()   # retire this pool from the process gauges

    def __enter__(self):
        return self.start()

    def __exit__(self, *a):
        self.stop()
        return False

    def _service_loop(self):
        while True:
            with self._cv:
                # proceed when there are slots to decode, queue to admit
                # (unless draining — a draining engine only runs its
                # in-flight slots down), or a kill request to honor
                while (not self._stop and self._kill is None
                       and not self._slots
                       and (self._draining or not self._queue)):
                    self._cv.wait(0.05)
                if self._stop:
                    break
            if self._kill is not None:
                # an external kill() lands HERE, between windows — the
                # same boundary a real window fault dies at, so slot
                # bookkeeping (emitted-token counts the failover replay
                # skip relies on) is never snapshotted mid-window
                self._fail_all(self._kill)
                break
            try:
                self._admit()
                if self._slots:
                    # speculative rounds replace plain windows while the
                    # draft arm is healthy; a dead/suspect draft degrades
                    # to plain decode (zero failed requests — spec-on is
                    # bit-identical to spec-off, so the stream just
                    # continues at one token per step)
                    if self.spec is not None and self.spec.armed:
                        self.spec.run_round()
                    else:
                        self._run_window()
            except BaseException as e:  # noqa: BLE001 — fail requests, die
                self._fail_all(repr(e))
                break

    def kill(self, why: str):
        """Kill the engine from ANY thread (tests, bench chaos arms, an
        operator). If the service thread is running, death is deferred to
        the next window boundary so it can never race the in-flight
        window's slot accounting; otherwise it is immediate."""
        with self._cv:
            t = self._thread
            if (t is not None and t.is_alive()
                    and t is not threading.current_thread()):
                self._kill = why
                self._cv.notify_all()
                return
        self._fail_all(why)

    def _fail_all(self, why: str):
        """The engine is dead. With a frontend failover sink installed the
        in-flight work is SNAPSHOTTED (request + handle carrying the
        tokens streamed so far) and handed over for re-dispatch — the
        deterministic decode contract makes the replay bit-identical;
        without one (standalone engine) every request fails typed."""
        self._dead = why
        # self-report SUSPECT when a frontend is watching (it confirms
        # DEAD on its next health tick); standalone engines go straight
        # to DEAD — nobody will resurrect them
        self._set_health(Health.SUSPECT if self._failover is not None
                         else Health.DEAD)
        _metrics.inc("serving.engine_failures")
        with self._cv:
            pending = list(self._queue)
            self._queue.clear()
            slots = dict(self._slots)
            self._slots.clear()
            _metrics.set_gauge("serving.queue_depth", 0)
        for idx in slots:
            self.cache.release(idx)
        if self.spec is not None:
            self.spec.release_all()
        victims = [(req, handle) for req, handle in pending]
        victims += [(slot.handle.request, slot.handle)
                    for slot in slots.values()]
        if self._failover is not None:
            self._failover(self, victims, why)
            return
        for _, handle in victims:
            handle._finish(RequestState.FAILED, "engine failed", error=why)

    # ---- drain + resurrection -------------------------------------------
    def drain(self, timeout_s: Optional[float] = None) -> List[tuple]:
        """Graceful drain: stop admitting, finish the in-flight slots,
        hand back the NEVER-SERVED queue as [(Request, RequestHandle)].
        Handed-back handles finish `shed:draining` (their callers stop
        waiting); the Requests are the caller's to re-route. A queued
        failover victim that already streamed tokens is NOT handed back —
        it fails typed (RequestFailedError) instead, because "shed" and
        "re-routable" both promise the request was never served. Stops
        the engine afterwards; `timeout_s` bounds the WHOLE call,
        including the service-thread join, so a wedged window cannot
        push a SIGTERM drain past the supervisor's grace."""
        if timeout_s is None:
            timeout_s = float(flag("FLAGS_serving_drain_timeout_ms")) \
                / 1000.0
        with self._cv:
            self._draining = True
            self._cv.notify_all()
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with self._cv:
                # a request mid-admission (popped, not yet slotted) is
                # in-flight work too: drain must wait for its prefill to
                # land and its slot to decode out, not stop() under it
                busy = bool(self._slots) or self._admitting is not None
            t = self._thread
            if (not busy or self._dead is not None
                    or t is None or not t.is_alive()):
                break
            time.sleep(0.01)
        with self._cv:
            queued = list(self._queue)
            self._queue.clear()
            _metrics.set_gauge("serving.queue_depth", 0)
        unstarted = []
        for req, handle in queued:
            if handle.tokens_so_far():
                handle._finish(
                    RequestState.FAILED, "drained mid-failover",
                    error="engine drained while the request awaited its "
                          "failover re-decode (tokens already streamed)")
            else:
                unstarted.append((req, handle))
                self._shed(handle, "draining", "engine drained")
        self.stop(join_timeout_s=max(deadline - time.monotonic(), 0.2))
        return unstarted

    def resurrect(self) -> "DecodeEngine":
        """Rebuild the dead engine's cache pool against the SHARED weight
        arrays and clear its death. The window/prefill jits survive (same
        shapes — no recompile); the pools do not (they were donated into
        the dispatch that died), so a fresh PagedKVCache replaces them.
        The caller (ServingFrontend health loop) gates rejoin on a canary
        decode."""
        self._set_health(Health.RESURRECTING)
        _metrics.inc("serving.resurrections")
        self.cache.close()
        self.cache = self._build_cache()
        if self.prefix_cache is not None:
            # cached chains pointed into the pool that died with the
            # failed dispatch — start cold (the suffix jits survive:
            # same shapes, no recompile)
            self.prefix_cache = RadixPrefixCache(self.config.block_size)
        if self.spec is not None:
            # the draft arm's pool was dispatched alongside the target's:
            # rebuild it and re-arm speculation — the caller's canary
            # gate then validates the WHOLE spec-on path before rejoin
            self.spec.reset()
        with self._cv:
            self._queue.clear()
            self._slots.clear()
            self._admitting = None
        self._dead = None
        self._kill = None
        self._draining = False
        self._stop = False
        return self

    # ---- admission -------------------------------------------------------
    def _bucket_for(self, plen: int) -> int:
        for b in self.buckets:
            if b >= plen:
                return b
        return self.buckets[-1]

    def _assign_evicting(self, slot_idx: int,
                         n_blocks: int) -> Optional[List[int]]:
        """cache.assign with one eviction retry: idle refcount-1 prefix
        chains are reclaimable pool space, so admission pressure evicts
        them LRU-first before giving up and parking the FCFS head."""
        blocks = self.cache.assign(slot_idx, n_blocks)
        if blocks is None and self.prefix_cache is not None:
            need = n_blocks - self.cache.allocator.free_blocks
            if self.prefix_cache.evict(self.cache.allocator, need) > 0:
                blocks = self.cache.assign(slot_idx, n_blocks)
        return blocks

    def _fund(self, slot_idx: int, req: Request, plen: int):
        """Fund the head request's blocks, all-or-nothing. Returns
        (blocks, matched_prefix_tokens, cow_src_block | None), or None
        if the pool cannot fund it (the request stays queued, FCFS).

        Cold path: the full budget from the free list — the SAME
        `_block_budget` formula as submit's unfundable shed (the two must
        agree or never-fundable heads wedge the FCFS queue; the shed
        check stays on the conservative cold formula because a cache hit
        is not guaranteed at admission time). Prefix path: look up the
        longest cached prefix, pin the whole chain, map its full blocks
        read-only into the slot row (assign_with_prefix takes the row's
        own references) and fund only the uncovered chain suffix. A
        partially-filled tail block stays OUT of the row — the suffix
        prefill copies it into the slot's first private block before any
        write (copy-on-write) — and remains pinned until the prefill
        lands (_prefill_into releases it). Either path may evict LRU
        refcount-1 chains to find room; the pin is what keeps the
        eviction retry from recycling the very chain just matched."""
        bs = self.config.block_size
        n_cold = self._block_budget(plen, req.max_new_tokens)
        if self.prefix_cache is None:
            blocks = self.cache.assign(slot_idx, n_cold)
            return None if blocks is None else (blocks, 0, None)
        alloc = self.cache.allocator
        chain, matched = self.prefix_cache.lookup(req.prompt)
        if not matched:
            blocks = self._assign_evicting(slot_idx, n_cold)
            return None if blocks is None else (blocks, 0, None)
        alloc.share(chain)                       # pin across eviction
        nf = matched // bs
        shared = chain[:nf]
        cow_src = chain[-1] if matched % bs else None
        n_chain = -(-(plen + req.max_new_tokens) // bs)
        n_private = n_chain - nf                 # >= 1: matched < plen
        private = self.cache.assign_with_prefix(slot_idx, shared,
                                                n_private)
        if private is None:
            self.prefix_cache.evict(alloc,
                                    n_private - alloc.free_blocks)
            private = self.cache.assign_with_prefix(slot_idx, shared,
                                                    n_private)
        if private is None:
            alloc.free(chain)                    # unpin, stay queued
            return None
        alloc.free(shared)   # row holds its own refs; keep cow_src pinned
        return self.cache.blocks_of(slot_idx), matched, cow_src

    def _admit(self):
        while True:
            with self._cv:
                if not self._queue or self._draining:
                    return
                entry = self._queue[0]
                req, handle = entry
            free = [i for i in range(self.config.max_slots)
                    if i not in self._slots]
            if not free:
                return
            plen = int(req.prompt.shape[0])
            bucket = self._bucket_for(plen)
            slot_idx = free[0]
            funding = self._fund(slot_idx, req, plen)
            if funding is None:
                # pool cannot fund the head request (even after evicting
                # idle prefix chains): FCFS — wait for a retirement to
                # free blocks rather than starving big requests behind
                # small ones
                return
            blocks, matched, cow_src = funding
            with self._cv:
                # re-verify the head: a concurrent drain()/stop() may
                # have cleared the queue (and claimed the entry) while
                # the lock was released for the funding work — popping
                # blind would IndexError and spuriously kill the engine
                # in the middle of a graceful drain
                if not self._queue or self._queue[0] is not entry:
                    head_claimed = True
                else:
                    head_claimed = False
                    self._queue.pop(0)
                    # visible to drain()'s busy check while the entry is
                    # neither queued nor slotted (the whole prefill)
                    self._admitting = entry
                    _metrics.set_gauge("serving.queue_depth",
                                       len(self._queue))
            if head_claimed:
                self.cache.release(slot_idx)
                if cow_src is not None:
                    self.cache.allocator.free([cow_src])   # drop the pin
                return
            if self.prefix_cache is not None:
                if matched:
                    self._prefix_hits += 1
                    self._prefill_tokens_saved += matched
                    _metrics.inc("serving.prefix_cache.hits")
                    _metrics.inc("serving.prefill_tokens_saved", matched)
                else:
                    self._prefix_misses += 1
                    _metrics.inc("serving.prefix_cache.misses")
            if handle.failovers == 0:    # re-dispatches would skew it
                _metrics.observe(
                    "serving.queue_wait_ms",
                    (time.perf_counter() - handle.t_submit) * 1000.0)
            try:
                self._prefill_into(slot_idx, blocks, req, handle, plen,
                                   bucket, matched, cow_src)
            except Exception as e:  # noqa: BLE001 — isolate to the request
                # a per-request admission failure (bad prompt content, a
                # transient compile error) fails THAT request, not the
                # engine and everything in flight; a failure inside a
                # WINDOW still escalates (shared pool state is suspect).
                # With a failover sink installed the victim is re-
                # dispatched (bounded by the failover budget) instead of
                # failed — a flaky prefill on one replica should not kill
                # the request.
                if self.cache.blocks_of(slot_idx):   # early-retire may
                    self.cache.release(slot_idx)     # have released it
                with self._cv:
                    self._slots.pop(slot_idx, None)
                if self._failover is not None:
                    self._failover(self, [(req, handle)],
                                   f"prefill failed: {e!r}",
                                   charge_unserved=True)
                else:
                    handle._finish(RequestState.FAILED, "prefill failed",
                                   error=repr(e))
            finally:
                with self._cv:
                    self._admitting = None

    def _prefill_into(self, slot_idx, blocks, req, handle, plen, bucket,
                      matched=0, cow_src=None):
        fault_point("serving.prefill")
        handle._set_state(RequestState.PREFILL)
        _trace.instant("serving.admit",
                       args={"uid": req.uid, "slot": slot_idx})
        try:
            if matched:
                first = self._suffix_prefill(slot_idx, req, plen,
                                             matched, cow_src)
            else:
                first = self._cold_prefill(req, plen, bucket, blocks)
        finally:
            if cow_src is not None:
                # drop the CoW-source pin (_fund): the private copy is
                # in the dispatch; the radix cache keeps its own ref
                self.cache.allocator.free([cow_src])
        # TTFT is measured at HOST materialization of the first token —
        # through the FetchHandle ledger like every other fetch
        tok = int(FetchHandle(first, name="serving.first_token").numpy())
        handle._append_tokens([tok])
        handle._set_state(RequestState.DECODE)
        if not handle._ttft_observed:   # a failover replay is not a TTFT
            handle._ttft_observed = True
            _metrics.observe("serving.ttft_ms", handle.ttft_ms())
        _trace.instant("serving.first_token", args={"uid": req.uid})
        eos = -1 if req.eos_token is None else int(req.eos_token)
        if req.max_new_tokens == 1 or tok == eos:
            self._publish_prefix(slot_idx, req)
            self.cache.release(slot_idx)
            self._retire(handle, "eos" if tok == eos else "length")
            return
        with self._cv:    # load()/stats() iterate _slots cross-thread
            self._slots[slot_idx] = _Slot(
                handle, pos=plen, gen=1, token=tok, eos=eos,
                max_new=req.max_new_tokens, temp=float(req.temperature),
                top_k=int(req.top_k), seed=int(req.seed))
        if self.spec is not None:
            # mapped/reserve split (cache.py): keep only the blocks the
            # prefill actually wrote in the page-table row; the rest of
            # the funded budget waits in the reserve so a rejected round
            # can truncate the row back without touching the allocator
            bs = self.config.block_size
            covered = (-(-plen // bs)) if matched else bucket // bs
            self.cache.reserve_tail(slot_idx, covered)
            self.spec.on_admit(slot_idx, req, plen, tok)

    def _cold_prefill(self, req, plen, bucket, blocks):
        """Dense prefill over the whole padded prompt bucket + block
        scatter (the no-cache / cache-miss path)."""
        import jax.numpy as jnp
        fn = self._prefill_jits.get(bucket)
        if fn is None:
            fn = self._prefill_jits[bucket] = self._prefill_fn(bucket)
        padded = np.zeros((bucket,), np.int32)
        padded[:plen] = req.prompt
        scales = self.scales if self.scales is not None else {}
        with _trace.RecordEvent("serving.prefill",
                                args={"uid": req.uid, "bucket": bucket}):
            k_seq, v_seq, first = fn(
                self.params, scales, jnp.asarray(padded),
                jnp.int32(plen), jnp.float32(req.temperature),
                jnp.int32(req.top_k), jnp.uint32(req.seed))
            nb = bucket // self.config.block_size
            wfn = self._write_jits.get(nb)
            if wfn is None:
                wfn = self._write_jits[nb] = self._write_fn(nb)
            k_pool, v_pool = wfn(self.cache.k_pool, self.cache.v_pool,
                                 k_seq, v_seq,
                                 jnp.asarray(blocks[:nb], jnp.int32))
            self.cache.update_pools(k_pool, v_pool)
        return first

    def _suffix_prefill(self, slot_idx, req, plen, matched, cow_src):
        """Prefill only the uncovered suffix of a prefix-cache hit: the
        shared full blocks are already in the slot's row; a partial tail
        (cow_src, pinned by _fund) is copied into the slot's first
        private block inside the dispatch before any write."""
        import jax.numpy as jnp
        bs = self.config.block_size
        mb = self.cache.config.max_blocks_per_slot
        row = self.cache.blocks_of(slot_idx)
        nf = matched // bs
        has_partial = bool(matched % bs)
        src = int(cow_src) if has_partial else SCRATCH_BLOCK
        dst = int(row[nf]) if has_partial else SCRATCH_BLOCK
        chain = row[:nf] + ([src] if has_partial else [])
        # pow2-padded prefix width: one compile per (p_pad, sbucket).
        # Floor of 2: at the degenerate single-block gather width XLA
        # refuses the pool donation alias and copies both pools (census-
        # verified); one extra SCRATCH block of gather is fully masked
        # (bit-neutral) and keeps the alias at every key.
        p_pad = 2
        while p_pad < len(chain):
            p_pad *= 2
        pb = np.full((p_pad,), SCRATCH_BLOCK, np.int32)
        pb[:len(chain)] = chain
        s_len = plen - matched
        sbucket = self._bucket_for(s_len)
        suffix = np.zeros((sbucket,), np.int32)
        suffix[:s_len] = req.prompt[matched:]
        slot_row = np.full((mb,), SCRATCH_BLOCK, np.int32)
        slot_row[:len(row)] = row
        # attention width = the COLD prompt bucket: bit-parity requires
        # the suffix program's reductions to run at exactly the width
        # the cold prefill would have used for this prompt
        width = self._bucket_for(plen)
        key = (p_pad, sbucket, width)
        fn = self._suffix_jits.get(key)
        if fn is None:
            fn = self._suffix_jits[key] = self._suffix_prefill_fn(
                p_pad, sbucket, width)
        scales = self.scales if self.scales is not None else {}
        with _trace.RecordEvent(
                "serving.suffix_prefill",
                args={"uid": req.uid, "matched": matched,
                      "suffix_bucket": sbucket}):
            k_pool, v_pool, first = fn(
                self.params, scales, self.cache.k_pool,
                self.cache.v_pool, jnp.asarray(pb), jnp.int32(matched),
                jnp.asarray(suffix), jnp.int32(s_len),
                jnp.asarray(slot_row), jnp.int32(dst),
                jnp.float32(req.temperature), jnp.int32(req.top_k),
                jnp.uint32(req.seed))
            self.cache.update_pools(k_pool, v_pool)
        return first

    def _publish_prefix(self, slot_idx: int, req: Request):
        """Publish a retiring slot's prompt chain into the radix cache.
        The cache takes its own block references (insert -> share), so
        the chain survives the release that follows; chunks already
        cached keep their existing blocks."""
        if self.prefix_cache is None:
            return
        blocks = self.cache.blocks_of(slot_idx)
        if blocks:
            self.prefix_cache.insert(req.prompt, blocks,
                                     self.cache.allocator)

    def _retire(self, handle, reason: str):
        handle._finish(RequestState.DONE, reason)
        self._completed += 1
        _metrics.inc("serving.completed")
        tpot = handle.tpot_ms()
        if tpot is not None:
            _metrics.observe("serving.tpot_ms", tpot)
        if handle.flow_id is not None:
            _trace.flow_end("serving.request", handle.flow_id,
                            args={"uid": handle.request.uid,
                                  "reason": reason})

    # ---- decode window ---------------------------------------------------
    def _window_args(self):
        import jax.numpy as jnp
        B = self.config.max_slots
        tokens = np.zeros((B,), np.int32)
        pos = np.zeros((B,), np.int32)
        gen = np.zeros((B,), np.int32)
        live = np.zeros((B,), bool)
        temps = np.zeros((B,), np.float32)
        top_ks = np.zeros((B,), np.int32)
        seeds = np.zeros((B,), np.uint32)
        eos = np.full((B,), -1, np.int32)
        max_new = np.full((B,), 1, np.int32)
        for i, s in self._slots.items():
            tokens[i], pos[i], gen[i] = s.token, s.pos, s.gen
            live[i], temps[i], top_ks[i] = True, s.temp, s.top_k
            seeds[i], eos[i], max_new[i] = s.seed, s.eos, s.max_new
        pt = jnp.asarray(self.cache.page_table_rows(B))
        return tuple(jnp.asarray(a) for a in
                     (pt, tokens, pos, gen, live, temps, top_ks, seeds,
                      eos, max_new))

    def _run_window(self):
        from ..framework.executor import _deadline_call
        # the chaos-drill kill site: an injected error here escalates
        # through the service loop to _fail_all — the same path a real
        # mid-window crash takes — BEFORE the flight step opens
        fault_point("serving.window")
        self._windows += 1
        _metrics.inc("serving.windows")
        owner = 0x5E0 + self._id   # flight-recorder lane per engine
        _flight.begin_step(self._windows, owner=owner)
        status = "ok"
        scales = self.scales if self.scales is not None else {}
        if self.spec is not None:
            # degraded-to-plain path on a spec engine: the mapped row may
            # lag the reserve split, so map enough blocks to cover every
            # position this window can write for each slot
            bs = self.config.block_size
            for idx, s in list(self._slots.items()):
                last = s.pos + min(self.config.window,
                                   s.max_new - s.gen) - 1
                self.cache.extend_mapped(idx, last // bs + 1)
        args = self._window_args()
        fid = _trace.new_flow()
        t0 = time.perf_counter()

        def dispatch_and_drain():
            with _trace.RecordEvent(
                    "serving.window",
                    args={"window": self._windows,
                          "active": len(self._slots)}):
                _trace.flow_start("serving.window_fetch", fid)
                k_pool, v_pool, toks, acts = self._window_jit(
                    self.params, scales, self.cache.k_pool,
                    self.cache.v_pool, *args,
                    self._window_max_blocks())
                self.cache.update_pools(k_pool, v_pool)
                h = FetchHandle(toks, name="serving.window_tokens",
                                flow=fid)
                return h.numpy(), np.asarray(acts)

        from ..framework import errors as _errors
        deadline = float(flag("FLAGS_step_deadline_ms") or 0.0)
        try:
            if deadline > 0:
                toks, acts = _deadline_call(
                    dispatch_and_drain, deadline,
                    f"serving window ({len(self._slots)} active slots)")
            else:
                toks, acts = dispatch_and_drain()
        except _errors.DeadlineExceededError:
            status = "sla_trip"
            _metrics.inc("serving.sla_trips")
            raise
        except BaseException:
            status = "error"
            raise
        finally:
            _flight.end_step(self._windows, status=status, owner=owner)
        window_ms = (time.perf_counter() - t0) * 1000.0
        _metrics.observe("serving.window_ms", window_ms)
        # EWMA of window wall time: the queue-wait estimator's clock
        self._window_ms_ewma = (
            window_ms if self._window_ms_ewma is None
            else 0.8 * self._window_ms_ewma + 0.2 * window_ms)
        self._apply_window(toks, acts)

    def _apply_slot_tokens(self, idx: int, slot: _Slot, tokens) -> tuple:
        """Host-side walk of one slot's emitted tokens (eos/length
        truncation), shared by the plain window and the speculative
        verify round. Appends to the handle, retires the slot when it
        finishes. Returns (n_emitted, finish_reason | None)."""
        emitted = []
        finished = None
        for tok in tokens:
            tok = int(tok)
            emitted.append(tok)
            slot.gen += 1
            slot.pos += 1
            slot.token = tok
            if tok == slot.eos:
                finished = "eos"
                break
            if slot.gen >= slot.max_new:
                finished = "length"
                break
        if emitted:
            slot.handle._append_tokens(emitted)
        if finished is not None:
            self._publish_prefix(idx, slot.handle.request)
            self.cache.release(idx)
            with self._cv:    # load()/stats() iterate cross-thread
                self._slots.pop(idx, None)
            if self.spec is not None:
                self.spec.on_release(idx)
            self._retire(slot.handle, finished)
        return len(emitted), finished

    def _apply_window(self, toks: np.ndarray, acts: np.ndarray):
        for idx in list(self._slots):
            slot = self._slots.get(idx)
            if slot is None:    # defensively tolerate a concurrent clear
                continue
            run = []
            for t in range(toks.shape[0]):
                if not acts[t, idx]:
                    break
                run.append(int(toks[t, idx]))
            self._apply_slot_tokens(idx, slot, run)

    # ---- speculative verify round (serving/spec.py drives this) ---------
    def _verify_args(self, cand: np.ndarray, valid: np.ndarray):
        import jax.numpy as jnp
        B = self.config.max_slots
        pos = np.zeros((B,), np.int32)
        gen = np.zeros((B,), np.int32)
        live = np.zeros((B,), bool)
        temps = np.zeros((B,), np.float32)
        top_ks = np.zeros((B,), np.int32)
        seeds = np.zeros((B,), np.uint32)
        for i, s in self._slots.items():
            pos[i], gen[i] = s.pos, s.gen
            live[i], temps[i] = True, s.temp
            top_ks[i], seeds[i] = s.top_k, s.seed
        pt = jnp.asarray(self.cache.page_table_rows(B))
        return tuple(jnp.asarray(a) for a in
                     (pt, cand, pos, live, valid, gen, temps, top_ks,
                      seeds))

    def _run_verify(self, cand: np.ndarray, valid: np.ndarray):
        """Dispatch ONE speculative verify round: the batched program
        from _verify_fn scoring span candidate positions per slot.
        Mirrors _run_window's envelope — same serving.window fault site
        (a chaos kill lands at the identical boundary whether speculation
        is armed or not), same flight step / SLA deadline / EWMA clock.
        Returns (vtok [B, span], n_acc [B]) as host arrays; the caller
        (SpecDecoder.run_round) applies them."""
        from ..framework.executor import _deadline_call
        fault_point("serving.window")
        span = int(cand.shape[1])
        self._windows += 1
        _metrics.inc("serving.windows")
        owner = 0x5E0 + self._id
        _flight.begin_step(self._windows, owner=owner)
        status = "ok"
        scales = self.scales if self.scales is not None else {}
        fn = self._verify_jit_for(span, self._max_blocks_hint(span))
        args = self._verify_args(cand, valid)
        fid = _trace.new_flow()
        t0 = time.perf_counter()

        def dispatch_and_drain():
            with _trace.RecordEvent(
                    "serving.spec_verify",
                    args={"window": self._windows, "span": span,
                          "active": len(self._slots)}):
                _trace.flow_start("serving.window_fetch", fid)
                k_pool, v_pool, vtok, n_acc = fn(
                    self.params, scales, self.cache.k_pool,
                    self.cache.v_pool, *args)
                self.cache.update_pools(k_pool, v_pool)
                h = FetchHandle(vtok, name="serving.verify_tokens",
                                flow=fid)
                return h.numpy(), np.asarray(n_acc)

        from ..framework import errors as _errors
        deadline = float(flag("FLAGS_step_deadline_ms") or 0.0)
        try:
            if deadline > 0:
                vtok, n_acc = _deadline_call(
                    dispatch_and_drain, deadline,
                    f"serving verify ({len(self._slots)} active slots)")
            else:
                vtok, n_acc = dispatch_and_drain()
        except _errors.DeadlineExceededError:
            status = "sla_trip"
            _metrics.inc("serving.sla_trips")
            raise
        except BaseException:
            status = "error"
            raise
        finally:
            _flight.end_step(self._windows, status=status, owner=owner)
        window_ms = (time.perf_counter() - t0) * 1000.0
        _metrics.observe("serving.window_ms", window_ms)
        self._window_ms_ewma = (
            window_ms if self._window_ms_ewma is None
            else 0.8 * self._window_ms_ewma + 0.2 * window_ms)
        return vtok, n_acc

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------
    def stats(self) -> dict:
        row = {
            "windows": self._windows,
            "completed": self._completed,
            "active_slots": len(self._slots),
            "queued": len(self._queue),
            "free_blocks": self.cache.allocator.free_blocks,
            "dead": self._dead,
            "health": self.health,
            "load": self.load(),
        }
        if self.prefix_cache is not None:
            looked = self._prefix_hits + self._prefix_misses
            row.update({
                "prefix_cache_nodes": len(self.prefix_cache),
                "prefix_cache_hits": self._prefix_hits,
                "prefix_cache_misses": self._prefix_misses,
                "prefix_cache_hit_rate": (
                    self._prefix_hits / looked if looked else 0.0),
                "prefill_tokens_saved": self._prefill_tokens_saved,
                "shared_blocks": self.cache.allocator.shared_blocks,
            })
        if self.spec is not None:
            row.update(self.spec.stats())
        return row

    def window_abstract_args(self):
        """ShapeDtypeStructs of one window call (serving/audit.py lowers
        the window program from these without consuming real buffers)."""
        import jax
        import jax.numpy as jnp
        B = self.config.max_slots
        sds = jax.ShapeDtypeStruct
        tree_sds = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda a: sds(a.shape, a.dtype), t)
        pool = sds(self.cache.config.pool_shape(),
                   self.cache.k_pool.dtype)
        mb = self.cache.config.max_blocks_per_slot
        return (tree_sds(self.params),
                tree_sds(self.scales if self.scales is not None else {}),
                pool, pool,
                sds((B, mb), jnp.int32), sds((B,), jnp.int32),
                sds((B,), jnp.int32), sds((B,), jnp.int32),
                sds((B,), jnp.bool_), sds((B,), jnp.float32),
                sds((B,), jnp.int32), sds((B,), jnp.uint32),
                sds((B,), jnp.int32), sds((B,), jnp.int32),
                mb)

    def verify_abstract_args(self, span: int):
        """ShapeDtypeStructs of one verify call (serving/audit.py lowers
        the speculative verify program from these to extend the zero-copy
        and dense-gather censuses to the new compiled surface)."""
        import jax
        import jax.numpy as jnp
        B = self.config.max_slots
        sds = jax.ShapeDtypeStruct
        tree_sds = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda a: sds(a.shape, a.dtype), t)
        pool = sds(self.cache.config.pool_shape(),
                   self.cache.k_pool.dtype)
        mb = self.cache.config.max_blocks_per_slot
        return (tree_sds(self.params),
                tree_sds(self.scales if self.scales is not None else {}),
                pool, pool,
                sds((B, mb), jnp.int32), sds((B, span), jnp.int32),
                sds((B,), jnp.int32), sds((B,), jnp.bool_),
                sds((B, span), jnp.bool_), sds((B,), jnp.int32),
                sds((B,), jnp.float32), sds((B,), jnp.int32),
                sds((B,), jnp.uint32))

    def suffix_abstract_args(self, p_pad: int = 2,
                             sbucket: Optional[int] = None):
        """ShapeDtypeStructs of one suffix-prefill call at the given
        compile key (serving/audit.py lowers the suffix program from
        these to extend the zero-copy census to the prefix-cache path)."""
        import jax
        import jax.numpy as jnp
        if sbucket is None:
            sbucket = self.buckets[0]
        sds = jax.ShapeDtypeStruct
        tree_sds = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda a: sds(a.shape, a.dtype), t)
        pool = sds(self.cache.config.pool_shape(),
                   self.cache.k_pool.dtype)
        mb = self.cache.config.max_blocks_per_slot
        return (tree_sds(self.params),
                tree_sds(self.scales if self.scales is not None else {}),
                pool, pool,
                sds((p_pad,), jnp.int32), sds((), jnp.int32),
                sds((sbucket,), jnp.int32), sds((), jnp.int32),
                sds((mb,), jnp.int32), sds((), jnp.int32),
                sds((), jnp.float32), sds((), jnp.int32),
                sds((), jnp.uint32))
