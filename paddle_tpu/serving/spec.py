"""Speculative decoding: draft-engine propose, one-window batched verify.

Classic speculative decoding (Leviathan et al. '23; Chen et al. '23)
trades FLOPs for latency: a cheap DRAFT model guesses the next gamma
tokens, the TARGET model scores all of them in ONE batched forward, and
the longest agreeing prefix is emitted — decode throughput rises by the
acceptance rate without changing the output distribution. This module
grafts that loop onto the continuous-batching engine with a stronger
contract than the papers need: because this stack's sampling is already
a pure function of (seed, token_index) — `fold_in(PRNGKey(seed), idx)`,
the property PR-12 built failover replay on — classic rejection sampling
DEGENERATES to exact-match verification. The verify program computes the
token the target would deterministically emit at every candidate
position (greedy AND seeded top-k) and accepts draft tokens only while
they are equal, so **spec-on output is bit-identical to spec-off by
construction**, not in expectation. That makes speculation free to
compose with everything keyed off determinism: failover replay, the
resurrection canary, the radix prefix cache's published chains.

Shape of one round (SpecDecoder.run_round):

1. **Propose** — the draft arm (an int8 weight arm of the SAME
   checkpoint by default, or a separate small model via SpecConfig) runs
   its own compiled decode window of length gamma over its own paged
   pool, producing gamma candidate tokens per live slot. The draft is an
   unstarted DecodeEngine driven synchronously on the target's service
   thread: same geometry, no prefix cache, no extra threads.
2. **Verify** — the target engine scores all gamma+1 positions per slot
   in ONE batched window-shaped program over the paged KV cache
   (engine._verify_fn): per-position writes and attends with the
   window's exact op shapes, sampled at generated indices gen..gen+gamma
   with the window's sample rule. Compile keys stay bounded: one program
   per (span, max_blocks ladder hint).
3. **Accept / roll back** — the longest agreeing prefix plus the
   target's correction/bonus token is emitted through the SAME host-side
   walk as the plain window (engine._apply_slot_tokens), and the blocks
   covering only-rejected positions are truncated back into the slot's
   ordered reserve (cache.truncate_mapped) — the allocator's refcounts
   never move mid-flight, so rejection can never leak a block or touch a
   prefix-cache chain's shared blocks.

Draft state rides a LAG-ONE sync: after a fully-accepted round the
draft's next window re-writes the last accepted token's k/v before
proposing (its first sample is checked against the already-emitted bonus
token and discarded), so the draft cache never accumulates holes; after
any rejection the target's correction overwrites the draft's stale tail
positions before they can be read (the window mask reaches a position
only after that window has rewritten it). Draft quality only moves the
ACCEPTANCE RATE — a wrong, stale, or garbage draft costs throughput,
never correctness.

Failure semantics (docs/serving.md "Speculative decoding"): any draft
fault — prefill error, a `serving.spec.draft` fault-site injection, an
operator kill_draft() — degrades the engine to plain decode at the next
round boundary (`serving.spec.degraded`), with ZERO failed requests:
spec-on equals spec-off bitwise, so the stream just continues one token
per step. The ServingFrontend's health loop walks the draft through the
same live -> suspect -> dead -> resurrecting ladder as an engine and
re-arms speculation only after the target's canary decode passes WITH
speculation armed (a valid gate precisely because of the bit-parity
contract).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from ..flags import flag
from ..models.gpt import GPTConfig
from ..observability import metrics as _metrics
from ..observability import trace as _trace
from ..resilience.faults import fault_point
from .resilience import Health


@dataclasses.dataclass
class SpecConfig:
    """Draft-arm geometry. `tokens` is gamma — the draft depth per round
    (0 = FLAGS_serving_spec_tokens). The default draft is the SAME
    checkpoint requantized to `draft_dtype` (int8): no second model to
    ship, and the int8 arm agrees with the full-precision target often
    enough to pay — acceptance is an A/B-measured quantity
    (bench.bench_serving_spec), never a correctness input. A separate
    small model rides `draft_params` + `draft_model_config` (its vocab
    must match the target's: proposals are candidate TARGET tokens)."""
    tokens: int = 0
    draft_dtype: str = "int8"
    draft_params: Optional[Dict] = None
    draft_model_config: Optional[GPTConfig] = None

    def resolve(self) -> "SpecConfig":
        c = dataclasses.replace(self)
        if not c.tokens:
            c.tokens = int(flag("FLAGS_serving_spec_tokens"))
        if not 1 <= c.tokens <= 16:
            raise ValueError(
                f"spec tokens (gamma) must be in [1, 16], got {c.tokens}")
        if c.draft_dtype not in ("float32", "bfloat16", "int8"):
            raise ValueError(
                f"draft_dtype must be float32|bfloat16|int8, "
                f"got {c.draft_dtype!r}")
        if (c.draft_params is None) != (c.draft_model_config is None):
            raise ValueError(
                "draft_params and draft_model_config come together: a "
                "separate draft model needs its own config, and a config "
                "without weights is not a draft")
        return c


class _DraftSlot:
    """Draft-side mirror of one target slot. `token` is the committed
    token whose k/v the next draft window writes first, at `pos`;
    `pending` (lag-one sync) is the following committed token, already
    emitted by the target — the draft window's first sample is checked
    against it and consumed, so a fully-accepted round never leaves a
    k/v hole in the draft cache."""
    __slots__ = ("token", "pos", "pending")

    def __init__(self, token: int, pos: int,
                 pending: Optional[int] = None):
        self.token = token
        self.pos = pos
        self.pending = pending


class SpecDecoder:
    """The speculation driver owned by a DecodeEngine (engine.spec).
    Everything here runs on the target's service thread between windows
    — the same boundary admission and retirement own — except
    `kill_draft`, which (like engine.kill) only posts a flag honored at
    the next round boundary."""

    def __init__(self, engine, config: SpecConfig,
                 raw_params: Optional[Dict] = None,
                 _draft_prepared: Optional[tuple] = None):
        self.engine = engine
        self.config = config
        self.health = Health.LIVE
        self.health_history: List[str] = [Health.LIVE]
        self._kill: Optional[str] = None
        self._dead_reason: Optional[str] = None
        self._rounds = 0
        self._proposed = 0
        self._accepted = 0
        self._rejected = 0
        self._degraded = 0
        mc = config.draft_model_config or engine.model_config
        if mc.vocab_size != engine.model_config.vocab_size:
            raise ValueError(
                f"draft vocab {mc.vocab_size} != target vocab "
                f"{engine.model_config.vocab_size}: draft proposals are "
                "candidate TARGET tokens")
        if (config.draft_params is None and raw_params is None
                and _draft_prepared is None):
            raise ValueError(
                "no draft weights: the default same-checkpoint draft "
                "needs the raw params (or a prepared clone source)")
        self.draft = self._build_draft(mc, raw_params, _draft_prepared)
        _metrics.set_gauge("serving.spec.armed", 1)

    def _build_draft(self, mc: GPTConfig, raw_params, _draft_prepared):
        """The draft arm: an UNSTARTED DecodeEngine sharing the target's
        geometry (same slots/blocks/max_len — mirror slots map 1:1) with
        window = gamma, no prefix cache, float KV pools, and no spec of
        its own. Its service thread never starts; run_round drives its
        compiled prefill/window programs synchronously."""
        from .engine import DecodeEngine, EngineConfig
        eng = self.engine
        t = eng.config
        dcfg = EngineConfig(
            max_slots=t.max_slots, block_size=t.block_size,
            num_blocks=t.num_blocks, max_len=t.max_len,
            window=self.config.tokens, dtype=self.config.draft_dtype,
            max_queue=t.max_queue, kv_dtype="",
            decode_kernel=t.decode_kernel, prefix_cache=False,
            spec=None, requested_max_len=t.requested_max_len)
        params = (self.config.draft_params
                  if self.config.draft_params is not None else raw_params)
        return DecodeEngine(params, mc, config=dcfg,
                            _prepared=_draft_prepared)

    @property
    def draft_prepared(self) -> tuple:
        """The draft's prepared device arrays, for frontend._clone_engine
        — replicas adopt ONE draft weight copy exactly like they adopt
        one target copy."""
        return (self.draft.params, self.draft.scales,
                self.draft.compute_dtype)

    @property
    def armed(self) -> bool:
        """Whether the service loop should run speculative rounds. A
        posted kill stays armed until run_round honors it at the round
        boundary (so the degrade is counted and traced exactly once)."""
        return self.health == Health.LIVE

    # ------------------------------------------------------------------
    # health
    # ------------------------------------------------------------------
    def _set_health(self, state: str):
        if state != self.health:
            self.health = state
            self.health_history.append(state)
            del self.health_history[:-64]

    def _degrade(self, why: str):
        """Draft failure -> plain decode. SUSPECT when a frontend is
        watching (its health tick confirms DEAD and later resurrects);
        straight to DEAD standalone. Mirror slots are dropped (host-side
        bookkeeping only — safe even if the draft pool died inside a
        dispatch); the pool itself is rebuilt by resurrect/reset."""
        self._dead_reason = why
        self._degraded += 1
        _metrics.inc("serving.spec.degraded")
        _metrics.set_gauge("serving.spec.armed", 0)
        _trace.instant("serving.spec.degraded",
                       args={"engine": self.engine._id, "why": why})
        self._set_health(Health.SUSPECT
                         if self.engine._failover is not None
                         else Health.DEAD)
        try:
            self.release_all()
        except Exception:   # noqa: BLE001 — a torn draft allocator must
            # not take the TARGET engine down; the rebuild replaces it
            self.draft._slots.clear()

    def kill_draft(self, why: str):
        """Kill the draft arm from ANY thread (tests, chaos drills, an
        operator). Honored at the next round boundary — the same
        deferral engine.kill uses — so it can never race an in-flight
        draft dispatch's slot accounting."""
        self._kill = why

    def resurrect_draft(self):
        """Rebuild the draft arm's pool (it died with whatever dispatch
        degraded it) and clear the kill. The caller (ServingFrontend
        health loop) re-arms + canaries before traffic sees it."""
        self._set_health(Health.RESURRECTING)
        _metrics.inc("serving.spec.resurrections")
        d = self.draft
        d._slots.clear()
        d.cache.close()
        d.cache = d._build_cache()
        self._kill = None
        self._dead_reason = None

    def rearm(self):
        """LIVE again (frontend, after the spec-armed canary passed;
        also the provisional arm that lets the canary decode THROUGH
        speculation — valid gate because spec-on == spec-off bitwise)."""
        self._set_health(Health.LIVE)
        _metrics.set_gauge("serving.spec.armed", 1)

    def reset(self):
        """engine.resurrect(): both pools died with the failed dispatch;
        rebuild the draft's alongside the target's and re-arm — the
        frontend's canary then validates the WHOLE spec-on path."""
        self.resurrect_draft()
        self.rearm()

    def close(self):
        self.draft.cache.close()

    # ------------------------------------------------------------------
    # slot lifecycle (called by the target engine)
    # ------------------------------------------------------------------
    def on_admit(self, slot_idx: int, req, plen: int, first_token: int):
        """Fund + prefill the draft mirror of a freshly admitted slot.
        The draft never prefix-caches (its pool is private and its
        values are approximations anyway) and its first sampled token is
        discarded — the TARGET's first token seeds the mirror. Any
        failure degrades; an unfundable draft pool just leaves the slot
        uncovered (gamma_eff = 0 rounds, still bit-correct)."""
        if not self.armed or self._kill is not None:
            return
        d = self.draft
        try:
            n_cold = d._block_budget(plen, req.max_new_tokens)
            blocks = d.cache.assign(slot_idx, n_cold)
            if blocks is None:
                _metrics.inc("serving.spec.draft_unfunded")
                return
            bucket = d._bucket_for(plen)
            d._cold_prefill(req, plen, bucket, blocks)
            d._slots[slot_idx] = _DraftSlot(first_token, plen)
        except Exception as e:   # noqa: BLE001 — degrade, never fail
            if d.cache.blocks_of(slot_idx):
                d.cache.release(slot_idx)
            self._degrade(f"draft prefill failed: {e!r}")

    def on_release(self, slot_idx: int):
        d = self.draft
        if d._slots.pop(slot_idx, None) is not None:
            d.cache.release(slot_idx)

    def release_all(self):
        for idx in list(self.draft._slots):
            self.on_release(idx)

    # ------------------------------------------------------------------
    # the speculative round
    # ------------------------------------------------------------------
    def _propose(self) -> Dict[int, List[int]]:
        """One draft decode window (gamma steps) over the mirror slots;
        returns usable proposals per slot index. A mirror lagging one
        position (pending set) burns its first sample on the lag-one
        re-write check; a pending mismatch yields no proposals this
        round (the post-round sync re-aims the mirror)."""
        import jax.numpy as jnp
        fault_point("serving.spec.draft")
        eng, d = self.engine, self.draft
        gamma = self.config.tokens
        B = eng.config.max_slots
        tokens = np.zeros((B,), np.int32)
        pos = np.zeros((B,), np.int32)
        gen = np.zeros((B,), np.int32)
        live = np.zeros((B,), bool)
        temps = np.zeros((B,), np.float32)
        top_ks = np.zeros((B,), np.int32)
        seeds = np.zeros((B,), np.uint32)
        eos = np.full((B,), -1, np.int32)       # never latch mid-window
        max_new = np.full((B,), 1, np.int32)
        covered = []
        for i, ds in d._slots.items():
            t = eng._slots.get(i)
            if t is None:
                continue
            gap = t.pos - ds.pos                # 0, or 1 when pending
            tokens[i], pos[i] = ds.token, ds.pos
            gen[i] = t.gen - gap                # draft samples ride the
            live[i] = True                      # TARGET's (seed, index)
            temps[i], top_ks[i] = t.temp, t.top_k   # schedule, so an
            seeds[i] = t.seed                   # agreeing draft token IS
            max_new[i] = gen[i] + gamma + 1     # the target's token
            covered.append(i)
        if not covered:
            return {}
        pt = jnp.asarray(d.cache.page_table_rows(B))
        args = tuple(jnp.asarray(a) for a in
                     (pt, tokens, pos, gen, live, temps, top_ks, seeds,
                      eos, max_new))
        scales = d.scales if d.scales is not None else {}
        k_pool, v_pool, toks, _ = d._window_jit(
            d.params, scales, d.cache.k_pool, d.cache.v_pool, *args,
            d._window_max_blocks())
        d.cache.update_pools(k_pool, v_pool)
        toks = np.asarray(toks)             # [gamma, B]
        props: Dict[int, List[int]] = {}
        for i in covered:
            chain = [int(toks[s, i]) for s in range(gamma)]
            ds = d._slots[i]
            if ds.pending is not None:
                if chain[0] != ds.pending:
                    props[i] = []   # mis-rewrote the pending position;
                    continue        # post-round sync re-aims the mirror
                chain = chain[1:]
            props[i] = chain
        return props

    def run_round(self):
        """One speculative round: propose -> batched verify -> emit the
        agreeing prefix + correction -> roll rejected blocks back into
        the reserve -> lag-one draft sync. Every fallback inside keeps
        the stream bit-identical to spec-off — the only variable is how
        many tokens land per dispatch."""
        eng = self.engine
        if self._kill is not None:
            why, self._kill = self._kill, None
            self._degrade(f"draft killed: {why}")
            eng._run_window()
            return
        gamma = self.config.tokens
        span = gamma + 1
        B = eng.config.max_slots
        bs = eng.config.block_size
        try:
            props = self._propose()
        except Exception as e:   # noqa: BLE001 — draft faults degrade,
            # target faults (inside _run_verify below) still escalate
            self._degrade(f"draft propose failed: {e!r}")
            eng._run_window()
            return
        if not props:
            # no mirror coverage at all (e.g. every live slot was
            # admitted while degraded): a plain window emits more
            # tokens per dispatch than a gamma_eff=0 verify would
            eng._run_window()
            return
        cand = np.zeros((B, span), np.int32)
        valid = np.zeros((B, span), bool)
        g_eff: Dict[int, int] = {}
        before: Dict[int, int] = {}             # slot.token pre-apply
        for idx, slot in list(eng._slots.items()):
            cand[idx, 0] = slot.token
            valid[idx, 0] = True
            p = props.get(idx, [])
            g = max(0, min(gamma, slot.max_new - slot.gen - 1, len(p)))
            for j in range(g):
                cand[idx, 1 + j] = p[j]
                valid[idx, 1 + j] = True
            g_eff[idx] = g
            before[idx] = slot.token
            # map reserve blocks up to the furthest REAL write this
            # round (invalid columns land on the scratch block)
            eng.cache.extend_mapped(idx, (slot.pos + g) // bs + 1)
        vtok, n_acc = eng._run_verify(cand, valid)
        self._rounds += 1
        _metrics.inc("serving.spec.rounds")
        for idx in list(eng._slots):
            slot = eng._slots.get(idx)
            if slot is None:
                continue
            g = g_eff.get(idx, 0)
            a = min(int(n_acc[idx]), g)
            self._proposed += g
            self._accepted += a
            self._rejected += g - a
            if g:
                _metrics.inc("serving.spec.proposed", g)
            if a:
                _metrics.inc("serving.spec.accepted", a)
            _, finished = eng._apply_slot_tokens(
                idx, slot, [int(vtok[idx, j]) for j in range(a + 1)])
            if finished is not None:
                continue        # released (on_release dropped the mirror)
            # rejected-tail rollback: keep only the blocks covering the
            # committed positions 0..pos-1; the rest rejoin the ordered
            # reserve (refcounts untouched — shared prefix blocks are
            # always inside the kept span since pos > prompt_len)
            eng.cache.truncate_mapped(idx, (slot.pos - 1) // bs + 1)
            ds = self.draft._slots.get(idx)
            if ds is None:
                continue
            p = props.get(idx, [])
            if a < g:
                # the correction overwrote the draft's stale tail before
                # any future read can reach it: mirror rejoins at the
                # target's exact state
                ds.token, ds.pos, ds.pending = \
                    slot.token, slot.pos, None
            else:
                # fully accepted (or nothing verified): the last
                # committed token's k/v is not in the draft cache yet —
                # lag one position and re-write it next round
                ds.token = before[idx] if a == 0 else p[a - 1]
                ds.pos = slot.pos - 1
                ds.pending = slot.token
        if self._proposed:
            _metrics.set_gauge("serving.spec.accept_rate",
                               self._accepted / self._proposed)

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------
    def stats(self) -> dict:
        return {
            "spec_decode": True,
            "spec_armed": self.armed,
            "spec_gamma": self.config.tokens,
            "spec_draft_health": self.health,
            "spec_rounds": self._rounds,
            "spec_proposed": self._proposed,
            "spec_accepted": self._accepted,
            "spec_rejected": self._rejected,
            "spec_accept_rate": (self._accepted / self._proposed
                                 if self._proposed else 0.0),
            "spec_degraded": self._degraded,
            "spec_draft_free_blocks":
                self.draft.cache.allocator.free_blocks,
        }
