"""Global flags registry.

Reference counterpart: the gflags tier (platform/flags.cc, 30+ flags,
re-exported via pybind/global_value_getter_setter.cc and the
fluid/__init__.py __bootstrap__ env whitelist). One typed registry here;
FLAGS_* environment variables seed the initial values at import, matching
the reference's interpreter-start semantics. Device/allocator flags that XLA
owns are accepted as documented no-ops.
"""
from __future__ import annotations

import os
from typing import Any, Dict

_DEFS: Dict[str, tuple] = {
    # (default, help)
    "FLAGS_check_nan_inf": (False, "scan step outputs/state for NaN/Inf "
                                   "(reference operator.cc:1129)"),
    "FLAGS_check_nan_inf_level": (0, "0: raise on first non-finite; "
                                     "1: warn only"),
    "FLAGS_eager_delete_tensor_gb": (0.0, "no-op: XLA owns HBM lifetimes"),
    "FLAGS_allocator_strategy": ("auto_growth", "no-op: XLA runtime "
                                                "allocates"),
    "FLAGS_fraction_of_gpu_memory_to_use": (0.92, "no-op on TPU"),
    "FLAGS_paddle_num_threads": (1, "no-op: XLA threadpool"),
    "FLAGS_use_pinned_memory": (True, "no-op"),
    "FLAGS_benchmark": (False, "no-op: the `executor.launch` span holds "
                               "the time it printed"),
    "FLAGS_profile_start_step": (-1, "auto-start profiler at this step"),
    "FLAGS_profile_stop_step": (-1, "auto-stop profiler at this step"),
    "FLAGS_tensor_array_capacity": (128, "default LoDTensorArray capacity"),
    "FLAGS_min_donate_bytes": (65536, "buffer-donation size floor for "
                               "written persistable state: smaller buffers "
                               "are passed un-donated, because donating a "
                               "tiny buffer saves almost nothing while its "
                               "in-place aliasing makes XLA insert a "
                               "value-preserving copy op whenever the "
                               "update's live range crosses a remaining "
                               "read (docs/perf_notes.md 'Copy census'); "
                               "0 donates everything"),
    "FLAGS_zero_stage": (0, "ZeRO sharding stage applied at fleet minimize "
                            "time (parallel/zero.py): 1 moves each gradient "
                            "bucket's optimizer state into flat dp-sharded "
                            "vars updated shard-locally (reduce_scatter -> "
                            "update -> all_gather); 2 additionally keeps "
                            "the averaged gradient SHARD resident (grad "
                            "bytes/device / dp, never all-gathered); 3 "
                            "also flat-shards parameter STORAGE with "
                            "on-demand __zero_gather__ (one all_gather per "
                            "layer-scan iteration for @LAYERS stacks); "
                            "0 keeps replicated state (grouped bucket "
                            "all-reduces still apply). Same switch as "
                            "DistributedStrategy.sharding_stage"),
    "FLAGS_verify_passes": (False, "run the static program verifier "
                            "(paddle_tpu/analysis/) after EVERY program "
                            "pass — layer_scan, recompute, gradient merge, "
                            "grad bucketing/ZeRO, sink code motion, fleet "
                            "minimize. An error-severity finding raises "
                            "PassVerificationError naming the offending "
                            "pass with a before/after op diff; the sink "
                            "motion additionally re-proves dataflow "
                            "preservation. Read-only: verified and "
                            "unverified builds produce byte-identical "
                            "programs (docs/static_analysis.md)"),
    "FLAGS_layer_scan": (False, "roll isomorphic per-layer segments into "
                                "one lax.scan at fleet minimize time "
                                "(parallel/transforms.apply_layer_scan; "
                                "same switch as DistributedStrategy."
                                "layer_scan)"),
    "FLAGS_async_dispatch": (False, "executor.run/run_steps default to "
                             "sync=False: fetches come back as lazy "
                             "FetchHandles that materialize to numpy only "
                             "on access, so the host never blocks on steps "
                             "nobody reads (framework/fetch.py; sync stays "
                             "the default until parity is pinned — "
                             "tests/test_async_dispatch.py). Falls back to "
                             "sync while a fault plan is installed or on a "
                             "staged-buffer donation conflict"),
    "FLAGS_dispatch_queue_depth": (2, "max pre-staged feed windows held by "
                                   "Executor.stage() (the host-side "
                                   "dispatch queue): while window n "
                                   "executes, window n+1's feeds coerce + "
                                   "device_put ahead of time; depth 1-2 is "
                                   "enough to hide host latency without "
                                   "pinning extra HBM (monitor stat "
                                   "executor.dispatch_queue_depth)"),
    # --- observability tier (observability/, docs/observability.md) ------
    "FLAGS_trace_events": (True, "record host RecordEvent spans / flow "
                           "events / instants into the bounded trace ring "
                           "(observability/trace.py). Always-on by design "
                           "(the flight recorder's backing store; ring-"
                           "bounded memory, ≤5% hot-path overhead pinned "
                           "by tests/test_observability.py); 0 turns span "
                           "recording into a no-op — the timing A/B's "
                           "baseline arm"),
    "FLAGS_trace_buffer_events": (65536, "trace ring capacity in events; "
                                  "oldest events drop past it, counted in "
                                  "the trace.dropped_events metric"),
    "FLAGS_flight_recorder": (True, "keep the last FLAGS_flight_steps "
                              "steps' wall windows + metric deltas and "
                              "dump them (with the trace ring) on step-"
                              "watchdog trips, gang failures, and "
                              "degraded bench rows "
                              "(observability/flight.py)"),
    "FLAGS_flight_steps": (16, "flight-recorder step-ring depth"),
    "FLAGS_flight_dump_dir": ("", "where flight dumps land; empty = "
                              "<tmpdir>/paddle_tpu_flight"),
    "FLAGS_collective_markers": (True, "stamp a correlation-key instant "
                                 "(step, bucket, seq) per collective op on "
                                 "every dispatch (framework/executor.py). "
                                 "Matching keys across gang ranks become "
                                 "the lane-crossing flow arrows and the "
                                 "arrival-skew telemetry of the pod-scope "
                                 "merge (observability/podscope.py, "
                                 "scripts/pod_trace.py); costs a few "
                                 "trace-ring appends per step, nothing "
                                 "when FLAGS_trace_events=0"),
    # --- serving tier (paddle_tpu/serving/, docs/serving.md) --------------
    "FLAGS_serving_window": (8, "decode tokens per serving scan window "
                             "(serving/engine.py): finished requests "
                             "retire and queued requests admit BETWEEN "
                             "windows, so this is the continuous-batching "
                             "scheduling quantum — smaller = lower "
                             "admission latency, larger = fewer host "
                             "round-trips per token. FLAGS_step_deadline_"
                             "ms bounds each window as the serving SLA "
                             "watchdog"),
    "FLAGS_serving_block_size": (16, "paged KV-cache block size in "
                                 "positions (serving/cache.py): each "
                                 "sequence owns ceil(len/block) pool "
                                 "blocks via its page-table row; smaller "
                                 "= less fragmentation, larger = smaller "
                                 "page tables and fewer scatter targets"),
    "FLAGS_serving_max_queue": (256, "submit-queue bound per decode "
                                "engine (admission control): a submit "
                                "past it is SHED with typed reason "
                                "queue_full instead of queueing toward "
                                "an unmeetable deadline "
                                "(serving/engine.py, counted in "
                                "serving.shed_total / "
                                "serving.shed.queue_full)"),
    "FLAGS_serving_failover_budget": (2, "re-dispatches a single request "
                                     "may consume after engine deaths "
                                     "before it fails with the typed "
                                     "RequestFailedError "
                                     "(serving/resilience.py; each "
                                     "re-dispatch replays the "
                                     "deterministic decode bit-"
                                     "identically on a healthy replica)"),
    "FLAGS_serving_health_interval_ms": (200.0, "ServingFrontend health-"
                                         "loop tick: suspect engines are "
                                         "confirmed dead and dead "
                                         "engines resurrected (cache "
                                         "rebuild + canary gate) at "
                                         "this cadence"),
    "FLAGS_serving_resurrect_budget": (3, "canary-gated resurrection "
                                      "attempts per engine death "
                                      "(RetryPolicy max_attempts); "
                                      "exhaustion parks the engine dead "
                                      "permanently (serving."
                                      "resurrect_gave_up)"),
    "FLAGS_serving_drain_timeout_ms": (30000.0, "graceful-drain bound: "
                                       "how long drain() waits for in-"
                                       "flight slots to decode to "
                                       "completion before stopping the "
                                       "engine anyway (the launch.py "
                                       "SIGTERM grace usually bounds it "
                                       "tighter via PADDLE_LAUNCH_"
                                       "GRACE_S)"),
    "FLAGS_serving_spec_tokens": (4, "speculative-decoding draft depth "
                                  "gamma (serving/spec.py): tokens the "
                                  "draft engine proposes per slot per "
                                  "round; the target engine scores all "
                                  "gamma+1 positions in ONE batched "
                                  "verify program and accepts the "
                                  "longest agreeing prefix, so spec-on "
                                  "output is bit-identical to spec-off. "
                                  "Higher gamma = more tokens per "
                                  "target pass when acceptance is high, "
                                  "more wasted draft work when it is "
                                  "low (docs/serving.md 'Speculative "
                                  "decoding')"),
    # --- Pallas kernel tier (ops/pallas/, docs/perf_notes.md) ------------
    "FLAGS_pallas_decode": (False, "serve decode attention through the "
                            "fused paged-attention Pallas kernel "
                            "(ops/pallas/paged_attention.py): page-table "
                            "walk in-kernel, no dense cache-view "
                            "materialization, bit-identical to the "
                            "paged_attend fallback. Env twin for A/B "
                            "benching: PADDLE_TPU_PALLAS_DECODE=0|1"),
    "FLAGS_pallas_opt": (False, "run the shard-local ZeRO bucket update "
                         "through the fused optimizer kernel "
                         "(ops/pallas/zero_update.py): one HBM pass per "
                         "bucket, bit-identical to the registry rules, "
                         "checkpoint-portable both directions. Env twin "
                         "for A/B benching: PADDLE_TPU_PALLAS_OPT=0|1"),
    # --- resilience tier (resilience/, docs/resilience.md) ---------------
    "FLAGS_fault_plan": ("", "fault-injection plan spec, e.g. "
                             "'kv.pull:error:every=3;ckpt.write:kill:at=2'"),
    "FLAGS_fault_seed": (0, "seed for probabilistic (p=) fault rules and "
                            "retry jitter"),
    "FLAGS_retry_max_attempts": (4, "RetryPolicy default attempt budget"),
    "FLAGS_retry_base_delay_ms": (20.0, "RetryPolicy first-backoff delay"),
    "FLAGS_retry_max_delay_ms": (2000.0, "RetryPolicy backoff ceiling"),
    "FLAGS_rpc_deadline_ms": (10000.0, "per-op deadline on PS RPC / gloo "
                                       "paths; DeadlineExceeded after"),
    "FLAGS_gloo_timeout_ms": (60000.0, "gloo rendezvous + collective-round "
                                       "timeout"),
    "FLAGS_dataloader_max_respawns": (0, "respawn budget for abnormally-"
                                         "dead dataloader workers "
                                         "(0 = fail fast, seed behavior)"),
    # --- training integrity tier (resilience/snapshot.py, integrity.py) ---
    "FLAGS_snapshot_steps": (0, "async in-memory snapshot cadence: capture "
                                "a double-buffered device->host copy of "
                                "the portable training state every N steps "
                                "off the hot path (0 = disabled). SIGTERM "
                                "flushes the newest snapshot to "
                                "FLAGS_snapshot_dir inside the launcher "
                                "grace window"),
    "FLAGS_snapshot_dir": ("", "root for flushed snapshots + recovery "
                               "stamps; empty resolves PADDLE_SNAPSHOT_DIR "
                               "(exported per-gang by distributed/"
                               "launch.py) then a per-pid tmp dir"),
    "FLAGS_fingerprint_steps": (0, "cross-replica divergence sentinel "
                                   "cadence: sha256-fingerprint the "
                                   "dp-replicated state and all-gather/"
                                   "compare across ranks every N steps "
                                   "(0 = disabled); mismatch raises "
                                   "ReplicaDivergenceError naming the "
                                   "minority rank or heals from the "
                                   "quorum's snapshot"),
    "FLAGS_loss_spike_factor": (10.0, "TrainingGuard poison-batch rule: a "
                                "loss above this multiple of the trailing-"
                                "window median (or any NaN/Inf) triggers "
                                "rollback to the last good snapshot, "
                                "skipping the batch (0 disables the spike "
                                "rule; NaN/Inf always fires)"),
    "FLAGS_rollback_budget": (2, "how many poison-batch rollbacks "
                                 "TrainingGuard performs before giving up "
                                 "and raising RollbackExhausted"),
    # --- elasticity / preemption tier (docs/resilience.md) ----------------
    "FLAGS_step_deadline_ms": (0.0, "hang watchdog for the executor's "
                               "SYNCHRONOUS step path: bound dispatch and "
                               "fetch materialization by this wall-clock "
                               "deadline; a trip raises the typed "
                               "DeadlineExceededError with a full "
                               "thread-stack dump and counts "
                               "executor.step_deadline_trips, so a wedged "
                               "collective (one dead pod host) surfaces as "
                               "a typed error the gang supervisor can act "
                               "on instead of an indefinite hang. 0 (the "
                               "default) disables the watchdog"),
    "FLAGS_rendezvous_deadline_ms": (60000.0, "gang-launch rendezvous "
                                     "deadline (distributed/launch.py): "
                                     "every worker must check in (create "
                                     "its heartbeat file) within this "
                                     "budget or the supervisor kills the "
                                     "whole gang and raises "
                                     "DeadlineExceededError — a straggler "
                                     "must fail the launch, never wedge "
                                     "the surviving workers in a "
                                     "collective"),
    "FLAGS_launch_heartbeat_interval_ms": (1000.0, "how often each "
                                           "launched worker's heartbeat "
                                           "thread touches its liveness "
                                           "file; the supervisor treats a "
                                           "file stale past the launcher's "
                                           "--heartbeat_timeout_ms as a "
                                           "hung worker"),
}

_values: Dict[str, Any] = {}


def _coerce(default, raw: str):
    if isinstance(default, bool):
        return raw.lower() in ("1", "true", "yes", "on")
    return type(default)(raw)


def _init():
    for name, (default, _help) in _DEFS.items():
        raw = os.environ.get(name)
        _values[name] = _coerce(default, raw) if raw is not None else default


_init()


def get_flags(names):
    if isinstance(names, str):
        names = [names]
    return {n: _values.get(n) for n in names}


def set_flags(flags: Dict[str, Any]):
    for name, value in flags.items():
        if name not in _DEFS:
            raise KeyError(f"unknown flag {name!r}; known: {sorted(_DEFS)}")
        default = _DEFS[name][0]
        _values[name] = (_coerce(default, value)
                         if isinstance(value, str) else type(default)(value))


def flag(name: str):
    return _values[name]
