"""Driver of a causal-LM training cell: the program's own trainer
(`models.deepseek_v3`, `fleet.distributed_optimizer`,
`fluid.Executor.run_steps`) timed reading by reading and held against the
plain reference. Same contract as `drivers/train.py`: the first reading is
the check, the window is `run_steps(k)` readings, `series.json`,
`checks.json`, the same `ctx` keys. What a model of 576 M parameters on a
16 GB chip adds: the state norms are taken leaf by leaf (no second copy of
the weights), and the program's state is freed before the reference runs.
"""
from __future__ import annotations

import gc
import os
import time

import numpy as np

from .. import common, lm_traffic, stats, xplane
from ..common import log
from .train import TRACED_READINGS, checks_from, compare

# the configuration file's keys -> models.deepseek_v3.DeepseekV3Config
_PUBLISHED = ("hidden_size", "num_attention_heads", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
              "intermediate_size", "moe_intermediate_size",
              "n_shared_experts", "num_experts_per_tok",
              "first_k_dense_replace", "routed_scaling_factor",
              "norm_topk_prob", "rms_norm_eps", "rope_theta")


class Trainer:
    """The one compiled step with its state that set-up builds, the check
    drives through its first steps and the window then times."""

    def __init__(self, cfg: dict, spec: dict, seed: int, chips: int):
        import jax
        import paddle_tpu as paddle
        import paddle_tpu.fluid as fluid
        from paddle_tpu.distributed import fleet
        from paddle_tpu.models import deepseek_v3
        from paddle_tpu.testing import reset_programs

        if chips != 1:
            raise common.Refused("the causal-LM driver runs one chip's "
                                 "share on one chip")
        self.cfg, self.spec, self.seed = cfg, spec, seed
        self.k = spec["steps_per_reading"]
        self.rows = spec["batch_per_chip"]
        self.seq = spec["seq"]
        self.ref = common.load_reference(cfg)
        self.model = deepseek_v3
        reset_programs(seed=seed % (2 ** 31))
        mcfg = deepseek_v3.DeepseekV3Config(
            vocab_size=cfg["vocab"], num_hidden_layers=cfg["layers"],
            n_routed_experts=cfg["experts_total"],
            experts_held=cfg["n_routed_experts"],
            expert_offset=cfg["expert_offset"], seq_len=self.seq,
            initializer_range=cfg["assumed"]["initializer_std"],
            **{key: cfg[key] for key in _PUBLISHED})
        _, self.loss, routed = deepseek_v3.build_causal_lm_program(mcfg)
        fleet.init(is_collective=True)
        strategy = fleet.DistributedStrategy()
        strategy.amp = True
        if cfg["assumed"].get("recompute"):
            strategy.recompute = True
            strategy.recompute_configs = {
                "checkpoints": list(self.loss._layer_checkpoints)}
        fleet.distributed_optimizer(
            paddle.optimizer.Adam(learning_rate=self.ref.ADAM["lr"]),
            strategy).minimize(self.loss)
        if len(jax.devices()) > chips:
            # a host with more chips than the cell asks for: the same
            # program on a mesh cut to the cell's one chip
            from paddle_tpu.parallel import DistConfig, attach, build_mesh
            prog = fluid.default_main_program()
            attach(prog, DistConfig(
                mesh=build_mesh(dp=chips, devices=jax.devices()[:chips]),
                param_rules=prog._dist_config.param_rules))
        # the losses, the first expert layer's routed choice and every
        # expert layer's load leave the device in ONE run_steps call
        self.fetch = [self.loss, routed[0][0]] + [r[1] for r in routed]
        self.exe = fluid.Executor()
        self.exe.run(fluid.default_startup_program())
        self.scope = fluid.global_scope()
        self.names = sorted(self.ref.param_shapes(cfg))
        # the benchmark's own weights, leaf by leaf on the device; the
        # reference starts from the same draws
        for name in self.names + sorted(self.ref.buffer_shapes(cfg)):
            if self.scope.find(name) is None:
                raise RuntimeError(f"the program has no parameter {name!r}")
            self.scope.set(name, self.fresh_leaf(name))

    def fresh_leaf(self, name: str):
        return self.ref.init_leaf(self.cfg, common.seed_key(self.seed), name)

    def device_feed(self, index: int) -> tuple:
        """(feed for run_steps, the host arrays the reference follows)."""
        import jax
        host = lm_traffic.lm_feed(self.spec, self.cfg["vocab"], self.rows,
                                  self.seed, index)
        return {"tokens": jax.device_put(host["ids"])}, host

    def reading(self, feed) -> tuple:
        """One reading: `run_steps(k)` ending in the host read of its k
        losses and expert loads. -> (seconds to the call's return, seconds
        in all, CPU seconds the process used meanwhile, losses, the
        program's routing gauges, the routed choice still on the device)"""
        t0, c0 = time.perf_counter(), time.process_time()
        out = self.exe.run_steps(self.k, feed=feed, fetch_list=self.fetch,
                                 return_numpy=False)
        t1 = time.perf_counter()
        losses = np.asarray(out[0], np.float64).reshape(-1)
        loads = np.stack([np.asarray(v) for v in out[2:]])   # [L, k, E]
        t2 = time.perf_counter()
        routing = self.model.record_expert_load(loads, self.rows * self.seq)
        return (t1 - t0, t2 - t0, time.process_time() - c0, losses, routing,
                out[1])

    def state_norms(self) -> dict:
        """Per-leaf norms of Adam's first moment as it stands, and of the
        parameters' change from the seeded weights, one leaf at a time."""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def norms(moment, param, p0):
            f32 = jnp.float32
            return (jnp.linalg.norm(moment.astype(f32)),
                    jnp.linalg.norm(param.astype(f32) - p0))

        m, d = {}, {}
        for n in self.names:
            mn, dn = norms(self.scope.find(n + "_moment1_0"),
                           self.scope.find(n), self.fresh_leaf(n))
            m[n], d[n] = float(mn), float(dn)
        return {"moment1_norms": m, "delta_norms": d}

    def free(self):
        """Give the chip back before the reference runs: the executor's
        programs, every array of the scope, JAX's loaded executables."""
        self.exe.close()
        for name in self.scope.local_names():
            self.scope.erase(name)
        _unload_programs()


def _unload_programs():
    """Every executable JAX holds leaves the chip, with the memory it
    keeps reserved for its temporaries (6.3 GB for the step, 5.3 GB for
    the reference's gradient program: my chip run, PR 26). The window froze
    the heap (`common.settle_heap`); thaw it, or the collector never reaches
    the cycles that hold a compiled block."""
    import jax
    gc.unfreeze()
    gc.collect()
    jax.clear_caches()
    _reference_on_chip[0] = "nothing"


# which reference program the chip holds: "nothing", or its `quant`
_reference_on_chip = ["nothing"]


def run_reference(tr: Trainer, host_batches: dict, quant=None, steps=None,
                  cfg: dict | None = None) -> dict:
    """The plain reference over the first `steps` (default: all k) batches
    of one feed, from the seeded weights, in blocks of rows; `cfg`, if
    given, is the configuration with an assumption changed (a fault)."""
    cfg = cfg or tr.cfg
    if _reference_on_chip[0] not in ("nothing", quant):
        # the control is another program of the same size: two do not fit
        _unload_programs()
    _reference_on_chip[0] = quant
    rows = max(1, cfg["reference_tokens_per_block"] // tr.seq)
    batches = [{"ids": host_batches["ids"][i],
                "labels": host_batches["labels"][i]}
               for i in range(steps or tr.k)]
    return tr.ref.follow(
        cfg, lambda: tr.ref.init_params(cfg, common.seed_key(tr.seed)),
        batches, rows, quant=quant)


def route_mismatch_share(program_idx, reference_idx) -> float:
    """Share of the (token, slot) choices of the first expert layer at
    step 1 that differ from the reference's: a token's chosen experts as a
    set, so the order of two equal weights does not count."""
    got = np.sort(np.asarray(program_idx).reshape(
        -1, np.asarray(reference_idx).shape[-1]), axis=1)
    want = np.sort(np.asarray(reference_idx), axis=1)
    return float((got != want).mean())


def compare_lm(program: dict, reference: dict) -> dict:
    gaps = compare(program, reference)
    gaps["route_mismatch_share"] = route_mismatch_share(
        program["first_route"], reference["first_route"])
    return gaps


def _jsonable(result: dict) -> dict:
    return {k: v for k, v in result.items() if k != "first_route"}


def check_readings(tr: Trainer, feed) -> dict:
    """The check's numbers from the program: the first reading's losses,
    the routed choice of its first step, and the state after its k steps."""
    *_, losses, routing, top_idx = tr.reading(feed)
    return {"losses": [float(v) for v in losses],
            "first_route": np.asarray(top_idx)[0], "routing": routing,
            **tr.state_norms()}


def run(cell, *, seed, seconds, trace, device, rehearsal, t_start):
    import jax
    from paddle_tpu.observability import metrics as prog_metrics

    cfg, spec = cell["config_file"], cell["traffic_file"]
    chips = min(cell["chips"], len(jax.devices()))
    out = common.out_dir(cell["name"], seed, trace)
    tr = Trainer(cfg, spec, seed, chips)
    log(f"trainer built: {tr.rows} rows x {tr.seq}, k={tr.k}")
    ring = [tr.device_feed(i) for i in range(spec["feed_ring"])]
    # the check's steps go through the window's own call and feed
    program = check_readings(tr, ring[0][0])
    tr.reading(ring[1 % len(ring)][0])      # second call: nothing compiles
    common.settle_heap()
    log(f"warm; first losses {program['losses']}, routing "
        f"{program['routing']}")
    misses0 = prog_metrics.get("executor.compile_cache_misses")
    dropped0 = prog_metrics.get("moe.tokens_dropped")
    compiles = common.CompileCounter()
    collections = common.CollectionLog()

    tokens_per_reading = tr.k * tr.rows * tr.seq
    readings, failed, trace_summary, tracing = [], 0, None, False
    profiler_s = 0.0
    logdir = os.path.join(out, "trace")
    setup_s = time.time() - t_start
    t_open = time.perf_counter()
    i = 0
    while time.perf_counter() - t_open < seconds:
        if trace and i == 1:
            t_prof = time.perf_counter()
            jax.profiler.start_trace(logdir)
            profiler_s += time.perf_counter() - t_prof
            tracing = True
        misses_before = prog_metrics.get("executor.compile_cache_misses")
        dispatch_s, total_s, cpu_s, losses, routing, _ = tr.reading(
            ring[i % len(ring)][0])
        bad = (not np.all(np.isfinite(losses))
               or prog_metrics.get("executor.compile_cache_misses")
               != misses_before)
        failed += int(bad)
        readings.append({"t": time.perf_counter() - t_open - total_s,
                         "dispatch_s": dispatch_s, "seconds": total_s,
                         "cpu_s": cpu_s, "loss_last": float(losses[-1]),
                         "failed": bool(bad), "routing": routing})
        if trace and i == TRACED_READINGS:
            t_prof = time.perf_counter()
            jax.profiler.stop_trace()
            profiler_s += time.perf_counter() - t_prof
            tracing = False
        i += 1
    # the window: the first reading's start to the last reading's end
    window_s = time.perf_counter() - t_open
    if tracing:
        jax.profiler.stop_trace()
    trace_path = step_hlo = None
    if trace and rehearsal is None:
        trace_path = xplane.newest_trace(logdir)
        trace_summary = xplane.reduce_trace(trace_path)
        # the executable the window ran, from the executor's own cache
        step_hlo = tr.exe.compiled_hlo(ring[0][0], tr.fetch, k=tr.k)
    memory = common.memory_peaks()
    compiled = (prog_metrics.get("executor.compile_cache_misses") - misses0
                + compiles.stop())
    dropped = prog_metrics.get("moe.tokens_dropped") - dropped0
    train_tok_s = stats.rate_over_window(
        len(readings) * tokens_per_reading, window_s - profiler_s)
    secs = [r["seconds"] for r in readings]
    common.write_json(os.path.join(out, "series.json"), {
        "workload": cell["name"], "seed": seed, "trace": trace,
        "tokens_per_reading": tokens_per_reading, "window_s": window_s,
        "profiler_s": profiler_s, "setup_s": setup_s, "readings": readings,
        "collections": collections.stop(t_open)})
    log(f"window closed: {len(readings)} readings in {window_s:.3f}s, median "
        f"{stats.median(secs):.4f}s, min {min(secs):.4f}, max {max(secs):.4f}")

    # the plain reference follows the k steps of the first reading, on a
    # chip the program has left; its time is not set-up and not the window
    tr.free()
    t_ref = time.perf_counter()
    reference = run_reference(tr, ring[0][1])
    gaps = compare_lm(program, reference)
    log(f"reference followed {tr.k} steps in "
        f"{time.perf_counter() - t_ref:.1f}s")
    checks = checks_from(gaps, spec["limits"], [
        {"name": "compiles_in_window", "value": compiled, "limit": 0},
        {"name": "failed_readings", "value": failed, "limit": 0},
        {"name": "tokens_dropped", "value": dropped, "limit": 0}])
    common.write_json(os.path.join(out, "checks.json"), {
        "checks": checks, "program": _jsonable(program),
        "reference": _jsonable(reference)})
    return common.finish(
        cell, device=device, trace=trace, rehearsal=rehearsal, checks=checks,
        attempted=len(readings), failed=failed, memory=memory,
        end_to_end={"train_tok_s": train_tok_s, "setup_s": setup_s},
        trace_summary=trace_summary,
        ctx={"kind": "train", "cfg": cfg, "spec": spec, "chips": chips,
             "rows": tr.rows, "seq": tr.seq, "k": tr.k, "readings": readings,
             "traced_readings": TRACED_READINGS, "train_tok_s": train_tok_s,
             "compiles_in_window": compiled, "trace_path": trace_path,
             "step_hlo": step_hlo})


def calibrate(cell, seeds, control_seeds):
    """The readings the limits are set from, at the cell's own size: the
    sound program's gaps on every seed of `seeds`, and on `control_seeds`
    what each fault the limits are there for would read: the fp8 control,
    a quarter of the rows left out, `SelectBias` left out of the
    selection. One process: the trainer is rebuilt per seed, its
    executable comes from the cache."""
    import jax
    cfg, spec = cell["config_file"], cell["traffic_file"]
    chips = min(cell["chips"], len(jax.devices()))
    rows = []
    for seed in seeds:
        tr = Trainer(cfg, spec, seed, chips)
        feed, host = tr.device_feed(0)
        program = check_readings(tr, feed)
        tr.free()
        reference = run_reference(tr, host)
        row = {"seed": seed, "program": compare_lm(program, reference),
               "routing": program["routing"]}
        if seed in control_seeds:
            row["fault_quarter_batch_loss_gap"] = _quarter_left_out(
                tr, host, reference)
            unbiased = dict(cfg, assumed=dict(cfg["assumed"],
                                              select_bias_std=0.0))
            row["fault_no_select_bias_route"] = route_mismatch_share(
                run_reference(tr, host, steps=1, cfg=unbiased)["first_route"],
                reference["first_route"])
            # last: the control is another program, loaded in the
            # reference's place
            row["control_fp8"] = compare_lm(
                run_reference(tr, host, "fp8"), reference)
        log(f"calibrate {cell['name']} {row}")
        rows.append(row)
        del tr
    return rows


def _quarter_left_out(tr, host, reference) -> float:
    """Loss gap of step 1 when the last quarter of the rows' positions
    contributes nothing (their labels removed) while the loss is still
    divided by every position's count: the fault the loss limit must
    catch."""
    labels = host["labels"].copy()
    n = labels.shape[2]
    labels[:, :, n - n // 4:] = lm_traffic.IGNORE
    cut = {"ids": host["ids"], "labels": labels}
    got = run_reference(tr, cut, steps=1)
    kept = float((labels[0] != lm_traffic.IGNORE).sum())
    full = float((host["labels"][0] != lm_traffic.IGNORE).sum())
    loss = got["losses"][0] * kept / full
    return abs(loss - reference["losses"][0]) / abs(reference["losses"][0])
