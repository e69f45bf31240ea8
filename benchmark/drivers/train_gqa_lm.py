"""Driver of a causal-LM training cell whose layers differ by kind and whose
query heads share KV heads: the program's own trainer (`models.mellum`,
`fleet.distributed_optimizer`, `fluid.Executor.run_steps`) timed reading by
reading and held against the plain reference. Everything but the builder
call, the configuration's key names and the faults of `calibrate` is
`drivers/train_lm.py`'s, imported: the feed, a reading, the state norms,
the reference's blocks, the comparison. `train_lm.run` builds its own
module's `Trainer`, so `run` is repeated here word for word (PERF.md
section 7 names the fold for the next `benchmark` PR).
"""
from __future__ import annotations

import os
import time

import numpy as np

from .. import common, stats, xplane
from ..common import log
from . import train_lm
from .train import TRACED_READINGS, checks_from
from .train_lm import (_jsonable, _quarter_left_out, _unload_programs,
                       check_readings, compare_lm, run_reference)

# the configuration file's keys -> models.mellum.MellumConfig
_PUBLISHED = ("hidden_size", "num_attention_heads", "num_key_value_heads",
              "head_dim", "moe_intermediate_size", "num_experts_per_tok",
              "norm_topk_prob", "rms_norm_eps", "sliding_window",
              "rope_parameters")


class Trainer(train_lm.Trainer):
    """`train_lm.Trainer` with another builder: the one compiled step with
    its state that set-up builds, the check drives through its first steps
    and the window then times."""

    def __init__(self, cfg: dict, spec: dict, seed: int, chips: int):
        import jax
        import paddle_tpu as paddle
        import paddle_tpu.fluid as fluid
        from paddle_tpu.distributed import fleet
        from paddle_tpu.models import mellum
        from paddle_tpu.testing import reset_programs

        if chips != 1:
            raise common.Refused("the causal-LM driver runs one chip's "
                                 "share on one chip")
        self.cfg, self.spec, self.seed = cfg, spec, seed
        self.k = spec["steps_per_reading"]
        self.rows = spec["batch_per_chip"]
        self.seq = spec["seq"]
        self.ref = common.load_reference(cfg)
        self.model = mellum
        reset_programs(seed=seed % (2 ** 31))
        mcfg = mellum.MellumConfig(
            vocab_size=cfg["vocab"], num_hidden_layers=cfg["layers"],
            num_experts=cfg["experts_total"],
            experts_held=cfg["num_experts"],
            expert_offset=cfg["expert_offset"], seq_len=self.seq,
            layer_types=tuple(cfg["layer_types"][:cfg["layers"]]),
            initializer_range=cfg["assumed"]["initializer_std"],
            **{key: cfg[key] for key in _PUBLISHED})
        _, self.loss, routed = mellum.build_causal_lm_program(mcfg)
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
        # the losses, the first layer's routed choice and every layer's
        # load leave the device in ONE run_steps call
        self.fetch = [self.loss, routed[0][0]] + [r[1] for r in routed]
        self.exe = fluid.Executor()
        self.exe.run(fluid.default_startup_program())
        self.scope = fluid.global_scope()
        self.names = sorted(self.ref.param_shapes(cfg))
        # the benchmark's own weights, leaf by leaf on the device; the
        # reference starts from the same draws
        for name in self.names:
            if self.scope.find(name) is None:
                raise RuntimeError(f"the program has no parameter {name!r}")
            self.scope.set(name, self.fresh_leaf(name))


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


def faults(cfg: dict, seq: int) -> dict:
    """The configuration with one thing wrong, for each fault the new
    mechanisms admit: what `correct` must not take for the model."""
    rope = cfg["rope_parameters"]
    assumed = cfg["assumed"]
    return {
        "window_ignored": dict(cfg, sliding_window=seq),
        "yarn_left_out": dict(cfg, rope_parameters=dict(
            rope, full_attention=rope["sliding_attention"])),
        "sigmoid_scores": dict(cfg, assumed=dict(assumed,
                                                 scoring="sigmoid")),
        "kv_head_0_for_all": dict(cfg, assumed=dict(assumed,
                                                    kv_head_rule="first")),
    }


def calibrate(cell, seeds, control_seeds):
    """The readings the limits are set from, at the cell's own size: the
    sound program's gaps on every seed of `seeds`, and on `control_seeds`
    what each fault the limits are there for would read: the fp8 control, a
    quarter of the row left out, and the reference with each of `faults`
    against the sound reference. One process: the trainer is rebuilt per
    seed, its executable comes from the cache."""
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
            for name, wrong in faults(cfg, tr.seq).items():
                # another program of the reference's size: two do not fit
                _unload_programs()
                row["fault_" + name] = compare_lm(
                    run_reference(tr, host, cfg=wrong), reference)
            _unload_programs()
            row["control_fp8"] = compare_lm(
                run_reference(tr, host, "fp8"), reference)
        log(f"calibrate {cell['name']} {row}")
        rows.append(row)
        del tr
    return rows
