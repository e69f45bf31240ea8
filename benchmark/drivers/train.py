"""Driver of a training cell: the program's own trainer (`models.bert`,
`fleet.distributed_optimizer`, `fluid.Executor.run_steps`) timed reading
by reading, and held against the plain reference."""
from __future__ import annotations

import os
import statistics
import time

import numpy as np

from .. import common, stats, traffic, xplane
from ..common import log

TRACED_READINGS = 3


class Trainer:
    """The one compiled step with its state that set-up builds, the check
    drives through its first steps and the window then times."""

    def __init__(self, cfg: dict, spec: dict, seed: int, chips: int):
        import jax
        import paddle_tpu as paddle
        import paddle_tpu.fluid as fluid
        from paddle_tpu.distributed import fleet
        from paddle_tpu.models import bert
        from paddle_tpu.testing import reset_programs

        self.cfg, self.spec, self.seed = cfg, spec, seed
        self.k = spec["steps_per_reading"]
        self.rows = spec["batch_per_chip"] * chips
        self.seq = spec["seq"]
        self.ref = common.load_reference(cfg)
        reset_programs(seed=seed % (2 ** 31))
        mcfg = bert.BertConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            intermediate_size=cfg["intermediate_size"],
            max_position=cfg["max_position_embeddings"],
            hidden_dropout=cfg["hidden_dropout_prob"],
            attention_dropout=cfg["attention_probs_dropout_prob"])
        mcfg.seq_len = self.seq
        _, _, self.loss = bert.build_pretrain_program(
            mcfg, use_input_mask=bool(spec.get("padded")))
        fleet.init(is_collective=True)
        strategy = fleet.DistributedStrategy()
        strategy.amp = True
        fleet.distributed_optimizer(
            paddle.optimizer.Adam(learning_rate=self.ref.ADAM["lr"]),
            strategy).minimize(self.loss)
        self.devices = jax.devices()[:chips]
        if len(jax.devices()) > chips:
            # a host with more chips than the cell asks for: the same
            # program on a mesh cut to the cell's chips
            from paddle_tpu.parallel import DistConfig, attach, build_mesh
            prog = fluid.default_main_program()
            attach(prog, DistConfig(
                mesh=build_mesh(dp=chips, devices=self.devices),
                param_rules=prog._dist_config.param_rules))
        self.exe = fluid.Executor()
        self.exe.run(fluid.default_startup_program())
        self.scope = fluid.global_scope()
        # the benchmark's own weights, one jitted call on the device; the
        # reference starts from the same call
        self._init = jax.jit(lambda key: self.ref.init_params(cfg, key))
        for name, value in self.fresh_params().items():
            if self.scope.find(name) is None:
                raise RuntimeError(f"the program has no parameter {name!r}")
            self.scope.set(name, value)
        self.names = sorted(self.ref.param_shapes(cfg))

    def fresh_params(self) -> dict:
        return self._init(common.seed_key(self.seed))

    def device_feed(self, index: int) -> tuple:
        """(feed for run_steps, the host arrays the reference follows)."""
        import jax
        host = traffic.train_feed(self.spec, self.cfg["vocab_size"],
                                  self.rows, self.seed, index)
        feed = {"input_ids": host["ids"], "mlm_labels": host["labels"][..., None]}
        if host["mask"] is not None:
            feed["input_mask"] = host["mask"]
        return {n: jax.device_put(v) for n, v in feed.items()}, host

    def reading(self, feed) -> tuple:
        """One reading: `run_steps(k)` ending in the host read of its k
        losses. -> (seconds to the call's return, seconds in all, CPU
        seconds the process used meanwhile, losses)"""
        t0, c0 = time.perf_counter(), time.process_time()
        out, = self.exe.run_steps(self.k, feed=feed, fetch_list=[self.loss],
                                  return_numpy=False)
        t1 = time.perf_counter()
        losses = np.asarray(out, np.float64).reshape(-1)
        t2 = time.perf_counter()
        return t1 - t0, t2 - t0, time.process_time() - c0, losses

    def state_norms(self) -> dict:
        """Per-leaf norms of Adam's first moment as it stands, and of the
        parameters' change from the seeded weights."""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def norms(moments, params, p0):
            f32 = jnp.float32
            return ({n: jnp.linalg.norm(a.astype(f32))
                     for n, a in moments.items()},
                    {n: jnp.linalg.norm(a.astype(f32) - p0[n].astype(f32))
                     for n, a in params.items()})

        m, d = norms({n: self.scope.find(n + "_moment1_0")
                      for n in self.names},
                     {n: self.scope.find(n) for n in self.names},
                     self.fresh_params())
        m = {n: float(v) for n, v in m.items()}
        d = {n: float(v) for n, v in d.items()}
        return {"moment1_norms": m, "delta_norms": d}


def worst_leaf_gap(got: dict, want: dict) -> float:
    """The gap between the program's norm and the reference's, by the
    worst leaf, against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    floor = statistics.median(want.values())
    return max(abs(got[n] - want[n]) / max(want[n], floor) for n in want)


def compare(program: dict, reference: dict) -> dict:
    gaps = {f"loss_gap_step{i + 1}": abs(a - b) / abs(b)
            for i, (a, b) in enumerate(zip(program["losses"],
                                           reference["losses"]))}
    gaps["moment1_gap"] = worst_leaf_gap(program["moment1_norms"],
                                         reference["moment1_norms"])
    gaps["delta_gap"] = worst_leaf_gap(program["delta_norms"],
                                       reference["delta_norms"])
    return gaps


def run_reference(tr: Trainer, host_batches: dict, quant=None,
                  steps=None, mask_stream=0) -> dict:
    """The plain reference over the first `steps` (default: all k) batches
    of one feed, from the seeded weights, in blocks of rows; on a cell of
    several chips the rows of a block are split over them. Its dropout
    masks are stream `mask_stream` of the seed, never the program's."""
    import jax
    devs = tr.devices
    rows = max(1, tr.cfg["reference_tokens_per_block"] // tr.seq)
    shard = None
    if len(devs) > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.array(devs), ("d",))
        rows *= len(devs)

        def shard(a):
            return jax.device_put(a, NamedSharding(
                mesh, P("d", *([None] * (a.ndim - 1)))))
    batches = [{"ids": host_batches["ids"][i],
                "labels": host_batches["labels"][i],
                "mask": (None if host_batches["mask"] is None
                         else host_batches["mask"][i])}
               for i in range(steps or tr.k)]
    drop_key = jax.random.fold_in(common.seed_key(tr.seed), 1 + mask_stream)
    return tr.ref.follow(tr.cfg, tr.fresh_params(), batches, rows,
                         quant=quant, shard=shard, drop_key=drop_key)


def checks_from(gaps: dict, limits: dict, extra: list) -> list:
    out = []
    for name, value in gaps.items():
        key = "loss_gap" if name.startswith("loss_gap") else name
        out.append({"name": name, "value": value, "limit": limits[key]})
    return out + extra


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
    *_, first_losses = tr.reading(ring[0][0])
    program = {"losses": [float(v) for v in first_losses], **tr.state_norms()}
    tr.reading(ring[1 % len(ring)][0])      # second call: nothing compiles
    common.settle_heap()
    log(f"warm; first losses {program['losses']}")
    misses0 = prog_metrics.get("executor.compile_cache_misses")
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
        dispatch_s, total_s, cpu_s, losses = tr.reading(
            ring[i % len(ring)][0])
        bad = (not np.all(np.isfinite(losses))
               or prog_metrics.get("executor.compile_cache_misses")
               != misses_before)
        failed += int(bad)
        readings.append({"t": time.perf_counter() - t_open - total_s,
                         "dispatch_s": dispatch_s, "seconds": total_s,
                         "cpu_s": cpu_s,
                         "loss_last": float(losses[-1]), "failed": bool(bad)})
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
    if trace and rehearsal is None:
        trace_summary = xplane.reduce_trace(xplane.newest_trace(logdir))
    memory = common.memory_peaks()
    compiled = (prog_metrics.get("executor.compile_cache_misses") - misses0
                + compiles.stop())
    # all the work over all the time: a stall anywhere in the window is in
    # the number. Only the profiler's own start and stop calls, which a
    # `--trace 1` run makes between readings, are taken out
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

    # the plain reference follows the k steps of the first reading; its
    # time is not set-up and not the window
    t_ref = time.perf_counter()
    reference = run_reference(tr, ring[0][1])
    gaps = compare(program, reference)
    log(f"reference followed {tr.k} steps in "
        f"{time.perf_counter() - t_ref:.1f}s")
    checks = checks_from(gaps, spec["limits"], [
        {"name": "compiles_in_window", "value": compiled, "limit": 0},
        {"name": "failed_readings", "value": failed, "limit": 0}])
    common.write_json(os.path.join(out, "checks.json"), {
        "checks": checks, "program": program, "reference": reference})
    tr.exe.close()
    return common.finish(
        cell, device=device, trace=trace, rehearsal=rehearsal, checks=checks,
        attempted=len(readings), failed=failed, memory=memory,
        end_to_end={"train_tok_s": train_tok_s, "setup_s": setup_s},
        trace_summary=trace_summary,
        ctx={"kind": "train", "cfg": cfg, "spec": spec, "chips": chips,
             "rows": tr.rows, "seq": tr.seq, "k": tr.k, "readings": readings,
             "traced_readings": TRACED_READINGS, "train_tok_s": train_tok_s,
             "compiles_in_window": compiled})


def calibrate(cell, seeds, control_seeds):
    """The readings the limits are set from, at the cell's own size: the
    sound program's gaps on every seed of `seeds`, and on `control_seeds`
    the gaps of the control (the reference with every matmul operand
    rounded to float8_e4m3, under other dropout masks) against the float32
    reference. One process:
    the trainer is rebuilt per seed, its executable comes from the cache."""
    import jax
    cfg, spec = cell["config_file"], cell["traffic_file"]
    chips = min(cell["chips"], len(jax.devices()))
    rows = []
    for seed in seeds:
        tr = Trainer(cfg, spec, seed, chips)
        feed, host = tr.device_feed(0)
        *_, losses = tr.reading(feed)
        program = {"losses": [float(v) for v in losses], **tr.state_norms()}
        tr.exe.close()
        reference = run_reference(tr, host)
        row = {"seed": seed, "program": compare(program, reference)}
        if seed in control_seeds:
            # the control stands in the program's place: lower precision
            # and masks the reference does not know
            row["control_fp8"] = compare(
                run_reference(tr, host, "fp8", mask_stream=1), reference)
            # what two mask streams alone differ by, in full precision
            row["other_masks"] = compare(
                run_reference(tr, host, mask_stream=1), reference)
            # what a part of the batch left out, or a step that returns its
            # state unchanged, would read
            row["fault_quarter_batch_loss_gap"] = _quarter_left_out(
                tr, host, reference)
        log(f"calibrate {cell['name']} {row}")
        rows.append(row)
        del tr
    return rows


def _quarter_left_out(tr, host, reference) -> float:
    """Loss gap of step 1 when the last quarter of the rows contributes
    nothing (their labels removed), the fault the loss limit must catch."""
    cut = {k: (None if v is None else v.copy()) for k, v in host.items()}
    n = cut["labels"].shape[1]
    cut["labels"][:, n - n // 4:] = -100
    got = run_reference(tr, cut, steps=1)
    return abs(got["losses"][0] - reference["losses"][0]) / abs(
        reference["losses"][0])
