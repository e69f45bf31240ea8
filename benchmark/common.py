"""What every cell's run shares: the manifest, the look for the chip, the
per-layer readers, the output directory and the result line."""
from __future__ import annotations

import importlib
import importlib.util
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_ROOT = os.path.join(ROOT, "benchmark_out")


class Refused(SystemExit):
    """The run cannot be a measurement (no chip, too few chips, unknown
    device kind, unknown cell): exit non-zero and print no result."""

    def __init__(self, why: str, code: int = 3):
        print(f"[benchmark] refused: {why}", file=sys.stderr, flush=True)
        super().__init__(code)


_T_IMPORT = time.time()


def log(msg: str):
    print(f"[benchmark +{time.time() - _T_IMPORT:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(manifest: dict, name: str, root: str = ROOT) -> dict:
    """The cell's entry with its configuration file and traffic file read
    in; everything is found by the names in BENCHMARK.json."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json "
                      f"(have {sorted(cells)})", 2)
    cell = dict(cells[name])
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        cell["config_file"] = json.load(f)
    bench_dir = os.path.dirname(os.path.dirname(
        os.path.join(root, cfg_entry["file"])))
    with open(os.path.join(bench_dir, "traffic",
                           cell["traffic"] + ".json")) as f:
        cell["traffic_file"] = json.load(f)
    cell["bench_dir"] = bench_dir

    def listed(metric):
        return "workloads" not in metric or name in metric["workloads"]

    cell["end_to_end"] = [m for m in manifest["end_to_end"] if listed(m)]
    moved = {m["name"] for m in cell["end_to_end"]}
    cell["per_layer"] = [m for m in manifest["per_layer"]
                         if listed(m) and m["moves"] in moved]
    return cell


def load_reference(cfg: dict):
    """The configuration's plain reference, `reference/<name>.py`."""
    return importlib.import_module("benchmark.reference." + cfg["reference"])


def load_driver(cfg: dict):
    return importlib.import_module("benchmark.drivers." + cfg["driver"])


def seed_key(seed: int):
    """--seed may pass 2**31; a JAX key takes 31 bits of it."""
    import jax
    return jax.random.key(seed % (2 ** 31))


def open_cell(name: str, root: str = ROOT, tiny: dict | None = None):
    """(cell, device, driver) of a run: the chips the cell asks for, or,
    with `tiny` (rehearsals and tests only), no look for a chip and the
    tiny sizes laid over the files'. Turns the compile cache on."""
    cell = find_cell(load_manifest(root), name, root)
    if tiny is None:
        device = require_chips(cell["chips"])
    else:
        device = None
        cell["config_file"].update(tiny.get("config", {}))
        cell["traffic_file"].update(tiny.get("traffic", {}))
    from paddle_tpu import compile_cache
    compile_cache.enable()
    return cell, device, load_driver(cell["config_file"])


def require_chips(chips: int):
    """The accelerator, or no run. Returns the `device` object of the
    result line (without the memory peak, read after the window)."""
    import jax
    from .peaks import device_peaks
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"JAX found platform {devs[0].platform!r}, not a TPU")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {len(devs)}")
    try:
        device_peaks(devs[0].device_kind)
    except RuntimeError as e:
        raise Refused(str(e))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peaks() -> dict:
    """Peak HBM on the fullest chip, in its two parts. On a TPU
    `peak_bytes_in_use` counts the live arrays (weights, optimizer state,
    feeds) and `peak_bytes_reserved` what the loaded programs hold beside
    them for their temporaries: for a training step its activations and
    workspace, 8.4 GB of the 10.7 GB a BERT-base step takes (my chip run,
    PR 23). `memory_peak_bytes` of the result line is their sum."""
    import jax
    best = {"live": 0, "program_temporaries": 0}
    for d in jax.devices():
        stats = d.memory_stats() or {}
        got = {"live": int(stats.get("peak_bytes_in_use", 0)),
               "program_temporaries": int(stats.get("peak_bytes_reserved", 0))}
        if sum(got.values()) > sum(best.values()):
            best = got
    return best


def settle_heap():
    """Before the window opens: collect what set-up left behind and freeze
    the survivors out of the collector's reach. Tracing a 12-layer program
    leaves millions of objects; a full collection over them inside the
    window is a stall of seconds on the host (one stall of 2.7 s in a run
    of the serving cell this PR first built, before this call was there:
    my chip run, PR 23)."""
    import gc
    gc.collect()
    gc.freeze()


def out_dir(workload: str, seed: int, trace: int) -> str:
    path = os.path.join(OUT_ROOT, workload, f"seed{seed}_trace{trace}")
    os.makedirs(path, exist_ok=True)
    return path


def write_json(path: str, obj) -> None:
    with open(path, "w") as f:
        json.dump(obj, f)


def load_reader(bench_dir: str, metric_name: str):
    """The reader of one per-layer metric: `metrics/<name>.py`, a module
    with `read(ctx) -> float | None`."""
    path = os.path.join(bench_dir, "metrics", metric_name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + metric_name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_per_layer(cell: dict, ctx: dict) -> dict:
    """Every per-layer metric of the cell whose reader finds something."""
    units = {m["name"]: m["unit"] for m in cell["per_layer"]}
    out = {}
    for name, unit in units.items():
        value = load_reader(cell["bench_dir"], name)(ctx)
        if value is None or not math.isfinite(value):
            continue
        out[name] = {"value": float(value), "unit": unit}
    return out


def print_checks(checks: list) -> bool:
    """Each number compared beside its limit; True when all hold."""
    ok = True
    for c in checks:
        holds = c["value"] <= c["limit"]
        ok = ok and holds
        print(f"[benchmark] check {c['name']}: {c['value']:.6g} "
              f"(limit {c['limit']:.6g}) {'ok' if holds else 'FAILED'}",
              file=sys.stderr, flush=True)
    return ok


def finish(cell, *, device, trace, rehearsal, checks, attempted, failed,
           end_to_end, memory, ctx, trace_summary):
    """What a driver returns: the contract's result line, with the cell's
    end-to-end metrics (`--trace 0`) or its per-layer metrics, the device's
    busy time and the breakdown (`--trace 1`). A rehearsal's result is a
    dict without any metric."""
    correct = print_checks(checks)
    if rehearsal is not None:
        return {"rehearsal": True, "correct": correct,
                "attempted": attempted, "failed": failed, "checks": checks}
    device = dict(device, memory_peak_bytes=sum(memory.values()))
    if not trace:
        metrics = {m["name"]: {"value": float(end_to_end[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell["end_to_end"]}
        return result_line(correct=correct, attempted=attempted,
                           failed=failed, metrics=metrics, device=device)
    from . import xplane
    from .peaks import device_peaks
    ctx = dict(ctx, trace=trace_summary, memory=memory,
               peaks=device_peaks(device["kind"]))
    device.update(busy_s=trace_summary["busy_s"],
                  window_s=trace_summary["window_s"])
    return result_line(correct=correct, attempted=attempted, failed=failed,
                       metrics=read_per_layer(cell, ctx), device=device,
                       breakdown=xplane.breakdown(trace_summary))


def result_line(*, correct, attempted, failed, metrics, device,
                breakdown=None) -> str:
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    return json.dumps(line)


class CompileCounter:
    """Counts XLA backend compilations (a fetch from the persistent cache
    counts too: inside the window neither may happen) from JAX's own
    monitoring events, from construction until `stop()`."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring as mon
        self.count = 0
        self._on = True
        mon.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, seconds, **_):
        if self._on and event == self.EVENT:
            self.count += 1

    def stop(self) -> int:
        self._on = False
        return self.count


class CollectionLog:
    """The garbage collector's passes from construction until `stop()`, as
    [generation, seconds since `t_open`, seconds it took]: with a reading's
    `cpu_s` in series.json it says whether a slow reading was the
    collector, other work of this process, or the process not running."""

    def __init__(self):
        import gc
        self.rows, self._t0 = [], 0.0
        gc.callbacks.append(self._listen)

    def _listen(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._t0 = now
        else:
            self.rows.append([info["generation"], self._t0, now - self._t0])

    def stop(self, t_open: float) -> list:
        import gc
        gc.callbacks.remove(self._listen)
        return [[g, t - t_open, d] for g, t, d in self.rows]
