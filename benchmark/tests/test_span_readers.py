"""The readers of the program's span tree against a recorded ring
(data/ring_s512_rehearsal.json: a CPU rehearsal of `bert_base_s512` at
the tiny preset, cut to set-up and the last readings, so its times say
nothing about the chip), and against a program that has no such tree."""
import json
import math
import os

import numpy as np
import pytest

from benchmark import common, spans

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "ring_s512_rehearsal.json")
SPAN_READERS = {
    "setup_import_s": 5.6425611,
    "setup_build_s": 0.5456144,          # build + the OUTER minimize only
    "setup_startup_run_s": 0.6351669,
    "setup_trace_lower_s": 2.3406618,    # under executor.step roots only
    "setup_compile_s": 0.5851571,
    "exec_prepare_ms.train": 1.91695,    # median of the last 6 roots
    "exec_launch_ms.train": 5.7482,
    "exec_commit_ms.train": 0.038,
}


@pytest.fixture(scope="module")
def ctx():
    with open(DATA) as f:
        rec = json.load(f)
    return {"kind": "train", "k": rec["k"], "spans": rec["spans"],
            "readings": [{}] * rec["readings"]}


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_reader_against_the_recorded_ring(ctx, name):
    got = common.load_reader(common.HERE, name)(ctx)
    assert got == pytest.approx(SPAN_READERS[name], rel=1e-6)


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_reader_finds_nothing_in_a_program_without_the_tree(ctx, name):
    """The parent commit: spans without id or parent, names with their
    payload. The reader returns None, and the line leaves the metric out."""
    old = [{"name": "executor_run_steps#4", "ph": "X", "ts": 1.0,
            "dur": 2.0, "args": {"step": 3}}]
    assert spans.of({"spans": old}) == old
    assert common.load_reader(common.HERE, name)(dict(ctx, spans=[])) is None


def test_the_ring_of_this_process_is_read_when_no_ring_is_handed_in():
    from paddle_tpu.observability import trace
    with trace.RecordEvent("benchmark.test.span"):
        pass
    got = [e for e in spans.of({}) if e["name"] == "benchmark.test.span"]
    assert len(got) == 1 and got[0]["parent"] is None and got[0]["id"] > 0


def test_what_ran_under_no_root_is_left_out_and_nested_counts_once(ctx):
    evs = ctx["spans"]
    loose = [e for e in evs if e["name"] == "compile.lower"
             and e["parent"] is None]
    assert loose                               # the reference's own jits
    rooted = spans.under_roots(evs, {"compile.lower"})
    assert rooted and not {e["id"] for e in loose} & {
        e["id"] for e in rooted}
    minimize = [e for e in evs if e["name"] == "optimizer.minimize"]
    assert len(minimize) == 2                  # the fleet wrapper's and Adam's
    assert len(spans.outermost(evs, {"optimizer.minimize"})) == 1
    window = spans.roots(evs, kind="run_steps", k=ctx["k"])[-6:]
    assert all(r["args"]["program"] == "main" for r in window)
    assert spans.roots(evs, program="startup")[0]["args"]["kind"] == "run"


def test_idle_gaps_cut_with_the_executors_root_events():
    reader = common.load_reader(common.HERE, "idle_in_executor_pct.train")
    idle_under = reader.__globals__["idle_under"]
    gaps = np.array([[0.0, 10.0], [20.0, 30.0], [50.0, 60.0]])
    roots = np.array([[5.0, 25.0], [58.0, 100.0]])
    assert idle_under(gaps, roots) == 5.0 + 5.0 + 2.0
    assert idle_under(gaps, np.zeros((0, 2))) == 0.0
    # no trace (a --trace 0 context), no number
    assert reader({"kind": "train", "trace": None}) is None


def test_idle_reader_on_a_cut_chip_trace(tmp_path, monkeypatch):
    """data/s512_pt_gap.xplane.pb: one traced reading of `bert_base_s512`
    on a TPU v5e from 8 ms before its `pt/executor.step` to 6 ms after it
    (my chip run, PR 24; device 0's operations and the pt/ host events
    only, clipped to the window). The device idles 11.41 ms there, 8.16 ms
    of it under the executor's root span and 3.25 ms before the caller
    reached `run_steps`."""
    import shutil
    reader = common.load_reader(common.HERE, "idle_in_executor_pct.train")
    g = reader.__globals__
    src = os.path.join(os.path.dirname(DATA), "s512_pt_gap.xplane.pb")
    gaps, roots = g["gaps_and_roots"](src)
    assert len(roots) == 1
    assert roots[0][1] - roots[0][0] == pytest.approx(11.39482e6, rel=1e-6)
    assert g["idle_under"](gaps, roots) == pytest.approx(8.156752e6,
                                                         rel=1e-6)
    assert sum(b - a for a, b in gaps) == pytest.approx(11.41168e6, rel=1e-6)
    # the reader takes the newest trace under benchmark_out/
    where = tmp_path / "cell" / "seed1_trace1" / "trace"
    where.mkdir(parents=True)
    shutil.copy(src, where / "cut.xplane.pb")
    monkeypatch.setattr(common, "OUT_ROOT", str(tmp_path))
    ctx = {"kind": "train", "trace": {"window_s": 25.394819e-3}}
    assert reader(ctx) == pytest.approx(100 * 8.156752 / 25.394819, rel=1e-6)
    # a trace without the program's events (a parent commit): nothing
    monkeypatch.setitem(g, "ROOT_EVENT", "pt/not.there")
    assert reader(ctx) is None
    monkeypatch.setattr(common, "OUT_ROOT", str(tmp_path / "empty"))
    assert reader(ctx) is None


def test_exposed_collective_share():
    reader = common.load_reader(common.HERE, "collective_exposed_pct")
    ctx = {"kind": "train",
           "trace": {"collective_exposed_s": 0.05, "window_s": 2.0}}
    assert reader(ctx) == pytest.approx(2.5)
    assert reader({"kind": "train", "trace": None}) is None
    assert math.isfinite(reader(ctx))


def test_the_span_metrics_and_the_four_chip_cell_are_listed():
    """test_manifest.py's view, extended to this PR's entries (here: no
    file the benchmark had may be edited)."""
    m = common.load_manifest()
    per_layer = {p["name"]: p for p in m["per_layer"]}
    setup = sorted(n for n in SPAN_READERS if n.startswith("setup_"))
    phases = sorted(n for n in SPAN_READERS if n.startswith("exec_"))
    assert len(setup) == 5 and len(phases) == 3
    for name in setup + phases:
        p = per_layer[name]
        assert p["source"] == "program_span" and "workloads" not in p
        assert p["moves"] == ("setup_s" if name in setup else "train_tok_s")
    assert per_layer["idle_in_executor_pct.train"]["source"] == "device_trace"
    assert per_layer["collective_exposed_pct"]["workloads"] == [
        "bert_base_s128_dp4"]
    # new entries stand at the end of their lists, after PR 23's
    names = [p["name"] for p in m["per_layer"]]
    assert names.index("hbm_live_gb.train") < names.index("setup_import_s")
    assert [w["name"] for w in m["workloads"]][:1] == ["bert_base_s512"]
    cell = common.find_cell(m, "bert_base_s128_dp4")
    assert cell["chips"] == 4 and cell["traffic_file"] == dict(
        cell["traffic_file"], batch_per_chip=128, seq=128, padded=False,
        label_rate=0.15, steps_per_reading=8, feed_ring=4)
    got = {p["name"] for p in cell["per_layer"]}
    assert set(setup + phases) | {"collective_exposed_pct", "mfu_pct",
                                  "idle_in_executor_pct.train"} <= got
    assert not {"flash_time_pct", "flash_attention_roofline"} & got
    one = {p["name"] for p in common.find_cell(m, "bert_base_s512")[
        "per_layer"]}
    assert set(setup + phases) <= one and "collective_exposed_pct" not in one
