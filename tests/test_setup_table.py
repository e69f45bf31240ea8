"""scripts/setup_table.py: every microsecond of set-up in exactly one row,
on a hand-made ring and on the benchmark's recorded rehearsal ring (CPU,
tiny preset: its times say nothing about the chip)."""
import importlib.util
import io
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RING = os.path.join(ROOT, "benchmark", "tests", "data",
                    "ring_setup_rehearsal.json")


@pytest.fixture(scope="module")
def table():
    spec = importlib.util.spec_from_file_location(
        "setup_table", os.path.join(ROOT, "scripts", "setup_table.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def rings():
    with open(RING) as f:
        return json.load(f)


def _span(i, name, ts, dur, parent=None, **args):
    return {"name": name, "ph": "X", "ts": ts * 1e6, "dur": dur * 1e6,
            "id": i, "parent": parent, "args": args}


def _hand_made():
    def root(i, ts, dur, program="main", kind="run_steps", k=2):
        return _span(i, "executor.step", ts, dur, kind=kind, k=k,
                     program=program)
    return [
        _span(1, "startup.boot", 0, 10),
        _span(2, "startup.import", 10, 2),
        _span(3, "program.build", 12, 1),
        _span(4, "compile.trace", 12.2, 0.5, 3),        # shape inference
        root(5, 14, 2, program="startup", kind="run", k=1),
        _span(6, "compile.backend", 14.5, 1, 5, cache="hit", fetch_s=0.9),
        _span(7, "compile.backend", 17, 1, cache="miss_written"),  # harness
        root(8, 20, 10),
        _span(9, "executor.launch", 20.5, 9, 8),
        _span(10, "compile.trace", 21, 4, 9),
        _span(11, "executor.lower_block", 21.5, 1, 9, ops=3,
              shapes_only=True, by_op=[["mul", 2, 0.5]]),
        _span(12, "executor.lower_block", 23, 1.5, 9, ops=3,
              shapes_only=False, by_op=[["adam", 1, 1.0], ["mul", 2, 0.25]]),
        _span(13, "compile.lower", 25, 1, 9),
        _span(14, "compile.backend", 26, 2, 9, cache="hit", fetch_s=1.5,
              fun="jit(step)"),
        _span(15, "compile.backend", 28, 1, 9, cache="miss", fun="jit(x)"),
        _span(16, "stage", 30.5, 0.25),
        root(17, 31, 1), root(18, 33, 1), root(19, 35, 1),
    ]


def test_every_moment_is_in_exactly_one_row(table):
    rows = table.breakdown(_hand_made(), k=2, readings=2)
    assert set(rows) == set(table.ROWS)
    assert sum(rows.values()) == pytest.approx(33.0)    # to root 18's start
    want = {"boot": 10, "import": 2, "build": 1, "startup run": 2,
            "step: the program's lowering": 2.5,
            "step: JAX's tracing and MLIR": 1.5 + 1,    # trace less the walks
            "step: backend, fetched": 2, "step: backend, built": 1,
            "step: rest of the dispatches": 2 + 1,      # root 8's own + root 17
            "the harness's own compiles": 1, "other spans": 0.25,
            "outside spans": 33 - 10 - 2 - 1 - 2 - 1 - 10 - 0.25 - 1}
    for name, seconds in want.items():
        assert rows[name] == pytest.approx(seconds), name


def test_the_steps_compiles_and_its_by_op_table(table):
    evs = _hand_made()
    assert table.step_compiles(evs) == [
        ["jit(step)", "hit", pytest.approx(2.0), 1.5],
        ["jit(x)", "miss", pytest.approx(1.0), None]]
    assert table.by_op_of_the_step(evs) == [["adam", 1, 1.0],
                                            ["mul", 2, 0.25]]
    out = io.StringIO()
    table.print_table({"k": 2, "readings": 2, "spans": evs}, setup_s=32.9,
                      out=out)
    text = out.getvalue()
    assert "compile.backend jit(x): cache=miss" in text
    assert "(setup_s 32.900)" in text and "by_op adam" in text


def test_the_cut_keeps_set_up_and_the_windows_roots(table):
    evs = _hand_made() + [_span(20, "compile.backend", 40, 5)]  # reference
    cut = table.cut_to_setup(evs, k=2, readings=2)
    ids = {e["id"] for e in cut}
    assert 20 not in ids and {1, 8, 17, 18, 19} <= ids
    assert table.breakdown(cut, 2, 2) == table.breakdown(evs, 2, 2)


@pytest.mark.parametrize("run", ["cold", "warm"])
def test_rows_of_the_recorded_ring_agree_with_the_benchmarks_readers(
        table, rings, run):
    from benchmark import common
    rec = rings[run]
    rows = table.breakdown(rec["spans"], rec["k"], rec["readings"])
    ctx = {"kind": "train", "k": rec["k"], "spans": rec["spans"],
           "readings": [{}] * rec["readings"]}

    def read(name):
        return common.load_reader(common.HERE, name)(ctx)
    assert rows["boot"] == pytest.approx(read("setup_boot_s"))
    assert rows["import"] == pytest.approx(read("setup_import_s"))
    assert rows["outside spans"] == pytest.approx(
        read("setup_outside_spans_s"), abs=1e-6)
    assert rows["step: backend, built"] <= read(
        "setup_compile_built_s") + 1e-6
    # the reader counts the startup program's walk too; here that one is
    # part of the startup run's row
    by = {e["id"]: e for e in rec["spans"]}
    startup_walks = sum(
        e["dur"] for e in rec["spans"] if e["name"] == "executor.lower_block"
        and by[by[e["parent"]]["parent"]]["args"]["program"] == "startup")
    assert startup_walks > 0
    assert rows["step: the program's lowering"] == pytest.approx(
        read("setup_lower_block_s") - startup_walks * 1e-6, rel=1e-3)
    lo = [e for e in rec["spans"] if e["name"] == "startup.boot"][0]["ts"]
    hi = table.window_start(rec["spans"], rec["k"], rec["readings"])
    assert sum(rows.values()) == pytest.approx((hi - lo) * 1e-6)
    if run == "warm":
        assert rows["step: backend, built"] == 0.0
        assert rows["step: backend, fetched"] > 0.0
    else:
        assert rows["step: backend, fetched"] == 0.0
