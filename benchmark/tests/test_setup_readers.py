"""The four set-up readers of PR 34 against a recorded ring
(data/ring_setup_rehearsal.json: a CPU rehearsal of `bert_base_s512` at
the tiny preset, run twice against one temporary compile cache, cold then
warm, each cut to set-up and its window's readings, so its times say
nothing about the chip), and against a program without the new spans."""
import json
import os

import pytest

from benchmark import common, spans

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "ring_setup_rehearsal.json")
SETUP_READERS = {
    "cold": {"setup_boot_s": 0.045255163,
             "setup_compile_built_s": 3.172389029,   # all of setup_compile_s
             "setup_lower_block_s": 0.815450611,
             "setup_outside_spans_s": 0.287844808},
    "warm": {"setup_boot_s": 0.044787398,
             "setup_compile_built_s": 0.0,           # every backend a fetch
             "setup_lower_block_s": 0.912746014,
             "setup_outside_spans_s": 0.303476133},
}
NEW = sorted(SETUP_READERS["cold"])


@pytest.fixture(scope="module")
def rings():
    with open(DATA) as f:
        recs = json.load(f)
    return {run: {"kind": "train", "k": rec["k"], "spans": rec["spans"],
                  "readings": [{}] * rec["readings"]}
            for run, rec in recs.items()}


def read(ctx, name):
    return common.load_reader(common.HERE, name)(ctx)


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("run", ["cold", "warm"])
def test_reader_against_the_recorded_ring(rings, run, name):
    assert read(rings[run], name) == pytest.approx(
        SETUP_READERS[run][name], rel=1e-6, abs=1e-9)


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_without_the_new_spans(rings, name):
    """The parent commit: the same ring without `startup.boot`,
    `executor.lower_block` and the `cache` arg. Each reader returns None,
    and the line leaves the metric out."""
    old = []
    for e in rings["warm"]["spans"]:
        if e["name"] in ("startup.boot", "executor.lower_block"):
            continue
        if e["name"] == "compile.backend":
            e = dict(e, args={k: v for k, v in e["args"].items()
                              if k not in ("cache", "fetch_s")})
        old.append(e)
    assert read(dict(rings["warm"], spans=old), name) is None
    assert read(dict(rings["warm"], spans=[]), name) is None
    # the five readers that were there read the same from both
    for kept in ("setup_import_s", "setup_compile_s", "setup_trace_lower_s"):
        assert read(dict(rings["warm"], spans=old), kept) == read(
            rings["warm"], kept)


def test_what_the_four_split_is_what_the_old_five_read(rings):
    for run, ctx in rings.items():
        compile_s = read(ctx, "setup_compile_s")
        built = read(ctx, "setup_compile_built_s")
        assert 0.0 <= built <= compile_s + 1e-9
        # the program's own lowering lies inside JAX's trace of the step
        assert 0 < read(ctx, "setup_lower_block_s") < read(
            ctx, "setup_trace_lower_s")
    cold, warm = rings["cold"], rings["warm"]
    assert read(cold, "setup_compile_built_s") == pytest.approx(
        read(cold, "setup_compile_s"))
    backend = spans.under_roots(warm["spans"], {"compile.backend"})
    assert {e["args"]["cache"] for e in backend} == {"hit"}
    assert all(e["args"]["fetch_s"] > 0 for e in backend)
    assert {e["args"]["cache"] for e in spans.under_roots(
        cold["spans"], {"compile.backend"})} == {"miss_written"}


def test_outside_spans_is_the_interval_less_the_union_of_all_spans(rings):
    reader = common.load_reader(common.HERE, "setup_outside_spans_s")

    def span(i, name, ts, dur, parent=None, **args):
        return {"name": name, "ph": "X", "ts": ts, "dur": dur, "id": i,
                "parent": parent, "args": args}

    def root(i, ts, k=2):
        return span(i, "executor.step", ts, 1e6, kind="run_steps", k=k,
                    program="main")
    evs = [span(1, "startup.boot", 0.0, 4e6),
           span(2, "startup.import", 4e6, 2e6),
           span(3, "compile.backend", 7e6, 2e6),       # the harness's own
           span(4, "compile.lower", 8e6, 2e6),         # overlaps it by 1 s
           root(5, 12e6), span(6, "executor.launch", 12.1e6, 0.5e6, 5),
           root(7, 15e6), root(8, 17e6), root(9, 19e6)]
    ctx = {"kind": "train", "k": 2, "spans": evs, "readings": [{}] * 2}
    # to the start of the window's first root (the second to last): 17 s,
    # less boot 4, import 2, the compiles' union 3, two set-up roots 2
    assert reader(ctx) == pytest.approx(17.0 - 4 - 2 - 3 - 2)
    assert reader(dict(ctx, readings=[{}] * 3)) == pytest.approx(15 - 10)
    assert reader(dict(ctx, readings=[])) is None
    assert reader(dict(ctx, k=4)) is None              # no such window
    assert reader(dict(ctx, kind="serve")) is None


def test_the_four_entries_stand_at_the_end_of_per_layer():
    """test_manifest.py's view of this PR's entries (no file the
    benchmark had may be edited)."""
    m = common.load_manifest()
    assert [p["name"] for p in m["per_layer"]][-4:] == [
        "setup_boot_s", "setup_compile_built_s", "setup_lower_block_s",
        "setup_outside_spans_s"]
    layers = {"setup_boot_s": "Start-up", "setup_compile_built_s": "Compile",
              "setup_lower_block_s": "Executor",
              "setup_outside_spans_s": "Start-up"}
    known = {p["layer"] for p in m["per_layer"][:-4]}
    for p in m["per_layer"][-4:]:
        assert p == {"name": p["name"], "unit": "s", "better": "lower",
                     "source": "program_span", "layer": layers[p["name"]],
                     "moves": "setup_s"}
        assert p["layer"] in known
        assert os.path.exists(os.path.join(common.HERE, "metrics",
                                           p["name"] + ".py"))
    # no `workloads` list: every cell reports them, as it reports setup_s
    for w in m["workloads"]:
        got = {p["name"] for p in common.find_cell(m, w["name"])["per_layer"]}
        assert set(NEW) <= got, w["name"]
