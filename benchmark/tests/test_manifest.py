"""BENCHMARK.json against the contract's rules that a file can be held
to without a chip, and the last line's shape."""
import json
import os
import re

from benchmark import common

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_manifest_keeps_the_contract():
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        raw = f.read()
    m = json.loads(raw)
    assert len(raw) <= 64 * 1024
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51
    # a full check of 24 cells has to fit into 43200 s
    assert 1200 + (2 + 14 * 24) * (m["run_seconds"] + 60) + 24 * 180 <= 43200
    configs = {c["name"]: c for c in m["configs"]}
    cells = {w["name"]: w for w in m["workloads"]}
    assert len(cells) == len(m["workloads"]) <= 24
    assert len({(w["config"], w["traffic"]) for w in m["workloads"]}) == len(
        cells)
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        assert c["file"].startswith(m["paths"][0] + "/")
        with open(os.path.join(common.ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == c["reduced"]
        assert any(w["config"] == c["name"] for w in m["workloads"])
        for key in c["reduced"]:
            assert not re.search(r"(_dim$|_rank$|_size$|hidden|intermediate|head)",
                                 key), key
    four = 0
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        four += w["chips"] == 4
    assert four <= max(1, len(cells) // 4)
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(names) == len(set(names))
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.1
    for p in m["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert p["moves"] in e2e and p["source"] in SOURCES
        assert os.path.exists(os.path.join(
            common.HERE, "metrics", p["name"] + ".py")), p["name"]
    for x in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher")
        assert all(w in cells for w in x.get("workloads", []))
    for name in cells:
        cell = common.find_cell(m, name)
        assert len(cell["end_to_end"]) >= 2, name
        assert len(cell["per_layer"]) >= 1, name
        for p in cell["per_layer"]:
            moved = e2e[p["moves"]]
            assert name in moved.get("workloads", [name])


def test_result_line_has_the_keys_the_driver_reads():
    line = json.loads(common.result_line(
        correct=True, attempted=30, failed=0,
        metrics={"train_tok_s": {"value": 1.5, "unit": "tokens/s"}},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 1}))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device"]
    assert "\n" not in common.result_line(
        correct=False, attempted=0, failed=0, metrics={}, device={},
        breakdown={"device_ops": [["a", 1.0]], "idle_gaps": []})


def test_every_reader_loads():
    for p in common.load_manifest()["per_layer"]:
        assert callable(common.load_reader(common.HERE, p["name"]))


def test_without_an_accelerator_the_command_refuses_and_prints_no_result():
    import subprocess
    import sys
    m = common.load_manifest()
    proc = subprocess.run(
        [sys.executable] + m["command"][1:] + [
            "--workload", m["workloads"][0]["name"], "--seed", "4000000007",
            "--seconds", "1", "--trace", "0"],
        cwd=common.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "not a TPU" in proc.stderr
