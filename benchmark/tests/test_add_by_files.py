"""A configuration, a traffic mix, a per-layer metric and a cell are each
added by new files and one new entry: no file that is there is edited."""
import hashlib
import json
import os
import shutil

from benchmark import common, run


def _hashes(root):
    out = {}
    for base, _, files in os.walk(root):
        if "__pycache__" in base or "benchmark_out" in base:
            continue
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def _copy(tmp_path):
    root = str(tmp_path)
    shutil.copytree(common.HERE, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    shutil.copy(os.path.join(common.ROOT, "BENCHMARK.json"), root)
    return root, os.path.join(root, "benchmark")


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A data-parallel cell on four chips (here four virtual devices) with
    a configuration, a mix and a per-layer metric of its own."""
    root, bench = _copy(tmp_path)
    before = _hashes(bench)

    with open(os.path.join(bench, "configs", "bert_base.json")) as f:
        config = json.load(f)
    with open(os.path.join(bench, "rehearsal", "bert_base.json")) as f:
        config.update(json.load(f))
    config["name"] = "dummy_model"
    common.write_json(os.path.join(bench, "configs", "dummy_model.json"),
                      config)
    common.write_json(os.path.join(bench, "traffic", "dummy_mix.json"), {
        "kind": "train_batches", "batch_per_chip": 2, "seq": 16,
        "steps_per_reading": 2, "padded": False, "label_rate": 0.25,
        "feed_ring": 2,
        "limits": {"loss_gap": 0.05, "moment1_gap": 0.5, "delta_gap": 0.5}})
    with open(os.path.join(bench, "metrics", "dummy_readings.py"), "w") as f:
        f.write("def read(ctx):\n    return float(len(ctx['readings']))\n")

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "dummy_model", "source": "test", "reduced": [],
        "file": "benchmark/configs/dummy_model.json", "why": "test"})
    manifest["workloads"].append({
        "name": "dummy_cell", "config": "dummy_model",
        "traffic": "dummy_mix", "chips": 4, "why": "test"})
    next(e for e in manifest["end_to_end"]
         if e["name"] == "train_tok_s")["workloads"].append("dummy_cell")
    manifest["per_layer"].append({
        "name": "dummy_readings", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "Executor",
        "moves": "train_tok_s", "workloads": ["dummy_cell"]})
    common.write_json(os.path.join(root, "BENCHMARK.json"), manifest)

    cell = common.find_cell(manifest, "dummy_cell", root)
    assert "dummy_readings" in [p["name"] for p in cell["per_layer"]]
    read = common.load_reader(cell["bench_dir"], "dummy_readings")
    assert read({"readings": [1, 2, 3]}) == 3.0
    # and the harness drives it end to end, finding everything by name
    res = run.run_cell("dummy_cell", 2147483659, 1.0, 0,
                       rehearsal={"config": {}, "traffic": {}}, root=root)
    assert res["correct"] and res["attempted"] >= 1 and not res["failed"]
    after = _hashes(bench)
    assert {k: v for k, v in after.items() if k in before} == before
    assert sorted(set(after) - set(before)) == [
        "configs/dummy_model.json", "metrics/dummy_readings.py",
        "traffic/dummy_mix.json"]
