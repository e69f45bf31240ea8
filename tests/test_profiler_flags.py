"""Profiler/timeline, flags, NaN-Inf debug, monitor stats.

Mirrors reference tests test_profiler.py, test_nan_inf.py and the
platform/monitor.h stat registry behavior.
"""
import json

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers


def test_profiler_collects_spans_and_exports_timeline(tmp_path):
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    loss = layers.reduce_mean(layers.square(layers.fc(x, size=4)))
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    paddle.profiler.reset_profiler()
    path = str(tmp_path / "timeline.json")
    with fluid.profiler.profiler(profile_path=path):
        for _ in range(3):
            exe.run(feed={"x": np.ones((2, 4), np.float32)},
                    fetch_list=[loss])
    with open(path) as f:
        trace = json.load(f)
    names = [e["name"] for e in trace["traceEvents"]]
    assert len(names) >= 3
    assert "executor.step" in names and "executor.launch" in names
    # complete ("X") spans carry ts+dur; the export may also include
    # thread-name metadata ("M") and instant/flow events (no dur)
    spans = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    assert spans and all("ts" in e and "dur" in e for e in spans)
    # lanes are labeled with REAL thread ids + name metadata (process_*
    # metadata — rank/role lane labels — rides along without tids)
    metas = [e for e in trace["traceEvents"] if e.get("ph") == "M"]
    assert any(e["name"] == "thread_name" for e in metas)
    assert any(e["name"] == "process_name" for e in metas)
    span_tids = {e["tid"] for e in spans}
    assert span_tids <= {e["tid"] for e in metas
                         if e["name"] == "thread_name"}


def test_flags_set_get_and_env_rejects_unknown():
    assert paddle.get_flags("FLAGS_check_nan_inf")["FLAGS_check_nan_inf"] \
        is False
    paddle.set_flags({"FLAGS_check_nan_inf": True})
    assert paddle.get_flags("FLAGS_check_nan_inf")["FLAGS_check_nan_inf"]
    paddle.set_flags({"FLAGS_check_nan_inf": False})
    with pytest.raises(KeyError, match="unknown flag"):
        paddle.set_flags({"FLAGS_not_a_flag": 1})


def test_check_nan_inf_names_the_variable():
    x = fluid.layers.data(name="x", shape=[2], dtype="float32")
    bad = layers.log(x)  # log of negative -> nan
    exe = fluid.Executor()
    paddle.set_flags({"FLAGS_check_nan_inf": True})
    try:
        with pytest.raises(FloatingPointError, match=bad.name):
            exe.run(feed={"x": -np.ones((1, 2), np.float32)},
                    fetch_list=[bad])
        # warn-only level
        paddle.set_flags({"FLAGS_check_nan_inf_level": 1})
        with pytest.warns(UserWarning, match="NaN/Inf"):
            exe.run(feed={"x": -np.ones((1, 2), np.float32)},
                    fetch_list=[bad])
    finally:
        paddle.set_flags({"FLAGS_check_nan_inf": False,
                          "FLAGS_check_nan_inf_level": 0})


def test_monitor_stats():
    paddle.monitor.stat_reset()
    paddle.monitor.stat_add("reader_queue_size", 5)
    paddle.monitor.stat_add("reader_queue_size", 3)
    assert paddle.monitor.stat_get("reader_queue_size") == 8
    paddle.monitor.stat_set("high_watermark", 123)
    assert paddle.monitor.all_stats()["high_watermark"] == 123
    paddle.monitor.stat_reset("high_watermark")
    assert paddle.monitor.stat_get("high_watermark") == 0
    # device stats shape only (may be empty off-TPU)
    assert isinstance(paddle.monitor.device_memory_stats(), dict)
