"""The trace reduction against a small recorded trace: the last 6 ms of
one reading of `bert_base_s512` on a TPU v5e, the 10.3 ms in which the
host prepared the next `run_steps` call, and the first 6 ms of that one
(cut from my chip run of PR 23; events clipped to the window, stats
dropped)."""
import os

import numpy as np
import pytest

from benchmark import xplane

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "s512_two_readings.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return xplane.reduce_trace(TRACE)


def test_busy_idle_and_window(summary):
    assert summary["devices"] == 1
    assert summary["window_s"] == pytest.approx(22.306e-3, rel=1e-3)
    assert summary["busy_s"] == pytest.approx(11.99e-3, rel=2e-3)
    idle = summary["window_s"] - summary["busy_s"]
    assert idle == pytest.approx(10.31e-3, rel=5e-3)
    assert sum(s for _, s in summary["idle_gaps"]) == pytest.approx(
        idle, rel=0.01)


def test_operations_are_named_by_base_name_and_loops_are_not_counted(summary):
    ops = summary["ops"]
    assert "while" not in ops and "divide_subtract_fusion" in ops
    assert all(" = " not in name and "%" not in name for name in ops)
    assert sum(ops.values()) == pytest.approx(summary["busy0_s"], rel=0.02)
    assert xplane.kernel_seconds(summary, "flash_attention") == pytest.approx(
        1.0799e-3, rel=1e-3)
    assert summary["modules"]["jit__unknown"]["calls"] == 2


def test_idle_gaps_are_named_by_the_host_span_under_them(summary):
    top, seconds = summary["idle_gaps"][0]
    assert top == "executor.py:1483__run_steps_impl"
    assert seconds == pytest.approx(7.66e-3, rel=0.01)
    b = xplane.breakdown(summary)
    assert len(b["device_ops"]) <= 10 and b["device_ops"][0][0] == \
        "divide_subtract_fusion"


def test_base_name():
    assert xplane.base_name(
        "%fusion.16 = (u32[1]{0}, u32[1]{0}) fusion(u32[2]{0} %key.1), "
        "kind=kLoop") == "fusion"
    assert xplane.base_name("%all-reduce-start.3.1 = f32[8] x()") == \
        "all-reduce-start"
    assert xplane.base_name("copy.4") == "copy"


def test_collective_time_not_hidden_behind_compute():
    coll = xplane.union(np.array([[0.0, 10.0], [20.0, 30.0]]))
    comp = xplane.union(np.array([[5.0, 22.0], [21.0, 25.0], [40.0, 50.0]]))
    exposed = xplane.subtract(coll, comp)
    assert exposed.tolist() == [[0.0, 5.0], [25.0, 30.0]]
    assert xplane.total(exposed) == 10.0
    assert xplane.COLLECTIVE.match("all-reduce-done")
    assert not xplane.COLLECTIVE.match("fusion")
