"""The device step's account (`benchmark/step_account.py`) on the recorded
trace: every instruction in one row, the rows summing to the busy time."""
import os

import pytest

from benchmark import common, scopes, step_account, xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACE = os.path.join(DATA, "s512_two_readings.xplane.pb")
SEGMENT = "jit(step)/phase.bwd/transpose(jvp(phase.fwd))/jvp()/checkpoint/"
# op_names dealt out to the trace's instructions in turn: every phase, a
# scope inside a transform, one without a scope, one without a name
OP_NAMES = (
    "jit(step)/while/body/phase.fwd/layer.residual/add",
    "jit(step)/while/body/phase.fwd/jvp(ffn.dense)/dot_general",
    SEGMENT + "attn.proj/dot_general",
    SEGMENT + "rematted_computation/attn.proj/dot_general",
    "jit(step)/while/body/phase.bwd/head.mlm/transpose(jvp())/mul",
    "jit(step)/while/body/phase.opt/optimizer.adam/sub",
    "jit(step)/while/body/closed_call",
    "",
)


def _ctx():
    names = sorted(scopes.instruction_seconds(TRACE))
    hlo = "\n".join(
        f'  %{name} = f32[8]{{0}} fusion(), kind=kLoop, metadata={{'
        f'op_name="{OP_NAMES[i % len(OP_NAMES)]}"}}'
        if OP_NAMES[i % len(OP_NAMES)] else
        f"  %{name} = f32[8]{{0}} copy()"
        for i, name in enumerate(names))
    return {"kind": "train", "trace": xplane.reduce_trace(TRACE),
            "trace_path": TRACE, "step_hlo": hlo}, names


def test_rows_sum_to_the_busy_time_each_instruction_in_one():
    ctx, names = _ctx()
    rows = step_account.instructions(ctx)
    assert sorted(r[1] for r in rows) == names
    table = step_account.table(ctx)
    busy = ctx["trace"]["busy0_s"]
    assert sum(table.values()) == pytest.approx(busy, rel=1e-9)
    assert {phase for phase, _ in table} == set(step_account.PHASES)
    by_phase = [step_account.share(ctx, phases=(p,))
                for p in step_account.PHASES]
    assert sum(by_phase) == pytest.approx(100.0, abs=1e-6)
    # the rows without a layer scope: the loop's own and the nameless
    seconds = scopes.instruction_seconds(TRACE)
    want = sum(seconds[n] for i, n in enumerate(names)
               if i % len(OP_NAMES) >= 6)
    assert step_account.share(ctx, layer_scopes=("none",)) == \
        pytest.approx(100.0 * want / busy)


@pytest.mark.parametrize("metric, positive", [
    ("unscoped_time_pct", True), ("fwd_time_pct", True),
    ("bwd_time_pct", True), ("recompute_time_pct", True),
    ("head_time_pct", True), ("attn_proj_time_pct", True),
    ("dense_ffn_time_pct", True), ("residual_time_pct", True)])
def test_readers_over_the_account(metric, positive):
    read = common.load_reader(common.HERE, metric)
    ctx, _ = _ctx()
    assert 0.0 < read(ctx) < 100.0
    # no trace (an untraced run), and a tree without the catalogue (a
    # parent commit): nothing, and nothing raised
    assert read({"kind": "train"}) is None
    ctx, _ = _ctx()
    inner, step_account._catalogue = step_account._catalogue, lambda: None
    try:
        assert read(ctx) is None
    finally:
        step_account._catalogue = inner


def test_a_layer_no_instruction_names_reads_nothing():
    ctx, _ = _ctx()
    ctx["step_hlo"] = ctx["step_hlo"].replace("rematted_computation/", "")
    assert common.load_reader(common.HERE, "recompute_time_pct")(ctx) is None
    assert common.load_reader(common.HERE, "bwd_time_pct")(ctx) > 0


def test_an_account_that_is_not_the_traces_is_refused():
    ctx, _ = _ctx()
    ctx["trace"] = dict(ctx["trace"], busy0_s=2 * ctx["trace"]["busy0_s"])
    assert step_account.table(ctx) is None
    ctx, _ = _ctx()
    ctx["step_hlo"] = "HloModule other\n"
    assert step_account.table(ctx) is None


def test_overlapping_operations_are_counted_once():
    got = step_account.exclusive([
        (0.0, 10.0, "fusion.1"), (4.0, 6.0, "all-reduce.2"),
        (8.0, 14.0, "copy.3"), (20.0, 21.0, "fusion.1")])
    assert got == {"fusion.1": 7.0, "all-reduce.2": 2.0, "copy.3": 6.0}
    assert sum(got.values()) == 15.0      # the union of the intervals
