"""`scripts/step_table.py`: the phase x scope table of a saved trace and
HLO, its rows summing to the busy time, the unscoped residue by name."""
import io
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "scripts"))
sys.path.insert(0, ROOT)

import step_table  # noqa: E402
from benchmark import scopes  # noqa: E402

TRACE = os.path.join(ROOT, "benchmark", "tests", "data",
                     "s512_two_readings.xplane.pb")
OP_NAMES = ("jit(step)/phase.fwd/attn.proj/dot_general",
            "jit(step)/phase.bwd/attn.proj/transpose(jvp())/dot_general",
            "jit(step)/phase.opt/optimizer.adam/sub",
            "jit(step)/convert_element_type")


@pytest.fixture
def hlo_file(tmp_path):
    names = sorted(scopes.instruction_seconds(TRACE))
    path = tmp_path / "step.hlo.txt"
    path.write_text("\n".join(
        f'  %{n} = f32[8]{{0}} fusion(), metadata={{'
        f'op_name="{OP_NAMES[i % 4]}"}}' for i, n in enumerate(names)))
    return str(path)


def test_table_of_a_saved_trace_and_hlo(hlo_file, tmp_path, capsys):
    out = str(tmp_path / "rows.json")
    assert step_table.main(["--trace", TRACE, "--hlo", hlo_file,
                            "--out", out]) == 0
    printed = capsys.readouterr().out
    with open(out) as f:
        rec = json.load(f)
    assert sum(s for _, _, s in rec["table"]) == \
        pytest.approx(rec["busy0_s"], rel=1e-9)
    assert {(p, s) for p, s, _ in rec["table"]} == {
        ("fwd", "attn.proj"), ("bwd", "attn.proj"), ("opt", "optimizer.*"),
        ("none", "none")}
    # the sum line closes at 100 % and the residue is named
    total = next(l for l in printed.splitlines() if l.startswith("sum"))
    assert total.split()[-1] == "100.00"
    assert "jit(step)/convert_element_type" in printed
    assert len(rec["unscoped"]) == len(
        scopes.instruction_seconds(TRACE)) // 4


def test_table_of_saved_rows_prints_the_same(hlo_file, tmp_path, capsys):
    out = str(tmp_path / "rows.json")
    step_table.main(["--trace", TRACE, "--hlo", hlo_file, "--out", out])
    first = capsys.readouterr().out
    step_table.main(["--table", out])
    assert capsys.readouterr().out == first


def test_steps_turn_seconds_into_milliseconds_a_step():
    rec = {"busy0_s": 0.012, "steps": 6,
           "table": [["fwd", "attn.proj", 0.006], ["bwd", "none", 0.006]],
           "instructions": [], "unscoped": [
               [0.006, "fusion.7", "", "bwd", "none"]]}
    buf = io.StringIO()
    step_table.print_table(rec, out=buf)
    text = buf.getvalue()
    assert "ms/step" in text and "device 0 busy 2.000 ms/step" in text
    assert "fusion.7  (no op_name)" in text
