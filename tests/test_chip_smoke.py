"""chip_smoke.py must not rot between chip runs: every leg's function runs
here at the TINY preset on the CPU mesh (Pallas interpreted, no Mosaic
assertions), and the guards around the chip — refusing another backend,
where the compile cache lives, which devices have peak figures, bench.py's
exit code — are pinned as units. None of this is a run on the chip."""
import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def clock():
    return chip_smoke.CompileClock()


@pytest.mark.parametrize("name,leg", chip_smoke.LEGS,
                         ids=[n for n, _ in chip_smoke.LEGS])
def test_leg_runs_at_tiny_preset(name, leg, clock):
    row = chip_smoke.run_leg(name, leg, chip_smoke.TINY, clock)
    assert row["pass"] and row["wall_s"] > 0
    json.dumps(row)                      # the summary line must serialize


def test_parted_tokens_pass_only_at_a_near_tie():
    """The serving arms may part from the plain pass where two tokens
    score within NEAR_TIE under the reference, and nowhere else."""
    gaps = {1: 0.0, 2: 0.05, 3: 4.0, 9: 0.0}
    ref = types.SimpleNamespace(
        gap=lambda req, emitted, token, tol: gaps[token])
    want = {"a": [3, 1, 0], "b": [2, 2]}
    row = chip_smoke.same_tokens(ref, {"a": None, "b": None}, want,
                                 {"a": [3, 2, 9], "b": [2, 2]}, "arm")
    assert row == {"identical": 1, "near_tie_splits": {
        "a": {"index": 1, "gaps": [0.0, 0.05]}}}
    with pytest.raises(chip_smoke.SmokeFailure, match="no near tie"):
        chip_smoke.same_tokens(ref, {"a": None, "b": None}, want,
                               {"a": [3, 3, 0], "b": [2, 2]}, "arm")
    with pytest.raises(chip_smoke.SmokeFailure, match="emitted 2 tokens"):
        chip_smoke.same_tokens(ref, {"a": None, "b": None}, want,
                               {"a": [3, 1], "b": [2, 2]}, "arm")


def test_main_refuses_a_backend_that_is_not_a_tpu(capsys):
    assert chip_smoke.main() == 2        # tier-1 runs with JAX_PLATFORMS=cpu
    out = capsys.readouterr()
    assert out.out == ""                 # no result line
    assert "needs a TPU" in out.err and out.err.count("\n") == 1


def test_main_refuses_an_environment_that_hides_kernels(monkeypatch, capsys):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert chip_smoke.main() == 2
    assert "PADDLE_TPU_PALLAS_INTERPRET" in capsys.readouterr().err


def test_last_stdout_line_is_the_result_object_and_nothing_more(monkeypatch,
                                                               capsys):
    """The driver parses the last stdout line: exactly `ok` and `device`,
    the device exactly platform / kind / count. Legs go on the line above."""
    import jax
    from paddle_tpu import compile_cache
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])
    monkeypatch.setattr(compile_cache, "enable", lambda: "/nowhere")
    monkeypatch.setattr(chip_smoke, "LEGS", (
        ("train_bert_base_s128", lambda preset, clock: {"steps": 0}),
        ("four_chips", lambda preset, clock: pytest.fail("one device"))))
    assert chip_smoke.main() == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    summary = json.loads(lines[-2])["summary"]
    assert summary["claim"] is None
    assert summary["legs"]["train_bert_base_s128"]["pass"] is True
    assert summary["legs"]["four_chips"] == "not run (1 device)"


# --- the compile-cache helper ----------------------------------------------

@pytest.fixture
def cache_config(monkeypatch):
    """Records what enable() sets, without touching this process's jax."""
    import jax
    seen = {}
    monkeypatch.setattr(jax.config, "update", seen.__setitem__)
    return seen


def test_cache_dir_from_env_is_never_set_in_code(monkeypatch, cache_config):
    from paddle_tpu import compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.enable() == "/some/dir"
    assert "jax_compilation_cache_dir" not in cache_config


def test_cache_dir_defaults_to_the_checkout(monkeypatch, cache_config):
    from paddle_tpu import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(ROOT, ".jax_cache")
    assert compile_cache.enable() == want
    assert cache_config["jax_compilation_cache_dir"] == want
    # sub-second programs (prefill buckets, decode windows) are kept too
    assert cache_config["jax_persistent_cache_min_compile_time_secs"] == 0.0


# --- bench.py: peaks by device_kind, and the exit code -----------------------

def test_peaks_are_keyed_by_device_kind():
    import bench
    assert bench.device_peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(RuntimeError, match="no peak figures"):
        bench.device_peaks("TPU v9 imaginary")


def _fake_tpu(monkeypatch, kind="TPU v5 lite"):
    import jax
    from paddle_tpu import compile_cache
    dev = types.SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])
    monkeypatch.setattr(compile_cache, "enable", lambda: "/nowhere")
    monkeypatch.setenv("BENCH_WHICH", "none")      # primary row only


def test_bench_exits_nonzero_without_a_tpu(capsys):
    import bench
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code == 2
    assert capsys.readouterr().out == ""


def test_bench_exits_nonzero_on_an_unknown_device_kind(monkeypatch):
    import bench
    _fake_tpu(monkeypatch, kind="TPU v9 imaginary")
    with pytest.raises(RuntimeError, match="no peak figures"):
        bench.main()


def test_bench_exits_nonzero_when_a_requested_row_raises(monkeypatch,
                                                         capsys):
    import bench
    _fake_tpu(monkeypatch)

    def boom(*a, **kw):
        raise ValueError("row blew up")

    monkeypatch.setattr(bench, "bench_bert", boom)
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code == 1
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "row blew up" in rec["error"] and rec["value"] is None
    assert rec["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                             "count": 1}
