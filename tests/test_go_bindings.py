"""Go inference bindings (go/paddle) over the C API — reference
go/paddle/{config,predictor,tensor}.go. The bindings are REVIEW-ONLY
(README "C-API serving contract"): the permanent compiled contract for
non-Python consumers is native/capi + the multi-threaded C client in
tests/test_capi_serving.py. Here the package structure is asserted
unconditionally, and the real `go test` runs wherever a Go toolchain
exists (this image ships none — that end-to-end test is the suite's one
formally re-scoped skip)."""
import os
import shutil
import subprocess
import sysconfig

import pytest

import paddle_tpu as paddle  # noqa: F401
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _save_model(tmp):
    from paddle_tpu.testing import reset_programs
    reset_programs(seed=0)
    x = layers.data(name="x", shape=[4], dtype="float32")
    h = layers.fc(x, 8, act="relu")
    p = layers.fc(h, 3)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    fluid.io.save_inference_model(tmp, ["x"], [p], exe)


def test_go_package_files_complete():
    """The package mirrors the reference's four files + a real test."""
    pkg = os.path.join(REPO, "go", "paddle")
    for f in ("common.go", "config.go", "predictor.go", "tensor.go",
              "predictor_test.go"):
        assert os.path.exists(os.path.join(pkg, f)), f
    src = open(os.path.join(pkg, "predictor.go")).read()
    for sym in ("NewPredictor", "Clone", "GetInputNames", "Run"):
        assert sym in src, sym


def test_go_predictor_end_to_end(tmp_path):
    go = shutil.which("go")
    if go is None:
        pytest.skip("no Go toolchain in this image")
    from paddle_tpu.inference.capi_bridge import build_capi
    libpath = build_capi()
    if libpath is None:
        pytest.skip("toolchain unavailable for capi")
    model = str(tmp_path / "model")
    _save_model(model)

    libdir = sysconfig.get_config_var("LIBDIR") or ""
    pyver = f"python{sysconfig.get_python_version()}"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"            # Go consumer runs on CPU
    env["PADDLE_TPU_GO_TEST_MODEL"] = model
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["CGO_ENABLED"] = "1"
    env["CGO_LDFLAGS"] = f"-L{libdir} -l{pyver}"
    env["LD_LIBRARY_PATH"] = os.pathsep.join(
        [os.path.dirname(libpath), libdir, env.get("LD_LIBRARY_PATH", "")])
    proc = subprocess.run([go, "test", "-v", "./paddle/..."],
                          cwd=os.path.join(REPO, "go"), env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "PASS" in proc.stdout
