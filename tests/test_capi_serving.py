"""C inference API (reference inference/capi/paddle_c_api.h + the Go
binding go/paddle/predictor.go consume this shape of surface): create a
predictor from a saved inference model, run it through the C ABI, clone per
serving thread. Two layers of proof:

* ctypes in-process — the C ABI marshalling round-trips and matches the
  Python Predictor numerically;
* a REAL C program (g++-compiled, pthreads) — create + clone-per-thread +
  concurrent runs from C with no Python in the consumer's code.
"""
import ctypes
import os
import subprocess
import sys
import sysconfig
import tempfile
import textwrap

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers


class PD_CTensor(ctypes.Structure):
    _fields_ = [("name", ctypes.c_char * 64),
                ("dtype", ctypes.c_int),
                ("ndim", ctypes.c_int),
                ("shape", ctypes.c_int64 * 8),
                ("data", ctypes.c_void_p),
                ("byte_len", ctypes.c_size_t)]


def _save_model(tmp):
    from paddle_tpu.testing import reset_programs
    reset_programs(seed=0)
    x = layers.data(name="x", shape=[4], dtype="float32")
    h = layers.fc(x, 8, act="relu")
    p = layers.fc(h, 3)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    fluid.io.save_inference_model(tmp, ["x"], [p], exe)
    return p


def _lib():
    from paddle_tpu.inference.capi_bridge import build_capi
    path = build_capi()
    if path is None:
        pytest.skip("toolchain unavailable for capi")
    lib = ctypes.CDLL(path)
    lib.PD_PredictorCreate.restype = ctypes.c_void_p
    lib.PD_PredictorCreate.argtypes = [ctypes.c_char_p]
    lib.PD_PredictorClone.restype = ctypes.c_void_p
    lib.PD_PredictorClone.argtypes = [ctypes.c_void_p]
    lib.PD_PredictorDestroy.argtypes = [ctypes.c_void_p]
    lib.PD_PredictorNumInputs.argtypes = [ctypes.c_void_p]
    lib.PD_PredictorNumOutputs.argtypes = [ctypes.c_void_p]
    lib.PD_PredictorInputName.restype = ctypes.c_char_p
    lib.PD_PredictorInputName.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.PD_PredictorRun.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(PD_CTensor), ctypes.c_int,
        ctypes.POINTER(ctypes.POINTER(PD_CTensor)),
        ctypes.POINTER(ctypes.c_int)]
    lib.PD_FreeOutputs.argtypes = [ctypes.POINTER(PD_CTensor), ctypes.c_int]
    lib.PD_GetLastError.restype = ctypes.c_char_p
    return lib


def _run_once(lib, pred, xv):
    t = PD_CTensor()
    t.name = b"x"
    t.dtype = 0
    t.ndim = len(xv.shape)
    for d, s in enumerate(xv.shape):
        t.shape[d] = s
    buf = np.ascontiguousarray(xv)
    t.data = buf.ctypes.data_as(ctypes.c_void_p)
    t.byte_len = buf.nbytes
    outs = ctypes.POINTER(PD_CTensor)()
    n_out = ctypes.c_int()
    rc = lib.PD_PredictorRun(pred, ctypes.byref(t), 1, ctypes.byref(outs),
                             ctypes.byref(n_out))
    assert rc == 0, lib.PD_GetLastError().decode()
    assert n_out.value == 1
    o = outs[0]
    shape = tuple(o.shape[d] for d in range(o.ndim))
    arr = np.frombuffer(
        ctypes.string_at(o.data, o.byte_len), np.float32).reshape(shape)
    arr = arr.copy()
    lib.PD_FreeOutputs(outs, n_out.value)
    return arr


def test_capi_matches_python_predictor(tmp_path):
    d = str(tmp_path / "model")
    _save_model(d)
    lib = _lib()
    pred = lib.PD_PredictorCreate(d.encode())
    assert pred, lib.PD_GetLastError().decode()
    assert lib.PD_PredictorNumInputs(pred) == 1
    assert lib.PD_PredictorNumOutputs(pred) == 1
    assert lib.PD_PredictorInputName(pred, 0) == b"x"

    from paddle_tpu.inference import Config, Predictor
    py_pred = Predictor(Config(d))
    xv = np.random.RandomState(0).randn(5, 4).astype(np.float32)
    got = _run_once(lib, pred, xv)
    py_pred.get_input_handle("x").copy_from_cpu(xv)
    want = py_pred.run()[0]
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5)
    lib.PD_PredictorDestroy(pred)


def test_capi_clone_serving_threads(tmp_path):
    """threads x clone(): each thread serves on its own clone (shared
    weights), results identical to the base predictor's."""
    import threading
    d = str(tmp_path / "model")
    _save_model(d)
    lib = _lib()
    base = lib.PD_PredictorCreate(d.encode())
    assert base, lib.PD_GetLastError().decode()
    rng = np.random.RandomState(1)
    feeds = [rng.randn(3, 4).astype(np.float32) for _ in range(4)]
    want = [_run_once(lib, base, f) for f in feeds]
    results, errs = [None] * 4, []

    def serve(i):
        try:
            clone = lib.PD_PredictorClone(base)
            assert clone, lib.PD_GetLastError().decode()
            for _ in range(3):                      # steady-state serving
                results[i] = _run_once(lib, clone, feeds[i])
            lib.PD_PredictorDestroy(clone)
        except Exception as e:                      # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=serve, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive(), "serving thread hung past join timeout"
    assert not errs, errs
    for got, exp in zip(results, want):
        np.testing.assert_allclose(got, exp, rtol=1e-5)
    lib.PD_PredictorDestroy(base)


C_PROGRAM = textwrap.dedent("""
    #include <pthread.h>
    #include <stdint.h>
    #include <stdio.h>
    #include <stdlib.h>
    #include <string.h>

    typedef struct {
      char name[64]; int dtype; int ndim; int64_t shape[8];
      void* data; size_t byte_len;
    } PD_CTensor;
    typedef struct PD_Predictor PD_Predictor;
    #ifdef __cplusplus
    extern "C" {
    #endif
    extern int PD_Init();
    extern PD_Predictor* PD_PredictorCreate(const char*);
    extern PD_Predictor* PD_PredictorClone(PD_Predictor*);
    extern void PD_PredictorDestroy(PD_Predictor*);
    extern int PD_PredictorRun(PD_Predictor*, const PD_CTensor*, int,
                               PD_CTensor**, int*);
    extern void PD_FreeOutputs(PD_CTensor*, int);
    extern const char* PD_GetLastError();
    #ifdef __cplusplus
    }
    #endif

    static PD_Predictor* base;
    static float results[4];

    static void* serve(void* arg) {
      long tid = (long)arg;
      PD_Predictor* p = PD_PredictorClone(base);
      if (!p) { fprintf(stderr, "clone: %s\\n", PD_GetLastError()); exit(3); }
      float in[8];
      for (int i = 0; i < 8; i++) in[i] = (float)(tid + 1);
      PD_CTensor t; memset(&t, 0, sizeof t);
      snprintf(t.name, 64, "x"); t.dtype = 0; t.ndim = 2;
      t.shape[0] = 2; t.shape[1] = 4;
      t.data = in; t.byte_len = sizeof in;
      for (int rep = 0; rep < 3; rep++) {
        PD_CTensor* outs; int n_out;
        if (PD_PredictorRun(p, &t, 1, &outs, &n_out) != 0) {
          fprintf(stderr, "run: %s\\n", PD_GetLastError()); exit(4);
        }
        if (n_out != 1 || outs[0].shape[0] != 2 || outs[0].shape[1] != 3) {
          fprintf(stderr, "bad output shape\\n"); exit(5);
        }
        results[tid] = ((float*)outs[0].data)[0];
        PD_FreeOutputs(outs, n_out);
      }
      PD_PredictorDestroy(p);
      return NULL;
    }

    int main(int argc, char** argv) {
      PD_Init();
      base = PD_PredictorCreate(argv[1]);
      if (!base) { fprintf(stderr, "create: %s\\n", PD_GetLastError());
                   return 2; }
      pthread_t th[4];
      for (long i = 0; i < 4; i++) pthread_create(&th[i], NULL, serve,
                                                  (void*)i);
      for (int i = 0; i < 4; i++) pthread_join(th[i], NULL);
      // same weights => same input must give same value across threads'
      // clones; different inputs must differ
      for (int i = 1; i < 4; i++)
        if (results[i] == results[0]) { fprintf(stderr,
            "thread outputs identical for distinct inputs\\n"); return 6; }
      printf("C_SERVING_OK %f %f %f %f\\n", results[0], results[1],
             results[2], results[3]);
      return 0;
    }
""")


def test_capi_from_real_c_program(tmp_path):
    from paddle_tpu.inference.capi_bridge import build_capi
    libpath = build_capi()
    if libpath is None:
        pytest.skip("toolchain unavailable for capi")
    d = str(tmp_path / "model")
    _save_model(d)
    src = tmp_path / "serve.c"
    src.write_text(C_PROGRAM)
    exe_path = str(tmp_path / "serve")
    libdir = sysconfig.get_config_var("LIBDIR") or ""
    pyver = f"python{sysconfig.get_python_version()}"
    compile_cmd = ["g++", str(src), "-o", exe_path, libpath,
                   f"-L{libdir}", f"-l{pyver}", "-lpthread",
                   f"-Wl,-rpath,{os.path.dirname(libpath)}",
                   f"-Wl,-rpath,{libdir}"]
    subprocess.run(compile_cmd, check=True, capture_output=True, text=True)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"                # C consumer runs on CPU
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([exe_path, d], capture_output=True, text=True,
                          timeout=300, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "C_SERVING_OK" in proc.stdout, proc.stdout
