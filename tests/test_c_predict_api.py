"""C predict ABI: ctypes drive of libmxtpu_predict.so, plus a true
standalone C embedding host.

Reference analogue: include/mxnet/c_predict_api.h consumers
(amalgamation, matlab wrapper) driving MXPredCreate/SetInput/Forward/
GetOutput against a saved symbol+params.
"""
import ctypes
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
LIB = os.path.join(REPO, "mxnet_tpu", "_native", "libmxtpu_predict.so")


def _build_lib():
    if not shutil.which("make"):
        pytest.skip("no make toolchain")
    r = subprocess.run(["make", "-C", REPO], capture_output=True, text=True)
    if r.returncode != 0 or not os.path.exists(LIB):
        pytest.skip("predict lib build failed: %s" % r.stderr[-500:])


def _save_model(tmp_path):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data=data, num_hidden=6, name="fc1")
    net = mx.sym.Activation(data=net, act_type="relu")
    net = mx.sym.FullyConnected(data=net, num_hidden=3, name="fc2")
    net = mx.sym.SoftmaxOutput(data=net, name="softmax")
    shapes = {"data": (2, 5)}
    arg_shapes, _, _ = net.infer_shape(**shapes)
    rng = np.random.RandomState(0)
    params = {"arg:" + n: mx.nd.array(rng.randn(*s).astype(np.float32) * 0.3)
              for n, s in zip(net.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    pfile = str(tmp_path / "model.params")
    mx.nd.save(pfile, params)
    x = rng.rand(2, 5).astype(np.float32)
    # reference output through the Python Predictor
    from mxnet_tpu.predictor import Predictor
    pred = Predictor(net.tojson(), pfile, shapes)
    pred.forward(data=x)
    return net.tojson(), pfile, x, pred.get_output(0)


def _load():
    lib = ctypes.CDLL(LIB)
    lib.MXGetLastError.restype = ctypes.c_char_p
    return lib


def test_ctypes_predict_roundtrip(tmp_path):
    _build_lib()
    sym_json, pfile, x, ref = _save_model(tmp_path)
    lib = _load()
    param_blob = open(pfile, "rb").read()

    handle = ctypes.c_void_p()
    keys = (ctypes.c_char_p * 1)(b"data")
    indptr = (ctypes.c_uint32 * 2)(0, 2)
    shape_data = (ctypes.c_uint32 * 2)(2, 5)
    rc = lib.MXPredCreate(sym_json.encode(), param_blob, len(param_blob),
                          1, 0, 1, keys, indptr, shape_data,
                          ctypes.byref(handle))
    assert rc == 0, lib.MXGetLastError()

    xs = np.ascontiguousarray(x)
    rc = lib.MXPredSetInput(handle, b"data",
                            xs.ctypes.data_as(
                                ctypes.POINTER(ctypes.c_float)),
                            xs.size)
    assert rc == 0, lib.MXGetLastError()
    assert lib.MXPredForward(handle) == 0, lib.MXGetLastError()

    sdata = ctypes.POINTER(ctypes.c_uint32)()
    ndim = ctypes.c_uint32()
    rc = lib.MXPredGetOutputShape(handle, 0, ctypes.byref(sdata),
                                  ctypes.byref(ndim))
    assert rc == 0, lib.MXGetLastError()
    shape = tuple(sdata[i] for i in range(ndim.value))
    assert shape == (2, 3)

    out = np.zeros(shape, dtype=np.float32)
    rc = lib.MXPredGetOutput(handle, 0,
                             out.ctypes.data_as(
                                 ctypes.POINTER(ctypes.c_float)),
                             out.size)
    assert rc == 0, lib.MXGetLastError()
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    # error path: wrong output size
    bad = np.zeros(5, dtype=np.float32)
    rc = lib.MXPredGetOutput(handle, 0,
                             bad.ctypes.data_as(
                                 ctypes.POINTER(ctypes.c_float)),
                             bad.size)
    assert rc == -1
    assert b"size mismatch" in lib.MXGetLastError()
    assert lib.MXPredFree(handle) == 0

    # error path: bad symbol json
    h2 = ctypes.c_void_p()
    rc = lib.MXPredCreate(b"not json", param_blob, len(param_blob), 1, 0,
                          1, keys, indptr, shape_data, ctypes.byref(h2))
    assert rc == -1
    assert len(lib.MXGetLastError()) > 0


def test_ctypes_ndlist(tmp_path):
    _build_lib()
    lib = _load()
    arrs = {"mean_img": mx.nd.array(np.arange(6, dtype=np.float32)
                                    .reshape(2, 3))}
    pfile = str(tmp_path / "mean.nd")
    mx.nd.save(pfile, arrs)
    blob = open(pfile, "rb").read()
    handle = ctypes.c_void_p()
    length = ctypes.c_uint32()
    rc = lib.MXNDListCreate(blob, len(blob), ctypes.byref(handle),
                            ctypes.byref(length))
    assert rc == 0, lib.MXGetLastError()
    assert length.value == 1
    key = ctypes.c_char_p()
    data = ctypes.POINTER(ctypes.c_float)()
    shape = ctypes.POINTER(ctypes.c_uint32)()
    ndim = ctypes.c_uint32()
    rc = lib.MXNDListGet(handle, 0, ctypes.byref(key), ctypes.byref(data),
                         ctypes.byref(shape), ctypes.byref(ndim))
    assert rc == 0, lib.MXGetLastError()
    assert key.value == b"mean_img"
    assert tuple(shape[i] for i in range(ndim.value)) == (2, 3)
    vals = np.ctypeslib.as_array(data, shape=(6,))
    np.testing.assert_array_equal(vals, np.arange(6, dtype=np.float32))
    assert lib.MXNDListFree(handle) == 0


C_HOST = r"""
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include "mxnet_tpu/c_predict_api.h"

static char *read_file(const char *path, long *size) {
  FILE *f = fopen(path, "rb");
  if (!f) return NULL;
  fseek(f, 0, SEEK_END); *size = ftell(f); fseek(f, 0, SEEK_SET);
  char *buf = (char *)malloc(*size + 1);
  if (fread(buf, 1, *size, f) != (size_t)*size) { fclose(f); return NULL; }
  buf[*size] = 0; fclose(f);
  return buf;
}

int main(int argc, char **argv) {
  long sym_size, param_size;
  char *sym_json = read_file(argv[1], &sym_size);
  char *params = read_file(argv[2], &param_size);
  if (!sym_json || !params) { fprintf(stderr, "read fail\n"); return 2; }

  const char *keys[] = {"data"};
  mx_uint indptr[] = {0, 2};
  mx_uint dims[] = {2, 5};
  PredictorHandle h;
  if (MXPredCreate(sym_json, params, (int)param_size, 1, 0, 1, keys,
                   indptr, dims, &h) != 0) {
    fprintf(stderr, "create: %s\n", MXGetLastError()); return 3;
  }
  float x[10];
  for (int i = 0; i < 10; ++i) x[i] = (float)i / 10.0f;
  if (MXPredSetInput(h, "data", x, 10) != 0) {
    fprintf(stderr, "set_input: %s\n", MXGetLastError()); return 4;
  }
  if (MXPredForward(h) != 0) {
    fprintf(stderr, "forward: %s\n", MXGetLastError()); return 5;
  }
  mx_uint *shape, ndim;
  if (MXPredGetOutputShape(h, 0, &shape, &ndim) != 0) return 6;
  mx_uint total = 1;
  for (mx_uint i = 0; i < ndim; ++i) total *= shape[i];
  float *out = (float *)malloc(total * sizeof(float));
  if (MXPredGetOutput(h, 0, out, total) != 0) {
    fprintf(stderr, "get_output: %s\n", MXGetLastError()); return 7;
  }
  for (mx_uint i = 0; i < total; ++i) printf("%.6f ", out[i]);
  printf("\n");
  MXPredFree(h);
  return 0;
}
"""


def test_standalone_c_host(tmp_path):
    """Compile a pure-C program against the ABI and run it as a true
    embedding host (interpreter started by the library)."""
    _build_lib()
    if not shutil.which("gcc"):
        pytest.skip("no gcc")
    sym_json, pfile, x, ref = _save_model(tmp_path)
    sym_file = tmp_path / "model.json"
    sym_file.write_text(sym_json)
    src = tmp_path / "host.c"
    src.write_text(C_HOST)
    exe = tmp_path / "host"
    r = subprocess.run(
        ["gcc", str(src), "-o", str(exe),
         "-I", os.path.join(REPO, "include"),
         "-L", os.path.dirname(LIB), "-lmxtpu_predict",
         "-Wl,-rpath," + os.path.dirname(LIB)],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([str(exe), str(sym_file), pfile],
                       capture_output=True, text=True, env=env,
                       timeout=300)
    assert r.returncode == 0, (r.stdout, r.stderr)
    got = np.array([float(v) for v in r.stdout.split()],
                   dtype=np.float32).reshape(2, 3)
    # same input as the host program
    x_host = (np.arange(10, dtype=np.float32) / 10.0).reshape(2, 5)
    from mxnet_tpu.predictor import Predictor
    pred = Predictor(sym_json, pfile, {"data": (2, 5)})
    pred.forward(data=x_host)
    np.testing.assert_allclose(got, pred.get_output(0), rtol=1e-4,
                               atol=1e-5)


def test_reshape_keeps_original_handle(tmp_path):
    """MXPredReshape semantics: both the old and new handle stay usable
    at their own shapes."""
    _build_lib()
    sym_json, pfile, x, ref = _save_model(tmp_path)
    lib = _load()
    param_blob = open(pfile, "rb").read()
    h1 = ctypes.c_void_p()
    keys = (ctypes.c_char_p * 1)(b"data")
    indptr = (ctypes.c_uint32 * 2)(0, 2)
    dims = (ctypes.c_uint32 * 2)(2, 5)
    assert lib.MXPredCreate(sym_json.encode(), param_blob, len(param_blob),
                            1, 0, 1, keys, indptr, dims,
                            ctypes.byref(h1)) == 0

    h2 = ctypes.c_void_p()
    dims2 = (ctypes.c_uint32 * 2)(4, 5)
    assert lib.MXPredReshape(1, keys, indptr, dims2, h1,
                             ctypes.byref(h2)) == 0, lib.MXGetLastError()

    # original handle still works at batch 2
    xs = np.ascontiguousarray(x)
    assert lib.MXPredSetInput(h1, b"data",
                              xs.ctypes.data_as(
                                  ctypes.POINTER(ctypes.c_float)),
                              xs.size) == 0, lib.MXGetLastError()
    assert lib.MXPredForward(h1) == 0
    out1 = np.zeros((2, 3), np.float32)
    assert lib.MXPredGetOutput(h1, 0,
                               out1.ctypes.data_as(
                                   ctypes.POINTER(ctypes.c_float)),
                               out1.size) == 0, lib.MXGetLastError()
    np.testing.assert_allclose(out1, ref, rtol=1e-5, atol=1e-5)

    # new handle works at batch 4 with the same weights
    x4 = np.concatenate([xs, xs], axis=0)
    assert lib.MXPredSetInput(h2, b"data",
                              x4.ctypes.data_as(
                                  ctypes.POINTER(ctypes.c_float)),
                              x4.size) == 0, lib.MXGetLastError()
    assert lib.MXPredForward(h2) == 0
    out2 = np.zeros((4, 3), np.float32)
    assert lib.MXPredGetOutput(h2, 0,
                               out2.ctypes.data_as(
                                   ctypes.POINTER(ctypes.c_float)),
                               out2.size) == 0, lib.MXGetLastError()
    np.testing.assert_allclose(out2[:2], ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out2[2:], ref, rtol=1e-5, atol=1e-5)
    assert lib.MXPredFree(h1) == 0
    assert lib.MXPredFree(h2) == 0


def test_output_shape_before_forward_and_same_shape_reshape(tmp_path):
    _build_lib()
    sym_json, pfile, x, ref = _save_model(tmp_path)
    lib = _load()
    param_blob = open(pfile, "rb").read()
    h1 = ctypes.c_void_p()
    keys = (ctypes.c_char_p * 1)(b"data")
    indptr = (ctypes.c_uint32 * 2)(0, 2)
    dims = (ctypes.c_uint32 * 2)(2, 5)
    assert lib.MXPredCreate(sym_json.encode(), param_blob, len(param_blob),
                            1, 0, 1, keys, indptr, dims,
                            ctypes.byref(h1)) == 0

    # canonical client flow: shape is queryable BEFORE any forward
    sdata = ctypes.POINTER(ctypes.c_uint32)()
    ndim = ctypes.c_uint32()
    assert lib.MXPredGetOutputShape(h1, 0, ctypes.byref(sdata),
                                    ctypes.byref(ndim)) == 0, \
        lib.MXGetLastError()
    assert tuple(sdata[i] for i in range(ndim.value)) == (2, 3)

    # same-shape reshape must NOT alias inputs between handles
    h2 = ctypes.c_void_p()
    assert lib.MXPredReshape(1, keys, indptr, dims, h1,
                             ctypes.byref(h2)) == 0, lib.MXGetLastError()
    xs = np.ascontiguousarray(x)
    zeros = np.zeros_like(xs)
    assert lib.MXPredSetInput(h1, b"data",
                              xs.ctypes.data_as(
                                  ctypes.POINTER(ctypes.c_float)),
                              xs.size) == 0
    # writing through h2 must not clobber h1's pending input
    assert lib.MXPredSetInput(h2, b"data",
                              zeros.ctypes.data_as(
                                  ctypes.POINTER(ctypes.c_float)),
                              zeros.size) == 0
    assert lib.MXPredForward(h1) == 0
    out = np.zeros((2, 3), np.float32)
    assert lib.MXPredGetOutput(h1, 0,
                               out.ctypes.data_as(
                                   ctypes.POINTER(ctypes.c_float)),
                               out.size) == 0
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    lib.MXPredFree(h1)
    lib.MXPredFree(h2)
