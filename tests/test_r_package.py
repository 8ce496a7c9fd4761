"""R frontend validation without an R runtime.

Three gates (R-package/README.md): (1) the C glue compiles against the
real c_api.h (stub R headers supply the SEXP surface), (2) every .Call
from R resolves to a registered native routine with matching arity,
(3) NAMESPACE exports exist in the R source. The ABI semantics under the
glue are covered by test_c_api_core.py / test_perl_frontend.py."""
import os
import re
import subprocess
import tempfile

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
RPKG = os.path.join(REPO, "R-package")

R_STUB = r"""
#ifndef R_INTERNALS_STUB
#define R_INTERNALS_STUB
#include <stddef.h>
typedef void *SEXP;
typedef ptrdiff_t R_xlen_t;
typedef void (*R_CFinalizer_t)(SEXP);
#define STRSXP 16
#define INTSXP 13
#define REALSXP 14
#define VECSXP 19
extern SEXP R_NilValue, R_NamesSymbol;
SEXP Rf_allocVector(int, R_xlen_t);
SEXP Rf_mkChar(const char*); SEXP Rf_mkString(const char*);
SEXP Rf_install(const char*);
void SET_STRING_ELT(SEXP, R_xlen_t, SEXP);
SEXP STRING_ELT(SEXP, R_xlen_t);
void SET_VECTOR_ELT(SEXP, R_xlen_t, SEXP);
SEXP VECTOR_ELT(SEXP, R_xlen_t);
const char *CHAR(SEXP);
int *INTEGER(SEXP); double *REAL(SEXP);
int Rf_length(SEXP); R_xlen_t Rf_xlength(SEXP);
int Rf_asInteger(SEXP);
double Rf_asReal(SEXP);
SEXP Rf_ScalarInteger(int);
SEXP Rf_setAttrib(SEXP, SEXP, SEXP); SEXP Rf_getAttrib(SEXP, SEXP);
SEXP PROTECT(SEXP); void UNPROTECT(int);
void Rf_error(const char*, ...);
char *R_alloc(size_t, int);
SEXP R_MakeExternalPtr(void*, SEXP, SEXP);
void *R_ExternalPtrAddr(SEXP);
void R_ClearExternalPtr(SEXP);
void R_RegisterCFinalizerEx(SEXP, R_CFinalizer_t, int);
typedef void *DL_FUNC;
typedef struct { const char *name; DL_FUNC fun; int numArgs; }
    R_CallMethodDef;
typedef struct _DllInfo DllInfo;
int R_registerRoutines(DllInfo*, const void*, const R_CallMethodDef*,
                       const void*, const void*);
int R_useDynamicSymbols(DllInfo*, int);
#ifndef TRUE
#define TRUE 1
#define FALSE 0
#endif
#endif
"""


def test_glue_compiles_against_real_c_api():
    import shutil
    if shutil.which("gcc") is None:
        pytest.skip("no gcc toolchain")
    with tempfile.TemporaryDirectory() as tmp:
        with open(os.path.join(tmp, "Rinternals.h"), "w") as f:
            f.write(R_STUB)
        with open(os.path.join(tmp, "R.h"), "w") as f:
            f.write('#include "Rinternals.h"\n')
        out = subprocess.run(
            ["gcc", "-fsyntax-only", "-Wall", "-Werror",
             "-Wno-unused-variable", "-I", tmp,
             "-I", os.path.join(REPO, "include"),
             os.path.join(RPKG, "src", "mxnet_glue.c")],
            capture_output=True, text=True)
        assert out.returncode == 0, out.stderr


def _registered_routines():
    src = open(os.path.join(RPKG, "src", "mxnet_glue.c")).read()
    return dict(re.findall(
        r'\{"(mxr_\w+)",\s*\(DL_FUNC\)&\w+,\s*(\d+)\}', src))


def _r_calls():
    """Every .Call(symbol, args...) in R/ with its argument count."""
    calls = []
    for fname in os.listdir(os.path.join(RPKG, "R")):
        src = open(os.path.join(RPKG, "R", fname)).read()
        for m in re.finditer(r"\.Call\(", src):
            i = m.end()
            depth, args, cur = 1, [], []
            while depth > 0:
                ch = src[i]
                if ch in "([":
                    depth += 1
                elif ch in ")]":
                    depth -= 1
                    if depth == 0:
                        break
                elif ch == "," and depth == 1:
                    args.append("".join(cur))
                    cur = []
                    i += 1
                    continue
                cur.append(ch)
                i += 1
            args.append("".join(cur))
            calls.append((args[0].strip(), len(args) - 1, fname))
    return calls


def test_every_dotcall_resolves_with_matching_arity():
    routines = _registered_routines()
    calls = _r_calls()
    assert calls, "no .Call sites found — parser broken?"
    for symbol, nargs, fname in calls:
        assert symbol in routines, "%s: unregistered .Call %s" % (
            fname, symbol)
        assert int(routines[symbol]) == nargs, (
            "%s: .Call(%s) passes %d args, glue registers %s"
            % (fname, symbol, nargs, routines[symbol]))


def test_namespace_exports_defined():
    ns = open(os.path.join(RPKG, "NAMESPACE")).read()
    exports = re.findall(r"export\(([^)]+)\)", ns)
    rsrc = "".join(open(os.path.join(RPKG, "R", f)).read()
                   for f in os.listdir(os.path.join(RPKG, "R")))
    for name in exports:
        # value bindings count too (mx.metric.accuracy <- mx.metric.custom(...))
        pattern = re.escape(name) + r"\s*<-"
        assert re.search(pattern, rsrc), "export %s has no definition" % name


def test_c_registration_table_covers_all_functions():
    """Every SEXP-returning glue function is registered (a missing row
    means the R symbol silently resolves to NULL at runtime)."""
    src = open(os.path.join(RPKG, "src", "mxnet_glue.c")).read()
    defined = set(re.findall(r"^SEXP (mxr_\w+)\(", src, re.M))
    registered = set(_registered_routines())
    assert defined == registered, (defined - registered,
                                   registered - defined)


def test_r_glue_training_loop_executes(tmp_path):
    """Execution gate for the R frontend's native path: no R interpreter
    exists in this image, so tests/r_shim.c provides a REAL (minimal)
    implementation of the R C API and tests/r_glue_train.c performs the
    exact .Call sequence mx.model.FeedForward.create (R/model.R) drives
    — registry symbol construction, infer_shape with aux.shapes,
    simple_bind, per-batch set/forward/backward/get_grad, the
    optimizer.R SGD-momentum update — gating convergence to >= 0.9.
    What this cannot check is R-language semantics of the .R files;
    those are covered by the arity/NAMESPACE static gates above."""
    import shutil
    if shutil.which("gcc") is None or shutil.which("make") is None:
        pytest.skip("no gcc toolchain")
    r = subprocess.run(["make", "-C", REPO, "predict"],
                       capture_output=True, text=True)
    lib = os.path.join(REPO, "mxnet_tpu", "_native", "libmxtpu_predict.so")
    assert r.returncode == 0 and os.path.exists(lib), r.stderr[-800:]

    with tempfile.TemporaryDirectory() as tmp:
        with open(os.path.join(tmp, "Rinternals.h"), "w") as f:
            f.write(R_STUB)
        with open(os.path.join(tmp, "R.h"), "w") as f:
            f.write('#include "Rinternals.h"\n')
        exe = os.path.join(tmp, "r_glue_train")
        r = subprocess.run(
            ["gcc", os.path.join(REPO, "tests", "r_shim.c"),
             os.path.join(REPO, "tests", "r_glue_train.c"),
             os.path.join(RPKG, "src", "mxnet_glue.c"),
             "-o", exe, "-I", tmp, "-I", os.path.join(REPO, "include"),
             "-L", os.path.dirname(lib), "-lmxtpu_predict",
             "-Wl,-rpath," + os.path.dirname(lib)],
            capture_output=True, text=True)
        assert r.returncode == 0, r.stderr[-2000:]

        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        r = subprocess.run([exe], capture_output=True, text=True, env=env,
                           timeout=600)
        assert r.returncode == 0, (r.stdout, r.stderr[-2000:])
        acc = float(r.stdout.strip().split("final_acc=")[1])
        assert acc >= 0.9, r.stdout


def test_r_glue_rnn_training_and_inference_execute(tmp_path):
    """Execution gate for the R RNN tier's native path (round-4 item:
    reference R-package/R/{lstm,gru,rnn,rnn_model}.R): tests/
    r_glue_rnn_train.c performs the .Call sequence mx.lstm /
    mx.lstm.inference / mx.lstm.forward drive — Embedding/transpose/
    fused-RNN symbol construction, the new mxr_sym_get_output +
    mxr_sym_group glue for the state-carrying inference graph, training
    to convergence, then token-by-token stateful stepping — gating both
    accuracies >= 0.9."""
    import shutil
    if shutil.which("gcc") is None or shutil.which("make") is None:
        pytest.skip("no gcc toolchain")
    r = subprocess.run(["make", "-C", REPO, "predict"],
                       capture_output=True, text=True)
    lib = os.path.join(REPO, "mxnet_tpu", "_native", "libmxtpu_predict.so")
    assert r.returncode == 0 and os.path.exists(lib), r.stderr[-800:]

    with tempfile.TemporaryDirectory() as tmp:
        with open(os.path.join(tmp, "Rinternals.h"), "w") as f:
            f.write(R_STUB)
        with open(os.path.join(tmp, "R.h"), "w") as f:
            f.write('#include "Rinternals.h"\n')
        exe = os.path.join(tmp, "r_glue_rnn_train")
        r = subprocess.run(
            ["gcc", os.path.join(REPO, "tests", "r_shim.c"),
             os.path.join(REPO, "tests", "r_glue_rnn_train.c"),
             os.path.join(RPKG, "src", "mxnet_glue.c"),
             "-o", exe, "-I", tmp, "-I", os.path.join(REPO, "include"),
             "-L", os.path.dirname(lib), "-lmxtpu_predict",
             "-Wl,-rpath," + os.path.dirname(lib)],
            capture_output=True, text=True)
        assert r.returncode == 0, r.stderr[-2000:]

        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        r = subprocess.run([exe], capture_output=True, text=True, env=env,
                           timeout=900)
        assert r.returncode == 0, (r.stdout, r.stderr[-2000:])
        train_acc = float(r.stdout.split("train_acc=")[1].split()[0])
        infer_acc = float(r.stdout.split("infer_acc=")[1].split()[0])
        assert train_acc >= 0.9 and infer_acc >= 0.9, r.stdout
        # the Ops.MXNDArray arithmetic path (mxr_func_invoke) ran too
        assert "func_invoke_ok" in r.stdout, r.stdout


def test_rnn_R_defines_reference_surface():
    """The R RNN tier's public entry points exist with the reference's
    names (reference lstm.R:152-361, gru.R:150-355, rnn.R:136-342,
    viz.graph.R:24-158)."""
    rsrc = "".join(open(os.path.join(RPKG, "R", f)).read()
                   for f in os.listdir(os.path.join(RPKG, "R")))
    for fn in ["mx.lstm", "mx.lstm.inference", "mx.lstm.forward",
               "mx.gru", "mx.gru.inference", "mx.gru.forward",
               "mx.rnn", "mx.rnn.inference", "mx.rnn.forward",
               "mx.rnn.train", "mx.rnn.infer.model", "mx.rnn.step",
               "graph.viz", "mx.graph.viz",
               "mx.symbol.get.output", "mx.symbol.Group"]:
        assert re.search(re.escape(fn) + r"\s*(<-|<<-)", rsrc), \
            "missing %s" % fn


def test_model_R_defines_reference_training_surface():
    """mx.model.FeedForward.create and its reference companions exist in
    the R sources (reference R-package/R/model.R:94-562 scope)."""
    rsrc = "".join(open(os.path.join(RPKG, "R", f)).read()
                   for f in os.listdir(os.path.join(RPKG, "R")))
    for fn in ["mx.model.FeedForward.create", "mx.model.init.params",
               "mx.model.save", "mx.model.load", "mx.mlp",
               "mx.io.arrayiter", "mx.metric.accuracy", "mx.opt.sgd",
               "mx.init.Xavier", "mx.init.uniform",
               "mx.lr_scheduler.FactorScheduler",
               "mx.callback.log.train.metric"]:
        assert re.search(re.escape(fn) + r"\s*(<-|<<-)", rsrc), \
            "missing %s" % fn


def test_r_glue_io_iterators_train(tmp_path):
    """Execution gate for the R io-iterator bindings (round-4 verdict
    #3): tests/r_glue_io_train.c drives the exact .Call sequence
    mx.io.ImageRecordIter / CSVIter / MNISTIter (R/io.R) and the
    iterator form of mx.model.FeedForward.create perform — create from
    string kwargs, before_first/next/value, batches into a conv
    executor trained with the optimizer.R SGD math — gating >= 0.9
    accuracy from a recordio file, exact CSV read-back, and idx-format
    MNIST parsing. Reference surface: R-package/R/mxnet_generated.R:
    480-610."""
    import shutil
    if shutil.which("gcc") is None or shutil.which("make") is None:
        pytest.skip("no gcc toolchain")
    import sys as _sys

    import numpy as np

    _sys.path.insert(0, os.path.join(REPO, "tools"))
    from make_mnist_synth import write_idx_images, write_idx_labels

    from mxnet_tpu import recordio as rio

    # class-conditional 12x12 recordio (dark=0 / bright=1)
    rng = np.random.RandomState(0)
    rec = str(tmp_path / "t.rec")
    w = rio.MXRecordIO(rec, "w")
    for i in range(64):
        label = i % 2
        lo, hi = (0, 110) if label == 0 else (145, 255)
        w.write(rio.pack_img(
            rio.IRHeader(0, float(label), i, 0),
            rng.randint(lo, hi, (12, 12, 3)).astype(np.uint8),
            quality=95))
    w.close()

    csv = str(tmp_path / "t.csv")
    with open(csv, "w") as f:
        for r in range(4):
            f.write(",".join(str((r * 3 + c) * 0.5) for c in range(3))
                    + "\n")

    mimg = str(tmp_path / "imgs-idx3-ubyte")
    mlbl = str(tmp_path / "lbls-idx1-ubyte")
    write_idx_images(mimg, rng.randint(0, 255, (16, 28, 28))
                     .astype(np.uint8))
    write_idx_labels(mlbl, (np.arange(16) % 10).astype(np.uint8))

    r = subprocess.run(["make", "-C", REPO, "predict"],
                       capture_output=True, text=True)
    lib = os.path.join(REPO, "mxnet_tpu", "_native", "libmxtpu_predict.so")
    assert r.returncode == 0 and os.path.exists(lib), r.stderr[-800:]

    with tempfile.TemporaryDirectory() as tmp:
        with open(os.path.join(tmp, "Rinternals.h"), "w") as f:
            f.write(R_STUB)
        with open(os.path.join(tmp, "R.h"), "w") as f:
            f.write('#include "Rinternals.h"\n')
        exe = os.path.join(tmp, "r_glue_io_train")
        r = subprocess.run(
            ["gcc", os.path.join(REPO, "tests", "r_shim.c"),
             os.path.join(REPO, "tests", "r_glue_io_train.c"),
             os.path.join(RPKG, "src", "mxnet_glue.c"),
             "-o", exe, "-I", tmp, "-I", os.path.join(REPO, "include"),
             "-L", os.path.dirname(lib), "-lmxtpu_predict",
             "-Wl,-rpath," + os.path.dirname(lib)],
            capture_output=True, text=True)
        assert r.returncode == 0, r.stderr[-2000:]

        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        r = subprocess.run([exe, rec, csv, mimg, mlbl],
                           capture_output=True, text=True, env=env,
                           timeout=600)
        assert r.returncode == 0, (r.stdout, r.stderr[-2000:])
        acc = float(r.stdout.strip().split("final_acc=")[1])
        assert acc >= 0.9, r.stdout
