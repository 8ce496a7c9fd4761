"""Scala frontend validation without a JVM (scala-package/README.md):
JNI glue compiles against the real c_api.h; every Scala @native method
pairs with a JNI export; C-ABI usage is declared in the header."""
import os
import re
import shutil
import subprocess
import tempfile

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SPKG = os.path.join(REPO, "scala-package")
JNI_C = os.path.join(SPKG, "native", "src", "main", "native",
                     "mxnet_tpu_jni.c")
LIBINFO = os.path.join(SPKG, "core", "src", "main", "scala", "ml",
                       "mxnet_tpu", "LibInfo.scala")

JNI_STUB = r"""
#ifndef JNI_STUB_H
#define JNI_STUB_H
#include <stddef.h>
#include <stdint.h>
typedef int32_t jint;
typedef int64_t jlong;
typedef float jfloat;
typedef int32_t jsize;
typedef void *jlongArray;
typedef void *jobject;
typedef void *jclass;
typedef void *jstring;
typedef void *jobjectArray;
typedef void *jintArray;
typedef void *jfloatArray;
typedef void *jarray;
struct JNINativeInterface_;
typedef const struct JNINativeInterface_ *JNIEnv;
struct JNINativeInterface_ {
  jclass (*FindClass)(JNIEnv *, const char *);
  jint (*ThrowNew)(JNIEnv *, jclass, const char *);
  jsize (*GetArrayLength)(JNIEnv *, jarray);
  jint *(*GetIntArrayElements)(JNIEnv *, jintArray, void *);
  void (*ReleaseIntArrayElements)(JNIEnv *, jintArray, jint *, jint);
  jfloat *(*GetFloatArrayElements)(JNIEnv *, jfloatArray, void *);
  void (*ReleaseFloatArrayElements)(JNIEnv *, jfloatArray, jfloat *, jint);
  jlong *(*GetLongArrayElements)(JNIEnv *, jlongArray, void *);
  void (*ReleaseLongArrayElements)(JNIEnv *, jlongArray, jlong *, jint);
  jlongArray (*NewLongArray)(JNIEnv *, jsize);
  void (*SetLongArrayRegion)(JNIEnv *, jlongArray, jsize, jsize,
                             const jlong *);
  jfloatArray (*NewFloatArray)(JNIEnv *, jsize);
  void (*SetFloatArrayRegion)(JNIEnv *, jfloatArray, jsize, jsize,
                              const jfloat *);
  jintArray (*NewIntArray)(JNIEnv *, jsize);
  void (*SetIntArrayRegion)(JNIEnv *, jintArray, jsize, jsize,
                            const jint *);
  const char *(*GetStringUTFChars)(JNIEnv *, jstring, void *);
  void (*ReleaseStringUTFChars)(JNIEnv *, jstring, const char *);
  jstring (*NewStringUTF)(JNIEnv *, const char *);
  jobjectArray (*NewObjectArray)(JNIEnv *, jsize, jclass, jobject);
  void (*SetObjectArrayElement)(JNIEnv *, jobjectArray, jsize, jobject);
  jobject (*GetObjectArrayElement)(JNIEnv *, jobjectArray, jsize);
};
#define JNIEXPORT
#define JNICALL
#define JNI_ABORT 2
#endif
"""


def test_jni_glue_compiles_against_real_c_api():
    if shutil.which("gcc") is None:
        pytest.skip("no gcc toolchain")
    with tempfile.TemporaryDirectory() as tmp:
        with open(os.path.join(tmp, "jni.h"), "w") as f:
            f.write(JNI_STUB)
        out = subprocess.run(
            ["gcc", "-fsyntax-only", "-Wall", "-Werror", "-I", tmp,
             "-I", os.path.join(REPO, "include"), JNI_C],
            capture_output=True, text=True)
        assert out.returncode == 0, out.stderr


def _jni_exports():
    src = "\n".join(l for l in open(JNI_C).read().splitlines()
                    if not l.lstrip().startswith("#define"))
    return set(re.findall(r"JNIFN\(\w+,\s*(\w+)\)", src))


def _scala_natives():
    src = open(LIBINFO).read()
    return set(re.findall(r"@native def (\w+)\(", src))


def test_native_table_matches_jni_exports():
    natives = _scala_natives()
    exports = _jni_exports()
    assert natives, "no @native declarations found"
    assert natives == exports, (natives - exports, exports - natives)


def test_glue_only_uses_declared_abi_symbols():
    header = open(os.path.join(
        REPO, "include", "mxnet_tpu", "c_api.h")).read()
    declared = set(re.findall(r"\b(MX\w+)\s*\(", header))
    used = set(re.findall(r"\b(MX\w+)\s*\(", open(JNI_C).read()))
    missing = used - declared
    assert not missing, "glue calls undeclared ABI symbols: %s" % missing


def _strip_scala(src):
    """Remove string literals (incl. interpolated/triple-quoted) and
    comments so delimiter analysis sees only code."""
    src = re.sub(r'"""(?:.|\n)*?"""', '""', src)
    src = re.sub(r'"(?:[^"\\\n]|\\.)*"', '""', src)
    src = re.sub(r"'(?:[^'\\]|\\.)'", "' '", src)  # char literals
    src = re.sub(r"//[^\n]*", "", src)
    src = re.sub(r"/\*(?:.|\n)*?\*/", "", src)
    return src


def _scala_files():
    for root, _, files in os.walk(SPKG):
        for f in files:
            if f.endswith(".scala"):
                yield os.path.join(root, f)


def test_scala_sources_structurally_balanced():
    """Structural gate (no scalac in image): delimiters must nest as a
    well-formed stack — not just equal counts — and every `def` must
    carry balanced parameter parens and a body (`=` or `{`). Catches
    truncation, mismatched nesting, and cut-off signatures that a
    plain brace count misses."""
    pairs = {"(": ")", "[": "]", "{": "}"}
    closers = {v: k for k, v in pairs.items()}
    for path in _scala_files():
        stripped = _strip_scala(open(path).read())
        stack = []
        for ch in stripped:
            if ch in pairs:
                stack.append(ch)
            elif ch in closers:
                assert stack and stack[-1] == closers[ch], \
                    "%s: mismatched '%s'" % (path, ch)
                stack.pop()
        assert not stack, "%s: unclosed %s" % (path, stack[-5:])
        # every def has balanced parens in its signature and a body
        for m in re.finditer(r"\bdef\s+([\w$]+|`[^`]+`)", stripped):
            i = m.end()
            while i < len(stripped) and stripped[i] in " \t\n":
                i += 1
            if i < len(stripped) and stripped[i] in "([":
                depth = 0
                while i < len(stripped):
                    if stripped[i] in "([":
                        depth += 1
                    elif stripped[i] in ")]":
                        depth -= 1
                        if depth == 0:
                            i += 1
                            # skip further param lists / type params
                            while i < len(stripped) and \
                                    stripped[i] in " \t\n":
                                i += 1
                            if i < len(stripped) and stripped[i] in "([":
                                depth = 0
                                continue
                            break
                    i += 1
                assert depth == 0, "%s: unbalanced signature for %s" \
                    % (path, m.group(1))
            rest = stripped[i:i + 200].lstrip()
            assert rest.startswith(("=", ":", "{")) or rest == "", \
                "%s: def %s has no type/body" % (path, m.group(1))


def test_generated_scala_ops_in_sync():
    """Drift gate: the committed SymbolOpsGen.scala / NDArrayOpsGen.scala
    must match what tools/gen_scala_ops.py emits from the LIVE
    registries (the reference regenerated its typed surface every
    build; here the generated source is committed and this test is the
    build step)."""
    import sys
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "gen_scala_ops.py"),
         "--check"],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, (r.stdout, r.stderr[-1000:])


def test_generated_surface_covers_registry():
    """Every public registered op has a typed creator; every imperative
    function has a typed NDArray method (reference parity axis: its
    hand-written Symbol.scala/NDArray.scala covered the full registry
    of its day)."""
    gen = open(os.path.join(
        SPKG, "core", "src", "main", "scala", "ml", "mxnet_tpu",
        "SymbolOpsGen.scala")).read()
    ndgen = open(os.path.join(
        SPKG, "core", "src", "main", "scala", "ml", "mxnet_tpu",
        "NDArrayOpsGen.scala")).read()
    import sys
    sys.path.insert(0, REPO)
    import jax
    jax.config.update("jax_platforms", "cpu")
    from mxnet_tpu.ops import registry
    seen = set()
    for key in registry.OP_REGISTRY.list_names():
        cls = registry.OP_REGISTRY.get(key)
        op = getattr(cls, "op_name", key)
        if op.startswith("_") or op in seen:
            continue
        seen.add(op)
        assert re.search(r"\bdef %s\(" % re.escape(op), gen), \
            "SymbolOpsGen missing %s" % op
    from mxnet_tpu import capi_helpers as H
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from gen_scala_ops import scala_ident   # the one true name mapping
    for fn in H.list_functions():
        ident = scala_ident(fn.lstrip("_"))
        assert re.search(r"\bdef %s\(" % re.escape(ident), ndgen), \
            "NDArrayOpsGen missing %s" % fn


def test_spark_module_covers_reference_surface():
    src = open(os.path.join(
        SPKG, "spark", "src", "main", "scala", "ml", "mxnet_tpu",
        "spark", "MXNetTPUSpark.scala")).read()
    for needle in ("dist_sync", "setBatchSize", "setNumEpoch",
                   "setLearningRate", "trainPartition", "kv.push",
                   "kv.pull", "kv.barrier"):
        assert needle in src, needle


def _build_jni_driver(tmpdir):
    r = subprocess.run(["make", "-C", REPO, "predict"],
                       capture_output=True, text=True)
    lib = os.path.join(REPO, "mxnet_tpu", "_native", "libmxtpu_predict.so")
    assert r.returncode == 0 and os.path.exists(lib), r.stderr[-800:]
    with open(os.path.join(tmpdir, "jni.h"), "w") as f:
        f.write(JNI_STUB)
    exe = os.path.join(tmpdir, "jni_train")
    r = subprocess.run(
        ["gcc", os.path.join(REPO, "tests", "jni_shim.c"),
         os.path.join(REPO, "tests", "jni_train.c"), JNI_C,
         "-o", exe, "-I", tmpdir, "-I", os.path.join(REPO, "include"),
         "-L", os.path.dirname(lib), "-lmxtpu_predict",
         "-Wl,-rpath," + os.path.dirname(lib), "-lm"],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-2000:]
    return exe


def _driver_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_jni_module_training_executes(tmp_path):
    """Execution gate for the Scala frontend's native path: no JVM
    exists in this image, so tests/jni_shim.c implements the JNI
    environment for real and tests/jni_train.c performs the exact
    native sequence Module.scala's bind/initParams/fit drives —
    registry symbol construction, full shape inference, simple_bind,
    per-batch forward/backward/getGrad, SGD-momentum updates — gating
    convergence >= 0.9. (Scala-language semantics are covered by the
    structural gates above, as in the reference whose Spark module also
    only ran in a real cluster.)"""
    if shutil.which("gcc") is None or shutil.which("make") is None:
        pytest.skip("no gcc toolchain")
    exe = _build_jni_driver(str(tmp_path))
    r = subprocess.run([exe, "local"], capture_output=True, text=True,
                       env=_driver_env(), timeout=600)
    assert r.returncode == 0, (r.stdout, r.stderr[-2000:])
    acc = float(r.stdout.split("final_acc=")[1].split()[0])
    assert acc >= 0.9, r.stdout


def test_jni_ndarray_io_handles_are_caller_owned(tmp_path):
    """NDArrayIO.save/load (Scala loadCheckpoint path): ndLoad must
    return handles the caller can read AND free after the glue drops
    the load record (advisor r3 high finding: the ListFree-only version
    returned dangling handles). Built with AddressSanitizer when
    available so the old double-free aborts instead of passing
    silently."""
    if shutil.which("gcc") is None or shutil.which("make") is None:
        pytest.skip("no gcc toolchain")
    r = subprocess.run(["make", "-C", REPO, "predict"],
                       capture_output=True, text=True)
    lib = os.path.join(REPO, "mxnet_tpu", "_native", "libmxtpu_predict.so")
    assert r.returncode == 0 and os.path.exists(lib), r.stderr[-800:]
    with open(os.path.join(tmp_path, "jni.h"), "w") as f:
        f.write(JNI_STUB)
    srcs = [os.path.join(REPO, "tests", "jni_shim.c"),
            os.path.join(REPO, "tests", "jni_train.c"), JNI_C]
    common = ["-I", str(tmp_path), "-I", os.path.join(REPO, "include"),
              "-L", os.path.dirname(lib), "-lmxtpu_predict",
              "-Wl,-rpath," + os.path.dirname(lib), "-lm"]
    exe = os.path.join(tmp_path, "jni_ndio")
    asan = subprocess.run(
        ["gcc", "-fsanitize=address", *srcs, "-o", exe, *common],
        capture_output=True, text=True)
    if asan.returncode != 0:  # no ASAN runtime in image: plain build
        r = subprocess.run(["gcc", *srcs, "-o", exe, *common],
                           capture_output=True, text=True)
        assert r.returncode == 0, r.stderr[-2000:]
    env = _driver_env()
    env["ASAN_OPTIONS"] = "detect_leaks=0"  # embedded CPython "leaks"
    out = subprocess.run(
        [exe, "ndio", os.path.join(tmp_path, "params.bin")],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, (out.stdout, out.stderr[-3000:])
    assert "ndio_ok" in out.stdout


def test_jni_spark_dist_training_two_workers(tmp_path):
    """The Spark trainer's distribution invariant, executed for real:
    two processes launched by tools/launch.py each run the
    MXNetTPUSpark.trainPartition native sequence (rank-sharded data,
    dist_sync kvstore, per-step gradient push/pull through the
    collective). Gates: both ranks converge AND end with bit-identical
    weights (reference scala-package/spark MXNet.scala's guarantee via
    the shared parameter server)."""
    import signal
    import sys as _sys
    if shutil.which("gcc") is None or shutil.which("make") is None:
        pytest.skip("no gcc toolchain")
    exe = _build_jni_driver(str(tmp_path))
    env = _driver_env()
    proc = subprocess.Popen(
        [_sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--coordinator", "127.0.0.1:23473", exe, "dist"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, start_new_session=True)
    try:
        stdout, stderr = proc.communicate(timeout=300)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        stdout, stderr = proc.communicate()
        raise
    err_l = (stderr or "").lower()
    if proc.returncode != 0 and "final_acc" not in stdout and (
            "distributed" in err_l
            or "multiprocess computations aren't implemented" in err_l):
        # the second message is the CPU backend refusing multi-process
        # collectives outright — same "no distributed runtime here" skip,
        # just reported after jax.distributed.initialize succeeds
        pytest.skip("jax.distributed unavailable: %s" % stderr[-200:])
    assert proc.returncode == 0, (stdout[-1000:], stderr[-2000:])
    accs = [float(x.split()[0]) for x in stdout.split("final_acc=")[1:]]
    sums = [x.split()[0] for x in stdout.split("weights_sum=")[1:]]
    assert len(accs) == 2 and len(sums) == 2, stdout
    assert all(a >= 0.9 for a in accs), accs
    assert sums[0] == sums[1], "ranks diverged: %s" % sums


def test_jni_io_iterator_training_executes(tmp_path):
    """Execution gate for the Scala io surface (MXDataIter,
    Module.scala): tests/jni_io_train.c drives iterCreate with string
    kwargs, beforeFirst/next/getData/getLabel per batch, dataShape, and
    the CSVIter exact read-back — training a convnet from a recordio
    file to >= 0.9 through the real JNI glue. Reference parity:
    scala-package ml.dmlc.mxnet.io.MXDataIter."""
    if shutil.which("gcc") is None or shutil.which("make") is None:
        pytest.skip("no gcc toolchain")
    import numpy as np

    from mxnet_tpu import recordio as rio

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
        for r_ in range(4):
            f.write(",".join(str((r_ * 3 + c) * 0.5) for c in range(3))
                    + "\n")

    r = subprocess.run(["make", "-C", REPO, "predict"],
                       capture_output=True, text=True)
    lib = os.path.join(REPO, "mxnet_tpu", "_native", "libmxtpu_predict.so")
    assert r.returncode == 0 and os.path.exists(lib), r.stderr[-800:]
    tmpdir = str(tmp_path)
    with open(os.path.join(tmpdir, "jni.h"), "w") as f:
        f.write(JNI_STUB)
    exe = os.path.join(tmpdir, "jni_io_train")
    r = subprocess.run(
        ["gcc", os.path.join(REPO, "tests", "jni_shim.c"),
         os.path.join(REPO, "tests", "jni_io_train.c"), JNI_C,
         "-o", exe, "-I", tmpdir, "-I", os.path.join(REPO, "include"),
         "-L", os.path.dirname(lib), "-lmxtpu_predict",
         "-Wl,-rpath," + os.path.dirname(lib), "-lm"],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-2000:]
    r = subprocess.run([exe, rec, csv], capture_output=True, text=True,
                       env=_driver_env(), timeout=600)
    assert r.returncode == 0, (r.stdout, r.stderr[-2000:])
    acc = float(r.stdout.split("final_acc=")[1].split()[0])
    assert acc >= 0.9, r.stdout
