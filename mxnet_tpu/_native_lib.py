"""Loader for the native runtime library (C++ engine + recordio codec).

Builds ``mxnet_tpu/_native/libmxtpu.so`` from ``src/native/*.cc`` on first
use (``make`` at repo root does the same), and REBUILDS it when it is older
than any of its sources: the library is a generated file git ignores, so
one found on disk may predate the checkout. Without a working compiler the
pure-Python implementations serve, after one warning that carries the
compiler's stderr. Set ``MXNET_TPU_NO_NATIVE=1`` to force pure Python.
"""
from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading

from . import env as _env

_log = logging.getLogger(__name__)
_lock = threading.Lock()
_lib = None
_tried = False

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_LIB_PATH = os.path.join(_REPO, "mxnet_tpu", "_native", "libmxtpu.so")
_SRC_DIR = os.path.join(_REPO, "src", "native")


def _sources():
    if not os.path.isdir(_SRC_DIR):
        return []
    return [os.path.join(_SRC_DIR, f) for f in sorted(os.listdir(_SRC_DIR))
            if f.endswith(".cc")]


def _stale(srcs) -> bool:
    """True when the library is missing or older than any source."""
    try:
        built = os.path.getmtime(_LIB_PATH)
    except OSError:
        return True
    return any(os.path.getmtime(src) > built for src in srcs)


def _build(srcs) -> bool:
    os.makedirs(os.path.dirname(_LIB_PATH), exist_ok=True)
    # compile beside the target and rename into place: a concurrent
    # process never loads a half-written library
    tmp = "%s.%d.tmp" % (_LIB_PATH, os.getpid())
    cmd = ["g++", "-O3", "-std=c++17", "-fPIC", "-shared", "-pthread",
           "-o", tmp] + srcs
    try:
        res = subprocess.run(cmd, capture_output=True, timeout=120)
        failure = None if res.returncode == 0 else "g++ exited %d:\n%s" % (
            res.returncode, res.stderr.decode(errors="replace").strip())
    except (OSError, subprocess.TimeoutExpired) as e:
        failure = str(e)
    if failure is None:
        os.replace(tmp, _LIB_PATH)
        return True
    if os.path.exists(tmp):
        os.remove(tmp)
    _log.warning("native library build failed; the pure-Python engine and "
                 "recordio codec serve instead. %s", failure)
    return False


def _configure(lib):
    i8p = ctypes.POINTER(ctypes.c_uint8)
    lib.mxtpu_recio_writer_open.restype = ctypes.c_void_p
    lib.mxtpu_recio_writer_open.argtypes = [ctypes.c_char_p]
    lib.mxtpu_recio_write.restype = ctypes.c_longlong
    lib.mxtpu_recio_write.argtypes = [ctypes.c_void_p, i8p, ctypes.c_uint64]
    lib.mxtpu_recio_writer_close.argtypes = [ctypes.c_void_p]
    lib.mxtpu_recio_reader_open.restype = ctypes.c_void_p
    lib.mxtpu_recio_reader_open.argtypes = [ctypes.c_char_p]
    lib.mxtpu_recio_reader_seek.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.mxtpu_recio_read.restype = ctypes.c_longlong
    lib.mxtpu_recio_read.argtypes = [ctypes.c_void_p, ctypes.POINTER(i8p)]
    lib.mxtpu_recio_reader_close.argtypes = [ctypes.c_void_p]

    lib.mxtpu_engine_create.restype = ctypes.c_void_p
    lib.mxtpu_engine_create.argtypes = [ctypes.c_int]
    lib.mxtpu_engine_destroy.argtypes = [ctypes.c_void_p]
    lib.mxtpu_engine_new_var.restype = ctypes.c_void_p
    lib.mxtpu_engine_new_var.argtypes = [ctypes.c_void_p]
    lib.mxtpu_engine_push.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_void_p), ctypes.c_int,
        ctypes.POINTER(ctypes.c_void_p), ctypes.c_int, ctypes.c_int]
    lib.mxtpu_engine_wait_all.argtypes = [ctypes.c_void_p]
    lib.mxtpu_engine_var_version.restype = ctypes.c_uint64
    lib.mxtpu_engine_var_version.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    return lib


def get_lib():
    """The loaded native library, or None when unavailable/disabled."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if _env.get("MXNET_TPU_NO_NATIVE"):
            return None
        srcs = _sources()
        if srcs and _stale(srcs) and not _build(srcs):
            return None
        if not os.path.exists(_LIB_PATH):
            return None
        try:
            _lib = _configure(ctypes.CDLL(_LIB_PATH))
        except OSError:
            _lib = None
        return _lib
