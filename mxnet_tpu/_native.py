"""ctypes bindings for the native runtime (src/native/).

Reference analog: python/mxnet/base.py's ctypes loader for libmxnet.so.
The native library provides the host-side runtime — threaded dependency
engine (versioned vars, exception propagation at sync points), RecordIO,
and a prefetching reader. It is built on demand with `make` (g++); when no
toolchain is available everything gracefully reports unavailable and pure-
Python fallbacks take over (recordio.py).

Set MXNET_TPU_NO_NATIVE=1 to force the pure-Python paths.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Callable, Optional, Sequence

from .base import MXNetError, get_env

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_LIB_PATH = os.path.join(_REPO_ROOT, "build", "libmxt_native.so")
_SRC_DIR = os.path.join(_REPO_ROOT, "src", "native")

_lib = None
# bare on purpose: leaf guard below the audit layer (native library bootstrap)
_lib_lock = threading.Lock()  # mx-lint: allow=MXA009
_load_failed = False

_OP_FN = ctypes.CFUNCTYPE(ctypes.c_int, ctypes.c_void_p)


#: set by get_lib(): did ``make`` (re)write the library this process
#: loaded? False means make found build/libmxt_native.so already current
#: with src/native/.
_built_this_run = False


def _lib_mtime():
    return os.path.getmtime(_LIB_PATH) if os.path.exists(_LIB_PATH) \
        else None


def _build_lib() -> bool:
    try:
        r = subprocess.run(["make", "-C", _SRC_DIR],
                           capture_output=True, timeout=240)
        return r.returncode == 0 and os.path.exists(_LIB_PATH)
    except (OSError, subprocess.TimeoutExpired):
        return False


def built_this_run() -> bool:
    """True when the loaded library was compiled from src/native/ by
    THIS process (chip_smoke.py reports it)."""
    return get_lib() is not None and _built_this_run


def _declare(lib):
    lib.MXTGetLastError.restype = ctypes.c_char_p
    lib.MXTSetCallbackError.argtypes = [ctypes.c_char_p]
    H = ctypes.c_void_p
    lib.MXTEngineCreate.argtypes = [ctypes.c_int, ctypes.POINTER(H)]
    lib.MXTEngineDestroy.argtypes = [H]
    lib.MXTEngineNewVar.argtypes = [H, ctypes.POINTER(H)]
    lib.MXTEngineDeleteVar.argtypes = [H, H]
    lib.MXTEnginePushAsync.argtypes = [H, _OP_FN, ctypes.c_void_p,
                                       ctypes.c_void_p, ctypes.POINTER(H),
                                       ctypes.c_int, ctypes.POINTER(H),
                                       ctypes.c_int]
    lib.MXTEngineWaitForVar.argtypes = [H, H]
    lib.MXTEngineWaitForAll.argtypes = [H]
    lib.MXTEngineVarVersion.argtypes = [H, H,
                                        ctypes.POINTER(ctypes.c_uint64)]
    lib.MXTRecordIOWriterCreate.argtypes = [ctypes.c_char_p,
                                            ctypes.POINTER(H)]
    lib.MXTRecordIOWriterWrite.argtypes = [H, ctypes.c_char_p,
                                           ctypes.c_size_t,
                                           ctypes.POINTER(ctypes.c_uint64)]
    lib.MXTRecordIOWriterTell.argtypes = [H, ctypes.POINTER(ctypes.c_uint64)]
    lib.MXTRecordIOWriterClose.argtypes = [H]
    lib.MXTRecordIOReaderCreate.argtypes = [ctypes.c_char_p,
                                            ctypes.POINTER(H)]
    lib.MXTRecordIOReaderNext.argtypes = [H, ctypes.POINTER(ctypes.c_void_p),
                                          ctypes.POINTER(ctypes.c_size_t)]
    lib.MXTRecordIOReaderSeek.argtypes = [H, ctypes.c_uint64]
    lib.MXTRecordIOReaderTell.argtypes = [H, ctypes.POINTER(ctypes.c_uint64)]
    lib.MXTRecordIOReaderClose.argtypes = [H]
    lib.MXTPrefetchCreate.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                      ctypes.POINTER(H)]
    lib.MXTPrefetchNext.argtypes = [H, ctypes.POINTER(ctypes.c_void_p),
                                    ctypes.POINTER(ctypes.c_size_t)]
    lib.MXTPrefetchDestroy.argtypes = [H]
    lib.MXTBatchifyStack.argtypes = [
        ctypes.POINTER(ctypes.c_void_p), ctypes.c_int, ctypes.c_size_t,
        ctypes.c_void_p, ctypes.c_int]
    lib.MXTBatchifyImageNormalize.argtypes = [
        ctypes.POINTER(ctypes.c_void_p), ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
        ctypes.c_int]
    try:
        # OPTIONAL symbols (need libjpeg at build time): a stale library
        # without them must not poison engine/recordio/batchify — image.py
        # hasattr-guards the decode fast path
        lib.MXTImageJPEGInfo.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int)]
        lib.MXTImageJPEGDecode.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int]
    except AttributeError:
        pass
    try:
        lib.MXTImagePNGInfo.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int)]
        lib.MXTImagePNGDecode.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int]
    except AttributeError:
        pass


def get_lib():
    """The loaded native library, or None when unavailable."""
    global _lib, _load_failed, _built_this_run
    if _lib is not None or _load_failed:
        return _lib
    with _lib_lock:
        if _lib is not None or _load_failed:
            return _lib
        if get_env("MXNET_TPU_NO_NATIVE", "0") == "1":
            _load_failed = True
            return None
        # make is a fast no-op when the .so is current, and rebuilds it
        # when headers/sources changed (stale-symbol protection)
        before = _lib_mtime()
        if not _build_lib():
            if before is not None:
                # a library make could not vouch for is not a fallback
                raise MXNetError(
                    f"`make -C {_SRC_DIR}` failed but {_LIB_PATH} exists: "
                    "refusing to load a library that may not match "
                    "src/native/. Fix the build, delete the file, or set "
                    "MXNET_TPU_NO_NATIVE=1 for the pure-Python paths.")
            _load_failed = True
            return None
        _built_this_run = _lib_mtime() != before
        try:
            lib = ctypes.CDLL(_LIB_PATH)
            _declare(lib)
            _lib = lib
        except (OSError, AttributeError):
            _load_failed = True
    return _lib


def available() -> bool:
    return get_lib() is not None


def _check(rc: int):
    if rc != 0:
        msg = get_lib().MXTGetLastError().decode() or "native call failed"
        raise MXNetError(msg)


class NativeEngine:
    """Host-task dependency engine (reference ThreadedEngine semantics:
    shared reads, exclusive writes per var, async exceptions surfacing at
    wait points). Schedules Python callables on C++ threads."""

    def __init__(self, num_threads: int = 0):
        lib = get_lib()
        if lib is None:
            raise MXNetError("native runtime not available")
        self._lib = lib
        h = ctypes.c_void_p()
        _check(lib.MXTEngineCreate(num_threads, ctypes.byref(h)))
        self._h = h
        self._closures = {}
        # bare on purpose: leaf, engine-internal; never nests with audited locks
        self._closure_lock = threading.Lock()  # mx-lint: allow=MXA009
        self._next_token = 1  # 0 would round-trip as NULL/None through ctypes

        def trampoline(token):
            with self._closure_lock:
                fn = self._closures.pop(token, None)
            if fn is None:
                return -1
            try:
                fn()
                return 0
            except Exception as e:  # surfaced at wait_for_var/wait_for_all
                self._lib.MXTSetCallbackError(
                    f"{type(e).__name__}: {e}".encode())
                return -1

        self._trampoline = _OP_FN(trampoline)  # keep alive

    def new_var(self) -> int:
        h = ctypes.c_void_p()
        _check(self._lib.MXTEngineNewVar(self._h, ctypes.byref(h)))
        return h.value

    def delete_var(self, var: int):
        _check(self._lib.MXTEngineDeleteVar(self._h, ctypes.c_void_p(var)))

    def push(self, fn: Callable[[], None],
             const_vars: Sequence[int] = (),
             mutable_vars: Sequence[int] = ()):
        """Schedule ``fn`` after its dependencies; reads run concurrently,
        writes exclusively (reference Engine::PushAsync)."""
        with self._closure_lock:
            token = self._next_token
            self._next_token += 1
            self._closures[token] = fn
        cv = (ctypes.c_void_p * max(len(const_vars), 1))(*const_vars)
        mv = (ctypes.c_void_p * max(len(mutable_vars), 1))(*mutable_vars)
        _check(self._lib.MXTEnginePushAsync(
            self._h, self._trampoline, ctypes.c_void_p(token), None,
            cv, len(const_vars), mv, len(mutable_vars)))

    def wait_for_var(self, var: int):
        _check(self._lib.MXTEngineWaitForVar(self._h, ctypes.c_void_p(var)))

    def wait_for_all(self):
        _check(self._lib.MXTEngineWaitForAll(self._h))

    def var_version(self, var: int) -> int:
        out = ctypes.c_uint64()
        _check(self._lib.MXTEngineVarVersion(self._h, ctypes.c_void_p(var),
                                             ctypes.byref(out)))
        return out.value

    def close(self):
        if self._h is not None:
            self._lib.MXTEngineDestroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativeRecordIOWriter:
    def __init__(self, path: str):
        self._lib = get_lib()
        h = ctypes.c_void_p()
        _check(self._lib.MXTRecordIOWriterCreate(path.encode(),
                                                 ctypes.byref(h)))
        self._h = h

    def write(self, data: bytes) -> int:
        pos = ctypes.c_uint64()
        _check(self._lib.MXTRecordIOWriterWrite(self._h, data, len(data),
                                                ctypes.byref(pos)))
        return pos.value

    def tell(self) -> int:
        out = ctypes.c_uint64()
        _check(self._lib.MXTRecordIOWriterTell(self._h, ctypes.byref(out)))
        return out.value

    def close(self):
        if self._h is not None:
            self._lib.MXTRecordIOWriterClose(self._h)
            self._h = None


class NativeRecordIOReader:
    def __init__(self, path: str):
        self._lib = get_lib()
        h = ctypes.c_void_p()
        _check(self._lib.MXTRecordIOReaderCreate(path.encode(),
                                                 ctypes.byref(h)))
        self._h = h

    def read(self) -> Optional[bytes]:
        data = ctypes.c_void_p()
        ln = ctypes.c_size_t()
        _check(self._lib.MXTRecordIOReaderNext(self._h, ctypes.byref(data),
                                               ctypes.byref(ln)))
        if data.value is None:
            return None
        return ctypes.string_at(data.value, ln.value)

    def seek(self, pos: int):
        _check(self._lib.MXTRecordIOReaderSeek(self._h, pos))

    def tell(self) -> int:
        out = ctypes.c_uint64()
        _check(self._lib.MXTRecordIOReaderTell(self._h, ctypes.byref(out)))
        return out.value

    def close(self):
        if self._h is not None:
            self._lib.MXTRecordIOReaderClose(self._h)
            self._h = None


class NativePrefetchReader:
    """C++ read-ahead thread over a RecordIO file (bounded queue)."""

    def __init__(self, path: str, capacity: int = 64):
        self._lib = get_lib()
        h = ctypes.c_void_p()
        _check(self._lib.MXTPrefetchCreate(path.encode(), capacity,
                                           ctypes.byref(h)))
        self._h = h

    def read(self) -> Optional[bytes]:
        data = ctypes.c_void_p()
        ln = ctypes.c_size_t()
        _check(self._lib.MXTPrefetchNext(self._h, ctypes.byref(data),
                                         ctypes.byref(ln)))
        if data.value is None:
            return None
        return ctypes.string_at(data.value, ln.value)

    def __iter__(self):
        while True:
            rec = self.read()
            if rec is None:
                return
            yield rec

    def close(self):
        if self._h is not None:
            self._lib.MXTPrefetchDestroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
