"""Loader for the native C++ runtime core (paddle_tpu/csrc/runtime.cc).

The reference ships these services as C++ (flags registry
paddle/phi/core/flags.h:180, LoDTensorBlockingQueue, TCPStore
paddle/phi/core/distributed/store/tcp_store.h:120, host tracer
paddle/fluid/platform/profiler/host_tracer.h:26). We compile the single-TU
runtime with g++ on first import (pybind11 is unavailable — flat C ABI via
ctypes) and cache the .so next to the source, under a name that carries a
digest of the source: the library loads on the import path, and build
products are git-ignored files that travel with a copied tree, so a binary
built from another runtime.cc must never be the one loaded, whatever the
mtimes say.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess

from .memo import Lazy

_CSRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "csrc")
_SRC = os.path.join(_CSRC, "runtime.cc")
_CXXFLAGS = ("-O2", "-std=c++17", "-fPIC", "-shared", "-pthread",
             "-fvisibility=hidden")


def _so_path() -> str:
    """The runtime library for THIS runtime.cc under THESE flags."""
    h = hashlib.sha256(" ".join(_CXXFLAGS).encode())
    with open(_SRC, "rb") as f:
        h.update(f.read())
    return os.path.join(_CSRC, f"libpaddle_tpu_rt.{h.hexdigest()[:16]}.so")


def _build() -> tuple[str | None, str | None]:
    """Build the shared library unless this source's is there already.
    Returns (path, error), one of them None.

    Concurrent-safe: N worker processes may import simultaneously (the launch
    path), so each compiles to a private mkstemp path and publishes with an
    atomic os.replace — never a shared fixed temp file that racers could
    truncate mid-compile."""
    import tempfile
    try:
        so = _so_path()
        if os.path.exists(so):
            return so, None
        fd, tmp = tempfile.mkstemp(suffix=".so", prefix=".rt_build_",
                                   dir=_CSRC)
        os.close(fd)
        try:
            proc = subprocess.run(["g++", *_CXXFLAGS, _SRC, "-o", tmp],
                                  capture_output=True, text=True,
                                  timeout=300)
            if proc.returncode != 0:
                return None, proc.stderr[-2000:]
            ctypes.CDLL(tmp)  # verify before publishing
            os.replace(tmp, so)
        finally:
            if os.path.exists(tmp):
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
        return so, None
    except Exception as e:  # toolchain missing etc. — callers fall back to Python
        return None, str(e)


def build_capi() -> str:
    """(Re)build the serving C ABI (csrc/predictor_capi.cc →
    libpaddle_tpu_capi.so, the capi_exp analog). Returns the .so path;
    raises on compile failure. Same atomic-publish discipline as _build()."""
    import tempfile
    src = os.path.join(_CSRC, "predictor_capi.cc")
    out = os.path.join(_CSRC, "libpaddle_tpu_capi.so")
    if os.path.exists(out) and os.path.getmtime(out) >= os.path.getmtime(src):
        return out
    includes = subprocess.run(["python3-config", "--includes"],
                              capture_output=True, text=True,
                              check=True).stdout.split()
    ldflags = subprocess.run(["python3-config", "--ldflags", "--embed"],
                             capture_output=True, text=True,
                             check=True).stdout.split()
    fd, tmp = tempfile.mkstemp(suffix=".so", prefix=".capi_build_", dir=_CSRC)
    os.close(fd)
    try:
        cmd = (["g++", "-O2", "-std=c++17", "-fPIC", "-shared", "-pthread",
                src] + includes + ldflags + ["-o", tmp])
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=600)
        if proc.returncode != 0:
            raise RuntimeError(f"capi build failed:\n{proc.stderr[-2000:]}")
        os.replace(tmp, out)
    finally:
        if os.path.exists(tmp):
            try:
                os.unlink(tmp)
            except OSError:
                pass
    return out


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    c = ctypes
    sigs = {
        "pt_free": (None, [c.c_void_p]),
        "pt_now_ns": (c.c_longlong, []),
        "pt_flags_set": (None, [c.c_char_p, c.c_char_p]),
        "pt_flags_get": (c.c_long, [c.c_char_p, c.c_char_p, c.c_long]),
        "pt_flags_count": (c.c_long, []),
        "pt_queue_new": (c.c_void_p, [c.c_int]),
        "pt_queue_push": (c.c_int, [c.c_void_p, c.c_char_p, c.c_long, c.c_double]),
        "pt_queue_pop": (c.c_long, [c.c_void_p, c.POINTER(c.c_void_p), c.c_double]),
        "pt_queue_size": (c.c_int, [c.c_void_p]),
        "pt_queue_close": (None, [c.c_void_p]),
        "pt_queue_free": (None, [c.c_void_p]),
        "pt_store_server_start": (c.c_void_p, [c.c_int]),
        "pt_store_server_port": (c.c_int, [c.c_void_p]),
        "pt_store_server_stop": (None, [c.c_void_p]),
        "pt_store_client_new": (c.c_void_p, [c.c_char_p, c.c_int, c.c_double]),
        "pt_store_set": (c.c_int, [c.c_void_p, c.c_char_p, c.c_char_p, c.c_long]),
        "pt_store_get": (c.c_long, [c.c_void_p, c.c_char_p, c.POINTER(c.c_void_p)]),
        "pt_store_add": (c.c_longlong, [c.c_void_p, c.c_char_p, c.c_longlong]),
        "pt_store_wait": (c.c_int, [c.c_void_p, c.c_char_p]),
        "pt_store_wait_timeout": (c.c_int, [c.c_void_p, c.c_char_p,
                                            c.c_double]),
        "pt_store_client_set_op_timeout": (None, [c.c_void_p, c.c_double]),
        "pt_store_client_last_error": (c.c_int, [c.c_void_p]),
        "pt_store_client_shutdown": (None, [c.c_void_p]),
        "pt_store_client_ok": (c.c_int, [c.c_void_p]),
        "pt_store_delete": (c.c_int, [c.c_void_p, c.c_char_p]),
        "pt_store_lease": (c.c_int, [c.c_void_p, c.c_char_p, c.c_longlong]),
        "pt_store_lease_check": (c.c_int, [c.c_void_p, c.c_char_p]),
        "pt_store_client_free": (None, [c.c_void_p]),
        "pt_trace_enable": (None, [c.c_int]),
        "pt_trace_is_enabled": (c.c_int, []),
        "pt_trace_record": (None, [c.c_char_p, c.c_char_p, c.c_longlong,
                                   c.c_longlong, c.c_longlong]),
        "pt_trace_clear": (None, []),
        "pt_trace_count": (c.c_long, []),
        "pt_trace_dump": (c.c_long, [c.POINTER(c.c_void_p)]),
        "pt_rpc_server_start": (c.c_void_p, [c.c_char_p, c.c_char_p, c.c_int]),
        "pt_rpc_server_port": (c.c_int, [c.c_void_p]),
        "pt_rpc_next_request": (c.c_long, [c.c_void_p, c.POINTER(c.c_void_p),
                                           c.POINTER(c.c_long), c.c_double]),
        "pt_rpc_send_response": (None, [c.c_void_p, c.c_long, c.c_char_p,
                                        c.c_long]),
        "pt_rpc_server_stop": (None, [c.c_void_p]),
        "pt_rpc_server_free": (None, [c.c_void_p]),
        "pt_rpc_call": (c.c_long, [c.c_char_p, c.c_int, c.c_char_p, c.c_int,
                                   c.c_char_p, c.c_long, c.POINTER(c.c_void_p),
                                   c.c_double]),
    }
    for name, (res, args) in sigs.items():
        fn = getattr(lib, name)
        fn.restype = res
        fn.argtypes = args
    return lib


def _load() -> tuple[ctypes.CDLL | None, str | None]:
    """Build + bind once per process; returns (lib, error), one of them None."""
    so, err = _build()
    if err is not None:
        return None, err
    try:
        return _bind(ctypes.CDLL(so)), None
    except OSError as e:
        # A corrupt artifact must not be cached on disk forever: remove it so
        # a later process (or rebuild) regenerates from source.
        try:
            os.unlink(so)
        except OSError:
            pass
        return None, str(e)


_loaded = Lazy(_load)


def get_lib():
    """Compile-on-demand and return the ctypes library, or None if unavailable."""
    return _loaded()[0]


def available() -> bool:
    return get_lib() is not None


def load_error() -> str | None:
    return _loaded()[1]


def _take_bytes(lib, ptr: ctypes.c_void_p, n: int) -> bytes:
    try:
        return ctypes.string_at(ptr, n)
    finally:
        lib.pt_free(ptr)


class BlockingQueue:
    """Bounded blocking queue of byte blobs backed by the native ring buffer.

    Analog of the reference's LoDTensorBlockingQueue feeding the device from a
    background thread. Falls back to queue.Queue semantics via the wrapper in
    io/dataloader.py when the native library is unavailable.
    """

    def __init__(self, capacity: int):
        self._lib = get_lib()
        if self._lib is None:
            raise RuntimeError(f"native runtime unavailable: {load_error()}")
        self._q = self._lib.pt_queue_new(int(capacity))

    def push(self, data: bytes, timeout: float = -1.0) -> bool:
        rc = self._lib.pt_queue_push(self._q, data, len(data), float(timeout))
        if rc == -2:
            raise RuntimeError("queue closed")
        return rc == 0

    def pop(self, timeout: float = -1.0):
        out = ctypes.c_void_p()
        n = self._lib.pt_queue_pop(self._q, ctypes.byref(out), float(timeout))
        if n == -1:
            return None  # timeout
        if n == -2:
            raise RuntimeError("queue closed")
        return _take_bytes(self._lib, out, n)

    def size(self) -> int:
        return self._lib.pt_queue_size(self._q)

    def close(self):
        self._lib.pt_queue_close(self._q)

    def __del__(self):
        try:
            if getattr(self, "_q", None):
                self._lib.pt_queue_free(self._q)
                self._q = None
        except Exception:
            pass
