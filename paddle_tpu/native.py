"""ctypes bridge to the native core (libpaddle_tpu_core.so, built from
csrc/ — the framework's C++ runtime layer: flag registry, host staging
arena, host tracer, TCPStore rendezvous, batch staging engine).

The library is built from csrc/ on first use (cmake+ninja) and rebuilt
whenever a source is newer than it, so a checkout never runs a library its
sources do not describe.  Every consumer degrades to a pure-Python path
when no library can be had (no toolchain); `lib_status()` says which.
"""

from __future__ import annotations

import ctypes
import glob
import logging
import os
import subprocess
import threading

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CSRC = os.path.join(_ROOT, "csrc")
_BUILD = os.path.join(_CSRC, "build")
_LIBNAME = "libpaddle_tpu_core.so"
# installed wheel layout: the .so is bundled inside the package dir
_PKG_LIB = os.path.join(os.path.dirname(os.path.abspath(__file__)), _LIBNAME)

_lib = None
_tried = False
_status = "not loaded"
_lock = threading.Lock()


def _try_build():
    """Returns (path or None, status)."""
    if not os.path.isdir(_CSRC):
        return None, "unavailable: no csrc/ beside the package"
    try:
        subprocess.run(
            ["cmake", "-B", _BUILD, "-G", "Ninja", "-DCMAKE_BUILD_TYPE=Release"],
            cwd=_CSRC, capture_output=True, timeout=120, check=True,
        )
        subprocess.run(
            ["ninja", "-C", _BUILD, "paddle_tpu_core"],
            capture_output=True, timeout=300, check=True,
        )
    except (OSError, subprocess.SubprocessError) as e:
        tail = (getattr(e, "stderr", None) or b"")[-300:].decode(errors="replace")
        return None, f"unavailable: build failed ({e}) {tail}".strip()
    path = os.path.join(_BUILD, _LIBNAME)
    if not os.path.exists(path):
        return None, "unavailable: build produced no library"
    return path, f"built from csrc/ into {path}"


def _older_than_sources(path):
    built = os.path.getmtime(path)
    sources = glob.glob(os.path.join(_CSRC, "*.cc")) + [
        os.path.join(_CSRC, "CMakeLists.txt")
    ]
    return any(os.path.getmtime(s) > built for s in sources)


def _locate():
    """Returns (path or None, status): the wheel's bundled library, else the
    csrc/build one — rebuilt first if it is missing or older than a source."""
    if os.path.exists(_PKG_LIB):
        return _PKG_LIB, f"bundled {_PKG_LIB}"
    path = os.path.join(_BUILD, _LIBNAME)
    if os.path.exists(path) and not _older_than_sources(path):
        return path, f"prebuilt {path}"
    return _try_build()


def lib_status():
    """One line on which native library this process got: bundled, prebuilt,
    built on this call, or unavailable and why."""
    get_lib()
    return _status


def get_lib():
    """Returns the loaded CDLL or None."""
    global _lib, _tried, _status
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        path, _status = _locate()
        if path is None:
            logging.getLogger("paddle_tpu").warning("native core %s", _status)
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError as e:
            _status = f"unavailable: {path} did not load ({e})"
            logging.getLogger("paddle_tpu").warning("native core %s", _status)
            return None
        # signatures
        lib.pt_host_alloc.restype = ctypes.c_void_p
        lib.pt_host_alloc.argtypes = [ctypes.c_size_t]
        lib.pt_host_free.argtypes = [ctypes.c_void_p]
        lib.pt_host_bytes_in_use.restype = ctypes.c_int64
        lib.pt_host_peak_bytes.restype = ctypes.c_int64
        lib.pt_host_bytes_reserved.restype = ctypes.c_int64
        lib.pt_host_alloc_count.restype = ctypes.c_int64
        lib.pt_trace_begin.restype = ctypes.c_int64
        lib.pt_trace_begin.argtypes = [ctypes.c_char_p]
        lib.pt_trace_end.argtypes = [ctypes.c_int64]
        lib.pt_trace_mark.argtypes = [ctypes.c_char_p]
        lib.pt_trace_export_chrome.argtypes = [ctypes.c_char_p]
        lib.pt_trace_event_count.restype = ctypes.c_int64
        lib.pt_flag_define_bool.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.pt_flag_define_int.argtypes = [ctypes.c_char_p, ctypes.c_longlong]
        lib.pt_flag_define_double.argtypes = [ctypes.c_char_p, ctypes.c_double]
        lib.pt_flag_define_string.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
        lib.pt_flag_set.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
        lib.pt_flag_get_bool.argtypes = [ctypes.c_char_p]
        lib.pt_flag_get_int.argtypes = [ctypes.c_char_p]
        lib.pt_flag_get_int.restype = ctypes.c_longlong
        lib.pt_flag_get_double.argtypes = [ctypes.c_char_p]
        lib.pt_flag_get_double.restype = ctypes.c_double
        lib.pt_flag_get_string.argtypes = [ctypes.c_char_p]
        lib.pt_flag_get_string.restype = ctypes.c_char_p
        lib.pt_store_server_start.restype = ctypes.c_void_p
        lib.pt_store_server_start.argtypes = [ctypes.c_int]
        lib.pt_store_server_port.restype = ctypes.c_int
        lib.pt_store_server_port.argtypes = [ctypes.c_void_p]
        lib.pt_store_server_stop.argtypes = [ctypes.c_void_p]
        lib.pt_store_connect.restype = ctypes.c_void_p
        lib.pt_store_connect.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.pt_store_set.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int]
        lib.pt_store_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int]
        lib.pt_store_add.restype = ctypes.c_longlong
        lib.pt_store_add.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_longlong]
        lib.pt_store_check.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.pt_store_close.argtypes = [ctypes.c_void_p]
        lib.pt_stage_create.restype = ctypes.c_void_p
        lib.pt_stage_create.argtypes = [ctypes.c_int]
        lib.pt_stage_destroy.argtypes = [ctypes.c_void_p]
        lib.pt_stage_submit.restype = ctypes.c_void_p
        lib.pt_stage_submit.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
        ]
        lib.pt_stage_ready.argtypes = [ctypes.c_void_p]
        lib.pt_stage_buffer.restype = ctypes.c_void_p
        lib.pt_stage_buffer.argtypes = [ctypes.c_void_p]
        lib.pt_stage_release.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def available():
    return get_lib() is not None


# ---------------------------------------------------------------------------
# convenience wrappers
# ---------------------------------------------------------------------------


def host_memory_stats():
    lib = get_lib()
    if lib is None:
        return {}
    return {
        "host_bytes_in_use": lib.pt_host_bytes_in_use(),
        "host_peak_bytes": lib.pt_host_peak_bytes(),
        "host_bytes_reserved": lib.pt_host_bytes_reserved(),
        "host_alloc_count": lib.pt_host_alloc_count(),
    }


class TCPStore:
    """Rendezvous KV store over the native server (reference: paddle TcpStore).

    is_master=True starts the server in-process; all ranks connect as clients.
    """

    def __init__(self, host="127.0.0.1", port=0, is_master=False, world_size=None, timeout=None):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native core library unavailable; build csrc/ first")
        self._lib = lib
        self._server = None
        if is_master:
            self._server = lib.pt_store_server_start(port)
            if not self._server:
                raise RuntimeError(f"failed to bind TCPStore on port {port}")
            port = lib.pt_store_server_port(self._server)
        self.host = host
        self.port = port
        self._client = lib.pt_store_connect(host.encode(), port)
        if not self._client:
            raise RuntimeError(f"failed to connect TCPStore {host}:{port}")

    def set(self, key, value):
        if isinstance(value, str):
            value = value.encode()
        self._lib.pt_store_set(self._client, key.encode(), value, len(value))

    def get(self, key, cap=1 << 16):
        buf = ctypes.create_string_buffer(cap)
        n = self._lib.pt_store_get(self._client, key.encode(), buf, cap)
        if n < 0:
            raise RuntimeError(f"TCPStore get({key!r}) failed")
        return buf.raw[:n]

    def add(self, key, delta):
        out = self._lib.pt_store_add(self._client, key.encode(), delta)
        if out < 0:
            # counters are non-negative by construction; -1 means the
            # connection died (e.g. the master exited) — surface it instead
            # of letting callers supervise forever against a dead store
            raise RuntimeError(f"TCPStore add({key!r}) failed: connection lost")
        return out

    def check(self, key):
        return bool(self._lib.pt_store_check(self._client, key.encode()))

    def wait(self, keys):
        for k in keys if isinstance(keys, (list, tuple)) else [keys]:
            self.get(k)

    def barrier(self, name, world_size):
        n = self.add(f"__barrier__{name}", 1)
        if n == world_size:
            self.set(f"__barrier__{name}__done", "1")
        self.get(f"__barrier__{name}__done")

    def close(self):
        if self._client:
            self._lib.pt_store_close(self._client)
            self._client = None
        if self._server:
            self._lib.pt_store_server_stop(self._server)
            self._server = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class BatchStage:
    """Native gather engine for DataLoader fast path: rows of a contiguous
    numpy array gathered into arena buffers by C++ threads (GIL-free)."""

    def __init__(self, num_workers=2):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native core library unavailable")
        self._lib = lib
        self._h = lib.pt_stage_create(num_workers)

    def gather(self, array, indices):
        """array: 2D+ C-contiguous np array; indices: int list → new np array."""
        import numpy as np

        arr = np.ascontiguousarray(array)
        row_bytes = arr.dtype.itemsize * int(np.prod(arr.shape[1:]))
        idx = np.asarray(indices, np.int64)
        c_idx = idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
        job = self._lib.pt_stage_submit(
            self._h, arr.ctypes.data_as(ctypes.c_void_p), row_bytes, c_idx, len(idx)
        )
        import time

        while not self._lib.pt_stage_ready(job):
            time.sleep(0)
        buf = self._lib.pt_stage_buffer(job)
        out_shape = (len(idx),) + arr.shape[1:]
        out = np.ctypeslib.as_array(
            ctypes.cast(buf, ctypes.POINTER(ctypes.c_uint8)), (row_bytes * len(idx),)
        ).view(arr.dtype).reshape(out_shape).copy()
        self._lib.pt_stage_release(job)
        return out

    def close(self):
        if self._h:
            self._lib.pt_stage_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class RecordEventNative:
    """Host tracer span via the native recorder (chrome-trace exportable)."""

    def __init__(self, name):
        self.name = name.encode()
        self._id = -1

    def __enter__(self):
        lib = get_lib()
        if lib is not None:
            self._id = lib.pt_trace_begin(self.name)
        return self

    def __exit__(self, *exc):
        lib = get_lib()
        if lib is not None:
            lib.pt_trace_end(self._id)
        return False


def trace_enable(on=True):
    lib = get_lib()
    if lib is not None:
        lib.pt_trace_enable(1 if on else 0)


def trace_export(path):
    lib = get_lib()
    if lib is not None:
        return lib.pt_trace_export_chrome(path.encode())
    return -1
