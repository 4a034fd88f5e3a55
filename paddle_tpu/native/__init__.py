"""Native (C++) runtime components, loaded via ctypes.

The reference keeps its data hand-off and dataset parsing in C++
(paddle/fluid/operators/reader/blocking_queue.h,
paddle/fluid/framework/data_feed.cc); so do we.  Sources live in
``csrc/`` and are compiled on first use with g++ into a cached shared
library (no pybind11 in this image — plain C ABI + ctypes).  The outputs
are never committed: a checkout builds them from what git holds, and every
build is keyed on a hash of its inputs (``build_if_stale``), not on mtimes —
a copied tree gives every file the same age.
"""

import ctypes
import hashlib
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_CSRC = os.path.join(_HERE, "csrc")


def _lib_dir():
    """Build output location: next to the sources when writable (dev
    checkout), else a per-user cache dir (read-only wheel installs)."""
    if os.access(_HERE, os.W_OK):
        return _HERE
    cache = os.path.join(
        os.environ.get("XDG_CACHE_HOME",
                       os.path.join(os.path.expanduser("~"), ".cache")),
        "paddle_tpu")
    os.makedirs(cache, exist_ok=True)
    return cache


_LIB_PATH = os.path.join(_lib_dir(), "_libpaddle_tpu_native.so")

_lib = None
_lib_lock = threading.Lock()


def _sources():
    return sorted(
        os.path.join(_CSRC, f) for f in os.listdir(_CSRC) if f.endswith(".cc")
    )


def build_if_stale(out, inputs, cmd):
    """Build ``out`` with ``cmd + ["-o", <tmp>]`` unless it was built from
    exactly these input bytes by exactly this command.  The key — sha256
    over the command line and every input file — sits beside the output in
    ``out + ".key"``; a binary with no key, or another key, is rebuilt.
    A missing compiler raises: nothing here has a pure-Python stand-in."""
    h = hashlib.sha256("\0".join(cmd).encode())
    for path in inputs:
        with open(path, "rb") as f:
            h.update(f.read())
    key = h.hexdigest()
    try:
        with open(out + ".key") as f:
            if f.read() == key and os.path.exists(out):
                return out
    except OSError:
        pass
    # a tmp name of its own for every builder: concurrent first users
    # (threads or processes) must not interleave their compilers' writes;
    # os.replace publishes atomically and the last one wins with the same
    # bytes
    tmp = "%s.%d.%d.tmp" % (out, os.getpid(), threading.get_ident())
    try:
        subprocess.run(cmd + ["-o", tmp], check=True, capture_output=True)
    except subprocess.CalledProcessError as e:
        raise RuntimeError("native build failed: %s\n%s"
                           % (" ".join(cmd), e.stderr.decode())) from e
    os.replace(tmp, out)
    with open(tmp, "w") as f:
        f.write(key)
    os.replace(tmp, out + ".key")
    return out


def _declare(lib):
    c = ctypes
    lib.dq_create.restype = c.c_void_p
    lib.dq_create.argtypes = [c.c_int]
    lib.dq_destroy.argtypes = [c.c_void_p]
    lib.dq_push.restype = c.c_int
    lib.dq_push.argtypes = [c.c_void_p, c.c_char_p, c.c_int64, c.c_int]
    lib.dq_pop.restype = c.c_int64
    lib.dq_pop.argtypes = [c.c_void_p, c.POINTER(c.c_void_p), c.c_int]
    lib.dq_free.argtypes = [c.c_void_p]
    lib.dq_close.argtypes = [c.c_void_p]
    lib.dq_kill.argtypes = [c.c_void_p]
    lib.dq_reopen.argtypes = [c.c_void_p]
    lib.dq_size.restype = c.c_int
    lib.dq_size.argtypes = [c.c_void_p]
    lib.dq_is_closed.restype = c.c_int
    lib.dq_is_closed.argtypes = [c.c_void_p]

    lib.ms_create.restype = c.c_void_p
    lib.ms_create.argtypes = [c.c_int, c.POINTER(c.c_int)]
    lib.ms_destroy.argtypes = [c.c_void_p]
    lib.ms_load_file.restype = c.c_int64
    lib.ms_load_file.argtypes = [c.c_void_p, c.c_char_p]
    lib.ms_num_records.restype = c.c_int64
    lib.ms_num_records.argtypes = [c.c_void_p]
    lib.ms_shuffle.argtypes = [c.c_void_p, c.c_uint64]
    lib.ms_clear.argtypes = [c.c_void_p]
    lib.ms_batch_slot_len.restype = c.c_int64
    lib.ms_batch_slot_len.argtypes = [c.c_void_p, c.c_int64, c.c_int64, c.c_int]
    lib.ms_batch_fill.argtypes = [
        c.c_void_p, c.c_int64, c.c_int64, c.c_int, c.c_void_p,
        c.POINTER(c.c_int64),
    ]

    # tensor RPC (tensor_rpc.cc) — PS transport
    lib.rpcs_create.restype = c.c_void_p
    lib.rpcs_create.argtypes = [c.c_int]
    lib.rpcs_port.restype = c.c_int
    lib.rpcs_port.argtypes = [c.c_void_p]
    lib.rpcs_poll.restype = c.c_int
    lib.rpcs_poll.argtypes = [
        c.c_void_p, c.c_char_p, c.c_int, c.POINTER(c.c_ubyte),
        c.POINTER(c.c_longlong), c.c_int, c.POINTER(c.c_int),
        c.POINTER(c.c_void_p), c.POINTER(c.c_longlong),
    ]
    lib.rpcs_set_var.argtypes = [
        c.c_void_p, c.c_char_p, c.c_ubyte, c.POINTER(c.c_longlong),
        c.c_int, c.c_void_p, c.c_longlong,
    ]
    lib.rpcs_set_vars.argtypes = [
        c.c_void_p, c.c_int, c.POINTER(c.c_char_p), c.POINTER(c.c_ubyte),
        c.POINTER(c.c_int), c.POINTER(c.c_longlong), c.POINTER(c.c_void_p),
        c.POINTER(c.c_longlong), c.c_int, c.POINTER(c.c_char_p),
    ]
    lib.rpcs_wait_stats.argtypes = [c.c_void_p, c.POINTER(c.c_longlong)]
    lib.rpcs_time_gets.argtypes = [c.c_void_p, c.c_char_p]
    lib.rpcs_drain_gets.restype = c.c_longlong
    lib.rpcs_drain_gets.argtypes = [c.c_void_p, c.c_void_p, c.c_longlong]
    lib.rpcs_serve.argtypes = [c.c_void_p, c.c_int]
    lib.rpcs_del_var.argtypes = [c.c_void_p, c.c_char_p]
    lib.rpcs_destroy.argtypes = [c.c_void_p]
    lib.rpcc_connect.restype = c.c_void_p
    lib.rpcc_connect.argtypes = [c.c_char_p, c.c_int]
    lib.rpcc_send_var.restype = c.c_int
    lib.rpcc_send_var.argtypes = [
        c.c_void_p, c.c_char_p, c.c_ubyte, c.POINTER(c.c_longlong),
        c.c_int, c.c_void_p, c.c_longlong,
    ]
    lib.rpcc_barrier.restype = c.c_int
    lib.rpcc_barrier.argtypes = [c.c_void_p, c.c_char_p]
    lib.rpcc_complete.restype = c.c_int
    lib.rpcc_complete.argtypes = [c.c_void_p]
    lib.rpcc_get_var.restype = c.c_longlong
    lib.rpcc_get_var.argtypes = [
        c.c_void_p, c.c_char_p, c.POINTER(c.c_ubyte),
        c.POINTER(c.c_longlong), c.c_int, c.POINTER(c.c_int),
        c.POINTER(c.c_void_p),
    ]
    lib.rpc_free.argtypes = [c.c_void_p]
    lib.rpcc_set_deadline.argtypes = [c.c_void_p, c.c_double]
    lib.rpcc_close.argtypes = [c.c_void_p]


def load():
    """Compile (if stale) and load the native library. Thread-safe."""
    global _lib
    if _lib is not None:
        return _lib
    # outside the lock: the build blocks for seconds, is idempotent and
    # publishes atomically, so racing first users need no serialization
    build_if_stale(
        _LIB_PATH, _sources(),
        ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread",
         *_sources()])
    with _lib_lock:
        if _lib is None:
            lib = ctypes.CDLL(_LIB_PATH)
            _declare(lib)
            _lib = lib
    return _lib


def available():
    """True when the native library can be built/loaded on this machine."""
    try:
        load()
        return True
    except Exception:
        return False
