"""Build helpers for the C API library and the standalone C++ demo trainer
(parity: cmake/generic.cmake cc_library/cc_binary for c_api.cc +
train/demo/CMakeLists)."""

import os
import sysconfig

from . import build_if_stale

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(os.path.dirname(_HERE))
_CAPI_SRC = os.path.join(_HERE, "csrc_capi", "paddle_tpu_c.cc")
_CAPI_LIB = os.path.join(_HERE, "_libpaddle_tpu_c.so")


def _py_flags():
    inc = sysconfig.get_path("include")
    libdir = sysconfig.get_config_var("LIBDIR")
    ver = sysconfig.get_config_var("LDVERSION") or sysconfig.get_config_var(
        "VERSION")
    return ["-I" + inc], ["-L" + libdir, "-Wl,-rpath," + libdir,
                          "-lpython" + ver, "-ldl", "-lm"]


def build_capi():
    """Compile native/csrc_capi/paddle_tpu_c.cc -> _libpaddle_tpu_c.so."""
    cflags, ldflags = _py_flags()
    return build_if_stale(
        _CAPI_LIB, [_CAPI_SRC],
        ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread",
         *cflags, _CAPI_SRC, *ldflags])


def build_demo_trainer(out_path=None):
    """Compile tools/demo_trainer.cc linking the C API library."""
    lib = build_capi()
    src = os.path.join(_REPO, "tools", "demo_trainer.cc")
    return build_if_stale(
        out_path or os.path.join(_HERE, "_demo_trainer"), [src, lib],
        ["g++", "-O2", "-std=c++17", src, lib,
         "-Wl,-rpath," + os.path.dirname(lib)])
