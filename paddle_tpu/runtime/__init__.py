"""Native runtime extensions (C++), loaded via ctypes.

The reference implements its data pipeline in C++ (framework/data_feed.cc,
data_set.cc, channel.h); this package holds the TPU framework's native
equivalents.  Libraries are compiled on first use with g++ (no pybind11 in
the image — plain C ABI + ctypes) and cached next to the source; a pure
Python fallback exists for every native path, selected automatically when the
toolchain is unavailable or PADDLE_TPU_NO_NATIVE=1 is set.
"""

import ctypes
import os
import subprocess
import threading
import warnings

_build_lock = threading.Lock()
_cache = {}


def _python_flags():
    """Include/link flags for extensions that embed CPython (capi.cc)."""
    import sysconfig

    inc = sysconfig.get_paths()["include"]
    libdir = sysconfig.get_config_var("LIBDIR") or ""
    ver = sysconfig.get_config_var("LDVERSION") or sysconfig.get_config_var(
        "VERSION")
    return (["-I" + inc],
            (["-L" + libdir] if libdir else []) + ["-lpython%s" % ver])


def _build(name):
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(here, name + ".cc")
    so = os.path.join(here, "lib" + name + ".so")
    hdr = os.path.join(here, name + ".h")
    newest = max([os.path.getmtime(src)]
                 + ([os.path.getmtime(hdr)] if os.path.exists(hdr) else []))
    if os.path.exists(so) and os.path.getmtime(so) >= newest:
        return so
    cflags, ldflags = ([], [])
    if name == "capi":
        cflags, ldflags = _python_flags()
    cmd = (["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread"]
           + cflags + [src, "-o", so] + ldflags)
    subprocess.run(cmd, check=True, capture_output=True, text=True)
    return so


def load(name):
    """Load (building if needed) the native library `name`; returns a
    ctypes.CDLL or None when native is disabled/unbuildable."""
    if os.environ.get("PADDLE_TPU_NO_NATIVE"):
        return None
    with _build_lock:
        if name in _cache:
            return _cache[name]
        try:
            lib = ctypes.CDLL(_build(name))
        except (OSError, subprocess.CalledProcessError) as e:
            warnings.warn("native runtime lib%s.so not built (%s: %s); "
                          "using the pure-Python path"
                          % (name, type(e).__name__,
                             getattr(e, "stderr", None) or e))
            lib = None
        _cache[name] = lib
        return lib
