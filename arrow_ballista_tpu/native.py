"""ctypes bindings for the native runtime components under native/.

Builds on demand with g++ (no pybind11 in the image; plain C ABI).  The
native pieces are optional accelerations: every caller falls back to the
Python implementation when the toolchain or the .so is unavailable.
"""
from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional

log = logging.getLogger(__name__)

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                           "native")
_BUILD_DIR = os.path.join(_NATIVE_DIR, "build")
_lock = threading.Lock()
_dataplane_lib: Optional[ctypes.CDLL] = None
_dataplane_failed = False


def _build(so_name: str, source: str) -> Optional[str]:
    so_path = os.path.join(_BUILD_DIR, so_name)
    src_path = os.path.join(_NATIVE_DIR, source)
    if os.path.exists(so_path) and \
            os.path.getmtime(so_path) >= os.path.getmtime(src_path):
        return so_path
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # several processes (test workers, executor daemons) may find the
    # library missing at once: each links under its own name and renames
    # into place, so none ever loads a half-written file
    tmp_path = f"{so_path}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-std=c++17", "-fPIC", "-shared", "-o", tmp_path,
           src_path, "-lpthread"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp_path, so_path)
        return so_path
    except (subprocess.SubprocessError, FileNotFoundError) as e:
        stderr = getattr(e, "stderr", b"") or b""
        log.warning("native build failed (%s): %s", so_name,
                    stderr.decode(errors="replace")[-2000:])
        return None


def dataplane() -> Optional[ctypes.CDLL]:
    """The native shuffle data-plane server (native/dataplane.cpp).
    Returns None when unavailable."""
    global _dataplane_lib, _dataplane_failed
    with _lock:
        if _dataplane_lib is not None or _dataplane_failed:
            return _dataplane_lib
        so = _build("libdataplane.so", "dataplane.cpp")
        if so is None:
            _dataplane_failed = True
            return None
        lib = ctypes.CDLL(so)
        lib.dp_start.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                 ctypes.c_char_p, ctypes.c_int]
        lib.dp_start.restype = ctypes.c_int
        lib.dp_stop.argtypes = []
        lib.dp_stop.restype = None
        lib.dp_bytes_served.argtypes = []
        lib.dp_bytes_served.restype = ctypes.c_uint64
        _dataplane_lib = lib
        return lib
