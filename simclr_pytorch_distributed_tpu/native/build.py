"""Build + load the native staging library (ctypes, no pip/pybind needed).

Compiled once per machine into the package dir; falls back to None (callers use
numpy paths) if no toolchain is available.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "gather.cpp")
_FLAGS = ["-O3", "-shared", "-fPIC", "-std=c++17"]
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _lib_path() -> str:
    """The library's file name carries a hash of its source and flags, so a
    stale ``.so`` (other source, a copied tree) is never the one loaded."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read() + " ".join(_FLAGS).encode())
    return os.path.join(_DIR, f"libsptpu_native.{digest.hexdigest()[:16]}.so")


def _compile(lib_path: str) -> bool:
    # build under a private name, then rename: concurrent first imports
    # (pytest workers) never load a half-written file
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    cmd = ["g++", *_FLAGS, _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, lib_path)
        return True
    except (OSError, subprocess.SubprocessError) as e:  # no toolchain
        logging.warning(
            "native staging lib unavailable (%s); using numpy paths", e
        )
        return False


def load() -> Optional[ctypes.CDLL]:
    """Returns the loaded library or None. Thread-safe, compiles on first use."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("SPTPU_NATIVE", "1") == "0":
            return None
        lib_path = _lib_path()
        if not os.path.exists(lib_path) and not _compile(lib_path):
            return None
        try:
            lib = ctypes.CDLL(lib_path)
        except OSError as e:
            logging.warning("failed to load native lib: %s", e)
            return None
        lib.gather_rows_u8.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p,
        ]
        lib.gather_rows_i32.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
        ]
        _lib = lib
        return _lib
