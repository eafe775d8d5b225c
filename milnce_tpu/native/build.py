"""Build + load the native C++ runtime library (ctypes, no pybind11).

Compiles ``native/milnce_native.cpp`` on first use into
``build/libmilnce_native-<hash of the source>.so``: the name IS the
staleness check, so a library built from another source — ``build/`` is
git-ignored and travels with a copied tree — is never loaded.
Everything that uses it degrades gracefully when no C++ toolchain is
present.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import shutil
import subprocess
import threading
from typing import Optional

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "native", "milnce_native.cpp")


def _out_path() -> str:
    with open(_SRC, "rb") as fh:
        digest = hashlib.sha256(fh.read()).hexdigest()[:12]
    return os.path.join(_REPO_ROOT, "build",
                        f"libmilnce_native-{digest}.so")


_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False


def _compile(out: str) -> bool:
    cxx = shutil.which("g++") or shutil.which("c++")
    if cxx is None:
        return False
    os.makedirs(os.path.dirname(out), exist_ok=True)
    # build beside the target and rename: several processes (test
    # workers) may build at once, and none may dlopen a half-written file
    tmp = f"{out}.{os.getpid()}.tmp"
    cmd = [cxx, "-O3", "-shared", "-fPIC", "-pthread", "-std=c++17",
           "-o", tmp, _SRC]
    try:
        subprocess.run(cmd, check=True, capture_output=True)
        os.replace(tmp, out)
        return True
    except subprocess.CalledProcessError as e:
        import sys

        print(f"milnce_native build failed:\n{e.stderr.decode()}",
              file=sys.stderr)
        return False


def load_native_library() -> Optional[ctypes.CDLL]:
    """Compile-if-absent and dlopen the native library built from THIS
    source; None if unavailable."""
    global _lib, _load_failed
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        if not os.path.exists(_SRC):
            _load_failed = True
            return None
        out = _out_path()
        if not os.path.exists(out) and not _compile(out):
            _load_failed = True
            return None
        try:
            lib = ctypes.CDLL(out)
        except OSError:
            _load_failed = True
            return None
        _declare(lib)
        _lib = lib
        return _lib


def native_available() -> bool:
    return load_native_library() is not None


def _declare(lib: ctypes.CDLL) -> None:
    u8p = ctypes.POINTER(ctypes.c_uint8)
    f32p = ctypes.POINTER(ctypes.c_float)
    lib.reader_create.restype = ctypes.c_void_p
    lib.reader_create.argtypes = [ctypes.c_int]
    lib.reader_submit.restype = ctypes.c_long
    lib.reader_submit.argtypes = [ctypes.c_void_p, ctypes.c_char_p, u8p,
                                  ctypes.c_long]
    lib.reader_wait.restype = ctypes.c_long
    lib.reader_wait.argtypes = [ctypes.c_void_p, ctypes.c_long]
    lib.reader_destroy.restype = None
    lib.reader_destroy.argtypes = [ctypes.c_void_p]
    lib.softdtw_forward_cpu.restype = None
    lib.softdtw_forward_cpu.argtypes = [f32p, f32p, f32p, ctypes.c_int,
                                        ctypes.c_int, ctypes.c_int,
                                        ctypes.c_float, ctypes.c_int]
    lib.softdtw_backward_cpu.restype = None
    lib.softdtw_backward_cpu.argtypes = [f32p, f32p, f32p, f32p, ctypes.c_int,
                                         ctypes.c_int, ctypes.c_int,
                                         ctypes.c_float, ctypes.c_int]
