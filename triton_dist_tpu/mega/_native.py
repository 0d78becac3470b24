"""ctypes loader/builder for the native scheduler library.

The reference ships native planning code built by its cmake tree
(ref: csrc/CMakeLists.txt, python/setup.py:54-146); here one translation
unit is compiled on demand with g++ into the package build dir (pybind11
is not available in this environment — the C ABI + ctypes is the binding).
Every native entry point has a pure-Python mirror in mega/scheduler.py,
selected ONLY by `TDT_NO_NATIVE=1`: a build or load that fails is an
error, never a silent change of scheduler.

The library is keyed by the CONTENT of csrc/scheduler.cc
(`libtdtsched-<sha256 prefix>.so`), so whatever else sits in the
git-ignored build directory — a stale build of another source — is
never loaded.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

_SRC = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "csrc", "scheduler.cc"))
_OUT_DIR = os.path.join(os.path.dirname(_SRC), "build")

_lock = threading.Lock()
_cached: Optional[ctypes.CDLL] = None


def _lib_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_OUT_DIR, f"libtdtsched-{digest}.so")


def _build(lib: str) -> None:
    os.makedirs(_OUT_DIR, exist_ok=True)
    tmp = f"{lib}.tmp{os.getpid()}"
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, lib)  # atomic: concurrent builders agree
    except (OSError, subprocess.SubprocessError) as e:
        if os.path.exists(tmp):
            os.unlink(tmp)
        detail = getattr(e, "stderr", b"") or b""
        raise RuntimeError(
            f"native scheduler build failed ({' '.join(cmd)}): {e}\n"
            f"{detail.decode(errors='replace')}\n"
            "set TDT_NO_NATIVE=1 to run the pure-Python scheduler instead"
        ) from e


def load() -> Optional[ctypes.CDLL]:
    """The native lib, built on first use. None ONLY when
    TDT_NO_NATIVE=1 asked for the Python scheduler; a failed build or
    load raises."""
    global _cached
    if os.environ.get("TDT_NO_NATIVE") == "1":
        return None
    with _lock:
        if _cached is not None:
            return _cached
        path = _lib_path()
        if not os.path.exists(path):
            _build(path)
        lib = ctypes.CDLL(path)
        i32p = ctypes.POINTER(ctypes.c_int32)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        f64p = ctypes.POINTER(ctypes.c_double)
        lib.tdt_schedule.restype = ctypes.c_int
        lib.tdt_schedule.argtypes = [
            ctypes.c_int32, ctypes.c_int32, i32p, i32p, f64p,
            ctypes.c_int32, ctypes.c_int32, i32p, i32p,
        ]
        lib.tdt_watermarks.restype = ctypes.c_int
        lib.tdt_watermarks.argtypes = [
            ctypes.c_int32, ctypes.c_int32, i32p, i32p, i32p, i32p,
            ctypes.c_int32, i32p,
        ]
        lib.tdt_plan_slots.restype = ctypes.c_int
        lib.tdt_plan_slots.argtypes = [
            ctypes.c_int32, i32p, i32p, u8p, i32p,
        ]
        _cached = lib
        return _cached
