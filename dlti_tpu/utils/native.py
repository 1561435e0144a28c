"""Loader for the native (C++) runtime library.

The reference outsources its native runtime to external wheels (torch/NCCL/
DeepSpeed ops — SURVEY.md §2b); ours is in-tree under ``native/``.

What runs is a function of the committed sources: the library is built
from ``native/*.cc`` by the first process that asks for it (about a second
with ``g++``) and rebuilt whenever a source is newer than it, so a checkout
and a copy of a working tree cannot end up on different allocator/packer
code because one of them happened to carry a stale binary. Where it cannot
be built (no compiler, read-only tree) a warning says so once and the
pure-Python implementations serve; they are also the tests' oracle.
"""

from __future__ import annotations

import ctypes
import fcntl
import glob
import os
import subprocess
import warnings
from typing import Optional

_LIB: Optional[ctypes.CDLL] = None
_TRIED = False

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libdlti_runtime.so")


def _up_to_date(sources: list) -> bool:
    try:
        built = os.path.getmtime(_LIB_PATH)
    except OSError:
        return False
    return all(os.path.getmtime(s) <= built for s in sources)


def _build() -> bool:
    """Compile ``native/*.cc`` into the library — the one build recipe
    there is. One builder at a time: concurrent first users
    (test subprocesses, fleet workers) queue on a file lock and find the
    result; the output lands under its final name by rename, so a reader
    never maps a half-written file."""
    sources = sorted(glob.glob(os.path.join(_NATIVE_DIR, "*.cc")))
    if not sources:
        return False
    try:
        with open(os.path.join(_NATIVE_DIR, ".build.lock"), "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            if _up_to_date(sources):
                return True
            staged = _LIB_PATH + ".build"
            proc = subprocess.run(
                [os.environ.get("CXX", "g++"), "-O2", "-fPIC", "-std=c++17",
                 "-shared", "-o", staged, *sources],
                capture_output=True, text=True)
            if proc.returncode != 0:
                warnings.warn(
                    f"native runtime build failed (rc {proc.returncode}); "
                    f"using the pure-Python paths: {proc.stderr[-400:]}")
                return False
            os.replace(staged, _LIB_PATH)
            return True
    except OSError as e:
        warnings.warn(f"native runtime not built ({e}); using the "
                      f"pure-Python paths")
        return False


def load_native_runtime() -> Optional[ctypes.CDLL]:
    """Return the loaded native runtime, or None if unavailable.

    Set ``DLTI_DISABLE_NATIVE=1`` to force the pure-Python paths (used by
    tests to cover both implementations).
    """
    global _LIB, _TRIED
    if os.environ.get("DLTI_DISABLE_NATIVE") == "1":
        return None
    if _TRIED:
        return _LIB
    _TRIED = True
    if not _build():
        return None
    lib = ctypes.CDLL(_LIB_PATH)
    # Allocator ABI.
    lib.dlti_allocator_create.argtypes = [ctypes.c_int32]
    lib.dlti_allocator_create.restype = ctypes.c_void_p
    lib.dlti_allocator_destroy.argtypes = [ctypes.c_void_p]
    lib.dlti_allocator_num_free.argtypes = [ctypes.c_void_p]
    lib.dlti_allocator_num_free.restype = ctypes.c_int32
    lib.dlti_allocator_allocate.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, ctypes.POINTER(ctypes.c_int32)]
    lib.dlti_allocator_allocate.restype = ctypes.c_int32
    # Guarded free: 1 = freed, 0 = rejected batch (out-of-range / double
    # free); rejection frees nothing.
    lib.dlti_allocator_free_checked.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, ctypes.POINTER(ctypes.c_int32)]
    lib.dlti_allocator_free_checked.restype = ctypes.c_int32
    # Packer ABI.
    lib.dlti_pack_assign.argtypes = [
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32, ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32)]
    lib.dlti_pack_assign.restype = ctypes.c_int32
    _LIB = lib
    return _LIB


def native_runtime_name() -> str:
    """``"native"`` or ``"python"``: which allocator/packer implementation
    this process runs (for build-time logs)."""
    return "native" if load_native_runtime() is not None else "python"
