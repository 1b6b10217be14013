"""Native (C++) runtime pieces, built on demand with g++.

The compiled library is cached under ``native/build/`` (ignored by git)
under a name keyed on what produced it: the bytes of ``spanring.cpp``,
the compiler flags, and — because ``-march=native`` makes the output a
function of the machine — the host CPU's feature flags. A tree copied
from another machine or another revision therefore rebuilds unless all
three match; file times are never consulted. Import ``lib()`` to get
the ctypes handle; the higher-level Python API lives in
``odigos_tpu.transport``.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading

_HERE = os.path.dirname(__file__)
_SRC = os.path.join(_HERE, "spanring.cpp")
_BUILD_DIR = os.path.join(_HERE, "build")
_FLAGS = ("-O3", "-std=c++17", "-shared", "-fPIC", "-march=native")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None

u64 = ctypes.c_uint64
i64 = ctypes.c_int64
u32 = ctypes.c_uint32
i32 = ctypes.c_int32
i8 = ctypes.c_int8
u8 = ctypes.c_uint8
p = ctypes.POINTER


def _host_cpu_flags() -> bytes:
    """What ``-march=native`` resolves against on this machine."""
    try:
        with open("/proc/cpuinfo", "rb") as f:
            for line in f:
                if line.startswith((b"flags", b"Features")):
                    return line
    except OSError:
        pass
    return b""


def _so_path() -> str:
    """The library path for the CURRENT source, flags and host CPU."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_FLAGS).encode())
    h.update(_host_cpu_flags())
    return os.path.join(_BUILD_DIR, f"libspanring-{h.hexdigest()[:16]}.so")


def _build(so: str) -> None:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    tmp = f"{so}.{os.getpid()}.tmp"  # per-process: concurrent cold builds
    # race only through the atomic os.replace, never through the same file
    subprocess.run(["g++", *_FLAGS, _SRC, "-o", tmp],
                   check=True, capture_output=True)
    os.replace(tmp, so)
    # libraries built for another key are dead weight (unlinking one a
    # live process still has mapped is safe)
    for stale in glob.glob(os.path.join(_BUILD_DIR, "libspanring*.so")):
        if stale != so:
            try:
                os.unlink(stale)
            except OSError:
                pass


def _signatures(lib: ctypes.CDLL) -> None:
    lib.sr_map_len.restype = u64
    lib.sr_map_len.argtypes = [u64]
    lib.sr_init.restype = ctypes.c_void_p
    lib.sr_init.argtypes = [ctypes.c_void_p, u64]
    lib.sr_attach.restype = ctypes.c_void_p
    lib.sr_attach.argtypes = [ctypes.c_void_p]
    lib.sr_close.argtypes = [ctypes.c_void_p]
    for fn in ("sr_capacity", "sr_dropped", "sr_written", "sr_backlog"):
        getattr(lib, fn).restype = u64
        getattr(lib, fn).argtypes = [ctypes.c_void_p]
    lib.sr_write_batch.restype = i64
    lib.sr_write_batch.argtypes = (
        [ctypes.c_void_p, u64] + [p(u64)] * 6 + [p(i8)] * 2 + [p(i32)] * 2
        + [p(u8), p(u32)])
    lib.sr_drain.restype = i64
    lib.sr_drain.argtypes = (
        [ctypes.c_void_p, u64] + [p(u64)] * 6 + [p(i8)] * 2 + [p(i32)] * 2
        + [p(u8), u64, p(u32), u64, p(u64)])


def lib() -> ctypes.CDLL:
    """The loaded (building if needed) native library."""
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        so = _so_path()
        if not os.path.exists(so):
            _build(so)
        _lib = ctypes.CDLL(so)
        _signatures(_lib)
        return _lib
