"""ctypes loader for the native runtime library (native/).

Runs `make` on first use (it rebuilds libceph_tpu_native.so when a source
changed) if the toolchain is available; callers degrade gracefully to pure-Python
fallbacks when neither a binary nor a compiler exists.
"""

from __future__ import annotations

import ctypes
import pathlib
import subprocess
import threading

from ceph_tpu.common.lockdep import make_lock

_NATIVE_DIR = pathlib.Path(__file__).resolve().parent.parent.parent / "native"
_LIB_PATH = _NATIVE_DIR / "libceph_tpu_native.so"

_lock = make_lock("native_bindings")
_lib: ctypes.CDLL | None = None
_load_failed = False


def _build() -> bool:
    try:
        subprocess.run(
            ["make", "-s"],
            cwd=_NATIVE_DIR,
            check=True,
            capture_output=True,
            timeout=120,
        )
        return True
    except (subprocess.SubprocessError, FileNotFoundError):
        return False


def load() -> ctypes.CDLL | None:
    """The native library, building it on demand; None if unavailable."""
    global _lib, _load_failed
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        # make decides what is stale, so the library always matches the
        # committed sources
        if not _build() and not _LIB_PATH.exists():
            _load_failed = True
            return None
        try:
            lib = ctypes.CDLL(str(_LIB_PATH))
            lib.ceph_tpu_crc32c.restype = ctypes.c_uint32
            lib.ceph_tpu_crc32c.argtypes = [
                ctypes.c_uint32,
                ctypes.c_char_p,
                ctypes.c_size_t,
            ]
            lib.ceph_tpu_crc32c_hw_available.restype = ctypes.c_int
            lib.ceph_tpu_crush_hash32.restype = ctypes.c_uint32
            lib.ceph_tpu_crush_hash32.argtypes = [ctypes.c_uint32]
            lib.ceph_tpu_crush_hash32_2.restype = ctypes.c_uint32
            lib.ceph_tpu_crush_hash32_2.argtypes = [ctypes.c_uint32] * 2
            lib.ceph_tpu_crush_hash32_3.restype = ctypes.c_uint32
            lib.ceph_tpu_crush_hash32_3.argtypes = [ctypes.c_uint32] * 3
            lib.ceph_tpu_crush_set_ln_table.restype = None
            lib.ceph_tpu_crush_set_ln_table.argtypes = [
                ctypes.POINTER(ctypes.c_int32)
            ]
            lib.ceph_tpu_crush_ln_table_set.restype = ctypes.c_int
            lib.ceph_tpu_straw2_choose.restype = ctypes.c_int32
            lib.ceph_tpu_straw2_choose.argtypes = [
                ctypes.c_uint32,
                ctypes.c_uint32,
                ctypes.POINTER(ctypes.c_int32),
                ctypes.POINTER(ctypes.c_int32),
                ctypes.c_int32,
            ]
            _lib = lib
        except (OSError, AttributeError):
            # AttributeError: a stale prebuilt .so lacking newer symbols —
            # degrade to the pure-Python fallbacks like any other failure.
            _load_failed = True
        return _lib
