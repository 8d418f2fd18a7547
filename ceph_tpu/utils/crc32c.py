"""crc32c (Castagnoli) — chunk integrity digests.

The reference tracks per-shard cumulative crc32c in the `hinfo` xattr
(/root/reference/src/osd/ECUtil.h:101-160) and verifies it on every sub-read
(ECBackend.cc:1023-1156).  Hot path is the native SSE4.2 implementation
(native/crc32c.cc via ctypes); the pure-Python table fallback keeps
correctness on toolchain-less hosts.

Every call is a layer section named after its caller's layer
(`msgr.crc32c`, `store.crc32c`, ...; common/tracer.py), with the bytes
it digested, and the implementation that ran is counted once per
process as `crc32c.impl.<native_hw|native_table|python>`.
"""

from __future__ import annotations

import numpy as np

from ..common.lockdep import make_lock
from ..common.tracer import count_section, layer_call
from .native import load as _load_native

_POLY = 0x82F63B78  # reflected Castagnoli


def _build_table() -> np.ndarray:
    table = np.zeros(256, dtype=np.uint32)
    for i in range(256):
        c = i
        for _ in range(8):
            c = (c >> 1) ^ _POLY if c & 1 else c >> 1
        table[i] = c
    return table


_TABLE = _build_table()


def _crc32c_py(crc: int, data: bytes) -> int:
    c = crc ^ 0xFFFFFFFF
    for b in data:
        c = int(_TABLE[(c ^ b) & 0xFF]) ^ (c >> 8)
    return c ^ 0xFFFFFFFF


def _implementation():
    """The native library (None for the Python loop).  Loaded once: the
    loader's answer is final; the implementation is counted then."""
    global _IMPL, _LIB
    if _IMPL is None:
        with _IMPL_LOCK:
            if _IMPL is None:
                _LIB = _load_native()
                if _LIB is None:
                    impl = "python"
                elif _LIB.ceph_tpu_crc32c_hw_available():
                    impl = "native_hw"
                else:
                    impl = "native_table"
                count_section(f"crc32c.impl.{impl}")
                _IMPL = impl
    return _LIB


_IMPL: str | None = None
_LIB = None
_IMPL_LOCK = make_lock("crc32c_impl")


def _digest(lib, data, crc: int = 0) -> int:
    if isinstance(data, np.ndarray):
        data = np.ascontiguousarray(data, dtype=np.uint8).tobytes()
    if lib is not None:
        return int(lib.ceph_tpu_crc32c(crc, data, len(data)))
    return _crc32c_py(crc, data)


def _digests(lib, blocks) -> list[int]:
    return [_digest(lib, b) for b in blocks]


def crc32c(data: bytes | np.ndarray, crc: int = 0) -> int:
    """Cumulative crc32c; pass the previous digest to chain appends."""
    nbytes = data.nbytes if isinstance(data, np.ndarray) else len(data)
    return layer_call("crc32c", nbytes, _digest, _implementation(), data, crc)


def crc32c_blocks(blocks) -> list[int]:
    """crc32c of each of `blocks`, timed as one section."""
    nbytes = sum(len(b) for b in blocks)
    return layer_call("crc32c", nbytes, _digests, _implementation(), blocks)


def hw_available() -> bool:
    lib = _load_native()
    return bool(lib and lib.ceph_tpu_crc32c_hw_available())
