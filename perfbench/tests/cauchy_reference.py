"""A shard reference for tests: plain GF(2^8) RS with ISA-L's Cauchy matrix.

ISA-L `gf_gen_cauchy1_matrix`: parity row i, column j is the inverse of
(k+i) ^ j over the field x^8+x^4+x^3+x^2+1, here from the log and
antilog tables of this module.  Nothing of `ceph_tpu` is imported.  The
stripe layout, padding and work counts are those of any systematic
code, taken from perfbench/reference.py; only the parity rows differ.
"""

from __future__ import annotations

import numpy as np

from perfbench import reference
from perfbench.reference import (  # noqa: F401  the rest of the interface
    chunk_count, decode_bytes, encode_bytes, stripe_width)

EXP = [0] * 255
LOG = [0] * 256
_x = 1
for _i in range(255):
    EXP[_i], LOG[_x] = _x, _i
    _x = (_x << 1) ^ (0x11D if _x & 0x80 else 0)


def inverse(a: int) -> int:
    return EXP[(255 - LOG[a]) % 255]


def cauchy_rows(k: int, m: int) -> np.ndarray:
    return np.array([[inverse((k + i) ^ j) for j in range(k)] for i in range(m)],
                    np.uint8)


def object_shards(payload: bytes, cfg: dict) -> list[bytes]:
    return reference.systematic_shards(payload, cfg, cauchy_rows(cfg["k"], cfg["m"]))
