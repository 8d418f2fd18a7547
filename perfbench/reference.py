"""Plain GF(2^8) Reed-Solomon, the yardstick for every stored shard.

Written from ISA-L's public definitions (`gf_gen_rs_matrix`, the field
polynomial x^8+x^4+x^3+x^2+1) and nothing of `ceph_tpu.codec` or
`ceph_tpu.gf`: the same operations on the same bytes give the same
shards, so a shard on an OSD's store that differs from `encode` here is
a wrong result of the program.

This is also the default shard reference of a configuration.  A
configuration names its own with `"reference": "<module>"`, a module of
the `perfbench` package (`tests.cauchy_reference` is
`perfbench/tests/cauchy_reference.py`); the harness resolves it once, at
`load_cell`.  A reference module imports nothing of `ceph_tpu` and
takes nothing the program has made.  It exposes these functions of the
configuration dict `cfg`:

- `chunk_count(cfg)`: the shards the pool stores for one object;
- `stripe_width(cfg)`: the user bytes of one stripe;
- `object_shards(payload, cfg) -> list[bytes]`: every shard, in shard
  order, that the pool stores for `payload`;
- `encode_bytes(cfg, stripes)`, `decode_bytes(cfg, stripes, erasures)`:
  the bytes a coding kernel has to move to encode `stripes` whole
  stripes, or to rebuild `erasures` chunks of each (perfbench/work.py).
"""

from __future__ import annotations

import numpy as np

POLY = 0x11D


def _tables() -> np.ndarray:
    exp = np.zeros(512, np.int64)
    log = np.zeros(256, np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    exp[255:510] = exp[:255]
    mul = np.zeros((256, 256), np.uint8)
    a = np.arange(1, 256)
    for c in range(1, 256):
        mul[c, 1:] = exp[log[c] + log[a]]
    return mul


MUL = _tables()


def vandermonde_rows(k: int, m: int) -> np.ndarray:
    """ISA-L gf_gen_rs_matrix's m parity rows: row i is 1, g, g^2, ...
    with g = 2^i."""
    rows = np.zeros((m, k), np.uint8)
    gen = 1
    for i in range(m):
        p = 1
        for j in range(k):
            rows[i, j] = p
            p = int(MUL[p, gen])
        gen = int(MUL[gen, 2])
    return rows


def encode(data: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """(stripes, k, L) data chunks -> (stripes, m, L) parity chunks."""
    m, k = rows.shape
    out = np.zeros((data.shape[0], m, data.shape[2]), np.uint8)
    for i in range(m):
        for j in range(k):
            c = int(rows[i, j])
            if c == 1:
                out[:, i] ^= data[:, j]
            elif c:
                out[:, i] ^= MUL[c][data[:, j]]
    return out


def systematic_shards(payload: bytes, cfg: dict, rows: np.ndarray) -> list[bytes]:
    """The k+m shard objects of a systematic code with parity `rows`:
    the object zero-padded to whole stripes of k * stripe_unit bytes,
    and shard s the concatenation of chunk s of every stripe."""
    k, m, stripe_unit = cfg["k"], cfg["m"], cfg["stripe_unit"]
    width = k * stripe_unit
    stripes = -(-len(payload) // width)
    buf = np.zeros(stripes * width, np.uint8)
    buf[: len(payload)] = np.frombuffer(payload, np.uint8)
    data = buf.reshape(stripes, k, stripe_unit)
    parity = encode(data, rows)
    full = np.concatenate([data, parity], axis=1)
    return [full[:, s].tobytes() for s in range(k + m)]


def object_shards(payload: bytes, cfg: dict) -> list[bytes]:
    """The k+m shard objects an RS(k, m) pool stores for `payload`."""
    return systematic_shards(payload, cfg, vandermonde_rows(cfg["k"], cfg["m"]))


def chunk_count(cfg: dict) -> int:
    return cfg["k"] + cfg["m"]


def stripe_width(cfg: dict) -> int:
    return cfg["k"] * cfg["stripe_unit"]


def encode_bytes(cfg: dict, stripes: int) -> int:
    """A full-stripe encode reads k chunks and writes m: (k+m) chunks."""
    return stripes * (cfg["k"] + cfg["m"]) * cfg["stripe_unit"]


def decode_bytes(cfg: dict, stripes: int, erasures: int) -> int:
    """A decode reads k surviving chunks and writes the rebuilt ones."""
    return stripes * (cfg["k"] + erasures) * cfg["stripe_unit"]
