"""Plain CLAY(k, m, d), the yardstick for every shard of a CLAY pool.

Written from the coupled-layer construction as Ceph's ErasureCodeClay
defines it (doc/rados/operations/erasure-code-clay.rst; Vajha et al.,
"Clay Codes", FAST 2018), with plain numpy loops over planes and nodes
and nothing of `ceph_tpu`:

- q = d - k + 1; nu shortening nodes pad k + m to a multiple of q; the
  t = (k + m + nu) / q rows of q nodes form the grid.  Data chunk i is
  node i, the nu shortening nodes hold zeros and are never stored,
  parity chunk k + j is node k + nu + j.  Node n sits at x = n % q in
  row y = n // q.
- A chunk is q^t planes of equal sub-chunks, plane z at offset z * sc.
  Plane z has the base-q digits z_0 .. z_{t-1}, most significant first.
- Coupled values C (what is stored) and uncoupled values U: at plane z,
  node (x, y) is on its dot where z_y = x, and there U = C.  Elsewhere
  it pairs with node (z_y, y) at the plane z' that is z with digit y set
  to x, and [U_A; U_B] = P [C_A; C_B], A the member with the larger x,
  P the parity rows of the scalar code's (2, 2) instance (the pairwise
  transform).
- In every plane, the U values of the k + nu data and shortening nodes
  and the m parity nodes form a codeword of the scalar (k + nu, m) code,
  ISA-L's Vandermonde rows (`reference.vandermonde_rows`).

The encode solves for the parity C layer by layer, in the order of the
planes' intersection score (how many parity nodes sit on their dots):
a plane's parity U comes from the scalar code once every data node's U
is known there, and a parity node's C from its U and its partner's C or
U.  The stripe layout, padding and work counts are those of any
systematic code, taken from perfbench/reference.py.
"""

from __future__ import annotations

import numpy as np

from perfbench.reference import (  # noqa: F401  the rest of the interface
    MUL, chunk_count, decode_bytes, encode_bytes, stripe_width, vandermonde_rows)


def _inverse(a: int) -> int:
    return int(np.nonzero(MUL[a] == 1)[0][0])


class Geometry:
    def __init__(self, k: int, m: int, d: int):
        self.k, self.m, self.d = k, m, d
        self.q = d - k + 1
        self.nu = (self.q - (k + m) % self.q) % self.q
        self.t = (k + m + self.nu) // self.q
        self.planes = self.q ** self.t
        self.nodes = self.q * self.t
        self.parity = list(range(k + self.nu, self.nodes))
        self.P = vandermonde_rows(2, 2)
        self.V = vandermonde_rows(k + self.nu, m)

    def digit(self, z: int, y: int) -> int:
        return (z // self.q ** (self.t - 1 - y)) % self.q

    def partner(self, node: int, z: int) -> tuple[int, int]:
        x, y = node % self.q, node // self.q
        zy = self.digit(z, y)
        return y * self.q + zy, z + (x - zy) * self.q ** (self.t - 1 - y)


def _scale(c: int, v: np.ndarray) -> np.ndarray:
    return MUL[c][v]


def encode_parity(data: np.ndarray, g: Geometry) -> np.ndarray:
    """(stripes, k, chunk) data chunks -> (stripes, m, chunk) parity."""
    stripes, k, chunk = data.shape
    sc = chunk // g.planes
    C = np.zeros((g.nodes, g.planes, stripes, sc), np.uint8)
    for i in range(k):
        C[i] = data[:, i].reshape(stripes, g.planes, sc).transpose(1, 0, 2)
    U = np.zeros_like(C)
    P = g.P
    score = [sum(g.digit(z, e // g.q) == e % g.q for e in g.parity)
             for z in range(g.planes)]
    for s in sorted(set(score)):
        layer = [z for z in range(g.planes) if score[z] == s]
        for z in layer:
            # U of the data and shortening nodes, then the scalar code
            for node in range(k + g.nu):
                p, zp = g.partner(node, z)
                if p == node:
                    U[node, z] = C[node, z]
                elif node % g.q > p % g.q:
                    U[node, z] = _scale(P[0, 0], C[node, z]) ^ _scale(P[0, 1], C[p, zp])
                else:
                    U[node, z] = _scale(P[1, 1], C[node, z]) ^ _scale(P[1, 0], C[p, zp])
            for j, e in enumerate(g.parity):
                acc = np.zeros((stripes, sc), np.uint8)
                for i in range(k + g.nu):
                    acc ^= _scale(g.V[j, i], U[i, z])
                U[e, z] = acc
        for z in layer:
            for e in g.parity:
                p, zp = g.partner(e, z)
                x = e % g.q
                if p == e:
                    C[e, z] = U[e, z]
                elif p not in g.parity:
                    # the partner's C is known: solve its pair for C_e
                    if x > p % g.q:
                        C[e, z] = _scale(_inverse(P[0, 0]),
                                         U[e, z] ^ _scale(P[0, 1], C[p, zp]))
                    else:
                        C[e, z] = _scale(_inverse(P[1, 1]),
                                         U[e, z] ^ _scale(P[1, 0], C[p, zp]))
                else:
                    # both unknown: invert the pair's 2x2 transform
                    det = int(MUL[P[0, 0], P[1, 1]] ^ MUL[P[0, 1], P[1, 0]])
                    inv = _inverse(det)
                    if x > p % g.q:  # e is A
                        C[e, z] = _scale(MUL[inv, P[1, 1]], U[e, z]) ^ _scale(
                            MUL[inv, P[0, 1]], U[p, zp])
                    else:  # e is B
                        C[e, z] = _scale(MUL[inv, P[1, 0]], U[p, zp]) ^ _scale(
                            MUL[inv, P[0, 0]], U[e, z])
    parity = C[g.parity]  # (m, planes, stripes, sc)
    return parity.transpose(2, 0, 1, 3).reshape(stripes, g.m, chunk)


def object_shards(payload: bytes, cfg: dict) -> list[bytes]:
    """The k+m shard objects a CLAY(k, m, d) pool stores for `payload`:
    the object zero-padded to whole stripes of k * stripe_unit bytes,
    shard s the concatenation of chunk s of every stripe."""
    k, m, unit = cfg["k"], cfg["m"], cfg["stripe_unit"]
    g = Geometry(k, m, int(cfg["profile"]["d"]))
    width = k * unit
    stripes = -(-len(payload) // width)
    buf = np.zeros(stripes * width, np.uint8)
    buf[: len(payload)] = np.frombuffer(payload, np.uint8)
    data = buf.reshape(stripes, k, unit)
    full = np.concatenate([data, encode_parity(data, g)], axis=1)
    return [full[:, s].tobytes() for s in range(k + m)]
