"""CLAY coupled-layer coding as one device program per stripe batch.

A CLAY(k, m, d) chunk is Q = q^t planes of sub-chunks; encode, layered
decode and single-chunk repair are sequences of GF(2^8)-linear maps over
those sub-chunks (codec/clay.py has the construction and its host
oracle).  This module runs each as one jitted program over a whole batch
of stripes (`clay_encode`; `clay_decode` for decodes and repairs).

Layout: the stripes ride in the byte axis.  A batch (S, n, Q * sc) of n
chunks per stripe becomes a tensor (nodes, Q, S * sc): row (node, plane)
holds that plane's sub-chunk of every stripe, and each stage is a map
over whole rows.  The coupling is static in the grid: node (x, y) at a
plane whose digit y is d pairs with node (d, y) at the plane whose digit
y is x, so for each grid row y the partners' values are one transpose of
that row's (x, planes) block, and the dot / larger-x / smaller-x sides
of every pair are fixed masks.  A round then is:

- uncouple: U = C on dots, P [C; C_partner] on pairs (in a repair, the
  pair solved through U_partner where the partner sent nothing);
- the inner (k+nu, m) MDS decode of the erased nodes' U, every plane at
  once, its matrix an operand: each output the XOR of the xtime powers
  of the survivors that the coefficients' bits select;
- recouple: the erased nodes' C from their U and the partner's C (or,
  both erased, through P^-1).

What depends on the erasure pattern is data: the lost nodes, each
round's planes, the partner masks, the survivors and the decode matrix.
It is computed on the host once per pattern and cached (`pattern`), and
the rounds are a loop whose trip count is data too, so one compiled
program serves every pattern of a shape: a new erasure pattern on the
read path compiles nothing.  `run_host`
runs the same program in numpy: the byte-identical oracle the offload
runtime falls back to, independent of the jax runtime.
"""

from __future__ import annotations

import functools
from collections import OrderedDict
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ceph_tpu.common.lockdep import make_lock
from ceph_tpu.gf import GF_MUL_TABLE, gf_inv, isa_decode_matrix

from .dispatch import CLAY, record_fallback, record_launch
from .packed_gf import _xtime, _xtime_host

PATTERN_CACHE_CAPACITY = 512


def _mul(a: int, b: int) -> int:
    return int(GF_MUL_TABLE[a][b])


@dataclass(frozen=True)
class ClayGeometry:
    """What fixes a CLAY code's programs: its grid and its two matrices.
    `P` is the pairwise transform's (2, 2) parity matrix, `dist` the
    inner (k+nu, m) code's distribution matrix, both as nested tuples."""

    k: int
    m: int
    nu: int
    q: int
    t: int
    P: tuple
    dist: tuple

    @property
    def planes(self) -> int:
        return self.q ** self.t

    @property
    def nodes(self) -> int:
        return self.q * self.t

    def digit(self, z, y: int):
        return (z // self.q ** (self.t - 1 - y)) % self.q

    @functools.cached_property
    def masks(self) -> tuple[np.ndarray, np.ndarray]:
        """(dot, larger-x) (nodes, planes) masks: the node on its dot at
        the plane; the node the pair member with the larger x."""
        z = np.arange(self.planes)
        dot = np.zeros((self.nodes, self.planes), bool)
        big = np.zeros_like(dot)
        for n in range(self.nodes):
            x, d = n % self.q, self.digit(z, n // self.q)
            dot[n], big[n] = d == x, x > d
        return dot, big

    @functools.cached_property
    def coefs(self) -> dict[str, int]:
        """The pairwise transform's coefficients as each stage uses them;
        A is the pair member with the larger x."""
        (p00, p01), (p10, p11) = self.P
        inv = gf_inv(_mul(p00, p11) ^ _mul(p01, p10))
        i00, i11, i01, i10 = gf_inv(p00), gf_inv(p11), gf_inv(p01), gf_inv(p10)
        return {
            # uncouple: U_A = P00 C_A ^ P01 C_B, U_B = P11 C_B ^ P10 C_A
            "p00": p00, "p01": p01, "p11": p11, "p10": p10,
            # recouple, the partner's C known: C_A = P00^-1 (U_A ^ P01 C_B)
            "a_u": i00, "a_c": _mul(i00, p01), "b_u": i11, "b_c": _mul(i11, p10),
            # recouple, both erased: [C_A; C_B] = P^-1 [U_A; U_B]
            "aa": _mul(inv, p11), "ab": _mul(inv, p01),
            "ba": _mul(inv, p10), "bb": _mul(inv, p00),
            # repair, the partner sent nothing but its U is known: the
            # partner's C from the pair, then U_self
            "ra_c": p00 ^ _mul(p01, _mul(i11, p10)), "ra_u": _mul(p01, i11),
            "rb_c": p11 ^ _mul(p10, _mul(i00, p01)), "rb_u": _mul(p10, i00),
            # repair, the lost node's C from its partner's U and C
            "la_u": i10, "la_c": _mul(i10, p11), "lb_u": i01, "lb_c": _mul(i01, p00),
        }


def padded_erasures(geo: ClayGeometry, erased) -> set[int]:
    """The grid nodes a layered decode treats as lost: `erased`, padded
    to exactly m with parity nodes in grid order (codec/clay.py
    `_decode_layered`)."""
    lost = set(erased)
    for node in range(geo.k + geo.nu, geo.nodes):
        if len(lost) >= geo.m:
            break
        lost.add(node)
    assert len(lost) == geo.m, (erased, geo.m)
    return lost


class Pattern:
    """One erasure pattern (or repair) of a code, planned on the host.

    `repair` and `n_out` pick the compiled program with the input's
    shape; `rounds` counts the non-empty layers.  `arrays` (numpy, and
    `device_arrays()`, cached on the device) are its data: the input
    nodes and the planes each holds, the nodes uncoupled and recoupled,
    each round's planes (m+1 rows, the unused ones empty) and their
    count, the partner masks, the inner decode's survivors, erased nodes
    and coefficient bits, and the output nodes."""

    def __init__(self, geo, repair, in_nodes, in_planes, uncouple, recouple,
                 layers, partner_lost, partner_aloof, erased, out_nodes):
        dmat, index = isa_decode_matrix(np.array(geo.dist, np.uint8), erased,
                                        geo.k + geo.nu)
        self.repair = repair
        self.rounds = len(layers)
        self.n_out = len(out_nodes)
        padded = np.zeros((geo.m + 1, geo.planes), bool)
        padded[: self.rounds] = layers
        bits = (dmat[:, :, None] >> np.arange(8)) & 1
        self.arrays = (
            np.asarray(in_nodes, np.int32), np.asarray(in_planes, np.int32),
            np.asarray(uncouple, bool), np.asarray(recouple, bool), padded,
            np.int32(self.rounds), np.asarray(partner_lost, bool),
            np.asarray(partner_aloof, bool), np.asarray(index, np.int32),
            np.asarray(erased, np.int32), (bits * 0xFF).astype(np.uint8),
            np.asarray(out_nodes, np.int32),
        )
        self._device = None

    def device_arrays(self):
        if self._device is None:
            self._device = tuple(jnp.asarray(a) for a in self.arrays)
        return self._device


def _partner_of(geo: ClayGeometry, nodes: set[int]) -> np.ndarray:
    """(nodes, planes): is the (node, plane)'s partner one of `nodes`."""
    mask = np.zeros((geo.nodes, geo.planes, 1), bool)
    mask[sorted(nodes)] = True
    return _partner(np, geo, mask)[:, :, 0]


def _layers(geo: ClayGeometry, on_dot: set[int], planes) -> list[np.ndarray]:
    """Planes grouped by intersection score (how many `on_dot` nodes sit
    on their dots there), rising: the rounds of a layered decode."""
    z = np.asarray(planes)
    score = sum((geo.digit(z, e // geo.q) == e % geo.q).astype(int) for e in on_dot)
    out = []
    for s in sorted(set(score.tolist())):
        layer = np.zeros(geo.planes, bool)
        layer[z[score == s]] = True
        out.append(layer)
    return out


def plan_layered(geo: ClayGeometry, erased, want) -> Pattern:
    """Layered decode of the grid nodes `erased` (an encode: the parity
    nodes), as codec/clay.py `_decode_layered` does it.  Inputs: the
    stored nodes outside the padded erasures, in grid order, every
    plane; outputs: `want`, every plane."""
    lost = padded_erasures(geo, erased)
    virtual = set(range(geo.k, geo.k + geo.nu))
    lost_mask = np.isin(np.arange(geo.nodes), sorted(lost))
    return Pattern(
        geo, False,
        in_nodes=[n for n in range(geo.nodes) if n not in lost | virtual],
        in_planes=range(geo.planes), uncouple=~lost_mask, recouple=lost_mask,
        layers=_layers(geo, lost, range(geo.planes)),
        partner_lost=_partner_of(geo, lost), partner_aloof=np.zeros(
            (geo.nodes, geo.planes), bool),
        erased=sorted(lost), out_nodes=list(want))


def plan_repair(geo: ClayGeometry, lost: int, helpers) -> Pattern:
    """Single-chunk repair of grid node `lost` from the repair planes of
    the stored nodes `helpers` (d of them, grid order), as codec/clay.py
    `_repair` does it: the helpers send the planes where `lost` sits on
    its dot.  Output: every plane of `lost`."""
    q = geo.q
    have = set(helpers) | set(range(geo.k, geo.k + geo.nu))
    aloof = {n for n in range(geo.nodes) if n not in have and n != lost}
    erased = {lost // q * q + x for x in range(q)} | aloof
    assert len(erased) == geo.m, (erased, geo.m)
    planes = [z for z in range(geo.planes) if geo.digit(z, lost // q) == lost % q]
    return Pattern(
        geo, True, in_nodes=list(helpers), in_planes=planes,
        uncouple=np.isin(np.arange(geo.nodes), sorted(have - erased)),
        recouple=np.zeros(geo.nodes, bool),
        layers=_layers(geo, {lost} | aloof, planes),
        partner_lost=np.zeros((geo.nodes, geo.planes), bool),
        partner_aloof=_partner_of(geo, aloof), erased=sorted(erased),
        out_nodes=[lost])


_PATTERNS: "OrderedDict[tuple, Pattern]" = OrderedDict()
_PATTERNS_LOCK = make_lock("clay_patterns")


def pattern(kind: str, geo: ClayGeometry, *args) -> Pattern:
    """The cached pattern for `kind`: "encode"; "decode" with (erased,
    want) grid nodes; "repair" with (lost, helpers)."""
    key = (kind, geo, *args)
    with _PATTERNS_LOCK:
        pat = _PATTERNS.get(key)
        if pat is not None:
            _PATTERNS.move_to_end(key)
            return pat
    if kind == "encode":
        parity = tuple(range(geo.k + geo.nu, geo.nodes))
        pat = plan_layered(geo, parity, parity)
    elif kind == "decode":
        pat = plan_layered(geo, *args)
    else:
        pat = plan_repair(geo, *args)
    with _PATTERNS_LOCK:
        _PATTERNS[key] = pat
        while len(_PATTERNS) > PATTERN_CACHE_CAPACITY:
            _PATTERNS.popitem(last=False)
    return pat


# -- the program ---------------------------------------------------------------


def _partner(xp, geo: ClayGeometry, X):
    """X (nodes, planes, ...) -> each (node, plane)'s partner's value: per
    grid row y, its (x, planes) block with x and plane digit y swapped."""
    q, t = geo.q, geo.t
    rest = X.shape[2:]
    rows = []
    for y in range(t):
        blk = X[y * q:(y + 1) * q].reshape(q, q ** y, q, q ** (t - 1 - y), *rest)
        rows.append(xp.swapaxes(blk, 0, 2).reshape(q, geo.planes, *rest))
    return xp.concatenate(rows)


class _Numpy:
    xp = np

    @staticmethod
    def mul(c: int, v):
        return GF_MUL_TABLE[c][v]

    @staticmethod
    def put(a, idx, v):
        a = a.copy()
        a[idx] = v
        return a

    @staticmethod
    def inner(coef_masks, x):
        powers = [x]
        for _ in range(7):
            powers.append(_xtime_host(powers[-1]))
        m, n, _ = coef_masks.shape
        out = np.zeros((x.shape[0], m, x.shape[2]), np.uint8)
        for i, j, b in zip(*np.nonzero(coef_masks)):
            out[:, i] ^= powers[b][:, j]
        return out

    @staticmethod
    def rounds(count, one_round, state):
        for r in range(int(count)):
            state = one_round(r, state)
        return state


class _Jax:
    xp = jnp

    @staticmethod
    def mul(c: int, v):
        """c * v over GF(2^8) as the XOR of xtime powers of v."""
        acc, power = None, v
        while True:
            if c & 1:
                acc = power if acc is None else acc ^ power
            c >>= 1
            if not c:
                return jnp.zeros_like(v) if acc is None else acc
            power = _xtime(power)

    @staticmethod
    def put(a, idx, v):
        return a.at[idx].set(v)

    @staticmethod
    def inner(coef_masks, x):
        """The inner code, its matrix as data: (planes, n, W) survivors'
        U -> (planes, m, W), output i the XOR over j and the bits b of
        c_ij of xtime^b(x_j); `coef_masks` (m, n, 8) is 0xff where bit b
        of c_ij is set."""
        powers = [x]
        for _ in range(7):
            powers.append(_xtime(powers[-1]))
        terms = jnp.stack(powers, axis=-2)[:, None] & coef_masks[None, :, :, :, None]
        return jax.lax.reduce(terms, jnp.uint8(0), jax.lax.bitwise_xor, (2, 3))

    @staticmethod
    def rounds(count, one_round, state):
        return jax.lax.fori_loop(0, count, one_round, state)


def _run(be, geo: ClayGeometry, data, arrays, n_out: int, repair: bool):
    """The program on backend `be` (numpy or jax): (S, n_in, in_planes *
    sc) -> (S, n_out, planes * sc)."""
    xp, mul, where = be.xp, be.mul, be.xp.where
    (in_nodes, in_planes, uncouple, recouple, layers, rounds, partner_lost,
     partner_aloof, index, erased, coef_masks, out_nodes) = arrays
    S, n_in, L = data.shape
    sc = L // in_planes.shape[0]
    W = S * sc
    c = geo.coefs
    dot, big = (mask[:, :, None] for mask in geo.masks)
    rows = data.reshape(S, n_in, -1, sc).transpose(1, 2, 0, 3).reshape(n_in, -1, W)
    C = be.put(xp.zeros((geo.nodes, geo.planes, W), xp.uint8),
               (in_nodes[:, None], in_planes[None, :]), rows)

    def one_round(r, state):
        C, U = state
        now = layers[r][None, :, None]
        # 1. uncouple
        Cp = _partner(xp, geo, C)
        pair = where(big, mul(c["p00"], C) ^ mul(c["p01"], Cp),
                     mul(c["p11"], C) ^ mul(c["p10"], Cp))
        if repair:
            Up = _partner(xp, geo, U)
            solved = where(big, mul(c["ra_c"], C) ^ mul(c["ra_u"], Up),
                           mul(c["rb_c"], C) ^ mul(c["rb_u"], Up))
            pair = where(partner_aloof[:, :, None], solved, pair)
        U = where(uncouple[:, None, None] & now, where(dot, C, pair), U)
        # 2. the inner MDS decode of the erased nodes, every plane at once
        dec = be.inner(coef_masks, U[index].transpose(1, 0, 2)).transpose(1, 0, 2)
        U = be.put(U, erased, where(now, dec, U[erased]))
        if repair:
            return C, U
        # 3. recouple
        Up, Cp = _partner(xp, geo, U), _partner(xp, geo, C)
        known = where(big, mul(c["a_u"], U) ^ mul(c["a_c"], Cp),
                      mul(c["b_u"], U) ^ mul(c["b_c"], Cp))
        both = where(big, mul(c["aa"], U) ^ mul(c["ab"], Up),
                     mul(c["ba"], Up) ^ mul(c["bb"], U))
        fresh = where(dot, U, where(partner_lost[:, :, None], both, known))
        return where(recouple[:, None, None] & now, fresh, C), U

    C, U = be.rounds(rounds, one_round, (C, xp.zeros_like(C)))
    if repair:
        # the lost node: its dots from U; each other plane from its
        # partner there, a lost-row helper whose U and C are known
        Up, Cp = _partner(xp, geo, U), _partner(xp, geo, C)
        C = where(dot, U, where(big, mul(c["la_u"], Up) ^ mul(c["la_c"], Cp),
                                mul(c["lb_u"], Up) ^ mul(c["lb_c"], Cp)))
    out = C[out_nodes]
    return out.reshape(n_out, geo.planes, S, sc).transpose(2, 0, 1, 3).reshape(
        S, n_out, geo.planes * sc)


_STATIC = ("geo", "n_out", "repair")


# Two program names, so that a device trace tells encode from decode.
@functools.partial(jax.jit, static_argnames=_STATIC)
def clay_encode(data, arrays, *, geo, n_out, repair):
    return _run(_Jax, geo, data, arrays, n_out, repair)


@functools.partial(jax.jit, static_argnames=_STATIC)
def clay_decode(data, arrays, *, geo, n_out, repair):
    return _run(_Jax, geo, data, arrays, n_out, repair)


def launch(geo: ClayGeometry, pat: Pattern, data, decode: bool):
    """Dispatch the program of `pat` over (S, n_in, chunk or fragment)
    uint8 -> a device array (S, n_out, chunk), asynchronously."""
    stripes = int(data.shape[0])
    record_launch(stripes, int(np.prod(data.shape)), decode=decode)
    CLAY.record_launch(decode, stripes, pat.rounds)
    fn = clay_decode if decode else clay_encode
    return fn(jnp.asarray(data, dtype=jnp.uint8), pat.device_arrays(), geo=geo,
              n_out=pat.n_out, repair=pat.repair)


def run_host(geo: ClayGeometry, pat: Pattern, data) -> np.ndarray:
    """The program of `pat` in numpy, byte-identical to `launch`; never
    touches jax."""
    data = np.asarray(data, dtype=np.uint8)
    CLAY.record_host(data.shape[0])
    return _run(_Numpy, geo, data, pat.arrays, pat.n_out, pat.repair)


def run_guarded(geo: ClayGeometry, pat: Pattern, data) -> np.ndarray:
    """The program of `pat` on the device and back, under the device
    guard, for a caller that needs the bytes now (single-chunk repair).
    A device failure, or a DEGRADED backend, runs it on the host: a host
    fallback, and a failure marks the backend DEGRADED."""
    from .guard import device_guard

    guard = device_guard()
    cause = None
    if guard.maybe_probe():
        try:
            return guard.call(lambda: np.asarray(launch(geo, pat, data, True)),
                              what="decode dispatch")
        except Exception as e:
            cause = e
    out = run_host(geo, pat, data)
    record_fallback(int(data.shape[0]), int(np.prod(data.shape)))
    if cause is not None:
        guard.mark_degraded(f"clay launch failed: {cause!r}")
    return out
